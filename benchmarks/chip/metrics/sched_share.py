"""Share of the window's wall time spent in ``scheduler.schedule`` (%).

The cluster's loop is serial, so every second here is a second no
engine step is issued.
"""


def read(run):
    if run.t_close <= 0:
        return None
    return 100.0 * run.sched_s / run.t_close
