"""Mean job completion time over every job that arrived in the window.

A job still open at the drain bound counts with the time it had waited
by then, so a failing job never makes the mean look better.
"""


def read(run):
    if run.kind != "steady" or not run.jcts:
        return None
    return sum(run.jcts) / len(run.jcts)
