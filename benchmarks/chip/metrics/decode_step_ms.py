"""Mean host time of an engine decode step, all of the window's samples.

The engine's own samples (``_lat_samples``): the clock runs from the
decode call to the logits on the host, so device time is in it.
"""


def read(run):
    if not run.lat_samples:
        return None
    return 1000.0 * sum(run.lat_samples) / len(run.lat_samples)
