"""90th percentile of job completion time over every arrived job."""

from harness import pct


def read(run):
    if run.kind != "steady":
        return None
    return pct(run.jcts, 90)
