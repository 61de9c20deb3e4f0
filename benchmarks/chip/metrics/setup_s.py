"""Set-up seconds: process start to the window's start (host clock).

Imports, device start, weights made from the seed, replicas built,
every step program compiled or loaded from the persistent cache and run
once, the scheduler's profiles fitted.
"""


def read(run):
    return run.setup_s
