"""Output tokens served in the window, over the window's seconds.

Counts the output tokens that the window's requests hold at its close,
finished or still decoding, for all requests.  Tokens that a preempted
request threw away (the engine restarts it from its prompt) are not
served and do not count.
"""


def read(run):
    if run.kind != "batch" or run.t_close <= 0:
        return None
    return run.out_tokens / run.t_close
