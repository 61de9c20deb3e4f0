"""Mean number of rows (requests) in an engine decode step, in the window.

From the engine's own step samples, which it keys by the rows decoded.
A batch window that opens on an empty engine shows its fill here: the
rows grow while prompts are prefilled a chunk per step.
"""


def read(run):
    if not run.decode_rows:
        return None
    return sum(run.decode_rows) / len(run.decode_rows)
