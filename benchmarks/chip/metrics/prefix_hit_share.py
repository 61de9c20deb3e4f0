"""Share of prompt tokens served from the prefix cache (%).

Prompt tokens the engines skipped (``prefill_skipped_tokens``) over
those skipped plus those prefilled, in the window; nothing to read
where no prompt token was served.
"""


def read(run):
    total = run.prefill_skipped + run.prefill_run
    if total <= 0:
        return None
    return 100.0 * run.prefill_skipped / total
