"""95th percentile of time to first token over every LLM request (ms).

From the moment the request's stage became ready (its job's arrival for
a root stage, else the finish of its last parent stage) to its first
output token on the host, on the harness's one clock.  A request with
no token by the window's close counts with the time it had waited.
"""

from harness import pct


def read(run):
    if run.kind != "steady" or not run.ttft_s:
        return None
    return 1000.0 * pct(run.ttft_s, 95)
