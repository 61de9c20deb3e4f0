"""The paged decode kernel's share of its roofline (%).

The least time for the kernel's necessary work, the attention FLOPs and
the K/V bytes of live tokens only (whatever the kernel reads besides),
as the configuration's block module counts them, at the chip's peaks,
over the kernel's device time in the trace.  On decode the bytes set
the bound.
"""


def read(run):
    if run.trace is None:
        return None
    t = run.trace["kernel_s"].get("paged_decode", 0.0)
    if t <= 0:
        return None
    flops = bytes_ = 0.0
    for rec in run.steps:
        if rec[0] == "decode":
            f, b = run.block.attention_work(run.spec, rec[1])
            flops += f
            bytes_ += b
    least = max(flops / run.peaks["bf16_flops"], bytes_ / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
