"""The grouped expert kernel's share of its roofline (%).

The least time for the held experts' necessary work over the traced
window's decode and prefill records, the FLOPs of the tokens routed to
them and the bytes of their weights that those tokens touch, in
expectation, as the configuration's block module counts them
(``expert_work``), at the chip's peaks, over the device time of the ops
named ``expert_ffn``.  A block without held experts, or a trace without
that kernel, has nothing to read.
"""


def read(run):
    if run.trace is None or not hasattr(run.block, "expert_work"):
        return None
    t = sum(v for k, v in run.trace["ops"].items() if "expert_ffn" in k)
    if t <= 0:
        return None
    flops = bytes_ = 0.0
    for rec in run.steps:
        tokens = len(rec[1]) if rec[0] == "decode" else rec[2]
        f, b = run.block.expert_work(run.spec, tokens)
        flops += f
        bytes_ += b
    least = max(flops / run.peaks["bf16_flops"], bytes_ / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
