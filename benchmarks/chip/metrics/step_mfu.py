"""Model FLOPs of the decode and prefill programs over their device time.

FLOPs from the configuration's block module and the rows, contexts and
chunks recorded around each call in the traced window (padding rows
excluded), over the device seconds of those programs in the trace times
the bf16 peak (%).
"""


def read(run):
    if run.trace is None:
        return None
    t = run.trace["program_s"].get("decode", 0.0) + run.trace["program_s"].get("prefill", 0.0)
    if t <= 0:
        return None
    flops = 0.0
    for rec in run.steps:
        if rec[0] == "decode":
            flops += run.block.decode_step_flops(run.spec, rec[1])
        else:
            flops += run.block.prefill_chunk_flops(run.spec, rec[1], rec[2])
    return 100.0 * flops / (t * run.peaks["bf16_flops"])
