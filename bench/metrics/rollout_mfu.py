"""The paged engine's share of the chip's bf16 peak: model FLOPs of the
tokens generated in the traced window (2 per matmul weight plus attention
over each token's context, ``bench.flops.decode_flops``) over the traced
window's length on the trace's own clock, the chips and the peak."""


def read(ctx):
    d = ctx.data
    if not d.get("decode_flops") or not d.get("window_s"):
        return None
    peak = ctx.peaks["flops_bf16"] * ctx.chips
    return 100.0 * d["decode_flops"] / (d["window_s"] * peak)
