"""Whole a3po train step's share of the chip's bf16 peak: model FLOPs of
the real (non-padding) tokens of the traced steps (``bench.flops``) over
the traced window's length on the trace's own clock, the chips and the
peak. Every batch of this traffic holds the same lengths, so here it is
``train_tokens_per_s`` rescaled; it bounds the kernels' rooflines."""


def read(ctx):
    d = ctx.data
    if not d.get("train_flops") or not d.get("window_s"):
        return None
    peak = ctx.peaks["flops_bf16"] * ctx.chips
    return 100.0 * d["train_flops"] / (d["window_s"] * peak)
