"""The decoder stack's share of the chip's bf16 peak in the traced train
steps: model FLOPs of the layers for the real tokens of those steps
(``bench.phases.decoder_flops``: the whole step's less the output head's;
recompute not counted) over the device time of the ops in the program's
``decoder`` scope (forward, recompute and backward), the chips and the
peak."""

from bench import phases


def read(ctx):
    ph = phases.read(ctx)
    if ph is None:
        return None
    t = sum(ph["seconds"][p] for p in phases.DECODER)
    if not t:
        return None
    work = ph["steps"] * phases.decoder_flops(ctx.cfg, ph["lengths"])
    return 100.0 * work / (t * ctx.chips * ctx.peaks["flops_bf16"])
