"""The expert layers' routing, gather into expert order and weighted
combine: their least HBM traffic in the traced train steps
(``bench.flops_moe.dispatch_cost``, over the real tokens of every
expert-layer call and the assignments the benchmark's own forward gives
the experts held here), forward and backward, over the device time of the
ops in the program's ``moe_route`` and ``moe_combine`` scopes. Recompute
events are left out of both time and cost."""

from bench import flops, flops_moe, phases_moe
from bench import reference_deepseek_v2 as RD


def read(ctx):
    ph = phases_moe.read(ctx)
    if ph is None:
        return None
    t = sum(ph["moe"][f"{scope}.{d}"] for scope in ("moe_route", "moe_combine")
            for d in ("fwd", "bwd"))
    if not t:
        return None
    s = RD.dims(ctx.cfg)
    cost = flops_moe.dispatch_cost(
        ctx.cfg, ctx.data["moe_tokens"] * (s["L"] - s["k"]),
        ctx.data["moe_local_rows"])
    return flops.roofline_share(cost, t, ctx.peaks)["share_pct"]
