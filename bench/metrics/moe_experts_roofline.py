"""The expert layers' grouped matmuls' share of their roofline in the
traced train steps. FLOPs and bytes come from the assignments the
benchmark's own forward gives the experts held here in those steps
(``bench.flops_moe.experts_cost``: 3 matrices x 2 d ff a row forward,
twice that backward; the held experts' weights once per call and the
rows in and out), the time from the device trace: the ops in the
program's ``moe_experts`` scope, forward and backward. Recompute events
are left out of both time and cost."""

from bench import flops, flops_moe, phases_moe
from bench import reference_deepseek_v2 as RD


def read(ctx):
    ph = phases_moe.read(ctx)
    if ph is None:
        return None
    t = ph["moe"]["moe_experts.fwd"] + ph["moe"]["moe_experts.bwd"]
    if not t:
        return None
    s = RD.dims(ctx.cfg)
    passes = ph["steps"] * ctx.data["minibatches"] * (s["L"] - s["k"])
    cost = flops_moe.experts_cost(ctx.cfg, ctx.data["moe_local_rows"],
                                  passes)
    return flops.roofline_share(cost, t, ctx.peaks)["share_pct"]
