"""The paged decode-attention kernel's share of its roofline in the
traced window. Bytes and FLOPs come from the context of every token the
harness saw generated there (``bench.flops.paged_decode_cost``: each
token reads its context's K and V once per layer); the time from the
device trace, the ``paged_decode`` Pallas custom calls."""

from bench import flops, trace_reduce as tr


def read(ctx):
    d = ctx.data
    trace = d.get("trace")
    if trace is None or not d.get("decode_contexts"):
        return None
    ns = tr.op_time(trace, lambda op: "paged_decode" in op[0],
                    d["lo"], d["hi"])
    if not ns:
        return None
    cost = flops.paged_decode_cost(ctx.cfg, d["decode_contexts"])
    return flops.roofline_share(cost, ns / 1e9, ctx.peaks)["share_pct"]
