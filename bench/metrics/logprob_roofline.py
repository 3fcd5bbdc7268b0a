"""The fused token-logprob kernel's share of its roofline in the traced
train steps. FLOPs and bytes come from the call's shapes
(``bench.flops.logprob_pass_cost``: rows = minibatch rows x positions, the
head's width and the vocabulary), the time from the device trace: the
forward is the ``logprob`` Pallas custom call, the backward the loop over
vocabulary blocks, found as the one loop whose state carries the
``[d, V]`` head weight and no layer-stacked weight. Each pass is costed
once for every event of it that lies whole inside the traced window, so
an event the trace lost or cut is neither timed nor costed."""

from bench import flops


def pass_of(op, d, V, L):
    """``"fwd"``, ``"bwd"`` or None for a device op."""
    name, _, _, text = op
    if "logprob" in name:
        return "fwd"
    if not name.startswith("while"):
        return None
    head = text.split(" while(", 1)[0]
    if f"[{d},{V}]" in head and f"[{L}," not in head:
        return "bwd"
    return None


def read(ctx):
    d = ctx.data
    trace = d.get("trace")
    if trace is None or not d.get("logprob_rows"):
        return None
    dims = flops.dims(ctx.cfg)
    cost = flops.logprob_pass_cost(dims["d"], dims["V"], d["logprob_rows"])
    total = {"flops": 0.0, "bytes": 0.0}
    ns = 0
    for ops in trace.devices:
        bwd = [(o[1], o[2]) for o in ops if o[1] >= d["lo"]
               and o[2] <= d["hi"]
               and pass_of(o, dims["d"], dims["V"], dims["L"]) == "bwd"]
        for o in ops:
            kind = pass_of(o, dims["d"], dims["V"], dims["L"])
            if kind is None or o[1] < d["lo"] or o[2] > d["hi"]:
                continue
            # a forward call inside the backward loop is its recompute
            if kind == "fwd" and any(a <= o[1] and o[2] <= b
                                     for a, b in bwd):
                continue
            ns += o[2] - o[1]
            for k in total:
                total[k] += cost[kind][k]
    if not ns:
        return None
    return flops.roofline_share(total, ns / 1e9, ctx.peaks)["share_pct"]
