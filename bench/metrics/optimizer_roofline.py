"""Adam's share of its roofline in the traced train steps: the least HBM
traffic of one update over the reference's weights
(``bench.phases.adam_bytes``) times the updates of those steps (steps x
minibatches), over the device time of the ops in the program's
``optimizer`` scope, global norm included."""

from bench import flops, phases


def read(ctx):
    from repro.configs.base import RLConfig

    ph = phases.read(ctx)
    if ph is None or not ph["seconds"]["optimizer"]:
        return None
    rl = RLConfig(group_size=ctx.mix["group_size"])
    updates = ph["steps"] * min(rl.num_minibatches, ctx.cell["batch"])
    cost = {"flops": 0.0, "bytes": updates * phases.adam_bytes(ctx.cfg)}
    return flops.roofline_share(cost, ph["seconds"]["optimizer"],
                                ctx.peaks)["share_pct"]
