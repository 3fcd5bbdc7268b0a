"""The expert layer's phases on the device trace of a DeepSeek-V2 train
cell, beside ``bench.phases`` (whose step and work read Qwen2 shapes).

The program names the parts of its expert layer with ``jax.named_scope``
inside the ``decoder`` scope: ``moe_route`` (router, top-k, the sort and
the gather into expert order), ``moe_experts`` (the grouped matmuls),
``moe_combine`` (the weighted scatter back) and ``moe_shared`` (the shared
experts). Each op of a scope is given its direction: ``recompute`` inside
a checkpointed layer's ``rematted_computation``, ``fwd`` under
``jvp(decoder)``, else ``bwd`` (a transpose that lost the caller's scopes
is backward work). The scope map and the seconds of each phase are
``bench.phases``'s own (``scope_map``, ``phase_seconds``), read from the
compiled text of the step the traced window ran.

Every reduction returns None where it finds nothing to read, as on a
program without the scopes.
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import phases

MOE = ("moe_route", "moe_experts", "moe_combine", "moe_shared")
DIRECTIONS = ("fwd", "recompute", "bwd")
# a path ``phases.classify`` gives each direction's decoder phase
_AS = dict(zip(DIRECTIONS, ("jvp(decoder)/op",
                            "decoder/checkpoint/rematted_computation/op",
                            "transpose(jvp(decoder))/op")))


def direction(path: str) -> str:
    if "rematted_computation" in path:
        return "recompute"
    if "jvp(decoder)" in path and "transpose(jvp(decoder))" not in path:
        return "fwd"
    return "bwd"


def moe_seconds(trace, scopes: Dict[str, str], lo: float, hi: float
                ) -> Dict[str, float]:
    """Device seconds of ``<scope>.<direction>`` for each expert-layer
    scope in [lo, hi], averaged over the chips."""
    out: Dict[str, float] = {}
    for scope in MOE:
        sub = {n: _AS[direction(p)] for n, p in scopes.items()
               if phases._has(p, scope)}
        sec = phases.phase_seconds(trace, sub, lo, hi)
        for d, ph in zip(DIRECTIONS, phases.DECODER):
            out[f"{scope}.{d}"] = sec[ph]
    return out


def step_text(ctx) -> Optional[str]:
    """The compiled text of the train step the traced window ran, or None
    where the jit's cache does not hold it (the lowering would compile,
    so it is not the window's program)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from bench.drivers import common, train_moe
    from repro.configs.base import RLConfig
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.models import model as M
    from repro.training import trainer as T

    mcfg = train_moe.model_config(ctx.cfg)
    rl = RLConfig(group_size=ctx.mix["group_size"])
    B, S = ctx.cell["batch"], ctx.mix["pad_to"]
    sds = jax.ShapeDtypeStruct
    mesh = common.local_mesh(ctx.chips)
    env = ShardingEnv(mesh)
    with mesh, use_sharding(env):
        trainer = T.Trainer(mcfg, rl, "a3po")
        # the state as ``Trainer.init_state`` places it; the batches and
        # the version are uncommitted device arrays
        params = jax.tree.map(
            lambda a, s: sds(a.shape, a.dtype, sharding=s),
            M.abstract_params(mcfg), M.param_shardings(mcfg, env))
        moment = jax.tree.map(
            lambda a: sds(a.shape, jnp.float32, sharding=a.sharding), params)
        opt = {"m": moment, "v": moment,
               "t": sds((), jnp.int32,
                        sharding=NamedSharding(mesh, PartitionSpec()))}
        args = (params, opt, sds((), jnp.int32),
                sds((B, S), jnp.int32), sds((B, S - 1), jnp.float32),
                sds((B, S - 1), jnp.float32), sds((B,), jnp.int32),
                sds((B,), jnp.float32), None)
        before = dict(ctx.compile_log.total)
        compiled = T._train_step.lower(
            *args, cfg=mcfg, rl=rl, algo=trainer.algo,
            num_minibatches=min(rl.num_minibatches, B), num_microbatches=1,
            skip_nonfinite=False).compile()
    if ctx.compile_log.total != before:
        ctx.log("phases: the step lowered for the window's shapes was not "
                "in the jit's cache; no scope map")
        return None
    return compiled.as_text()


def read(ctx) -> Optional[Dict]:
    """The traced window's phases, once per run (kept in ``ctx.data``),
    with the phase lines logged: ``seconds`` (``bench.phases.PHASES``),
    ``moe`` (``moe_seconds``), ``steps`` (train steps traced). None
    without a trace, a scope map, or an expert-layer op in the window."""
    d = ctx.data
    if "moe_phases" in d:
        return d["moe_phases"]
    d["moe_phases"] = None
    if d.get("trace") is None or not d.get("traced_steps"):
        return None
    text = step_text(ctx)
    if text is None:
        return None
    scopes = phases.scope_map(text)
    sec = phases.phase_seconds(d["trace"], scopes, d["lo"], d["hi"])
    moe = moe_seconds(d["trace"], scopes, d["lo"], d["hi"])
    if not any(moe.values()):
        ctx.log("phases: no op of the traced window carries an expert "
                "scope")
        return None
    n = d["traced_steps"]
    busy = d.get("busy_s") or sum(sec.values())
    ctx.log("phases, device s per traced step: "
            + ", ".join(f"{p} {sec[p] / n:.4f}" for p in phases.PHASES)
            + f"; other {100.0 * sec['other'] / busy:.2f} % of busy")
    ctx.log("phases of the expert layers, device s per traced step: "
            + ", ".join(f"{p} {v / n:.4f}" for p, v in moe.items())
            + f"; {d['moe_local_rows'] / n:.0f} expert rows a step")
    d["moe_phases"] = {"seconds": sec, "moe": moe, "steps": n}
    return d["moe_phases"]
