"""The phases of the a3po train step on the device trace, and the work of
the two phases that have a metric of their own.

The program names the phases of its train step with ``jax.named_scope``:
``decoder`` around the layer scan, ``logprob_head`` around the fused
token-logprob call and ``logprob_head_bwd`` inside its custom-VJP backward,
``policy_loss`` around the algorithm's loss and ``optimizer`` around Adam.
JAX writes the scope path into each HLO instruction's
``metadata={op_name=...}`` and marks the direction itself: ``jvp(decoder)``
forward, ``transpose(jvp(decoder))`` backward, and
``.../checkpoint/rematted_computation`` the recompute of a checkpointed
layer. These names are the contract with the program.

The device trace names an op by its HLO instruction (``fusion.589``), so
the map from name to scope path is read from the compiled text of the step
the traced window ran: the jitted step lowered for the window's argument
shapes and shardings, which the jit's own cache returns without a compile.
A lowering that compiles is not the window's program, and gives no map.

Every reduction returns None where it finds nothing to read, as on a
program without the scopes.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Sequence

from bench import flops, trace_reduce as tr

PHASES = ("decoder.fwd", "decoder.recompute", "decoder.bwd",
          "logprob_head.fwd", "logprob_head.bwd", "policy_loss",
          "optimizer", "other")
DECODER = PHASES[:3]

# "%fused_computation.3 (param_0: f32[4]) -> f32[4] {" opens a computation
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
# "  %fusion.12 = bf16[...] fusion(...), ..., metadata={op_name="a/b" ...}"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLEES = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")


def scope_map(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` scope path. An instruction
    the compiler made without one (an async copy, a slice, a fusion that
    lost its metadata) takes the path of the instruction that runs its
    computation, such as the loop whose body holds it."""
    own: Dict[str, Optional[str]] = {}
    home: Dict[str, str] = {}           # instruction -> its computation
    caller: Dict[str, str] = {}         # computation -> calling instruction
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        home[name] = comp
        for callee in _CALLEES.findall(line):
            caller.setdefault(callee, name)

    def path(name: str) -> Optional[str]:
        if own[name]:
            return own[name]
        up = caller.get(home[name])
        return path(up) if up else None

    return {n: p for n in own if (p := path(n))}


def _has(path: str, scope: str) -> bool:
    return re.search(rf"(?<![\w]){re.escape(scope)}(?![\w])", path) \
        is not None


def classify(path: Optional[str]) -> str:
    """The phase of an op from its scope path (``other`` without one)."""
    if not path:
        return "other"
    if _has(path, "logprob_head_bwd") \
            or "transpose(jvp(logprob_head))" in path:
        return "logprob_head.bwd"
    if _has(path, "logprob_head"):
        return "logprob_head.fwd"
    if _has(path, "decoder"):
        if "rematted_computation" in path:
            return "decoder.recompute"
        if "transpose(jvp(decoder))" in path:
            return "decoder.bwd"
        return "decoder.fwd"
    if _has(path, "policy_loss"):
        return "policy_loss"
    if _has(path, "optimizer"):
        return "optimizer"
    return "other"


def phase_seconds(trace: "tr.Trace", scopes: Dict[str, str], lo: float,
                  hi: float) -> Dict[str, float]:
    """Device seconds of each phase in [lo, hi] (the union of its ops'
    intervals), averaged over the chips. Loops and calls are left out, as
    ``top_ops`` leaves them out: their inner ops carry the time."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.devices:
        by: Dict[str, List] = collections.defaultdict(list)
        for name, a, b, _ in ops:
            if not name.startswith(tr.CONTAINERS):
                by[classify(scopes.get(name))].append((a, b))
        for phase, iv in by.items():
            tot[phase] += tr.length(tr.clip(tr.merge(iv), lo, hi)) / 1e9
    n = max(len(trace.devices), 1)
    return {p: tot.get(p, 0.0) / n for p in PHASES}


# ---------------------------------------------------------------- the work
def decoder_flops(cfg: Dict, lengths: Sequence[int]) -> float:
    """Model FLOPs of the decoder stack in one forward and backward pass
    over sequences of the given real lengths: ``flops.train_flops`` less
    the output head's ``6 d V`` a token (recompute not counted)."""
    return (flops.train_flops(cfg, lengths)
            - 6.0 * flops.head_params(cfg) * sum(int(S) for S in lengths))


def adam_bytes(cfg: Dict) -> float:
    """Least HBM traffic of one Adam update over the reference's weights:
    per element the gradient read twice (the global norm, then the update)
    and the parameter read and written, at the configuration's dtype, and
    the two float32 moments read and written. Its few FLOPs an element
    leave it bound by bandwidth; they are not counted."""
    import numpy as np

    from bench import reference

    p = np.dtype(cfg["torch_dtype"]).itemsize
    n = sum(int(np.prod(shape))
            for shape, _, _ in reference.leaf_specs(cfg).values())
    return n * (2 * p + 2 * p + 2 * 4 + 2 * 4)


# ------------------------------------------------------------- traced run
def step_text(ctx) -> Optional[str]:
    """The compiled text of the train step the traced window ran, or None
    where the jit's cache does not hold it (the lowering below would
    compile, so it is not the window's program)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from bench.drivers import common
    from repro.configs.base import RLConfig
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.models import model as M
    from repro.training import trainer as T

    mcfg = common.model_config(ctx.cfg)
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
    with the phase line logged: ``seconds`` (device seconds of each phase),
    ``steps`` (train steps in the window: the driver's FLOPs of their real
    tokens over one batch's, since every batch of the traffic holds the
    same lengths) and ``lengths`` (one batch's real lengths). None without
    a trace, a scope map, or a scoped op in the window."""
    from bench import traffic

    d = ctx.data
    if "phases" in d:
        return d["phases"]
    d["phases"] = None
    if d.get("trace") is None or not d.get("train_flops"):
        return None
    text = step_text(ctx)
    if text is None:
        return None
    sec = phase_seconds(d["trace"], scope_map(text), d["lo"], d["hi"])
    if not any(sec[p] for p in PHASES[:-1]):
        ctx.log("phases: no op of the traced window carries a phase scope")
        return None
    batch = traffic.rl_batches(ctx.mix, ctx.seed, 1, ctx.cell["batch"],
                               ctx.cfg["vocab_size"])[0]
    lengths = [int(S) for S in batch["lengths"]]
    n = d["train_flops"] / flops.train_flops(ctx.cfg, lengths)
    busy = d.get("busy_s") or sum(sec.values())
    ctx.log("phases, device s per traced step: "
            + ", ".join(f"{p} {sec[p] / n:.4f}" for p in PHASES)
            + f"; other {100.0 * sec['other'] / busy:.2f} % of busy")
    d["phases"] = {"seconds": sec, "steps": n, "lengths": lengths}
    return d["phases"]
