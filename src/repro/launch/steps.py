"""Jit-able production step functions + abstract input specs.

These are the programs the dry-run lowers for every (arch x shape x mesh)
combination:

* train        — ``training.trainer._train_step``, the RL update
                 ``Trainer.step`` runs (``lower_train_step``)
* ``prefill_step`` — prompt ingestion, returns last-token logits + kv cache
* ``decode_step``  — one token for every sequence against a full cache

All steps take a single ``batch`` dict whose abstract structure comes from
``input_specs`` (ShapeDtypeStructs — no allocation) so in_shardings line up
1:1 with the spec tree.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig, RLConfig
from repro.core.algorithms import resolve_algorithm
from repro.distributed.sharding import ShardingEnv, use_sharding
from repro.models import model as M
from repro.models.layers import logits_from_hidden
from repro.training.trainer import _train_step, check_trainable


def decode_window(cfg: ModelConfig, shape: InputShape) -> Optional[int]:
    """Sliding-window policy at the long-context decode point.

    SSM/hybrid state is O(1); MLA's latent cache is compact enough to keep
    the full 500k context. Full-attention archs use the documented
    sliding-window variant (DESIGN.md §4)."""
    if shape.name != "long_500k":
        return None
    if cfg.arch_type in ("ssm", "hybrid"):
        return None
    if cfg.mla is not None:
        return None
    return cfg.long_context_window


# ----------------------------------------------------------------- factories
def make_prefill_step(cfg: ModelConfig, shape: InputShape,
                      num_microbatches: int = 1):
    """Prefill the prompt batch; ``num_microbatches`` > 1 scans over batch
    chunks (prefill chunking — §Perf lever: activation temp scales with
    the live chunk while the produced KV cache is unchanged)."""
    window = decode_window(cfg, shape)

    def one(params, batch):
        hidden, cache = M.prefill(params, cfg, batch["tokens"],
                                  embeds=batch.get("embeds"), window=window)
        logits = logits_from_hidden(params["embedding"], hidden[:, -1:],
                                    cfg)[:, 0]
        return logits, cache

    if num_microbatches <= 1:
        return one

    def prefill_step(params, batch):
        B = batch["tokens"].shape[0]
        nm = num_microbatches if B % num_microbatches == 0 else 1
        if nm == 1:
            return one(params, batch)
        mb = {k: v.reshape((nm, B // nm) + v.shape[1:])
              for k, v in batch.items()}

        def body(_, micro):
            return None, one(params, micro)

        _, (logits, caches) = jax.lax.scan(body, None, mb)
        # un-chunk: [nm, B/nm, ...] -> [B, ...]; per-layer cache leaves are
        # [nm, L, B/nm, ...] -> [L, B, ...]
        logits = logits.reshape((B,) + logits.shape[2:])
        caches = jax.tree.map(
            lambda x: (jnp.moveaxis(x, 0, 1).reshape(
                (x.shape[1], B) + x.shape[3:])
                if x.ndim >= 3 else x.reshape((B,) + x.shape[2:])),
            caches)
        return logits, caches

    return prefill_step


def make_decode_step(cfg: ModelConfig, shape: InputShape):
    window = decode_window(cfg, shape)

    def decode_step(params, batch):
        return M.decode_step(params, cfg, batch["cache"], batch["tokens"],
                             window=window)

    return decode_step


def make_step(cfg: ModelConfig, shape: InputShape, rl: RLConfig,
              algo="a3po"):
    """The step of ``shape``'s kind over the batch of ``input_specs``: for
    a train shape, ``Trainer.step``'s compiled step (``_train_step``) on
    its operands, returning new params, Adam state and packed metrics."""
    if shape.kind == "prefill":
        return make_prefill_step(cfg, shape)
    if shape.kind == "decode":
        return make_decode_step(cfg, shape)
    algo = resolve_algorithm(algo, rl)

    def train_step(params, opt, batch):
        args, statics = _train_args(cfg, rl, algo, batch)
        return _train_step(params, opt, *args, **statics)

    return train_step


def _train_args(cfg: ModelConfig, rl: RLConfig, algo, batch: Dict[str, Any],
                num_microbatches: int = 1):
    """``_train_step``'s operands after params and opt, and its statics, as
    ``Trainer.step`` passes them, from a train ``batch`` of ``input_specs``'
    names; ``behav_logp`` stands in for a ``needs_prox_forward`` prox."""
    args = (batch["version"], batch["tokens"], batch["behav_logp"],
            batch["mask"], batch["versions"], batch["rewards"],
            batch["behav_logp"] if algo.needs_prox_forward else None)
    statics = dict(cfg=cfg, rl=rl, algo=algo,
                   num_minibatches=min(rl.num_minibatches,
                                       batch["tokens"].shape[0]),
                   num_microbatches=num_microbatches, skip_nonfinite=False)
    return args, statics


def lower_train_step(cfg: ModelConfig, rl: RLConfig, algo, env: ShardingEnv,
                     batch: int, seq_len: int, num_microbatches: int = 1):
    """Lower ``training.trainer._train_step``, the jit ``Trainer.step``
    calls, for ``batch`` sequences of ``seq_len`` tokens, as the Trainer
    would call it under ``env``: ShapeDtypeStructs carry the state's
    placements (params by the logical rules, Adam moments beside them in
    f32) and the batch split over its batch axes. A ``needs_prox_forward``
    algorithm gets ``behav_logp`` as its stand-in prox (same shape and
    sharding). Returns the ``Lowered`` step; ``.compile()`` it."""
    algo = resolve_algorithm(algo, rl)
    shape = InputShape("train", seq_len, batch, "train")
    specs = input_specs(cfg, shape)
    sds = jax.ShapeDtypeStruct
    params = jax.tree.map(lambda a, sh: sds(a.shape, a.dtype, sharding=sh),
                          M.abstract_params(cfg), M.param_shardings(cfg, env))
    moment = jax.tree.map(
        lambda a: sds(a.shape, jnp.float32, sharding=a.sharding), params)
    opt = {"m": moment, "v": moment,
           "t": sds((), jnp.int32, sharding=env.sharding((), ()))}
    sh = batch_shardings(cfg, shape, env, specs)
    ops = {k: sds(v.shape, v.dtype, sharding=sh[k]) for k, v in specs.items()}
    args, statics = _train_args(cfg, rl, algo, ops, num_microbatches)
    with env.mesh, use_sharding(env):
        return _train_step.lower(params, opt, *args, **statics)


# --------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, shape: InputShape,
                rl: Optional[RLConfig] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this workload."""
    del rl
    B, S = shape.global_batch, shape.seq_len
    dtype = jnp.dtype(cfg.dtype)
    i32, f32 = jnp.int32, jnp.float32
    F = cfg.frontend_tokens if cfg.frontend else 0
    specs: Dict[str, Any] = {}
    if shape.kind == "train":
        # ``Trainer.step``'s operands: the target version and a TrainBatch
        check_trainable(cfg)
        specs["version"] = jax.ShapeDtypeStruct((), i32)
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        specs["behav_logp"] = jax.ShapeDtypeStruct((B, S - 1), f32)
        specs["mask"] = jax.ShapeDtypeStruct((B, S - 1), f32)
        specs["versions"] = jax.ShapeDtypeStruct((B,), i32)
        specs["rewards"] = jax.ShapeDtypeStruct((B,), f32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S - F), i32)
        if F:
            specs["embeds"] = jax.ShapeDtypeStruct((B, F, cfg.d_model),
                                                   dtype)
    elif shape.kind == "decode":
        specs["tokens"] = jax.ShapeDtypeStruct((B,), i32)
        window = decode_window(cfg, shape)
        specs["cache"] = M.init_cache(cfg, B, S, abstract=True,
                                      window=window)
    else:
        raise ValueError(shape.kind)
    return specs


def batch_shardings(cfg: ModelConfig, shape: InputShape, env: ShardingEnv,
                    specs: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, spec in specs.items():
        if name == "cache":
            out["cache"] = M.cache_shardings(cfg, env, spec)
        elif name == "embeds":
            out[name] = env.sharding(spec.shape, ("batch", None, "act_embed"))
        elif spec.ndim == 0:
            out[name] = env.sharding((), ())
        elif spec.ndim == 1:
            out[name] = env.sharding(spec.shape, ("batch",))
        else:
            logical = ("batch",) + (None,) * (spec.ndim - 1)
            out[name] = env.sharding(spec.shape, logical)
    return out
