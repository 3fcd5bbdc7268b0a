"""Composable decoder LM over the assigned architecture families.

Layers are *stacked and scanned* (``jax.lax.scan``) so the HLO is O(1) in
depth — required to compile 64–88 layer models against a 512-device host
mesh in tolerable time. Hybrid (Zamba2) models scan over periods of
(attn_every-1) SSM blocks followed by one *shared* attention block.

Public entry points:
  model_spec / init_params / abstract_params / param_shardings
  forward_hidden / forward_logits                  (train + prefill)
  init_cache / cache_shardings / decode_step       (serving)
  prefill                                          (populate a decode cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardingEnv, constrain
from repro.models import blocks
from repro.models.layers import (
    embed_tokens,
    embedding_spec,
    logits_from_hidden,
    rmsnorm,
    rmsnorm_spec,
)
from repro.models import ssm as ssm_mod
from repro.models.params import (
    ParamSpec,
    SpecTree,
    abstract_from_specs,
    init_from_specs,
    shardings_from_specs,
    stack_specs,
)


# ----------------------------------------------------------------- structure
def _layout(cfg: ModelConfig):
    """(n_attn, n_ssm, n_periods, per_period_ssm, tail_ssm)."""
    kinds = cfg.block_kinds()
    n_attn = sum(1 for k in kinds if k == "attn")
    n_ssm = len(kinds) - n_attn
    if cfg.arch_type == "hybrid":
        assert cfg.share_attn_params, "hybrid wiring assumes shared attn"
        n_periods = cfg.num_layers // cfg.attn_every
        per = cfg.attn_every - 1
        tail = cfg.num_layers % cfg.attn_every
        assert n_periods * per + tail == n_ssm and n_periods == n_attn
        return n_attn, n_ssm, n_periods, per, tail
    return n_attn, n_ssm, 0, 0, 0


def model_spec(cfg: ModelConfig) -> SpecTree:
    spec: SpecTree = {
        "embedding": embedding_spec(cfg),
        "final_norm": rmsnorm_spec(cfg.d_model),
    }
    if cfg.frontend is not None:
        # learned projector bias marks the modality boundary (frontend
        # embeddings themselves are provided precomputed per assignment)
        spec["frontend_proj"] = ParamSpec(
            (cfg.d_model, cfg.d_model), ("embed", "act_embed"),
            scale=cfg.d_model ** -0.5)
    n_attn, n_ssm, n_periods, per, tail = _layout(cfg)
    if cfg.arch_type == "hybrid":
        spec["ssm_blocks"] = stack_specs(blocks.ssm_block_spec(cfg), n_ssm)
        spec["shared_attn"] = blocks.attn_block_spec(cfg)
    elif cfg.arch_type == "ssm":
        spec["blocks"] = stack_specs(blocks.ssm_block_spec(cfg),
                                     cfg.num_layers)
    else:
        k = _dense_lead(cfg)
        if k:
            spec["dense_blocks"] = stack_specs(
                blocks.attn_block_spec(cfg, dense=True), k)
        spec["blocks"] = stack_specs(blocks.attn_block_spec(cfg),
                                     cfg.num_layers - k)
    return spec


def _dense_lead(cfg: ModelConfig) -> int:
    """Leading dense layers of an MoE stack."""
    return cfg.first_dense_layers if cfg.moe is not None else 0


def _attn_stacks(params, cfg: ModelConfig):
    """(stacked params, dense) of each attention stack in layer order: the
    leading dense layers of an MoE stack, then the rest."""
    lead = [(params["dense_blocks"], True)] if _dense_lead(cfg) else []
    return lead + [(params["blocks"], False)]


@functools.lru_cache(maxsize=64)
def _cached_spec(cfg: ModelConfig) -> SpecTree:
    """Memoized ``model_spec`` for read-only consumers (ModelConfig is a
    frozen/hashable dataclass). Callers must not mutate the returned tree."""
    return model_spec(cfg)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    return init_from_specs(_cached_spec(cfg), key, dtype)


def abstract_params(cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    return abstract_from_specs(_cached_spec(cfg), dtype)


def param_shardings(cfg: ModelConfig, env: ShardingEnv):
    return shardings_from_specs(_cached_spec(cfg), env)


# ---------------------------------------------------------------- embeddings
def _embed_inputs(params, cfg: ModelConfig, tokens, embeds):
    x = embed_tokens(params["embedding"], tokens, cfg)
    if cfg.frontend is not None:
        assert embeds is not None, f"{cfg.name} needs frontend embeds"
        fe = jnp.einsum("bfd,de->bfe", embeds.astype(x.dtype),
                        params["frontend_proj"])
        x = jnp.concatenate([fe, x], axis=1)
    return constrain(x, "batch", None, "act_embed")


# ------------------------------------------------------------------ full seq
def packable(cfg: ModelConfig) -> bool:
    """Whether several sequences may share a row of ``forward_hidden``
    (``segment_ids``): an attention stack, plain or latent, with dense or
    drop-free routed FFNs, keeps them apart. Recurrent SSM state and
    frontend prefixes would mix or shift them."""
    return cfg.arch_type in ("dense", "moe") and cfg.frontend is None


def forward_hidden(params, cfg: ModelConfig, tokens: jax.Array,
                   embeds: Optional[jax.Array] = None,
                   positions: Optional[jax.Array] = None,
                   pad_mask: Optional[jax.Array] = None,
                   window: Optional[int] = None,
                   segment_ids: Optional[jax.Array] = None,
                   with_stats: bool = False):
    """tokens [B,St] (+embeds [B,F,d]) -> (hidden [B,S,d], aux loss), and
    with ``with_stats`` the expert layers' counters (``moe.STATS``; none
    for a stack without experts) third.

    ``segment_ids`` [B,S] (-1 = padding) marks rows that pack several
    sequences, with ``positions`` restarting at 0 in each; attention stays
    within a segment and padding is routed to no expert, as where
    ``pad_mask`` is false. Only for a ``packable`` config."""
    if segment_ids is not None and not packable(cfg):
        raise ValueError(f"{cfg.name}: sequences cannot share a row")
    x = _embed_inputs(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    aux0 = blocks.zero_aux(cfg)

    if cfg.arch_type == "hybrid":
        with jax.named_scope("decoder"):
            x, aux = _hybrid_full(params, cfg, x, positions, pad_mask,
                                  window)
    elif cfg.arch_type == "ssm":
        def body(carry, layer_params):
            h, aux = carry
            h, a, _ = blocks.ssm_block_full(layer_params, h, cfg, pad_mask)
            return (h, blocks.add_aux(aux, a)), None
        if cfg.remat:
            body = jax.checkpoint(body)
        with jax.named_scope("decoder"):
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
    else:
        def stack(x, aux, stacked, dense):
            def body(carry, layer_params):
                h, aux = carry
                # sequence-parallel region boundary: under the opt-in
                # ("seq_sp" -> "model") rule the residual stream (and
                # hence the remat-stored layer inputs) is seq-sharded
                # between blocks; GSPMD turns the TP all-reduces into
                # reduce-scatter/all-gather pairs around the attention/FFN
                # matmuls. Default rule is None => no-op.
                h = constrain(h, "batch", "seq_sp", "act_embed")
                h, a, _ = blocks.attn_block_full(
                    layer_params, h, cfg, positions, pad_mask, window,
                    segment_ids, dense)
                return (h, blocks.add_aux(aux, a)), None
            if cfg.remat:
                body = jax.checkpoint(body)
            return jax.lax.scan(body, (x, aux), stacked)[0]

        with jax.named_scope("decoder"):
            aux = aux0
            for stacked, dense in _attn_stacks(params, cfg):
                x, aux = stack(x, aux, stacked, dense)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if with_stats:
        return x, aux["loss"], {k: v for k, v in aux.items() if k != "loss"}
    return x, aux["loss"]


def _hybrid_full(params, cfg, x, positions, pad_mask, window):
    n_attn, n_ssm, n_periods, per, tail = _layout(cfg)
    aux0 = blocks.zero_aux(cfg)
    main = jax.tree.map(
        lambda a: a[: n_periods * per].reshape((n_periods, per) + a.shape[1:]),
        params["ssm_blocks"])
    tail_p = jax.tree.map(lambda a: a[n_periods * per:], params["ssm_blocks"])

    def ssm_body(carry, layer_params):
        h, aux = carry
        h, a, _ = blocks.ssm_block_full(layer_params, h, cfg, pad_mask)
        return (h, blocks.add_aux(aux, a)), None

    def period_body(carry, period_params):
        h, aux = carry
        (h, aux), _ = jax.lax.scan(ssm_body, (h, aux), period_params)
        h, a, _ = blocks.attn_block_full(params["shared_attn"], h, cfg,
                                         positions, pad_mask, window)
        return (h, blocks.add_aux(aux, a)), None

    if cfg.remat:
        period_body = jax.checkpoint(period_body)
    (x, aux), _ = jax.lax.scan(period_body, (x, aux0), main)
    if tail:
        (x, aux), _ = jax.lax.scan(ssm_body, (x, aux), tail_p)
    return x, aux


def forward_logits(params, cfg: ModelConfig, tokens, embeds=None,
                   positions=None, pad_mask=None, window=None):
    h, aux = forward_hidden(params, cfg, tokens, embeds, positions,
                            pad_mask, window)
    return logits_from_hidden(params["embedding"], h, cfg), aux


# -------------------------------------------------------------------- caches
def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               abstract: bool = False, window: Optional[int] = None,
               dtype=None) -> Dict[str, Any]:
    """Stacked per-layer decode caches + per-seq lengths."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_attn, n_ssm, n_periods, per, tail = _layout(cfg)
    cache: Dict[str, Any] = {}

    def stack(tree, n):
        return jax.tree.map(
            lambda leaf: (jax.ShapeDtypeStruct((n,) + leaf.shape, leaf.dtype)
                          if abstract
                          else jnp.broadcast_to(leaf, (n,) + leaf.shape).copy()
                          ), tree)

    if n_attn:
        one = blocks.attn_cache_for(cfg, batch, max_len, abstract=abstract,
                                    window=window, dtype=dtype)
        cache["attn"] = stack(one, n_attn)
    if n_ssm:
        one = ssm_mod.init_ssm_cache(cfg, batch, abstract=abstract,
                                     dtype=dtype)
        cache["ssm"] = stack(one, n_ssm)
    cache["lengths"] = (jax.ShapeDtypeStruct((batch,), jnp.int32) if abstract
                        else jnp.zeros((batch,), jnp.int32))
    return cache


def cache_logical_axes(cfg: ModelConfig, cache: Dict[str, Any]):
    out: Dict[str, Any] = {}
    if "attn" in cache:
        log = blocks.attn_cache_logical(cfg)
        out["attn"] = {k: ("layers",) + v for k, v in log.items()}
    if "ssm" in cache:
        out["ssm"] = {k: ("layers",) + v
                      for k, v in ssm_mod.SSM_CACHE_LOGICAL.items()}
    out["lengths"] = ("batch",)
    return out


def cache_shardings(cfg: ModelConfig, env: ShardingEnv,
                    cache: Dict[str, Any]):
    logical = cache_logical_axes(cfg, cache)
    return jax.tree.map(
        lambda leaf, log: env.sharding(leaf.shape, log),
        cache, logical,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, jax.Array)))


# -------------------------------------------------------------------- decode
def decode_step(params, cfg: ModelConfig, cache: Dict[str, Any],
                tokens: jax.Array, window: Optional[int] = None,
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One token for every sequence. tokens [B] -> (logits [B,V], cache)."""
    lengths = cache["lengths"]
    x = embed_tokens(params["embedding"], tokens[:, None], cfg)[:, 0]
    x = constrain(x, "batch", "act_embed")
    new_cache = dict(cache)

    if cfg.arch_type == "hybrid":
        x = _hybrid_decode(params, cfg, x, new_cache, lengths, window)
    elif cfg.arch_type == "ssm":
        def body(carry, xs):
            h = carry
            layer_params, layer_cache = xs
            h, _, layer_cache = blocks.ssm_block_decode(layer_params, h, cfg,
                                                        layer_cache)
            return h, layer_cache
        x, ssm_cache = jax.lax.scan(body, x,
                                    (params["blocks"], cache["ssm"]))
        new_cache["ssm"] = ssm_cache
    else:
        done, caches = 0, []
        for stacked, dense in _attn_stacks(params, cfg):
            def body(carry, xs, dense=dense):
                h = carry
                layer_params, layer_cache = xs
                h, _, layer_cache = blocks.attn_block_decode(
                    layer_params, h, cfg, layer_cache, lengths, window,
                    dense)
                return h, layer_cache
            n = jax.tree.leaves(stacked)[0].shape[0]
            x, c = jax.lax.scan(body, x, (stacked, jax.tree.map(
                lambda a: a[done:done + n], cache["attn"])))
            done += n
            caches.append(c)
        new_cache["attn"] = _concat_layers(caches)

    x = rmsnorm(params["final_norm"], x[:, None], cfg.norm_eps)[:, 0]
    logits = logits_from_hidden(params["embedding"], x, cfg)
    new_cache["lengths"] = lengths + 1
    return logits, new_cache


def _concat_layers(trees):
    """Stacked per-layer trees joined along the layer axis."""
    if len(trees) == 1:
        return trees[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *trees)


def _hybrid_decode(params, cfg, x, cache, lengths, window):
    n_attn, n_ssm, n_periods, per, tail = _layout(cfg)
    main_ssm_p = jax.tree.map(
        lambda a: a[: n_periods * per].reshape((n_periods, per) + a.shape[1:]),
        params["ssm_blocks"])
    tail_ssm_p = jax.tree.map(lambda a: a[n_periods * per:],
                              params["ssm_blocks"])
    main_ssm_c = jax.tree.map(
        lambda a: a[: n_periods * per].reshape((n_periods, per) + a.shape[1:]),
        cache["ssm"])
    tail_ssm_c = jax.tree.map(lambda a: a[n_periods * per:], cache["ssm"])

    def ssm_body(carry, xs):
        h = carry
        p, c = xs
        h, _, c = blocks.ssm_block_decode(p, h, cfg, c)
        return h, c

    def period_body(carry, xs):
        h = carry
        p_ssm, c_ssm, c_attn = xs
        h, c_ssm = jax.lax.scan(ssm_body, h, (p_ssm, c_ssm))
        h, _, c_attn = blocks.attn_block_decode(params["shared_attn"], h,
                                                cfg, c_attn, lengths, window)
        return h, (c_ssm, c_attn)

    x, (main_c, attn_c) = jax.lax.scan(
        period_body, x, (main_ssm_p, main_ssm_c, cache["attn"]))
    if tail:
        x, tail_c = jax.lax.scan(ssm_body, x, (tail_ssm_p, tail_ssm_c))
    else:
        tail_c = tail_ssm_c
    cache["ssm"] = jax.tree.map(
        lambda m, t: jnp.concatenate(
            [m.reshape((n_periods * per,) + m.shape[2:]), t], axis=0),
        main_c, tail_c)
    cache["attn"] = attn_c
    return x


# ------------------------------------------------------------------- prefill
def prefill(params, cfg: ModelConfig, tokens: jax.Array,
            embeds: Optional[jax.Array] = None,
            lengths: Optional[jax.Array] = None,
            max_len: Optional[int] = None,
            window: Optional[int] = None,
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the prompt, returning (hidden [B,S,d], populated decode cache).

    ``lengths`` are true per-seq prompt lengths (right padding); defaults to
    the full width.
    """
    x = _embed_inputs(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    max_len = max_len or S
    if window is None and max_len < S:
        raise ValueError(
            f"decode cache max_len={max_len} < prompt length {S} "
            "(includes frontend tokens); only windowed caches may wrap")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if lengths is None:
        lengths = jnp.full((B,), tokens.shape[1], jnp.int32)
    if cfg.frontend is not None:
        lengths = lengths + cfg.frontend_tokens  # frontend prefix is valid
    pad_mask = jnp.arange(S)[None, :] < lengths[:, None]
    dtype = jnp.dtype(cfg.dtype)
    n_attn, n_ssm, n_periods, per, tail = _layout(cfg)
    L = min(max_len, window) if window else max_len

    def write_kv(kv):
        """kv: dict of [B,S,...] -> cache arrays [B,L,...]."""
        out = {}
        for name, arr in kv.items():
            buf_shape = (B, L) + arr.shape[2:]
            buf = jnp.zeros(buf_shape, dtype)
            if S <= L:
                buf = jax.lax.dynamic_update_slice(
                    buf, arr.astype(dtype), (0,) * arr.ndim)
            else:
                slots = jnp.arange(S - L, S) % L
                buf = buf.at[:, slots].set(arr[:, S - L:].astype(dtype))
            out[name] = buf
        return out

    aux0 = blocks.zero_aux(cfg)
    cache: Dict[str, Any] = {}
    if cfg.arch_type == "hybrid":
        x, attn_c, ssm_c = _hybrid_prefill(params, cfg, x, positions,
                                           pad_mask, window, write_kv)
        cache["attn"], cache["ssm"] = attn_c, ssm_c
    elif cfg.arch_type == "ssm":
        def body(carry, layer_params):
            h, aux = carry
            h, a, c = blocks.ssm_block_full(layer_params, h, cfg, pad_mask)
            return (h, blocks.add_aux(aux, a)), c
        (x, _), ssm_c = jax.lax.scan(body, (x, aux0), params["blocks"])
        cache["ssm"] = ssm_c
    else:
        caches = []
        for stacked, dense in _attn_stacks(params, cfg):
            def body(carry, layer_params, dense=dense):
                h, aux = carry
                h, a, kv = blocks.attn_block_full(
                    layer_params, h, cfg, positions, pad_mask, window,
                    dense=dense)
                if cfg.mla is not None:
                    kv = write_kv({"ckv": kv[0], "krope": kv[1]})
                else:
                    kv = write_kv({"k": kv[0], "v": kv[1]})
                return (h, blocks.add_aux(aux, a)), kv
            (x, _), c = jax.lax.scan(body, (x, aux0), stacked)
            caches.append(c)
        cache["attn"] = _concat_layers(caches)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    cache["lengths"] = lengths
    return x, cache


def _hybrid_prefill(params, cfg, x, positions, pad_mask, window, write_kv):
    n_attn, n_ssm, n_periods, per, tail = _layout(cfg)
    aux0 = blocks.zero_aux(cfg)
    main = jax.tree.map(
        lambda a: a[: n_periods * per].reshape((n_periods, per) + a.shape[1:]),
        params["ssm_blocks"])
    tail_p = jax.tree.map(lambda a: a[n_periods * per:],
                          params["ssm_blocks"])

    def ssm_body(carry, layer_params):
        h, aux = carry
        h, a, c = blocks.ssm_block_full(layer_params, h, cfg, pad_mask)
        return (h, blocks.add_aux(aux, a)), c

    def period_body(carry, period_params):
        h, aux = carry
        (h, aux), ssm_c = jax.lax.scan(ssm_body, (h, aux), period_params)
        h, a, kv = blocks.attn_block_full(params["shared_attn"], h, cfg,
                                          positions, pad_mask, window)
        return (h, blocks.add_aux(aux, a)), (ssm_c,
                                             write_kv({"k": kv[0],
                                                       "v": kv[1]}))

    (x, aux), (main_ssm_c, attn_c) = jax.lax.scan(period_body, (x, aux0),
                                                  main)
    main_ssm_c = jax.tree.map(
        lambda a: a.reshape((n_periods * per,) + a.shape[2:]), main_ssm_c)
    if tail:
        (x, aux), tail_c = jax.lax.scan(ssm_body, (x, aux), tail_p)
        ssm_c = jax.tree.map(lambda m, t: jnp.concatenate([m, t], axis=0),
                             main_ssm_c, tail_c)
    else:
        ssm_c = main_ssm_c
    return x, attn_c, ssm_c
