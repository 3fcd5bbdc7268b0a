"""GQA/MHA attention: chunked-causal full-sequence path + cached decode.

Full-sequence attention is computed in query chunks (flash-style row
blocking in pure jnp) so the [S, S] score matrix never materializes — this
is what makes the 32k prefill dry-run memory-sane without the Pallas kernel
(which is the TPU fast path, validated separately in interpret mode).

Decode supports both a full KV cache and a fixed-size sliding-window ring
buffer (the documented sub-quadratic variant used at long_500k for
full-attention architectures).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope
from repro.models.params import ParamSpec

NEG_INF = -1e30


def attention_spec(cfg: ModelConfig) -> Dict[str, Any]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    spec = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        spec["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return spec


def _project_qkv(params, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "act_heads", None)
    k = constrain(k, "batch", None, "act_heads", None)
    return q, k, v


def _pick_chunk(seq: int, target: int = 512) -> int:
    if seq <= target:
        return seq
    c = target
    while seq % c != 0:
        c //= 2
        if c == 1:
            return seq
    return c


def chunked_causal_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, Skv, KV, hd]
    v: jax.Array,  # [B, Skv, KV, hd]
    *,
    q_positions: jax.Array,  # [B, S]
    kv_positions: jax.Array,  # [B, Skv]
    kv_valid: Optional[jax.Array] = None,  # [B, Skv] bool
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,  # [B, S] int, -1 = padding
) -> jax.Array:
    """Causal attention of ``q`` over ``k``/``v``, one query chunk at a
    time. ``segment_ids`` (self-attention only, ``S == Skv``) packs several
    sequences into one row: a query sees only keys of its own segment, and
    no query sees a padding key (-1)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    dv = v.shape[-1]  # may differ from hd (MLA)
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    qc = _pick_chunk(S)
    n_chunks = S // qc
    qg = q.reshape(B, n_chunks, qc, KV, G, hd)
    qpos = q_positions.reshape(B, n_chunks, qc)
    xs = (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(qpos, 1, 0))
    if segment_ids is not None:
        xs += (jnp.moveaxis(segment_ids.reshape(B, n_chunks, qc), 1, 0),)

    def one_chunk(args):
        q_i, qpos_i = args[:2]  # [B, qc, KV, G, hd], [B, qc]
        # dtype note: dots stay in the input dtype (TPU MXU accumulates in
        # f32 natively); the explicit upcast happens at the softmax. Using
        # preferred_element_type=f32 here would make every cross-shard
        # partial-sum collective f32 (2x bytes).
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_i, k
                       ).astype(jnp.float32) * scale
        causal = qpos_i[:, :, None] >= kv_positions[:, None, :]  # [B, qc, Skv]
        mask = causal
        if window is not None:
            mask &= (qpos_i[:, :, None] - kv_positions[:, None, :]) < window
        if kv_valid is not None:
            mask &= kv_valid[:, None, :]
        if segment_ids is not None:
            seg_i = args[2]  # [B, qc]
            mask &= ((seg_i[:, :, None] == segment_ids[:, None, :])
                     & (segment_ids >= 0)[:, None, :])
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
        return o.astype(q_i.dtype)

    out = jax.lax.map(one_chunk, xs)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, dv)
    return out


def decode_attention(
    q: jax.Array,  # [B, H, hd] (single new token, rope already applied)
    k_cache: jax.Array,  # [B, L, KV, hd]
    v_cache: jax.Array,  # [B, L, KV, hd]
    kv_valid: jax.Array,  # [B, L] bool
    *,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    B, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,blkd->bkgl", qg, k_cache
                   ).astype(jnp.float32) * scale
    s = jnp.where(kv_valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,blkd->bkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, H, hd).astype(q.dtype)


# --------------------------------------------------------------------- cache
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  *, abstract: bool = False, dtype=None) -> Dict[str, Any]:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = dtype or jnp.bfloat16
    shape = (batch, max_len, kv, hd)
    if abstract:
        mk = lambda: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    else:
        mk = lambda: jnp.zeros(shape, dtype)  # noqa: E731
    return {"k": mk(), "v": mk()}


KV_CACHE_LOGICAL = {"k": ("batch", "kv_seq", "kv_heads", "head_dim"),
                    "v": ("batch", "kv_seq", "kv_heads", "head_dim")}


def _write_cache(cache_arr: jax.Array, new: jax.Array,
                 idx: jax.Array) -> jax.Array:
    """cache [B, L, KV, hd] <- new [B, KV, hd] at per-batch index idx [B]."""

    def upd(c, n, i):
        return jax.lax.dynamic_update_slice(c, n[None], (i, 0, 0))

    return jax.vmap(upd)(cache_arr, new, idx)


def attention_full(params, x, cfg: ModelConfig, positions,
                   pad_mask=None, window=None, segment_ids=None):
    """Train/prefill attention over the whole sequence (or over rows of
    packed sequences, given ``segment_ids``).

    Returns (out [B,S,d], kv) so prefill can also populate a cache.
    """
    q, k, v = _project_qkv(params, x, cfg, positions)
    out = chunked_causal_attention(
        q, k, v, q_positions=positions, kv_positions=positions,
        kv_valid=pad_mask, window=window, segment_ids=segment_ids)
    out = constrain(out, "batch", None, "act_heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, (k, v)


def attention_decode(params, x, cfg: ModelConfig, cache: Dict[str, Any],
                     lengths: jax.Array, *, window: Optional[int] = None
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token decode. x: [B, d]; lengths: [B] tokens already in cache."""
    B = x.shape[0]
    q = jnp.einsum("bd,dhk->bhk", x, params["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, params["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    # rope at absolute position = lengths
    q = apply_rope(q[:, None], lengths[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], lengths[:, None], cfg.rope_theta)[:, 0]

    L = cache["k"].shape[1]
    if window is not None and L == window:
        write_idx = lengths % window
        n_valid = jnp.minimum(lengths + 1, window)
    else:
        write_idx = jnp.minimum(lengths, L - 1)
        n_valid = jnp.minimum(lengths + 1, L)
    k_cache = _write_cache(cache["k"], k.astype(cache["k"].dtype), write_idx)
    v_cache = _write_cache(cache["v"], v.astype(cache["v"].dtype), write_idx)
    kv_valid = jnp.arange(L)[None, :] < n_valid[:, None]

    o = decode_attention(q, k_cache, v_cache, kv_valid)
    y = jnp.einsum("bhk,hkd->bd", o, params["wo"])
    return y, {"k": k_cache, "v": v_cache}


def prefill_into_cache(cache: Dict[str, Any], k: jax.Array, v: jax.Array,
                       ) -> Dict[str, Any]:
    """Copy prefill keys/values into the head of a (longer) decode cache."""
    S = k.shape[1]
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
    del S
    return {"k": k_cache, "v": v_cache}
