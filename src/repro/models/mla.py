"""Multi-head Latent Attention (DeepSeek-V2).

KV state is compressed into a rank-``r`` latent (plus a shared RoPE key).
The decode path uses the *absorbed* formulation: queries are projected into
latent space so attention runs directly against the cached latent — the
cache is [B, L, r + rope] instead of [B, L, H, 2*hd], which is what makes
long_500k memory-feasible for deepseek-v2-lite.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.attention import chunked_causal_attention
from repro.models.layers import apply_rope, rmsnorm, rmsnorm_spec
from repro.models.params import ParamSpec

NEG_INF = -1e30


def mla_spec(cfg: ModelConfig) -> Dict[str, Any]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": ParamSpec((d, h, qk), ("embed", "heads", "head_dim")),
        "w_dkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                           ("embed", "mla_rank")),
        "kv_norm": rmsnorm_spec(m.kv_lora_rank),
        "w_uk": ParamSpec((m.kv_lora_rank, h, m.qk_nope_head_dim),
                          ("mla_rank", "heads", "head_dim")),
        "w_uv": ParamSpec((m.kv_lora_rank, h, m.v_head_dim),
                          ("mla_rank", "heads", "head_dim")),
        "wo": ParamSpec((h, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def _latent(params, x, cfg: ModelConfig, positions):
    """x [B,S,d] -> (c_kv [B,S,r] normed, k_rope [B,S,rope] roped)."""
    m = cfg.mla
    dkv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(params["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0]
    return c_kv, k_rope


def softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale ** 2``."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling is not None:
        scale *= cfg.rope_scaling.attn_factor
    return scale


def mla_full(params, x, cfg: ModelConfig, positions, pad_mask=None,
             window=None, segment_ids=None
             ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Train/prefill MLA. Returns (out, (c_kv, k_rope)) for cache handoff.
    ``segment_ids`` (packed rows) keeps attention within a segment."""
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.num_heads
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    c_kv, k_rope = _latent(params, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                  (B, S, h, m.qk_rope_head_dim))], axis=-1)
    qc = jnp.concatenate([q_nope, q_rope], axis=-1)
    qc = constrain(qc, "batch", None, "act_heads", None)
    k = constrain(k, "batch", None, "act_heads", None)
    out = chunked_causal_attention(
        qc, k, v, q_positions=positions, kv_positions=positions,
        kv_valid=pad_mask, window=window, softmax_scale=softmax_scale(cfg),
        segment_ids=segment_ids)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, (c_kv, k_rope)


def mla_decode(params, x, cfg: ModelConfig, cache: Dict[str, Any],
               lengths: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
    """Absorbed one-token decode against the latent cache.

    cache: {"ckv": [B, L, r], "krope": [B, L, rope]}; x: [B, d].
    """
    m = cfg.mla
    B, _ = x.shape
    q = jnp.einsum("bd,dhk->bhk", x, params["wq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope[:, None], lengths[:, None], cfg.rope_theta,
                        cfg.rope_scaling)[:, 0]

    c_kv_t, k_rope_t = _latent(params, x[:, None], cfg, lengths[:, None])
    c_kv_t, k_rope_t = c_kv_t[:, 0], k_rope_t[:, 0]

    L = cache["ckv"].shape[1]
    idx = jnp.minimum(lengths, L - 1)

    def upd(c, n, i):
        return jax.lax.dynamic_update_slice(c, n[None], (i, 0))

    ckv = jax.vmap(upd)(cache["ckv"], c_kv_t.astype(cache["ckv"].dtype), idx)
    krope = jax.vmap(upd)(cache["krope"],
                          k_rope_t.astype(cache["krope"].dtype), idx)
    valid = jnp.arange(L)[None, :] < jnp.minimum(lengths + 1, L)[:, None]

    # absorb W_uk into the query: score = (q_nope W_uk) . c_kv + q_rope . k_rope
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, params["w_uk"])
    scale = softmax_scale(cfg)
    s = (jnp.einsum("bhr,blr->bhl", q_lat, ckv).astype(jnp.float32)
         + jnp.einsum("bhp,blp->bhl", q_rope, krope).astype(jnp.float32)
         ) * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhl,blr->bhr", p.astype(ckv.dtype), ckv
                       ).astype(x.dtype)
    o = jnp.einsum("bhr,rhk->bhk", o_lat, params["w_uv"])
    y = jnp.einsum("bhk,hkd->bd", o, params["wo"])
    return y, {"ckv": ckv, "krope": krope}


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   *, abstract: bool = False, dtype=None) -> Dict[str, Any]:
    m = cfg.mla
    dtype = dtype or jnp.bfloat16
    shapes = {"ckv": (batch, max_len, m.kv_lora_rank),
              "krope": (batch, max_len, m.qk_rope_head_dim)}
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}
    return {k: jnp.zeros(s, dtype) for k, s in shapes.items()}


MLA_CACHE_LOGICAL = {"ckv": ("batch", "kv_seq", "mla_rank"),
                     "krope": ("batch", "kv_seq", None)}
