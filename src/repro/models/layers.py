"""Shared primitive layers: RMSNorm, RoPE, SwiGLU FFN, embeddings."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, YarnConfig
from repro.distributed.sharding import constrain
from repro.models.params import ParamSpec


# ----------------------------------------------------------------------- norm
def rmsnorm_spec(d: int):
    return {"scale": ParamSpec((d,), ("embed",), init="ones")}


def rmsnorm(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float,
               scaling: Optional[YarnConfig] = None) -> jax.Array:
    """Rotary inverse frequencies [head_dim // 2]. With YaRN ``scaling``,
    DeepSeek-V2's blend (``DeepseekV2YarnRotaryEmbedding``): the bands
    that turn fewer than ``beta_slow`` times over the original context
    are interpolated (divided by ``factor``), those that turn more than
    ``beta_fast`` times keep their frequency, and a linear ramp joins
    them."""
    half = head_dim // 2
    extra = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is None:
        return extra

    def dim_of(rotations):  # yarn_find_correction_dim
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)  # 0 where the band is kept
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: Optional[YarnConfig] = None) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq] (int)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, scaling)  # [half]
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None and scaling.cos_sin_factor != 1.0:
        cos, sin = cos * scaling.cos_sin_factor, sin * scaling.cos_sin_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------------ ffn
def swiglu_spec(d: int, d_ff: int):
    return {
        "w_gate": ParamSpec((d, d_ff), ("embed", "ff")),
        "w_up": ParamSpec((d, d_ff), ("embed", "ff")),
        "w_down": ParamSpec((d_ff, d), ("ff", "embed")),
    }


def swiglu(params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = jax.nn.silu(g) * u
    # NOTE: leading dim must stay "batch" — P(None, ...) would FORCE batch
    # replication (None = replicated, not "unspecified") and GSPMD would
    # all-gather every activation across the data axis.
    h = constrain(h, "batch", *((None,) * (h.ndim - 2)), "act_ff")
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# ------------------------------------------------------------------ embedding
def embedding_spec(cfg: ModelConfig):
    # std d^-0.5: tied logits h @ embed.T stay O(1); the input side is
    # rescaled by sqrt(d) in embed_tokens (Gemma/Cohere convention).
    spec = {"embed": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"),
                               scale=cfg.d_model ** -0.5)}
    if not cfg.tie_embeddings:
        spec["out_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"))
    return spec


def embed_tokens(params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.tie_embeddings:
        # scale tied embeddings so logits stay O(1) (Gemma/Cohere style)
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def output_head_weight(params, cfg: ModelConfig) -> jax.Array:
    """[d_model, vocab] matrix producing logits."""
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["out_head"]


def logits_from_hidden(params, hidden: jax.Array, cfg: ModelConfig,
                       w: Optional[jax.Array] = None) -> jax.Array:
    w = output_head_weight(params, cfg) if w is None else w
    logits = jnp.einsum("...d,dv->...v", hidden, w,
                        preferred_element_type=jnp.float32)
    return constrain(logits, "batch", *((None,) * (logits.ndim - 2)),
                     "vocab")
