"""Transformer / SSM block wiring (pre-norm residual, parallel, hybrid)."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import rmsnorm, rmsnorm_spec, swiglu, swiglu_spec


# ------------------------------------------------------------------ specs
def attn_block_spec(cfg: ModelConfig, dense: bool = False) -> Dict[str, Any]:
    """An attention block; ``dense``: a SwiGLU of width ``d_ff`` in place
    of the experts (the leading dense layers of an MoE stack)."""
    d = cfg.d_model
    spec: Dict[str, Any] = {"ln1": rmsnorm_spec(d)}
    spec["attn"] = (mla_mod.mla_spec(cfg) if cfg.mla is not None
                    else attn_mod.attention_spec(cfg))
    if not cfg.parallel_block:
        spec["ln2"] = rmsnorm_spec(d)
    spec["ffn"] = (moe_mod.moe_spec(cfg) if cfg.moe is not None and not dense
                   else swiglu_spec(d, cfg.d_ff))
    return spec


def ssm_block_spec(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln": rmsnorm_spec(cfg.d_model), "ssm": ssm_mod.ssm_spec(cfg)}


# ------------------------------------------------------------------ ffn glue
# A block's ``aux`` is a dict: the auxiliary loss under "loss" and, in an
# MoE stack, the expert layer's counters (``moe.STATS``), which a stack
# adds up over its layers (the most rows any expert got: the largest).
def zero_aux(cfg: ModelConfig) -> Dict[str, jax.Array]:
    aux = {"loss": jnp.zeros((), jnp.float32)}
    if cfg.moe is not None:
        aux.update({k: jnp.zeros((), jnp.int32) for k in moe_mod.STATS})
    return aux


def add_aux(acc: Dict[str, jax.Array], a: Dict[str, jax.Array]
            ) -> Dict[str, jax.Array]:
    out = dict(acc)
    for k, v in a.items():
        out[k] = (jnp.maximum(acc[k], v) if k == "moe_expert_rows_max"
                  else acc[k] + v)
    return out


def _ffn(params, x, cfg: ModelConfig, dense: bool = False, valid=None
         ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    if cfg.moe is not None and not dense:
        y, loss, stats = moe_mod.moe_apply(params, x, cfg, valid)
        return y, dict(stats, loss=loss)
    return swiglu(params, x), {"loss": jnp.zeros((), jnp.float32)}


# ------------------------------------------------------------------ full seq
def attn_block_full(params, x, cfg: ModelConfig, positions, pad_mask=None,
                    window=None, segment_ids=None, dense: bool = False):
    """Returns (x, aux, kv) with kv the cacheables for prefill.
    ``segment_ids`` (packed rows, -1 = padding) keeps attention within a
    segment and routes padding to no expert, as ``pad_mask`` does."""
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a_out, kv = mla_mod.mla_full(params["attn"], h, cfg, positions,
                                     pad_mask, window, segment_ids)
    else:
        a_out, kv = attn_mod.attention_full(params["attn"], h, cfg, positions,
                                            pad_mask, window, segment_ids)
    valid = None
    if cfg.moe is not None and not dense:
        valid = segment_ids >= 0 if segment_ids is not None else pad_mask
    if cfg.parallel_block:
        f_out, aux = _ffn(params["ffn"], h, cfg, dense, valid)
        return x + a_out + f_out, aux, kv
    x = x + a_out
    h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
    f_out, aux = _ffn(params["ffn"], h2, cfg, dense, valid)
    return x + f_out, aux, kv


def ssm_block_full(params, x, cfg: ModelConfig, pad_mask=None,
                   initial_cache=None, valid_lens=None):
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    y, cache = ssm_mod.ssm_full(params["ssm"], h, cfg, initial_cache,
                                pad_mask=pad_mask, valid_lens=valid_lens)
    return x + y, {"loss": jnp.zeros((), jnp.float32)}, cache


# ------------------------------------------------------------------- decode
def attn_block_decode(params, x, cfg: ModelConfig, cache: Dict[str, Any],
                      lengths, window=None, dense: bool = False):
    """x: [B, d]; cache: this layer's attention cache slice."""
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a_out, cache = mla_mod.mla_decode(params["attn"], h, cfg, cache,
                                          lengths)
    else:
        a_out, cache = attn_mod.attention_decode(params["attn"], h, cfg,
                                                 cache, lengths,
                                                 window=window)
    if cfg.parallel_block:
        f_out, aux = _ffn(params["ffn"], h[:, None], cfg, dense)
        return x + a_out + f_out[:, 0], aux, cache
    x = x + a_out
    h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
    f_out, aux = _ffn(params["ffn"], h2[:, None], cfg, dense)
    return x + f_out[:, 0], aux, cache


def ssm_block_decode(params, x, cfg: ModelConfig, cache: Dict[str, Any]):
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    y, cache = ssm_mod.ssm_decode(params["ssm"], h, cfg, cache)
    return x + y, jnp.zeros((), jnp.float32), cache


# ------------------------------------------------------------- cache builders
def attn_cache_for(cfg: ModelConfig, batch: int, max_len: int, *,
                   abstract: bool, window: Optional[int], dtype=None):
    L = min(max_len, window) if window else max_len
    if cfg.mla is not None:
        return mla_mod.init_mla_cache(cfg, batch, L, abstract=abstract,
                                      dtype=dtype)
    return attn_mod.init_kv_cache(cfg, batch, L, abstract=abstract,
                                  dtype=dtype)


def attn_cache_logical(cfg: ModelConfig):
    return (mla_mod.MLA_CACHE_LOGICAL if cfg.mla is not None
            else attn_mod.KV_CACHE_LOGICAL)
