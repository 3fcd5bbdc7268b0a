"""Pallas TPU kernel: fused token logprob + entropy over a blocked vocab.

This is the hot spot the paper's "recompute" baseline pays for: scoring
every token against a (up to 256k-entry) vocabulary. The kernel streams
the logits through VMEM with an online max/logsumexp/moment accumulator so
the [T, V] logit matrix never exists in HBM, and the d_model contraction is
itself blocked so every working tile fits VMEM and feeds the MXU with
128-aligned shapes.

Grid: (T/bt, V/bv, D/bd) with D innermost (matmul accumulation), V middle
(online softmax), T outer. Scratch persists across the (V, D) inner loops
for a given T block. Per-token vectors (targets, outputs, running stats)
are 2-D ``[bt, 1]`` blocks: Mosaic refuses 1-D blocks whose tiling differs
from XLA's layout of the operand. A vocab that ``bv`` does not divide
leaves a partial last block; its out-of-range columns are masked.

``token_logprob_entropy_bwd`` is the matching backward pass: a
``lax.scan`` over vocab blocks that recomputes each ``[T, bv]`` logit tile
from the saved log-partition, so the backward never holds ``[T, V]``
either.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(hidden_ref, w_ref, targets_ref, logp_ref, ent_ref, logz_ref,
            logits_acc, m_ref, l_ref, s_ref, tgt_ref, *, bv: int,
            n_v: int, n_d: int, vocab: int):
    j = pl.program_id(1)  # vocab block
    k = pl.program_id(2)  # d_model block

    # ---- matmul accumulation over d blocks (native-dtype MXU inputs,
    # f32 accumulation)
    @pl.when(k == 0)
    def _init_logits():
        logits_acc[...] = jnp.zeros_like(logits_acc)

    logits_acc[...] += jnp.dot(hidden_ref[...], w_ref[...],
                               preferred_element_type=jnp.float32)

    # ---- after the last d block: online softmax update for this v block
    @pl.when(k == n_d - 1)
    def _online_update():
        logits = logits_acc[...]  # [bt, bv] f32
        # mask vocab padding (the partial last block reads past the vocab)
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        valid = j * bv + col < vocab
        logits = jnp.where(valid, logits, NEG_INF)

        @pl.when(j == 0)
        def _init_stats():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            s_ref[...] = jnp.zeros_like(s_ref)
            tgt_ref[...] = jnp.zeros_like(tgt_ref)

        m_prev, l_prev, s_prev = m_ref[...], l_ref[...], s_ref[...]  # [bt,1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p_blk = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        l_new = l_prev * corr + jnp.sum(p_blk, axis=1, keepdims=True)
        # entropy first moment: sum p_shifted * logits
        s_new = s_prev * corr + jnp.sum(
            p_blk * jnp.where(valid, logits, 0.0), axis=1, keepdims=True)
        m_ref[...], l_ref[...], s_ref[...] = m_new, l_new, s_new

        # gather the target logit if it lives in this vocab block
        local = targets_ref[...] - j * bv  # [bt, 1]
        tgt_ref[...] += jnp.sum(jnp.where(col == local, logits, 0.0),
                                axis=1, keepdims=True)

        @pl.when(j == n_v - 1)
        def _finalize():
            logz = m_ref[...] + jnp.log(l_ref[...])
            logp_ref[...] = tgt_ref[...] - logz
            ent_ref[...] = logz - s_ref[...] / l_ref[...]
            logz_ref[...] = logz


@functools.partial(jax.jit, static_argnames=("bt", "bv", "bd", "interpret"))
def logprob_stats_pallas(
    hidden: jax.Array,  # [T, d]
    w: jax.Array,       # [d, V]
    targets: jax.Array,  # [T] int32
    *, bt: int = 256, bv: int = 512, bd: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (logp, entropy, logz), each [T] float32."""
    T, d = hidden.shape
    V = w.shape[1]
    bt = min(bt, T)
    bv = min(bv, V)
    bd = min(bd, d)
    if d % bd:
        # the contraction must not read past d: zero-pad it (only odd
        # widths pay this copy; published d_models divide the block)
        dp = pl.cdiv(d, bd) * bd
        hidden = jnp.pad(hidden, ((0, 0), (0, dp - d)))
        w = jnp.pad(w, ((0, dp - d), (0, 0)))
        d = dp
    dt = jnp.promote_types(hidden.dtype, w.dtype)
    hidden, w = hidden.astype(dt), w.astype(dt)
    n_t, n_v, n_d = pl.cdiv(T, bt), pl.cdiv(V, bv), d // bd

    kernel = functools.partial(_kernel, bv=bv, n_v=n_v, n_d=n_d, vocab=V)
    row = pl.BlockSpec((bt, 1), lambda i, j, k: (i, 0))
    vec = jax.ShapeDtypeStruct((T, 1), jnp.float32)
    logp, ent, logz = pl.pallas_call(
        kernel,
        grid=(n_t, n_v, n_d),
        in_specs=[
            pl.BlockSpec((bt, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bd, bv), lambda i, j, k: (k, j)),
            row,
        ],
        out_specs=(row, row, row),
        scratch_shapes=[
            pltpu.VMEM((bt, bv), jnp.float32),  # logits accumulator
            pltpu.VMEM((bt, 1), jnp.float32),   # running max
            pltpu.VMEM((bt, 1), jnp.float32),   # running sum-exp
            pltpu.VMEM((bt, 1), jnp.float32),   # running sum p*logit
            pltpu.VMEM((bt, 1), jnp.float32),   # target logit
        ],
        out_shape=(vec, vec, vec),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(hidden, w, targets.astype(jnp.int32)[:, None])
    return logp[:, 0], ent[:, 0], logz[:, 0]


@functools.partial(jax.jit, static_argnames=("bv",))
def token_logprob_entropy_bwd(hidden, w, targets, logz, ent, g_logp, g_ent,
                              *, bv: int = 2048
                              ) -> Tuple[jax.Array, jax.Array]:
    """Cotangents of (logp, entropy) -> (d hidden [T, d], d w [d, V]).

    Per row, with z the logits and p = softmax(z):
      d logp / dz = onehot(t) - p
      d H / dz    = -p * (z - E_p[z]),   E_p[z] = logz - H
    so dz = g_logp * (onehot - p) - g_ent * p * (z - E_p[z]), and
    dh = dz @ w.T, dw = hidden.T @ dz. A scan over ``bv``-column vocab
    blocks recomputes each logit tile from the saved ``logz``; the last
    block is shifted back to end at V and masks the columns an earlier
    block already covered, so w is never padded or copied.
    """
    T, d = hidden.shape
    V = w.shape[1]
    bv = min(bv, V)
    n_v = pl.cdiv(V, bv)
    mean_z = (logz - ent)[:, None]
    logz, g_logp, g_ent = logz[:, None], g_logp[:, None], g_ent[:, None]
    targets = targets[:, None]

    def block(carry, j):
        dh, dw = carry
        start = jnp.minimum(j * bv, V - bv)
        w_blk = jax.lax.dynamic_slice_in_dim(w, start, bv, axis=1)
        z = jnp.dot(hidden, w_blk, preferred_element_type=jnp.float32)
        col = start + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
        fresh = col >= j * bv
        p = jnp.exp(z - logz)
        dz = (g_logp * (jnp.where(col == targets, 1.0, 0.0) - p)
              - g_ent * p * (z - mean_z))
        dz = jnp.where(fresh, dz, 0.0)
        dh = dh + jnp.dot(dz.astype(w.dtype), w_blk.T,
                          preferred_element_type=jnp.float32)
        dw_blk = jnp.dot(hidden.T, dz.astype(hidden.dtype),
                         preferred_element_type=jnp.float32)
        # stale (already-covered) columns got dz == 0: adding keeps them
        prev = jax.lax.dynamic_slice_in_dim(dw, start, bv, axis=1)
        dw = jax.lax.dynamic_update_slice_in_dim(
            dw, prev + dw_blk.astype(dw.dtype), start, axis=1)
        return (dh, dw), None

    init = (jnp.zeros((T, d), jnp.float32), jnp.zeros((d, V), w.dtype))
    (dh, dw), _ = jax.lax.scan(block, init, jnp.arange(n_v))
    return dh.astype(hidden.dtype), dw
