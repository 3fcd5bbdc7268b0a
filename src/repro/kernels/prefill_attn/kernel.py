"""Pallas TPU kernel: chunked, segment-packed paged prefill attention.

The decode kernel (``decode_attn/paged_kernel.py``) processes one query
per sequence; prefill needs *many* queries per sequence — whole prompt
chunks, possibly several short prompts packed into one launch. This
kernel keeps the decode kernel's scalar-prefetch block-table walk (the
k/v ``index_map`` selects the physical pool block per (segment,
key-block) grid cell, so only ``block_size`` rows of K/V stream through
VMEM at a time and no dense per-slot view is ever built) but carries the
whole chunk of queries through the sweep with a per-row online-softmax
accumulator.

Grid: ``(n_kv_heads, n_seqs, max_blocks_per_seq)``. GQA rides in the
query block: the wrapper lays the chunk out as ``[KV, C * G, hd]`` (row
``c * G + g`` is query head ``g`` of the group at chunk row ``c``), so a
K/V tile of the ``[n_blocks, KV, bs, hd]`` pool is read once per group.
For a fixed kv head the (s, j) sweep visits every segment's mapped
blocks; each row accumulates only blocks of its own segment at key
positions at or before its own (``seg_ids[i] == s and kpos <=
q_pos[i]``) — causal within the chunk, isolated across packed prompts.
Segments with no resident keys (idle slots) are skipped via the
prefetched per-segment key counts. Padding rows (``seg_ids[i] < 0``)
never match a segment, so their accumulator stays empty and they emit
zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, kv_lens_ref, seg_ref, pos_ref, q_ref, k_ref, v_ref,
            o_ref, acc, m_ref, l_ref, *, bs: int, n_seg: int, n_b: int,
            scale: float):
    s_i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((s_i == 0) & (j == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip key blocks past this segment's resident-token count (block j
    # covers positions [j*bs, (j+1)*bs); unmapped table entries are
    # clamped to block 0 by the wrapper and always land in skipped or
    # masked territory)
    @pl.when(j * bs < kv_lens_ref[s_i])
    def _accumulate():
        q = q_ref[...]                           # [R, hd]
        k = k_ref[...]                           # [bs, hd]
        v = v_ref[...]                           # [bs, hd]
        seg = seg_ref[...]                       # [R, 1]
        pos = pos_ref[...]                       # [R, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [R, bs]
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # own segment only, causally up to and including the row's own
        # position (its K/V is written to the pool before attention)
        mask = (seg == s_i) & (kpos <= pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]  # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # the mask factor kills rows whose running max is still NEG_INF
        # (padding / no keys yet): there exp(s - m_new) == exp(0) == 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [R, bs]
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((s_i == n_seg - 1) & (j == n_b - 1))
    def _done():
        # rows that accumulated nothing (padding) have l == 0 -> emit 0
        o_ref[...] = (acc[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_pallas(q: jax.Array, pool_k: jax.Array,
                                   pool_v: jax.Array,
                                   block_tables: jax.Array,
                                   seg_ids: jax.Array, q_pos: jax.Array,
                                   kv_lens: jax.Array, *,
                                   interpret: bool = False) -> jax.Array:
    """q [C,H,hd]; pool_k/v [n_blocks,KV,bs,hd] (one layer's pool);
    block_tables [S,max_blocks] int32 (-1 = unmapped); seg_ids [C] slot
    per row (-1 = padding); q_pos [C] absolute positions; kv_lens [S]
    per-segment resident-token counts (block-skip) -> [C,H,hd]."""
    C, H, hd = q.shape
    KV, bs = pool_k.shape[1], pool_k.shape[2]
    S, mb = block_tables.shape
    G = H // KV
    R = C * G
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)
    kernel = functools.partial(_kernel, bs=bs, n_seg=S, n_b=mb,
                               scale=hd ** -0.5)
    # [C, H, hd] -> [KV, C*G, hd]: one kv group's queries per grid row
    qg = q.reshape(C, KV, G, hd).transpose(1, 0, 2, 3).reshape(KV, R, hd)
    rows = lambda x: jnp.repeat(x.astype(jnp.int32), G)[:, None]  # noqa
    row_spec = pl.BlockSpec((R, 1), lambda g, s, j, tbl, ln: (0, 0))
    q_spec = pl.BlockSpec((None, R, hd), lambda g, s, j, tbl, ln: (g, 0, 0))
    # the paged gather: physical block straight from the table
    kv_spec = pl.BlockSpec((None, None, bs, hd),
                           lambda g, s, j, tbl, ln: (tbl[s, j], g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(KV, S, mb),
        in_specs=[row_spec, row_spec, q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((R, hd), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, R, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables, kv_lens.astype(jnp.int32), rows(seg_ids), rows(q_pos),
      qg.astype(pool_k.dtype), pool_k, pool_v)
    return out.reshape(KV, C, G, hd).transpose(1, 0, 2, 3).reshape(C, H, hd)
