"""Continuous batching server over the paged KV cache.

Requests are admitted into fixed slots as others finish (so the decode
step compiles once for ``max_seqs``); finished sequences release their
pages back to the allocator. This is the serving loop the paper's rollout
engines (vLLM/SGLang) implement, in-framework.

Supports dense GQA/MHA architectures (the paged pool holds per-layer
K/V), pure-SSM stacks (mamba2 — a constant-size per-slot state pool
instead of KV blocks), and hybrid stacks (zamba2 — SSM state slots plus
the paged pool for the shared attention layers).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RLConfig
from repro.data import tokenizer as tok
from repro.kernels.decode_attn.ops import paged_decode_attention_op
from repro.kernels.decode_attn.ref import gather_pages
from repro.kernels.prefill_attn.ops import paged_prefill_attention_op
from repro.models import blocks as blk_mod
from repro.models import model as M
from repro.models.attention import decode_attention
from repro.models.layers import (
    apply_rope,
    embed_tokens,
    logits_from_hidden,
    rmsnorm,
)
from repro.models.layers import swiglu
from repro.obs.tracing import annotate, span
from repro.rollout import paged_cache as pc
from repro.rollout.sampler import (
    fused_sample_step,
    greedy_token,
    sample_token,
)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # [P] token ids (unpadded)
    max_new: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # --- staleness-aware control plane bookkeeping -----------------------
    # behavior logprob of each generated token (under the params that
    # produced its logits) and the weight version of those params: the
    # per-token [B, T] stamps a3po.staleness consumes.
    gen_logp: List[float] = dataclasses.field(default_factory=list)
    token_versions: List[int] = dataclasses.field(default_factory=list)
    priority: int = 0            # scheduler class (lower = more urgent)
    submit_version: int = 0      # weight version when the request arrived
    prefix_hit_tokens: int = 0   # prompt tokens served from the radix cache
    preempt_count: int = 0
    # chunked-prefill cursor: prompt tokens whose K/V is resident in the
    # paged pool (radix hits count). The slot only enters the decode
    # horizon once prefill_done.
    prefill_pos: int = 0
    # lifecycle stamps (control-plane clock — wall by default, virtual
    # under the loadgen replay harness; -1 = unset)
    t_submit: float = -1.0
    t_admit: float = -1.0
    t_first_token: float = -1.0
    t_done: float = -1.0
    # --- multi-tenant / SLO bookkeeping (loadgen harness) ----------------
    tenant: str = ""
    slo_class: str = ""          # SLO class name (stamped by SLO scheduler)
    deadline_s: float = float("inf")  # absolute TTFT deadline (clock time)
    drop_reason: str = ""        # staleness_budget | max_preempts | slo_shed

    @property
    def prefill_done(self) -> bool:
        return self.prefill_pos >= len(self.prompt)

    def min_version(self) -> int:
        return min(self.token_versions) if self.token_versions \
            else self.submit_version

    def reset_generation(self) -> None:
        """Discard sampled state for a fresh restart (preempt/resubmit).

        The first-token stamp is cleared too: a restarted request lost
        its partial generation, so the first token the caller actually
        receives is the one after the restart (TTFT re-observes).
        """
        self.generated = []
        self.gen_logp = []
        self.token_versions = []
        self.done = False
        self.prefill_pos = 0
        self.t_first_token = -1.0


def _token_layer_stack(params, cfg: ModelConfig, lens, tokens, kv,
                       append_attend):
    """One-token transformer stack shared by both decode towers.

    Embeds ``tokens`` [S] and runs the layer stack;
    ``append_attend(li, q, k, v, kv) -> (o, kv)`` owns the KV-cache
    representation — the paged pool for the single-step path, a
    horizon-local contiguous view for the fused loop — so the layer math
    (and hence TPU/off-TPU bit-parity) lives in exactly one place.
    Returns (logits [S, V], kv).
    """
    x = embed_tokens(params["embedding"], tokens[:, None], cfg)[:, 0]

    def layer(carry, xs):
        x, kv = carry
        lp, li = xs
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        ap = lp["attn"]
        q = jnp.einsum("bd,dhk->bhk", h, ap["wq"])
        k = jnp.einsum("bd,dhk->bhk", h, ap["wk"])
        v = jnp.einsum("bd,dhk->bhk", h, ap["wv"])
        if cfg.qkv_bias:
            q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
        # one rope over q‖k: positions (and their sin/cos) are shared
        qk = apply_rope(jnp.concatenate([q, k], axis=1)[:, None],
                        lens[:, None], cfg.rope_theta)[:, 0]
        q, k = qk[:, : q.shape[1]], qk[:, q.shape[1]:]
        o, kv = append_attend(li, q, k, v, kv)
        y = jnp.einsum("bhk,hkd->bd", o, ap["wo"])
        if cfg.parallel_block:
            f = swiglu(lp["ffn"], h)
            x = x + y + f
        else:
            x = x + y
            h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
            x = x + swiglu(lp["ffn"], h2)
        return (x, kv), None

    li = jnp.arange(len(cfg.block_kinds()), dtype=jnp.int32)
    # fully unrolled: serving stacks are shallow and the per-iteration
    # scan machinery (dynamic pool slicing) dominates tiny decode matmuls
    (x, kv), _ = jax.lax.scan(layer, (x, kv), (params["blocks"], li),
                              unroll=True)
    x = rmsnorm(params["final_norm"], x[:, None], cfg.norm_eps)[:, 0]
    logits = logits_from_hidden(params["embedding"], x, cfg)
    return logits, kv


def _decode_tower(params, cfg: ModelConfig, pool_k, pool_v, block_tables,
                  lens, tokens, write_block, offset):
    """One-token layer stack over the paged pool.

    Appends each layer's K/V at ``(write_block, offset)`` per slot and
    attends through the block table via ``paged_decode_attention_op``
    (Pallas on TPU, XLA gather elsewhere) -> (logits, pool_k, pool_v).
    Callers choose the write targets: the single-step path writes at the
    current length for every slot; the fused horizon redirects finished
    slots to the scratch block so a masked-out step can never touch live
    pages.
    """
    def append_attend(li, q, k, v, kv):
        pool_k, pool_v = kv
        pool_k = pc.write_pages(pool_k, li, write_block, offset, k)
        pool_v = pc.write_pages(pool_v, li, write_block, offset, v)
        # lens + 1: the just-written token is attended (inclusive mask)
        o = paged_decode_attention_op(q, pool_k[li], pool_v[li],
                                      block_tables, lens + 1)
        return o, (pool_k, pool_v)

    logits, (pool_k, pool_v) = _token_layer_stack(
        params, cfg, lens, tokens, (pool_k, pool_v), append_attend)
    return logits, pool_k, pool_v


@functools.partial(jax.jit, static_argnames=("cfg", "trash_block"),
                   donate_argnames=("pool_k", "pool_v"))
def _paged_decode_step(params, cfg: ModelConfig, pool_k, pool_v,
                       block_tables, seq_lens, tokens, active, *,
                       trash_block: int = 0):
    """One token for every slot against the paged pool.

    tokens: [S_max]; active: [S_max] bool — inactive slots (idle, or
    mid-prefill with live pages at their cursor) have their K/V append
    redirected to the scratch block so a batch-wide launch can never
    corrupt pages it doesn't own. Returns (logits [S_max, V], pool_k,
    pool_v).
    """
    bs = pool_k.shape[3]
    safe_tables = jnp.maximum(block_tables, 0)
    blk_idx = seq_lens // bs
    write_block = jnp.take_along_axis(safe_tables, blk_idx[:, None],
                                      axis=1)[:, 0]
    write_block = jnp.where(active, write_block, trash_block)
    offset = jnp.where(active, seq_lens % bs, 0)
    return _decode_tower(params, cfg, pool_k, pool_v, block_tables,
                         seq_lens, tokens, write_block, offset)


def _prefill_tower(params, cfg: ModelConfig, pool_k, pool_v, block_tables,
                   seg_ids, q_pos, kv_lens, tokens, write_block, offset):
    """Chunk-of-tokens layer stack over the paged pool.

    The chunk's ``C`` rows are virtual decode slots: the same
    ``_token_layer_stack`` runs with per-row positions ``q_pos``, each
    layer scatters the chunk's K/V into pool pages at ``(write_block,
    offset)`` in ONE dispatch (padding rows land on scratch), and
    attention walks each row's slot block table via
    ``paged_prefill_attention_op`` — so the per-row math is identical to
    the decode tower and no dense [L, P, KV, hd] intermediate ever
    exists. Returns (logits [C, V], pool_k, pool_v).
    """
    def append_attend(li, q, k, v, kv):
        pool_k, pool_v = kv
        pool_k = pc.write_pages(pool_k, li, write_block, offset, k)
        pool_v = pc.write_pages(pool_v, li, write_block, offset, v)
        o = paged_prefill_attention_op(q, pool_k[li], pool_v[li],
                                       block_tables, seg_ids, q_pos,
                                       kv_lens)
        return o, (pool_k, pool_v)

    logits, (pool_k, pool_v) = _token_layer_stack(
        params, cfg, q_pos, tokens, (pool_k, pool_v), append_attend)
    return logits, pool_k, pool_v


@functools.partial(jax.jit, static_argnames=("cfg", "trash_block"),
                   donate_argnames=("pool_k", "pool_v", "next_logits"))
def _paged_prefill_chunk(params, cfg: ModelConfig, pool_k, pool_v,
                         block_tables, seq_lens, next_logits, tokens,
                         seg_ids, q_pos, kv_lens, last_rows, complete,
                         seg_counts, *, trash_block: int):
    """One fixed-shape prefill chunk: C prompt tokens, possibly spanning
    several slots (segment-packed), written straight into pool pages.

    tokens/seg_ids/q_pos: [C] (padding rows carry seg -1); kv_lens [S]
    per-slot resident count *after* this chunk; last_rows/complete/
    seg_counts: [S] — the chunk row holding each slot's final prompt
    token (when ``complete``), whether the slot finishes its prompt here,
    and how many rows belong to it. Completing slots get their
    next-token logits installed; ``seq_lens`` advances by the rows
    written. Compiles once per (C bucket, S) shape.
    """
    bs = pool_k.shape[3]
    safe_tables = jnp.maximum(block_tables, 0)
    row_tables = safe_tables[jnp.maximum(seg_ids, 0)]        # [C, mb]
    blk_idx = jnp.minimum(q_pos // bs, row_tables.shape[1] - 1)
    wb = jnp.take_along_axis(row_tables, blk_idx[:, None], axis=1)[:, 0]
    wb = jnp.where(seg_ids >= 0, wb, trash_block)
    off = jnp.where(seg_ids >= 0, q_pos % bs, 0)
    logits, pool_k, pool_v = _prefill_tower(
        params, cfg, pool_k, pool_v, block_tables, seg_ids, q_pos, kv_lens,
        tokens, wb, off)
    sel = logits[jnp.maximum(last_rows, 0)]                  # [S, V]
    next_logits = jnp.where(complete[:, None],
                            sel.astype(next_logits.dtype), next_logits)
    return next_logits, pool_k, pool_v, seq_lens + seg_counts


@functools.partial(jax.jit, static_argnames=("cfg", "trash_block"),
                   donate_argnames=("pool_k", "pool_v"))
def _dense_prefill(params, cfg: ModelConfig, pool_k, pool_v, tokens,
                   length, table, *, trash_block: int):
    """Whole-sequence dense prefill into pool pages, one scatter.

    tokens [1, Pb] right-padded to a chunk-ladder bucket (so the compile
    shape is the bucket, not the prompt length); length: true prompt
    length; table [max_blocks] this slot's block table. Returns
    (next-token logits [V], pool_k, pool_v) — the K/V of all Pb
    positions lands in the pool via a single batched scatter (padding
    positions on the scratch block) instead of a host loop of per-block
    copies.
    """
    Pb = tokens.shape[1]
    bs = pool_k.shape[3]
    hidden, cache = M.prefill(params, cfg, tokens,
                              lengths=length[None], max_len=Pb)
    k = cache["attn"]["k"][:, 0]  # [L, Pb, KV, hd]
    v = cache["attn"]["v"][:, 0]
    pos = jnp.arange(Pb)
    blk_idx = jnp.minimum(pos // bs, table.shape[0] - 1)
    phys = jnp.where(pos < length, jnp.maximum(table, 0)[blk_idx],
                     trash_block)
    off = jnp.where(pos < length, pos % bs, 0)
    pool_k = pc.write_pages(pool_k, None, phys, off, k)
    pool_v = pc.write_pages(pool_v, None, phys, off, v)
    h_last = jnp.take(hidden[0], length - 1, axis=0)
    logits = logits_from_hidden(params["embedding"], h_last[None], cfg)[0]
    return logits, pool_k, pool_v


def _decode_tower_view(params, cfg: ModelConfig, view_k, view_v, lens,
                       tokens, rows):
    """Horizon-local variant of ``_decode_tower`` over contiguous views.

    ``view_k``/``view_v`` [L, S, max_blocks*bs, KV, hd] are each slot's
    block-table gather, materialized ONCE per horizon — so the per-token
    hot loop is an in-place append at ``(slot, lens)`` plus dense decode
    attention, with no per-token pool gather/scatter. Identical values to
    the paged path (the view captures exactly what the gather would
    read), hence bit-identical logits.
    """
    # the inclusive valid mask is layer-independent: compute it once
    valid = jnp.arange(view_k.shape[2])[None, :] <= lens[:, None]

    def append_attend(li, q, k, v, kv):
        view_k, view_v = kv
        view_k = view_k.at[li, rows, lens].set(k.astype(view_k.dtype))
        view_v = view_v.at[li, rows, lens].set(v.astype(view_v.dtype))
        o = decode_attention(q, view_k[li], view_v[li], valid)
        return o, (view_k, view_v)

    logits, (view_k, view_v) = _token_layer_stack(
        params, cfg, lens, tokens, (view_k, view_v), append_attend)
    return logits, view_k, view_v


@functools.partial(jax.jit, static_argnames=("cfg", "horizon", "temperature",
                                             "top_p", "greedy",
                                             "trash_block", "use_view"),
                   donate_argnames=("pool_k", "pool_v"))
def _paged_decode_horizon(params, cfg: ModelConfig, pool_k, pool_v,
                          block_tables, seq_lens, next_logits,
                          budget, key, *, trash_block: int, horizon: int,
                          temperature: float, top_p: float, greedy: bool,
                          use_view: Optional[bool] = None):
    """A whole decode horizon as one compiled ``lax.scan``.

    Each iteration samples on device from the carried logits
    (``fused_sample_step``: PAD/zero-mask for finished rows, EOS folded
    into the done flags), appends K/V, and bumps the emitting slots'
    lengths — no host round-trip anywhere inside. ``budget`` [S] caps
    per-slot emissions (a slot's remaining ``max_new``); finished or
    over-budget slots keep decoding masked (their writes land in scratch
    space and their mask is 0). The per-token key schedule is
    ``key, sub = split(key)`` per iteration — exactly the schedule a
    step-by-step driver uses, so seeded sampling is bit-identical to
    ``horizon`` calls of ``step``.

    On TPU the scan attends through the block table with the paged Pallas
    kernel every token (no dense materialization — VMEM streaming is the
    win there). Elsewhere the block table is frozen for the horizon
    anyway, so each slot's KV view is gathered ONCE up front, the scan
    runs on the contiguous views (same values, bit-identical logits), and
    the new K/V is scattered back to the pool in one shot at the end —
    removing the per-token gather/scatter that dominates XLA-CPU decode.

    Returns (packed [3, horizon, S] float32 — tokens / logps / masks,
    drained to host as ONE transfer), plus the updated pool, lengths, and
    next-token logits, which all stay on device.
    """
    bs = pool_k.shape[3]
    S, mb = block_tables.shape
    safe_tables = jnp.maximum(block_tables, 0)
    if use_view is None:
        use_view = jax.default_backend() != "tpu"
    rows = jnp.arange(S)
    done0 = budget <= 0  # inactive slots ship with budget 0

    def sample(logits, done, key, t):
        key, sub = jax.random.split(key)
        done_in = done | (t >= budget)
        token, logp, mask, done_out = fused_sample_step(
            logits, sub, done_in, temperature=temperature, top_p=top_p,
            greedy=greedy)
        done_out = done_out | (t + 1 >= budget)
        return token, logp, mask, done_out, key

    def one_token_paged(carry, t):
        pool_k, pool_v, lens, logits, done, key = carry
        token, logp, mask, done, key = sample(logits, done, key, t)
        emit = mask > 0.0
        blk_idx = lens // bs
        wb = jnp.take_along_axis(safe_tables, blk_idx[:, None],
                                 axis=1)[:, 0]
        wb = jnp.where(emit, wb, trash_block)
        off = jnp.where(emit, lens % bs, 0)
        logits, pool_k, pool_v = _decode_tower(
            params, cfg, pool_k, pool_v, block_tables, lens, token, wb,
            off)
        lens = lens + emit.astype(lens.dtype)
        return (pool_k, pool_v, lens, logits, done, key), (token, logp,
                                                           mask)

    def one_token_view(carry, t):
        view_k, view_v, lens, logits, done, key = carry
        token, logp, mask, done, key = sample(logits, done, key, t)
        # non-emitting slots overwrite their own (never-valid, never
        # written-back) position `lens`; OOB appends are dropped
        logits, view_k, view_v = _decode_tower_view(
            params, cfg, view_k, view_v, lens, token, rows)
        lens = lens + (mask > 0.0).astype(lens.dtype)
        return (view_k, view_v, lens, logits, done, key), (token, logp,
                                                           mask)

    ts = jnp.arange(horizon, dtype=jnp.int32)
    if use_view:
        gather = jax.vmap(gather_pages, in_axes=(0, None))
        view_k = gather(pool_k, block_tables)
        view_v = gather(pool_v, block_tables)
        (view_k, view_v, lens, logits, _, _), (tokens, logps, masks) = \
            jax.lax.scan(one_token_view,
                         (view_k, view_v, seq_lens, next_logits, done0,
                          key), ts)
        # write the horizon's new K/V back to the paged pool in one shot:
        # emissions are a prefix, so token t of slot s sits at view
        # position seq_lens[s] + t; masked rows are parked on the
        # scratch block
        emits = masks > 0.0                              # [H, S]
        pos = seq_lens[None, :] + ts[:, None]            # [H, S]
        vpos = jnp.minimum(pos, mb * bs - 1)
        new_k = view_k[:, rows[None, :], vpos]           # [L, H, S, KV, hd]
        new_v = view_v[:, rows[None, :], vpos]
        blk = safe_tables[rows[None, :], jnp.minimum(pos // bs, mb - 1)]
        blk = jnp.where(emits, blk, trash_block).reshape(-1)
        off = jnp.where(emits, pos % bs, 0).reshape(-1)
        flat = (new_k.shape[0], horizon * S) + new_k.shape[3:]
        pool_k = pc.write_pages(pool_k, None, blk, off, new_k.reshape(flat))
        pool_v = pc.write_pages(pool_v, None, blk, off, new_v.reshape(flat))
    else:
        (pool_k, pool_v, lens, logits, _, _), (tokens, logps, masks) = \
            jax.lax.scan(one_token_paged,
                         (pool_k, pool_v, seq_lens, next_logits, done0,
                          key), ts)
    # one packed drain: token ids are exact in f32 (vocab << 2**24)
    packed = jnp.stack([tokens.astype(jnp.float32), logps, masks])
    return packed, pool_k, pool_v, lens, logits


# ---------------------------------------------------- multi-architecture
def _multiarch_token_stack(params, cfg: ModelConfig, lens, tokens, conv,
                           state, kv, append_attend, update_mask):
    """One-token stack over SSM/hybrid layer sequences.

    ``conv``/``state`` are the per-slot recurrent pools [n_ssm, S, ...];
    ``update_mask`` [S] gates their update — a masked slot carries its
    state through bit-exactly (the SSM analogue of redirecting KV appends
    to the scratch block). Attention layers (hybrid's shared block) run
    the same math as ``_token_layer_stack``'s body through
    ``append_attend``. Python-unrolled over ``cfg.block_kinds()``: the
    layer sequence is heterogeneous and serving stacks are shallow.
    """
    x = embed_tokens(params["embedding"], tokens[:, None], cfg)[:, 0]
    ssm_params = params["blocks"] if cfg.arch_type == "ssm" \
        else params["ssm_blocks"]
    si = ai = 0
    for kind in cfg.block_kinds():
        if kind == "ssm":
            lp = jax.tree.map(lambda a, i=si: a[i], ssm_params)
            c_in = {"conv": conv[si], "state": state[si]}
            x, _, c_out = blk_mod.ssm_block_decode(lp, x, cfg, c_in)
            m3 = update_mask[:, None, None]
            conv = conv.at[si].set(jnp.where(m3, c_out["conv"], conv[si]))
            state = state.at[si].set(
                jnp.where(m3[..., None], c_out["state"], state[si]))
            si += 1
        else:
            lp = params["shared_attn"]
            h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
            ap = lp["attn"]
            q = jnp.einsum("bd,dhk->bhk", h, ap["wq"])
            k = jnp.einsum("bd,dhk->bhk", h, ap["wk"])
            v = jnp.einsum("bd,dhk->bhk", h, ap["wv"])
            if cfg.qkv_bias:
                q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
            qk = apply_rope(jnp.concatenate([q, k], axis=1)[:, None],
                            lens[:, None], cfg.rope_theta)[:, 0]
            q, k = qk[:, : q.shape[1]], qk[:, q.shape[1]:]
            o, kv = append_attend(ai, q, k, v, kv)
            y = jnp.einsum("bhk,hkd->bd", o, ap["wo"])
            if cfg.parallel_block:
                x = x + y + swiglu(lp["ffn"], h)
            else:
                x = x + y
                h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
                x = x + swiglu(lp["ffn"], h2)
            ai += 1
    x = rmsnorm(params["final_norm"], x[:, None], cfg.norm_eps)[:, 0]
    logits = logits_from_hidden(params["embedding"], x, cfg)
    return logits, conv, state, kv


@functools.partial(jax.jit, static_argnames=("cfg", "trash_block"),
                   donate_argnames=("pool_k", "pool_v", "conv", "state"))
def _multiarch_decode_step(params, cfg: ModelConfig, pool_k, pool_v, conv,
                           state, block_tables, seq_lens, tokens, active,
                           *, trash_block: int = 0):
    """SSM/hybrid variant of ``_paged_decode_step``: one token per slot,
    KV appended into the paged pool (hybrid attention layers) and the
    recurrent state pools advanced, with ``active`` gating both."""
    bs = pool_k.shape[3]
    safe_tables = jnp.maximum(block_tables, 0)
    blk_idx = seq_lens // bs
    write_block = jnp.take_along_axis(safe_tables, blk_idx[:, None],
                                      axis=1)[:, 0]
    write_block = jnp.where(active, write_block, trash_block)
    offset = jnp.where(active, seq_lens % bs, 0)

    def append_attend(li, q, k, v, kv):
        pool_k, pool_v = kv
        pool_k = pc.write_pages(pool_k, li, write_block, offset, k)
        pool_v = pc.write_pages(pool_v, li, write_block, offset, v)
        o = paged_decode_attention_op(q, pool_k[li], pool_v[li],
                                      block_tables, seq_lens + 1)
        return o, (pool_k, pool_v)

    logits, conv, state, (pool_k, pool_v) = _multiarch_token_stack(
        params, cfg, seq_lens, tokens, conv, state, (pool_k, pool_v),
        append_attend, active)
    return logits, pool_k, pool_v, conv, state


@functools.partial(jax.jit, static_argnames=("cfg", "horizon", "temperature",
                                             "top_p", "greedy",
                                             "trash_block"),
                   donate_argnames=("pool_k", "pool_v", "conv", "state"))
def _multiarch_decode_horizon(params, cfg: ModelConfig, pool_k, pool_v,
                              conv, state, block_tables, seq_lens,
                              next_logits, budget, key, *,
                              trash_block: int, horizon: int,
                              temperature: float, top_p: float,
                              greedy: bool):
    """SSM/hybrid variant of ``_paged_decode_horizon``.

    The recurrent pools ride in the scan carry next to the KV pool; the
    per-token emit mask gates both the KV append (scratch redirect) and
    the state update (masked slots carry state through unchanged), so EOS
    masking, budget exhaustion, and mid-prefill slots behave exactly as
    in the dense horizon. No contiguous-view fast path: SSM state is
    already O(1) per slot, and the hybrid attention layers take the paged
    path on every backend.
    """
    bs = pool_k.shape[3]
    safe_tables = jnp.maximum(block_tables, 0)
    done0 = budget <= 0

    def sample(logits, done, key, t):
        key, sub = jax.random.split(key)
        done_in = done | (t >= budget)
        token, logp, mask, done_out = fused_sample_step(
            logits, sub, done_in, temperature=temperature, top_p=top_p,
            greedy=greedy)
        done_out = done_out | (t + 1 >= budget)
        return token, logp, mask, done_out, key

    def one_token(carry, t):
        pool_k, pool_v, conv, state, lens, logits, done, key = carry
        token, logp, mask, done, key = sample(logits, done, key, t)
        emit = mask > 0.0
        blk_idx = lens // bs
        wb = jnp.take_along_axis(safe_tables, blk_idx[:, None],
                                 axis=1)[:, 0]
        wb = jnp.where(emit, wb, trash_block)
        off = jnp.where(emit, lens % bs, 0)

        def append_attend(li, q, k, v, kv):
            pool_k, pool_v = kv
            pool_k = pc.write_pages(pool_k, li, wb, off, k)
            pool_v = pc.write_pages(pool_v, li, wb, off, v)
            o = paged_decode_attention_op(q, pool_k[li], pool_v[li],
                                          block_tables, lens + 1)
            return o, (pool_k, pool_v)

        logits, conv, state, (pool_k, pool_v) = _multiarch_token_stack(
            params, cfg, lens, token, conv, state, (pool_k, pool_v),
            append_attend, emit)
        lens = lens + emit.astype(lens.dtype)
        return (pool_k, pool_v, conv, state, lens, logits, done, key), (
            token, logp, mask)

    ts = jnp.arange(horizon, dtype=jnp.int32)
    (pool_k, pool_v, conv, state, lens, logits, _, _), \
        (tokens, logps, masks) = jax.lax.scan(
            one_token, (pool_k, pool_v, conv, state, seq_lens,
                        next_logits, done0, key), ts)
    packed = jnp.stack([tokens.astype(jnp.float32), logps, masks])
    return packed, pool_k, pool_v, conv, state, lens, logits


@functools.partial(jax.jit, static_argnames=("cfg", "trash_block"),
                   donate_argnames=("pool_k", "pool_v", "conv", "state",
                                    "next_logits"))
def _multiarch_prefill_chunk(params, cfg: ModelConfig, pool_k, pool_v,
                             conv, state, block_tables, seq_lens,
                             next_logits, tokens, starts, counts,
                             complete, *, trash_block: int):
    """One fixed-shape SSM/hybrid prefill chunk, one batch row per slot.

    Unlike the attention chunk lane (segment-packed [C] rows), the SSD
    scan is recurrent per sequence, so each prefilling slot owns one row
    of a [S, Cb] batch: ``tokens`` right-padded to the bucket,
    ``counts`` [S] real tokens per row (0 = slot not prefilling),
    ``starts`` [S] the per-slot prompt cursor. SSM layers run the
    chunked SSD scan resuming from (and updating) the slot state pools —
    pad rows carry dt=0 so they freeze the state exactly, and the conv
    tail is sliced at ``counts`` so ragged chunks resume bit-exactly.
    Hybrid attention layers flatten to [S*Cb] virtual decode rows over
    the paged pool, exactly like ``_prefill_tower``. Completing slots
    get next-token logits installed; ``seq_lens`` advances by ``counts``.
    """
    S, Cb = tokens.shape
    bs = pool_k.shape[3]
    row_active = counts > 0
    pad_mask = jnp.arange(Cb)[None, :] < counts[:, None]           # [S, Cb]
    positions = starts[:, None] + jnp.arange(Cb, dtype=jnp.int32)  # [S, Cb]
    kv_lens = seq_lens + counts

    # flattened [S*Cb] rows for the attention layers (hybrid only)
    seg_flat = jnp.where(pad_mask, jnp.arange(S, dtype=jnp.int32)[:, None],
                         -1).reshape(-1)
    pos_flat = positions.reshape(-1)
    safe_tables = jnp.maximum(block_tables, 0)
    row_tables = safe_tables[jnp.maximum(seg_flat, 0)]
    blk_idx = jnp.minimum(pos_flat // bs, row_tables.shape[1] - 1)
    wb = jnp.take_along_axis(row_tables, blk_idx[:, None], axis=1)[:, 0]
    wb = jnp.where(seg_flat >= 0, wb, trash_block)
    off = jnp.where(seg_flat >= 0, pos_flat % bs, 0)

    x = embed_tokens(params["embedding"], tokens, cfg)             # [S,Cb,d]
    ssm_params = params["blocks"] if cfg.arch_type == "ssm" \
        else params["ssm_blocks"]
    si = ai = 0
    for kind in cfg.block_kinds():
        if kind == "ssm":
            lp = jax.tree.map(lambda a, i=si: a[i], ssm_params)
            c_in = {"conv": conv[si], "state": state[si]}
            x, _, c_out = blk_mod.ssm_block_full(
                lp, x, cfg, pad_mask=pad_mask, initial_cache=c_in,
                valid_lens=counts)
            m3 = row_active[:, None, None]
            conv = conv.at[si].set(jnp.where(m3, c_out["conv"], conv[si]))
            state = state.at[si].set(
                jnp.where(m3[..., None], c_out["state"], state[si]))
            si += 1
        else:
            lp = params["shared_attn"]
            h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
            ap = lp["attn"]
            q = jnp.einsum("bsd,dhk->bshk", h, ap["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, ap["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, ap["wv"])
            if cfg.qkv_bias:
                q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
            qk = apply_rope(jnp.concatenate([q, k], axis=2), positions,
                            cfg.rope_theta)
            q, k = qk[:, :, : q.shape[2]], qk[:, :, q.shape[2]:]

            def flat(t):
                return t.reshape((S * Cb,) + t.shape[2:])

            pool_k = pc.write_pages(pool_k, ai, wb, off, flat(k))
            pool_v = pc.write_pages(pool_v, ai, wb, off, flat(v))
            o = paged_prefill_attention_op(flat(q), pool_k[ai], pool_v[ai],
                                           block_tables, seg_flat,
                                           pos_flat, kv_lens)
            y = jnp.einsum("bshk,hkd->bsd",
                           o.reshape((S, Cb) + o.shape[1:]), ap["wo"])
            if cfg.parallel_block:
                x = x + y + swiglu(lp["ffn"], h)
            else:
                x = x + y
                h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
                x = x + swiglu(lp["ffn"], h2)
            ai += 1
    h_last = jnp.take_along_axis(
        x, jnp.maximum(counts - 1, 0)[:, None, None], axis=1)[:, 0]
    h_last = rmsnorm(params["final_norm"], h_last[:, None],
                     cfg.norm_eps)[:, 0]
    logits = logits_from_hidden(params["embedding"], h_last, cfg)
    next_logits = jnp.where(complete[:, None],
                            logits.astype(next_logits.dtype), next_logits)
    return next_logits, pool_k, pool_v, conv, state, seq_lens + counts


class ContinuousBatchingEngine:
    def __init__(self, cfg: ModelConfig, *, max_seqs: int = 8,
                 block_size: int = 16, n_blocks: int = 256,
                 max_blocks_per_seq: int = 16,
                 rl: Optional[RLConfig] = None, greedy: bool = False,
                 prefix_cache=None, decode_horizon: int = 1,
                 prefill_chunk: int = 32, prefill_mode: str = "chunked"):
        assert cfg.arch_type in ("dense", "ssm", "hybrid"), \
            f"paged serving: dense/ssm/hybrid archs, got {cfg.arch_type}"
        assert prefill_mode in ("chunked", "dense"), prefill_mode
        self.cfg = cfg
        self.rl = rl or RLConfig()
        self.greedy = greedy
        self.max_seqs = max_seqs
        # prefill lane: prompts stream through fixed-shape chunk launches
        # of at most ``prefill_chunk`` tokens (short prompts packed
        # together, long prompts resumable via Request.prefill_pos).
        # Launches are padded up the bucket ladder so the chunk step
        # compiles once per bucket, not once per prompt length.
        # ``prefill_mode="dense"`` keeps the legacy inline whole-sequence
        # path (the bench baseline), itself bucket-padded.
        self.prefill_mode = prefill_mode
        self.prefill_chunk = int(prefill_chunk)
        self._chunk_buckets = tuple(sorted(
            {max(8, self.prefill_chunk // 4),
             max(8, self.prefill_chunk // 2), self.prefill_chunk}))
        # tokens decoded per compiled launch: 1 = the per-token fallback
        # (step), >1 = the fused horizon (step_horizon) — host bookkeeping
        # then runs only at horizon boundaries. Callers that observe
        # per-token state between steps (publish-interleaved tests, the
        # per-token baseline bench) keep the default of 1.
        self.decode_horizon = int(decode_horizon)
        # duck-typed serving.prefix_cache.RadixPrefixCache (kept untyped to
        # avoid a rollout -> serving import cycle)
        self.prefix_cache = prefix_cache
        # SSM/hybrid: constant-size per-slot recurrent state rides next to
        # the paged KV pool (which has zero layers for pure-SSM stacks —
        # block/length bookkeeping stays uniform at no memory cost)
        self.n_ssm = sum(1 for k in cfg.block_kinds() if k == "ssm")
        if self.n_ssm:
            assert cfg.moe is None and cfg.frontend is None, \
                "SSM/hybrid serving: no MoE or frontend stacks"
            assert prefill_mode == "chunked", \
                "SSM/hybrid serving requires the chunked prefill lane"
            assert prefix_cache is None, \
                "radix prefix cache shares KV blocks across sequences; " \
                "recurrent SSM state cannot be shared that way"
            self.ssm_cache = pc.init_ssm_state_cache(
                cfg, max_seqs=max_seqs, dtype=jnp.dtype(cfg.dtype))
            self.ssm_pool = pc.SSMSlotPool(max_seqs)
        else:
            self.ssm_cache = None
            self.ssm_pool = None
        # the control plane checks this before attaching a radix cache
        self.supports_prefix_cache = self.n_ssm == 0
        # reserve the last block as the scratch target for idle slots
        self.allocator = pc.BlockAllocator(n_blocks - 1)
        self.trash_block = n_blocks - 1
        self.state = pc.init_paged_cache(
            cfg, n_blocks=n_blocks, block_size=block_size,
            max_seqs=max_seqs, max_blocks_per_seq=max_blocks_per_seq,
            dtype=jnp.dtype(cfg.dtype))
        # idle slots write into the scratch block
        bt = np.full((max_seqs, max_blocks_per_seq), -1, np.int32)
        bt[:, 0] = self.trash_block
        self.state = dataclasses.replace(
            self.state, block_tables=jnp.asarray(bt))
        # host mirrors of block_tables/seq_lens: all decode-path
        # bookkeeping (capacity, CoW, release, headroom) reads these, so
        # the hot loop never blocks on a device readback. Refreshed from
        # the device after admission/prefill (_sync_mirrors), updated
        # in-place at horizon boundaries.
        self._tables = bt
        self._lens = np.zeros((max_seqs,), np.int32)
        self.slots: Dict[int, Optional[Request]] = {
            i: None for i in range(max_seqs)}
        self._pending: List[Request] = []
        self._next_logits = jnp.zeros((max_seqs, cfg.vocab_size),
                                      jnp.float32)
        # weight version of the params that produced each slot's
        # _next_logits row — the stamp for the *next* sampled token
        self._logits_version: List[int] = [0] * max_seqs
        self._rid = 0
        # decode-path telemetry (ServingMetrics folds these into
        # StepRecord.serving): blocking device->host drains, compiled
        # decode launches, and tokens emitted.
        self.host_syncs = 0
        self.decode_launches = 0
        self.tokens_emitted = 0
        self.last_emitted = 0
        # prefill-lane telemetry: chunk launches, prompt tokens computed
        # through the chunk path, and distinct compile shapes seen (the
        # cache-miss counter the bucket-ladder tests pin)
        self.prefill_launches = 0
        self.prefill_chunk_tokens = 0
        self.prefill_compiles = 0
        self._prefill_shapes: set = set()

    # ------------------------------------------------------------- requests
    def submit(self, prompt_ids, max_new: int = 16, *, priority: int = 0,
               submit_version: int = 0) -> int:
        self._rid += 1
        self._pending.append(Request(self._rid, np.asarray(prompt_ids),
                                     max_new, priority=priority,
                                     submit_version=submit_version))
        return self._rid

    def _cache_plan(self, prompt) -> tuple:
        """(n_blocks, n_tokens) the radix cache will actually serve.

        In dense mode, returns (0, 0) when the match is too small to pay
        off: the legacy cached-suffix path costs one full-width decode
        step per remaining prompt token, so a tiny match on a long prompt
        would be far slower than one dense prefill. The chunked lane
        replays a suffix in ceil(len/C) launches, so any match pays.
        """
        if self.prefix_cache is None:
            return 0, 0
        P = len(prompt)
        n_blocks, n_matched = self.prefix_cache.lookup(prompt,
                                                       max_tokens=P - 1)
        if n_matched == 0:
            return 0, 0
        if self.prefill_mode != "chunked":
            suffix = (P - 1) - n_matched
            if suffix > max(2 * self.state.block_size, (P - 1) // 2):
                return 0, 0
        return n_blocks, n_matched

    def blocks_needed(self, prompt, max_new: int) -> int:
        """Fresh blocks a request needs, given current prefix-cache state.

        Reserves headroom for the copy-on-write forks a cached partial
        block can trigger (one for a matched shared tail, one for this
        prompt's own tail once the cache holds a reference to it).
        """
        P = len(prompt)
        bs = self.state.block_size
        total = -(-(P + max_new) // bs)
        if self.prefix_cache is None:
            return total
        n_blocks, n_matched = self._cache_plan(prompt)
        spare = (1 if n_matched % bs else 0) + (1 if P % bs else 0)
        return total - n_blocks + spare

    def _reclaim_headroom(self, n: int = 1) -> None:
        """Evict cache-only blocks so a decode-time alloc (capacity growth
        or CoW fork) cannot OOM while reclaimable blocks exist."""
        if self.prefix_cache is not None and self.allocator.n_free < n:
            self.prefix_cache.evict(n - self.allocator.n_free)

    def decode_block_shortfall(self) -> int:
        """Blocks the next decode launch would need beyond what the pool
        can supply (free + cache-evictable). Mirrors ``_prepare_decode``'s
        need computation — unmapped blocks in each decode-ready slot's
        write range plus a CoW fork for a radix-shared first block — so
        the control plane can *shed* work before the allocator hard-OOMs
        mid-fork (which would desync the host mirrors). 0 when safe.
        """
        bs = self.state.block_size
        mb = self.state.max_blocks
        H = max(self.decode_horizon, 1)
        need = 0
        for slot in self.decode_ready_slots():
            r = self.slots[slot]
            n = min(H, r.max_new - len(r.generated))
            if n <= 0:
                continue
            first, last = pc.write_range(int(self._lens[slot]), n, bs, mb)
            need += int(np.sum(self._tables[slot, first: last + 1] < 0))
            blk = int(self._tables[slot, first])
            if blk >= 0 and self.allocator.refs(blk) > 1:
                need += 1
        supply = self.allocator.n_free
        if self.prefix_cache is not None:
            supply += self.prefix_cache.evictable_count()
        return max(need - supply, 0)

    def free_slots(self) -> List[int]:
        return [s for s, r in self.slots.items() if r is None]

    def decode_ready_slots(self) -> List[int]:
        """Slots whose prompt K/V is fully resident (decode-lane set)."""
        return [s for s, r in self.slots.items()
                if r is not None and r.prefill_done]

    def prefilling_slots(self) -> List[int]:
        return [s for s, r in self.slots.items()
                if r is not None and not r.prefill_done]

    def _admit(self, params, version: int = 0) -> None:
        for slot in self.free_slots():
            if not self._pending:
                break
            nxt = self._pending[0]
            if self.blocks_needed(nxt.prompt, nxt.max_new) \
                    > self.allocator.n_free:
                break
            self._pending.pop(0)
            self.admit_request(params, slot, nxt, version=version)

    def admit_request(self, params, slot: int, req: Request,
                      version: int = 0, *, prefill: bool = True) -> None:
        """Place ``req`` into ``slot`` (control-plane entry).

        ``prefill=True`` (the legacy contract) leaves the slot fully
        prefilled on return — inline for dense mode, by draining the
        chunk lane for chunked mode. The control plane passes
        ``prefill=False`` and streams chunks through ``prefill_step``
        under its per-boundary budget instead, so a long prompt never
        blocks the decode lane for its whole prefill.
        """
        assert self.slots[slot] is None, f"slot {slot} occupied"
        if self.prefill_mode == "dense":
            self.slots[slot] = req
            self._prefill_into(params, slot, req, version=version)
            req.prefill_pos = len(req.prompt)
            self._sync_mirrors()
            return
        self.start_prefill(slot, req, version=version)
        if prefill:
            while not req.prefill_done:
                self.prefill_step(params, version=version, max_chunks=1)

    def start_prefill(self, slot: int, req: Request,
                      version: int = 0) -> None:
        """Map pages for ``req`` (radix prefix included) without running
        any prefill compute; chunk launches stream the rest."""
        assert self.slots[slot] is None, f"slot {slot} occupied"
        self.slots[slot] = req
        P = len(req.prompt)
        matched: List[int] = []
        n_matched = 0
        if self._cache_plan(req.prompt)[1]:
            matched, n_matched = self.prefix_cache.match(req.prompt,
                                                         max_tokens=P - 1)
        if n_matched:
            self.state = pc.map_sequence_prefixed(
                self.state, self.allocator, slot, matched, n_matched,
                P + req.max_new)
        else:
            self.state = pc.map_sequence(self.state, self.allocator, slot,
                                         P + req.max_new)
        req.prefix_hit_tokens = n_matched
        req.prefill_pos = n_matched
        if self.ssm_pool is not None:
            # fresh sequence: map the slot and zero its recurrent state
            self.ssm_pool.map(slot)
            self.ssm_cache = pc.ssm_reset_slots(self.ssm_cache,
                                                np.asarray([slot]))
        self._logits_version[slot] = version
        self._sync_mirrors()

    def prefill_step(self, params, version: int = 0,
                     max_chunks: Optional[int] = None) -> int:
        """Run up to ``max_chunks`` chunk launches over mid-prefill slots
        (all of them when None); returns the number launched."""
        launched = 0
        while max_chunks is None or launched < max_chunks:
            work = self._gather_prefill_work()
            if not work:
                break
            self._prefill_chunk_launch(params, work, version)
            launched += 1
        return launched

    def _gather_prefill_work(self) -> List[tuple]:
        """Pack pending prompt tokens into one chunk: [(slot, start, n)].

        Shortest-remaining-first, so short prompts reach their first
        token fast even while a long prompt is streaming; the long
        prompt takes whatever chunk capacity is left each launch, so it
        still progresses every boundary.

        SSM/hybrid stacks cannot pack segments into one row stream (the
        SSD scan is recurrent per sequence), so each prefilling slot owns
        a batch row instead and advances by up to a full chunk per
        launch.
        """
        if self.n_ssm:
            return [(s, self.slots[s].prefill_pos,
                     min(len(self.slots[s].prompt)
                         - self.slots[s].prefill_pos, self.prefill_chunk))
                    for s in sorted(self.prefilling_slots())]
        order = sorted(
            self.prefilling_slots(),
            key=lambda s: (len(self.slots[s].prompt)
                           - self.slots[s].prefill_pos, s))
        work: List[tuple] = []
        used = 0
        for slot in order:
            r = self.slots[slot]
            take = min(len(r.prompt) - r.prefill_pos,
                       self.prefill_chunk - used)
            if take <= 0:
                break
            work.append((slot, r.prefill_pos, take))
            used += take
        return work

    def _chunk_bucket(self, n: int) -> int:
        """Smallest ladder bucket holding ``n`` tokens (n <= chunk)."""
        for b in self._chunk_buckets:
            if n <= b:
                return b
        return self.prefill_chunk

    def _dense_bucket(self, n: int) -> int:
        """Pad width for a dense whole-sequence prefill: the chunk ladder
        below ``prefill_chunk``, whole chunks above it."""
        if n <= self.prefill_chunk:
            return self._chunk_bucket(n)
        return -(-n // self.prefill_chunk) * self.prefill_chunk

    def _note_compile(self, shape: tuple) -> None:
        if shape not in self._prefill_shapes:
            self._prefill_shapes.add(shape)
            self.prefill_compiles += 1

    def _prefill_chunk_launch(self, params, work: List[tuple],
                              version: int) -> None:
        """One segment-packed chunk launch over ``[(slot, start, n)]``."""
        if self.n_ssm:
            self._multiarch_prefill_launch(params, work, version)
            return
        n_rows = sum(n for _, _, n in work)
        bucket = self._chunk_bucket(n_rows)
        tokens = np.full((bucket,), tok.PAD, np.int32)
        seg = np.full((bucket,), -1, np.int32)
        pos = np.zeros((bucket,), np.int32)
        kv_lens = np.zeros((self.max_seqs,), np.int32)
        last_rows = np.zeros((self.max_seqs,), np.int32)
        complete = np.zeros((self.max_seqs,), bool)
        seg_counts = np.zeros((self.max_seqs,), np.int32)
        row = 0
        for slot, start, n in work:
            r = self.slots[slot]
            tokens[row: row + n] = r.prompt[start: start + n]
            seg[row: row + n] = slot
            pos[row: row + n] = np.arange(start, start + n)
            kv_lens[slot] = start + n
            seg_counts[slot] = n
            if start + n == len(r.prompt):
                complete[slot] = True
                last_rows[slot] = row + n - 1
            row += n
        with span("prefill_chunk", rows=n_rows, bucket=bucket,
                  segments=len(work), version=version,
                  completed=int(complete.sum())):
            # fork the (possibly radix-shared) first write block of each
            # slot, pre-map the rest, push the table mirror once
            self._prepare_decode({slot: n for slot, _, n in work})
            next_logits, pool_k, pool_v, seq_lens = _paged_prefill_chunk(
                params, self.cfg, self.state.pool_k, self.state.pool_v,
                self.state.block_tables, self.state.seq_lens,
                self._next_logits, jnp.asarray(tokens), jnp.asarray(seg),
                jnp.asarray(pos), jnp.asarray(kv_lens),
                jnp.asarray(last_rows), jnp.asarray(complete),
                jnp.asarray(seg_counts), trash_block=self.trash_block)
        self._next_logits = next_logits
        self.state = dataclasses.replace(self.state, pool_k=pool_k,
                                         pool_v=pool_v, seq_lens=seq_lens)
        self.prefill_launches += 1
        self.prefill_chunk_tokens += n_rows
        self._note_compile(("chunk", bucket))
        bs = self.state.block_size
        for slot, start, n in work:
            r = self.slots[slot]
            r.prefill_pos = start + n
            self._lens[slot] += n
            if r.prefill_done:
                self._logits_version[slot] = version
                if self.prefix_cache is not None:
                    n_blocks = -(-len(r.prompt) // bs)
                    self.prefix_cache.insert(
                        r.prompt,
                        [int(b) for b in self._tables[slot][:n_blocks]])

    def _multiarch_prefill_launch(self, params, work: List[tuple],
                                  version: int) -> None:
        """One batched SSM/hybrid prefill launch over ``[(slot, start,
        n)]`` — each slot owns a row of a [max_seqs, bucket] batch."""
        nmax = max(n for _, _, n in work)
        bucket = self._chunk_bucket(nmax)
        S = self.max_seqs
        tokens = np.full((S, bucket), tok.PAD, np.int32)
        starts = np.zeros((S,), np.int32)
        counts = np.zeros((S,), np.int32)
        complete = np.zeros((S,), bool)
        for slot, start, n in work:
            r = self.slots[slot]
            tokens[slot, :n] = r.prompt[start: start + n]
            starts[slot] = start
            counts[slot] = n
            complete[slot] = (start + n == len(r.prompt))
        with span("prefill_chunk", rows=int(counts.sum()), bucket=bucket,
                  segments=len(work), version=version,
                  completed=int(complete.sum())):
            self._prepare_decode({slot: n for slot, _, n in work})
            (next_logits, pool_k, pool_v, conv, state, seq_lens) = \
                _multiarch_prefill_chunk(
                    params, self.cfg, self.state.pool_k,
                    self.state.pool_v, self.ssm_cache.conv,
                    self.ssm_cache.state, self.state.block_tables,
                    self.state.seq_lens, self._next_logits,
                    jnp.asarray(tokens), jnp.asarray(starts),
                    jnp.asarray(counts), jnp.asarray(complete),
                    trash_block=self.trash_block)
        self._next_logits = next_logits
        self.state = dataclasses.replace(self.state, pool_k=pool_k,
                                         pool_v=pool_v, seq_lens=seq_lens)
        self.ssm_cache = pc.SSMStateCache(conv=conv, state=state)
        self.prefill_launches += 1
        self.prefill_chunk_tokens += int(counts.sum())
        self._note_compile(("machunk", bucket))
        for slot, start, n in work:
            r = self.slots[slot]
            r.prefill_pos = start + n
            self._lens[slot] += n
            if r.prefill_done:
                self._logits_version[slot] = version

    def _sync_mirrors(self) -> None:
        """Refresh host mirrors from the device (admission/prefill only —
        the decode loop itself never reads device state back)."""
        self._tables = np.array(self.state.block_tables)
        self._lens = np.array(self.state.seq_lens)

    def _prefill_into(self, params, slot: int, req: Request,
                      version: int = 0) -> None:
        with span("prefill", slot=slot, prompt_tokens=len(req.prompt),
                  version=version) as sp:
            self._prefill_into_impl(params, slot, req, version)
            sp.set(prefix_hit_tokens=req.prefix_hit_tokens)

    def _prefill_into_impl(self, params, slot: int, req: Request,
                           version: int = 0) -> None:
        P = len(req.prompt)
        bs = self.state.block_size
        matched: List[int] = []
        n_matched = 0
        if self._cache_plan(req.prompt)[1]:
            # cap at P-1: the last prompt token always runs through the
            # decode step so the slot has next-token logits to sample from
            matched, n_matched = self.prefix_cache.match(req.prompt,
                                                         max_tokens=P - 1)
        if n_matched:
            self.state = pc.map_sequence_prefixed(
                self.state, self.allocator, slot, matched, n_matched,
                P + req.max_new)
            self._prefill_suffix(params, slot, req.prompt[n_matched:])
        else:
            self.state = pc.map_sequence(self.state, self.allocator, slot,
                                         P + req.max_new)
            # pad to the chunk-bucket ladder (compile per bucket, not per
            # prompt length) and scatter all K/V into pages in one jitted
            # launch — no host block-copy loop
            Pb = self._dense_bucket(P)
            toks = np.full((1, Pb), tok.PAD, np.int32)
            toks[0, :P] = req.prompt
            logits, pool_k, pool_v = _dense_prefill(
                params, self.cfg, self.state.pool_k, self.state.pool_v,
                jnp.asarray(toks), jnp.asarray(P, jnp.int32),
                self.state.block_tables[slot],
                trash_block=self.trash_block)
            self._note_compile(("dense", Pb))
            self.state = dataclasses.replace(
                self.state, pool_k=pool_k, pool_v=pool_v,
                seq_lens=self.state.seq_lens.at[slot].set(P))
            self._next_logits = self._next_logits.at[slot].set(logits)
        req.prefix_hit_tokens = n_matched
        if self.prefix_cache is not None:
            table = np.asarray(self.state.block_tables[slot])
            n_prompt_blocks = -(-P // bs)
            self.prefix_cache.insert(
                req.prompt, [int(b) for b in table[:n_prompt_blocks]])
        self._logits_version[slot] = version

    def _prefill_suffix(self, params, slot: int, suffix) -> None:
        """Prefill the uncached prompt tail through the paged decode path.

        The cached prefix KV is already resident in this slot's blocks, so
        each remaining prompt token is one decode step that attends over
        the shared pages. Every *other* slot is pointed at the scratch
        block for the duration so its pool pages and sampled logits are
        untouched.
        """
        for t in suffix:
            self._reclaim_headroom(2)  # capacity growth + possible fork
            self.state = pc.ensure_capacity(self.state, self.allocator,
                                            slot)
            self.state = pc.ensure_writable(self.state, self.allocator,
                                            slot)
            bt = np.full((self.max_seqs, self.state.max_blocks), -1,
                         np.int32)
            bt[:, 0] = self.trash_block
            bt[slot] = np.asarray(self.state.block_tables[slot])
            lens = np.zeros((self.max_seqs,), np.int32)
            lens[slot] = int(self.state.seq_lens[slot])
            tokens = np.full((self.max_seqs,), int(t), np.int32)
            one_hot = np.zeros((self.max_seqs,), bool)
            one_hot[slot] = True
            logits, pool_k, pool_v = _paged_decode_step(
                params, self.cfg, self.state.pool_k, self.state.pool_v,
                jnp.asarray(bt), jnp.asarray(lens), jnp.asarray(tokens),
                jnp.asarray(one_hot), trash_block=self.trash_block)
            self.state = dataclasses.replace(
                self.state, pool_k=pool_k, pool_v=pool_v,
                seq_lens=self.state.seq_lens.at[slot].add(1))
            self._next_logits = self._next_logits.at[slot].set(logits[slot])

    # ----------------------------------------------------------------- step
    def _prepare_decode(self, slot_tokens: Dict[int, int]) -> None:
        """Horizon-boundary bookkeeping, entirely on the host mirrors.

        Reclaims allocator headroom for everything the next
        ``slot_tokens[slot]`` writes of each slot may need, forks the
        first write block of any slot resuming on radix-cache-shared
        pages (only that block can be shared: later blocks in the write
        range are always freshly allocated), and pre-maps every missing
        block — then pushes the block-table mirror to the device at most
        once. No device readback anywhere.
        """
        bs = self.state.block_size
        mb = self.state.max_blocks
        need = 0
        for slot, n in slot_tokens.items():
            if n <= 0:
                continue
            first, last = pc.write_range(int(self._lens[slot]), n, bs, mb)
            need += int(np.sum(self._tables[slot, first: last + 1] < 0))
            blk = int(self._tables[slot, first])
            if blk >= 0 and self.allocator.refs(blk) > 1:
                need += 1  # CoW fork below
        self._reclaim_headroom(need)
        dirty = False
        for slot, n in slot_tokens.items():
            if n <= 0:
                continue
            first = int(self._lens[slot]) // bs
            blk = int(self._tables[slot, first])
            if blk >= 0 and self.allocator.refs(blk) > 1:
                self.state, new = pc.fork_block(self.state, self.allocator,
                                                blk)
                self._tables[slot, first] = new
                dirty = True
        dirty |= pc.alloc_horizon_blocks(self.allocator, self._tables,
                                         self._lens, slot_tokens, bs)
        if __debug__:
            # every active slot's upcoming write positions must be mapped:
            # an unmapped write is silently routed to the scratch block by
            # write_token/_decode_tower, so catch the bookkeeping bug here
            for slot, n in slot_tokens.items():
                if n <= 0:
                    continue
                first, last = pc.write_range(int(self._lens[slot]), n, bs,
                                             mb)
                tab = self._tables[slot, first: last + 1]
                assert (tab >= 0).all(), (
                    f"slot {slot}: unmapped write blocks {tab.tolist()} "
                    f"in range [{first}, {last}]")
        if dirty:
            self.state = dataclasses.replace(
                self.state, block_tables=jnp.asarray(self._tables))

    def step(self, params, key, version: int = 0) -> List[Request]:
        """One decode step for every active slot; returns finished reqs.

        ``params``/``version`` may change between calls (interruptible
        generation): in-flight sequences keep their paged KV and resume
        under the new weights, and every sampled token is stamped with the
        version of the params that produced its logits.

        This is the per-token fallback path (``decode_horizon=1``): it
        pays one sampled-token drain per token. ``step_horizon`` amortizes
        that over a whole compiled horizon.
        """
        with span("decode_step", version=version) as sp:
            finished = self._step_impl(params, key, version)
            sp.set(tokens=self.last_emitted, finished=len(finished))
        return finished

    def _step_impl(self, params, key, version: int = 0) -> List[Request]:
        # mid-prefill slots are not decode-ready: they have no sampled
        # logits yet and their pages (possibly radix-shared) sit at the
        # write cursor — they stay masked out of the launch entirely
        active = self.decode_ready_slots()
        if not active:
            return []
        if self.greedy:
            tokens, logps = greedy_token(self._next_logits)
        else:
            tokens, logps = sample_token(self._next_logits, key,
                                         temperature=self.rl.temperature,
                                         top_p=self.rl.top_p)
        tokens = np.asarray(tokens)
        logps = np.asarray(logps)
        self.host_syncs += 2  # token + logp drains, one per token decoded
        self.decode_launches += 1
        self._prepare_decode({slot: 1 for slot in active})
        active_arr = np.zeros((self.max_seqs,), bool)
        active_arr[active] = True
        if self.n_ssm:
            logits, pool_k, pool_v, conv, state = _multiarch_decode_step(
                params, self.cfg, self.state.pool_k, self.state.pool_v,
                self.ssm_cache.conv, self.ssm_cache.state,
                self.state.block_tables, self.state.seq_lens,
                jnp.asarray(tokens), jnp.asarray(active_arr),
                trash_block=self.trash_block)
            self.ssm_cache = pc.SSMStateCache(conv=conv, state=state)
        else:
            logits, pool_k, pool_v = _paged_decode_step(
                params, self.cfg, self.state.pool_k, self.state.pool_v,
                self.state.block_tables, self.state.seq_lens,
                jnp.asarray(tokens), jnp.asarray(active_arr),
                trash_block=self.trash_block)
        # mid-prefill rows of _next_logits become garbage here, which is
        # fine: they are only ever read after their completion chunk
        # overwrites them (completion always precedes decode-readiness)
        self._next_logits = logits
        # bump all active lens with a single vectorized update
        self.state = dataclasses.replace(
            self.state, pool_k=pool_k, pool_v=pool_v,
            seq_lens=self.state.seq_lens
            + jnp.asarray(active_arr, jnp.int32))
        self._lens += active_arr
        self.last_emitted = len(active)
        self.tokens_emitted += len(active)
        finished: List[Request] = []
        for slot in active:
            req = self.slots[slot]
            t = int(tokens[slot])
            req.generated.append(t)
            req.gen_logp.append(float(logps[slot]))
            req.token_versions.append(int(self._logits_version[slot]))
            if t == tok.EOS or len(req.generated) >= req.max_new:
                req.done = True
                finished.append(req)
                self.release_slot(slot)
        # logits computed this step came from `params`
        for slot in active:
            if self.slots.get(slot) is not None:
                self._logits_version[slot] = version
        return finished

    def step_horizon(self, params, key, version: int = 0) -> List[Request]:
        """Decode up to ``decode_horizon`` tokens per active slot in one
        compiled launch; returns finished reqs.

        Sampling, paged KV appends, EOS done-masking, and length bumps
        all run inside the jitted scan; tokens/logps/masks drain to the
        host as ONE packed transfer per horizon (vs ~2 per token for
        ``step``). Host bookkeeping — capacity, CoW, slot release, stamps
        — happens only here, at the boundary. Token 0 of the horizon is
        stamped with the version that produced the carried-in logits;
        later tokens with ``version`` (the params decoding this horizon),
        exactly as ``horizon`` per-token steps would stamp them.
        """
        with span("decode_horizon", horizon=self.decode_horizon,
                  version=version) as sp:
            finished = self._step_horizon_impl(params, key, version)
            sp.set(tokens=self.last_emitted, finished=len(finished))
        return finished

    def _step_horizon_impl(self, params, key,
                           version: int = 0) -> List[Request]:
        H = self.decode_horizon
        # decode lane only: mid-prefill slots keep budget 0 (the scan's
        # emit mask already parks zero-budget writes on scratch), and
        # their garbage _next_logits rows are rewritten at completion
        active = {s: self.slots[s] for s in self.decode_ready_slots()}
        if not active:
            return []
        budget = np.zeros((self.max_seqs,), np.int32)
        for s, r in active.items():
            budget[s] = min(H, r.max_new - len(r.generated))
        self._prepare_decode({s: int(budget[s]) for s in active})
        with annotate("decode_horizon"):
            if self.n_ssm:
                (packed, pool_k, pool_v, conv, state, lens, logits) = \
                    _multiarch_decode_horizon(
                        params, self.cfg, self.state.pool_k,
                        self.state.pool_v, self.ssm_cache.conv,
                        self.ssm_cache.state, self.state.block_tables,
                        self.state.seq_lens, self._next_logits,
                        jnp.asarray(budget), key,
                        trash_block=self.trash_block, horizon=H,
                        temperature=self.rl.temperature,
                        top_p=self.rl.top_p, greedy=self.greedy)
                self.ssm_cache = pc.SSMStateCache(conv=conv, state=state)
            else:
                packed, pool_k, pool_v, lens, logits = \
                    _paged_decode_horizon(
                        params, self.cfg, self.state.pool_k,
                        self.state.pool_v, self.state.block_tables,
                        self.state.seq_lens, self._next_logits,
                        jnp.asarray(budget), key,
                        trash_block=self.trash_block, horizon=H,
                        temperature=self.rl.temperature,
                        top_p=self.rl.top_p, greedy=self.greedy)
        self.state = dataclasses.replace(self.state, pool_k=pool_k,
                                         pool_v=pool_v, seq_lens=lens)
        self._next_logits = logits
        drained = np.asarray(packed)  # the one blocking drain per horizon
        self.host_syncs += 1
        self.decode_launches += 1
        tokens = drained[0].astype(np.int64)
        logps, masks = drained[1], drained[2]
        # emissions are a prefix per slot (done is sticky), so the mask sum
        # is the emitted count — no per-token host loop
        n_emit = masks.sum(axis=0).astype(np.int64)
        finished: List[Request] = []
        released: List[int] = []
        for s, r in active.items():
            n = int(n_emit[s])
            if n:
                r.generated.extend(tokens[:n, s].tolist())
                r.gen_logp.extend(logps[:n, s].tolist())
                r.token_versions.append(int(self._logits_version[s]))
                r.token_versions.extend([version] * (n - 1))
            self._lens[s] += n
            if (n and r.generated[-1] == tok.EOS) \
                    or len(r.generated) >= r.max_new:
                r.done = True
                finished.append(r)
                released.append(s)
            else:
                self._logits_version[s] = version
        if released:
            # free all finished slots' pages with ONE device update (vs a
            # per-slot release_slot dispatch pair)
            for s in released:
                self._release_host(s)
            idx = jnp.asarray(np.asarray(released, np.int32))
            self.state = dataclasses.replace(
                self.state,
                block_tables=self.state.block_tables.at[idx].set(
                    jnp.asarray(self._tables[released])),
                seq_lens=self.state.seq_lens.at[idx].set(0))
        self.last_emitted = int(n_emit.sum())
        self.tokens_emitted += self.last_emitted
        return finished

    def _release_host(self, slot: int) -> None:
        """Host half of a slot release: return pages to the allocator and
        reset the mirrors + slot bookkeeping (callers push to device)."""
        self.allocator.release(
            [int(b) for b in self._tables[slot] if b >= 0])
        if self.ssm_pool is not None:
            # stale recurrent state stays in the pool; the next map of
            # this slot zeroes it (ssm_reset_slots in start_prefill)
            self.ssm_pool.release(slot)
        self._tables[slot] = -1
        self._tables[slot, 0] = self.trash_block
        self._lens[slot] = 0
        self.slots[slot] = None
        self._logits_version[slot] = 0

    def release_slot(self, slot: int) -> Optional[Request]:
        """Free a slot's pages (finish or preemption) and park it.

        Works off the host block-table mirror — no device readback — and
        parks the idle slot back on the scratch block.
        """
        req = self.slots[slot]
        self._release_host(slot)
        self.state = dataclasses.replace(
            self.state,
            block_tables=self.state.block_tables.at[slot].set(
                jnp.asarray(self._tables[slot])),
            seq_lens=self.state.seq_lens.at[slot].set(0))
        return req

    # ------------------------------------------------------------------ run
    def run(self, params, key, max_steps: int = 10_000) -> List[Request]:
        """Drive admission + decode to completion. With ``decode_horizon``
        > 1 each iteration is a fused horizon (``max_steps`` counts
        launches, not tokens)."""
        done: List[Request] = []
        steps = 0
        while (self._pending or any(r is not None
                                    for r in self.slots.values())):
            self._admit(params)
            if not any(r is not None for r in self.slots.values()):
                break
            key, sub = jax.random.split(key)
            if self.decode_horizon > 1:
                done.extend(self.step_horizon(params, sub))
            else:
                done.extend(self.step(params, sub))
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serving loop exceeded max_steps")
        return done
