"""Top-k MoE (+ shared experts), drop-free.

Two dispatch paths:

* ``moe_apply_local`` — the layer over the experts held here
  (``MoEConfig.held``): every real token is routed over all of the
  router's outputs, the assignments to held experts are sorted by expert
  and run as grouped matmuls (``grouped_matmul``: the megablox kernel on
  one TPU chip, ``jax.lax.ragged_dot`` elsewhere) whose work follows the
  rows routed here, and the results are scattered back weighted. The
  sorted buffer takes the smaller of two static sizes (``buffer_rungs``)
  that holds the call's routed rows: the held experts' balanced share,
  or the worst case, ``T * min(top_k, held)`` rows, so no token is
  dropped. The experts held elsewhere are left out, so a share of the
  experts gives its part of the layer's result, as one rank of expert
  parallelism does before its exchange.
* ``moe_apply_ep`` — explicit expert-parallel shard_map: tokens are
  seq-sharded over the "model" axis, routed pairs are bucketed by
  destination rank and exchanged with ``jax.lax.all_to_all``, experts
  compute locally, and a reverse all-to-all brings results home. Its
  buckets are bounded by ``capacity_factor``.

``moe_apply`` picks EP when the active ShardingEnv requests it, the layer
holds every expert and the shapes allow (seq at least the model axis),
else the local path. Both return ``(y, aux, stats)``: ``stats`` counts the
assignments computed (``moe_local_rows``), the most any one expert got
(``moe_expert_rows_max``) and the rows of the buffer the experts ran on
(``moe_buffer_rows``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed.sharding import current_env
from repro.models.layers import swiglu, swiglu_spec
from repro.models.params import ParamSpec

STATS = ("moe_local_rows", "moe_expert_rows_max", "moe_buffer_rows")
# megablox row tile, and the contraction and output tile, capped at the
# widths (the kernel masks a ragged last tile; its backward swaps the two)
GMM_ROWS, GMM_COLS = 512, 1024


def moe_spec(cfg: ModelConfig) -> Dict[str, Any]:
    m = cfg.moe
    d, n = cfg.d_model, m.num_held
    spec: Dict[str, Any] = {
        "router": ParamSpec((d, m.num_experts), ("embed", "experts")),
        "w_gate": ParamSpec((n, d, m.d_ff_expert),
                            ("experts", "embed", "expert_ff")),
        "w_up": ParamSpec((n, d, m.d_ff_expert),
                          ("experts", "embed", "expert_ff")),
        "w_down": ParamSpec((n, m.d_ff_expert, d),
                            ("experts", "expert_ff", "embed")),
    }
    if m.num_shared_experts > 0:
        spec["shared"] = swiglu_spec(d, m.num_shared_experts * m.d_ff_expert)
    return spec


def route(router_w: jax.Array, x_flat: jax.Array, m: MoEConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax over every router output, greedy top-k. Returns (probs
    [T,E] f32, top-k weights [T,k], top-k idx [T,k]); the weights are
    renormalised to sum 1 when ``norm_topk_prob``, then scaled by
    ``routed_scaling_factor``."""
    logits = jnp.einsum("td,de->te", x_flat, router_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    if m.routed_scaling_factor != 1.0:
        top_w = top_w * m.routed_scaling_factor
    return probs, top_w, top_i


def load_balance_loss(probs: jax.Array, top_i: jax.Array, m: MoEConfig,
                      valid: Optional[jax.Array] = None) -> jax.Array:
    """Switch-style aux loss over the real tokens (``valid`` [T], all
    when None): E * sum_e f_e * P_e, with f_e the share of the top-k
    assignments that go to expert e and P_e its mean probability."""
    T = probs.shape[0]
    w = (jnp.ones((T,), jnp.float32) if valid is None
         else valid.astype(jnp.float32))
    n = jnp.maximum(jnp.sum(w), 1.0)
    counts = jnp.zeros((m.num_experts,), jnp.float32).at[
        top_i.reshape(-1)].add(jnp.repeat(w, m.top_k))
    f = counts / (n * m.top_k)
    p = jnp.sum(probs * w[:, None], axis=0) / n
    return m.num_experts * jnp.sum(f * p)


def _use_kernel() -> bool:
    """The Pallas grouped matmul runs on one TPU chip; XLA partitions its
    own ragged dot over a mesh, not a Pallas call."""
    env = current_env()
    return (jax.default_backend() == "tpu"
            and (env is None or env.mesh.size == 1))


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array
                   ) -> jax.Array:
    """lhs [M, k] with its rows sorted by group, rhs [G, k, n], sizes [G]
    -> [M, n]; rows past ``sum(sizes)`` are undefined. On one TPU chip the
    megablox kernel, which visits only the row tiles its groups cover
    (XLA's ragged dot took 2.8x as long over the train cell's expert layer
    on a v5e, and its ops lose the caller's scopes); elsewhere
    ``jax.lax.ragged_dot``."""
    if not _use_kernel():
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    m, k = lhs.shape
    t = min(GMM_COLS, k, rhs.shape[2])
    out = gmm(jnp.pad(lhs, ((0, -m % GMM_ROWS), (0, 0))), rhs, sizes,
              preferred_element_type=lhs.dtype, tiling=(GMM_ROWS, t, t))
    return out[:m]


def moe_apply(params, x: jax.Array, cfg: ModelConfig,
              valid: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, d] -> (y, aux_loss, stats); ``valid`` [B, S] marks the
    real tokens (padding is routed nowhere). Chooses EP shard_map vs the
    local drop-free path."""
    env = current_env()
    m = cfg.moe
    if (env is not None and getattr(env, "ep_shard_map", False)
            and "model" in env.mesh.axis_names
            and m.num_held == m.num_experts):
        n_ranks = env.mesh.shape["model"]
        if m.num_experts % n_ranks == 0 and x.shape[1] >= n_ranks:
            return moe_apply_ep(params, x, cfg, env)
    return moe_apply_local(params, x, cfg, valid)


def buffer_rungs(T: int, m: MoEConfig) -> Tuple[int, ...]:
    """The dispatch buffer's sizes for ``T`` tokens, smallest first: the
    held experts' balanced share of the assignments, ``ceil(T * top_k *
    held / num_experts)``, and the worst case ``T * min(top_k, held)``,
    each rounded up to ``GMM_ROWS`` (so the grouped matmul pads nothing).
    A layer holding every expert has its share at the worst case: one
    rung."""
    def up(r: int) -> int:
        return -(-r // GMM_ROWS) * GMM_ROWS

    worst = up(T * min(m.top_k, m.num_held))
    share = up(-(-T * m.top_k * m.num_held // m.num_experts))
    return tuple(sorted({min(share, worst), worst}))


def _dispatch(xf, top_w, order, sizes, routed, w_gate, w_up, w_down, *,
              rows: int, k: int) -> jax.Array:
    """The routed assignments through the held experts on a buffer of
    ``rows`` rows (``order``'s first, sorted by expert; those past
    ``routed`` are masked), weighted and added back to [T, d]."""
    T, d = xf.shape
    with jax.named_scope("moe_route"):
        order = order[:rows]
        live = jnp.arange(rows) < routed
        tok = order // k
        xs = jnp.where(live[:, None], jnp.take(xf, tok, axis=0), 0)
        w = jnp.where(live, top_w[order], 0.0).astype(xf.dtype)

    with jax.named_scope("moe_experts"):
        g = grouped_matmul(xs, w_gate, sizes)
        u = grouped_matmul(xs, w_up, sizes)
        ys = grouped_matmul(jax.nn.silu(g) * u, w_down, sizes)

    with jax.named_scope("moe_combine"):
        # rows past the routed ones are never computed: mask, not multiply
        ys = jnp.where(live[:, None], ys, 0) * w[:, None]
        return jnp.zeros((T, d), xf.dtype).at[tok].add(ys.astype(xf.dtype))


def moe_apply_local(params, x: jax.Array, cfg: ModelConfig,
                    valid: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """The layer over the experts held here, drop-free. x: [B, S, d].

    The assignments to held experts, sorted by expert, fill a buffer of
    the smaller of ``buffer_rungs(B * S, cfg.moe)`` that holds the
    call's routed rows, picked by a ``lax.switch`` with one branch a rung,
    each branch checkpointed (its backward recomputes its forward, as the
    decoder's checkpointed layer would). The worst case is always a rung,
    so no assignment is dropped; every rung computes the same rows in the
    same order. ``stats`` counts the rung's rows (``moe_buffer_rows``)
    beside the routed ones (``moe_local_rows``)."""
    m = cfg.moe
    B, S, d = x.shape
    T, k = B * S, m.top_k
    first, n_here = m.held_range
    rungs = buffer_rungs(T, m)
    xf = x.reshape(T, d)
    ok = None if valid is None else valid.reshape(T)

    with jax.named_scope("moe_route"):
        probs, top_w, top_i = route(params["router"], xf, m)
        aux = load_balance_loss(probs, top_i, m, ok) * m.router_aux_weight
        local = top_i - first
        mine = (local >= 0) & (local < n_here)
        if ok is not None:
            mine &= ok[:, None]
        # assignments sorted by local expert, the rest (key n_here) last;
        # the largest rung's rounding pads past the T * k of them with
        # rows that are never live
        key = jnp.where(mine, local, n_here).reshape(T * k)
        order = jnp.pad(jnp.argsort(key, stable=True),
                        (0, max(rungs[-1] - T * k, 0)))
        sizes = jnp.zeros((n_here + 1,), jnp.int32).at[key].add(1)[:n_here]
        routed = jnp.sum(sizes)
        # the smallest rung that holds every routed row
        pick = jnp.sum(routed > jnp.asarray(rungs[:-1], jnp.int32))

    operands = (xf, top_w.reshape(T * k), order, sizes, routed,
                params["w_gate"], params["w_up"], params["w_down"])
    if len(rungs) == 1:
        y = _dispatch(*operands, rows=rungs[0], k=k)
    else:
        # each branch checkpointed, so that its residuals are its operands,
        # which the branches share: a switch's backward keeps every
        # branch's own residuals, zero-filled by the branches not taken
        y = jax.lax.switch(pick, [
            jax.checkpoint(functools.partial(_dispatch, rows=r, k=k))
            for r in rungs], *operands)

    if m.num_shared_experts > 0:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(params["shared"], xf)
    stats = {"moe_local_rows": routed,
             "moe_expert_rows_max": jnp.max(sizes),
             "moe_buffer_rows": jnp.asarray(rungs, jnp.int32)[pick]}
    return y.reshape(B, S, d), aux, stats


# ---------------------------------------------------------------- EP path
def _bucket_by(ids: jax.Array, values: jax.Array, n_buckets: int,
               cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort (ids, values) into [n_buckets, cap, ...] with overflow drop.

    Returns (bucketed values, slot index per pair (== n_buckets*cap for
    dropped), sort order) so callers can route auxiliary arrays the same
    way and invert the permutation.
    """
    N = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sid = ids[order]
    starts = jnp.searchsorted(sid, jnp.arange(n_buckets), side="left")
    pos = jnp.arange(N) - starts[sid]
    slot = jnp.where(pos < cap, sid * cap + pos, n_buckets * cap)
    buf = jnp.zeros((n_buckets * cap + 1,) + values.shape[1:],
                    values.dtype).at[slot].set(values[order])
    return buf[:-1].reshape((n_buckets, cap) + values.shape[1:]), slot, order


def _ep_body(x, router_w, w_gate, w_up, w_down, *, m: MoEConfig,
             n_ranks: int, all_axes):
    """Per-device expert-parallel MoE. x: [T_loc, d] (unique local tokens);
    w_*: this rank's expert slab [E/n_ranks, ...]. Returns (y, aux, the
    assignments to each expert, the experts' buffer rows on every device
    together)."""
    T, d = x.shape
    e_per = m.num_experts // n_ranks
    k = m.top_k

    probs, top_w, top_i = route(router_w, x, m)
    aux = load_balance_loss(probs, top_i, m) * m.router_aux_weight
    aux = jax.lax.pmean(aux, all_axes)
    counts = jax.lax.psum(jnp.zeros((m.num_experts,), jnp.int32).at[
        top_i.reshape(-1)].add(1), all_axes)

    N = T * k
    flat_e = top_i.reshape(N)
    flat_w = top_w.reshape(N).astype(x.dtype)
    flat_t = jnp.repeat(jnp.arange(T), k)
    dest = flat_e // e_per

    # first-level bucket: destination rank, with the local-expert id (+1,
    # 0 marks padding) riding along in an int payload
    cap_send = max(int(math.ceil(N / n_ranks * m.capacity_factor)), k)
    send_x, slot, order = _bucket_by(dest, x[flat_t], n_ranks, cap_send)
    eid = ((flat_e % e_per) + 1).astype(jnp.int32)  # 0 == invalid
    send_e = jnp.zeros((n_ranks * cap_send + 1,), jnp.int32
                       ).at[slot].set(eid[order])
    send_e = send_e[:-1].reshape(n_ranks, cap_send)

    recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, "model", 0, 0, tiled=False)

    # second-level bucket: local expert (invalid slots -> trash bucket)
    Rn = n_ranks * cap_send
    rx = recv_x.reshape(Rn, d)
    re = jnp.where(recv_e.reshape(Rn) > 0, recv_e.reshape(Rn) - 1, e_per)
    C2 = max(int(math.ceil(Rn / e_per * m.capacity_factor)), 1)
    xe_full, slot2, order2 = _bucket_by(re, rx, e_per + 1, C2)
    xe = xe_full[:e_per]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)

    # invert second-level bucketing back to the recv layout
    padded2 = jnp.concatenate(
        [ye.reshape(e_per * C2, d),
         jnp.zeros(((e_per + 1) * C2 - e_per * C2 + 1, d), ye.dtype)],
        axis=0)  # trash bucket and overflow row read zeros
    y_sorted = padded2[jnp.minimum(slot2, e_per * C2)]
    y_sorted = jnp.where((slot2 < e_per * C2)[:, None], y_sorted, 0.0)
    inv2 = jnp.argsort(order2, stable=True)
    ry = y_sorted[inv2].astype(x.dtype)  # [Rn, d], recv layout

    # reverse exchange back to the source ranks
    back = jax.lax.all_to_all(ry.reshape(n_ranks, cap_send, d),
                              "model", 0, 0, tiled=False)
    flat_back = jnp.concatenate(
        [back.reshape(n_ranks * cap_send, d),
         jnp.zeros((1, d), back.dtype)], axis=0)
    y_pairs_sorted = flat_back[slot]  # dropped pairs hit the zero row
    inv = jnp.argsort(order, stable=True)
    y_pairs = y_pairs_sorted[inv] * flat_w[:, None]
    y = jnp.zeros((T, d), x.dtype).at[flat_t].add(y_pairs)
    rows = jax.lax.psum(jnp.int32(e_per * C2), all_axes)
    return y, aux, counts, rows


def moe_apply_ep(params, x: jax.Array, cfg: ModelConfig, env
                 ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel MoE via shard_map + all_to_all over 'model'."""
    from jax.experimental.shard_map import shard_map

    m = cfg.moe
    B, S, d = x.shape
    mesh = env.mesh
    n_ranks = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # pad seq to a model-axis multiple (pad tokens only waste a sliver of
    # capacity; their outputs are sliced off below)
    orig_S = S
    S = -(-S // n_ranks) * n_ranks
    if S != orig_S:
        x = jnp.pad(x, ((0, 0), (0, S - orig_S), (0, 0)))

    all_axes = batch_axes + ("model",)

    def body(x_blk, router_w, w_gate, w_up, w_down):
        T = x_blk.shape[0] * x_blk.shape[1]
        y, aux, counts, rows = _ep_body(
            x_blk.reshape(T, d), router_w, w_gate, w_up, w_down, m=m,
            n_ranks=n_ranks, all_axes=all_axes)
        return y.reshape(x_blk.shape), aux, counts, rows

    x_spec = P(batch_axes if batch_axes else None, "model", None)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(), P("model"), P("model"), P("model")),
        out_specs=(x_spec, P(), P(), P()),
        check_rep=False)
    y, aux, counts, rows = mapped(x, params["router"], params["w_gate"],
                                  params["w_up"], params["w_down"])
    if m.num_shared_experts > 0:
        y = y + swiglu(params["shared"], x.reshape(B * S, d)
                       ).reshape(B, S, d)
    if S != orig_S:
        y = y[:, :orig_S]
    stats = {"moe_local_rows": jnp.sum(counts),
             "moe_expert_rows_max": jnp.max(counts), "moe_buffer_rows": rows}
    return y, aux, stats
