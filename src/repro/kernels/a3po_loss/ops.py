"""Dispatch + autodiff wrappers for the fused A-3PO loss.

``a3po_objective`` is the training-path entry point: a ``custom_vjp`` whose
forward pass runs the fused Pallas kernel (interpret mode off-TPU) and whose
backward pass is the analytic elementwise gradient of the clipped surrogate
— no differentiation through ``pallas_call`` is ever needed, and the pure-jnp
``ref.a3po_loss_ref`` serves as the gradient oracle in tests.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import map_batch_shards
from repro.kernels.a3po_loss.kernel import a3po_loss_pallas
from repro.kernels.a3po_loss.ref import a3po_loss_ref


def _run_fused(static, logp, behav_logp, alpha, adv, mask):
    clip_eps, iw_cap, use_kernel, interpret = static
    lead = logp.shape
    flat = lambda x: x.astype(jnp.float32).reshape(-1)  # noqa: E731
    args = (flat(logp), flat(behav_logp), flat(alpha), flat(adv), flat(mask))
    if use_kernel:
        kernel = functools.partial(a3po_loss_pallas, clip_eps=clip_eps,
                                   iw_cap=iw_cap, interpret=interpret)
        # Mosaic kernels are not auto-partitioned: one call per batch shard
        outs = map_batch_shards(kernel, *args, batch_args=(True,) * 5)
    else:
        outs = a3po_loss_ref(*args, clip_eps=clip_eps, iw_cap=iw_cap)
    return tuple(o.reshape(lead) for o in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _a3po_objective(static, logp, behav_logp, alpha, adv, mask):
    return _run_fused(static, logp, behav_logp, alpha, adv, mask)


def _a3po_objective_fwd(static, logp, behav_logp, alpha, adv, mask):
    outs = _run_fused(static, logp, behav_logp, alpha, adv, mask)
    _, clip_tok, iw, ratio = outs
    return outs, (clip_tok, iw, ratio, adv, mask)


def _a3po_objective_bwd(static, res, cts):
    # The anchor (prox) and importance weight are frozen (stop_gradient in
    # the modular loss), so the only gradient path is
    #   d loss_tok / d logp = -iw * mask * d obj / d logp
    # with d obj / d logp = ratio * adv on the unclipped branch and 0 where
    # the clip is active (clip_tok already folds the mask in). At exact
    # min-ties both branches carry the same ratio*adv, matching jnp.minimum's
    # split-gradient convention. Cotangents for the metric outputs
    # (clip/iw/ratio) and the data operands are zero by construction.
    clip_tok, iw, ratio, adv, mask = res
    g_loss = cts[0].astype(jnp.float32)
    live = 1.0 - jnp.where(clip_tok > 0, 1.0, 0.0)
    g_logp = g_loss * (-(iw * ratio * adv) * mask * live)
    z = jnp.zeros_like(g_logp)
    return (g_logp, z, z, z, z)


_a3po_objective.defvjp(_a3po_objective_fwd, _a3po_objective_bwd)


def a3po_objective(logp: jax.Array, behav_logp: jax.Array, alpha: jax.Array,
                   adv: jax.Array, mask: jax.Array, *,
                   clip_eps: float = 0.2, iw_cap: float = 5.0,
                   use_kernel: bool = True,
                   interpret: bool = None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Differentiable fused A-3PO objective over [B, T] (or [T]) tensors.

    Returns per-token ``(loss_tok, clip_tok, iw, ratio)``; ``loss_tok`` is
    the negated, masked clipped surrogate and carries the analytic VJP
    w.r.t. ``logp``. The metric outputs (clip/iw/ratio) are detached —
    stop_gradient makes the zero-cotangent assumption of the backward pass
    mechanically true for any downstream use. On non-TPU backends the
    kernel runs in interpret mode.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    static = (float(clip_eps), float(iw_cap), bool(use_kernel),
              bool(interpret))
    loss_tok, clip_tok, iw, ratio = _a3po_objective(
        static, logp, behav_logp, alpha, adv, mask)
    sg = jax.lax.stop_gradient
    return loss_tok, sg(clip_tok), sg(iw), sg(ratio)


def a3po_loss_fused(logp: jax.Array, behav_logp: jax.Array,
                    alpha: jax.Array, adv: jax.Array, mask: jax.Array, *,
                    clip_eps: float = 0.2, iw_cap: float = 5.0,
                    interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Forward-only dispatch (benchmarks): kernel on TPU/interpret, else ref."""
    lead = logp.shape
    flat = lambda x: x.reshape(-1)  # noqa: E731
    args = (flat(logp), flat(behav_logp), flat(alpha), flat(adv), flat(mask))
    if jax.default_backend() == "tpu" or interpret:
        outs = a3po_loss_pallas(*args, clip_eps=clip_eps, iw_cap=iw_cap,
                                interpret=jax.default_backend() != "tpu")
    else:
        outs = a3po_loss_ref(*args, clip_eps=clip_eps, iw_cap=iw_cap)
    return tuple(o.reshape(lead) for o in outs)
