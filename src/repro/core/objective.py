"""Unified A-3PO training objective (the hot inner loop of the engine).

The losses of the three methods the paper compares:

* ``sync``      — coupled PPO/GRPO (Eq. 1): pi_old is IS weight + anchor.
* ``recompute`` — decoupled PPO (Eq. 2) with an explicitly recomputed
                  proximal anchor (the forward pass A-3PO deletes).
* ``a3po``      — A-3PO (Eq. 3-4 / Listing 1): the anchor is a log-linear
                  interpolation weighted by the staleness-aware alpha.

``resolve_alpha`` is the single dispatch point for every alpha schedule —
including the beyond-paper ``kl_adaptive`` controller, which needs the
live/behavior logps and therefore cannot be computed from staleness alone.

The ``a3po`` clipped-surrogate inner loop routes through the fused
``kernels/a3po_loss`` Pallas kernel (interpret mode off-TPU) behind a
``custom_vjp``: one fused elementwise pass computes loss, clip indicators,
importance weights, and trust-region ratios; the backward pass is the
analytic gradient, with the pure-jnp ref as the oracle. Alpha is computed
from the ``[B]`` or ``[B, T]`` version stamps and broadcast into the fused
path. ``core.algorithms`` wraps these losses as registered ``Algorithm``
objects, the one way a caller names an objective.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RLConfig
from repro.core.a3po import (
    alpha_from_staleness,
    kl_adaptive_alpha,
    staleness,
)
from repro.kernels.a3po_loss import a3po_objective

Metrics = Dict[str, jax.Array]


def _masked_mean(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _masked_max(x, mask):
    return jnp.max(jnp.where(mask > 0, x, -jnp.inf))


def _masked_min(x, mask):
    return jnp.min(jnp.where(mask > 0, x, jnp.inf))


def _clip_objective(ratio: jax.Array, adv: jax.Array, eps: float
                    ) -> Tuple[jax.Array, jax.Array]:
    """PPO clipped surrogate per token. Returns (objective, clipped_mask)."""
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * adv
    obj = jnp.minimum(unclipped, clipped)
    was_clipped = (unclipped > clipped).astype(jnp.float32)
    return obj, was_clipped


# public names for Algorithm plugins (core.algorithms and third parties)
masked_mean = _masked_mean
clip_objective = _clip_objective


def _common_metrics(iw, ratio, was_clipped, mask, entropy) -> Metrics:
    m: Metrics = {
        "iw_max": _masked_max(iw, mask),
        "iw_min": _masked_min(iw, mask),
        "iw_mean": _masked_mean(iw, mask),
        "ratio_mean": _masked_mean(ratio, mask),
        "clipped_tokens": jnp.sum(was_clipped * mask),
        "clipped_frac": _masked_mean(was_clipped, mask),
    }
    if entropy is not None:
        m["entropy"] = _masked_mean(entropy, mask)
    return m


common_metrics = _common_metrics


def apply_regularizers(loss: jax.Array, metrics: Metrics, logp: jax.Array,
                       anchor_logp: jax.Array, mask: jax.Array,
                       cfg: RLConfig, entropy: Optional[jax.Array]
                       ) -> Tuple[jax.Array, Metrics]:
    """Shared loss tail for every algorithm: KL penalty + entropy bonus.

    ``kl`` is the k1 estimator of KL(pi_theta || anchor) on the response
    tokens — the anchor is whatever trust-region reference the algorithm
    uses (behavior, recomputed prox, log-linear prox). It is always
    reported in ``metrics`` and added to the loss when ``cfg.kl_coef`` is
    set (this is the wiring of the previously-dead ``RLConfig.kl_coef``).
    """
    kl = _masked_mean(
        logp.astype(jnp.float32)
        - jax.lax.stop_gradient(anchor_logp.astype(jnp.float32)), mask)
    metrics["kl"] = kl
    if cfg.kl_coef:
        loss = loss + cfg.kl_coef * kl
    if entropy is not None and cfg.entropy_coef:
        loss = loss - cfg.entropy_coef * metrics["entropy"]
    return loss, metrics


# ------------------------------------------------------------- alpha dispatch
def resolve_alpha(
    cfg: RLConfig,
    *,
    versions: Optional[jax.Array] = None,
    current_version=None,
    logp: Optional[jax.Array] = None,
    behav_logp: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
    schedule: Optional[str] = None,
) -> jax.Array:
    """The one place every alpha schedule is dispatched from.

    Staleness schedules (inverse/exp/clipped/const) need the ``[B]`` or
    ``[B, T]`` version stamps; ``kl_adaptive`` needs the live/behavior
    logps and yields a per-sequence ``[B, 1]``. The result broadcasts
    against ``[B, T]`` token tensors in all cases and carries no gradient.
    """
    schedule = schedule or cfg.alpha_schedule
    if schedule == "kl_adaptive":
        assert logp is not None and behav_logp is not None \
            and mask is not None, "kl_adaptive alpha needs logps + mask"
        return kl_adaptive_alpha(behav_logp, logp, mask)
    assert versions is not None and current_version is not None, \
        f"schedule {schedule!r} needs version stamps"
    return alpha_from_staleness(staleness(versions, current_version), cfg,
                                schedule)


# ------------------------------------------------------------------ jnp paths
def coupled_ppo_loss(
    logp: jax.Array,        # log pi_theta  [B, T]
    behav_logp: jax.Array,  # log pi_behav  [B, T]
    advantages: jax.Array,  # [B, T] (already broadcast / normalized)
    mask: jax.Array,        # [B, T] response mask
    cfg: RLConfig,
    entropy: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Metrics]:
    """Standard PPO/GRPO (Eq. 1): pi_old doubles as IS weight + anchor."""
    logp = logp.astype(jnp.float32)
    behav_logp = behav_logp.astype(jnp.float32)
    ratio = jnp.exp(logp - behav_logp)
    obj, was_clipped = _clip_objective(ratio, advantages, cfg.clip_eps)
    loss = -_masked_mean(obj, mask)
    metrics = _common_metrics(ratio, ratio, was_clipped, mask, entropy)
    return apply_regularizers(loss, metrics, logp, behav_logp, mask, cfg,
                              entropy)


def decoupled_ppo_loss(
    logp: jax.Array,
    behav_logp: jax.Array,
    prox_logp: jax.Array,   # frozen trust-region anchor [B, T]
    advantages: jax.Array,
    mask: jax.Array,
    cfg: RLConfig,
    entropy: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Metrics]:
    """Decoupled loss (Eq. 2): behavior IS weight x prox-anchored clip."""
    logp = logp.astype(jnp.float32)
    behav_logp = behav_logp.astype(jnp.float32)
    prox_logp = jax.lax.stop_gradient(prox_logp.astype(jnp.float32))
    # importance weight pi_prox / pi_behav — detached, capped for stability
    iw = jnp.exp(prox_logp - behav_logp)
    iw = jnp.minimum(iw, cfg.behav_weight_cap)
    iw = jax.lax.stop_gradient(iw)
    # trust-region ratio pi_theta / pi_prox
    ratio = jnp.exp(logp - prox_logp)
    obj, was_clipped = _clip_objective(ratio, advantages, cfg.clip_eps)
    loss = -_masked_mean(iw * obj, mask)
    metrics = _common_metrics(iw, ratio, was_clipped, mask, entropy)
    return apply_regularizers(loss, metrics, logp, prox_logp, mask, cfg,
                              entropy)


# ----------------------------------------------------------------- fused path
def fused_a3po_loss(
    logp: jax.Array,
    behav_logp: jax.Array,
    alpha: jax.Array,       # [B, T], [B, 1] or [B] — broadcast over tokens
    advantages: jax.Array,
    mask: jax.Array,
    cfg: RLConfig,
    entropy: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Metrics]:
    """A-3PO decoupled loss through the fused kernel + analytic VJP.

    Numerically identical to ``decoupled_ppo_loss`` over the log-linear
    anchor ``alpha * behav + (1 - alpha) * logp`` — but prox interpolation,
    IS weight, ratio, clip, and masking run as one fused pass, and the
    iw/ratio metric tensors fall out of the same pass.
    """
    logp = logp.astype(jnp.float32)
    behav_logp = behav_logp.astype(jnp.float32)
    if alpha.ndim == logp.ndim - 1:
        alpha = alpha[..., None]
    alpha = jax.lax.stop_gradient(
        jnp.broadcast_to(alpha, logp.shape).astype(jnp.float32))
    loss_tok, clip_tok, iw, ratio = a3po_objective(
        logp, behav_logp, alpha, advantages, mask,
        clip_eps=cfg.clip_eps, iw_cap=cfg.behav_weight_cap)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(loss_tok) / denom
    metrics: Metrics = {
        "iw_max": _masked_max(iw, mask),
        "iw_min": _masked_min(iw, mask),
        "iw_mean": _masked_mean(iw, mask),
        "ratio_mean": _masked_mean(ratio, mask),
        "clipped_tokens": jnp.sum(clip_tok),
        "clipped_frac": jnp.sum(clip_tok) / denom,
    }
    if entropy is not None:
        metrics["entropy"] = _masked_mean(entropy, mask)
    # the log-linear anchor, reconstructed for the shared KL path (the
    # fused kernel keeps it internal)
    anchor = alpha * behav_logp + (1.0 - alpha) * logp
    return apply_regularizers(loss, metrics, logp, anchor, mask, cfg,
                              entropy)
