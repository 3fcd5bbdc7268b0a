"""RL training engine: one compiled, mesh-sharded update per training step.

Matches the paper's procedure (§4.1): one *training step* consumes a rollout
batch, optionally recomputes the proximal policy with an extra forward pass
(algorithm ``recompute`` — the cost A-3PO deletes), then performs
``num_minibatches`` gradient updates with the frozen anchor.

Engine architecture (PR 2): the whole update path is a single jitted
``train_step`` — advantages, a ``lax.scan`` over minibatches (each with an
optional inner gradient-accumulation scan over microbatches), Adam, and
metric accumulation all run on device. Metrics are packed into one array,
so a training step costs exactly **one** host transfer (plus the explicit
prox forward for the 'recompute' baseline, which is the point of the
comparison). Params and Adam moments are placed with the active
``ShardingEnv``'s logical rules, and batch tensors carry ("pod","data")
sharding constraints.

Algorithm dispatch (PR 3): the engine takes a first-class ``Algorithm``
(``core.algorithms``) instead of a method string. The frozen instance is
hashed as a jit static, its ``loss`` runs inside the scan (the ``a3po``
built-in still compiles to the fused ``kernels/a3po_loss`` Pallas path),
and its requires-flags decide what the step computes at all: only
``needs_prox_forward`` algorithms pay the extra forward pass, and only
``needs_behav_logp`` / ``needs_versions`` algorithms get those tensors
threaded through the compiled minibatch scan.

Sequence packing: a rollout batch is padded to its longest possible
sequence, so most of its positions are padding. For an attention stack
(``models.model.packable``: plain or latent attention, dense or drop-free
routed FFNs) the compiled step packs each minibatch's
sequences first-fit-decreasing into its first rows (``first_fit``, by the
input positions its mask reads), attention kept within each sequence by
segment ids. The decoder layers run on the fewest whole blocks of rows
(``row_block``: a quarter of the minibatch) that hold them, and their
hidden states are unpacked to the padded layout before the output head, so
the head, the loss and every batch tensor keep their shapes. Each block
count is a branch of one ``lax.switch`` around the minibatch's value and
gradient (``_by_rows``), so one program serves any lengths and no plan
crosses from the host.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RLConfig
from repro.core.algorithms import Algorithm, LossInputs, resolve_algorithm
from repro.data.tokenizer import PAD
from repro.distributed.sharding import (
    batch_shard_count,
    constrain,
    current_env,
)
from repro.kernels.logprob import token_logprob_entropy
from repro.models import model as M
from repro.models.layers import output_head_weight
from repro.models.moe import STATS as MOE_STATS
from repro.obs.metrics import get_registry
from repro.obs.tracing import span
from repro.rollout.engine import RolloutBatch
from repro.training.optimizer import adam_init, adam_update


class TrainState(NamedTuple):
    params: Any
    opt: Any
    version: jax.Array  # int32 scalar — the target-policy version v(pi_theta)


@dataclasses.dataclass
class TrainBatch:
    """Device-ready training batch assembled from rollouts."""

    tokens: jax.Array        # [B, T]
    response_mask: jax.Array  # [B, T-1] (1 on generated-token predictions)
    behav_logp: jax.Array    # [B, T-1] (0 outside mask)
    # behavior policy versions: [B] (one per sequence) or [B, T-1]
    # (per-token stamps from the interruptible serving control plane)
    versions: jax.Array
    rewards: jax.Array       # [B]


def assemble_train_batch(rollouts: List[RolloutBatch],
                         rewards: np.ndarray) -> TrainBatch:
    """Scatter ragged generation logps into [B, T-1] aligned tensors.

    If any rollout carries per-token version stamps (``gen_versions``,
    produced when generation crossed a weight publish), ``versions`` is
    emitted as [B, T-1] so ``a3po.staleness`` sees the true per-token
    ``d`` — the alpha interpolation then varies *within* a sequence at
    the publish boundary. Otherwise the legacy [B] form is kept.

    The scatter is vectorized: position t predicts tokens[t+1], so row b's
    generated span starts at column prompt_lengths[b] - 1 — one fancy-index
    write per rollout instead of a per-sequence Python loop.
    """
    tokens = np.concatenate([r.tokens for r in rollouts], axis=0)
    B, T = tokens.shape
    behav = np.zeros((B, T - 1), np.float32)
    mask = np.zeros((B, T - 1), np.float32)
    per_token = any(r.gen_versions is not None for r in rollouts)
    if per_token:
        versions = np.zeros((B, T - 1), np.int32)
    else:
        versions = np.zeros((B,), np.int32)
    row = 0
    for r in rollouts:
        N = r.gen_logp.shape[1]
        rows = slice(row, row + r.batch_size)
        cols = (np.asarray(r.prompt_lengths, np.int64) - 1)[:, None] \
            + np.arange(N)[None, :]
        np.put_along_axis(behav[rows], cols,
                          np.asarray(r.gen_logp, np.float32), axis=1)
        np.put_along_axis(mask[rows], cols,
                          np.asarray(r.gen_mask, np.float32), axis=1)
        if per_token:
            versions[rows] = r.version
            if r.gen_versions is not None:
                stamped = np.where(r.gen_mask > 0, r.gen_versions,
                                   r.version).astype(np.int32)
                np.put_along_axis(versions[rows], cols, stamped, axis=1)
        else:
            versions[rows] = r.version
        row += r.batch_size
    return TrainBatch(
        tokens=jnp.asarray(tokens),
        response_mask=jnp.asarray(mask),
        behav_logp=jnp.asarray(behav),
        versions=jnp.asarray(versions),
        rewards=jnp.asarray(rewards, jnp.float32),
    )


# ------------------------------------------------------------------- packing
def row_block(n: int, shards: int = 1) -> int:
    """The step the decoder's row count takes over a unit of ``n`` packed
    sequences: a quarter of ``n``, rounded up to the batch's shard count so
    packed rows still split over its shards. A single block (``n`` at most)
    packs nothing."""
    q = -(-n // 4)
    return -(-q // shards) * shards


def _block(cfg: ModelConfig, n: int) -> Optional[int]:
    """``row_block`` of a unit of ``n`` sequences under the active mesh, or
    None where the unit keeps its padded rows: a stack whose rows cannot be
    shared, or a unit that is one block or not whole blocks."""
    if not M.packable(cfg):
        return None
    q = row_block(n, batch_shard_count())
    return q if q < n and n % q == 0 else None


class RowFit(NamedTuple):
    """Where first-fit-decreasing puts each sequence of a unit (a minibatch,
    or a microbatch of one), over the units' leading axes. int32."""

    need: Any     # [..., n] input positions the sequence's loss reads
    row: Any      # [..., n] its packed row
    offset: Any   # [..., n] its first position in that row
    blocks: Any   # [...] row blocks that hold the unit's sequences


def input_needs(mask: jax.Array) -> jax.Array:
    """Input positions each sequence's loss reads, [..., S] -> [...]: all
    up to the last one its mask keeps (none where it keeps none)."""
    S = mask.shape[-1]
    return jnp.max(jnp.where(mask > 0, jnp.arange(1, S + 1, dtype=jnp.int32),
                             0), axis=-1)


def first_fit(need: jax.Array, width: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """First-fit-decreasing of sequences of ``need`` [n] positions (each at
    most ``width``) into rows of ``width``: each one's row and offset [n],
    and the rows used (at least 1). n rows always hold n sequences, so a
    row is always found."""
    n = need.shape[0]
    order = jnp.argsort(-need, stable=True)

    def place(i, carry):
        fill, row, offset = carry
        b = order[i]
        r = jnp.argmax(fill + need[b] <= width)
        return (fill.at[r].add(need[b]), row.at[b].set(r),
                offset.at[b].set(fill[r]))

    zero = jnp.zeros((n,), jnp.int32)
    fill, row, offset = jax.lax.fori_loop(0, n, place, (zero, zero, zero))
    # a row opens only when every earlier one is too full for the sequence,
    # so the rows used are the first ones
    return row, offset, jnp.maximum(jnp.sum(fill > 0), 1)


def fit_rows(mask: jax.Array, block: int) -> RowFit:
    """``first_fit`` of each unit of ``mask`` [..., n, S] into rows of
    ``S``, and the blocks of ``block`` rows that hold it."""
    lead, (n, S) = mask.shape[:-2], mask.shape[-2:]
    need = input_needs(mask).reshape(-1, n)
    row, offset, used = jax.vmap(lambda x: first_fit(x, S))(need)
    blocks = -(-used // block)
    return RowFit(*(a.reshape(lead + a.shape[1:])
                    for a in (need, row, offset, blocks)))


def pack_index(fit: RowFit, S: int, rows: int
               ) -> Tuple[jax.Array, jax.Array]:
    """One unit packed into ``rows`` rows of ``S`` as ``fit`` places it:
    ``src`` [rows, S], each packed position's place in the unit's inputs
    flattened to [n*S] (-1: padding), and ``dest`` [n, S], each input
    position's place in the packed rows flattened (``rows*S``: none)."""
    n = fit.need.shape[0]
    col = jnp.arange(S, dtype=jnp.int32)
    dest = jnp.where(col < fit.need[:, None],
                     (fit.row * S + fit.offset)[:, None] + col, rows * S)
    src = jnp.full((rows * S,), -1, jnp.int32).at[dest.reshape(-1)].set(
        jnp.arange(n * S, dtype=jnp.int32), mode="drop")
    return src.reshape(rows, S), dest


def _packed_hidden(params, cfg: ModelConfig, inputs: jax.Array, fit: RowFit,
                   rows: int):
    """``forward_hidden`` over ``inputs`` [n, S] packed into ``rows`` rows
    as ``fit`` places them, unpacked to [n, S, d], with the aux loss and
    the expert counters. A position past its sequence's need takes a zero
    hidden state: no loss reads it. The final norm runs on the packed
    rows; it is per position, so unpacking after it changes nothing."""
    S = inputs.shape[1]
    src, dest = pack_index(fit, S, rows)
    src = constrain(src, "batch", None)
    ok = src >= 0
    packed = jnp.where(ok, jnp.take(inputs.reshape(-1), jnp.maximum(src, 0)),
                       PAD)
    h, aux, stats = M.forward_hidden(params, cfg, packed,
                                     positions=jnp.where(ok, src % S, 0),
                                     segment_ids=jnp.where(ok, src // S, -1),
                                     with_stats=True)
    h = jnp.take(h.reshape(rows * S, -1), jnp.minimum(dest, rows * S - 1),
                 axis=0)
    h = jnp.where((dest < rows * S)[..., None], h, 0)
    return constrain(h, "batch", None, "act_embed"), aux, stats


def _by_rows(fn, n: int, block: Optional[int], fit: Optional[RowFit],
             *args):
    """``fn(*args, fit, rows=R)`` with the decoder on ``R`` packed rows, the
    fewest whole blocks that hold the unit ``fit`` places, or
    ``fn(*args, None, rows=None)`` on its ``n`` padded rows where only all
    of them do (or ``block`` is None). A branch for each block count is
    compiled; the lengths pick one."""
    def padded(*a):
        return fn(*a[:-1], None, rows=None)

    if block is None:
        return padded(*args, None)
    branches = [functools.partial(fn, rows=k * block)
                for k in range(1, n // block)] + [padded]
    return jax.lax.switch(fit.blocks - 1, branches, *args, fit)


# --------------------------------------------------------------------- score
def _score_tokens(params, cfg: ModelConfig, tokens: jax.Array,
                  fit: Optional[RowFit] = None, rows: Optional[int] = None,
                  valid: Optional[jax.Array] = None):
    """Per-position logp and entropy of ``tokens``, the aux loss and the
    expert counters (``moe.STATS``; none without experts); the decoder on
    the padded rows, or on ``rows`` packed rows as ``fit`` places them.
    On padded rows ``valid`` [B, T-1] marks the positions routed to
    experts (all where None); packed rows route their sequences' own."""
    tokens = constrain(tokens, "batch", None)
    if rows is None:
        hidden, aux, stats = M.forward_hidden(
            params, cfg, tokens[:, :-1], pad_mask=valid, with_stats=True)
    else:
        hidden, aux, stats = _packed_hidden(params, cfg, tokens[:, :-1], fit,
                                            rows)
    w = output_head_weight(params["embedding"], cfg)
    with jax.named_scope("logprob_head"):
        logp, entropy = token_logprob_entropy(hidden, w, tokens[:, 1:])
    return (constrain(logp, "batch", None), constrain(entropy, "batch", None),
            aux, stats)


@functools.partial(jax.jit, static_argnames=("cfg",))
def score_tokens(params, cfg: ModelConfig, tokens: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-position logp of tokens[t+1] + entropy. Returns ([B,T-1]x2, aux).

    Uses the fused logprob kernel path — the [T, V] logits never
    materialize (this is exactly the computation the 'recompute' baseline
    pays for every training step).
    """
    return _score_tokens(params, cfg, tokens)[:3]


@functools.partial(jax.jit, static_argnames=("cfg",))
def recompute_prox_logp(params, cfg: ModelConfig, tokens: jax.Array,
                        mask: Optional[jax.Array] = None) -> jax.Array:
    """The explicit proximal forward pass of decoupled PPO (Hilton 2022).

    This is the per-step cost A-3PO eliminates (paper Fig. 1). Given the
    batch's ``mask``, the layers run on packed rows as in the train step,
    the whole batch one unit."""
    n = tokens.shape[0]
    block = None if mask is None else _block(cfg, n)
    fit = None if block is None else fit_rows(mask, block)

    def score(params, tokens, fit, rows):
        return _score_tokens(params, cfg, tokens, fit, rows)[0]

    return jax.lax.stop_gradient(
        _by_rows(score, n, block, fit, params, tokens))


# --------------------------------------------------------------- fused step
# Fixed pack order for the on-device metrics vector — a single [K] f32
# array is the step's one device->host transfer.
METRIC_KEYS: Tuple[str, ...] = (
    "clipped_frac", "clipped_tokens", "entropy", "grad_norm", "iw_max",
    "iw_mean", "iw_min", "kl", "loss", "nonfinite", "packed_rows",
    "ratio_mean", "reward_mean", "staleness_mean", "tokens",
)


def metric_keys(cfg: ModelConfig) -> Tuple[str, ...]:
    """The step's metric array: ``METRIC_KEYS``, then for an expert stack
    the expert counters of the step's forward passes, ``moe_local_rows``
    (assignments computed by the experts held here, over every layer and
    minibatch), ``moe_expert_rows_max`` (the most rows any held expert
    got in one minibatch of one layer) and ``moe_buffer_rows`` (the rows
    of the dispatch buffers they ran on, summed as ``moe_local_rows``)."""
    return METRIC_KEYS + (MOE_STATS if cfg.moe is not None else ())


def _reduce_metrics(stacked: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Fold [n]-stacked per-minibatch metrics: means, except extremes/sums
    (exactly the seed loop-trainer's host-side aggregation, on device)."""
    out = {k: jnp.mean(v, axis=0) for k, v in stacked.items()}
    for k in ("moe_local_rows", "moe_buffer_rows"):
        if k in stacked:
            out[k] = jnp.sum(stacked[k], axis=0)
    if "moe_expert_rows_max" in stacked:
        out["moe_expert_rows_max"] = jnp.max(stacked["moe_expert_rows_max"],
                                             axis=0)
    if "iw_max" in stacked:
        out["iw_max"] = jnp.max(stacked["iw_max"], axis=0)
    if "iw_min" in stacked:
        out["iw_min"] = jnp.min(stacked["iw_min"], axis=0)
    if "clipped_tokens" in stacked:
        out["clipped_tokens"] = jnp.sum(stacked["clipped_tokens"], axis=0)
    if "nonfinite" in stacked:
        # minibatches whose update was non-finite: a count, not a mean
        out["nonfinite"] = jnp.sum(stacked["nonfinite"], axis=0)
    return out


def _constrain_batch(t: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: constrain(v, *(("batch",) + (None,) * (v.ndim - 1)))
            for k, v in t.items()}


def _train_step_impl(params, opt, version, tokens, behav_logp, mask,
                     versions, rewards, prox_logp=None, *, cfg: ModelConfig,
                     rl: RLConfig, algo: Algorithm, num_minibatches: int,
                     num_microbatches: int, skip_nonfinite: bool = False):
    """One full training step, compiled: advantages -> scan over minibatch
    updates (optionally gradient-accumulated over microbatches) -> packed
    metrics. Exactly one output array carries every scalar metric. The
    ``algo`` static supplies the loss and its requires-flags decide which
    batch tensors are threaded through the minibatch scan at all. Each
    minibatch (or microbatch) runs its decoder on the fewest packed rows
    that hold its sequences (``fit_rows``)."""
    B = tokens.shape[0]
    nmb = num_minibatches
    mb_size = B // nmb
    nmi = (num_microbatches
           if num_microbatches > 1 and mb_size % num_microbatches == 0 else 1)

    full = _constrain_batch(dict(tokens=tokens, behav_logp=behav_logp,
                                 mask=mask, versions=versions,
                                 rewards=rewards))
    tokens, behav_logp, mask, versions, rewards = (
        full["tokens"], full["behav_logp"], full["mask"], full["versions"],
        full["rewards"])

    advantages = algo.advantages(rewards, mask, rl)

    # full-batch staleness/reward telemetry (matches the seed trainer)
    d = version.astype(jnp.float32) - versions.astype(jnp.float32)
    if versions.ndim == 2:
        # per-token stamps: average over response tokens only (prompt
        # positions carry a filler version, not behavior staleness)
        staleness_mean = jnp.sum(d * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        staleness_mean = d.mean()

    # requires-flags gate what enters the compiled minibatch scan: an
    # algorithm that declares no use for behavior logps or version stamps
    # never sees them (and XLA never materializes the minibatched copies)
    mbt = dict(tokens=tokens, advantages=advantages, mask=mask)
    if algo.needs_behav_logp:
        mbt["behav_logp"] = behav_logp
    if algo.needs_versions:
        mbt["versions"] = versions
    if prox_logp is not None:
        mbt["prox"] = prox_logp
    # seed semantics: rows beyond nmb * mb_size are dropped from updates
    # (but still count toward reward/staleness telemetry above)
    mbt = jax.tree.map(
        lambda x: x[: nmb * mb_size].reshape((nmb, mb_size) + x.shape[1:]),
        mbt)
    # where each unit's sequences go in its packed rows
    unit = mb_size // nmi
    block = _block(cfg, unit)
    fit = None
    if block is not None:
        lead = (nmb, nmi) if nmi > 1 else (nmb,)
        fit = fit_rows(mask[: nmb * mb_size].reshape(lead + (unit, -1)),
                       block)

    def loss_fn(p, t, fit, rows):
        t = _constrain_batch(t)
        valid = None
        if cfg.moe is not None:
            # the positions packing keeps: up to each sequence's need
            valid = (jnp.arange(t["mask"].shape[-1], dtype=jnp.int32)
                     < input_needs(t["mask"])[:, None])
        logp, entropy, aux, stats = _score_tokens(p, cfg, t["tokens"], fit,
                                                  rows, valid)
        with jax.named_scope("policy_loss"):
            loss, metrics = algo.loss(logp, LossInputs(
                advantages=t["advantages"], mask=t["mask"],
                behav_logp=t.get("behav_logp"), versions=t.get("versions"),
                current_version=version, prox_logp=t.get("prox"),
                entropy=entropy), rl)
        return loss + aux, dict(metrics, **stats)

    def value_and_grads(p, t, fit, rows):
        return jax.value_and_grad(functools.partial(loss_fn, rows=rows),
                                  has_aux=True)(p, t, fit)

    def grads_of(p, t, fit):
        if nmi == 1:
            return _by_rows(value_and_grads, unit, block, fit, p, t)
        micro = jax.tree.map(
            lambda x: x.reshape((nmi, mb_size // nmi) + x.shape[1:]), t)
        g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)

        # Accumulate weighted by each microbatch's response-token count:
        # the losses are masked *means*, so an equal average would
        # over-weight tokens in sparse microbatches relative to the
        # single-pass minibatch objective.
        def accum(carry, xs):
            mi, mfit = xs
            g_acc, loss_acc, w_acc = carry
            w = jnp.sum(mi["mask"])
            (loss, metrics), grads = _by_rows(value_and_grads, unit, block,
                                              mfit, p, mi)
            g_acc = jax.tree.map(
                lambda a, g: a + w * g.astype(jnp.float32), g_acc, grads)
            return (g_acc, loss_acc + w * loss, w_acc + w), metrics

        (grads, loss, w_tot), ms = jax.lax.scan(
            accum, (g0, jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32)), (micro, fit))
        w_tot = jnp.maximum(w_tot, 1.0)
        grads = jax.tree.map(lambda g: g / w_tot, grads)
        return (loss / w_tot, _reduce_metrics(ms)), grads

    def minibatch_body(carry, xs):
        t, fit = xs
        p, o = carry
        (loss, metrics), grads = grads_of(p, t, fit)
        with jax.named_scope("optimizer"):
            p2, o2, gnorm = adam_update(grads, o, p, rl)
        # on-device non-finite guard: grad_norm is a global reduction, so
        # any NaN/Inf gradient leaf poisons it — one scalar flag covers
        # loss + every gradient, and it rides the packed metric array
        # (zero extra host syncs).
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        if skip_nonfinite:
            # poisoned minibatch: keep params AND the whole Adam state
            # (moments + step count) bit-identical — the update never
            # happened (resilience.guards skip-step policy)
            sel = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
            p = jax.tree.map(sel, p2, p)
            o = jax.tree.map(sel, o2, o)
        else:
            p, o = p2, o2
        metrics = dict(metrics, loss=loss, grad_norm=gnorm,
                       nonfinite=(~ok).astype(jnp.float32))
        return (p, o), metrics

    (params, opt), stacked = jax.lax.scan(minibatch_body, (params, opt),
                                          (mbt, fit))
    out = _reduce_metrics(stacked)
    out["reward_mean"] = rewards.mean()
    out["staleness_mean"] = staleness_mean
    # response tokens that actually received a gradient (rows past
    # nmb * mb_size are dropped from the scan, so don't count them)
    out["tokens"] = jnp.sum(mask[: nmb * mb_size])
    # rows the decoder ran per pass over the step's units
    out["packed_rows"] = (
        jnp.float32(nmb * mb_size) if block is None
        else jnp.sum(fit.blocks * block).astype(jnp.float32))
    keys = metric_keys(cfg)
    assert set(out) == set(keys), sorted(out)
    packed = jnp.stack([out[k].astype(jnp.float32) for k in keys])
    return params, opt, packed


# The step donates only the optimizer state: the async runtime keeps older
# params alive as behavior policies (WeightStore / staleness history), so
# donating them would invalidate live behavior-policy buffers.
_train_step = jax.jit(_train_step_impl,
                      static_argnames=("cfg", "rl", "algo", "num_minibatches",
                                       "num_microbatches", "skip_nonfinite"),
                      donate_argnums=(1,))


# -------------------------------------------------------------------- driver
def check_trainable(cfg: ModelConfig) -> None:
    """The step reads token ids only; a stack whose inputs start with a
    frontend's embeddings (audio codes, image patches) has no train path."""
    if cfg.frontend is not None:
        raise ValueError(
            f"{cfg.name}: the train step reads token ids only, and this "
            f"stack needs {cfg.frontend!r} frontend embeddings")


class Trainer:
    """One training engine. ``step`` = the paper's 'training step'.

    ``algo`` selects the policy-optimization algorithm: an ``Algorithm``
    instance from ``core.algorithms``, a registry name, or None (falls
    back to ``rl.algo``, then ``a3po``).

    ``num_microbatches`` > 1 adds gradient accumulation *inside* the
    minibatch scan for batches that exceed memory. The trainer trains
    token-only stacks: a stack with a frontend is refused."""

    def __init__(self, cfg: ModelConfig, rl: Optional[RLConfig] = None,
                 algo=None, *, num_microbatches: int = 1,
                 skip_nonfinite: bool = False):
        check_trainable(cfg)
        self.cfg = cfg
        self.rl = rl or RLConfig()
        self.algo = resolve_algorithm(algo, self.rl)
        self.num_microbatches = num_microbatches
        # skip-step guard: non-finite minibatch updates are dropped on
        # device (params/opt unchanged) instead of poisoning the run; the
        # packed `nonfinite` metric counts them (resilience.guards)
        self.skip_nonfinite = skip_nonfinite
        self.last_host_syncs = 0  # host transfers in the most recent step

    def init_state(self, key, dtype=None) -> TrainState:
        """Initialize params + Adam moments, placed with the active
        ``ShardingEnv``'s logical-axis rules when one is installed.

        Under a mesh the initializer is one jitted program whose
        ``out_shardings`` are those placements, so each device only ever
        materializes its own shard of the state."""
        def init(key):
            params = M.init_params(self.cfg, key, dtype=dtype)
            return params, adam_init(params)

        env = current_env()
        if env is None:
            params, opt = init(key)
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            psh = M.param_shardings(self.cfg, env)
            rep = NamedSharding(env.mesh, PartitionSpec())
            params, opt = jax.jit(init, out_shardings=(
                psh, {"m": psh, "v": psh, "t": rep}))(key)
        return TrainState(params, opt, jnp.zeros((), jnp.int32))

    def step(self, state: TrainState, batch: TrainBatch
             ) -> Tuple[TrainState, Dict[str, float]]:
        rl = self.rl
        B = batch.tokens.shape[0]
        nmb = min(rl.num_minibatches, B)
        if self.num_microbatches > 1 \
                and (B // nmb) % self.num_microbatches != 0:
            raise ValueError(
                f"num_microbatches={self.num_microbatches} does not divide "
                f"the minibatch size {B // nmb} (B={B}, nmb={nmb}); the "
                "memory-saving accumulation would be silently skipped")
        host_syncs = 0

        # --- explicit prox forward pass, paid only by algorithms that
        # declare needs_prox_forward (the recompute baseline); otherwise
        # no prox operand enters the compiled step at all
        t0 = time.perf_counter()
        prox = None
        if self.algo.needs_prox_forward:
            with span("prox_forward", algo=self.algo.name):
                prox = recompute_prox_logp(state.params, self.cfg,
                                           batch.tokens, batch.response_mask)
                prox.block_until_ready()
            host_syncs += 1
        prox_time = time.perf_counter() - t0

        # train_dispatch returns once the step is enqueued; train_fetch is
        # the host waiting on the device; train_metrics is host bookkeeping
        with span("train_update", algo=self.algo.name,
                  batch=int(B), minibatches=int(nmb)) as update_span:
            with span("train_dispatch"):
                params, opt, packed = _train_step(
                    state.params, state.opt, state.version, batch.tokens,
                    batch.behav_logp, batch.response_mask, batch.versions,
                    batch.rewards, prox, cfg=self.cfg, rl=rl,
                    algo=self.algo, num_minibatches=nmb,
                    num_microbatches=self.num_microbatches,
                    skip_nonfinite=self.skip_nonfinite)

            # the single device->host transfer of the step
            with span("train_fetch"):
                values = jax.device_get(packed)
            host_syncs += 1
            out = {k: float(v)
                   for k, v in zip(metric_keys(self.cfg), values)}
            out["prox_time_s"] = prox_time
            out["host_syncs"] = float(host_syncs)
            # the padded rows the decoder's packed rows stand for, and the
            # rows it ran per unit (minibatch, or microbatch of one)
            units = nmb * self.num_microbatches
            out["padded_rows"] = float(B // nmb * nmb)
            update_span.set(rows=out["packed_rows"] / units,
                            **{k: out[k] for k in MOE_STATS if k in out})
            self.last_host_syncs = host_syncs
            with span("train_metrics"):
                self._publish_metrics(out)
        new_state = TrainState(params, opt, state.version + 1)
        return new_state, out

    # training-side metrics mirrored into the process-wide obs registry
    # (gauges: latest step's value; counters: lifetime accumulation), so
    # one ``registry.snapshot()`` / prometheus dump covers trainer state
    # alongside the serving facade.
    _GAUGE_KEYS = ("loss", "reward_mean", "entropy", "grad_norm",
                   "iw_max", "iw_min", "iw_mean", "kl", "clipped_frac",
                   "ratio_mean", "staleness_mean", "prox_time_s",
                   "packed_rows", "padded_rows") + MOE_STATS
    _COUNTER_KEYS = ("tokens", "clipped_tokens", "host_syncs", "nonfinite")

    def _publish_metrics(self, out: Dict[str, float]) -> None:
        reg = get_registry()
        for k in self._GAUGE_KEYS:
            if k in out:
                reg.gauge(f"train_{k}").set(out[k])
        for k in self._COUNTER_KEYS:
            if k in out:
                reg.counter(f"train_{k}_total").inc(out[k])
        reg.counter("train_steps_total").inc()


# ----------------------------------------------------------------- SFT warmup
@functools.partial(jax.jit, static_argnames=("cfg", "lr"), donate_argnums=(2,))
def sft_update(cfg: ModelConfig, params, opt, tokens, mask, lr: float = 1e-3):
    rl = RLConfig(learning_rate=lr, max_grad_norm=1.0)

    def loss_fn(p):
        logp, _, aux, _ = _score_tokens(p, cfg, tokens)
        ce = -jnp.sum(logp * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return ce + aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    params, opt, _ = adam_update(grads, opt, params, rl)
    return params, opt, loss
