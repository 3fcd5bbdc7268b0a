"""RL training engine: one compiled, mesh-sharded update per training step.

Matches the paper's procedure (§4.1): one *training step* consumes a rollout
batch, optionally recomputes the proximal policy with an extra forward pass
(method='recompute' — the cost A-3PO deletes), then performs
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
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RLConfig
from repro.core.algorithms import Algorithm, LossInputs, resolve_algorithm
from repro.distributed.sharding import constrain, current_env
from repro.kernels.logprob import token_logprob_entropy
from repro.models import model as M
from repro.models.layers import output_head_weight
from repro.obs.metrics import get_registry
from repro.obs.tracing import annotate, span
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


# --------------------------------------------------------------------- score
def _score_tokens(params, cfg: ModelConfig, tokens: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    tokens = constrain(tokens, "batch", None)
    hidden, aux = M.forward_hidden(params, cfg, tokens[:, :-1])
    w = output_head_weight(params["embedding"], cfg)
    logp, entropy = token_logprob_entropy(hidden, w, tokens[:, 1:])
    return (constrain(logp, "batch", None), constrain(entropy, "batch", None),
            aux)


@functools.partial(jax.jit, static_argnames=("cfg",))
def score_tokens(params, cfg: ModelConfig, tokens: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-position logp of tokens[t+1] + entropy. Returns ([B,T-1]x2, aux).

    Uses the fused logprob kernel path — the [T, V] logits never
    materialize (this is exactly the computation the 'recompute' baseline
    pays for every training step).
    """
    return _score_tokens(params, cfg, tokens)


@functools.partial(jax.jit, static_argnames=("cfg",))
def recompute_prox_logp(params, cfg: ModelConfig, tokens: jax.Array
                        ) -> jax.Array:
    """The explicit proximal forward pass of decoupled PPO (Hilton 2022).

    This is the per-step cost A-3PO eliminates (paper Fig. 1)."""
    logp, _, _ = _score_tokens(params, cfg, tokens)
    return jax.lax.stop_gradient(logp)


# --------------------------------------------------------------- fused step
# Fixed pack order for the on-device metrics vector — a single [K] f32
# array is the step's one device->host transfer.
METRIC_KEYS: Tuple[str, ...] = (
    "clipped_frac", "clipped_tokens", "entropy", "grad_norm", "iw_max",
    "iw_mean", "iw_min", "kl", "loss", "nonfinite", "ratio_mean",
    "reward_mean", "staleness_mean", "tokens",
)


def _reduce_metrics(stacked: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Fold [n]-stacked per-minibatch metrics: means, except extremes/sums
    (exactly the seed loop-trainer's host-side aggregation, on device)."""
    out = {k: jnp.mean(v, axis=0) for k, v in stacked.items()}
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
    batch tensors are threaded through the minibatch scan at all."""
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

    def loss_fn(p, t):
        t = _constrain_batch(t)
        logp, entropy, aux = _score_tokens(p, cfg, t["tokens"])
        loss, metrics = algo.loss(logp, LossInputs(
            advantages=t["advantages"], mask=t["mask"],
            behav_logp=t.get("behav_logp"), versions=t.get("versions"),
            current_version=version, prox_logp=t.get("prox"),
            entropy=entropy), rl)
        return loss + aux, metrics

    def grads_of(p, t):
        if nmi == 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(p, t)
        micro = jax.tree.map(
            lambda x: x.reshape((nmi, mb_size // nmi) + x.shape[1:]), t)
        g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)

        # Accumulate weighted by each microbatch's response-token count:
        # the losses are masked *means*, so an equal average would
        # over-weight tokens in sparse microbatches relative to the
        # single-pass minibatch objective.
        def accum(carry, mi):
            g_acc, loss_acc, w_acc = carry
            w = jnp.sum(mi["mask"])
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, mi)
            g_acc = jax.tree.map(
                lambda a, g: a + w * g.astype(jnp.float32), g_acc, grads)
            return (g_acc, loss_acc + w * loss, w_acc + w), metrics

        (grads, loss, w_tot), ms = jax.lax.scan(
            accum, (g0, jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32)), micro)
        w_tot = jnp.maximum(w_tot, 1.0)
        grads = jax.tree.map(lambda g: g / w_tot, grads)
        return (loss / w_tot, _reduce_metrics(ms)), grads

    def minibatch_body(carry, t):
        p, o = carry
        (loss, metrics), grads = grads_of(p, t)
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

    (params, opt), stacked = jax.lax.scan(minibatch_body, (params, opt), mbt)
    out = _reduce_metrics(stacked)
    out["reward_mean"] = rewards.mean()
    out["staleness_mean"] = staleness_mean
    # response tokens that actually received a gradient (rows past
    # nmb * mb_size are dropped from the scan, so don't count them)
    out["tokens"] = jnp.sum(mask[: nmb * mb_size])
    assert set(out) == set(METRIC_KEYS), sorted(out)
    packed = jnp.stack([out[k].astype(jnp.float32) for k in METRIC_KEYS])
    return params, opt, packed


_STEP_STATICS = ("cfg", "rl", "algo", "num_minibatches", "num_microbatches",
                 "skip_nonfinite")
# Default engine donates only the optimizer state: the async runtime keeps
# older params alive as behavior policies (WeightStore / staleness history),
# so donating them would invalidate live behavior-policy buffers.
_train_step = jax.jit(_train_step_impl, static_argnames=_STEP_STATICS,
                      donate_argnums=(1,))
# Opt-in variant for pure synchronous loops that never re-read old params:
# donates params + opt, letting XLA update weights and moments in place.
_train_step_donating = jax.jit(_train_step_impl,
                               static_argnames=_STEP_STATICS,
                               donate_argnums=(0, 1))


# -------------------------------------------------------------------- driver
class Trainer:
    """One training engine. ``step`` = the paper's 'training step'.

    ``algo`` selects the policy-optimization algorithm: an ``Algorithm``
    instance from ``core.algorithms``, a registry name, or None (falls
    back to ``rl.algo`` / the deprecated ``rl.method`` string). The legacy
    ``method=`` keyword still works but emits a ``DeprecationWarning``.

    ``num_microbatches`` > 1 adds gradient accumulation *inside* the
    minibatch scan for batches that exceed memory. ``donate_params=True``
    selects the params-donating compiled step (only safe when no other
    component holds the previous weights)."""

    def __init__(self, cfg: ModelConfig, rl: Optional[RLConfig] = None,
                 algo=None, *, method: Optional[str] = None,
                 num_microbatches: int = 1, donate_params: bool = False,
                 skip_nonfinite: bool = False):
        if method is not None:
            warnings.warn(
                "Trainer(..., method=...) is deprecated; pass an Algorithm "
                "or registry name as `algo` (repro.core.algorithms)",
                DeprecationWarning, stacklevel=2)
            if algo is None:
                algo = method
        self.cfg = cfg
        self.rl = rl or RLConfig()
        self.algo = resolve_algorithm(algo, self.rl)
        self.num_microbatches = num_microbatches
        self.donate_params = donate_params
        # skip-step guard: non-finite minibatch updates are dropped on
        # device (params/opt unchanged) instead of poisoning the run; the
        # packed `nonfinite` metric counts them (resilience.guards)
        self.skip_nonfinite = skip_nonfinite
        self.last_host_syncs = 0  # host transfers in the most recent step

    @property
    def method(self) -> str:
        """Legacy spelling: the resolved algorithm's registry name."""
        return self.algo.name

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
            with span("prox_forward", algo=self.algo.name), \
                    annotate("prox_forward"):
                prox = recompute_prox_logp(state.params, self.cfg,
                                           batch.tokens)
                prox.block_until_ready()
            host_syncs += 1
        prox_time = time.perf_counter() - t0

        with span("train_update", algo=self.algo.name,
                  batch=int(B), minibatches=int(nmb)), \
                annotate("train_update"):
            step_fn = (_train_step_donating if self.donate_params
                       else _train_step)
            params, opt, packed = step_fn(
                state.params, state.opt, state.version, batch.tokens,
                batch.behav_logp, batch.response_mask, batch.versions,
                batch.rewards, prox, cfg=self.cfg, rl=rl, algo=self.algo,
                num_minibatches=nmb,
                num_microbatches=self.num_microbatches,
                skip_nonfinite=self.skip_nonfinite)

            # the single device->host transfer of the step
            values = jax.device_get(packed)
        host_syncs += 1
        out = {k: float(v) for k, v in zip(METRIC_KEYS, values)}
        out["prox_time_s"] = prox_time
        out["host_syncs"] = float(host_syncs)
        self.last_host_syncs = host_syncs
        self._publish_metrics(out)
        new_state = TrainState(params, opt, state.version + 1)
        return new_state, out

    # training-side metrics mirrored into the process-wide obs registry
    # (gauges: latest step's value; counters: lifetime accumulation), so
    # one ``registry.snapshot()`` / prometheus dump covers trainer state
    # alongside the serving facade.
    _GAUGE_KEYS = ("loss", "reward_mean", "entropy", "grad_norm",
                   "iw_max", "iw_min", "iw_mean", "kl", "clipped_frac",
                   "ratio_mean", "staleness_mean", "prox_time_s")
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
        logp, _, aux = _score_tokens(p, cfg, tokens)
        ce = -jnp.sum(logp * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return ce + aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    params, opt, _ = adam_update(grads, opt, params, rl)
    return params, opt, loss
