"""Training cells: the a3po train step alone, fed pre-made rollout-shaped
batches; no rollout runs.

Set-up builds the one object the window drives, ``Trainer`` with its state
(weights from the seed, made on the device by ``init_state``), and runs its
first ``check_steps`` steps through the window's own call,
``Trainer.step``, on batches whose rows all differ; those steps compile
every program the window uses. The window keeps stepping through the same
pool of batches until ``--seconds`` have passed, closing at the end of the
first step that ends after it.

Behavior log-probs are the current policy's log-probs plus a seeded
offset, as ``assemble_train_batch`` would deliver a rollout that is a few
versions stale. The policy's log-probs come from the benchmark's own
forward pass at bfloat16 (``bench.reference`` at default precision over
weights made from the same seed), never from the program. Importance
weights then sit near 1, the clipped surrogate carries a gradient on every
unclipped token, and the iw cap and the clip keep every step finite for as
long as the window runs, since the learning rate moves the weights far less
than the offset.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import numpy as np

from bench import compare, flops, reference, traffic
from bench.drivers import common


def _rl_dict(rl) -> Dict:
    keys = ("learning_rate", "adam_b1", "adam_b2", "adam_eps",
            "max_grad_norm", "clip_eps", "behav_weight_cap",
            "num_minibatches", "group_size")
    return {k: getattr(rl, k) for k in keys}


def behavior_logps(cfg: Dict, key, batches: List[Dict], rows: int) -> None:
    """Adds ``behav_logp`` = policy log-probs (bf16 forward of the
    benchmark's own model) + the batch's seeded offset, masked."""
    import jax
    import jax.numpy as jnp

    model = reference.Model(cfg, precision=jax.lax.Precision.DEFAULT)
    fwd = reference.Forward(model)
    w = reference.make_weights(cfg, key)
    for b in batches:
        toks = b["tokens"]
        lp = np.zeros(b["response_mask"].shape, np.float32)
        for r0 in range(0, toks.shape[0], rows):
            t = toks[r0:r0 + rows]
            x = fwd.hidden(w, t[:, :-1])
            n, S, d = x.shape
            lp[r0:r0 + rows] = np.asarray(reference.token_logp(
                model, x.reshape(n * S, d), w["final_norm"]["scale"],
                w["embedding"]["embed"],
                jnp.asarray(t[:, 1:].reshape(-1)))).reshape(n, S)
        b["behav_logp"] = (lp + b["behav_offset"]) * b["response_mask"]
    del w


def _reference_run(cfg, rl, cl, key, batches, **kw) -> Dict:
    """``bench.reference.Trainer`` over the check batches from its own
    weights; the change of each weight leaf replaces the final weights."""
    out = reference.Trainer(cfg, _rl_dict(rl),
                            rows_per_block=cl["reference_rows"], **kw).run(
        reference.make_weights(cfg, key), batches)
    wn = reference.flatten(out.pop("weights"))
    w0 = reference.flatten(reference.make_weights(cfg, key))
    out["dparam"] = {k: math.sqrt(float(reference.sqdist(wn[k], w0[k])))
                     for k in w0}
    return out


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import RLConfig
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.training import trainer as T

    cfg, cl, mix = ctx.cfg, ctx.cell, ctx.mix
    mcfg = common.model_config(cfg)
    rl = RLConfig(group_size=mix["group_size"])
    key = common.seed_key(ctx.seed)
    n_check = cl["check_steps"]

    batches = traffic.rl_batches(mix, ctx.seed, cl["pool_batches"],
                                 cl["batch"], cfg["vocab_size"])
    behavior_logps(cfg, key, batches, cl["reference_rows"])
    real_tokens = [int(b["lengths"].sum()) for b in batches]

    mesh = common.local_mesh(ctx.chips)
    with mesh, use_sharding(ShardingEnv(mesh)):
        trainer = T.Trainer(mcfg, rl, "a3po")
        state = trainer.init_state(key)
        dev_batches = [T.TrainBatch(
            tokens=jnp.asarray(b["tokens"]),
            response_mask=jnp.asarray(b["response_mask"]),
            behav_logp=jnp.asarray(b["behav_logp"]),
            versions=jnp.asarray(b["versions"]),
            rewards=jnp.asarray(b["rewards"])) for b in batches]

        # the check steps: the window's own call on distinct batches
        theta0 = jax.device_get(state.params)
        prog_losses: List[float] = []
        m1 = None
        for i in range(n_check):
            state, m = trainer.step(state, dev_batches[i])
            prog_losses.append(m["loss"])
            if i == 0:
                sq = jax.device_get(reference.leaf_sqnorms(state.opt["m"]))
                m1 = {k: math.sqrt(float(v))
                      for k, v in reference.flatten(sq).items()}
        flat_now = reference.flatten(state.params)
        dparam = {k: math.sqrt(float(reference.sqdist(flat_now[k],
                                                      jnp.asarray(v))))
                  for k, v in reference.flatten(theta0).items()}
        del theta0, flat_now
        prog = dict(losses=prog_losses, m1=m1, dparam=dparam)

        # the window
        traced = common.TracedWindow(ctx) if ctx.trace else None
        trace_steps = cl["trace_steps"]
        k, steps, failed, tokens = n_check, 0, 0, 0
        traced_lengths: List[int] = []
        t_open = time.perf_counter()
        with common.window(ctx):
            while True:
                if traced is not None and steps == 0:
                    traced.start()
                b = k % len(dev_batches)
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    state, m = trainer.step(state, dev_batches[b])
                k += 1
                steps += 1
                tokens += real_tokens[b]
                if not (math.isfinite(m["loss"])
                        and math.isfinite(m["grad_norm"])):
                    failed += 1
                if traced is not None and traced.active:
                    traced_lengths.extend(batches[b]["lengths"].tolist())
                    if steps == 1 + trace_steps:
                        traced.stop()
                elif traced is not None and traced.pending:
                    traced.open()
                if time.perf_counter() - t_open >= ctx.seconds \
                        and not (traced is not None and traced.active):
                    break
        t_close = time.perf_counter()
        peak = common.memory_peak(ctx.chips)
        if traced is not None:
            ctx.data["logprob_rows"] = (cl["batch"] // rl.num_minibatches
                                        * (mix["pad_to"] - 1))
        del state, trainer, dev_batches
    gc.collect()

    window_s = t_close - t_open
    if traced is not None:
        traced.reduce(labels=lambda n: n.startswith("bench.")
                      or n in ("train_update", "prox_forward"))
        ctx.data["train_flops"] = flops.train_flops(cfg, traced_lengths)

    # the reference follows the check steps from its own weights
    t_ref = time.perf_counter()
    ref = _reference_run(cfg, rl, cl, key, batches[:n_check])
    ref_s = time.perf_counter() - t_ref
    numbers = compare.train_numbers(prog, ref)
    # readings of the control and the planted faults, put in the
    # program's place (bench/readings.py asks for them)
    for variant in ctx.data.get("variants", ()):
        kw = {"quant": variant} if variant == "fp8" else {"fault": variant}
        out = _reference_run(cfg, rl, cl, key, batches[:n_check], **kw)
        ctx.data.setdefault("variant_numbers", {})[variant] = \
            compare.train_numbers(out, ref)

    return dict(
        e2e={"setup_s": t_open - ctx.t_start,
             "train_tokens_per_s": tokens / window_s},
        attempted=steps, failed=failed, numbers=numbers,
        memory_peak_bytes=peak,
        info=[f"window {window_s:.3f} s, {steps} steps, {tokens} real "
              f"tokens ({tokens / (steps * cl['batch'] * mix['pad_to']):.4f}"
              f" of padded positions)",
              f"check losses program {prog_losses} reference "
              f"{ref['losses']}",
              f"reference {ref_s:.3f} s for {n_check} steps"])
