"""Training cells of the DeepSeek-V2 configuration: the a3po train step
alone, fed pre-made rollout-shaped batches, as ``bench.drivers.train``
runs the Qwen2 cells.

The program's ``ModelConfig`` is built from the configuration file: the
published widths, the depth given, and the share of the deployment the
file states (the router over all its outputs, the experts held here, the
vocabulary slice). Set-up builds ``Trainer`` with its state and runs its
first ``check_steps`` steps through ``Trainer.step``; the window keeps
stepping through the pool of batches until ``--seconds`` have passed.

Behavior log-probs are the benchmark's own bf16 forward
(``bench.reference_deepseek_v2`` at default precision) plus the batch's
seeded offset. The same forward counts, for each pool batch, the
assignments its real tokens give the experts held here in every expert
layer: the work of the routed experts, which ``bench.flops_moe`` costs.
The reference follows the check steps after the window.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import numpy as np

from bench import compare, flops_moe, reference as R, traffic
from bench import reference_deepseek_v2 as RD
from bench.drivers import common
from bench.drivers.train import _rl_dict


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a DeepSeek-V2 configuration file."""
    from repro.configs.base import (
        MLAConfig,
        ModelConfig,
        MoEConfig,
        YarnConfig,
    )

    rs = cfg.get("rope_scaling") or {}
    if (cfg["model_type"] != "deepseek_v2" or cfg["hidden_act"] != "silu"
            or cfg["q_lora_rank"] is not None
            or cfg["scoring_func"] != "softmax"
            or cfg["topk_method"] != "greedy" or cfg["moe_layer_freq"] != 1
            or rs.get("type") != "yarn"):
        raise SystemExit(f"bench: no program path for {cfg['name']}")
    s = RD.dims(cfg)
    return ModelConfig(
        name=cfg["name"], arch_type="moe", num_layers=s["L"],
        d_model=s["d"], num_heads=s["H"], num_kv_heads=s["H"],
        head_dim=s["v"], d_ff=s["ff_dense"], vocab_size=s["V"],
        moe=MoEConfig(num_experts=s["E"], top_k=s["top_k"],
                      d_ff_expert=s["ff"], num_shared_experts=s["shared"],
                      router_aux_weight=RD.AUX_WEIGHT,
                      held=(s["first"], s["n"]),
                      norm_topk_prob=cfg["norm_topk_prob"],
                      routed_scaling_factor=float(
                          cfg["routed_scaling_factor"])),
        mla=MLAConfig(kv_lora_rank=s["r"], qk_nope_head_dim=s["nope"],
                      qk_rope_head_dim=s["rope"], v_head_dim=s["v"]),
        first_dense_layers=s["k"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


def behavior_logps(cfg: Dict, key, batches: List[Dict], rows: int) -> None:
    """Adds ``behav_logp`` = policy log-probs (bf16 forward of the
    benchmark's own model) + the batch's seeded offset, masked, and
    ``moe_rows``: the assignments of the batch's real tokens (those its
    loss reads) to the experts held here, summed over the expert layers."""
    import jax
    import jax.numpy as jnp

    model = RD.Model(cfg, precision=jax.lax.Precision.DEFAULT)
    fwd = RD.Forward(model)
    w = RD.make_weights(cfg, key)
    for b in batches:
        toks = b["tokens"]
        need = RD.input_needs(b["response_mask"])
        cols = np.arange(toks.shape[1] - 1)
        lp = np.zeros(b["response_mask"].shape, np.float32)
        held = 0.0
        for r0 in range(0, toks.shape[0], rows):
            t = toks[r0:r0 + rows]
            x, counts = fwd.hidden(w, t[:, :-1],
                                   cols[None, :] < need[r0:r0 + rows, None])
            held += sum(counts)
            n, S, d = x.shape
            lp[r0:r0 + rows] = np.asarray(R.token_logp(
                model, x.reshape(n * S, d), w["final_norm"]["scale"],
                w["embedding"]["out_head"],
                jnp.asarray(t[:, 1:].reshape(-1)))).reshape(n, S)
        b["behav_logp"] = (lp + b["behav_offset"]) * b["response_mask"]
        b["moe_rows"] = held
        b["moe_tokens"] = float(need.sum())
    del w


def _reference_run(cfg, rl, cl, key, batches, **kw) -> Dict:
    """``bench.reference_deepseek_v2.Trainer`` over the check batches from
    its own weights; the change of each weight leaf replaces the final
    weights."""
    out = RD.Trainer(cfg, _rl_dict(rl), rows_per_block=cl["reference_rows"],
                     **kw).run(RD.make_weights(cfg, key), batches)
    wn = R.flatten(out.pop("weights"))
    w0 = R.flatten(RD.make_weights(cfg, key))
    out["dparam"] = {k: math.sqrt(float(R.sqdist(wn[k], w0[k])))
                     for k in w0}
    return out


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import RLConfig
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.training import trainer as T

    cfg, cl, mix = ctx.cfg, ctx.cell, ctx.mix
    mcfg = model_config(cfg)
    rl = RLConfig(group_size=mix["group_size"])
    key = common.seed_key(ctx.seed)
    n_check = cl["check_steps"]

    batches = traffic.rl_batches(mix, ctx.seed, cl["pool_batches"],
                                 cl["batch"], cfg["vocab_size"])
    t0 = time.perf_counter()
    behavior_logps(cfg, key, batches, cl["reference_rows"])
    behav_s = time.perf_counter() - t0
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
        prog_rows: List[float] = []
        m1 = None
        step_s: List[float] = []
        for i in range(n_check):
            t0 = time.perf_counter()
            state, m = trainer.step(state, dev_batches[i])
            step_s.append(time.perf_counter() - t0)
            prog_losses.append(m["loss"])
            prog_rows.append(m["moe_local_rows"])
            if i == 0:
                sq = jax.device_get(R.leaf_sqnorms(state.opt["m"]))
                m1 = {k: math.sqrt(float(v))
                      for k, v in R.flatten(sq).items()}
                rows_max = m["moe_expert_rows_max"]
        flat_now = R.flatten(state.params)
        dparam = {k: math.sqrt(float(R.sqdist(flat_now[k], jnp.asarray(v))))
                  for k, v in R.flatten(theta0).items()}
        del theta0, flat_now
        prog = dict(losses=prog_losses, m1=m1, dparam=dparam)

        # the window
        traced = common.TracedWindow(ctx) if ctx.trace else None
        trace_steps = cl["trace_steps"]
        k, steps, failed, tokens = n_check, 0, 0, 0
        traced_lengths: List[int] = []
        traced_rows = traced_tokens = 0.0
        traced_steps = 0
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
                    traced_rows += batches[b]["moe_rows"]
                    traced_tokens += batches[b]["moe_tokens"]
                    traced_steps += 1
                    if steps == 1 + trace_steps:
                        traced.stop()
                elif traced is not None and traced.pending:
                    traced.open()
                if time.perf_counter() - t_open >= ctx.seconds \
                        and not (traced is not None and traced.active):
                    break
        t_close = time.perf_counter()
        peak = common.memory_peak(ctx.chips)
        del state, trainer, dev_batches
    gc.collect()

    window_s = t_close - t_open
    if traced is not None:
        traced.reduce(labels=lambda n: n.startswith("bench.")
                      or n in ("train_update", "prox_forward"))
        ctx.data["train_flops"] = flops_moe.train_flops(
            cfg, traced_lengths, traced_rows)
        ctx.data.update(moe_local_rows=traced_rows,
                        moe_tokens=traced_tokens, traced_steps=traced_steps,
                        minibatches=min(rl.num_minibatches, cl["batch"]))

    # the reference follows the check steps from its own weights
    t_ref = time.perf_counter()
    ref = _reference_run(cfg, rl, cl, key, batches[:n_check])
    ref_s = time.perf_counter() - t_ref
    numbers = compare.train_numbers(prog, ref)
    # readings of the control and the planted faults, put in the
    # program's place (bench/readings.py asks for them)
    variant_s = {}
    for variant in ctx.data.get("variants", ()):
        kw = {"quant": variant} if variant == "fp8" else {"fault": variant}
        t0 = time.perf_counter()
        out = _reference_run(cfg, rl, cl, key, batches[:n_check], **kw)
        variant_s[variant] = round(time.perf_counter() - t0, 3)
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
              f"expert rows of the check steps: program moe_local_rows "
              f"{prog_rows}, the benchmark's bf16 forward "
              f"{[batches[i]['moe_rows'] for i in range(n_check)]}; first "
              f"step moe_expert_rows_max {rows_max}",
              f"set-up: behavior log-probs {behav_s:.3f} s, check steps "
              f"{[round(t, 3) for t in step_s]} s (the first compiles)",
              f"reference {ref_s:.3f} s for {n_check} steps; variants "
              f"{variant_s} s"])
