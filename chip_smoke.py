#!/usr/bin/env python3
"""Chip smoke test: the async A-3PO loop at Qwen2.5-1.5B width on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the FSDP-sharded trainer on four

One chip: each main-path Pallas kernel, compiled at the model's widths,
against its jnp reference; then three a3po training steps of Qwen2.5-1.5B
(every width as published, depth cut to ``ONE_CHIP_LAYERS``, random
weights from ``--seed``) fed by the paged continuous-batching engine
through the async orchestrator, as ``python -m repro.launch.train
--engine async`` drives them.

Four chips: three a3po steps of the full 28-layer model with params and
Adam moments FSDP-sharded over the local mesh, then a one-step parity
check of the trainer at ``ONE_CHIP_LAYERS`` on a one-device mesh against
the four-device mesh. Nothing else runs.

Everything runs in this one process: a chip belongs to one process. Every
figure printed is a smoke figure, not a benchmark. The last line of stdout
is one JSON object, ``{"ok": true, "device": {...}}``; any failed check
exits non-zero before it. There is no CPU path: without a TPU the script
exits 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import RLConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.launch.compile_cache import (  # noqa: E402
    cache_counts,
    compile_seconds,
    enable_compile_cache,
)

MODEL = "qwen2.5-1.5b"
# Depth on one chip, from compiles for a described v5e.
ONE_CHIP_LAYERS = 12
WHY_L = (
    "the a3po train step (32 sequences x 80 tokens, 2 minibatches, bf16 "
    "params, f32 Adam moments) needs 11.65 GiB at 10 layers, 12.93 at 12 "
    "and 13.99 at 13 of the chip's 15.75 GiB; beside it the rollout "
    "engine may still hold the previous version's params for an in-flight "
    "decode horizon (1.48 GiB at 12 layers, 1.57 at 13) plus its KV pool "
    "and decode temporaries (~0.1 GiB): 14.5 GiB at 12 layers fits, 15.7 "
    "at 13 does not")
STEPS = 3
N_PROMPTS, MAX_NEW = 8, 64           # x group 4 = 32 sequences
# A random-weight policy never answers an ArithmeticTask prompt right: all
# rewards are 0 and so are the group advantages. The entropy bonus keeps a
# gradient flowing through the whole model (and the entropy cotangent
# through the logprob kernel's backward), so the params do move.
RL = RLConfig(group_size=4, num_minibatches=2, learning_rate=2e-4,
              entropy_coef=1e-3)
# kernel-parity problem sizes: one train minibatch of tokens, and the
# rollout engine's pool geometry (AsyncOrchestrator's control plane)
TOKENS = 16 * 79
N_BLOCKS, BLOCK_SIZE, MAX_BLOCKS, SLOTS, CHUNK = 512, 8, 16, 32, 32
# Tolerances, fixed before the first chip run. The logprob forward feeds
# bf16 operands to the MXU with f32 accumulation, as the reference does
# at "highest" precision: only the summation order differs. The gradients
# and attention outputs round through bf16 (cotangents and softmax
# weights enter the MXU in bf16): relative L2 error.
TOL_LOGPROB_ABS = 2e-3
TOL_REL_L2 = 1e-2
# one-device vs four-device trainer step (bf16 model, different
# reduction orders): grad norm relative, loss absolute (the
# group-normalised a3po loss sits near zero)
TOL_PARITY_REL = 2e-2
TOL_PARITY_LOSS_ABS = 1e-3


def fail(what: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {what}")


def check(ok: bool, what: str) -> None:
    if not ok:
        fail(what)
    print(f"  ok   {what}", flush=True)


def smoke(label: str, value) -> None:
    print(f"  smoke figure (not a benchmark): {label} = {value}", flush=True)


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def holds_kernel(name: str, lowered) -> None:
    text = lowered.compile().as_text()
    check("tpu_custom_call" in text,
          f"{name}: the compiled program holds a Pallas kernel "
          f"(tpu_custom_call)")


def f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


def memory_stat(device, key: str = "peak_bytes_in_use") -> int:
    return int(device.memory_stats()[key])


# --------------------------------------------------------- kernel parity
def kernel_parity(cfg, seed: int) -> None:
    """Compiled kernels at the model's widths vs their jnp references."""
    from repro.kernels.decode_attn.ops import paged_decode_attention_op
    from repro.kernels.decode_attn.ref import paged_decode_attention_ref
    from repro.kernels.logprob.ops import token_logprob_entropy
    from repro.kernels.logprob.ref import token_logprob_entropy_ref
    from repro.kernels.prefill_attn.ops import paged_prefill_attention_op
    from repro.kernels.prefill_attn.ref import paged_prefill_attention_ref

    print("phase: kernel parity at real width", flush=True)
    d, V = cfg.d_model, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16
    h = jax.random.normal(k[0], (TOKENS, d), bf)
    w = (jax.random.normal(k[1], (d, V)) * d ** -0.5).astype(bf)
    t = jax.random.randint(k[2], (TOKENS,), 0, V)
    g = jax.random.uniform(k[3], (2, TOKENS), minval=0.5, maxval=1.5)

    fwd = jax.jit(token_logprob_entropy)
    holds_kernel("logprob forward", fwd.lower(h, w, t))
    lp, en = fwd(h, w, t)
    with jax.default_matmul_precision("highest"):
        lp_r, en_r = jax.jit(token_logprob_entropy_ref)(*f32(h, w), t)
    err = max(float(jnp.max(jnp.abs(lp - lp_r))),
              float(jnp.max(jnp.abs(en - en_r))))
    check(err <= TOL_LOGPROB_ABS,
          f"logprob forward vs reference: max abs err {err:.3e} "
          f"<= {TOL_LOGPROB_ABS}")

    def objective(fn):
        def f(h, w):
            lp, en = fn(h, w, t)
            return jnp.sum(g[0] * lp + g[1] * en)
        return jax.jit(jax.grad(f, argnums=(0, 1)))

    bwd = objective(token_logprob_entropy)
    holds_kernel("logprob forward+backward", bwd.lower(h, w))
    dh, dw = bwd(h, w)
    with jax.default_matmul_precision("highest"):
        dh_r, dw_r = objective(token_logprob_entropy_ref)(*f32(h, w))
    for name, a, b in (("d hidden", dh, dh_r), ("d w", dw, dw_r)):
        err = rel_l2(a, b)
        check(err <= TOL_REL_L2, f"logprob backward {name} vs jax.grad of "
              f"the reference: rel L2 err {err:.3e} <= {TOL_REL_L2}")
    del w, dw, dw_r

    rng = np.random.default_rng(seed)
    tables = rng.permutation(N_BLOCKS)[: SLOTS * MAX_BLOCKS].reshape(
        SLOTS, MAX_BLOCKS)
    lengths = rng.integers(CHUNK // 4, MAX_BLOCKS * BLOCK_SIZE + 1,
                           size=SLOTS)
    for s in range(SLOTS):  # unmapped tail, as the serving engine leaves it
        tables[s, -(-int(lengths[s]) // BLOCK_SIZE):] = -1
    tables, lengths = jnp.asarray(tables, jnp.int32), jnp.asarray(lengths)
    pool = (N_BLOCKS, KV, BLOCK_SIZE, hd)
    pk = jax.random.normal(k[4], pool, bf)
    pv = jax.random.normal(k[5], pool, bf)

    q = jax.random.normal(k[6], (SLOTS, H, hd), bf)
    dec = jax.jit(paged_decode_attention_op)
    holds_kernel("paged decode", dec.lower(q, pk, pv, tables, lengths))
    out = dec(q, pk, pv, tables, lengths)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_decode_attention_ref)(*f32(q, pk, pv), tables,
                                                  lengths)
    err = rel_l2(out, ref)
    check(err <= TOL_REL_L2,
          f"paged decode vs reference: rel L2 err {err:.3e} <= {TOL_REL_L2}")

    # a packed chunk: four prompts' last 7 positions each, 4 padding rows
    n_seg, per = 4, CHUNK // 4 - 1
    seg = np.full((CHUNK,), -1, np.int32)
    pos = np.zeros((CHUNK,), np.int32)
    for s in range(n_seg):
        rows = slice(s * per, (s + 1) * per)
        seg[rows] = s
        pos[rows] = int(lengths[s]) - per + np.arange(per)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    qc = jax.random.normal(k[7], (CHUNK, H, hd), bf)
    pre = jax.jit(paged_prefill_attention_op)
    holds_kernel("paged prefill",
                 pre.lower(qc, pk, pv, tables, seg, pos, lengths))
    out = pre(qc, pk, pv, tables, seg, pos, lengths)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_prefill_attention_ref)(*f32(qc, pk, pv),
                                                   tables, seg, pos)
    err = rel_l2(out, ref)
    check(err <= TOL_REL_L2,
          f"paged prefill vs reference: rel L2 err {err:.3e} "
          f"<= {TOL_REL_L2}")


# ------------------------------------------------------------ async loop
def _fingerprint(params) -> np.ndarray:
    leaves = jax.tree.leaves(params)
    return np.asarray(jax.jit(lambda xs: jnp.stack(
        [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in xs]))(leaves))


def async_loop(cfg, seed: int) -> None:
    """Three a3po steps through AsyncOrchestrator and the paged engine."""
    from repro.async_rl.orchestrator import AsyncOrchestrator
    from repro.data.tasks import ArithmeticTask
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.launch.mesh import make_local_mesh
    from repro.resilience import ResilienceConfig
    from repro.rollout import continuous
    from repro.training import trainer as T

    print(f"phase: async a3po loop, {STEPS} steps, "
          f"{N_PROMPTS * RL.group_size} sequences x {MAX_NEW} new tokens",
          flush=True)
    task = ArithmeticTask(seed=seed)
    mesh = make_local_mesh()
    with mesh, use_sharding(ShardingEnv(mesh)):
        trainer = T.Trainer(cfg, RL, "a3po")
        state = trainer.init_state(jax.random.PRNGKey(seed))
        before = _fingerprint(state.params)
        # no faults, guard or checkpoints: only the waits are bounded, so
        # that a cold compile of the first rollout does not count as a
        # hung worker, and a worker crash fails the run at once
        waits = ResilienceConfig(max_worker_restarts=0,
                                 heartbeat_timeout_s=900.0,
                                 pop_deadline_s=900.0)
        orch = AsyncOrchestrator(cfg, RL, task, "a3po", n_prompts=N_PROMPTS,
                                 max_new_tokens=MAX_NEW,
                                 use_control_plane=True, seed=seed,
                                 resilience=waits)
        t0 = time.perf_counter()
        state, recs = orch.run(state, STEPS)
        wall = time.perf_counter() - t0
        after = _fingerprint(state.params)

        check(len(recs) == STEPS, f"{STEPS} training steps ran")
        for r in recs:
            check(math.isfinite(r.loss),
                  f"step {r.step}: loss {r.loss:+.4e} is finite")
            check(r.train_tokens > 0,
                  f"step {r.step}: {r.train_tokens:.0f} generated tokens "
                  f"trained on")
        check(int(state.version) == STEPS,
              f"weight version advanced 0 -> {int(state.version)}")
        changed = int(np.sum(before != after))
        check(changed > 0, f"params changed ({changed}/{len(before)} "
              f"tensors)")
        serving = recs[-1].serving
        check(serving["decode_tokens"] > 0,
              f"paged engine decoded {serving['decode_tokens']:.0f} tokens")
        check(serving["prefill_chunks"] > 0,
              f"chunked prefill lane ran {serving['prefill_chunks']:.0f} "
              f"chunks")

        B = N_PROMPTS * RL.group_size
        T_ = task.prompt_len + MAX_NEW
        sds = jax.ShapeDtypeStruct
        batch = (sds((B, T_), jnp.int32), sds((B, T_ - 1), jnp.float32),
                 sds((B, T_ - 1), jnp.float32), sds((B, T_ - 1), jnp.int32),
                 sds((B,), jnp.float32))
        holds_kernel("a3po train step", T._train_step.lower(
            state.params, state.opt, state.version, *batch, None, cfg=cfg,
            rl=RL, algo=trainer.algo, num_minibatches=RL.num_minibatches,
            num_microbatches=1, skip_nonfinite=False))
        eng = orch.control_plane.engine
        st = eng.state
        holds_kernel("decode horizon", continuous._paged_decode_horizon.lower(
            state.params, cfg, st.pool_k, st.pool_v, st.block_tables,
            st.seq_lens, sds((eng.max_seqs, cfg.vocab_size), jnp.float32),
            sds((eng.max_seqs,), jnp.int32), jax.random.PRNGKey(0),
            trash_block=eng.trash_block, horizon=eng.decode_horizon,
            temperature=eng.rl.temperature, top_p=eng.rl.top_p,
            greedy=eng.greedy))

    for r in recs:
        smoke(f"step {r.step} seconds (train / rollout mean / wall)",
              f"{r.train_time_s:.3f} / {r.rollout_time_s:.3f} / "
              f"{r.wall_time_s:.3f}")
    smoke("loop wall seconds, first compiles included", f"{wall:.3f}")
    smoke("tokens decoded by the paged engine",
          f"{serving['decode_tokens']:.0f}")
    smoke("response tokens trained on",
          f"{sum(r.train_tokens for r in recs):.0f}")


# ------------------------------------------------------------ four chips
def _synthetic_batch(cfg, seed: int):
    """A rollout-shaped a3po batch from ArithmeticTask prompts: 64 random
    response tokens and 0/1 rewards. ``behav_logp`` holds only the
    behavior policy's offset from the current policy; ``_train`` adds the
    current policy's own logps before each step, so importance ratios stay
    near 1 and the step carries a real gradient."""
    from repro.data.tasks import ArithmeticTask
    from repro.training.trainer import TrainBatch

    group = RL.group_size
    B = N_PROMPTS * group
    prompts = np.repeat(ArithmeticTask(seed=seed).sample(N_PROMPTS).prompts,
                        group, axis=0)
    P = prompts.shape[1]
    rng = np.random.default_rng(seed)
    resp = rng.integers(0, cfg.vocab_size, (B, MAX_NEW))
    tokens = np.concatenate([prompts, resp], axis=1).astype(np.int32)
    mask = np.zeros((B, P + MAX_NEW - 1), np.float32)
    mask[:, P - 1:] = 1.0
    offset = 0.3 * rng.standard_normal(mask.shape) * mask
    return TrainBatch(tokens=jnp.asarray(tokens),
                      response_mask=jnp.asarray(mask),
                      behav_logp=jnp.asarray(offset, jnp.float32),
                      versions=jnp.zeros((B,), jnp.int32),
                      rewards=jnp.asarray(rng.integers(0, 2, B), jnp.float32))


def _train(cfg, rl, mesh, batch, steps: int, seed: int):
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.training.trainer import Trainer, score_tokens

    with mesh, use_sharding(ShardingEnv(mesh)):
        trainer = Trainer(cfg, rl, "a3po")
        state = trainer.init_state(jax.random.PRNGKey(seed))
        jax.block_until_ready(state)
        in_use = [memory_stat(d, "bytes_in_use") for d in mesh.devices.flat]
        metrics = []
        for _ in range(steps):
            # a fresh rollout of the current policy, as the async loop
            # would deliver it at staleness 0
            logp, _, _ = score_tokens(state.params, cfg, batch.tokens)
            fresh = dataclasses.replace(
                batch,
                behav_logp=batch.behav_logp + logp * batch.response_mask,
                versions=jnp.full_like(batch.versions, state.version))
            state, m = trainer.step(state, fresh)
            metrics.append(m)
    return metrics, in_use


def four_chips(base, seed: int) -> None:
    from jax.sharding import Mesh
    from repro.launch.mesh import make_local_mesh

    devices = jax.devices()
    if len(devices) != 4:
        fail(f"--chips 4 needs four devices, found {len(devices)}")
    batch = _synthetic_batch(base, seed)
    mesh4 = make_local_mesh()
    GiB = 2 ** 30

    full = dataclasses.replace(base, dtype="bfloat16")
    print(f"phase: full {full.num_layers}-layer {MODEL} "
          f"({full.num_params() / 1e9:.3f}B params), FSDP over mesh "
          f"{dict(mesh4.shape)}, {STEPS} a3po steps", flush=True)
    t0 = time.perf_counter()
    metrics, in_use = _train(full, RL, mesh4, batch, STEPS, seed)
    wall = time.perf_counter() - t0
    for i, m in enumerate(metrics):
        check(math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"]),
              f"step {i}: loss {m['loss']:+.6f}, grad norm "
              f"{m['grad_norm']:.4f} finite")
    peaks = [memory_stat(d) for d in devices]
    state_bytes = full.num_params() * (2 + 4 + 4)  # bf16 params, f32 m, v
    smoke("per-device bytes in use after init, GiB",
          [round(b / GiB, 3) for b in in_use])
    smoke("per-device peak_bytes_in_use, GiB",
          [round(b / GiB, 3) for b in peaks])
    check(max(in_use) < 0.3 * state_bytes,
          f"each device holds its quarter of the state: max "
          f"{max(in_use) / GiB:.2f} GiB < 0.3 x {state_bytes / GiB:.2f} GiB")
    check(max(peaks) < 2.0 * min(peaks),
          "peak memory spread over all four chips (max < 2 x min)")
    smoke("wall seconds for init + 3 steps, compiles included",
          f"{wall:.3f}")

    cfg = dataclasses.replace(base, num_layers=ONE_CHIP_LAYERS,
                              dtype="bfloat16")
    rl1 = dataclasses.replace(RL, num_minibatches=1)
    print(f"phase: parity at {ONE_CHIP_LAYERS} layers, one a3po step on a "
          f"1-device mesh vs the 4-device mesh", flush=True)
    mesh1 = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    (m1,), _ = _train(cfg, rl1, mesh1, batch, 1, seed)
    (m4,), _ = _train(cfg, rl1, mesh4, batch, 1, seed)
    check(m1["grad_norm"] > 1e-3, f"the parity step carries a gradient "
          f"(grad norm {m1['grad_norm']:.4f})")
    err = abs(m4["grad_norm"] - m1["grad_norm"]) / m1["grad_norm"]
    check(err <= TOL_PARITY_REL,
          f"grad norm: 1-device {m1['grad_norm']:.6f} vs 4-device "
          f"{m4['grad_norm']:.6f}, rel err {err:.3e} <= {TOL_PARITY_REL}")
    err = abs(m4["loss"] - m1["loss"])
    check(err <= TOL_PARITY_LOSS_ABS,
          f"loss: 1-device {m1['loss']:+.6f} vs 4-device "
          f"{m4['loss']:+.6f}, abs err {err:.3e} <= {TOL_PARITY_LOSS_ABS}")


# ------------------------------------------------------------------ main
def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the FSDP-sharded trainer and its "
                        "1-vs-4-device parity check")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}; there "
              f"is no CPU path", file=sys.stderr)
        sys.exit(2)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    base = get_config(MODEL)
    if args.chips == 4:
        four_chips(base, args.seed)
    else:
        cfg = dataclasses.replace(base, num_layers=ONE_CHIP_LAYERS,
                                  dtype="bfloat16")
        print(f"config: {MODEL} at full width (d_model {cfg.d_model}, "
              f"{cfg.num_heads} q / {cfg.num_kv_heads} kv heads, head_dim "
              f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
              f"{cfg.vocab_size}), depth L={cfg.num_layers} of "
              f"{base.num_layers}, {cfg.num_params() / 1e9:.3f}B params, "
              f"bf16", flush=True)
        print(f"why L={ONE_CHIP_LAYERS}: {WHY_L}", flush=True)
        kernel_parity(cfg, args.seed)
        async_loop(cfg, args.seed)
        smoke("peak_bytes_in_use, GiB", f"{memory_stat(dev) / 2 ** 30:.3f}")

    for name, secs in sorted(compile_seconds().items(),
                             key=lambda kv: -kv[1])[:8]:
        smoke(f"compile seconds, {name}", f"{secs:.3f}")
    smoke("compile seconds, all programs",
          f"{sum(compile_seconds().values()):.3f}")
    counts = cache_counts()
    print(f"compile cache: {counts['hits']} hits, {counts['misses']} misses"
          f" ({'hit' if counts['hits'] else 'no hits'})", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
