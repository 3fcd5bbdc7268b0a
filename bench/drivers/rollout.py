"""Rollout cells: GRPO groups through the serving control plane and the
paged engine, closed loop; the trainer is bypassed.

The control plane is built by ``AsyncOrchestrator._build_control_plane``,
the construction the async loop uses, so every serving knob stays at the
program's default; the cell sets only the deployment's sizing (slots, KV
blocks, blocks per sequence). Weights come from the seed through the
program's ``init_params``, on the device.

``outstanding_groups`` groups are always submitted: when the last member
of a group finishes, a new group is submitted at once, so the queue never
runs dry. Warm-up runs until ``warmup_finished`` of the first
``warmup_of`` sequences have finished, so the in-flight set has mixed ages
and every program the window uses has been built. A sequence's time runs
from its ``submit`` to the step that returns it finished, on this
process's clock.

After the window closes the reference runs over what the served path
produced: the tokens of a seeded sample of the sequences finished in the
window (the longest among them), and the next-token logits of a seeded
sample of the slots in flight at the close.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import numpy as np

from bench import compare, flops, reference, traffic
from bench.drivers import common


def _sample(rng, items: List, n: int, first=None) -> List:
    """``n`` of ``items`` in a seeded order, ``first`` among them."""
    order = [items[i] for i in rng.permutation(len(items))]
    if first is not None:
        order = [first] + [x for x in order if x != first]
    return order[:n]


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.async_rl.orchestrator import AsyncOrchestrator
    from repro.async_rl.weights import WeightStore
    from repro.configs.base import RLConfig
    from repro.data.tasks import ArithmeticTask
    from repro.models import model as M
    from repro.obs import tracing

    cfg, cl, mix = ctx.cfg, ctx.cell, ctx.mix
    mcfg = common.model_config(cfg)
    rl = RLConfig(group_size=mix["group_size"])
    key = common.seed_key(ctx.seed)
    params = jax.jit(M.init_params, static_argnums=0)(mcfg, key)

    orch = AsyncOrchestrator(mcfg, rl, ArithmeticTask(seed=0), "a3po",
                             n_prompts=cl["max_seqs"] // rl.group_size,
                             use_control_plane=True,
                             serve_kwargs=dict(
                                 max_seqs=cl["max_seqs"],
                                 n_blocks=cl["n_blocks"],
                                 max_blocks_per_seq=cl["max_blocks_per_seq"]))
    store = WeightStore(params, 0)
    cp = orch._build_control_plane(store)
    eng = cp.engine
    groups = traffic.rollout_groups(mix, ctx.seed, cfg["vocab_size"])
    sk = jax.random.fold_in(key, 1)

    reqs: Dict[int, Dict] = {}          # rid -> bookkeeping
    open_groups: Dict[int, int] = {}    # group -> members not finished

    def submit_group(g: int) -> None:
        prompt, budgets = next(groups)
        open_groups[g] = len(budgets)
        for b in budgets:
            rid = cp.submit(prompt, max_new=b)
            reqs[rid] = dict(group=g, t_submit=time.perf_counter(),
                             prompt_len=len(prompt), budget=b, t_done=None)

    n_groups = mix["outstanding_groups"]
    for g in range(n_groups):
        submit_group(g)
    next_group = n_groups

    finished_reqs: Dict[int, object] = {}
    contexts: List[int] = []            # attended tokens per emitted token

    def step() -> List:
        nonlocal sk, next_group
        sk, sub = jax.random.split(sk)
        with jax.profiler.TraceAnnotation("bench.serve_step"):
            fin = cp.step(sub)
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.traffic"):
            for r in fin:
                info = reqs[r.rid]
                info["t_done"] = now
                finished_reqs[r.rid] = r
                g = info["group"]
                open_groups[g] -= 1
                if open_groups[g] == 0:
                    del open_groups[g]
                    submit_group(next_group)
                    next_group += 1
        return fin

    # warm-up: until enough of the first sequences have finished
    first = set(sorted(reqs)[: cl["warmup_of"]])
    t_w = time.perf_counter()
    n_steps, t_log = 0, t_w
    while True:
        n_first = sum(1 for r in first if reqs[r]["t_done"] is not None)
        now = time.perf_counter()
        if n_first >= cl["warmup_finished"] or now - t_log >= 20.0:
            t_log = now
            ctx.log(f"warm-up {now - t_w:.1f} s: {n_steps} steps, "
                    f"{n_first} of the first {len(first)} finished, "
                    f"{len(eng.decode_ready_slots())} decoding, "
                    f"{len(eng.prefilling_slots())} prefilling, "
                    f"{len(cp.scheduler)} queued, {eng.tokens_emitted} "
                    f"tokens, prefill {cp.metrics.prefill_time_s:.1f} s, "
                    f"decode {cp.metrics.decode_time_s:.1f} s")
        if n_first >= cl["warmup_finished"]:
            break
        if now - t_w > cl["warmup_limit_s"]:
            raise RuntimeError("warm-up did not finish in time")
        step()
        n_steps += 1
    warm_release(eng, cl["max_seqs"])

    traced = common.TracedWindow(ctx) if ctx.trace else None
    tracer = tracing.SpanTracer() if ctx.trace else None
    tok0 = eng.tokens_emitted
    done_before = set(finished_reqs)
    t_open = time.perf_counter()
    with common.window(ctx):
        if traced is not None:
            traced.start()
        while True:
            in_trace = traced is not None and traced.active
            snap = ({s: (r.rid, len(r.prompt) + len(r.generated))
                     for s, r in eng.slots.items()
                     if r is not None and r.prefill_done}
                    if in_trace else None)
            step()
            if in_trace:
                # every token emitted in this step attends its prefix
                for s, (rid, ctx0) in snap.items():
                    r = finished_reqs.get(rid) or eng.slots.get(s)
                    if r is None or r.rid != rid:
                        continue
                    n = len(r.prompt) + len(r.generated) - ctx0
                    contexts.extend(ctx0 + 1 + j for j in range(n))
                if time.perf_counter() - traced.t0 >= cl["trace_seconds"]:
                    traced.stop()
                    tracing.install_tracer(None)
            elif traced is not None and traced.pending:
                tracing.install_tracer(tracer, annotate_jax=True)
                traced.open()
            if time.perf_counter() - t_open >= ctx.seconds \
                    and not (traced is not None and traced.active):
                break
    t_close = time.perf_counter()
    window_s = t_close - t_open
    emitted = eng.tokens_emitted - tok0
    peak = common.memory_peak(ctx.chips)

    done = [rid for rid, info in reqs.items()
            if info["t_done"] is not None and t_open <= info["t_done"]
            <= t_close and rid not in done_before]
    lat = [reqs[rid]["t_done"] - reqs[rid]["t_submit"] for rid in done]
    in_flight = [r for r in eng.slots.values() if r is not None]
    dropped = len(cp.dropped_requests)

    # what the served path produced, for the reference
    rng = traffic.rng_for(ctx.seed, "check")
    longest = max(done, key=lambda rid: len(finished_reqs[rid].generated),
                  default=None)
    served = [dict(tokens=np.concatenate([finished_reqs[rid].prompt,
                                          finished_reqs[rid].generated]),
                   P=len(finished_reqs[rid].prompt),
                   logp=np.asarray(finished_reqs[rid].gen_logp, np.float64))
              for rid in _sample(rng, done, cl["check_finished"], longest)]
    ready = [s for s in sorted(eng.slots) if eng.slots[s] is not None
             and eng.slots[s].prefill_done]
    pick = _sample(rng, ready, cl["check_inflight"])
    rows = np.asarray(eng._next_logits[jnp.asarray(pick, jnp.int32)])
    inflight = [dict(tokens=np.concatenate(
                         [eng.slots[s].prompt,
                          np.asarray(eng.slots[s].generated, np.int64)]),
                     logits=rows[i]) for i, s in enumerate(pick)]

    if traced is not None:
        ctx.data["spans"] = tracer.events()
        ctx.data["decode_contexts"] = contexts
        ctx.data["decode_flops"] = flops.decode_flops(cfg, contexts)
    del cp, eng, orch, store, params, rows
    gc.collect()
    if traced is not None:
        traced.reduce(labels=lambda n: n.startswith("bench.")
                      or n in ("decode_horizon",))

    t_ref = time.perf_counter()
    check_reference(cfg, key, served, inflight)
    ref_s = time.perf_counter() - t_ref
    numbers = compare.rollout_numbers(inflight, served)
    # the control: the reference one precision lower in the program's
    # place, over the same tokens (bench/readings.py asks for it)
    for variant in ctx.data.get("variants", ()):
        c_served = [dict(tokens=s["tokens"], P=s["P"]) for s in served]
        c_inflight = [dict(tokens=s["tokens"]) for s in inflight]
        check_reference(cfg, key, c_served, c_inflight, quant=variant)
        for c, s in zip(c_served, served):
            c["logp"] = c["ref_logp"]
            c["ref_logp"] = s["ref_logp"]
        for c, s in zip(c_inflight, inflight):
            c["logits"], c["ref_logits"] = c["ref_logits"], s["ref_logits"]
        ctx.data.setdefault("variant_numbers", {})[variant] = \
            compare.rollout_numbers(c_inflight, c_served)
    n_served = sum(len(s["logp"]) for s in served)
    p50, p95 = (np.percentile(lat, [50, 95]) if lat else (math.nan,) * 2)
    return dict(
        e2e={"setup_s": t_open - ctx.t_start,
             "rollout_tokens_per_s": emitted / window_s,
             "rollout_seq_s_p95": float(p95)},
        attempted=len(done) + len(in_flight), failed=dropped,
        numbers=numbers, memory_peak_bytes=peak,
        info=[f"window {window_s:.3f} s, {emitted} tokens, {len(done)} "
              f"sequences completed (p50 {p50:.3f} s, "
              f"p95 {p95:.3f} s), {len(in_flight)} in "
              f"flight, {dropped} dropped",
              f"reference {ref_s:.3f} s over {len(served)} finished "
              f"sequences ({n_served} served tokens) and {len(inflight)} "
              f"slots in flight"])


def warm_release(eng, max_seqs: int) -> None:
    """The engine frees the slots that finish in a horizon with one scatter
    whose shape is the number of them: build the programs of every count
    in set-up, so that none is built inside the window. The results are
    dropped; the engine's state is not touched."""
    import jax
    import jax.numpy as jnp

    st, out = eng.state, None
    for k in range(1, max_seqs + 1):
        idx = jnp.asarray(np.arange(k, dtype=np.int32))
        out = (st.block_tables.at[idx].set(jnp.asarray(eng._tables[:k])),
               st.seq_lens.at[idx].set(0))
    jax.block_until_ready(out)


def check_reference(cfg: Dict, key, served: List[Dict],
                    inflight: List[Dict], quant=None) -> None:
    """Fills ``ref_logp`` of each served sequence and
    ``ref_logits`` of each slot in flight, from the reference's own
    weights (or, with ``quant``, the reference one precision lower)."""
    model = reference.Model(cfg, quant=quant)
    fwd = reference.Forward(model)
    w = reference.make_weights(cfg, key)
    fs, E = w["final_norm"]["scale"], w["embedding"]["embed"]
    for s in served:
        toks, P = s["tokens"], s["P"]
        x = fwd.hidden(w, toks[None, :-1])[0, P - 1:]
        lg = np.asarray(fwd.logits(x, fs, E), np.float64)
        gen = toks[P:]
        lse = np.log(np.sum(np.exp(lg - lg.max(1, keepdims=True)), 1)) \
            + lg.max(1)
        s["ref_logp"] = lg[np.arange(len(gen)), gen] - lse
    for s in inflight:
        x = fwd.hidden(w, s["tokens"][None, :])[0, -1:]
        s["ref_logits"] = np.asarray(fwd.logits(x, fs, E))[0]
