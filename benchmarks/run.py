"""Benchmark harness entry point — one bench per paper table/figure.

  fig1    -> bench_prox_time     (prox logprob computation time)
  table1  -> bench_training      (end-to-end training: time + reward,
                                  figs 2-6 statistics)
  roofline-> bench_roofline      (dry-run derived roofline per arch x mesh)
  kernels -> bench_kernels       (hot-spot microbenches)
  prefix  -> bench_prefix_cache  (radix prefix cache: shared prefills for
                                  GRPO-style grouped prompts)
  decode  -> bench_decode        (serving: per-token vs fused-horizon
                                  decode tokens/sec + host syncs)
  prefill -> bench_prefill       (serving: inline dense prefill vs the
                                  chunked prefill lane — TTFT + tok/s)
  load    -> bench_load          (serving: SLO-aware scheduling vs FIFO
                                  under trace-driven overload)
  load_multiarch -> bench_load --multiarch (serving: one overload trace
                                  against dense/SSM/hybrid towers with
                                  per-arch fitted cost models)
  resilience -> bench_resilience (fault tolerance: worker-crash MTTR,
                                  steps lost vs ckpt_every, checkpoint
                                  save/restore latency, publish retries)

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

from benchmarks.common import CsvOut
from repro.launch.compile_cache import enable_compile_cache

PHASE_JSON = (pathlib.Path(__file__).resolve().parent.parent
              / "experiments" / "bench_phases.json")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   choices=["fig1", "table1", "roofline", "kernels",
                            "prefix", "decode", "prefill", "load",
                            "load_multiarch", "resilience"])
    p.add_argument("--steps", type=int, default=30,
                   help="RL steps for the training bench")
    p.add_argument("--quick", action="store_true",
                   help="CI smoke mode: tiny step counts; skips the "
                        "kernels/roofline/prefix sections unless --only "
                        "is given")
    p.add_argument("--phase-json", default=None, metavar="FILE",
                   help="attach the span tracer and write a per-phase "
                        "(rollout/prefill/decode/train/publish) breakdown "
                        "JSON; defaults on under --quick")
    p.add_argument("--trace", default=None, metavar="FILE",
                   help="also export the full Chrome trace.json")
    args = p.parse_args()
    enable_compile_cache()
    steps = min(args.steps, 3) if args.quick else args.steps
    sft_steps = 10 if args.quick else 150

    phase_json = args.phase_json or (str(PHASE_JSON) if args.quick else None)
    tracer = None
    if phase_json or args.trace:
        from repro.obs.tracing import SpanTracer, install_tracer
        tracer = install_tracer(SpanTracer())

    csv = CsvOut()
    csv.header()
    failures = []

    def section(name, fn, skip_quick=False):
        if args.only and args.only != name:
            return
        if args.quick and skip_quick and not args.only:
            return
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            import traceback
            traceback.print_exc()

    from benchmarks import (bench_decode, bench_kernels, bench_load,
                            bench_prefill, bench_prefix_cache,
                            bench_prox_time, bench_resilience,
                            bench_roofline, bench_training)
    section("fig1", lambda: bench_prox_time.run(csv))
    section("kernels", lambda: bench_kernels.run(csv), skip_quick=True)
    section("roofline", lambda: bench_roofline.run(csv), skip_quick=True)
    section("prefix", lambda: bench_prefix_cache.run(csv), skip_quick=True)
    # quick mode keeps a decode row (tiny horizon sweep) but never
    # overwrites the committed experiment JSON (PR 3 convention)
    section("decode", lambda: bench_decode.run(csv, quick=args.quick,
                                               save_json=not args.quick))
    section("prefill", lambda: bench_prefill.run(csv, quick=args.quick,
                                                 save_json=not args.quick))
    section("load", lambda: bench_load.run(csv, quick=args.quick,
                                           save_json=not args.quick))
    section("load_multiarch",
            lambda: bench_load.run_multiarch(csv, quick=args.quick,
                                             save_json=not args.quick))
    section("resilience",
            lambda: bench_resilience.run(csv, quick=args.quick,
                                         save_json=not args.quick))
    section("table1", lambda: bench_training.run(
        csv, num_steps=steps, sft_steps=sft_steps,
        save_json=not args.quick))

    if tracer is not None:
        from repro.obs.tracing import phase_breakdown
        phases = phase_breakdown(tracer.events())
        if args.trace:
            tracer.export(args.trace)
            print(f"# trace -> {args.trace}", flush=True)
        if phase_json:
            pathlib.Path(phase_json).parent.mkdir(parents=True,
                                                  exist_ok=True)
            with open(phase_json, "w") as f:
                json.dump({"phases": phases,
                           "quick": args.quick,
                           "sections": args.only or "default"}, f, indent=2)
            print(f"# phase breakdown -> {phase_json}", flush=True)
        for name, st in sorted(phases.items()):
            print(f"# phase {name}: {st['total_s']:.3f}s over "
                  f"{st['count']} spans (mean {st['mean_ms']:.2f}ms)",
                  flush=True)

    if failures:
        print(f"# FAILED sections: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("# all benchmark sections completed")


if __name__ == "__main__":
    main()
