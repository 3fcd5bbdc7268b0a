"""Production training launcher.

``--mesh local`` builds a mesh over the devices present, shards params and
Adam moments with the logical rules, and drives async A-3PO training; the
rollout engine and the trainer share those devices, and a weight publish
hands the trainer's arrays to the engine in-process (no separate rollout
slice exists). On a CPU host it runs at toy scale, and
``--mesh prod``/``prod-multipod`` dry-runs the compiled training
engine against the full-scale mesh: params and Adam moments are placed with
``ShardingEnv``'s logical-axis rules, ``Trainer.step``'s compiled step is
lowered + compiled with those placements (``steps.lower_train_step``), and
the launcher verifies no weight matrix is left fully replicated.

Algorithm selection goes through the Algorithm registry
(``core.algorithms``): ``--algo a3po|recompute|sync|asympo|grpo_mu|...``
(``--algo list`` enumerates it, including third-party registrations).

Observability (``repro.obs``): ``--trace trace.json`` records spans for
rollout, prefill, decode horizons, weight publishes, prox passes, and
train steps (Chrome/Perfetto-loadable, publish->resume flow events
included) and brackets the compiled hot paths with
``jax.profiler.TraceAnnotation``; ``--log-jsonl run.jsonl`` writes one
schema-versioned record per step; ``--quiet`` suppresses the human
stdout lines; ``--metrics-prom FILE`` dumps the metrics registry in
prometheus text format at exit. ``--engine async`` drives the real
thread-decoupled orchestrator through the serving control plane
(continuous batching + fused decode horizons) instead of the
deterministic simulator. Render a run summary afterwards with
``python -m repro.obs.report --jsonl run.jsonl --trace trace.json``.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch toy-2m --steps 20 \
      --algo a3po [--mesh local|prod|prod-multipod] \
      [--trace trace.json] [--log-jsonl run.jsonl] [--quiet] \
      [--engine sim|async]
  PYTHONPATH=src python -m repro.launch.train --algo list
"""
from __future__ import annotations

import os
import sys

# The production meshes need 256/512 placeholder host devices; XLA_FLAGS
# must be set before the first jax import (same trick as launch/dryrun.py).
if __name__ == "__main__" and any(
        a in ("prod", "prod-multipod") or a.startswith("--mesh=prod")
        for a in sys.argv):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import RLConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.algorithms import (  # noqa: E402
    Algorithm,
    registry_table,
    resolve_algorithm,
)
from repro.async_rl.orchestrator import simulate_async  # noqa: E402
from repro.data.tasks import ArithmeticTask  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    ShardingEnv,
    use_sharding,
)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh, make_production_mesh  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.obs.metrics import get_registry  # noqa: E402
from repro.obs.runlog import RunLogger  # noqa: E402
from repro.obs.tracing import SpanTracer, install_tracer  # noqa: E402
from repro.training.checkpoints import save_checkpoint  # noqa: E402


def _replicated_weights(sh_tree, abs_tree) -> list:
    """Paths of >=2-D tensors whose sharding spec is fully replicated."""
    flat_sh, _ = jax.tree_util.tree_flatten_with_path(sh_tree)
    flat_abs = jax.tree.leaves(abs_tree)
    bad = []
    for (path, sh), leaf in zip(flat_sh, flat_abs):
        if len(leaf.shape) >= 2 and all(p is None for p in sh.spec):
            bad.append(jax.tree_util.keystr(path))
    return bad


def sharded_dryrun(cfg, rl: RLConfig, env: ShardingEnv, algo: Algorithm,
                   batch_size: int = 32, seq_len: int = 14,
                   num_microbatches: int = 1) -> None:
    """Lower + compile ``Trainer.step``'s compiled step on the production
    mesh (``steps.lower_train_step``) with ShardingEnv placements for
    params, Adam moments, and batch."""
    params_abs = M.abstract_params(cfg)
    param_sh = M.param_shardings(cfg, env)
    bad = _replicated_weights(param_sh, params_abs)
    assert not bad, f"fully-replicated weight tensors on the mesh: {bad}"

    t0 = time.time()
    lowered = steps.lower_train_step(cfg, rl, algo, env, batch_size, seq_len,
                                     num_microbatches)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    (_, opt_sh, *_), _ = compiled.input_shardings
    bad_m = _replicated_weights(opt_sh["m"], params_abs)
    assert not bad_m, f"fully-replicated Adam moments on the mesh: {bad_m}"
    print(f"[sharded] params + Adam moments carry ShardingEnv placements "
          f"({len(jax.tree.leaves(param_sh))} tensors, 0 replicated "
          f"weight matrices)")
    out_p_sh, _, _ = compiled.output_shardings
    bad_out = [p for (p, sh), leaf in
               zip(jax.tree_util.tree_flatten_with_path(out_p_sh)[0],
                   jax.tree.leaves(params_abs))
               if len(leaf.shape) >= 2 and sh.is_fully_replicated]
    assert not bad_out, f"compiled step replicates weights: {bad_out}"
    mem = compiled.memory_analysis()
    print(f"[sharded] train_step lower {t_lower:.1f}s compile "
          f"{t_compile:.1f}s | args "
          f"{mem.argument_size_in_bytes / 2**20:.1f}MiB temp "
          f"{mem.temp_size_in_bytes / 2**20:.1f}MiB | output params stay "
          f"sharded")


def print_algo_list() -> None:
    """``--algo list``: enumerate the Algorithm registry with flags."""
    cols = ("needs_behav_logp", "needs_prox_forward", "needs_versions",
            "needs_group_rewards", "on_policy")
    header = f"{'name':10s} {'aliases':10s} " \
        + " ".join(f"{c:>{len(c)}s}" for c in cols)
    print(header)
    print("-" * len(header))
    for r in registry_table():
        alias = ",".join(r["aliases"]) or "-"
        flags = " ".join(f"{'yes' if r[c] else 'no':>{len(c)}s}"
                         for c in cols)
        print(f"{r['name']:10s} {alias:10s} {flags}  # {r['doc']}")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="toy-2m")
    p.add_argument("--algo", default=None,
                   help="policy-optimization algorithm (registry name, "
                        "default a3po), or 'list' to enumerate the "
                        "registry")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--staleness", type=int, default=2)
    p.add_argument("--mesh", default="local",
                   choices=["local", "prod", "prod-multipod"])
    p.add_argument("--microbatch", type=int, default=1,
                   help="gradient-accumulation microbatches per minibatch")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--engine", default="sim", choices=["sim", "async"],
                   help="sim: deterministic single-thread simulation; "
                        "async: thread-decoupled orchestrator through the "
                        "serving control plane (continuous batching, "
                        "fused decode horizons, interruptible publishes)")
    p.add_argument("--trace", default=None, metavar="FILE",
                   help="record spans and export a Chrome/Perfetto "
                        "trace.json here")
    p.add_argument("--log-jsonl", default=None, metavar="FILE",
                   help="write one schema-versioned JSONL record per "
                        "training step")
    p.add_argument("--quiet", action="store_true",
                   help="suppress human status lines (JSONL/trace still "
                        "written)")
    p.add_argument("--metrics-prom", default=None, metavar="FILE",
                   help="dump the metrics registry (serving + training) "
                        "in prometheus text format at exit")
    # fault tolerance (repro.resilience)
    p.add_argument("--ckpt-dir", default=None, metavar="DIR",
                   help="crash-consistent step-named checkpoints go here "
                        "(atomic npz+json pairs with checksum + a 'latest' "
                        "pointer)")
    p.add_argument("--ckpt-every", type=int, default=0, metavar="N",
                   help="commit a checkpoint every N completed steps "
                        "(requires --ckpt-dir)")
    p.add_argument("--resume", default=None, metavar="auto|STEP",
                   help="'auto': resume from the newest valid checkpoint "
                        "in --ckpt-dir (fresh start when none); an "
                        "integer: resume from exactly that step's "
                        "checkpoint. Sim-engine resume is bit-identical "
                        "to the uninterrupted run.")
    p.add_argument("--fault", action="append", default=[],
                   metavar="KIND@AT[xN][:MAG]",
                   help="inject a deterministic fault (repeatable), e.g. "
                        "rollout_crash@1, train_crash@3, publish_fail@0x2, "
                        "queue_stall@2:0.5, nan_grad@4, kv_exhaust@5x3:64, "
                        "nan_logits@2")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for the fault plane's RNG (which row/reward "
                        "gets poisoned, backoff jitter)")
    p.add_argument("--guard", default="off",
                   choices=["off", "skip", "rollback"],
                   help="non-finite update policy: 'skip' keeps the "
                        "previous params/opt for poisoned minibatches "
                        "(on-device, no extra host sync); 'rollback' also "
                        "restores the last checkpoint when a step goes "
                        "non-finite or diverges")
    args = p.parse_args()

    if args.algo == "list":
        print_algo_list()
        return
    enable_compile_cache()
    algo = resolve_algorithm(args.algo or "a3po")

    log = RunLogger(args.log_jsonl, quiet=args.quiet)
    tracer = (install_tracer(SpanTracer(), annotate_jax=True)
              if args.trace else None)

    if args.mesh == "local":
        mesh = make_local_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "prod-multipod")
    env = ShardingEnv(mesh)
    n_dev = int(np.prod(list(mesh.shape.values())))
    log.print(f"mesh {dict(mesh.shape)} ({n_dev} devices), "
              f"arch {args.arch}, algo {algo.name}")
    log.log_event("meta", mesh=args.mesh, n_devices=n_dev, arch=args.arch,
                  algo=algo.name, steps=args.steps, engine=args.engine,
                  staleness=args.staleness)

    cfg = get_config(args.arch)
    if jax.default_backend() == "cpu":
        cfg = dataclasses.replace(cfg, dtype="float32")

    rl = RLConfig(group_size=4, num_minibatches=2, learning_rate=2e-4,
                  max_staleness=args.staleness + 1)

    if args.mesh != "local" and jax.default_backend() == "cpu":
        # full-scale mesh on the host platform: dry-run the compiled,
        # sharded engine instead of stepping 256 emulated devices
        sharded_dryrun(cfg, rl, env, algo,
                       num_microbatches=args.microbatch)
        if tracer is not None:
            install_tracer(None)
            tracer.export(args.trace)
        log.close()
        return

    if jax.default_backend() == "cpu" and cfg.num_params() > 5e7:
        raise SystemExit(
            f"{args.arch} is full-scale ({cfg.num_params()/1e9:.0f}B "
            "params): use launch.dryrun or --mesh prod on this host, or a "
            "TPU slice to actually train. Toy archs: toy-2m / toy-20m.")

    task = ArithmeticTask(max_operand=9, n_terms=2, prompt_len=8)

    # --- fault tolerance: checkpoints, guards, fault plane, resume -------
    resilience = None
    resume = None
    if args.ckpt_dir or args.fault or args.guard != "off":
        from repro.resilience import (CheckpointManager, FaultPlan,
                                      ResilienceConfig, TrainGuard)
        resilience = ResilienceConfig(
            faults=(FaultPlan.from_strings(args.fault, seed=args.fault_seed)
                    if args.fault else None),
            guard=(TrainGuard(policy=args.guard) if args.guard != "off"
                   else None),
            checkpointer=(CheckpointManager(args.ckpt_dir)
                          if args.ckpt_dir else None),
            ckpt_every=args.ckpt_every, seed=args.fault_seed)
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume requires --ckpt-dir")
        ckpt = resilience.checkpointer
        if args.resume == "auto":
            resume = ckpt.restore_latest()
        else:
            resume = ckpt.restore(ckpt.path_for(int(args.resume)))
        if resume is not None:
            log.print(f"resuming at step {resume.step} "
                      f"(version {int(resume.state.version)}) from "
                      f"{resume.path}")
            log.log_event("resume", step=resume.step, path=resume.path)
        else:
            log.print(f"--resume auto: no valid checkpoint in "
                      f"{args.ckpt_dir}; starting fresh")

    with mesh, use_sharding(env):
        if args.engine == "async":
            from repro.async_rl.orchestrator import AsyncOrchestrator
            from repro.training.trainer import Trainer
            orch = AsyncOrchestrator(
                cfg, rl, task, algo, n_prompts=8, max_new_tokens=6,
                use_control_plane=True, resilience=resilience)
            start_step = 0
            if resume is not None:
                state = resume.state
                start_step = resume.step
                if resume.task_rng_state is not None:
                    task.rng.bit_generator.state = resume.task_rng_state
            else:
                state = Trainer(cfg, rl, algo).init_state(
                    jax.random.PRNGKey(7))
            state, recs = orch.run(state, args.steps, run_logger=log,
                                   start_step=start_step)
        else:
            state, recs = simulate_async(
                cfg, rl, task, algo, args.steps, n_prompts=8,
                max_new_tokens=6,
                staleness=0 if algo.on_policy else args.staleness,
                num_microbatches=args.microbatch, run_logger=log,
                resilience=resilience, resume=resume)
    for r in recs[:: max(1, len(recs) // 8)]:
        log.print(
            f"  step {r.step:3d} reward {r.reward:.3f} loss {r.loss:+.4f} "
            f"prox {r.prox_time_s*1e3:.2f}ms stale {r.staleness_mean:.1f} "
            f"tok/s {r.train_tokens / max(r.train_time_s, 1e-9):.0f} "
            f"syncs {r.host_syncs:.0f}")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, {"params": state.params},
                        {"arch": args.arch, "algo": algo.name,
                         "steps": args.steps})
        log.print(f"saved {args.checkpoint}")
        log.log_event("checkpoint", path=args.checkpoint)
    if tracer is not None:
        install_tracer(None)
        tracer.export(args.trace)
        log.print(f"trace -> {args.trace}")
    if args.metrics_prom:
        get_registry().dump_prometheus(args.metrics_prom)
        log.print(f"prometheus metrics -> {args.metrics_prom}")
    log.close()


if __name__ == "__main__":
    main()
