"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers + compiles.

MUST set XLA_FLAGS before any jax import: the production meshes need 512
placeholder host devices. Do not import this module from code that wants
real single-device execution (tests/benches import repro.* directly).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch codeqwen1.5-7b \
      --shape train_4k [--multi-pod] [--all]
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs.base import RLConfig, SHAPES  # noqa: E402
from repro.configs.registry import get_config, list_archs  # noqa: E402
from repro.distributed.hlo_analysis import roofline_terms  # noqa: E402
from repro.distributed.hlo_cost import analyze as hlo_analyze  # noqa: E402
from repro.distributed.sharding import ShardingEnv, use_sharding  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    PRODUCTION_DEVICE_KIND,
    make_production_mesh,
)
from repro.launch import steps  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.obs.runlog import RunLogger  # noqa: E402
from repro.training.trainer import check_trainable  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def _mem_dict(mem) -> dict:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        try:
            out[attr] = int(getattr(mem, attr))
        except Exception:  # noqa: BLE001
            pass
    return out


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               algo="a3po", fsdp: bool = True,
               save: bool = True, verbose: bool = True,
               rules=None, kv_seq_shard: bool = False,
               tp_fallback: bool = False, ep_moe: bool = False,
               num_microbatches: int = 1, prefill_microbatches: int = 1,
               tag_suffix: str = "", run_logger: RunLogger = None) -> dict:
    """Lower and compile one (arch x shape) on the production mesh. A train
    shape lowers ``Trainer.step``'s own compiled step
    (``steps.lower_train_step``); a stack the Trainer refuses (a frontend
    stack) is skipped with the reason, as ``{"skipped": reason}``."""
    from repro.core.algorithms import resolve_algorithm
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        try:
            check_trainable(cfg)
        except ValueError as e:
            if verbose:
                line = f"[dryrun] {arch} x {shape_name}: skipped ({e})"
                if run_logger is not None:
                    run_logger.print(line)
                else:
                    print(line, flush=True)
            return {"arch": arch, "shape": shape_name, "kind": shape.kind,
                    "skipped": str(e)}
    rl = RLConfig()
    algo = resolve_algorithm(algo, rl)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    if kv_seq_shard:
        # §Perf lever: shard the decode KV cache along the sequence axis
        # (GSPMD all-reduces the softmax partials) — rescues archs whose
        # kv_heads don't divide the model axis from cache replication.
        from repro.distributed.sharding import DEFAULT_RULES
        rules = tuple(r for r in (rules or DEFAULT_RULES)
                      if r[0] != "kv_seq") + (("kv_seq", "model"),)
    env = (ShardingEnv(mesh, fsdp=fsdp, tp_fallback=tp_fallback)
           if rules is None
           else ShardingEnv(mesh, rules=rules, fsdp=fsdp,
                            tp_fallback=tp_fallback))
    env.ep_shard_map = ep_moe

    t0 = time.time()
    if shape.kind == "train":
        lowered = steps.lower_train_step(
            cfg, rl, algo, env, shape.global_batch, shape.seq_len,
            num_microbatches)
    else:
        specs = steps.input_specs(cfg, shape)
        if shape.kind == "prefill" and prefill_microbatches > 1:
            step = steps.make_prefill_step(cfg, shape, prefill_microbatches)
        else:
            step = steps.make_step(cfg, shape, rl, algo)
        params_abs = M.abstract_params(cfg)
        param_sh = M.param_shardings(cfg, env)
        batch_sh = steps.batch_shardings(cfg, shape, env, specs)
        # donate the KV/SSM cache: serving aliases it in place
        donate = (1,) if shape.kind == "decode" else ()
        with mesh, use_sharding(env):
            lowered = jax.jit(step, in_shardings=(param_sh, batch_sh),
                              donate_argnums=donate).lower(params_abs, specs)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    # newer jax returns a per-program list of dicts; older a single dict
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    # trip-count-aware per-device cost from the compiled HLO (XLA's
    # cost_analysis counts while bodies once — useless for scanned layers)
    hc = hlo_analyze(compiled.as_text())
    flops = hc.flops
    bytes_accessed = hc.traffic_bytes
    coll_bytes = hc.collective_bytes
    coll_ops = {k: {"count": int(v["count"]), "bytes": int(v["bytes"])}
                for k, v in hc.collective_ops.items()}
    terms = roofline_terms(flops, bytes_accessed, coll_bytes,
                           PRODUCTION_DEVICE_KIND)

    n_params = cfg.num_params()
    n_active = cfg.num_active_params()
    # MODEL_FLOPS: 6*N*D for a train step (fwd+bwd), 2*N*D for inference
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind != "decode" else shape.global_batch)
    mult = 6 if shape.kind == "train" else 2
    model_flops_global = mult * n_active * tokens
    model_flops_per_dev = model_flops_global / n_chips

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": shape.kind,
        "algo": algo.name,
        "fsdp": fsdp,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": _mem_dict(mem),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_bytes,
        "collective_ops": coll_ops,
        "xla_cost_analysis_raw": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "roofline": {k: (v if isinstance(v, str) else float(v))
                     for k, v in terms.items()},
        "n_params": n_params,
        "n_active_params": n_active,
        "model_flops_per_device": model_flops_per_dev,
        "useful_flops_ratio": (model_flops_per_dev / flops
                               if flops else None),
    }
    if verbose:
        mb = record["memory"].get("temp_size_in_bytes", 0) / 2**30
        arg_gb = record["memory"].get("argument_size_in_bytes", 0) / 2**30
        line = (f"[dryrun] {arch} x {shape_name} x {record['mesh']}: "
                f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
                f"args {arg_gb:.2f}GiB temp {mb:.2f}GiB | "
                f"flops/dev {flops:.3g} coll/dev {coll_bytes:.3g}B | "
                f"dominant={terms['dominant']}")
        if run_logger is not None:
            run_logger.print(line)
        else:
            print(line, flush=True)
    if run_logger is not None:
        run_logger.log_event(
            "dryrun", arch=arch, shape=shape_name, mesh=record["mesh"],
            shape_kind=shape.kind, lower_s=record["lower_s"],
            compile_s=record["compile_s"],
            temp_bytes=record["memory"].get("temp_size_in_bytes", 0),
            hlo_flops_per_device=flops,
            collective_bytes_per_device=coll_bytes,
            dominant=terms["dominant"])
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        tag = f"{arch}_{shape_name}_{record['mesh']}"
        if not fsdp:
            tag += "_nofsdp"
        tag += tag_suffix
        with open(os.path.join(RESULTS_DIR, tag + ".json"), "w") as f:
            json.dump(record, f, indent=2)
    return record


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, help="architecture id")
    p.add_argument("--shape", default=None, choices=sorted(SHAPES))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true",
                   help="run every assigned arch x shape")
    p.add_argument("--algo", default=None,
                   help="policy-optimization algorithm (registry name, "
                        "default a3po)")
    p.add_argument("--no-fsdp", action="store_true")
    # §Perf optimization levers (see EXPERIMENTS.md §4)
    p.add_argument("--ep-moe", action="store_true",
                   help="expert-parallel shard_map MoE dispatch")
    p.add_argument("--kv-seq-shard", action="store_true",
                   help="shard decode KV cache along sequence")
    p.add_argument("--tp-fallback", action="store_true",
                   help="row-parallel fallback for non-divisible heads")
    p.add_argument("--tag", default="", help="suffix for result files")
    p.add_argument("--log-jsonl", default=None, metavar="FILE",
                   help="append one schema-versioned JSONL record per combo")
    p.add_argument("--quiet", action="store_true",
                   help="suppress stdout progress lines (JSONL still logs)")
    args = p.parse_args()

    combos = []
    if args.all:
        for arch in list_archs(assigned_only=True):
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    log = RunLogger(args.log_jsonl, quiet=args.quiet)
    failures, skipped = [], 0
    try:
        for arch, shape in combos:
            try:
                rec = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                                 algo=args.algo or "a3po",
                                 fsdp=not args.no_fsdp,
                                 ep_moe=args.ep_moe,
                                 kv_seq_shard=args.kv_seq_shard,
                                 tp_fallback=args.tp_fallback,
                                 tag_suffix=args.tag, run_logger=log)
                skipped += "skipped" in rec
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape, repr(e)))
                log.log_event("dryrun_failure", arch=arch, shape=shape,
                              error=repr(e))
                traceback.print_exc()
        if failures:
            log.print(f"\nFAILED {len(failures)}/{len(combos)}:")
            for f in failures:
                log.print(f"   {f}")
            raise SystemExit(1)
        log.print(f"\nALL {len(combos) - skipped} combos compiled OK "
                  f"({'2x16x16' if args.multi_pod else '16x16'}), "
                  f"{skipped} skipped")
    finally:
        log.close()


if __name__ == "__main__":
    main()
