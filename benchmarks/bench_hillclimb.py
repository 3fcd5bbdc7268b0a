"""Reproduce the §Perf hillclimb measurements (EXPERIMENTS.md §4).

Runs baseline + optimized dry-runs for the two config-level pairs and
prints the before/after roofline terms. Must run in its own process
(forces the 512-device host platform):

  PYTHONPATH=src:. python -m benchmarks.bench_hillclimb

The deepseek-coder pair is kv_seq + tp_fallback, the qwen3-moe pair the
EP dispatch (10.9x / 28.1x on the dominant term when first recorded).
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))


def main() -> None:
    from repro.launch.dryrun import dryrun_one

    pairs = [
        ("deepseek-coder-33b", "decode_32k", {},
         {"kv_seq_shard": True, "fsdp": False, "tp_fallback": True}),
        ("qwen3-moe-30b-a3b", "train_4k", {}, {"ep_moe": True}),
    ]
    print("name,us_per_call,derived")
    for arch, shape, base_kw, opt_kw in pairs:
        rb = dryrun_one(arch, shape, save=False, verbose=False, **base_kw)
        ro = dryrun_one(arch, shape, save=True, verbose=False,
                        tag_suffix="_opt", **opt_kw)
        for name, r in (("baseline", rb), ("optimized", ro)):
            t = r["roofline"]
            print(f"hillclimb/{arch}/{shape}/{name},"
                  f"{max(t['compute_s'], t['memory_s'], t['collective_s'])*1e6:.0f},"
                  f"compute={t['compute_s']:.2f}s memory={t['memory_s']:.2f}s "
                  f"coll={t['collective_s']:.2f}s "
                  f"args={r['memory']['argument_size_in_bytes']/2**30:.1f}GiB")
        speed = (max(rb["roofline"]["collective_s"], rb["roofline"]["memory_s"])
                 / max(ro["roofline"]["collective_s"],
                       ro["roofline"]["memory_s"], 1e-9))
        print(f"hillclimb/{arch}/{shape}/gain,0,{speed:.1f}x on dominant term")


if __name__ == "__main__":
    main()
