#!/usr/bin/env python3
"""The readings that limits are set from, many seeds in one process.

    python bench/readings.py --workload <cell> --seeds 11,12,13 \\
        [--variants fp8,half_batch] [--seconds 1] --out <file.jsonl>

For each seed, runs the cell as ``bench/run.py`` does (a short window) and
writes one JSON line with the program's numbers and, for each variant, the
numbers of the reference put in the program's place: ``fp8`` is the
control one precision below bfloat16; ``half_batch`` the planted fault of a
training cell (half of each minibatch left out, the mean over the rest).
The benchmark's own runs never run a variant.
"""
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--variants", default="")
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--out", required=True)
    a = p.parse_args()
    variants = [v for v in a.variants.split(",") if v]
    with open(a.out, "a") as f:
        for seed in (int(s) for s in a.seeds.split(",")):
            t0 = time.perf_counter()
            out = harness.run(a.workload, seed, a.seconds, False, t_start=t0,
                              overrides={"variants": variants})
            row = {"workload": a.workload, "seed": seed,
                   "correct": out["correct"], "numbers": out["numbers"],
                   "variants": out.get("variant_numbers", {}),
                   "seconds": time.perf_counter() - t0}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
