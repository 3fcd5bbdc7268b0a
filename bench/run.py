#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up (weights from the seed on the device, the cell's shapes warmed,
JAX's compile cache at ``.bench_cache/jax`` in the checkout), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard output.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args()
    if a.seed < 0:
        raise SystemExit("bench: --seed must be a non-negative integer")
    harness.report(harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                               t_start=T_START))
