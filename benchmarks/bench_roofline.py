"""Roofline table from the dry-run artifacts (deliverable g).

Reads experiments/dryrun/*.json (produced by launch/dryrun.py) and prints
per (arch x shape x mesh): the three roofline terms, the dominant
bottleneck, MODEL_FLOPS/HLO_FLOPS usefulness ratio, and bytes/device.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from benchmarks.common import CsvOut
from repro.distributed.hlo_analysis import device_peaks
from repro.launch.mesh import PRODUCTION_DEVICE_KIND

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")


def load_records(mesh: str = "16x16") -> List[Dict]:
    """Baseline records only (variant files carry a tag suffix)."""
    recs = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        stem = os.path.splitext(os.path.basename(path))[0]
        if (r.get("mesh") == mesh
                and stem == f"{r['arch']}_{r['shape']}_{r['mesh']}"):
            recs.append(r)
    return recs


def format_table(recs: List[Dict]) -> str:
    """memory_s is the trip-corrected op-boundary traffic (an UPPER bound:
    the CPU-backend HLO fuses less than TPU). mem_lb_s is the buffer-
    assignment lower bound (every allocated byte touched once)."""
    hdr = (f"{'arch':24s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'mem_lb_s':>9s} {'coll_s':>10s} {'dominant':>11s} "
           f"{'useful%':>8s} {'temp_GiB':>9s}")
    lines = [hdr, "-" * len(hdr)]
    for r in recs:
        t = r["roofline"]
        useful = r.get("useful_flops_ratio")
        useful_s = f"{useful * 100:.0f}" if useful else "-"
        mem = r["memory"]
        temp = mem.get("temp_size_in_bytes", 0) / 2**30
        lb_bytes = (mem.get("argument_size_in_bytes", 0)
                    + mem.get("output_size_in_bytes", 0)
                    + mem.get("temp_size_in_bytes", 0))
        lb_s = lb_bytes / device_peaks(PRODUCTION_DEVICE_KIND)["hbm_bw"]
        lines.append(
            f"{r['arch']:24s} {r['shape']:12s} {t['compute_s']:10.3e} "
            f"{t['memory_s']:10.3e} {lb_s:9.3e} {t['collective_s']:10.3e} "
            f"{t['dominant'].replace('_s',''):>11s} {useful_s:>8s} "
            f"{temp:9.2f}")
    return "\n".join(lines)


def run(csv: CsvOut) -> None:
    for mesh in ("16x16", "2x16x16"):
        recs = load_records(mesh)
        if not recs:
            continue
        print(f"\n=== Roofline ({mesh}, {len(recs)} combos) ===")
        print(format_table(recs))
        worst = min(
            (r for r in recs if r.get("useful_flops_ratio")),
            key=lambda r: r["useful_flops_ratio"])
        dom_counts: Dict[str, int] = {}
        for r in recs:
            dom_counts[r["roofline"]["dominant"]] = dom_counts.get(
                r["roofline"]["dominant"], 0) + 1
        csv.add(f"roofline/{mesh}/combos", 0.0,
                f"n={len(recs)} dominant={dom_counts} "
                f"worst_useful={worst['arch']}x{worst['shape']}="
                f"{worst['useful_flops_ratio']*100:.0f}%")
        for r in recs:
            t = r["roofline"]
            csv.add(f"roofline/{mesh}/{r['arch']}/{r['shape']}",
                    max(t["compute_s"], t["memory_s"], t["collective_s"]),
                    f"dominant={t['dominant']}")


if __name__ == "__main__":
    c = CsvOut()
    c.header()
    run(c)
