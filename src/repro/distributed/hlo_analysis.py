"""HLO parsing for the roofline: collective bytes + op census.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but not collective
traffic, so we parse the (SPMD-partitioned, per-device) HLO text and sum
the output-shape bytes of every collective op. ``*-start`` async forms are
counted once (their ``*-done`` pair is skipped).
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# e.g.  %all-reduce.5 = bf16[128,1024]{1,0} all-reduce(...)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"=\s*(?:\()?\s*((?:\w+\[[\d,]*\](?:\{[^}]*\})?(?:,\s*)?)+)\)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shapes_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> Tuple[int, Dict[str, Dict[str, int]]]:
    """Returns (total_bytes, {op: {count, bytes}}) from per-device HLO."""
    per_op: Dict[str, Dict[str, int]] = {
        op: {"count": 0, "bytes": 0} for op in COLLECTIVES}
    total = 0
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _LINE_RE.search(line)
        if not m:
            continue
        shapes_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shapes_str)
        per_op[op]["count"] += 1
        per_op[op]["bytes"] += b
        total += b
    return total, {k: v for k, v in per_op.items() if v["count"]}


# ------------------------------------------------------------------ roofline
# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s interconnect per chip
# (four links of 50 GB/s).
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; unknown kinds are an error."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add them "
            f"to DEVICE_PEAKS with their source") from None


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   collective_bytes_per_device: float, device_kind: str
                   ) -> Dict[str, float]:
    """Three roofline terms in seconds (per device / chip)."""
    peaks = device_peaks(device_kind)
    compute = flops_per_device / peaks["flops_bf16"]
    memory = bytes_per_device / peaks["hbm_bw"]
    collective = collective_bytes_per_device / peaks["ici_bw"]
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dominant = max(terms, key=terms.get)
    terms["dominant"] = dominant  # type: ignore[assignment]
    return terms
