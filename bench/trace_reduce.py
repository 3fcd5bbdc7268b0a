"""Reduce a ``jax.profiler`` trace to device busy and idle time, kernel
time, exposed collective time and the breakdown of where time went.

A trace is read once into plain interval lists, so that every reduction
below also runs on hand-built lists (the tests build them):

* ``devices``: one list of ops per chip of the cell, from the planes
  ``/device:TPU:0``, ``/device:TPU:1``, ... in that order (a trace also
  holds device planes that are no chip, such as
  ``/device:CUSTOM:Megascale Trace``, and those are left out), each op
  ``(name, start_ns, end_ns, text)`` from the plane's op line, with the
  HLO instruction's short name (``fusion.12``, ``while.3``) and, for loops
  and custom calls, the instruction's text with the shapes it carries;
* ``host``: host events other than Python frames, among them the
  annotations (``jax.profiler.TraceAnnotation``, the program's own
  ``annotate`` regions), ``(name, start_ns, end_ns)`` on the same clock.

Busy time is the union of the op intervals on a chip, clipped to the
window; the idle share is one minus busy over the window. Numbers are
averaged over the cell's chips, as the contract of ``busy_s`` asks.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Op = Tuple[str, float, float, str]          # name, start, end, text
Span = Tuple[str, float, float]             # name, start, end

# the op line of a TPU device plane; module and step lines repeat the ops
OP_LINES = ("XLA Ops",)
COLLECTIVE_WORDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "allgather",
                    "allreduce", "reducescatter")


@dataclasses.dataclass
class Trace:
    devices: List[List[Op]]
    host: List[Span]


CONTAINERS = ("while", "conditional", "call")


def short_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _keep_text(name: str, text: str) -> str:
    if name.startswith(CONTAINERS) or "custom-call" in text[:2000]:
        return text
    return ""


CHIP_PLANE = re.compile(r"/device:TPU:(\d+)$")


def chip_planes(planes: Sequence, chips: int) -> List:
    """The planes of the first ``chips`` chips, in chip order; planes that
    are no chip are left out. Fewer chip planes than ``chips`` is an
    error: the busy time would be averaged over chips the trace lacks."""
    found = sorted((p for p in planes if CHIP_PLANE.match(p.name)),
                   key=lambda p: int(CHIP_PLANE.match(p.name).group(1)))
    if len(found) < chips:
        raise ValueError(f"the trace holds {len(found)} chip planes, "
                         f"the cell uses {chips}")
    return found[:chips]


def load(trace_dir: str, chips: int) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``, keeping the
    device planes of the first ``chips`` chips."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(list(ProfileData.from_file(paths[-1]).planes), chips)


def from_planes(planes: Sequence, chips: int) -> Trace:
    """A ``Trace`` from the profiler's planes (``name``, ``lines``, each
    line with ``name`` and ``events``)."""
    devices: List[List[Op]] = []
    host: List[Span] = []
    for plane in chip_planes(planes, chips):
        ops: List[Op] = []
        for line in plane.lines:
            if line.name not in OP_LINES:
                continue
            for e in line.events:
                name = short_name(e.name)
                ops.append((name, e.start_ns, e.start_ns + e.duration_ns,
                            _keep_text(name, e.name)))
        devices.append(sorted(ops, key=lambda o: o[1]))
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    # "$file:line name" events are Python frames
                    if e.duration_ns > 0 and not e.name.startswith("$"):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return Trace(devices=devices, host=sorted(host, key=lambda s: s[1]))


# ---------------------------------------------------------------- intervals
def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(x, y) -> List[Tuple[float, float]]:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi] outside the disjoint sorted ``busy``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


# --------------------------------------------------------------- reductions
def window_of(trace: Trace, name: str) -> Tuple[float, float]:
    """The host annotation ``name`` that brackets the traced window."""
    spans = [s for s in trace.host if s[0] == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return spans[0][1], spans[-1][2]


def busy(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Device-busy nanoseconds in [lo, hi], averaged over the chips; None
    when the trace has no chip plane."""
    if not trace.devices:
        return None
    per = [length(clip(merge([(o[1], o[2]) for o in ops]), lo, hi))
           for ops in trace.devices]
    return sum(per) / len(per)


def op_time(trace: Trace, match: Callable[[Op], bool], lo: float,
            hi: float) -> Optional[float]:
    """Nanoseconds of ops that ``match`` in [lo, hi] (their own union, so
    nested events are not counted twice), averaged over device planes;
    None when no op matched."""
    per, found = [], False
    for ops in trace.devices:
        sel = [(o[1], o[2]) for o in ops if match(o)]
        found |= bool(sel)
        per.append(length(clip(merge(sel), lo, hi)))
    if not found:
        return None
    return sum(per) / len(per)


def is_collective(op: Op) -> bool:
    text = (op[0] + " " + op[3]).lower()
    return any(w in text for w in COLLECTIVE_WORDS)


def exposed_collective(trace: Trace, lo: float, hi: float
                       ) -> Optional[float]:
    """Nanoseconds in which a collective runs on a device and no other op
    does (a loop around both does not count as compute), averaged over
    device planes; None without collectives."""
    per, found = [], False
    for ops in trace.devices:
        coll = merge([(o[1], o[2]) for o in ops if is_collective(o)])
        comp = merge([(o[1], o[2]) for o in ops if not is_collective(o)
                      and not o[0].startswith(CONTAINERS)])
        found |= bool(coll)
        coll = clip(coll, lo, hi)
        per.append(length(coll) - length(intersect(coll, comp)))
    if not found:
        return None
    return sum(per) / len(per)


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10,
            exclude_loops: bool = True) -> List[List]:
    """The ``k`` op names that took the most device seconds in [lo, hi],
    averaged over device planes; loops and calls, whose time their inner
    ops already show, are left out with ``exclude_loops``."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.devices:
        for name, a, b, _ in ops:
            if exclude_loops and name.startswith(CONTAINERS):
                continue
            a, b = max(a, lo), min(b, hi)
            if b > a:
                tot[name] += (b - a) / 1e9
    n = max(len(trace.devices), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, s / n] for name, s in top]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10,
              names: Optional[Callable[[str], bool]] = None) -> List[List]:
    """Idle device seconds in [lo, hi], by the innermost host span open at
    each gap's midpoint (``"none"`` where none is). Only spans whose name
    passes ``names`` label a gap (all, without it). Averaged over device
    planes; the ``k`` largest labels."""
    host = [s for s in trace.host if names is None or names(s[0])]
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.devices:
        for a, b in gaps(merge([(o[1], o[2]) for o in ops]), lo, hi):
            mid = (a + b) / 2
            open_ = [s for s in host if s[1] <= mid < s[2]]
            label = min(open_, key=lambda s: s[2] - s[1])[0] \
                if open_ else "none"
            tot[label] += (b - a) / 1e9
    n = max(len(trace.devices), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, s / n] for name, s in top]
