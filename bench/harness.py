"""Finds a cell's parts by name, runs its driver, reads its metrics and
decides ``correct``. ``bench/run.py`` is the command line around ``run``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed paths inside the checkout: the compile cache is keyed on its path
CACHE_DIR = ROOT / ".bench_cache"


def read_json(path: pathlib.Path) -> Dict:
    return json.loads(path.read_text())


def manifest() -> Dict:
    return read_json(ROOT / "BENCHMARK.json")


def workload_entry(man: Dict, name: str) -> Dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict:
    return read_json(BENCH / "configs" / f"{name}.json")


def cell(name: str) -> Dict:
    return read_json(BENCH / "cells" / f"{name}.json")


def peaks(device_kind: str) -> Dict[str, float]:
    table = read_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"bench: no published peaks for device kind "
                         f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


def cell_metrics(man: Dict, entry: Dict, trace: bool) -> List[Dict]:
    """The end-to-end metrics a cell reports, or with ``trace`` its
    per-layer ones (those listing the cell, or listing none and moving one
    of the cell's end-to-end metrics)."""
    name = entry["name"]
    e2e = [m for m in man["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric: str, directory: pathlib.Path = BENCH / "metrics"
           ) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = directory / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ compile events
class CompileLog:
    """Persistent-cache hits and misses and backend compiles, in all and
    inside the measured window."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = {"hits": 0, "misses": 0, "compiles": 0}
        self.window = {"hits": 0, "misses": 0, "compiles": 0}
        self.window_names: Dict[str, int] = {}
        self.in_window = False

    def _bump(self, key: str) -> None:
        self.total[key] += 1
        if self.in_window:
            self.window[key] += 1

    def on_event(self, name: str, **_) -> None:
        if name in self.EVENTS:
            self._bump(self.EVENTS[name])

    def on_duration(self, name: str, secs: float, fun_name: str = "?",
                    **_) -> None:
        if name == self.COMPILE:
            self._bump("compiles")
            if self.in_window:
                self.window_names[fun_name] = \
                    self.window_names.get(fun_name, 0) + 1


# JAX's monitoring hooks cannot be removed: one pair per process feeds the
# log of the run in progress
_ACTIVE: List[CompileLog] = []


def enable_cache(log: CompileLog) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, whatever ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    import jax.monitoring

    path = CACHE_DIR / "jax"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _ACTIVE:
        jax.monitoring.register_event_listener(
            lambda *a, **k: _ACTIVE[-1].on_event(*a, **k))
        jax.monitoring.register_event_duration_secs_listener(
            lambda *a, **k: _ACTIVE[-1].on_duration(*a, **k))
    _ACTIVE.append(log)
    return str(path)


# ----------------------------------------------------------------- contexts
@dataclasses.dataclass
class Ctx:
    """What a driver gets, and later what a metric reader gets."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    cfg: Dict
    cell: Dict
    mix: Dict
    peaks: Dict
    t_start: float
    compile_log: CompileLog
    trace_dir: pathlib.Path
    # filled by the driver
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def log(self, msg: str) -> None:
        print(f"bench: {msg}", flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, overrides: Optional[Dict] = None,
        require_chip: bool = True) -> Dict:
    """Run one cell once; returns the result dict (``checks`` last)."""
    import jax

    from bench import compare, traffic

    overrides = overrides or {}
    man = overrides.get("manifest") or manifest()
    entry = workload_entry(man, workload)
    cfg = overrides.get("config") or config(entry["config"])
    cl = dict(cell(workload), **overrides.get("cell", {}))
    mix = dict(traffic.load(entry["traffic"]), **overrides.get("mix", {}))
    if not cl.get("limits"):
        raise SystemExit(f"bench: {workload} has no limits set from "
                         f"readings in bench/cells/{workload}.json")

    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu"
                         or len(devices) < entry["chips"]):
        print(f"bench: {workload} needs {entry['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {dev.platform} device(s). There is no "
              f"CPU fallback.", file=sys.stderr)
        raise SystemExit(2)
    log = CompileLog()
    cache = enable_cache(log)
    ctx = Ctx(workload=workload, seed=int(seed), seconds=float(seconds),
              trace=bool(trace), chips=entry["chips"], cfg=cfg, cell=cl,
              mix=mix,
              peaks=(peaks(dev.device_kind) if require_chip
                     else overrides.get("peaks", {})),
              t_start=t_start, compile_log=log,
              trace_dir=CACHE_DIR / "trace" / workload,
              data={"variants": overrides.get("variants", ())})
    ctx.log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
            f"compile cache {cache}")
    driver = importlib.import_module(f"bench.drivers.{cl['driver']}")
    res = driver.run(ctx)

    ctx.log(f"compile cache: {log.total['hits']} hits, "
            f"{log.total['misses']} misses, {log.total['compiles']} "
            f"compiles in all; inside the window {log.window['hits']} hits, "
            f"{log.window['misses']} misses, {log.window['compiles']} "
            f"compiles {sorted(log.window_names.items())[:12]}")
    for line in res.get("info", []):
        ctx.log(line)

    metrics: Dict[str, Dict] = {}
    for m in cell_metrics(man, entry, trace):
        if trace:
            value = reader(m["name"])(ctx)
        else:
            value = res["e2e"].get(m["name"])
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    correct, checks = compare.judge(res["numbers"], cl["limits"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    out: Dict[str, Any] = {"correct": bool(correct and not res.get("failed")
                                           and res.get("attempted", 0) > 0),
                           "attempted": int(res["attempted"]),
                           "failed": int(res["failed"]),
                           "metrics": metrics, "device": device}
    if trace and ctx.data.get("busy_s") is not None:
        device["busy_s"] = ctx.data["busy_s"]
        device["window_s"] = ctx.data["window_s"]
        out["breakdown"] = ctx.data["breakdown"]
    if ctx.data.get("variant_numbers"):
        out["variant_numbers"] = ctx.data["variant_numbers"]
    out["numbers"] = res["numbers"]
    out["checks"] = checks
    return out


def _finite(x):
    """JSON has no infinity: a number that is not finite prints as null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def report(out: Dict) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output."""
    from bench import compare

    for line in compare.describe(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(_finite(out), allow_nan=False), flush=True)
