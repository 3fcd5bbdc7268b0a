"""Pieces the drivers share: the program's model config from the
configuration file, seeds, meshes, memory and the traced window."""
from __future__ import annotations

import contextlib
import shutil
import time
from typing import Callable, Dict

import numpy as np


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a Qwen2 configuration file (every
    published size, the file's depth and dtype)."""
    from repro.configs.base import ModelConfig

    if cfg["model_type"] != "qwen2" or cfg["hidden_act"] != "silu":
        raise SystemExit(f"bench: no program path for {cfg['model_type']}")
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"])


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (more than 32 bits too)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def local_mesh(chips: int):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:chips]).reshape(chips, 1),
                ("data", "model"))


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


WINDOW_SPAN = "bench.window"


class TracedWindow:
    """``jax.profiler`` trace of part of the window. ``start`` starts the
    profiler; the traced window opens at ``open``, which the drivers call
    one step later, so that the profiler's start-up stays out of it, and
    ends at ``stop``. The traced window is bracketed by the host
    annotation ``bench.window``; ``reduce`` reads the trace into
    ``ctx.data``. Python frames are not traced: the readers need only the
    device ops and the annotations."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.started = self.active = False
        self.t0 = self.t1 = 0.0
        self._ann = None

    @property
    def pending(self) -> bool:
        """Started, and the traced window not yet opened."""
        return self.started and self._ann is None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
        self.ctx.trace_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.ctx.trace_dir),
                                 profiler_options=opts)
        self.started = True

    def open(self) -> None:
        import jax

        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def stop(self) -> None:
        import jax

        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self, labels: Callable[[str], bool]) -> None:
        """Busy time, the breakdown, and the trace itself for the metric
        readers; the trace files are deleted."""
        from bench import trace_reduce as tr

        trace = tr.load(str(self.ctx.trace_dir), self.ctx.chips)
        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
        lo, hi = tr.window_of(trace, WINDOW_SPAN)
        busy = tr.busy(trace, lo, hi)
        d = self.ctx.data
        d.update(trace=trace, lo=lo, hi=hi, window_s=(hi - lo) / 1e9,
                 host_window_s=self.t1 - self.t0,
                 busy_s=None if busy is None else busy / 1e9,
                 breakdown={
                     "device_ops": tr.top_ops(trace, lo, hi),
                     "idle_gaps": tr.idle_gaps(
                         trace, lo, hi,
                         names=lambda n: n != WINDOW_SPAN and labels(n))})


@contextlib.contextmanager
def window(ctx):
    """Marks the measured window for the compile log."""
    ctx.compile_log.in_window = True
    try:
        yield
    finally:
        ctx.compile_log.in_window = False
