"""Production serving launcher: batched decode against a sharded cache.

On TPU this jits ``prefill_step``/``decode_step`` with the production mesh
shardings (see dryrun.py for the full-scale lowering); on CPU it serves a
reduced/toy config end-to-end.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch toy-2m --batch 8 \
      --max-new 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import RLConfig
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.rollout.engine import RolloutEngine


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="toy-2m")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--waves", type=int, default=2)
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if jax.default_backend() == "cpu" and cfg.num_params() > 5e7:
        cfg = get_config(args.arch + "-reduced")
        print(f"(CPU host: serving reduced variant of {args.arch})")
    if jax.default_backend() == "cpu":
        cfg = dataclasses.replace(cfg, dtype="float32")

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = RolloutEngine(cfg, RLConfig(temperature=0.8),
                           max_new_tokens=args.max_new)
    rng = np.random.default_rng(0)
    for wave in range(args.waves):
        prompts = rng.integers(4, cfg.vocab_size,
                               (args.batch, args.prompt_len)).astype(np.int32)
        lengths = np.full((args.batch,), args.prompt_len, np.int32)
        t0 = time.perf_counter()
        rb = engine.generate(params, prompts, lengths,
                             jax.random.PRNGKey(wave))
        dt = time.perf_counter() - t0
        n = int(rb.gen_mask.sum())
        print(f"wave {wave}: {args.batch} seqs x {args.max_new} new -> "
              f"{n} tokens, {n/dt:.1f} tok/s")


if __name__ == "__main__":
    main()
