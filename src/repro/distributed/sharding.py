"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Weights and activations are annotated with *logical* axis names; this module
maps them onto whatever mesh is active. Rules degrade gracefully: if a
tensor dimension is not divisible by its mesh axis (e.g. kv_heads=8 on a
model=16 axis) the dimension is replicated instead of failing, which is
exactly what a production system must do across heterogeneous architectures.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisRule = Tuple[str, Union[str, Tuple[str, ...], None]]


# Default logical->mesh mapping. "embed" is the FSDP axis (weight d_model
# dims sharded over data); activations use "act_embed" which is never
# sharded over data.
DEFAULT_RULES: Tuple[AxisRule, ...] = (
    ("batch", ("pod", "data")),
    ("vocab", "model"),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("ff", "model"),
    ("experts", "model"),
    ("expert_ff", None),
    ("ssm_inner", "model"),
    ("ssm_heads", "model"),
    ("mla_rank", None),
    ("embed", "data"),      # FSDP weight sharding
    ("act_embed", None),
    ("act_heads", "model"),
    ("act_ff", "model"),
    ("seq", None),
    ("seq_sp", None),  # sequence-parallel residual stream (opt-in: "model")
    ("kv_seq", None),
    ("layers", None),
    ("head_dim", None),
    ("ssm_state", None),
    ("conv", None),
    ("capacity", None),
)


class ShardingEnv:
    """A mesh + rule set, resolving logical axes to concrete shardings."""

    def __init__(self, mesh: Mesh, rules: Sequence[AxisRule] = DEFAULT_RULES,
                 fsdp: bool = True, tp_fallback: bool = False):
        self.mesh = mesh
        self.rules: Dict[str, Union[str, Tuple[str, ...], None]] = dict(rules)
        self.fsdp = fsdp
        # tp_fallback: if a weight leaves the "model" axis unused (e.g.
        # heads=56 on model=16), shard its d_model ("embed") axis over
        # "model" instead — row-parallel TP with an extra activation
        # all-reduce, instead of full weight replication.
        self.tp_fallback = tp_fallback

    def _mesh_axes_for(self, logical: Optional[str]) -> Tuple[str, ...]:
        if logical is None:
            return ()
        target = self.rules.get(logical, None)
        if target is None:
            return ()
        if logical == "embed" and not self.fsdp:
            return ()
        if isinstance(target, str):
            target = (target,)
        return tuple(a for a in target if a in self.mesh.axis_names)

    def spec(self, shape: Sequence[int],
             logical_axes: Sequence[Optional[str]]) -> P:
        """PartitionSpec for ``shape`` under the rules, divisibility-aware."""
        assert len(shape) == len(logical_axes), (shape, logical_axes)
        used: set = set()
        parts = []
        for dim, name in zip(shape, logical_axes):
            axes = self._mesh_axes_for(name)
            # an axis of one device shards nothing: leaving it unnamed
            # keeps equivalent placements equal, so a jitted step whose
            # outputs come back replicated reuses its program
            axes = tuple(a for a in axes
                         if a not in used and self.mesh.shape[a] > 1)
            size = int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1
            if axes and dim % size == 0 and dim >= size:
                used.update(axes)
                parts.append(axes if len(axes) > 1 else axes[0])
            else:
                parts.append(None)
        if (self.tp_fallback and "model" in self.mesh.axis_names
                and "model" not in used and self.mesh.shape["model"] > 1):
            msize = self.mesh.shape["model"]
            for i, (dim, name) in enumerate(zip(shape, logical_axes)):
                if (name == "embed" and parts[i] is None
                        and dim % msize == 0 and dim >= msize):
                    parts[i] = "model"
                    break
        # trim trailing Nones for tidier HLO
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding(self, shape: Sequence[int],
                 logical_axes: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(shape, logical_axes))


_LOCAL = threading.local()


def current_env() -> Optional[ShardingEnv]:
    return getattr(_LOCAL, "env", None)


@contextlib.contextmanager
def use_sharding(env: Optional[ShardingEnv]):
    prev = current_env()
    _LOCAL.env = env
    try:
        yield env
    finally:
        _LOCAL.env = prev


def batch_shard_count() -> int:
    """Devices the "batch" logical axis splits over in the active mesh
    (1 outside a mesh)."""
    env = current_env()
    if env is None:
        return 1
    return int(np.prod([env.mesh.shape[a]
                        for a in env._mesh_axes_for("batch")]))


def map_batch_shards(fn: Callable, *args, batch_args: Sequence[bool]):
    """Run ``fn`` on each device's shard of the batch axis.

    Mosaic (Pallas TPU) kernels cannot be partitioned by XLA, so a kernel
    call inside a program over a multi-device mesh goes through
    ``shard_map``: arguments flagged in ``batch_args`` are split along
    their leading axis over the mesh axes of the "batch" rule, the rest
    are replicated, and every output is split like the batch. Outside a
    mesh (or on one device) this is a plain call.
    """
    env = current_env()
    if env is None or env.mesh.size == 1:
        return fn(*args)
    axes = env._mesh_axes_for("batch")
    spec = P(axes if len(axes) > 1 else axes[0]) if axes else P()
    in_specs = tuple(spec if b else P() for b in batch_args)
    return jax.shard_map(fn, mesh=env.mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)(*args)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical names; no-op outside a mesh."""
    env = current_env()
    if env is None or np.prod(list(env.mesh.shape.values())) == 1:
        return x
    spec = env.spec(x.shape, logical_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(env.mesh, spec))
