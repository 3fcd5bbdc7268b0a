"""Parameter spec trees.

Model code declares parameters as nested dicts of ``ParamSpec`` (shape +
logical axis names + init kind). From one spec tree we derive:

* concrete initialized params (``init_from_specs``),
* abstract ``ShapeDtypeStruct`` stand-ins for the dry-run,
* ``NamedSharding`` trees from the active ``ShardingEnv``.

This keeps model definitions framework-free (no flax) while still carrying
the logical-axis metadata GSPMD needs.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import ShardingEnv


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | a_log | dt_bias | conv
    scale: Optional[float] = None  # stddev override for "normal"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


SpecTree = Dict[str, Any]  # nested dicts of ParamSpec


def _fan_in(shape: Tuple[int, ...]) -> int:
    # weights are stored input-major: all but the last axis feed the output
    if len(shape) <= 1:
        return max(shape[0] if shape else 1, 1)
    return int(np.prod(shape[:-1]))


def _init_leaf(spec: ParamSpec, key: jax.Array, dtype: Any) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "a_log":
        # Mamba2 A in [1, 16]
        lo, hi = 1.0, 16.0
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        return jnp.log(lo + u * (hi - lo)).astype(dtype)
    if spec.init == "dt_bias":
        # inverse softplus of dt ~ U[1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        dt = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    std = spec.scale if spec.scale is not None else _fan_in(spec.shape) ** -0.5
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def _walk(tree: SpecTree, path=()):  # yields (path, spec)
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _walk(v, path + (k,))
        else:
            yield path + (k,), v


def init_from_specs(specs: SpecTree, key: jax.Array, dtype: Any) -> Any:
    out: Dict[str, Any] = {}
    for path, spec in _walk(specs):
        sub = out
        for p in path[:-1]:
            sub = sub.setdefault(p, {})
        # crc32, not hash(): str hashes are salted per process, and the
        # same seed must give the same weights in every process
        leaf_key = jax.random.fold_in(
            key, zlib.crc32("/".join(path).encode()) % (2**31))
        sub[path[-1]] = _init_leaf(spec, leaf_key, dtype)
    return out


def abstract_from_specs(specs: SpecTree, dtype: Any) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def shardings_from_specs(specs: SpecTree, env: ShardingEnv) -> Any:
    return jax.tree.map(
        lambda s: env.sharding(s.shape, s.logical),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def logical_axes_tree(specs: SpecTree) -> Any:
    return jax.tree.map(
        lambda s: s.logical, specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def count_params(specs: SpecTree) -> int:
    return sum(int(np.prod(s.shape)) for _, s in _walk(specs))


def stack_specs(spec: SpecTree, n: int, axis_name: str = "layers") -> SpecTree:
    """Prepend a stacked (scan) axis to every leaf of a block spec tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, (axis_name,) + s.logical,
                            s.init, s.scale),
        spec,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
