"""Jit'd wrapper for the fused logprob kernel with backend dispatch.

On TPU this calls the Pallas kernel (compiled) under a ``custom_vjp``
whose backward is ``token_logprob_entropy_bwd``: the analytic gradient
``d logp/dh = w[:, t] - E_p[w]`` (plus the entropy term), recomputed one
vocab block at a time, so neither pass writes the [T, V] logits to HBM.
Everywhere else it uses the pure-jnp oracle and ``jax.grad`` of it; the
test suite checks the kernel and its backward against that oracle in
interpret mode.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax

from repro.distributed.sharding import map_batch_shards
from repro.kernels.logprob.kernel import (
    logprob_stats_pallas,
    token_logprob_entropy_bwd,
)
from repro.kernels.logprob.ref import token_logprob_entropy_ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def token_logprob_entropy_kernel(hidden: jax.Array, w: jax.Array,
                                 targets: jax.Array, interpret: bool
                                 ) -> Tuple[jax.Array, jax.Array]:
    """hidden [T, d], w [d, V], targets [T] -> (logp, entropy), through
    the Pallas forward and the blocked analytic backward."""
    logp, ent, _ = logprob_stats_pallas(hidden, w, targets,
                                        interpret=interpret)
    return logp, ent


def _kernel_fwd(hidden, w, targets, interpret):
    logp, ent, logz = logprob_stats_pallas(hidden, w, targets,
                                           interpret=interpret)
    return (logp, ent), (hidden, w, targets, logz, ent)


def _kernel_bwd(interpret, res, g):
    hidden, w, targets, logz, ent = res
    g_logp, g_ent = g
    # its own scope: the name stack under a custom VJP's backward does not
    # reliably carry the caller's scopes
    with jax.named_scope("logprob_head_bwd"):
        dh, dw = token_logprob_entropy_bwd(hidden, w, targets, logz, ent,
                                           g_logp, g_ent)
    return dh, dw, None


token_logprob_entropy_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def token_logprob_entropy(hidden: jax.Array, w: jax.Array,
                          targets: jax.Array, *, interpret: bool = False
                          ) -> Tuple[jax.Array, jax.Array]:
    """hidden [..., d], w [d, V], targets [...] -> (logp, entropy) [...].

    ``interpret=True`` runs the kernel path in the Pallas interpreter off
    TPU (tests); on TPU the kernel is always compiled. Under a multi-device
    mesh the kernel runs on each device's rows (``map_batch_shards``)."""
    lead = hidden.shape[:-1]
    h2 = hidden.reshape(-1, hidden.shape[-1])
    t2 = targets.reshape(-1)
    if _use_pallas() or interpret:
        interp = not _use_pallas()

        def kernel(h, w, t):
            return token_logprob_entropy_kernel(h, w, t, interp)

        logp, ent = map_batch_shards(kernel, h2, w, t2,
                                     batch_args=(True, False, True))
    else:
        logp, ent = token_logprob_entropy_ref(h2, w, t2)
    return logp.reshape(lead), ent.reshape(lead)
