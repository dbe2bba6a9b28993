"""A forward-only `lax.optimization_barrier`.

Deliberate, not a version shim: jax 0.9.0's own barrier is
differentiable, but its transpose rule instantiates every zero
cotangent and wraps the cotangents in a second barrier. For the
layer-granular ZeRO `_tie` (core/moco.py) — which barriers the next
group's shards together with an activation-sized anchor whose barrier
output is then dropped — that would materialize an activation-sized
zero in the backward pass and tie the shard cotangents to it. The
barrier here constrains forward scheduling only; cotangents pass
through untouched, so the backward pass sees the gradients it would
see without any barrier.
"""

from __future__ import annotations

import jax


@jax.custom_vjp
def optimization_barrier(x):
    return jax.lax.optimization_barrier(x)


def _barrier_fwd(x):
    return optimization_barrier(x), None


def _barrier_bwd(_, g):
    return (g,)


optimization_barrier.defvjp(_barrier_fwd, _barrier_bwd)
