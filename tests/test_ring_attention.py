"""Ring attention == dense attention over the gathered sequence.

Runs under shard_map on the 8-virtual-CPU-device mesh (conftest), the
same harness the other cross-replica patterns use (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from moco_tpu.ops.flash_attention import _attn_reference
from moco_tpu.parallel.ring_attention import ring_attention
from jax import shard_map

B, H, D = 2, 2, 32
SEQ_AXIS = "seq"


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), (SEQ_AXIS,))


@pytest.mark.parametrize("n_dev,s_local", [(4, 64), (8, 32), (2, 128)])
def test_matches_dense_full_sequence(n_dev, s_local):
    mesh = _mesh(n_dev)
    s_total = n_dev * s_local
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, s_total, D), jnp.float32) for kk in ks)

    ring = jax.jit(
        shard_map(
            lambda q, k, v: ring_attention(q, k, v, SEQ_AXIS, block_q=32, block_k=32, interpret=True),
            mesh=mesh,
            in_specs=(P(None, None, SEQ_AXIS), P(None, None, SEQ_AXIS), P(None, None, SEQ_AXIS)),
            out_specs=P(None, None, SEQ_AXIS),
            check_vma=False,
        )
    )
    out = ring(q, k, v)
    ref, _ = _attn_reference(q, k, v, D**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_differentiable_through_ring():
    n_dev, s_local = 4, 32
    mesh = _mesh(n_dev)
    s_total = n_dev * s_local
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, H, s_total, D), jnp.float32) for kk in ks)

    def ring_loss(q, k, v):
        f = shard_map(
            lambda q, k, v: ring_attention(q, k, v, SEQ_AXIS, block_q=32, block_k=32, interpret=True),
            mesh=mesh,
            in_specs=(P(None, None, SEQ_AXIS),) * 3,
            out_specs=P(None, None, SEQ_AXIS),
            check_vma=False,
        )
        return jnp.sum(f(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(_attn_reference(q, k, v, D**-0.5)[0] ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), rtol=1e-3, atol=1e-3)
