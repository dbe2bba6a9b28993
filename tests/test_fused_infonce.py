"""Fused streaming InfoNCE kernel vs the dense jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.ops.fused_infonce import _reference, fused_infonce_loss, infonce_stats
from moco_tpu.ops.losses import cross_entropy, infonce_logits, l2_normalize, topk_accuracy

B, C, K = 16, 32, 256


@pytest.fixture(scope="module")
def data():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = l2_normalize(jax.random.normal(ks[0], (B, C)))
    k = l2_normalize(jax.random.normal(ks[1], (B, C)))
    queue = l2_normalize(jax.random.normal(ks[2], (K, C)))
    return q, k, queue


def test_stats_match_reference(data):
    q, k, queue = data
    pos, lse, above = infonce_stats(q, k, queue, 0.2, block_k=64, interpret=True)
    rpos, rlse, rabove = _reference(q, k, queue, 0.2)
    np.testing.assert_allclose(np.asarray(pos), np.asarray(rpos), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(above), np.asarray(rabove))


def test_loss_and_metrics_match_dense_chain(data):
    """Matches the existing infonce_logits → CE → topk path exactly."""
    q, k, queue = data
    loss, metrics = fused_infonce_loss(q, k, queue, 0.2, block_k=64, interpret=True)
    logits, labels = infonce_logits(q, k, queue, 0.2)
    ref_loss = cross_entropy(logits, labels)
    ref_metrics = topk_accuracy(logits, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(float(metrics["acc1"]), float(ref_metrics["acc1"]), atol=1e-4)
    np.testing.assert_allclose(float(metrics["acc5"]), float(ref_metrics["acc5"]), atol=1e-4)


def test_gradient_matches_dense_chain(data):
    q, k, queue = data

    def fused(q):
        loss, _ = fused_infonce_loss(q, k, queue, 0.2, block_k=64, interpret=True)
        return loss

    def dense(q):
        logits, labels = infonce_logits(q, k, queue, 0.2)
        return cross_entropy(logits, labels)

    g_fused = jax.grad(fused)(q)
    g_dense = jax.grad(dense)(q)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_dense), rtol=1e-4, atol=1e-6)


def test_gradient_chains_through_normalization(data):
    """The real call site normalizes q first — grads must chain."""
    _, k, queue = data
    raw = jax.random.normal(jax.random.PRNGKey(5), (B, C)) * 3.0

    def fused(raw):
        loss, _ = fused_infonce_loss(l2_normalize(raw), k, queue, 0.2, block_k=64, interpret=True)
        return loss

    def dense(raw):
        logits, labels = infonce_logits(l2_normalize(raw), k, queue, 0.2)
        return cross_entropy(logits, labels)

    np.testing.assert_allclose(
        np.asarray(jax.grad(fused)(raw)), np.asarray(jax.grad(dense)(raw)), rtol=1e-4, atol=1e-6
    )


def test_indivisible_k_raises(data):
    """The kernel entry never gives way to the dense path: a block that
    does not tile K is an error, in the forward and under grad."""
    q, k, queue = data
    with pytest.raises(ValueError, match="K=100, block_k=64"):
        infonce_stats(q, k, queue[:100], 0.2, block_k=64, interpret=True)
    with pytest.raises(ValueError, match="divides K"):
        jax.grad(
            lambda q: fused_infonce_loss(q, k, queue[:100], 0.2, block_k=64, interpret=True)[0]
        )(q)
