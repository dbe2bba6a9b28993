"""Fused streaming InfoNCE — Pallas TPU kernel.

Reference hot path (`moco/builder.py:~L128-161` + `main_moco.py:~L185`):
materialize `logits = [q·k | q·queueᵀ] / T` of shape (B, 1+K) — at the
default K=65536 that is a 67 MB fp32 intermediate per step — then run
CrossEntropyLoss over it, plus a top-k pass for the proxy accuracy.

This kernel never materializes the logits. The queue streams through
VMEM in (block_k, C) tiles while per-example running statistics are
carried in VMEM scratch across the sequential TPU grid:

    m       running max logit          (flash-softmax trick)
    l       running Σ exp(logit - m)
    n_above running count of negatives whose logit > the positive's

which yield exactly the three things the training step consumes:
  - per-example CE loss  = lse - pos          (lse = m + log l)
  - acc@1 = [n_above == 0], acc@5 = [n_above < 5]  (positive is column 0
    in the reference layout, so rank == #negatives above it)
  - the backward needs only (lse, pos): dq = Σ_j p_j·key_j/T - g·k/T with
    p_j = exp(q·key_j/T - lse), streamed again tile-by-tile.

queue and k get no gradient (the reference detaches both). Normalization
of q happens OUTSIDE (jnp) so autodiff chains through it naturally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 2048
NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, queue_ref, pos_ref, lse_ref, above_ref, m_sc, l_sc, a_sc, *, inv_t):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    q = q_ref[...]  # (B, C) fp32
    pos = jnp.sum(q * k_ref[...], axis=-1) * inv_t  # (B,)

    @pl.when(i == 0)
    def _():
        m_sc[...] = jnp.maximum(pos, NEG_INF)
        l_sc[...] = jnp.exp(pos - jnp.maximum(pos, NEG_INF))  # == 1
        a_sc[...] = jnp.zeros_like(a_sc)

    tile = queue_ref[...]  # (block_k, C)
    s = jax.lax.dot_general(
        q, tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * inv_t  # (B, block_k)

    m_prev, l_prev = m_sc[...], l_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    l_new = l_prev * jnp.exp(m_prev - m_new) + jnp.sum(jnp.exp(s - m_new[:, None]), axis=-1)
    m_sc[...] = m_new
    l_sc[...] = l_new
    a_sc[...] = a_sc[...] + jnp.sum((s > pos[:, None]).astype(jnp.int32), axis=-1)

    @pl.when(i == n - 1)
    def _():
        pos_ref[...] = pos
        lse_ref[...] = m_sc[...] + jnp.log(l_sc[...])
        above_ref[...] = a_sc[...]


def _bwd_kernel(q_ref, queue_ref, lse_ref, g_ref, dq_ref, acc_sc, *, inv_t):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    q = q_ref[...]

    @pl.when(i == 0)
    def _():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    tile = queue_ref[...]
    s = jax.lax.dot_general(
        q, tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * inv_t
    p = jnp.exp(s - lse_ref[...][:, None]) * g_ref[...][:, None]  # (B, block_k)
    acc_sc[...] = acc_sc[...] + jax.lax.dot_general(
        p, tile, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(i == n - 1)
    def _():
        dq_ref[...] = acc_sc[...] * inv_t


def _forward(q, k, queue, temperature, block_k, interpret):
    b, c = q.shape
    kk = queue.shape[0]
    kernel = functools.partial(_fwd_kernel, inv_t=1.0 / temperature)
    return pl.pallas_call(
        kernel,
        grid=(kk // block_k,),
        in_specs=[
            pl.BlockSpec((b, c), lambda i: (0, 0)),
            pl.BlockSpec((b, c), lambda i: (0, 0)),
            pl.BlockSpec((block_k, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b,), lambda i: (0,)),
            pl.BlockSpec((b,), lambda i: (0,)),
            pl.BlockSpec((b,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b,), jnp.float32),  # pos
            jax.ShapeDtypeStruct((b,), jnp.float32),  # lse
            jax.ShapeDtypeStruct((b,), jnp.int32),  # negatives above pos
        ],
        scratch_shapes=[
            pltpu.VMEM((b,), jnp.float32),
            pltpu.VMEM((b,), jnp.float32),
            pltpu.VMEM((b,), jnp.int32),
        ],
        interpret=interpret,
        name="infonce_fwd",
    )(q.astype(jnp.float32), k.astype(jnp.float32), queue.astype(jnp.float32))


def check_tiling(num_keys: int, block_k: int) -> None:
    """The kernel streams whole (block_k, C) tiles; nothing here gives
    way to the dense path. `make_train_step` calls this at build time
    for an explicit `fused_infonce=True`, and its auto rule only selects
    the fused loss when K tiles evenly; a direct call with a bad pair is
    refused here, where materializing (B, 1+K) unasked would be the
    wrong answer."""
    if block_k <= 0 or num_keys <= 0 or num_keys % block_k:
        raise ValueError(
            f"fused InfoNCE needs a positive block that divides K: "
            f"K={num_keys}, block_k={block_k}"
        )


def _reference(q, k, queue, temperature):
    """Dense jnp oracle for the tests: same outputs."""
    pos = jnp.sum(q * k, axis=-1) / temperature
    # k/queue are detached by construction: infonce_stats' custom_vjp
    # returns no cotangent for them (_vjp_bwd yields dq only)
    neg = q @ queue.T / temperature  # mocolint: disable=JX005
    all_logits = jnp.concatenate([pos[:, None], neg], axis=1)
    lse = jax.nn.logsumexp(all_logits, axis=-1)
    above = jnp.sum(neg > pos[:, None], axis=-1).astype(jnp.int32)
    return pos, lse, above


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def infonce_stats(
    q: jax.Array,  # (B, C) L2-normalized queries — grads flow
    k: jax.Array,  # (B, C) positive keys — detached
    queue: jax.Array,  # (K, C) negatives — detached
    temperature: float,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """(pos, lse, n_above) per example, without materializing (B, 1+K)."""
    check_tiling(queue.shape[0], block_k)
    return _forward(q, k, queue, temperature, block_k, interpret)


def _vjp_fwd(q, k, queue, temperature, block_k, interpret):
    out = infonce_stats(q, k, queue, temperature, block_k, interpret)
    pos, lse, above = out
    return out, (q, k, queue, lse)


def _vjp_bwd(temperature, block_k, interpret, res, cots):
    q, k, queue, lse = res
    g_pos, g_lse, _ = cots  # n_above is integer — no gradient
    inv_t = 1.0 / temperature
    b, c = q.shape
    kk = queue.shape[0]
    # dq from the lse term: sum_j p_j key_j / T (streamed), j over [pos]+queue
    if g_lse is None:
        g_lse = jnp.zeros((b,), jnp.float32)
    if g_pos is None:
        g_pos = jnp.zeros((b,), jnp.float32)
    kernel = functools.partial(_bwd_kernel, inv_t=inv_t)
    dq_neg = pl.pallas_call(
        kernel,
        grid=(kk // block_k,),
        in_specs=[
            pl.BlockSpec((b, c), lambda i: (0, 0)),
            pl.BlockSpec((block_k, c), lambda i: (i, 0)),
            pl.BlockSpec((b,), lambda i: (0,)),
            pl.BlockSpec((b,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((b, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, c), jnp.float32)],
        interpret=interpret,
        name="infonce_bwd",
    )(q.astype(jnp.float32), queue.astype(jnp.float32), lse, g_lse)
    # pos-logit path: through both the pos output and the lse
    pos = jnp.sum(q * k, axis=-1) * inv_t
    p_pos = jnp.exp(pos - lse)
    coeff = (g_pos + g_lse * p_pos) * inv_t
    dq = dq_neg + coeff[:, None] * k
    return dq.astype(q.dtype), None, None


infonce_stats.defvjp(_vjp_fwd, _vjp_bwd)


def fused_infonce_loss(
    q: jax.Array,
    k: jax.Array,
    queue: jax.Array,
    temperature: float,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """(mean CE loss, {'acc1','acc5'}) — drop-in for the
    infonce_logits → cross_entropy → topk_accuracy chain with the
    positive at column 0 (labels are implicitly all-zero)."""
    k = jax.lax.stop_gradient(k)
    queue = jax.lax.stop_gradient(queue)
    pos, lse, above = infonce_stats(q, k, queue, temperature, block_k, interpret)
    loss = jnp.mean(lse - pos)
    metrics = {
        "acc1": 100.0 * jnp.mean((above == 0).astype(jnp.float32)),
        "acc5": 100.0 * jnp.mean((above < 5).astype(jnp.float32)),
    }
    return loss, metrics
