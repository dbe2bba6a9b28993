"""Blockwise (flash) attention as Pallas TPU kernels, forward AND backward.

Why it exists: the reference is a CNN codebase with no attention at all
(SURVEY.md §5.7); this framework adds the ViT/MoCo-v3 family, and makes
long sequences first-class. At ViT's 197 tokens XLA's fused attention is
already fine — this kernel is for the long-sequence regime (high-res
images, video: thousands of tokens) where materializing the (S, S)
score matrix blows past VMEM. The classic streaming-softmax recipe
(Flash Attention; blockwise attention) keeps O(block²) live state:
running max `m`, running denominator `l`, running numerator `acc`,
renormalized as each key/value block arrives.

Arbitrary sequence lengths are supported by padding to the block size
and masking padded keys inside the kernel (ViT's 197 = 196 patches +
cls is prime — without masking no block size divides it and the kernel
would never engage).

The backward pass is two Pallas kernels (dq; dk/dv), each recomputing
attention probabilities from (q, k, lse) per tile — O(block²) live
state, like the forward. A jnp chunked-recompute fallback remains for
CPU/interpret use and as the grad oracle in tests.

It is also the per-device compute block of ring attention
(`moco_tpu/parallel/ring_attention.py`): `flash_attention_with_lse`
returns the (out, logsumexp) pair that lets partial attention results
from different devices be combined exactly, and the backward carries
the lse cotangent that merge induces.

`flash_attention` is non-causal (ViT is bidirectional). The decoder
stacks read `causal_flash_attention` at the end of this file: causal with
the key blocks above the diagonal skipped, a query/key width that may
differ from the value width (latent attention: 192 against 128), and a
key length per row. fp32 accumulation regardless of input dtype; jnp
reference implementations included for testing and as the CPU fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# Padded-row lse sentinel: exp(s - LSE_PAD) == 0 for any finite s, so
# padded queries contribute nothing in the backward kernels.
LSE_PAD = 1e30


def _attn_reference(q, k, v, scale):
    """Dense jnp reference: (B, H, S, D) -> (out, lse)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


def _pad_axis(x: jax.Array, axis: int, mult: int, value: float = 0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ------------------------------------------------------------- forward


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, scale: float, kv_len: int
):
    """One (batch*head, q-block) program: stream all K/V blocks.

    Refs: q (block_q, D); k, v (S_pad, D) — whole K/V in VMEM per program
    (ring attention keeps S_local small; for single-device long-S the
    grid could also block K, at the cost of a scratch accumulator).
    Keys at column ≥ kv_len are padding and masked to -inf.

    Dots run in the INPUT dtype with fp32 accumulation (MXU-native for
    bf16 inputs; forcing fp32 operands was measured ~2x slower than the
    XLA default-precision jnp fallback); softmax statistics stay fp32.
    """
    q = q_ref[...]
    seq_k, d = k_ref.shape
    block_q = q.shape[0]
    masked = kv_len < seq_k

    def body(start, carry):
        acc, m_prev, l_prev = carry
        kb = k_ref[pl.ds(start, block_k), :]
        vb = v_ref[pl.ds(start, block_k), :]
        s = (
            jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # (block_q, block_k) fp32
        if masked:  # static: only when padding exists
            cols = start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1)
        acc = acc * correction[:, None] + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    num_blocks = seq_k // block_k

    acc, m, l = jax.lax.fori_loop(
        0, num_blocks, lambda i, c: body(i * block_k, c), (acc0, m0, l0)
    )
    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)
    # lse is carried as a (1, block_q) row vector: Mosaic requires 2-D
    # blocks whose trailing dims are (8, 128)-aligned or full-array.
    lse_ref[0, :] = m + jnp.log(l)


def _flash_forward(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,
    v: jax.Array,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    if s_k < block_k:
        # short sequences: the dense path is already a single VMEM tile
        return _attn_reference(q, k, v, scale)
    bh = b * h
    qp = _pad_axis(q.reshape(bh, s_q, d), 1, block_q)
    kp = _pad_axis(k.reshape(bh, s_k, d), 1, block_k)
    vp = _pad_axis(v.reshape(bh, s_k, d), 1, block_k)
    sq_p, sk_p = qp.shape[1], kp.shape[1]

    kernel = functools.partial(_flash_kernel, block_k=block_k, scale=scale, kv_len=s_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),  # None: squeeze bh
            pl.BlockSpec((None, sk_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk_p, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq_p), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return (
        out[:, :s_q].reshape(b, h, s_q, d),
        lse[:, 0, :s_q].reshape(b, h, s_q),
    )


# ------------------------------------------------------------ backward


def _dq_kernel(
    q_ref, g_ref, lse_ref, delta_ref, glse_ref, k_ref, v_ref, dq_ref,
    *, block_k: int, scale: float, kv_len: int,
):
    """One (batch*head, q-block) program: dq for this query block,
    streaming K/V. ds = p ⊙ (g·vᵀ − Δ + g_lse); dq = ds·k·scale.
    Per-row stats arrive as (1, block_q) row vectors (Mosaic 2-D rule).

    NB a single fused dq+dk+dv kernel (score matrix computed once per
    tile, dq accumulated across the minor grid dim) was tried and wedged
    the remote-TPU session at compile/run; the two-pass split below is
    Mosaic-proven. Dots run in the INPUT dtype with fp32 accumulation
    (bf16 MXU passes; forcing fp32 operands measured ~2x slower)."""
    q = q_ref[...]
    g = g_ref[...]
    lse = lse_ref[0, :]
    coeff = glse_ref[0, :] - delta_ref[0, :]  # (block_q,)
    seq_k, d = k_ref.shape
    block_q = q.shape[0]
    masked = kv_len < seq_k

    def body(start, acc):
        kb = k_ref[pl.ds(start, block_k), :]
        vb = v_ref[pl.ds(start, block_k), :]
        s = (
            jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        if masked:
            cols = start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            g, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp + coeff[:, None])).astype(kb.dtype)
        return acc + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    num_blocks = seq_k // block_k
    acc = jax.lax.fori_loop(0, num_blocks, lambda i, a: body(i * block_k, a), acc0)
    dq_ref[...] = (acc * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    k_ref, v_ref, q_ref, g_ref, lse_ref, delta_ref, glse_ref, dk_ref, dv_ref,
    *, block_q: int, scale: float,
):
    """One (batch*head, k-block) program: dk, dv for this key block,
    streaming Q/G. Padded query rows carry lse = LSE_PAD ⇒ p = 0, so
    they contribute nothing; padded key rows are sliced off outside."""
    kb = k_ref[...]
    vb = v_ref[...]
    seq_q, d = q_ref.shape
    block_k = kb.shape[0]

    def body(start, carry):
        dk_acc, dv_acc = carry
        qb = q_ref[pl.ds(start, block_q), :]
        gb = g_ref[pl.ds(start, block_q), :]
        lse_b = lse_ref[0, pl.ds(start, block_q)]
        coeff_b = glse_ref[0, pl.ds(start, block_q)] - delta_ref[0, pl.ds(start, block_q)]
        s = (
            jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # (block_q, block_k)
        p = jnp.exp(s - lse_b[:, None])
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(gb.dtype), gb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            gb, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp + coeff_b[:, None])).astype(qb.dtype)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_acc, dv_acc

    zeros = jnp.zeros((block_k, d), jnp.float32)
    num_blocks = seq_q // block_q
    dk, dv = jax.lax.fori_loop(
        0, num_blocks, lambda i, c: body(i * block_q, c), (zeros, zeros)
    )
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_backward_pallas(
    q, k, v, out, lse, g, g_lse, scale, block_q, block_k, interpret
):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    bh = b * h
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qp = _pad_axis(q.reshape(bh, s_q, d), 1, block_q)
    gp = _pad_axis(g.reshape(bh, s_q, d), 1, block_q)
    # per-row stats as (bh, 1, Sq) row vectors — Mosaic needs 2-D blocks
    lsep = _pad_axis(lse.reshape(bh, 1, s_q), 2, block_q, value=LSE_PAD)
    deltap = _pad_axis(delta.reshape(bh, 1, s_q), 2, block_q)
    glsep = _pad_axis(g_lse.reshape(bh, 1, s_q), 2, block_q)
    kp = _pad_axis(k.reshape(bh, s_k, d), 1, block_k)
    vp = _pad_axis(v.reshape(bh, s_k, d), 1, block_k)
    sq_p, sk_p = qp.shape[1], kp.shape[1]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, scale=scale, kv_len=s_k),
        grid=(bh, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, sk_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk_p, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        interpret=interpret,
    )(qp, gp, lsep, deltap, glsep, kp, vp)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, scale=scale),
        grid=(bh, sk_p // block_k),
        in_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sq_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sq_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sq_p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sq_p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sq_p), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_p, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk_p, d), v.dtype),
        ],
        interpret=interpret,
    )(kp, vp, qp, gp, lsep, deltap, glsep)

    return (
        dq[:, :s_q].reshape(b, h, s_q, d),
        dk[:, :s_k].reshape(b, h, s_k, d),
        dv[:, :s_k].reshape(b, h, s_k, d),
    )


def _flash_backward_jnp(q, k, v, out, lse, g, g_lse, scale, block_q):
    """Recompute-based backward, CHUNKED over query blocks: attention
    probabilities are rebuilt from q, k and the saved lse per (block_q,
    S_k) tile inside a sequential `lax.map`, so peak memory is
    O(block_q·S_k) — never the full (S_q, S_k) matrix the forward kernel
    exists to avoid. dk/dv accumulate across chunks; dq is per-chunk.
    Serves as the CPU fallback and the grad oracle for the Pallas bwd."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    outf = out.astype(jnp.float32)
    s_q = q.shape[2]

    def chunk_grads(args):
        qc, gc, outc, lsec, glsec = args  # (B,H,bq,D) / (B,H,bq)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qc, kf) * scale
        p = jnp.exp(logits - lsec[..., None])  # (B,H,bq,Sk)
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, gc)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gc, vf)
        delta = jnp.sum(gc * outc, axis=-1, keepdims=True)
        # d(lse)/dq flows through p too
        ds = p * (dp - delta + glsec[..., None])
        dq_c = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, qc) * scale
        return dq_c, dk_c, dv_c

    if s_q % block_q or s_q == block_q:  # single chunk / odd sizes: one shot
        dq, dk, dv = chunk_grads((qf, gf, outf, lse, g_lse))
    else:
        n_chunks = s_q // block_q

        def to_chunks(x):  # (B,H,Sq,...) -> (n, B,H,bq,...)
            return jnp.stack(jnp.split(x, n_chunks, axis=2))

        dq_c, dk_c, dv_c = jax.lax.map(
            chunk_grads,
            (to_chunks(qf), to_chunks(gf), to_chunks(outf), to_chunks(lse), to_chunks(g_lse)),
        )
        dq = jnp.concatenate(list(dq_c), axis=2)
        dk = jnp.sum(dk_c, axis=0)
        dv = jnp.sum(dv_c, axis=0)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(out, lse) for non-causal attention over (B, H, S, D) inputs.

    `lse[b,h,q] = logsumexp_k(q·k*scale)` — the quantity ring attention
    needs to merge partial results across devices.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash_forward(q, k, v, scale, block_q, block_k, interpret)


def _fwd(q, k, v, scale, block_q, block_k, interpret):
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, scale_, block_q, block_k, interpret)
    return (out, lse), (q, k, v, out, lse)


def _bwd(scale, block_q, block_k, interpret, res, cotangents):
    q, k, v, out, lse = res
    g, g_lse = cotangents
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    g_lse_f = (
        jnp.zeros(lse.shape, jnp.float32) if g_lse is None else g_lse.astype(jnp.float32)
    )
    # Pallas bwd engages exactly when the fwd kernel did (else the fwd
    # saved lse came from the dense path and shapes are small anyway).
    if k.shape[2] >= block_k:
        dq, dk, dv = _flash_backward_pallas(
            q, k, v, out, lse, g, g_lse_f, scale_, block_q, block_k, interpret
        )
    else:
        dq, dk, dv = _flash_backward_jnp(
            q, k, v, out, lse, g, g_lse_f, scale_, block_q
        )
    return dq, dk, dv


flash_attention_with_lse.defvjp(_fwd, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Attention output only; differentiable."""
    out, _ = flash_attention_with_lse(q, k, v, scale, block_q, block_k, interpret)
    return out


# ------------------------------------------- causal, unequal widths, lengths
#
# What a decoder stack's attention needs and the kernels above lack: a
# causal mask whose blocks above the diagonal cost nothing, q/k of one
# width and v of another, keys masked beyond each row's own length, fewer
# key heads than query heads (grouped heads: query head j reads key head
# j // group, k and v are never copied out to the query heads), and a
# window (key p is visible to query t iff t - p < window) whose blocks
# older than the band cost nothing either.
# The grid is (batch*head, query block, key block) with the softmax state
# in VMEM scratch across the last axis, so neither K/V nor Q is ever whole
# in VMEM (8192 positions x 192 would be). A skipped key block maps to the
# block before it: its DMA is elided and its body does not run. Under a
# window the last axis counts from the first key block of the query
# block's band, so the blocks older than the band are no grid steps at all.

CAUSAL_BLOCK = 512
# below this the (S, S) scores of one head are a few VMEM tiles and XLA's
# fused attention is as good: the kernels engage on length, never on a flag
CAUSAL_MIN_SEQ = 1024
# what `_causal_fwd` names for a remat policy to keep: the attention output
# and its log-sum-exp, all the backward kernels need beyond q, k and v
CAUSAL_SAVED_NAMES = ("causal_attention_out", "causal_attention_lse")


def _causal_mask(s_q: int, s_k: int, kv_lens: jax.Array, window: Optional[int] = None) -> jax.Array:
    """(B, 1, S_q, S_k): key at or before the query, inside the row's
    length and, under a window, fewer than `window` positions back."""
    rows = jnp.arange(s_q)[:, None]
    cols = jnp.arange(s_k)[None, :]
    seen = cols <= rows
    if window is not None:
        seen = seen & (rows - cols < window)
    return seen[None, None] & (cols[None, None] < kv_lens[:, None, None, None])


def _causal_attn_reference(q, k, v, kv_lens, scale, window=None):
    """Dense masked jnp attention, (B, H, S, Dqk) x (B, Hk, S, Dv) -> (B, H, S, Dv)."""
    mask = _causal_mask(q.shape[2], k.shape[2], kv_lens, window)
    if k.shape[1] != q.shape[1]:  # grouped heads: query head j reads key head j // group
        b, h, s, _ = q.shape
        qg = q.reshape(b, k.shape[1], h // k.shape[1], s, q.shape[-1])
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k).astype(jnp.float32) * scale
        probs = jax.nn.softmax(jnp.where(mask[:, :, None], logits, NEG_INF), axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
        return out.reshape(b, h, s, v.shape[-1]).astype(v.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)).astype(v.dtype)


def _bound(pick_int, pick_traced, a, b):
    """max or min of two block indices, static (Python ints, for a grid's
    length) or traced (inside a kernel or an index map)."""
    return pick_int(a, b) if isinstance(a, int) and isinstance(b, int) else pick_traced(a, b)


def _key_block(i, j, block_q: int, block_k: int, window: Optional[int]):
    """The key block of step `j` of query block `i`: without a window the
    j-th; under one the j-th from the block that holds the oldest key the
    query block's first row sees."""
    if window is None:
        return j
    return _bound(max, jnp.maximum, i * block_q - (window - 1), 0) // block_k + j


def _last_query_block(j, block_q: int, block_k: int, window: int, n_q: int):
    """The last query block that reads key block `j` under a window: the
    one that holds the newest query its last key is visible to."""
    return _bound(min, jnp.minimum, (j * block_k + block_k - 1 + window - 1) // block_q, n_q - 1)


def _band_steps(s: int, block_q: int, block_k: int, window: Optional[int]) -> tuple:
    """(key blocks a query block reads at most, query blocks a key block is
    read by at most): the lengths of the kernels' last grid axis. Without a
    window every block of the sequence (those past the diagonal are skipped)."""
    n_q, n_k = s // block_q, s // block_k
    if window is None:
        return n_k, n_q
    keys = max(
        (i * block_q + block_q - 1) // block_k - _key_block(i, 0, block_q, block_k, window) + 1
        for i in range(n_q)
    )
    queries = max(
        _last_query_block(j, block_q, block_k, window, n_q) - (j * block_k) // block_q + 1
        for j in range(n_k)
    )
    return keys, queries


def _scores(q, kb, scale, q_start, k_start, kv_len, window=None):
    """Masked scaled scores of one (query block, key block) tile, fp32."""
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = (cols <= rows) & (cols < kv_len)
    if window is not None:
        seen = seen & (rows - cols < window)
    return jnp.where(seen, s, NEG_INF)


def _causal_fwd_kernel(
    lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
    *, scale: float, block_q: int, block_k: int, heads: int, window: Optional[int],
):
    i, j = pl.program_id(1), pl.program_id(2)
    kv_len = lens_ref[pl.program_id(0) // heads]
    q_start = i * block_q
    k_start = _key_block(i, j, block_q, block_k, window) * block_k

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when((k_start < q_start + block_q) & (k_start < kv_len))
    def _():
        # a row that sees no key of this block (the band's oldest block, under
        # a window) adds exp(NEG_INF - NEG_INF) = 1 a key to l and acc; the
        # first block with a key it does see (its own diagonal block at the
        # latest) multiplies both by exp(NEG_INF - m) = 0
        s = _scores(q_ref[...], k_ref[...], scale, q_start, k_start, kv_len, window)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        vb = v_ref[...]
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        # a row of a length-0 batch entry has seen no key: l = 0; it is
        # padding, its output is never read, but it must stay finite
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


def _causal_dq_kernel(
    lens_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref, acc,
    *, scale: float, block_q: int, block_k: int, heads: int, window: Optional[int],
):
    i, j = pl.program_id(1), pl.program_id(2)
    kv_len = lens_ref[pl.program_id(0) // heads]
    q_start = i * block_q
    k_start = _key_block(i, j, block_q, block_k, window) * block_k

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when((k_start < q_start + block_q) & (k_start < kv_len))
    def _():
        kb, vb, g = k_ref[...], v_ref[...], g_ref[...]
        s = _scores(q_ref[...], kb, scale, q_start, k_start, kv_len, window)
        p = jnp.exp(s - lse_ref[...])
        dp = jax.lax.dot_general(
            g, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta_ref[...])).astype(kb.dtype)
        acc[...] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = (acc[...] * scale).astype(dq_ref.dtype)


def _causal_dkv_kernel(
    lens_ref, k_ref, v_ref, q_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, scale: float, block_q: int, block_k: int, heads: int, window: Optional[int],
    group: int, q_steps: int, n_q: int,
):
    # key block outside; inside, the query blocks of every query head of the
    # key head's group stream through one accumulator, `q_steps` a head
    j, t = pl.program_id(1), pl.program_id(2)
    kv_len = lens_ref[pl.program_id(0) // heads]
    i = t if group == 1 else t % q_steps
    if window is not None:  # the axis counts from the key block's diagonal
        i = i + (j * block_k) // block_q
    q_start, k_start = i * block_q, j * block_k

    @pl.when(t == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    runs = (k_start < q_start + block_q) & (k_start < kv_len)
    if window is not None:
        runs = runs & (i <= _last_query_block(j, block_q, block_k, window, n_q))

    @pl.when(runs)
    def _():
        qb, g, vb = q_ref[...], g_ref[...], v_ref[...]
        s = _scores(qb, k_ref[...], scale, q_start, k_start, kv_len, window)
        p = jnp.exp(s - lse_ref[...])
        dv_acc[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            g, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta_ref[...])).astype(qb.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _causal_specs(block_q: int, block_k: int, d_qk: int, d_v: int, group: int,
                  window: Optional[int]):
    """Block specs over a (bh, query block, key block) grid. A key block
    above the diagonal maps to the last one needed, so it is not fetched;
    query head `b` reads key head `b // group`."""
    last_k = lambda i: (i * block_q + block_q - 1) // block_k
    q_map = lambda b, i, j, lens: (b, i, 0)
    kv_head = (lambda b: b) if group == 1 else (lambda b: b // group)
    k_map = lambda b, i, j, lens: (
        kv_head(b), jnp.minimum(_key_block(i, j, block_q, block_k, window), last_k(i)), 0
    )
    return {
        "q": pl.BlockSpec((None, block_q, d_qk), q_map),
        "g": pl.BlockSpec((None, block_q, d_v), q_map),
        "row": pl.BlockSpec((None, block_q, 1), q_map),
        "k": pl.BlockSpec((None, block_k, d_qk), k_map),
        "v": pl.BlockSpec((None, block_k, d_v), k_map),
    }


_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _causal_forward(q, k, v, kv_lens, scale, block_q, block_k, interpret, window, name):
    b, h, s, d_qk = q.shape
    h_k, d_v = k.shape[1], v.shape[-1]
    bh = b * h
    sp = _causal_specs(block_q, block_k, d_qk, d_v, h // h_k, window)
    k_steps, _ = _band_steps(s, block_q, block_k, window)
    out, lse = pl.pallas_call(
        functools.partial(
            _causal_fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, heads=h,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, s // block_q, k_steps),
            in_specs=[sp["q"], sp["k"], sp["v"]],
            out_specs=[sp["g"], sp["row"]],
            scratch_shapes=[
                pltpu.VMEM((block_q, d_v), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d_v), v.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=f"{name}_fwd",
    )(kv_lens, q.reshape(bh, s, d_qk), k.reshape(b * h_k, s, d_qk), v.reshape(b * h_k, s, d_v))
    return out.reshape(b, h, s, d_v), lse


def _causal_backward(q, k, v, kv_lens, out, lse, g, scale, block_q, block_k, interpret, window,
                     name):
    b, h, s, d_qk = q.shape
    h_k, d_v = k.shape[1], v.shape[-1]
    bh, bh_k, group = b * h, b * h_k, h // h_k
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    ).reshape(bh, s, 1)
    q3, k3 = q.reshape(bh, s, d_qk), k.reshape(bh_k, s, d_qk)
    v3, g3 = v.reshape(bh_k, s, d_v), g.reshape(bh, s, d_v)
    sp = _causal_specs(block_q, block_k, d_qk, d_v, group, window)
    k_steps, q_steps = _band_steps(s, block_q, block_k, window)
    n_q = s // block_q
    static = dict(scale=scale, block_q=block_q, block_k=block_k, window=window)
    dq = pl.pallas_call(
        functools.partial(_causal_dq_kernel, heads=h, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_q, k_steps),
            in_specs=[sp["q"], sp["g"], sp["row"], sp["row"], sp["k"], sp["v"]],
            out_specs=sp["q"],
            scratch_shapes=[pltpu.VMEM((block_q, d_qk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s, d_qk), q.dtype),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=f"{name}_dq",
    )(kv_lens, q3, g3, lse, delta, k3, v3)

    # key block outside, one grid cell a KEY head: the query blocks of its
    # group's query heads stream, head after head, and dk, dv are written
    # once. Those before the diagonal map to the first one needed and are
    # skipped; under a window the axis starts at the diagonal and those
    # past the band map to the last one needed
    first_q = lambda j: (j * block_k) // block_q
    q_head = (lambda b_, t: b_) if group == 1 else (lambda b_, t: b_ * group + t // q_steps)
    q_step = (lambda t: t) if group == 1 else (lambda t: t % q_steps)
    if window is None:
        q_block = lambda j, t: jnp.maximum(q_step(t), first_q(j))
    else:
        q_block = lambda j, t: jnp.minimum(
            first_q(j) + q_step(t), _last_query_block(j, block_q, block_k, window, n_q)
        )
    kq_map = lambda b_, j, t, lens: (q_head(b_, t), q_block(j, t), 0)
    kk_map = lambda b_, j, t, lens: (b_, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(
            _causal_dkv_kernel, heads=h_k, group=group, q_steps=q_steps, n_q=n_q, **static
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh_k, s // block_k, group * q_steps),
            in_specs=[
                pl.BlockSpec((None, block_k, d_qk), kk_map),
                pl.BlockSpec((None, block_k, d_v), kk_map),
                pl.BlockSpec((None, block_q, d_qk), kq_map),
                pl.BlockSpec((None, block_q, d_v), kq_map),
                pl.BlockSpec((None, block_q, 1), kq_map),
                pl.BlockSpec((None, block_q, 1), kq_map),
            ],
            out_specs=[
                pl.BlockSpec((None, block_k, d_qk), kk_map),
                pl.BlockSpec((None, block_k, d_v), kk_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d_qk), jnp.float32),
                pltpu.VMEM((block_k, d_v), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh_k, s, d_qk), k.dtype),
            jax.ShapeDtypeStruct((bh_k, s, d_v), v.dtype),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=f"{name}_dkv",
    )(kv_lens, k3, v3, q3, g3, lse, delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _causal_flash(q, k, v, kv_lens, scale, block_q, block_k, interpret, window, name):
    return _causal_forward(q, k, v, kv_lens, scale, block_q, block_k, interpret, window, name)[0]


def _causal_fwd(q, k, v, kv_lens, scale, block_q, block_k, interpret, window, name):
    """The forward rule. The kernel's two outputs carry `CAUSAL_SAVED_NAMES`,
    so a `jax.checkpoint` whose policy saves those names keeps them, and the
    forward kernel is dead code in its recomputation: what the backward
    kernels need arrives saved. Outside such a policy a name is an identity.
    The log-sum-exp is named without its unit axis: (B*H, S, 1) float32 pads
    the 1 to a tile's 128 lanes in HBM, 128 times the bytes of (B*H, S)."""
    out, lse = _causal_forward(q, k, v, kv_lens, scale, block_q, block_k, interpret, window, name)
    out = checkpoint_name(out, CAUSAL_SAVED_NAMES[0])
    lse = checkpoint_name(lse.reshape(lse.shape[:2]), CAUSAL_SAVED_NAMES[1])
    return out, (q, k, v, kv_lens, out, lse)


def _causal_bwd(scale, block_q, block_k, interpret, window, name, res, g):
    q, k, v, kv_lens, out, lse = res
    dq, dk, dv = _causal_backward(
        q, k, v, kv_lens, out, lse[..., None], g, scale, block_q, block_k, interpret, window, name
    )
    return dq, dk, dv, None


_causal_flash.defvjp(_causal_fwd, _causal_bwd)


def causal_flash_attention(
    q: jax.Array,  # (B, H, S, Dqk)
    k: jax.Array,  # (B, Hk, S, Dqk): Hk divides H; query head j reads key head j // (H / Hk)
    v: jax.Array,  # (B, Hk, S, Dv)
    kv_lens: jax.Array,  # (B,) int32: keys at or beyond a row's length are masked
    scale: Optional[float] = None,
    block_q: int = CAUSAL_BLOCK,
    block_k: int = CAUSAL_BLOCK,
    interpret: bool = False,
    window: Optional[int] = None,  # key p is visible to query t iff t - p < window
    name: Optional[str] = None,  # the kernels' names are `<name>_{fwd,dq,dkv}`
) -> jax.Array:
    """Causal attention (B, H, S, Dv); differentiable in q, k and v. The
    Pallas kernels run where the sequence is long enough to need them and
    the blocks divide it; a short one takes the dense masked product
    (blocks given by the caller always mean the kernels: a test's way to
    reach them at a test's length). The group and the window are the
    layer's own and static: with one key head a query head and no window
    the kernels are the programs they were without either. `name`: what a
    trace finds this caller's three `pallas_call`s by (default
    `causal_attention_*`, or `window_attention_*` under a window)."""
    s = q.shape[2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    kv_lens = kv_lens.astype(jnp.int32)
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{k.shape[1]} key and {v.shape[1]} value heads under {q.shape[1]} query heads")
    if window is not None and window < 1:
        raise ValueError(f"a window holds at least the query's own key, got {window}")
    # what a trace finds the three `pallas_call`s by: a call with a window is
    # another program than one without
    name = name or ("causal" if window is None else "window") + "_attention"
    if s < CAUSAL_MIN_SEQ and (block_q, block_k) == (CAUSAL_BLOCK, CAUSAL_BLOCK):
        return _causal_attn_reference(q, k, v, kv_lens, scale, window)
    if s % block_q or s % block_k:
        raise ValueError(f"sequence length {s} is not a multiple of blocks {block_q}, {block_k}")
    return _causal_flash(q, k, v, kv_lens, scale, block_q, block_k, interpret, window, name)
