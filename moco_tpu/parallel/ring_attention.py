"""Ring attention: exact sequence-parallel attention over a mesh axis.

The reference has no sequence dimension at all (SURVEY.md §5.7) — this
subsystem makes long-context a first-class capability of the rebuild:
sequences too long for one chip's HBM/VMEM are sharded over a mesh axis,
and attention over the FULL sequence is computed by rotating key/value
shards around the ring with `lax.ppermute` (XLA lowers neighbor
permutes to ICI transfers) while queries stay put.

Per ring step each device runs blockwise (flash) attention of its local
queries against the visiting K/V shard — `moco_tpu.ops.flash_attention`
returns (out, logsumexp), which is exactly what the numerically-stable
streaming merge needs:

    m'   = max(m, lse_blk)
    num  = num * e^(m-m') + out_blk * e^(lse_blk-m')
    den  = den * e^(m-m') + e^(lse_blk-m')

After n steps every device holds attention of its queries over the
whole sequence; K/V have completed a full rotation (back to their
owners). Communication per step is the K/V shard — the same volume a
single all_gather would move in total, but with O(S/n) peak memory
instead of O(S), and compute/comm naturally pipelined across steps.

Non-causal (bidirectional ViT-style); use inside `shard_map` with the
sequence axis named.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.obs import comms
from moco_tpu.ops.flash_attention import flash_attention_with_lse

NEG_INF = -1e30


def ring_attention(
    q: jax.Array,  # (B, H, S_local, D) — this device's query shard
    k: jax.Array,  # (B, H, S_local, D) — this device's key shard
    v: jax.Array,
    axis_name: str,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Exact attention over the axis-sharded sequence; call under shard_map.

    Returns this device's (B, H, S_local, D) output slice.
    """
    n = lax.axis_size(axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, h, s_local, d = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    num0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    den0 = jnp.zeros((b, h, s_local), jnp.float32)

    def body(_, carry):
        num, m, den, k_cur, v_cur = carry
        out_blk, lse_blk = flash_attention_with_lse(
            q, k_cur, v_cur, scale, block_q, block_k, interpret
        )
        m_new = jnp.maximum(m, lse_blk)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(lse_blk - m_new)
        num = num * c_old[..., None] + out_blk.astype(jnp.float32) * c_new[..., None]
        den = den * c_old + c_new
        # rotate K/V to the next device; after n steps they are home again
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return num, m_new, den, k_nxt, v_nxt

    # the ring rotates the K/V shards n times per call (the fori_loop
    # body traces once but executes n ppermute hops)
    with comms.tag("ring_attention.kv_ppermute", "ppermute", (k, v), n, calls_per_step=n):
        num, m, den, _, _ = jax.lax.fori_loop(0, n, body, (num0, m0, den0, k, v))
    return (num / den[..., None]).astype(q.dtype)
