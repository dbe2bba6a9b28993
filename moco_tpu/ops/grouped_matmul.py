"""Grouped matrix product: rows sorted by group, one weight matrix a group.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

`lhs` is (m, k) with the rows of group 0 first, then group 1's, and so on;
`group_sizes` (g,) says how many rows each group has; `rhs` is (g, k, n).
Rows past `sum(group_sizes)` belong to no group: they cost nothing and
come back as zeros. This is the product an expert layer needs once it
has sorted its (token, expert) assignments by expert: the caller sizes
the buffer (`models/decoder.py` takes the smallest of a short ladder that
holds the call's live assignments, the worst case last, so none is ever
dropped), and only the tiles that hold rows are computed.

On a TPU the product is the Pallas grouped matmul that ships with JAX
(`jax.experimental.pallas.ops.tpu.megablox`: a grid over the row tiles
that are live, found from `group_sizes` by scalar prefetch; its backward
is the same kernel on the transposed weights plus the transposed product
for the weights' gradient). Elsewhere it is `jax.lax.ragged_dot`, which
the CPU backend computes natively. The choice is the backend's, as for the
fused InfoNCE; no option selects it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Rows of one tile. An expert that sees a few hundred tokens is one or two
# row tiles, and every row tile reads its expert's whole weight matrix once.
TILE_ROWS = 512


def _tile(size: int) -> int:
    """The widest contraction/column tile that divides `size` (the whole
    axis where none does: a test's size)."""
    return next((t for t in (1024, 768, 512, 256, 128) if size % t == 0), size)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """(m, k) x (g, k, n) -> (m, n) in `lhs`'s dtype, float32 accumulation;
    differentiable in `lhs` and `rhs`."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    live = jnp.arange(m)[:, None] < jnp.sum(group_sizes)
    # the scope holds the product and, through autodiff, its backward (`tgmm`)
    with jax.named_scope("moco.expert_ffn"):
        if jax.default_backend() == "tpu":
            from jax.experimental.pallas.ops.tpu.megablox.ops import gmm

            # the kernel leaves the rows it never visits unwritten
            tiling = (TILE_ROWS if m % TILE_ROWS == 0 else m, _tile(k), _tile(n))
            out = gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, tiling)
        else:
            out = jax.lax.ragged_dot(
                lhs, rhs, group_sizes.astype(jnp.int32), preferred_element_type=jnp.float32
            ).astype(lhs.dtype)
    return jnp.where(live, out, 0)
