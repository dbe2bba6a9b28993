"""The colour stage of the v2 augmentation recipe, RandomApply(ColorJitter)
then RandomGrayscale, as one Pallas TPU kernel, and the colour formulas it
shares with the batched jnp path (`data/augment.py::color_jitter`).

XLA runs the batched path as passes over the whole batch in HBM: a blend
slot is a mean pass and a blend pass over the three channel planes, and
contrast's mean keeps one slot from fusing into the next, so a view takes
about thirteen passes. Here the grid steps over the images, one image's
three planes sit in VMEM (224 x 224 float32: 688 KB with the lanes padded
to 256), and the image's own ops run there in its drawn order: the planes
are read from HBM once and written once.

Each image's draws arrive in scalar memory: its four ops in drawn order
(0 brightness, 1 contrast, 2 saturation, 3 hue), their factors (hue's is
its delta), the RandomApply flag and the grayscale flag. An op is a scalar
branch around one sweep over the image in VMEM, in place in the output
block, so an image runs its own three blends and the hue round trip, not
six blend slots of which three are the identity; contrast sums the luma
of the image as it stands before its blend. A sweep goes over strips of
`ROWS` rows: a strip is eight vregs a plane, enough independent work
to hide most of the latency of the HSV round trip's dependency chain
without unrolling the loop over strips, which multiplies compile time.

Over a mesh of more than one device the kernel runs under `shard_map`,
each device on its own images: a Mosaic call is not partitioned by XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from moco_tpu.parallel.mesh import DATA_AXIS

ROWS = 32  # rows a strip: four sublane tiles
MIN_WIDTH = 128  # planes narrower than one lane tile stay on the jnp path
# rows x lane-padded columns: an image's planes in and out, double-buffered,
# take 3 MiB of VMEM at 256 x 256
MAX_PIXELS = 256 * 256


def fits(height: int, width: int) -> bool:
    """Whether images of this size take the kernel: the planes fill a lane
    tile, the rows divide into strips, and an image fits VMEM."""
    return (
        width >= MIN_WIDTH
        and height % ROWS == 0
        and height * pl.cdiv(width, 128) * 128 <= MAX_PIXELS
    )


def blend(a: jax.Array, b: jax.Array, factor: jax.Array) -> jax.Array:
    """torchvision _blend: factor*a + (1-factor)*b, clipped to [0,1]."""
    return jnp.clip(factor * a + (1.0 - factor) * b, 0.0, 1.0)


def luma(r: jax.Array, g: jax.Array, b: jax.Array) -> jax.Array:
    """ITU-R 601 luma, as PIL convert('L') uses."""
    return 0.299 * r + 0.587 * g + 0.114 * b


def remainder_in_range(x, m):
    """`x % m` for x in [-m, 2m): the same numbers to the bit, without the
    division a remainder lowers to on the vector unit (in the kernel, the
    two remainders and the sextant's were a quarter of the HSV round trip's
    bundles)."""
    return jnp.where(x < 0, x + m, jnp.where(x >= m, x - m, x))


def hue_planes(r, g, b, d, mod=jnp.remainder):
    """Hue shift by `d` on channel planes, `d` broadcastable to one and in
    [-0.5, 0.5]: a float HSV round trip, torchvision's `adjust_hue` model.
    `mod(x, m)` is x % m; its operands lie in [-m, 2m) (h / 6 in
    [-1/6, 5/6], h + d in [-1/2, 3/2), the sextant in [0, 6]), so the
    kernel passes `remainder_in_range` (on the batched path XLA counts
    `jnp.remainder` as the cheaper of the two: 202 flops an element of
    `color_jitter` against 261)."""
    maxc = jnp.maximum(jnp.maximum(r, g), b)
    minc = jnp.minimum(jnp.minimum(r, g), b)
    v = maxc
    c = maxc - minc
    s = jnp.where(maxc > 0, c / jnp.where(maxc > 0, maxc, 1.0), 0.0)
    safe_c = jnp.where(c > 0, c, 1.0)
    rc = (maxc - r) / safe_c
    gc = (maxc - g) / safe_c
    bc = (maxc - b) / safe_c
    h = jnp.where(
        r == maxc, bc - gc, jnp.where(g == maxc, 2.0 + rc - bc, 4.0 + gc - rc)
    )
    h = jnp.where(c > 0, mod(h / 6.0, 1.0), 0.0)
    h = mod(h + d, 1.0)

    # HSV -> RGB (colorsys sextant form)
    h6 = h * 6.0
    i = jnp.floor(h6)
    f = h6 - i
    p = v * (1.0 - s)
    q = v * (1.0 - s * f)
    t = v * (1.0 - s * (1.0 - f))
    i = mod(i.astype(jnp.int32), 6)

    def by_sextant(*choices):
        # nested where: `jnp.select` lowers to a concatenate and an argmax over
        # six stacked (B,H,W) conditions, three passes of their own on the chip
        out = choices[5]
        for n in (4, 3, 2, 1, 0):
            out = jnp.where(i == n, choices[n], out)
        return jnp.clip(out, 0.0, 1.0)

    return by_sextant(v, q, p, p, t, v), by_sextant(t, v, v, q, p, p), by_sextant(p, p, t, v, v, q)


def _kernel(kind_ref, factor_ref, flag_ref, x_ref, o_ref, *, hue: bool):
    n = pl.program_id(0)
    keep = flag_ref[2 * n] != 0
    _, height, width = x_ref.shape

    def sweep(src, fn):
        """o_ref = fn(src), strip by strip."""

        def strip(s, carry):
            rows = pl.ds(pl.multiple_of(s * ROWS, ROWS), ROWS)
            out = fn(tuple(src[c, rows, :] for c in range(3)))
            for c in range(3):
                o_ref[c, rows, :] = out[c]
            return carry

        lax.fori_loop(0, height // ROWS, strip, 0)

    def mean_luma():
        def strip(s, total):
            rows = pl.ds(pl.multiple_of(s * ROWS, ROWS), ROWS)
            return total + luma(*(o_ref[c, rows, :] for c in range(3)))

        total = lax.fori_loop(0, height // ROWS, strip, jnp.zeros((ROWS, width), jnp.float32))
        return jnp.sum(total, keepdims=True) / (height * width)

    # the image into the output block, then one sweep an op in place, each
    # under its own scalar branch (with hue off, hue has none)
    sweep(x_ref, lambda c: c)
    for p in range(4):
        kind, f = kind_ref[4 * n + p], factor_ref[4 * n + p]

        @pl.when(keep & (kind == 0))
        def _():
            sweep(o_ref, lambda c: tuple(blend(x, 0.0, f) for x in c))

        @pl.when(keep & (kind == 1))
        def _():
            mean = mean_luma()
            sweep(o_ref, lambda c: tuple(blend(x, mean, f) for x in c))

        @pl.when(keep & (kind == 2))
        def _():
            sweep(o_ref, lambda c: tuple(blend(x, luma(*c), f) for x in c))

        if hue:

            @pl.when(keep & (kind == 3))
            def _():
                sweep(o_ref, lambda c: hue_planes(*c, f, remainder_in_range))

    @pl.when(flag_ref[2 * n + 1] != 0)
    def _():
        sweep(o_ref, lambda c: (luma(*c),) * 3)


def colour_jitter(
    planes: jax.Array,  # (3, B, H, W) float32 in [0, 1]: the r, g and b planes
    kinds: jax.Array,  # (B, 4) int32: each image's ops in drawn order
    factors: jax.Array,  # (B, 4) float32: their factors, hue's delta at hue's position
    keep: jax.Array,  # (B,) bool: RandomApply kept the jitter
    gray: jax.Array,  # (B,) bool: RandomGrayscale took the image
    *,
    hue: bool,  # False: the hue range is 0 and hue is the identity
    mesh: Mesh | None = None,  # the images are sharded over its data axis
    interpret: bool = False,
) -> jax.Array:
    """The (3, B, H, W) planes after each image's jitter (where kept) and
    then its grayscale (where drawn); an image with neither comes out bit
    for bit. The planes lead, so that NHWC images whose layout puts the
    channel outermost (what XLA gives the crop's resample here) reach the
    kernel and leave it through bitcasts."""
    _, _, height, width = planes.shape
    if not fits(height, width):
        raise ValueError(f"{height} x {width} planes do not take the colour kernel")

    def call(planes, kinds, factors, keep, gray):
        block = pl.BlockSpec((3, None, height, width), lambda i, *_: (0, i, 0, 0))
        flags = jnp.stack([keep, gray], axis=1).astype(jnp.int32).reshape(-1)
        return pl.pallas_call(
            functools.partial(_kernel, hue=hue),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(planes.shape[1],), in_specs=[block], out_specs=block
            ),
            out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.float32),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
            interpret=interpret,
            name="colour_jitter",
        )(
            kinds.reshape(-1).astype(jnp.int32),
            factors.reshape(-1).astype(jnp.float32),
            flags,
            planes.astype(jnp.float32),
        )

    if mesh is not None and mesh.size > 1:
        rows = P(DATA_AXIS)
        call = shard_map(
            call, mesh=mesh, in_specs=(P(None, DATA_AXIS), rows, rows, rows, rows),
            out_specs=P(None, DATA_AXIS), check_vma=False,
        )
    return call(planes, kinds, factors, keep, gray)
