"""The v2 augmentation recipe after its crop, RandomApply(ColorJitter),
RandomGrayscale, RandomApply(GaussianBlur), the horizontal flip and the
normalise, as one Pallas TPU kernel, and the colour formulas it shares with
the batched jnp path (`data/augment.py`).

XLA runs the batched path as passes over the whole batch in HBM: a blend
slot is a mean pass and a blend pass over the three channel planes, and
contrast's mean keeps one slot from fusing into the next, so the colour
ops take about thirteen passes a view, and the blur (two convolutions,
edge pads, the RandomApply select), the flip's reverse and the normalise
each read and write the batch again. Here the grid steps over the images,
one image's three planes sit in VMEM (224 x 224 float32: 688 KB with the
lanes padded to 256), and the image's own ops run there in its drawn
order: the planes are read from HBM once and written once.

Each image's draws arrive in scalar memory: its four ops in drawn order
(0 brightness, 1 contrast, 2 saturation, 3 hue), their factors (hue's is
its delta), the RandomApply flag, the grayscale flag, the blur and flip
flags, and the blur's 1 / sigma and the reciprocal of its taps' sum. An
op is a scalar branch around one sweep over the image in VMEM, in place
in the output block, so an image runs its own three blends and the hue
round trip, not six blend slots of which three are the identity; contrast
sums the luma of the image as it stands before its blend. A sweep goes
over strips of `ROWS` rows: a strip is eight vregs a plane, enough
independent work to hide most of the latency of the HSV round trip's
dependency chain without unrolling the loop over strips, which multiplies
compile time.

The blur is two products on the matrix unit at `Precision.HIGHEST` (no
operand rounded below float32), `down @ plane @ across`, each band built
from the image's sigma (`_blur_band`) with the replicated border folded
into its edge entries; the Gaussian and the clamp are symmetric, so the
flip of a blurred image is the horizontal band mirrored, and an unblurred
flipped image takes the mirror alone. The normalise rides the last write.
Whether the blur is compiled in is static, as hue is (`Finish.sigma`).

Over a mesh of more than one device the kernel runs under `shard_map`,
each device on its own images: a Mosaic call is not partitioned by XLA.
"""

from __future__ import annotations

import functools
import struct
from typing import NamedTuple

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
TAPS = 23  # the blur's window, as the batched blur's (`data/augment.py::gaussian_blur`)


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


class Finish(NamedTuple):
    """What the v2 recipe does after its grayscale, for the kernel to run
    before it writes an image: RandomApply(GaussianBlur) on `sigma` and
    `blurred` (both None where the recipe has no blur), the horizontal flip
    and the per-channel normalise."""

    sigma: jax.Array | None  # (B,) float32: each image's blur sigma
    blurred: jax.Array | None  # (B,) bool: RandomApply kept the blur
    flipped: jax.Array  # (B,) bool
    mean: tuple[float, float, float]
    std: tuple[float, float, float]


def _blur_band(
    n: int, out_axis: int, inv_sigma, inv_z, flip=0,
    out_span: tuple[int, int] | None = None, in_span: tuple[int, int] | None = None,
) -> jax.Array:
    """The weights of the separable blur along an axis of n pixels, with
    the border replicated: entry (a, b) weighs the input pixel at the index
    that is not `out_axis` into the output pixel at the one that is, so the
    output is `band @ x` for `out_axis` 0 and `x @ band` for 1. The window
    takes `TAPS` taps exp(-(d * inv_sigma)^2 / 2) * inv_z; what it loses
    past the border goes to the border pixel (edge padding, as the batched
    blur pads). `flip` 1 mirrors the output axis, so that the flip of a
    blurred image rides its horizontal pass. `out_span` and `in_span`
    (first index, count) cut the band to those outputs and inputs; the
    inputs hold every output's window."""
    out0, outs = out_span or (0, n)
    in0, ins = in_span or (0, n)
    shape = (outs, ins) if out_axis == 0 else (ins, outs)
    src = in0 + lax.broadcasted_iota(jnp.int32, shape, 1 - out_axis)
    dst = out0 + lax.broadcasted_iota(jnp.int32, shape, out_axis)
    centre = dst + flip * (n - 1 - 2 * dst)
    d = src - centre
    x = d.astype(jnp.float32) * inv_sigma
    w = jnp.where(jnp.abs(d) <= TAPS // 2, jnp.exp(-0.5 * x * x) * inv_z, 0.0)
    lost = 1.0 - jnp.sum(w, axis=1 - out_axis, keepdims=True)
    edge = jnp.where(centre < n // 2, 0, n - 1)
    return w + jnp.where((src == edge) & (jnp.abs(centre - edge) < TAPS // 2), lost, 0.0)


def _halves(n: int) -> tuple[tuple[int, int, int], ...]:
    """(first output, first input, inputs) of each half of n rows: the
    rows of input that hold the half's windows, whole sublane tiles, so
    that a 224-row plane's vertical pass contracts 128 rows a half, one
    pass of the matrix unit where the whole plane takes two."""
    half, reach = n // 2, TAPS // 2
    spans = []
    for a in (0, half):
        k0 = max(0, (a - reach) // 8 * 8)
        k1 = min(n, -(-(a + half + reach) // 8) * 8)
        spans.append((a, k0, k1 - k0))
    return tuple(spans)


def _float32(x: float) -> float:
    return struct.unpack("f", struct.pack("f", x))[0]


def _reciprocals(values: tuple[float, ...]) -> tuple[float, ...]:
    """Each value's float32 reciprocal (a float64 quotient of float32
    operands rounds to the float32 one): dividing by a constant is
    multiplying by it, as XLA compiles the batched `normalize`."""
    return tuple(_float32(1.0 / _float32(v)) for v in values)


def _dot(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _mirrored(plane, mirror):
    """`plane @ mirror` for a (W, W) bfloat16 matrix of zeros and ones,
    exactly, in three bfloat16 passes: the plane is the sum of three
    bfloat16 parts, each product picks one element, and the parts add back
    to the plane in float32 without rounding."""
    hi = plane.astype(jnp.bfloat16)
    rest = plane - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    pick = lambda part: jnp.dot(part, mirror, preferred_element_type=jnp.float32)
    return (pick(hi) + pick(mid)) + pick(lo)


def _kernel(kind_ref, factor_ref, flag_ref, blur_ref, x_ref, o_ref, *, hue, blur, normalise):
    n = pl.program_id(0)
    keep = flag_ref[4 * n] != 0
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

    @pl.when(flag_ref[4 * n + 1] != 0)
    def _():
        sweep(o_ref, lambda c: (luma(*c),) * 3)

    mean, scale = normalise
    blurred = flag_ref[4 * n + 2] != 0 if blur else False
    flip = flag_ref[4 * n + 3]

    def normalised(c, plane):
        return (plane - mean[c]) * scale[c]

    if blur:

        @pl.when(blurred)
        def _():
            # the vertical pass by halves, then the horizontal one with the flip in it
            inv_sigma, inv_z = blur_ref[2 * n], blur_ref[2 * n + 1]
            halves = _halves(height)
            down = [_blur_band(height, 0, inv_sigma, inv_z, 0, (a, height // 2), (k0, k))
                    for a, k0, k in halves]
            across = _blur_band(width, 1, inv_sigma, inv_z, flip)
            # every plane's vertical pass reads the planes before any is written;
            # the horizontal one then takes the three planes as one operand
            vertical = jnp.concatenate(
                [_dot(band, o_ref[c, k0:k0 + k, :]) for c in range(3) for band, (_, k0, k) in zip(down, halves)]
            )
            out = _dot(vertical, across)
            for c in range(3):
                o_ref[c] = normalised(c, out[c * height:(c + 1) * height])

    @pl.when(jnp.logical_not(blurred) & (flip != 0))
    def _():
        src = lax.broadcasted_iota(jnp.int32, (width, width), 0)
        dst = lax.broadcasted_iota(jnp.int32, (width, width), 1)
        mirror = (src + dst == width - 1).astype(jnp.bfloat16)
        for c in range(3):
            o_ref[c] = normalised(c, _mirrored(o_ref[c], mirror))

    @pl.when(jnp.logical_not(blurred) & (flip == 0))
    def _():
        sweep(o_ref, lambda c: tuple((x - mean[i]) * scale[i] for i, x in enumerate(c)))


def colour_jitter(
    planes: jax.Array,  # (3, B, H, W) float32 in [0, 1]: the r, g and b planes
    kinds: jax.Array,  # (B, 4) int32: each image's ops in drawn order
    factors: jax.Array,  # (B, 4) float32: their factors, hue's delta at hue's position
    keep: jax.Array,  # (B,) bool: RandomApply kept the jitter
    gray: jax.Array,  # (B,) bool: RandomGrayscale took the image
    *,
    hue: bool,  # False: the hue range is 0 and hue is the identity
    finish: Finish,  # blur, flip and normalise after the grayscale
    mesh: Mesh | None = None,  # the images are sharded over its data axis
    interpret: bool = False,
) -> jax.Array:
    """The (3, B, H, W) planes after each image's jitter (where kept), its
    grayscale (where drawn), its blur (where drawn) and its flip (where
    drawn), normalised; an image with none of these comes out as its
    normalised input bit for bit. A mean of 0 and a std of 1 make the
    normalise the identity, bit for bit. The planes lead, so that NHWC
    images whose layout puts the channel outermost (what XLA gives the
    crop's resample here) reach the kernel and leave it through bitcasts."""
    _, b, height, width = planes.shape
    if not fits(height, width):
        raise ValueError(f"{height} x {width} planes do not take the colour kernel")
    blur = finish.sigma is not None
    normalise = (finish.mean, _reciprocals(finish.std))
    blurred = finish.blurred if blur else jnp.zeros((b,), bool)
    if blur:
        inv_sigma = 1.0 / finish.sigma.astype(jnp.float32)
        d = jnp.arange(-(TAPS // 2), TAPS // 2 + 1, dtype=jnp.float32)
        x = d[None, :] * inv_sigma[:, None]
        inv_z = 1.0 / jnp.sum(jnp.exp(-0.5 * x * x), axis=1)
        blur_params = jnp.stack([inv_sigma, inv_z], axis=1)
    else:
        blur_params = jnp.zeros((b, 2), jnp.float32)

    def call(planes, kinds, factors, flags, blur_params):
        block = pl.BlockSpec((3, None, height, width), lambda i, *_: (0, i, 0, 0))
        return pl.pallas_call(
            functools.partial(_kernel, hue=hue, blur=blur, normalise=normalise),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(planes.shape[1],), in_specs=[block], out_specs=block
            ),
            out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.float32),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
            interpret=interpret,
            name="colour_jitter",
        )(
            kinds.reshape(-1).astype(jnp.int32),
            factors.reshape(-1).astype(jnp.float32),
            flags.reshape(-1),
            blur_params.reshape(-1),
            planes.astype(jnp.float32),
        )

    flags = jnp.stack([keep, gray, blurred, finish.flipped], axis=1).astype(jnp.int32)
    if mesh is not None and mesh.size > 1:
        rows = P(DATA_AXIS)
        call = shard_map(
            call, mesh=mesh, in_specs=(P(None, DATA_AXIS), rows, rows, rows, rows),
            out_specs=P(None, DATA_AXIS), check_vma=False,
        )
    return call(planes, kinds, factors, flags, blur_params)
