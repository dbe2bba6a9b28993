"""On-device, batched, jittable augmentations — the TPU-native redesign of
the reference's PIL/torchvision pipeline (`moco/loader.py` +
`main_moco.py:~L225-255`).

The reference decodes and augments per-image in 32 DataLoader worker
processes (PIL C code). On TPU the elementwise augmentation work
(jitter/grayscale/blur/flip/normalize) fuses into one XLA program and runs
on-device on the whole batch, leaving the host only JPEG decode + crop.
Where the images fill a lane tile (224 px), the v2 recipe's colour stage,
jitter then grayscale, is one Pallas kernel in that program
(`colour_stage`, `ops/colour_jitter.py`) with the batched ops' draws.
Every op takes images in [0, 1] float, NHWC, and a per-call PRNG key; all
randomness is per-example (`jax.vmap` over split keys) except where noted.

Recipe parity (SURVEY.md §2.2 row 9):
- v2 / `--aug-plus`: RandomResizedCrop(224, scale=(0.2,1)),
  RandomApply(ColorJitter(0.4,0.4,0.4,0.1), p=0.8), RandomGrayscale(0.2),
  RandomApply(GaussianBlur(sigma∈[0.1,2]), p=0.5), HorizontalFlip(0.5),
  Normalize(ImageNet mean/std).
- v1: RandomResizedCrop, RandomGrayscale(0.2), ColorJitter(0.4,0.4,0.4,0.4)
  always applied, HorizontalFlip(0.5), Normalize.

Parity with PIL/torchvision (quantified in tests/test_aug_parity.py):
- RandomResizedCrop reproduces torchvision's 10-attempt rejection sampler
  exactly (integer-rounded crop boxes, randint top-left, center-crop
  fallback with ratio clamping) — vectorized over a fixed attempt axis
  with first-valid selection instead of a Python loop.
- ColorJitter draws the sub-op order per *image* (argsort-of-uniforms
  permutation), matching torchvision's per-call randperm(4).
- GaussianBlur uses a truncated separable Gaussian (fixed 23-tap window,
  the SimCLR convention of ~10% of image size) instead of PIL's
  sequential-box-blur approximation; measured deviation is bounded in the
  parity tests.
- Hue jitter is a float HSV round-trip (torchvision's tensor-backend
  model); it matches PIL's uint8 HSV shift to within quantization
  (~0.003 mean abs at ±0.1, bounded in the parity tests).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from moco_tpu.ops.colour_jitter import blend, hue_planes, luma
from moco_tpu.ops.colour_jitter import colour_jitter as colour_kernel
from moco_tpu.ops.colour_jitter import fits as colour_kernel_fits
from moco_tpu.utils.platform import pallas_interpret

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)

# ---------------------------------------------------------------- crops


def random_resized_crop_params(
    rng: jax.Array,
    batch: int,
    h: int,
    w: int,
    scale: tuple[float, float] = (0.2, 1.0),
    ratio: tuple[float, float] = (3.0 / 4.0, 4.0 / 3.0),
    attempts: int = 10,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-image crop boxes (y0, x0, ch, cw), each (batch,) float32 holding
    integer values — torchvision RandomResizedCrop.get_params semantics.

    torchvision loops up to 10 attempts: draw area∈scale·A and log-uniform
    aspect∈ratio, round to integer (cw, ch), accept iff the box fits, then
    draw an integer top-left uniformly; after 10 rejections it falls back
    to a ratio-clamped center crop. Vectorized here: all `attempts` draws
    happen up front along a second axis and the first valid one is
    selected per image (independent draws, so picking the first valid
    column is distributionally identical to the sequential loop).
    """
    area = float(h * w)
    k_area, k_ratio, k_y, k_x = jax.random.split(rng, 4)
    shape = (batch, attempts)
    target_area = jax.random.uniform(k_area, shape, minval=scale[0], maxval=scale[1]) * area
    aspect = jnp.exp(
        jax.random.uniform(k_ratio, shape, minval=jnp.log(ratio[0]), maxval=jnp.log(ratio[1]))
    )
    cw_all = jnp.round(jnp.sqrt(target_area * aspect))
    ch_all = jnp.round(jnp.sqrt(target_area / aspect))
    valid = (cw_all > 0) & (cw_all <= w) & (ch_all > 0) & (ch_all <= h)
    first = jnp.argmax(valid, axis=1)  # index of first valid attempt (0 if none)
    any_valid = jnp.any(valid, axis=1)

    def pick(arr):
        return jnp.take_along_axis(arr, first[:, None], axis=1)[:, 0]

    cw, ch = pick(cw_all), pick(ch_all)
    # randint(0, H-h+1) as floor(u * n) with u ∈ [0,1); drawn per attempt so
    # the accepted attempt's top-left is independent of the rejections.
    y0 = jnp.floor(pick(jax.random.uniform(k_y, shape)) * (h - ch + 1.0))
    x0 = jnp.floor(pick(jax.random.uniform(k_x, shape)) * (w - cw + 1.0))

    # Fallback: center crop clamped to the ratio range (static geometry).
    in_ratio = w / h
    if in_ratio < ratio[0]:
        fw, fh = w, round(w / ratio[0])
    elif in_ratio > ratio[1]:
        fh, fw = h, round(h * ratio[1])
    else:
        fw, fh = w, h
    fy, fx = (h - fh) // 2, (w - fw) // 2
    ch = jnp.where(any_valid, ch, float(fh))
    cw = jnp.where(any_valid, cw, float(fw))
    y0 = jnp.where(any_valid, y0, float(fy))
    x0 = jnp.where(any_valid, x0, float(fx))
    return y0, x0, ch, cw


def random_resized_crop(
    rng: jax.Array,
    images: jax.Array,  # (B, H, W, C) float in [0,1]
    out_size: int,
    scale: tuple[float, float] = (0.2, 1.0),
    ratio: tuple[float, float] = (3.0 / 4.0, 4.0 / 3.0),
) -> jax.Array:
    """torchvision RandomResizedCrop: 10-attempt rejection-sampled box
    (`random_resized_crop_params`), crop, bilinear-resize to
    (out_size, out_size)."""
    b, h, w, _ = images.shape
    y0, x0, ch, cw = random_resized_crop_params(rng, b, h, w, scale, ratio)

    def crop_one(img, y0_, x0_, ch_, cw_):
        # scale_and_translate maps output pixel p to input p/scale - translate/scale;
        # we want out [0, out_size) to cover input [x0, x0+cw).
        sy = out_size / ch_
        sx = out_size / cw_
        return jax.image.scale_and_translate(
            img,
            (out_size, out_size, img.shape[-1]),
            (0, 1),
            jnp.array([sy, sx]),
            jnp.array([-y0_ * sy, -x0_ * sx]),
            method="linear",
        )

    return jax.vmap(crop_one)(images, y0, x0, ch, cw)


def center_crop(images: jax.Array, out_size: int, resize_to: int = 256) -> jax.Array:
    """Eval transform: Resize(resize_to) + CenterCrop(out_size)
    (`main_lincls.py` val pipeline)."""
    b, h, w, c = images.shape
    short = min(h, w)
    nh, nw = int(round(h * resize_to / short)), int(round(w * resize_to / short))
    images = jax.image.resize(images, (b, nh, nw, c), method="linear")
    y0, x0 = (nh - out_size) // 2, (nw - out_size) // 2
    return images[:, y0 : y0 + out_size, x0 : x0 + out_size, :]


# ------------------------------------------------------------ color ops


def _planes(img: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    return img[..., 0], img[..., 1], img[..., 2]


def _rgb_to_gray(img: jax.Array) -> jax.Array:
    return luma(*_planes(img))[..., None]


def adjust_brightness(img, factor):
    return blend(img, jnp.zeros_like(img), factor)


def adjust_contrast(img, factor):
    mean = jnp.mean(_rgb_to_gray(img), axis=(-3, -2, -1), keepdims=True)
    return blend(img, mean, factor)


def adjust_saturation(img, factor):
    return blend(img, _rgb_to_gray(img), factor)


def adjust_hue(img, delta):
    """Hue shift by delta (fraction of the color wheel, torch range
    [-0.5, 0.5]) via a float HSV round-trip — the same model torchvision
    uses, preserving S and V exactly. (A YIQ chroma rotation was tried
    first: it preserves luma instead, and the PIL parity test measured
    ~0.17 mean abs deviation on saturated colors — HSV is the parity
    answer.) Branch-free piecewise conversion, vectorized over the batch.
    """
    # delta arrives (B,1,1,1); drop the channel dim so it broadcasts
    # against the (B,H,W) planes.
    d = jnp.reshape(delta, delta.shape[:-1]) if delta.ndim == img.ndim else delta
    return jnp.stack(hue_planes(*_planes(img), d), axis=-1)


def _blend_slot(planes, factor, alpha, beta):
    """One slot of `color_jitter` on (B,H,W) channel planes:
    `blend(c, alpha*luma + beta*mean(luma), factor)` for each channel c,
    with per-image (B,1,1) parameters. (alpha, beta) = (0, 0) is
    brightness, (0, 1) contrast, (1, 0) saturation; with factor 1 as well
    it returns the planes (in [0,1]) bit for bit."""
    gray = luma(*planes)
    base = alpha * gray + beta * jnp.mean(gray, axis=(-2, -1), keepdims=True)
    return tuple(blend(c, base, factor) for c in planes)


def _jitter_draws(rng, b, brightness, contrast, saturation, hue, apply_prob):
    """`color_jitter`'s per-image draws from its key: the (B, 4) order of
    the op indices (0 brightness, 1 contrast, 2 saturation, 3 hue), the
    four (B, 1, 1, 1) factors in that index order (hue's is its delta),
    and the (B, 1, 1, 1) RandomApply flag (all kept at `apply_prob` 1)."""
    k_order, k_apply, kb, kc, ks, kh = jax.random.split(rng, 6)
    fb = jax.random.uniform(kb, (b, 1, 1, 1), minval=max(0.0, 1 - brightness), maxval=1 + brightness)
    fc = jax.random.uniform(kc, (b, 1, 1, 1), minval=max(0.0, 1 - contrast), maxval=1 + contrast)
    fs = jax.random.uniform(ks, (b, 1, 1, 1), minval=max(0.0, 1 - saturation), maxval=1 + saturation)
    fh = jax.random.uniform(kh, (b, 1, 1, 1), minval=-hue, maxval=hue)
    # independent per-image permutations, as torchvision's randperm(4) per call
    order = jnp.argsort(jax.random.uniform(k_order, (b, 4)), axis=1)
    if apply_prob < 1.0:
        keep = jax.random.bernoulli(k_apply, apply_prob, (b, 1, 1, 1))
    else:
        keep = jnp.ones((b, 1, 1, 1), bool)
    return order, (fb, fc, fs, fh), keep


def color_jitter(
    rng: jax.Array,
    images: jax.Array,
    brightness: float = 0.4,
    contrast: float = 0.4,
    saturation: float = 0.4,
    hue: float = 0.0,
    apply_prob: float = 1.0,
) -> jax.Array:
    """torchvision ColorJitter(b, c, s, h) wrapped in RandomApply(p).

    Factors ~ U[max(0,1-x), 1+x] per image; hue ~ U[-h, h]. Sub-op order
    is a fresh randperm(4) per *image* (torchvision draws per call, i.e.
    per image), realized as argsort of per-image uniforms.

    Each image's order is evaluated once, fully batched, on the three
    channel planes: brightness, contrast and saturation are one blend with
    per-image parameters (`_blend_slot`), and the HSV round trip
    (`hue_planes`) runs once on the batch between three leading and three
    trailing blend slots. An image whose order has hue at position p takes
    its p earlier ops in the leading slots and its 3 - p later ones in the
    trailing slots; its other slots are the identity. (Computing all four candidates in each of four slots and
    selecting, as this did before, cost 16 passes with four round trips;
    the ledger put the augmentation program at a fifth of `train_r50_v2`'s
    device time.) `hue == 0` is static: no round trip, three slots.
    """
    b = images.shape[0]
    order, (fb, fc, fs, fh), keep = _jitter_draws(rng, b, brightness, contrast, saturation, hue, apply_prob)
    hue_pos = jnp.argmax(order == 3, axis=1)[:, None]
    # (B, 3): each image's three blends in its drawn order (its order with
    # hue taken out), their factors, and whether blend j comes before hue.
    j = jnp.arange(3)
    blends = jnp.take_along_axis(order, j + (j >= hue_pos), axis=1)
    factor = jnp.take_along_axis(jnp.concatenate([fb, fc, fs], axis=1)[:, :, 0, 0], blends, axis=1)
    before_hue = j < hue_pos

    def blend_slots(planes, active):
        # blend j where `active[:, j]`, the identity (1, 0, 0) elsewhere
        f = jnp.where(active, factor, 1.0)
        alpha = (active & (blends == 2)).astype(images.dtype)
        beta = (active & (blends == 1)).astype(images.dtype)
        for slot in range(3):
            planes = _blend_slot(planes, *(p[:, slot, None, None] for p in (f, alpha, beta)))
        return planes

    # on channel planes from here to the stack: a slot is then elementwise
    # on equal shapes, which XLA fuses into one pass (measured, PERF.md)
    planes = _planes(images)
    if hue > 0:
        planes = blend_slots(planes, before_hue)
        planes = hue_planes(*planes, fh[..., 0])
        planes = blend_slots(planes, ~before_hue)
    else:
        planes = blend_slots(planes, jnp.ones_like(before_hue))
    out = jnp.stack(planes, axis=-1)
    if apply_prob < 1.0:
        out = jnp.where(keep, out, images)
    return out


def random_grayscale(rng: jax.Array, images: jax.Array, prob: float = 0.2) -> jax.Array:
    b = images.shape[0]
    gray = jnp.broadcast_to(_rgb_to_gray(images), images.shape)
    take = jax.random.bernoulli(rng, prob, (b, 1, 1, 1))
    return jnp.where(take, gray, images)


def colour_stage(
    k_jit: jax.Array,
    k_gray: jax.Array,
    images: jax.Array,
    jitter: tuple[float, float, float, float],
    jitter_prob: float,
    grayscale_prob: float,
    mesh: Mesh | None = None,
) -> jax.Array:
    """`color_jitter(k_jit, ...)` then `random_grayscale(k_gray, ...)`, the
    v2 recipe's colour stage, with the same draws. Images whose planes fill
    a lane tile take one kernel that holds each image in VMEM
    (`ops/colour_jitter.py`), run on each device's own images where `mesh`
    shards them; smaller ones compose the two batched ops."""
    b, h, w, _ = images.shape
    if not colour_kernel_fits(h, w):
        x = color_jitter(k_jit, images, *jitter, apply_prob=jitter_prob)
        return random_grayscale(k_gray, x, grayscale_prob)
    order, factors, keep = _jitter_draws(k_jit, b, *jitter, jitter_prob)
    factor = jnp.take_along_axis(jnp.concatenate(factors, axis=1)[:, :, 0, 0], order, axis=1)
    gray = jax.random.bernoulli(k_gray, grayscale_prob, (b, 1, 1, 1))
    planes = colour_kernel(
        jnp.moveaxis(images, -1, 0), order, factor, keep.reshape(b), gray.reshape(b),
        hue=jitter[3] > 0, mesh=mesh, interpret=pallas_interpret(),
    )
    return jnp.moveaxis(planes, 0, -1)


# ---------------------------------------------------------------- blur


def _gaussian_kernels(sigma: jax.Array, taps: int) -> jax.Array:
    """(B, taps) normalized 1-D Gaussian kernels for per-example sigma."""
    x = jnp.arange(taps, dtype=jnp.float32) - (taps - 1) / 2.0
    k = jnp.exp(-0.5 * (x[None, :] / sigma[:, None]) ** 2)
    return k / jnp.sum(k, axis=1, keepdims=True)


def gaussian_blur(
    rng: jax.Array,
    images: jax.Array,
    sigma_range: tuple[float, float] = (0.1, 2.0),
    apply_prob: float = 0.5,
    taps: int = 23,
) -> jax.Array:
    """RandomApply(GaussianBlur(sigma∈U[range]), p) — SimCLR/MoCo-v2 blur
    (`moco/loader.py:~L23-35`), as a separable depthwise conv."""
    b, h, w, c = images.shape
    k_sigma, k_apply = jax.random.split(rng)
    sigma = jax.random.uniform(k_sigma, (b,), minval=sigma_range[0], maxval=sigma_range[1])
    kernels = _gaussian_kernels(sigma, taps)  # (B, taps)

    def blur_one(img, k1d):  # img (H, W, C)
        pad = taps // 2
        # Edge-replicate padding, as PIL's blur extends border pixels
        # (zero-padding would darken edges).
        x = jnp.pad(img, ((pad, pad), (pad, pad), (0, 0)), mode="edge")
        x = x.transpose(2, 0, 1)[:, None]  # (C, 1, H+2p, W+2p)
        kv = k1d.reshape(1, 1, taps, 1)
        kh = k1d.reshape(1, 1, 1, taps)
        x = lax.conv_general_dilated(x, kv, (1, 1), [(0, 0), (0, 0)])
        x = lax.conv_general_dilated(x, kh, (1, 1), [(0, 0), (0, 0)])
        return x[:, 0].transpose(1, 2, 0)

    blurred = jax.vmap(blur_one)(images, kernels)
    keep = jax.random.bernoulli(k_apply, apply_prob, (b, 1, 1, 1))
    return jnp.where(keep, blurred, images)


# ------------------------------------------------------------- flip/norm


def random_horizontal_flip(rng: jax.Array, images: jax.Array, prob: float = 0.5) -> jax.Array:
    b = images.shape[0]
    flip = jax.random.bernoulli(rng, prob, (b, 1, 1, 1))
    return jnp.where(flip, images[:, :, ::-1, :], images)


def normalize(images: jax.Array, mean=IMAGENET_MEAN, std=IMAGENET_STD) -> jax.Array:
    mean = jnp.asarray(mean, images.dtype)
    std = jnp.asarray(std, images.dtype)
    return (images - mean) / std


# -------------------------------------------------------------- recipes


class AugRecipe(NamedTuple):
    """A composed augmentation: fn(rng, images_in_01) -> normalized views."""

    name: str
    crop: bool  # random-resized-crop from the (larger) input
    jitter: tuple[float, float, float, float]
    jitter_prob: float
    grayscale_prob: float
    blur_prob: float
    crop_scale: tuple[float, float] = (0.2, 1.0)
    mean: tuple = IMAGENET_MEAN
    std: tuple = IMAGENET_STD


V1_RECIPE = AugRecipe("v1", True, (0.4, 0.4, 0.4, 0.4), 1.0, 0.2, 0.0)
V2_RECIPE = AugRecipe("v2", True, (0.4, 0.4, 0.4, 0.1), 0.8, 0.2, 0.5)
# Linear-probe training transform (`main_lincls.py` train pipeline):
# RandomResizedCrop (default scale 0.08-1.0) + flip + normalize only.
PROBE_RECIPE = AugRecipe("probe", True, (0.0, 0.0, 0.0, 0.0), 0.0, 0.0, 0.0, (0.08, 1.0))
# Geometric-only two-crop recipe (RRC + flip + normalize, pretrain crop
# scale): the BN-leak positive control's setting, where photometric
# jitter would swamp the weak global tint that carries BOTH the honest
# and the cheat channel (LeakControlSyntheticDataset).
CROPS_ONLY_RECIPE = AugRecipe("probe", True, (0.0, 0.0, 0.0, 0.0), 0.0, 0.0, 0.0, (0.2, 1.0))


def apply_recipe(
    recipe: AugRecipe, rng: jax.Array, images: jax.Array, out_size: int,
    mesh: Mesh | None = None,
) -> jax.Array:
    """One view. `images` float [0,1] NHWC, any (H, W) ≥ out_size, their
    rows sharded over `mesh`'s data axis where one is given."""
    k_crop, k_jit, k_gray, k_blur, k_flip = jax.random.split(rng, 5)
    x = images
    if recipe.crop:
        with jax.named_scope("moco.augment.crop"):
            x = random_resized_crop(k_crop, x, out_size, scale=recipe.crop_scale)
    if recipe.name == "v1":
        # v1 order: crop, grayscale, jitter, flip (main_moco.py:~L245-255)
        with jax.named_scope("moco.augment.colour"):
            x = random_grayscale(k_gray, x, recipe.grayscale_prob)
            x = color_jitter(k_jit, x, *recipe.jitter, apply_prob=recipe.jitter_prob)
    elif recipe.name == "probe":
        pass  # crop + flip + normalize only
    else:
        # v2 order: crop, jitter(p=0.8), grayscale, blur, flip (~L228-240)
        with jax.named_scope("moco.augment.colour"):
            x = colour_stage(
                k_jit, k_gray, x, recipe.jitter, recipe.jitter_prob, recipe.grayscale_prob, mesh
            )
        if recipe.blur_prob > 0:
            with jax.named_scope("moco.augment.blur"):
                x = gaussian_blur(k_blur, x, apply_prob=recipe.blur_prob)
    with jax.named_scope("moco.augment.flip_normalize"):
        x = random_horizontal_flip(k_flip, x)
        return normalize(x, recipe.mean, recipe.std)


def two_crop_augment(
    recipe: AugRecipe, rng: jax.Array, images: jax.Array, out_size: int,
    mesh: Mesh | None = None,
) -> dict[str, jax.Array]:
    """TwoCropsTransform (`moco/loader.py:~L10-20`): the same recipe applied
    twice with independent randomness → query and key views."""
    k_q, k_k = jax.random.split(rng)
    return {
        "im_q": apply_recipe(recipe, k_q, images, out_size, mesh),
        "im_k": apply_recipe(recipe, k_k, images, out_size, mesh),
    }


def get_recipe(aug_plus: bool, image_size: int, crops_only: bool = False) -> AugRecipe:
    """Recipe lookup; CIFAR-sized inputs skip blur (23-tap blur on 32px is
    degenerate) and use CIFAR normalization stats."""
    base = CROPS_ONLY_RECIPE if crops_only else (V2_RECIPE if aug_plus else V1_RECIPE)
    if image_size <= 64:
        return base._replace(
            blur_prob=0.0,
            mean=(0.4914, 0.4822, 0.4465),
            std=(0.2470, 0.2435, 0.2616),
        )
    return base
