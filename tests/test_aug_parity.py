"""Statistical parity of the on-device augmentations vs the reference's
PIL/torchvision semantics (`moco/loader.py`, `main_moco.py:~L225-255`).

torchvision itself is not installed in this image, so the oracles are
independent numpy/PIL re-statements of the documented torchvision
algorithms (RandomResizedCrop.get_params' 10-attempt rejection loop,
ImageEnhance blend formulas, uint8-HSV hue shift, ImageFilter blur).
Where our op is deliberately different (YIQ hue, true-Gaussian blur) the
test *bounds* the deviation instead of asserting equality, per VERDICT
round-1 item 5.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from PIL import Image, ImageEnhance, ImageFilter
from scipy.stats import ks_2samp

from moco_tpu.data.augment import (
    adjust_brightness,
    adjust_contrast,
    adjust_hue,
    adjust_saturation,
    color_jitter,
    gaussian_blur,
    random_resized_crop_params,
)

# ------------------------------------------------------------------ RRC


def tv_rrc_params_oracle(rng: np.random.Generator, h, w, scale, ratio, n):
    """Sequential-loop restatement of torchvision
    RandomResizedCrop.get_params (transforms.py, 10-attempt rejection +
    ratio-clamped center-crop fallback)."""
    area = h * w
    out = np.zeros((n, 4))
    for s in range(n):
        for _ in range(10):
            ta = rng.uniform(scale[0], scale[1]) * area
            ar = math.exp(rng.uniform(math.log(ratio[0]), math.log(ratio[1])))
            cw = round(math.sqrt(ta * ar))
            ch = round(math.sqrt(ta / ar))
            if 0 < cw <= w and 0 < ch <= h:
                y0 = rng.integers(0, h - ch + 1)
                x0 = rng.integers(0, w - cw + 1)
                break
        else:
            in_ratio = w / h
            if in_ratio < ratio[0]:
                cw, ch = w, round(w / ratio[0])
            elif in_ratio > ratio[1]:
                ch, cw = h, round(h * ratio[1])
            else:
                cw, ch = w, h
            y0, x0 = (h - ch) // 2, (w - cw) // 2
        out[s] = (y0, x0, ch, cw)
    return out


class TestRRCDistribution:
    N = 8000

    @pytest.mark.parametrize(
        "h,w",
        [(64, 64), (48, 120)],  # square + wide (wide exercises rejections/fallback)
        ids=["square", "wide"],
    )
    def test_box_distribution_matches_torchvision(self, h, w):
        scale, ratio = (0.2, 1.0), (3 / 4, 4 / 3)
        ours = np.stack(
            jax.jit(
                lambda k: random_resized_crop_params(k, self.N, h, w, scale, ratio)
            )(jax.random.PRNGKey(3)),
            axis=1,
        )
        oracle = tv_rrc_params_oracle(np.random.default_rng(7), h, w, scale, ratio, self.N)
        # integer-valued boxes
        np.testing.assert_array_equal(ours, np.round(ours))
        # per-marginal two-sample KS on (y0, x0, ch, cw)
        for col, name in enumerate(["y0", "x0", "ch", "cw"]):
            stat = ks_2samp(ours[:, col], oracle[:, col]).statistic
            assert stat < 0.035, f"{name}: KS={stat:.4f} (h={h}, w={w})"
        # joint sanity: crop areas agree in mean within 2%
        area_ours = (ours[:, 2] * ours[:, 3]).mean()
        area_orc = (oracle[:, 2] * oracle[:, 3]).mean()
        assert abs(area_ours - area_orc) / area_orc < 0.02

    def test_boxes_always_inside_image(self):
        h, w = 40, 100
        y0, x0, ch, cw = random_resized_crop_params(
            jax.random.PRNGKey(0), 4096, h, w, (0.2, 1.0), (3 / 4, 4 / 3)
        )
        assert float((y0 >= 0).all()) and float((x0 >= 0).all())
        assert float(((y0 + ch) <= h).all()) and float(((x0 + cw) <= w).all())
        assert float((ch > 0).all()) and float((cw > 0).all())

    def test_fallback_is_ratio_clamped_center_crop(self):
        # scale forces boxes taller than the image → all 10 attempts reject
        # (H=8, W=256: any aspect ≤ 4/3 at area ≥ 0.9·A needs ch ≥ 37 > 8)
        h, w = 8, 256
        y0, x0, ch, cw = random_resized_crop_params(
            jax.random.PRNGKey(1), 64, h, w, (0.9, 1.0), (3 / 4, 4 / 3)
        )
        # in_ratio = 32 > 4/3 → fallback ch = h, cw = round(h * 4/3)
        np.testing.assert_array_equal(np.asarray(ch), h)
        np.testing.assert_array_equal(np.asarray(cw), round(h * 4 / 3))
        np.testing.assert_array_equal(np.asarray(y0), 0)
        np.testing.assert_array_equal(np.asarray(x0), (w - round(h * 4 / 3)) // 2)


# --------------------------------------------------------------- jitter


def _jitter_all_candidates(rng, images, brightness, contrast, saturation, hue, apply_prob):
    """`color_jitter` as it was before it evaluated each image's order
    once: in each of four slots all four adjustments on the whole batch,
    one selected per image. Kept as the count test's yardstick."""
    b = images.shape[0]
    k_order, k_apply, kb, kc, ks, kh = jax.random.split(rng, 6)
    fb = jax.random.uniform(kb, (b, 1, 1, 1), minval=max(0.0, 1 - brightness), maxval=1 + brightness)
    fc = jax.random.uniform(kc, (b, 1, 1, 1), minval=max(0.0, 1 - contrast), maxval=1 + contrast)
    fs = jax.random.uniform(ks, (b, 1, 1, 1), minval=max(0.0, 1 - saturation), maxval=1 + saturation)
    fh = jax.random.uniform(kh, (b, 1, 1, 1), minval=-hue, maxval=hue)
    order = jnp.argsort(jax.random.uniform(k_order, (b, 4)), axis=1)
    out = images
    for slot in range(4):
        idx = order[:, slot][:, None, None, None]
        xb = adjust_brightness(out, fb)
        xc = adjust_contrast(out, fc)
        xs = adjust_saturation(out, fs)
        xh = adjust_hue(out, fh) if hue > 0 else out
        out = jnp.where(idx == 0, xb, jnp.where(idx == 1, xc, jnp.where(idx == 2, xs, xh)))
    if apply_prob < 1.0:
        keep = jax.random.bernoulli(k_apply, apply_prob, (b, 1, 1, 1))
        out = jnp.where(keep, out, images)
    return out


class TestJitterPerImageOrder:
    def test_matches_per_image_composition(self):
        """color_jitter == applying the four adjusts in each image's drawn
        order — recomputes the internal RNG splits and replays the exact
        composition per image."""
        rng = jax.random.PRNGKey(11)
        b, hue = 6, 0.1
        images = jax.random.uniform(jax.random.PRNGKey(5), (b, 12, 12, 3))
        out = color_jitter(rng, images, 0.4, 0.4, 0.4, hue, apply_prob=1.0)

        k_order, _, kb, kc, ks, kh = jax.random.split(rng, 6)
        fb = jax.random.uniform(kb, (b, 1, 1, 1), minval=0.6, maxval=1.4)
        fc = jax.random.uniform(kc, (b, 1, 1, 1), minval=0.6, maxval=1.4)
        fs = jax.random.uniform(ks, (b, 1, 1, 1), minval=0.6, maxval=1.4)
        fh = jax.random.uniform(kh, (b, 1, 1, 1), minval=-hue, maxval=hue)
        order = np.asarray(jnp.argsort(jax.random.uniform(k_order, (b, 4)), axis=1))

        adjusts = [adjust_brightness, adjust_contrast, adjust_saturation, adjust_hue]
        factors = [fb, fc, fs, fh]
        for i in range(b):
            x = images[i : i + 1]
            for op in order[i]:
                x = adjusts[op](x, factors[op][i : i + 1])
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(x[0]), atol=1e-5)

    @pytest.mark.parametrize(
        "seed,b,constants,apply_prob,orders",
        [
            (11, 6, (0.4, 0.4, 0.4, 0.0), 1.0, 2),
            (12, 8, (0.4, 0.4, 0.4, 0.4), 1.0, 2),
            (13, 16, (0.4, 0.4, 0.4, 0.1), 0.8, 2),
            (1, 64, (0.4, 0.4, 0.4, 0.1), 1.0, 24),
        ],
        ids=["hue0", "v1", "apply_prob_0.8", "all_24_orders"],
    )
    def test_replays_public_adjusts(self, seed, b, constants, apply_prob, orders):
        """The same replay over the recipes' constants: every position of
        hue and every arrangement of the three blends against the public
        `adjust_*`; a zero-range hue is skipped; an image RandomApply does
        not keep is its input bit for bit."""
        rng = jax.random.PRNGKey(seed)
        images = jax.random.uniform(jax.random.PRNGKey(5), (b, 12, 12, 3))
        out = np.asarray(color_jitter(rng, images, *constants, apply_prob=apply_prob))

        k_order, k_apply, *k_factors = jax.random.split(rng, 6)
        hue = constants[3]
        ranges = [(max(0.0, 1 - c), 1 + c) for c in constants[:3]] + [(-hue, hue)]
        factors = [
            jax.random.uniform(k, (b, 1, 1, 1), minval=lo, maxval=hi)
            for k, (lo, hi) in zip(k_factors, ranges)
        ]
        order = np.asarray(jnp.argsort(jax.random.uniform(k_order, (b, 4)), axis=1))
        keep = np.asarray(jax.random.bernoulli(k_apply, apply_prob, (b,))) | (apply_prob >= 1.0)
        if apply_prob < 1.0:
            assert keep.any() and not keep.all()
        assert len({tuple(o) for o in order}) >= orders

        adjusts = [adjust_brightness, adjust_contrast, adjust_saturation, adjust_hue]
        for i in range(b):
            x = images[i : i + 1]
            if not keep[i]:
                np.testing.assert_array_equal(out[i], np.asarray(x[0]))
                continue
            for op in order[i]:
                if op == 3 and hue == 0:
                    continue
                x = adjusts[op](x, factors[op][i : i + 1])
            np.testing.assert_allclose(out[i], np.asarray(x[0]), atol=1e-5)

    def test_evaluates_each_order_once(self):
        """A count, so it runs on the CPU: the HSV round trip is traced
        once (its `floor`), and XLA's flops an element stand under a third
        of the all-candidates form's (`_jitter_all_candidates`)."""
        shapes = (jax.ShapeDtypeStruct((2,), jnp.uint32), jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32))
        args = (0.4, 0.4, 0.4, 0.1, 0.8)

        def count(jaxpr, name):
            n = 0
            for eqn in jaxpr.eqns:
                n += eqn.primitive.name == name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    n += count(sub, name)
            return n

        def flops_per_element(fn):
            cost = jax.jit(lambda k, x: fn(k, x, *args)).lower(*shapes).compile().cost_analysis()
            return cost["flops"] / np.prod(shapes[1].shape)

        def floors(fn):
            return count(jax.make_jaxpr(lambda k, x: fn(k, x, *args))(*shapes).jaxpr, "floor")

        assert floors(color_jitter) == 1
        assert floors(_jitter_all_candidates) == 4  # the counter sees what it should
        new, old = flops_per_element(color_jitter), flops_per_element(_jitter_all_candidates)
        assert new < 753 / 3, new  # a third of what the all-candidates form cost when it was replaced
        # the oracle shares today's `adjust_hue`, whose nested where is cheaper than the
        # `jnp.select` it had then: 585 where it read 753
        assert old > 500 and new < old / 2.5, (new, old)

    def test_order_varies_across_images(self):
        orders = jnp.argsort(
            jax.random.uniform(jax.random.split(jax.random.PRNGKey(2), 1)[0], (64, 4)),
            axis=1,
        )
        assert len({tuple(np.asarray(o)) for o in orders}) > 1


# ----------------------------------------------------- PIL color parity


def _pil_roundtrip(img01: np.ndarray, fn) -> np.ndarray:
    pil = Image.fromarray((img01 * 255).round().astype(np.uint8))
    return np.asarray(fn(pil), np.float32) / 255.0


@pytest.fixture(scope="module")
def img01():
    rng = np.random.default_rng(0)
    # smooth-ish structured image: random low-freq field, upsampled
    small = rng.uniform(size=(8, 8, 3)).astype(np.float32)
    img = np.asarray(
        jax.image.resize(jnp.asarray(small), (64, 64, 3), "linear"), np.float32
    )
    return np.clip(img, 0.0, 1.0)


class TestPILColorParity:
    @pytest.mark.parametrize("factor", [0.6, 1.0, 1.4])
    def test_brightness(self, img01, factor):
        ours = np.asarray(adjust_brightness(jnp.asarray(img01)[None], jnp.full((1, 1, 1, 1), factor)))[0]
        want = _pil_roundtrip(img01, lambda im: ImageEnhance.Brightness(im).enhance(factor))
        assert np.abs(ours - want).mean() < 2 / 255
        assert np.abs(ours - want).max() < 4 / 255

    @pytest.mark.parametrize("factor", [0.6, 1.4])
    def test_saturation(self, img01, factor):
        ours = np.asarray(adjust_saturation(jnp.asarray(img01)[None], jnp.full((1, 1, 1, 1), factor)))[0]
        want = _pil_roundtrip(img01, lambda im: ImageEnhance.Color(im).enhance(factor))
        assert np.abs(ours - want).mean() < 2 / 255
        assert np.abs(ours - want).max() < 5 / 255

    @pytest.mark.parametrize("factor", [0.6, 1.4])
    def test_contrast(self, img01, factor):
        ours = np.asarray(adjust_contrast(jnp.asarray(img01)[None], jnp.full((1, 1, 1, 1), factor)))[0]
        want = _pil_roundtrip(img01, lambda im: ImageEnhance.Contrast(im).enhance(factor))
        # PIL computes the gray pivot from the rounded uint8 L-histogram
        # mean; allow that quantization plus blend rounding.
        assert np.abs(ours - want).mean() < 3 / 255
        assert np.abs(ours - want).max() < 6 / 255

    @pytest.mark.parametrize("delta", [-0.1, 0.1])
    def test_hue_bounded_vs_pil_hsv(self, img01, delta):
        """Float-HSV hue shift vs PIL's uint8 HSV shift (torchvision's
        PIL backend): same color model, so the residual is PIL's uint8
        quantization (~1-2/255). This test caught a wrong-direction YIQ
        rotation (0.17 mean abs) in an earlier implementation."""
        ours = np.asarray(adjust_hue(jnp.asarray(img01)[None], jnp.full((1, 1, 1, 1), delta)))[0]

        def pil_hue(im):
            h, s, v = im.convert("HSV").split()
            shift = int(round(delta * 255))
            h = h.point(lambda px: (px + shift) % 256)
            return Image.merge("HSV", (h, s, v)).convert("RGB")

        want = _pil_roundtrip(img01, pil_hue)
        assert np.abs(ours - want).mean() < 0.008
        assert np.abs(ours - want).max() < 0.05


# ------------------------------------------------------- PIL blur parity


class TestPILBlurParity:
    @pytest.mark.parametrize("sigma", [0.5, 1.5, 2.0])
    def test_blur_bounded_vs_pil(self, img01, sigma):
        """Reference blur is PIL ImageFilter.GaussianBlur(radius=sigma)
        (`moco/loader.py:~L23-35`). Ours is an exact truncated Gaussian;
        PIL's is its own windowed implementation — bound the gap."""
        ours = np.asarray(
            gaussian_blur(
                jax.random.PRNGKey(0),
                jnp.asarray(img01)[None],
                sigma_range=(sigma, sigma),
                apply_prob=1.0,
            )
        )[0]
        want = _pil_roundtrip(img01, lambda im: im.filter(ImageFilter.GaussianBlur(sigma)))
        # interior only: PIL pads by edge replication too but with its own
        # window; borders carry the largest discrepancy
        c = 4
        diff = np.abs(ours - want)[c:-c, c:-c]
        assert diff.mean() < 2 / 255
        assert diff.max() < 8 / 255


class TestHostRRCSampler:
    """numpy twin of the jax sampler (host-crop pipeline) against the
    same sequential torchvision oracle."""

    N = 8000

    @pytest.mark.parametrize("h,w", [(64, 64), (48, 120)], ids=["square", "wide"])
    def test_matches_oracle(self, h, w):
        from moco_tpu.data.datasets import sample_rrc_boxes

        scale, ratio = (0.2, 1.0), (3 / 4, 4 / 3)
        dims = np.full((self.N, 2), (h, w), np.int32)
        ours = sample_rrc_boxes(np.random.default_rng(11), dims, scale, ratio)
        oracle = tv_rrc_params_oracle(np.random.default_rng(7), h, w, scale, ratio, self.N)
        for col, name in enumerate(["y0", "x0", "ch", "cw"]):
            stat = ks_2samp(ours[:, col], oracle[:, col]).statistic
            assert stat < 0.035, f"{name}: KS={stat:.4f} (h={h}, w={w})"

    def test_boxes_inside_per_image_dims(self):
        from moco_tpu.data.datasets import sample_rrc_boxes

        rng = np.random.default_rng(0)
        dims = rng.integers(20, 200, (4096, 2)).astype(np.int32)
        b = sample_rrc_boxes(rng, dims)
        assert (b[:, 0] >= 0).all() and (b[:, 1] >= 0).all()
        assert (b[:, 0] + b[:, 2] <= dims[:, 0]).all()
        assert (b[:, 1] + b[:, 3] <= dims[:, 1]).all()
        assert (b[:, 2] > 0).all() and (b[:, 3] > 0).all()
