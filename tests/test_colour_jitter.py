"""The v2 recipe's colour stage as one kernel (`ops/colour_jitter.py`)
against the batched jnp path it replaces where images are wide enough:
`color_jitter` then `random_grayscale` on the same keys, and with the rest
of the recipe after the crop in the kernel too, `gaussian_blur`,
`random_horizontal_flip` and `normalize` after them. The kernel runs in
interpret mode here; `tests/test_tpu_kernels.py` runs it compiled on a chip
and `tests/test_tpu_compile.py` compiles it for a described one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.data.augment import (
    IMAGENET_MEAN,
    IMAGENET_STD,
    PROBE_RECIPE,
    V1_RECIPE,
    V2_RECIPE,
    Finish,
    apply_recipe,
    blur_draws,
    color_jitter,
    colour_stage,
    flip_draws,
    gaussian_blur,
    get_recipe,
    normalize,
    random_grayscale,
    random_horizontal_flip,
)
from moco_tpu.data.pipeline import TwoCropPipeline
from moco_tpu.ops.colour_jitter import fits
from moco_tpu.parallel import create_mesh
from moco_tpu.utils.config import DataConfig


def colour_only(b):
    """A finish that blurs and flips no image and normalises by a mean of
    0 and a std of 1, the identity: the kernel then runs the colour ops
    alone."""
    return Finish(None, None, jnp.zeros((b,), bool), (0.0, 0.0, 0.0), (1.0, 1.0, 1.0))


def _stage_and_reference(seed, b, hw, jitter, apply_prob):
    """The kernel's colour ops and the jnp composition on one seed's keys,
    and the draws that say which images were kept, grayed and in what
    order."""
    k_jit, k_gray = jax.random.split(jax.random.PRNGKey(seed))
    images = jax.random.uniform(jax.random.PRNGKey(seed + 1), (b, *hw, 3))
    got = jax.jit(lambda x: colour_stage(k_jit, k_gray, x, jitter, apply_prob, 0.2, colour_only(b)))(images)
    # the same keys on purpose, here and below: the reference and the draws replayed are the kernel's
    want = random_grayscale(k_gray, color_jitter(k_jit, images, *jitter, apply_prob=apply_prob), 0.2)  # mocolint: disable=JX003
    k_order, k_apply, *_ = jax.random.split(k_jit, 6)  # mocolint: disable=JX003
    order = np.asarray(jnp.argsort(jax.random.uniform(k_order, (b, 4)), axis=1))
    kept = np.asarray(jax.random.bernoulli(k_apply, apply_prob, (b,))) | (apply_prob >= 1.0)
    gray = np.asarray(jax.random.bernoulli(k_gray, 0.2, (b,)))  # mocolint: disable=JX003
    return np.asarray(images), np.asarray(got), np.asarray(want), order, kept, gray


@pytest.mark.parametrize(
    "seed,b,hw,hue,apply_prob",
    [
        (10, 6, (224, 224), 0.1, 0.8),
        (1, 96, (32, 128), 0.1, 1.0),
        (5, 96, (32, 128), 0.1, 0.8),
        (3, 96, (32, 128), 0.4, 1.0),
        (6, 96, (32, 128), 0.4, 0.8),
        (7, 96, (32, 128), 0.0, 1.0),
        (9, 96, (32, 128), 0.0, 0.8),
    ],
    ids=["224px", "hue0.1", "hue0.1_p0.8", "hue0.4", "hue0.4_p0.8", "hue0", "hue0_p0.8"],
)
def test_kernel_equals_jitter_then_grayscale(seed, b, hw, hue, apply_prob):
    """Every image within 1e-5 of the jnp composition, over the recipes'
    hue ranges and apply probabilities; at 96 images every order of the
    four ops comes up (every position of hue, every arrangement of the
    three blends); an image RandomApply skips and grayscale leaves is its
    input bit for bit."""
    images, got, want, order, kept, gray = _stage_and_reference(
        seed, b, hw, (0.4, 0.4, 0.4, hue), apply_prob
    )
    if b == 96:
        assert len({tuple(o) for o in order[kept]}) == 24
    if apply_prob < 1.0:
        assert (~kept & ~gray).any() and kept.any()
    assert gray.any() and not gray.all()
    for i in range(b):
        np.testing.assert_allclose(got[i], want[i], atol=1e-5, rtol=0, err_msg=f"image {i}")
        if not kept[i] and not gray[i]:
            np.testing.assert_array_equal(got[i], images[i])
        if gray[i]:
            np.testing.assert_array_equal(got[i, ..., 0], got[i, ..., 2])


def _chain_and_reference(seed, b, hw, blur_prob, flip_prob, jitter_prob=0.8):
    """The fused stage and the batched chain after the crop on one seed's
    keys (jitter, grayscale, blur, flip, normalise), the input, and the
    draws that say which images each step took."""
    k_jit, k_gray, k_blur, k_flip = jax.random.split(jax.random.PRNGKey(seed), 4)
    images = jax.random.uniform(jax.random.PRNGKey(seed + 1), (b, *hw, 3))
    jitter = (0.4, 0.4, 0.4, 0.1)
    sigma, blurred = blur_draws(k_blur, b, apply_prob=blur_prob) if blur_prob else (None, None)
    finish = Finish(sigma, blurred, flip_draws(k_flip, b, flip_prob), IMAGENET_MEAN, IMAGENET_STD)
    got = jax.jit(lambda x: colour_stage(k_jit, k_gray, x, jitter, jitter_prob, 0.2, finish))(images)
    # the same keys on purpose: the reference and the draws replayed are the kernel's
    want = random_grayscale(k_gray, color_jitter(k_jit, images, *jitter, apply_prob=jitter_prob), 0.2)  # mocolint: disable=JX003
    if blur_prob:
        want = gaussian_blur(k_blur, want, apply_prob=blur_prob)  # mocolint: disable=JX003
    want = normalize(random_horizontal_flip(k_flip, want, flip_prob))  # mocolint: disable=JX003
    _, k_apply, *_ = jax.random.split(k_jit, 6)  # mocolint: disable=JX003
    # the images no colour op and no blur took
    plain = (
        ~np.asarray(jax.random.bernoulli(k_apply, jitter_prob, (b,)))
        & ~np.asarray(jax.random.bernoulli(k_gray, 0.2, (b,)))  # mocolint: disable=JX003
    )
    if blur_prob:
        plain &= ~np.asarray(blurred)
    return np.asarray(images), np.asarray(got), np.asarray(want), plain, finish


@pytest.mark.parametrize("flip_prob", [0.0, 1.0], ids=["flip0", "flip1"])
@pytest.mark.parametrize("blur_prob", [0.0, 0.5, 1.0], ids=["blur0", "blur0.5", "blur1"])
@pytest.mark.parametrize("b,hw", [(4, (224, 224)), (16, (32, 128))], ids=["224px", "32x128"])
def test_fused_stage_equals_the_batched_chain(b, hw, blur_prob, flip_prob):
    """Jitter, grayscale, blur, flip and normalise in the kernel within
    1e-5 of the batched ops on the same keys, every image; the rows and
    columns within the blur's reach of the border on their own, where the
    kernel's band replicates the edge pixel as the batched blur pads it."""
    seed = 20 + int(10 * blur_prob) + int(flip_prob)
    images, got, want, plain, finish = _chain_and_reference(seed, b, hw, blur_prob, flip_prob)
    untouched = plain & ~np.asarray(finish.flipped)
    if blur_prob == 0.5:
        assert np.asarray(finish.blurred).any() and not np.asarray(finish.blurred).all()
    reach = 11  # half the 23-tap window
    normalised = np.asarray(jax.jit(normalize)(images))  # as the batched path compiles it
    for i in range(b):
        np.testing.assert_allclose(got[i], want[i], atol=1e-5, rtol=0, err_msg=f"image {i}")
        for name, edge in (("top rows", np.s_[:reach]), ("bottom rows", np.s_[-reach:]),
                           ("left columns", np.s_[:, :reach]), ("right columns", np.s_[:, -reach:])):
            np.testing.assert_allclose(got[i][edge], want[i][edge], atol=1e-5, rtol=0, err_msg=f"image {i} {name}")
        if untouched[i]:
            np.testing.assert_array_equal(got[i], normalised[i])


@pytest.mark.parametrize("blur_prob", [0.0, 0.5], ids=["no_blur", "blur0.5"])
def test_an_image_no_step_takes_is_its_normalised_input(blur_prob):
    """An image that RandomApply's jitter, grayscale, blur and flip all
    leave comes out of the kernel as `normalize` of its input, bit for
    bit (as a program compiles `normalize`: a multiply by the reciprocal
    of the constant): the kernel passes it through its VMEM block
    untouched until the normalise. One only flipped is its normalised
    mirror bit for bit: the mirror's product picks elements exactly."""
    images, got, _, plain, finish = _chain_and_reference(3, 48, (32, 128), blur_prob, 0.5, jitter_prob=0.5)
    flipped = np.asarray(finish.flipped)
    assert (plain & ~flipped).sum() >= 2 and (plain & flipped).sum() >= 2
    normalised = np.asarray(jax.jit(normalize)(images))  # as the batched path compiles it
    for i in np.flatnonzero(plain):
        np.testing.assert_array_equal(got[i], normalised[i][:, ::-1] if flipped[i] else normalised[i], err_msg=f"image {i}")


def test_the_v2_view_takes_the_batched_path_draws():
    """`apply_recipe`'s v2 view through the kernel equals the batched ops
    on the keys `apply_recipe` splits for them: the kernel takes the blur's
    sigma and flag and the flip's flag from `k_blur` and `k_flip` as the
    batched ops draw them."""
    key = jax.random.PRNGKey(2147483659)
    images = jax.random.uniform(jax.random.PRNGKey(8), (16, 32, 128, 3))
    recipe = V2_RECIPE._replace(crop=False)
    got = jax.jit(lambda x: apply_recipe(recipe, key, x, 32))(images)
    _, k_jit, k_gray, k_blur, k_flip = jax.random.split(key, 5)
    x = random_grayscale(k_gray, color_jitter(k_jit, images, *recipe.jitter, apply_prob=recipe.jitter_prob), 0.2)
    x = random_horizontal_flip(k_flip, gaussian_blur(k_blur, x, apply_prob=recipe.blur_prob))
    np.testing.assert_allclose(np.asarray(got), np.asarray(normalize(x)), atol=1e-5, rtol=0)


def _kernel_names(jaxpr, under=None):
    """The names of the `pallas_call`s in `jaxpr` and the jaxprs it holds;
    with `under`, only those inside an equation of that primitive."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and under is None:
            yield str(eqn.params["name"])
        inside = None if eqn.primitive.name == under else under
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_names(sub, inside)


def _primitives(jaxpr):
    """The names of the primitives in `jaxpr` and the jaxprs it holds."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def _view_jaxpr(recipe, size):
    return jax.make_jaxpr(lambda k, x: apply_recipe(recipe, k, x, size))(
        jax.random.PRNGKey(0), jnp.zeros((2, size, size, 3))
    ).jaxpr


@pytest.mark.parametrize(
    "recipe,size,calls,batched",
    [
        (V2_RECIPE, 224, 1, set()),
        (get_recipe(aug_plus=True, image_size=32), 32, 0, {"rev"}),
        (V1_RECIPE, 224, 0, {"rev"}),
        (PROBE_RECIPE, 224, 0, {"rev"}),
        (V2_RECIPE, 96, 0, {"rev", "conv_general_dilated"}),
    ],
    ids=["v2_224px", "v2_32px", "v1_224px", "probe_224px", "v2_96px"],
)
def test_the_kernel_engages_by_shape_in_the_v2_recipe(recipe, size, calls, batched):
    """One kernel, named `colour_jitter`, in the v2 view at 224 px, and no
    blur convolution or flip `rev` beside it: the kernel runs the view
    from the crop to the normalise. None at CIFAR's 32 px or at 96 px
    (planes narrower than a lane tile), nor in the v1 order (grayscale
    before jitter) or the probe recipe (no jitter): those keep the
    batched flip and, where the recipe blurs, the batched blur."""
    jaxpr = _view_jaxpr(recipe, size)
    names = list(_kernel_names(jaxpr))
    assert len(names) == calls and all(n.startswith("colour_jitter") for n in names), names
    assert set(_primitives(jaxpr)) & {"rev", "conv_general_dilated"} == batched


@pytest.mark.parametrize(
    "hw,takes",
    [((224, 224), True), ((256, 256), True), ((32, 128), True), ((32, 32), False),
     ((224, 96), False), ((216, 224), False), ((512, 512), False)],
)
def test_fits_is_a_rule_of_the_shape(hw, takes):
    assert fits(*hw) is takes


def test_the_pipeline_over_a_mesh_runs_the_kernel_on_each_device():
    """`TwoCropPipeline`'s two-view program over four devices calls the
    kernel once a view, each time under `shard_map` (XLA does not
    partition a Mosaic call: on a TPU mesh the call has to come
    partitioned), and gives the views that one device gives, bit for bit;
    so does its program for crops made on the host."""
    cfg = DataConfig(dataset="synthetic", image_size=128, global_batch=8, num_workers=1, aug_plus=True)
    views, pipes = {}, {}
    for n in (1, 4):
        pipes[n] = TwoCropPipeline(cfg, create_mesh(devices=jax.devices()[:n]), seed=3)
        it = pipes[n].epoch(0)
        views[n] = next(it)
        it.close()
    for name in ("im_q", "im_k"):
        assert len(views[4][name].sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(views[4][name]), np.asarray(views[1][name]))
    crops = np.random.default_rng(3).integers(0, 256, (8, 128, 128, 3), dtype=np.uint8)
    precropped = {}
    for n, pipe in pipes.items():
        x = jax.device_put(crops, pipe._sharding)
        precropped[n] = pipe._augment_precropped(jax.random.PRNGKey(4), x, x)
    for name in ("im_q", "im_k"):
        np.testing.assert_array_equal(np.asarray(precropped[4][name]), np.asarray(precropped[1][name]))
    raw = jnp.zeros((8, 128, 128, 3), jnp.uint8)
    for n, mapped in ((4, 2), (1, 0)):
        jaxpr = jax.make_jaxpr(pipes[n]._augment)(jax.random.PRNGKey(0), raw).jaxpr
        assert list(_kernel_names(jaxpr)) == ["colour_jitter"] * 2
        assert list(_kernel_names(jaxpr, under="shard_map")) == ["colour_jitter"] * mapped
