"""The Pallas kernels of the token path, and the image path's colour
kernel, compile for the chip at the published widths: the TPU's compiler
is installed here and compiles for a described `v5e:2x2` chip that is not
attached (what interpret mode cannot show: tiling, fast-memory use,
partitioning). Nothing runs, so nothing here is a result or a time. One
file and a fixture, so that only the worker that is handed this file
loads the TPU's library."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def four_chips():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_causal_attention_compiles_at_8192_positions(one_chip):
    """Forward, dq and dk/dv at 2 rows x 32 heads x 8192 positions, 192-wide
    q and k, 128-wide v, bfloat16: the cell's own call."""
    from moco_tpu.ops.flash_attention import causal_flash_attention

    qk = _shape(one_chip, (2, 32, 8192, 192), jnp.bfloat16)
    v = _shape(one_chip, (2, 32, 8192, 128), jnp.bfloat16)
    lens = _shape(one_chip, (2,), jnp.int32)

    def f(q, k, v, lens):
        loss = lambda q, k, v: jnp.sum(causal_flash_attention(q, k, v, lens).astype(jnp.float32))
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    text = jax.jit(f).lower(qk, qk, v, lens).compile().as_text()
    for name in ("causal_attention_fwd", "causal_attention_dq", "causal_attention_dkv"):
        assert name in text


@pytest.mark.parametrize("window", [None, 4096], ids=["full_layer", "window_layer"])
def test_grouped_head_attention_compiles_at_16384_positions(one_chip, window):
    """Forward, dq and dk/dv at 1 row x 28 query / 4 key heads x 16 384
    positions of 128, bfloat16, with and without the 4096-key window: the
    second token cell's own calls. dk and dv come back at the 4 key heads."""
    from moco_tpu.ops.flash_attention import causal_flash_attention

    q = _shape(one_chip, (1, 28, 16384, 128), jnp.bfloat16)
    kv = _shape(one_chip, (1, 4, 16384, 128), jnp.bfloat16)
    lens = _shape(one_chip, (1,), jnp.int32)

    def f(q, k, v, lens):
        loss = lambda q, k, v: jnp.sum(
            causal_flash_attention(q, k, v, lens, window=window).astype(jnp.float32)
        )
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    compiled = jax.jit(f).lower(q, kv, kv, lens).compile()
    kind = "causal" if window is None else "window"
    for which in ("fwd", "dq", "dkv"):
        assert f"{kind}_attention_{which}" in compiled.as_text()
    _, (dq, dk, dv) = jax.eval_shape(f, q, kv, kv, lens)
    assert dq.shape == (1, 28, 16384, 128) and dk.shape == dv.shape == (1, 4, 16384, 128)


_EXPERT_PRODUCTS = {  # cell: (held, hidden, the ladder's rows), expert width 768
    "experts": (16, 2048, (16384, 131072)),
    "reglu_experts": (8, 2560, (24576, 98304)),
}


@pytest.mark.parametrize(
    "rows,held,k,n",
    [
        shape
        for held, hidden, ladder in _EXPERT_PRODUCTS.values()
        for rows in ladder
        for shape in ((rows, held, hidden, 1536), (rows, held, 768, hidden))
    ],
    ids=[
        f"{cell}_{which}_{rows}"
        for cell, (_, _, ladder) in _EXPERT_PRODUCTS.items()
        for rows in ladder
        for which in ("in", "out")
    ],
)
def test_grouped_matmul_compiles_at_every_rung_s_buffer(one_chip, monkeypatch, rows, held, k, n):
    """16 held experts over the rungs of the first token cell's ladder
    (twice the even share of its 2 x 8192 x 8 assignments, and all of
    them), 8 over those of the second's 16 384 x 6: both products of an
    expert and their gradients."""
    from moco_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _shape(one_chip, (rows, k), jnp.bfloat16)
    w = _shape(one_chip, (held, k, n), jnp.bfloat16)
    sizes = _shape(one_chip, (held,), jnp.int32)

    def f(x, w, sizes):
        loss = lambda x, w: jnp.sum(gm.grouped_matmul(x, w, sizes).astype(jnp.float32))
        return jax.value_and_grad(loss, (0, 1))(x, w)

    assert "tpu_custom_call" in jax.jit(f).lower(x, w, sizes).compile().as_text()


def test_a_remat_block_keeps_what_its_attention_backward_needs(one_chip, monkeypatch):
    """Value and gradient of ONE rematerialised block (the dense one) at the
    published widths, 2 rows x 8192 positions, bfloat16: it compiles; beside
    its arguments it saves the attention output and the log-sum-exp with no
    unit axis (136 MB; (64, 8192, 1) float32 pads to 128 lanes: 402 MB);
    and the compiled module holds one forward kernel, the forward pass's,
    where the unpoliced block holds a second for the backward pass."""
    from jax._src.ad_checkpoint import saved_residuals

    from moco_tpu.models import joyai

    monkeypatch.setattr(joyai, "pallas_interpret", lambda: False)  # this process's backend is the CPU
    block = joyai.RematBlock(
        cfg=joyai._JOYAI_CONFIGS["joyai_llm_flash"], moe=False, first_expert=0, experts_held=16,
        train=True, dtype=jnp.bfloat16,
    )
    x = _shape(one_chip, (2, 8192, 2048), jnp.bfloat16)
    lens = _shape(one_chip, (2,), jnp.int32)
    params = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), x, lens))["params"],
    )

    def loss(params, x, lens):
        return jnp.sum(block.apply({"params": params}, x, lens).astype(jnp.float32))

    kept = {
        aval.str_short(): nbytes
        for aval, why in saved_residuals(loss, params, x, lens)
        if "from the argument" not in why
        and (nbytes := aval.size * aval.dtype.itemsize) >= 2**20  # not RoPE's constants
    }
    assert kept == {"bfloat16[2,32,8192,128]": 2**27, "float32[64,8192]": 2**21}
    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(params, x, lens).compile().as_text()
    for name in ("causal_attention_fwd", "causal_attention_dq", "causal_attention_dkv"):
        assert len(re.findall(rf"custom-call\(.*{name}", text)) == 1, name


@pytest.mark.parametrize("window", [None, 512], ids=["full_and_cross_layers", "window_layer"])
def test_differential_attention_compiles_at_16384_positions(one_chip, window):
    """One softmax of a differential layer as the third token cell calls
    it: 1 row x 20 query pairs of 64 over 10 key pairs of 64 and 10 value
    heads of 128, bfloat16, under the family's own kernel names."""
    from moco_tpu.ops.flash_attention import causal_flash_attention

    q = _shape(one_chip, (1, 20, 16384, 64), jnp.bfloat16)
    k = _shape(one_chip, (1, 10, 16384, 64), jnp.bfloat16)
    v = _shape(one_chip, (1, 10, 16384, 128), jnp.bfloat16)
    lens = _shape(one_chip, (1,), jnp.int32)

    def f(q, k, v, lens):
        loss = lambda q, k, v: jnp.sum(causal_flash_attention(
            q, k, v, lens, scale=0.125, window=window, name="diff_attention").astype(jnp.float32))
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    text = jax.jit(f).lower(q, k, v, lens).compile().as_text()
    for which in ("fwd", "dq", "dkv"):
        assert f"diff_attention_{which}" in text
    assert "causal_attention_fwd" not in text and "window_attention_fwd" not in text


def test_the_selective_scan_compiles_at_the_published_width(one_chip):
    """Forward and backward of the Mamba scan at 1 row x 16 384 positions x
    5120 channels x 16 states: the tiles fit the chip's fast memory."""
    from moco_tpu.ops.selective_scan import selective_scan

    x = _shape(one_chip, (1, 16384, 5120), jnp.bfloat16)
    dt = _shape(one_chip, (1, 16384, 5120), jnp.float32)
    a_log = _shape(one_chip, (5120, 16), jnp.float32)
    bc = _shape(one_chip, (1, 16384, 16), jnp.float32)
    d = _shape(one_chip, (5120,), jnp.float32)
    lens = _shape(one_chip, (1,), jnp.int32)

    def f(x, dt, a_log, b, c, d, lens):
        loss = lambda *a: jnp.sum(selective_scan(*a, lens).astype(jnp.float32))
        return jax.value_and_grad(loss, tuple(range(6)))(x, dt, a_log, b, c, d)

    text = jax.jit(f).lower(x, dt, a_log, bc, bc, d, lens).compile().as_text()
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text


@pytest.mark.parametrize("hue", [True, False], ids=["hue", "no_hue"])
def test_the_colour_kernel_compiles_at_an_r50_view(one_chip, hue):
    """The v2 colour stage's kernel on one view of the R50 cell, 256 images'
    (3, 256, 224, 224) float32 planes, with the hue round trip and without
    it: its blocks fit the default scoped VMEM (no limit is asked for), and
    it is in the program under its name."""
    from moco_tpu.ops.colour_jitter import colour_jitter

    planes = _shape(one_chip, (3, 256, 224, 224), jnp.float32)
    kinds = _shape(one_chip, (256, 4), jnp.int32)
    factors = _shape(one_chip, (256, 4), jnp.float32)
    flag = _shape(one_chip, (256,), jnp.bool_)

    def f(planes, kinds, factors, keep, gray):
        return colour_jitter(planes, kinds, factors, keep, gray, hue=hue)

    text = jax.jit(f).lower(planes, kinds, factors, flag, flag).compile().as_text()
    assert len(re.findall(r"%colour_jitter[.\d]* = .*custom-call\(", text)) == 1


@pytest.mark.parametrize("host_crops", [False, True], ids=["canvas", "host_crops"])
def test_the_two_view_program_compiles_over_four_chips(four_chips, monkeypatch, host_crops):
    """`TwoCropPipeline`'s two-view program, on the canvas and on host
    crops, for the R50 cell's 256 images over a 2x2 mesh: XLA refuses to
    partition a Mosaic call, so the colour kernel has to arrive under
    `shard_map`, once a view, on each chip's 64 images."""
    from moco_tpu.data import augment
    from moco_tpu.data.pipeline import TwoCropPipeline
    from moco_tpu.parallel import create_mesh
    from moco_tpu.utils.config import DataConfig

    monkeypatch.setattr(augment, "pallas_interpret", lambda: False)  # this process's backend is the CPU
    mesh = create_mesh(devices=four_chips)
    cfg = DataConfig(dataset="synthetic", image_size=224, global_batch=256, num_workers=1, aug_plus=True)
    pipe = TwoCropPipeline(cfg, mesh)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(mesh, P()))
    rows = NamedSharding(mesh, P("data"))
    if host_crops:
        view = jax.ShapeDtypeStruct((256, 224, 224, 3), jnp.uint8, sharding=rows)
        lowered = pipe._augment_precropped.lower(rng, view, view)
    else:
        canvas = pipe.dataset.load(0)[0].shape
        lowered = pipe._augment.lower(rng, jax.ShapeDtypeStruct((256, *canvas), jnp.uint8, sharding=rows))
    calls = re.findall(r"%colour_jitter[.\d]* = (.*) custom-call\(", lowered.compile().as_text())
    assert len(calls) == 2 and all("f32[3,64,224,224]" in c for c in calls), calls
