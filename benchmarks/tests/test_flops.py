"""Operation counts against the figures the papers give."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import flops
from benchmarks.harness.peaks import UnknownDeviceError, peaks_for


def _shapes(arch, size=224, **kw):
    from moco_tpu.core import build_encoder
    from moco_tpu.utils.config import MocoConfig

    enc = build_encoder(MocoConfig(arch=arch, **kw))
    return jax.eval_shape(
        lambda r: enc.init(r, jnp.zeros((1, size, size, 3)), train=False), jax.random.PRNGKey(0)
    )["params"]


def test_resnet50_forward_is_4_1_gmacs():
    fwd = flops.resnet_forward_flops(_shapes("resnet50", mlp=True)["backbone"], 224)
    assert fwd / 2 == pytest.approx(4.09e9, rel=0.01)  # arXiv:1512.03385 table 1, v1.5 stride


def test_resnet18_forward_is_1_8_gmacs():
    fwd = flops.resnet_forward_flops(_shapes("resnet18")["backbone"], 224)
    assert fwd / 2 == pytest.approx(1.81e9, rel=0.01)


def test_vit_b16_forward():
    fwd = flops.vit_forward_flops(_shapes("vit_b16", num_negatives=0, v3=True, dim=256)["backbone"], 224)
    s, d = 197, 768
    want = 2 * 16 * 16 * 3 * d * 196 + 12 * (24 * s * d * d + 4 * s * s * d)
    assert fwd == pytest.approx(want, rel=1e-6)
    assert fwd / 2 == pytest.approx(17.5e9, rel=0.02)  # ~17.5 GMACs, arXiv:2010.11929


def test_step_flops_and_infonce():
    p = _shapes("resnet50", mlp=True)
    step = flops.train_step_flops(p, {}, 224, 256, v3=False, dim=128, num_negatives=65536)
    fwd = flops.encoder_forward_flops(p, 224)
    nce = flops.infonce_required(256, 128, 65536)
    assert step == pytest.approx(256 * 4 * fwd + nce["flops"])
    assert nce["flops"] == 4.0 * 256 * 128 * 65537
    least, bound = flops.roofline_seconds(nce, peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(nce["bytes"] / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(UnknownDeviceError):
        peaks_for("TPU v9 imaginary")
