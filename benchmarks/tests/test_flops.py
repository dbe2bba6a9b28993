"""Operation counts against the figures the papers give, each family's
count at its home (its reference module), and the step's and the traced
readers' numbers against what the harness gave before the counts moved
(PR 28): equal to the last digit."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import flops
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.peaks import UnknownDeviceError, peaks_for
from benchmarks.reference import resnet_moco_v2, vit_moco_v3
from benchmarks.required import infonce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "train_r50_v2_trace_cut.json")


class _Cfg:  # what a family's `forward_flops` reads of the configuration
    class data:
        image_size = 224


def _shapes(arch, size=224, **kw):
    from moco_tpu.core import build_encoder
    from moco_tpu.utils.config import MocoConfig

    enc = build_encoder(MocoConfig(arch=arch, **kw))
    return jax.eval_shape(
        lambda r: enc.init(r, jnp.zeros((1, size, size, 3)), train=False), jax.random.PRNGKey(0)
    )["params"]


def test_resnet50_forward_is_4_1_gmacs():
    fwd = resnet_moco_v2.resnet_forward_flops(_shapes("resnet50", mlp=True)["backbone"], 224)
    assert fwd / 2 == pytest.approx(4.09e9, rel=0.01)  # arXiv:1512.03385 table 1, v1.5 stride


def test_resnet18_forward_is_1_8_gmacs():
    fwd = resnet_moco_v2.resnet_forward_flops(_shapes("resnet18")["backbone"], 224)
    assert fwd / 2 == pytest.approx(1.81e9, rel=0.01)


def test_vit_b16_forward():
    shapes = _shapes("vit_b16", num_negatives=0, v3=True, dim=256)
    fwd = vit_moco_v3.vit_forward_flops(shapes["backbone"], 224)
    s, d = 197, 768
    want = 2 * 16 * 16 * 3 * d * 196 + 12 * (24 * s * d * d + 4 * s * s * d)
    assert fwd == pytest.approx(want, rel=1e-6)
    assert fwd / 2 == pytest.approx(17.5e9, rel=0.02)  # ~17.5 GMACs, arXiv:2010.11929
    # the family's count is backbone + projection head
    assert vit_moco_v3.forward_flops(shapes, _Cfg) == fwd + flops.dense_flops(shapes["head"])


def test_step_flops_and_infonce():
    p = _shapes("resnet50", mlp=True)
    fwd = resnet_moco_v2.forward_flops(p, _Cfg)
    assert fwd == resnet_moco_v2.resnet_forward_flops(p["backbone"], 224) + flops.dense_flops(p["head"])
    step = flops.train_step_flops(fwd, {}, 256, v3=False, dim=128, num_negatives=65536)
    nce = infonce.work(256, 128, 65536)
    assert step == pytest.approx(256 * 4 * fwd + nce["flops"])
    assert nce["flops"] == 4.0 * 256 * 128 * 65537 == flops.infonce_flops(256, 128, 65536)
    least, bound = flops.roofline_seconds(nce, peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(nce["bytes"] / 819e9)
    ctx = {"chips": 4, "train_config": {"moco": {"dim": 128, "num_negatives": 65536},
                                        "data": {"global_batch": 1024}}}
    assert infonce.required(ctx) == nce  # a chip's share of the batch
    ctx["train_config"]["moco"]["num_negatives"] = 0
    assert infonce.required(ctx) is None  # queue-free: no such work


# what the harness of PR 27 (commit 4090520) computed, to the last digit: the step's operations of
# both cells, and what the `mfu` and `kernel` readers read from the recorded R50 trace cut
PARENT = {
    "train_r50_v2": {"step_flops": 8388171923456.0, "step_mfu": 28.332251457110395,
                     "infonce_roofline": 56.120744393465166, "infonce_kernel_ms": 0.1468755},
    "train_vit_b16_v3": {"step_flops": 18007671701504.0, "step_mfu": 60.823489010451325,
                         "infonce_roofline": None, "infonce_kernel_ms": 0.1468755},
}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_counts_and_readers_equal_the_parents(cell):
    from benchmarks.harness.common import build_train_config
    from benchmarks.harness.train_cell import _step_flops
    from benchmarks.trace_reduce import ops_inside, reduce_trace
    from moco_tpu.utils.config import config_to_dict

    m = Manifest()
    w = m.cell(cell)
    cfg_file = m.config_file(w["config"])
    cfg = build_train_config(cfg_file, m.traffic_file(w["traffic"]), 1, "/nonexistent", False)
    step = _step_flops(cfg, *m.family(cfg_file))
    got = {"step_flops": step}
    rec = json.load(open(FIXTURE))
    ops, mods = [tuple(e) for e in rec["ops"]], [tuple(e) for e in rec["modules"]]
    reduced = reduce_trace(ops, mods, rec["step_module"])
    ctx = {"trace": reduced, "trace_ops": ops_inside(ops, reduced), "peaks": peaks_for("TPU v5 lite"),
           "chips": 1, "train_config": config_to_dict(cfg), "step_flops": step}
    for name in ("step_mfu", "infonce_roofline", "infonce_kernel_ms"):
        spec = m.layer_metric_file(name)
        got[name] = m.reader(spec["reader"]).read(spec, ctx)
    assert got == PARENT[cell]


def test_unknown_device_is_an_error():
    with pytest.raises(UnknownDeviceError):
        peaks_for("TPU v9 imaginary")
