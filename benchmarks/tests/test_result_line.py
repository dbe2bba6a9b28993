"""The two halves of a result line, built from a hand-made run: `--trace 0`
gives the cell's end-to-end metrics, `--trace 1` its per-layer metrics with
the device's busy and traced seconds and the breakdown."""

import pytest

from benchmarks.harness import common, flops
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.peaks import peaks_for
from benchmarks.trace_reduce import ops_inside, reduce_trace

KERNEL = ('%jvp__.1 = (f32[256]{0}) custom-call(f32[256,128]{1,0} %q, f32[65536,128]{1,0} %queue), '
          'custom_call_target="tpu_custom_call"')


def test_end_to_end_half():
    m = Manifest()
    got = common.end_to_end_metrics(
        m, "train_r50_v2", {"train_img_per_s_chip": 1445.5, "setup_s": 64.0, "serve_p95_ms": 1.0}
    )
    assert got == {
        "train_img_per_s_chip": {"value": 1445.5, "unit": "img/s/chip"},
        "setup_s": {"value": 64.0, "unit": "s"},
    }


def test_traced_half_reports_every_per_layer_metric_of_the_cell():
    m = Manifest()
    ops = [("%fusion.1 = fusion(...), kind=kOutput", 0, 100_000_000), (KERNEL, 100_000_000, 150_000),
           ("%fusion.2 = fusion(...), kind=kLoop", 130_000_000, 40_000_000)]
    mods = [("jit_step_fn(1)", 0, 101_000_000), ("jit__augment(2)", 130_000_000, 40_000_000),
            ("jit_step_fn(1)", 175_000_000, 1_000_000)]
    reduced = reduce_trace(ops, mods, "jit_step_fn")
    lines = [
        {"step": 31, "time": 0.0, "t_data": 0.004, "t_dispatch": 60.0, "transfer_bytes": 38535168},
        {"step": 41, "time": 1.8, "t_data": 0.006, "t_dispatch": 0.05, "transfer_bytes": 38535168},
        {"step": 51, "time": 3.6, "t_data": 0.005, "t_dispatch": 0.05, "transfer_bytes": 38535168},
    ]
    ctx = {
        "train_lines": lines, "trace": reduced, "trace_ops": ops_inside(ops, reduced),
        "memory_peak_bytes": 9_161_016_320, "peaks": peaks_for("TPU v5 lite"), "chips": 1,
        "train_config": {"moco": {"dim": 128, "num_negatives": 65536}, "data": {"global_batch": 256}},
        "step_flops": 8.4e12,
    }
    result, detail = {"device": {}}, {}
    common.add_traced(result, detail, m, "train_r50_v2", ctx, {"lines": {}, "planes": []})
    want = {x["name"] for x in m.metrics_for("train_r50_v2", "per_layer")}
    assert set(result["metrics"]) == want
    v = {k: x["value"] for k, x in result["metrics"].items()}
    assert v["driver_dispatch_ms"] == pytest.approx(50.0)  # the pair sampled before the window is left out
    assert v["data_wait_ms"] == pytest.approx(5.0) and v["h2d_mb_per_step"] == pytest.approx(38.535168)
    assert v["step_device_ms"] == pytest.approx(reduced["busy_s"] / 2 * 1e3)
    assert v["step_mfu"] == pytest.approx(100 * 8.4e12 / (reduced["busy_s"] / 2 * 197e12))
    assert v["infonce_kernel_ms"] == pytest.approx(0.075)
    need = flops.infonce_required(256, 128, 65536)
    assert v["infonce_roofline"] == pytest.approx(100 * need["bytes"] / 819e9 / 75e-6)
    assert v["train_peak_hbm_gb"] == pytest.approx(9.16101632)
    assert 0 < v["device_idle_share.train"] < 100
    assert result["device"] == {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    assert len(result["breakdown"]["device_ops"]) <= 10 and result["breakdown"]["idle_gaps"]
    # a ViT cell has no queue and no InfoNCE kernel: those readers find nothing
    vit = {"device": {}}
    common.add_traced(vit, {}, m, "train_vit_b16_v3", ctx, {"lines": {}, "planes": []})
    assert "infonce_roofline" not in vit["metrics"] and "step_mfu" in vit["metrics"]
