"""The two halves of a result line, built from a hand-made run: `--trace 0`
gives the cell's end-to-end metrics, `--trace 1` its per-layer metrics with
the device's busy and traced seconds and the breakdown."""

import json
import os

import pytest

from benchmarks import host_attribution as ha
from benchmarks.harness import common
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.peaks import peaks_for
from benchmarks.required import infonce
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


def test_traced_half_reports_every_per_layer_metric_of_the_cell(tmp_path, monkeypatch):
    """The `ctx` a run leaves behind, whole: the window's lines with the program's `phase/*`
    account, its work directory with the `setup` line and a profile (here two hand-made host
    lines: the driver's and the ring's), the reduced trace."""
    m = Manifest()
    ms = 1_000_000
    ops = [("%fusion.1 = fusion(...), kind=kOutput", 0, 100 * ms), (KERNEL, 100 * ms, 150_000),
           ("%fusion.2 = fusion(...), kind=kLoop", 130 * ms, 40 * ms)]
    mods = [("jit_step_fn(1)", 0, 101 * ms), ("jit__augment(2)", 130 * ms, 40 * ms),
            ("jit_step_fn(1)", 175 * ms, 1 * ms)]
    reduced = reduce_trace(ops, mods, "jit_step_fn")
    # the driver's thread: the gap before the augment program lies under its flush, the one
    # before the second step program under its dispatch; the ring's thread beside it
    driver = [("train_step", 0, 120 * ms, 7), ("data_wait", 1 * ms, 2 * ms, 7), ("step", 5 * ms, 60 * ms, 7),
              ("log_flush", 101 * ms, 15 * ms, 7), ("metrics_fetch", 102 * ms, 10 * ms, 7),
              ("train_step", 120 * ms, 60 * ms, 8), ("step", 122 * ms, 56 * ms, 8)]
    ring = [("transfer", 90 * ms, 30 * ms, None), ("augment_dispatch", 105 * ms, 10 * ms, None),
            ("ring_blocked", 120 * ms, 200 * ms, None)]
    monkeypatch.setattr(ha, "load_host_lines", lambda path: [driver, ring])
    import benchmarks.trace_reduce as tr
    monkeypatch.setattr(tr, "load_events", lambda path, device=0: {"ops": ops, "modules": mods, "lines": {}})
    os.makedirs(tmp_path / "profile")
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"step": 1, "time": 0.0, "event": "setup", "setup/backend_s": 0.01,
                            "setup/state_init_s": 7.0, "setup/checkpoint_s": 0.0,
                            "setup/pipeline_start_s": 8.5, "setup/first_step_s": 18.5}) + "\n")
    phases = {"phase/data_wait": 0.002, "phase/step": 0.056, "phase/throttle_wait": 0.0865,
              "phase/log_flush": 0.0157, "phase/metrics_fetch": 0.0144, "phase/log_flush_host": 0.00125,
              "phase/transfer": 0.0247, "phase/augment_dispatch": 0.0106, "phase/ring_blocked": 0.1436,
              "phase/steps": 10}
    lines = [
        {"step": 31, "time": 0.0, "t_data": 0.004, "t_dispatch": 60.0, "transfer_bytes": 38535168, **phases},
        {"step": 41, "time": 1.8, "t_data": 0.006, "t_dispatch": 0.05, "transfer_bytes": 38535168, **phases},
        {"step": 51, "time": 3.6, "t_data": 0.005, "t_dispatch": 0.05, "transfer_bytes": 38535168, **phases},
    ]
    ctx = {
        "train_lines": lines, "trace": reduced, "trace_ops": ops_inside(ops, reduced),
        "memory_peak_bytes": 9_161_016_320, "peaks": peaks_for("TPU v5 lite"), "chips": 1,
        "train_config": {"moco": {"dim": 128, "num_negatives": 65536}, "data": {"global_batch": 256},
                         "workdir": str(tmp_path), "prefetch_depth": 2},
        "step_flops": 8.4e12,
    }
    result, detail = {"device": {}}, {}
    common.add_traced(result, detail, m, "train_r50_v2", ctx, {"lines": {}, "planes": []})
    want = {x["name"] for x in m.metrics_for("train_r50_v2", "per_layer")}
    assert set(result["metrics"]) == want and len(want) == 19
    v = {k: x["value"] for k, x in result["metrics"].items()}
    assert v["driver_dispatch_ms"] == pytest.approx(50.0)  # the pair sampled before the window is left out
    assert v["data_wait_ms"] == pytest.approx(5.0) and v["h2d_mb_per_step"] == pytest.approx(38.535168)
    assert v["step_device_ms"] == pytest.approx(reduced["busy_s"] / 2 * 1e3)
    assert v["step_mfu"] == pytest.approx(100 * 8.4e12 / (reduced["busy_s"] / 2 * 197e12))
    assert v["infonce_kernel_ms"] == pytest.approx(0.075)
    need = infonce.work(256, 128, 65536)
    assert v["infonce_roofline"] == pytest.approx(100 * need["bytes"] / 819e9 / 75e-6)
    assert v["train_peak_hbm_gb"] == pytest.approx(9.16101632)
    assert 0 < v["device_idle_share.train"] < 100
    # the host's account of a step, and the device's idle by the driver's span (2 traced steps)
    assert v["driver_throttle_ms"] == pytest.approx(86.5) and v["driver_log_flush_ms"] == pytest.approx(1.25)
    assert v["ring_blocked_ms"] == pytest.approx(143.6) and v["augment_dispatch_ms"] == pytest.approx(10.6)
    assert v["setup_state_init_s"] == 7.0 and v["setup_first_step_s"] == 18.5
    idle_ms = (reduced["window_s"] - reduced["busy_s"]) * 1e3 / 2
    assert v["idle_in_log_flush_ms"] + v["idle_in_dispatch_ms"] + v["idle_unattributed_ms"] == (
        pytest.approx(idle_ms)
    )
    assert v["idle_in_log_flush_ms"] == pytest.approx(15.0 / 2) and v["idle_in_data_wait_ms"] == 0.0
    assert result["device"] == {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    assert len(result["breakdown"]["device_ops"]) <= 10 and result["breakdown"]["idle_gaps"]
    # a ViT cell has no queue and no InfoNCE kernel: those readers find nothing
    vit = {"device": {}}
    vit_ctx = {**ctx, "train_config": {**ctx["train_config"], "moco": {"dim": 256, "num_negatives": 0}}}
    common.add_traced(vit, {}, m, "train_vit_b16_v3", vit_ctx, {"lines": {}, "planes": []})
    assert "infonce_roofline" not in vit["metrics"] and "step_mfu" in vit["metrics"]
    assert len(vit["metrics"]) == 17 == len(m.metrics_for("train_vit_b16_v3", "per_layer"))
