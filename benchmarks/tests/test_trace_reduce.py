"""The trace reduction on hand-made events and on the recorded fixture cut
from the first chip run of PR 24 (`fixtures/train_r50_v2_trace_cut.json`)."""

import json
import os

import pytest

from benchmarks.trace_reduce import (
    bucket_name, kernel_seconds, leaf_events, merge_intervals, module_name, op_class, opcode,
    ops_inside, reduce_trace, self_times, short_name,
)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "train_r50_v2_trace_cut.json")


def test_union_and_self_time():
    assert merge_intervals([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    ops = [("while.2", 100, 300), ("conv.3", 120, 50), ("all-reduce-done.4", 200, 100)]
    assert {n: t for n, _, t in self_times(ops)} == {
        "while.2": 150, "conv.3": 50, "all-reduce-done.4": 100,
    }
    assert [n for n, _, _ in leaf_events(ops)] == ["conv.3", "all-reduce-done.4"]


def test_reduce_hand_made():
    ops = [("fusion.1", 0, 100), ("while.2", 100, 300), ("convolution.3", 120, 50),
           ("all-reduce-done.4", 200, 100), ("copy.5", 500, 100)]
    mods = [("jit_step_fn(1)", 0, 400), ("jit_step_fn(1)", 450, 150), ("jit__augment(2)", 405, 40)]
    r = reduce_trace(ops, mods, "jit_step_fn")
    assert r["steps"] == 2 and r["window_s"] == pytest.approx(600e-9)
    assert r["busy_s"] == pytest.approx(500e-9) and r["idle_share"] == pytest.approx(1 / 6)
    assert r["step_device_s"] == pytest.approx(250e-9)
    assert r["collective_s"] == pytest.approx(100e-9)
    assert r["collective_exposed_s"] == pytest.approx(100e-9)  # only `while` spans it, a parent
    assert r["idle_gaps"] == [["after while before copy", pytest.approx(100e-9)]]
    assert dict(map(tuple, r["modules"])) == {"step_fn": pytest.approx(500e-9)}
    assert dict(map(tuple, r["device_ops"]))["step_fn/convolution"] == pytest.approx(50e-9)
    assert sum(v for _, v in r["op_classes"]) == pytest.approx(r["busy_s"])
    assert kernel_seconds(ops_inside(ops, r), r"^copy") == (pytest.approx(100e-9), 1)
    assert op_class("all-gather-start.3") == "collective" and op_class("fusion.7") == "elementwise_fusion"


def test_classes_read_the_hlo_text():
    conv = ("%convert_reduce_fusion.8 = (f32[256]{0:T(256)S(1)}, bf16[256,56,56,256]{3,0,2,1}) "
            "fusion(f32[256]{0} %copy-done.472), kind=kOutput, calls=%fused_computation")
    assert short_name(conv) == "convert_reduce_fusion.8" and opcode(conv) == "fusion"
    assert bucket_name(conv) == "convert_reduce_fusion" and op_class(conv) == "conv_matmul_fusion"
    kern = ('%jvp__.1 = (f32[256]{0}, s32[256]{0}) custom-call(f32[256,128]{1,0} %a, '
            'f32[65536,128]{1,0} %copy.782), custom_call_target="tpu_custom_call"')
    assert op_class(kern) == "pallas_kernel"
    assert op_class("%all-reduce-done.3 = f32[64]{0} all-reduce-done(f32[64]{0} %x)") == "collective"
    assert op_class("%copy-done.5 = f32[64]{0} copy-done((f32[64]{0}) %copy-start.5)") == "copy_layout"
    assert op_class("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kInput, calls=%f") == "reduce_fusion"
    assert module_name("jit__augment(15452720485661776160)") == "augment"
    pattern = r'custom-call\(.*f32\[65536,128\].*custom_call_target="tpu_custom_call"'
    assert kernel_seconds([(kern, 0, 90), (conv, 100, 10)], pattern) == (pytest.approx(90e-9), 1)


def test_no_events_reads_nothing():
    assert reduce_trace([], [], "jit_step_fn")["busy_s"] == 0.0


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded fixture")
def test_recorded_fixture():
    rec = json.load(open(FIXTURE))
    ops = [tuple(e) for e in rec["ops"]]
    mods = [tuple(e) for e in rec["modules"]]
    r = reduce_trace(ops, mods, rec["step_module"])
    want = rec["expected"]
    assert r["steps"] == want["steps"]
    for key in ("busy_s", "window_s", "idle_share", "step_device_s", "collective_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9), key
    assert r["device_ops"][0][0] == want["top_op"]
    assert 0.0 <= r["idle_share"] < 1.0 and r["busy_s"] <= r["window_s"]
    # class totals are self times: they add up to the busy time exactly
    assert sum(v for _, v in reduce_trace(ops, mods, rec["step_module"], top=99)["op_classes"]) == (
        pytest.approx(r["busy_s"], rel=1e-6)
    )
