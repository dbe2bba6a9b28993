"""Device idle time named by host phase: on hand-made events, on two whole
steps cut from a chip trace of each train cell (PR 26, `fixtures/host_steps_*.json.gz`,
cut with `python benchmarks/host_attribution.py <profile> --dump 2`), and
through the two readers PR 26 added."""

import gzip
import json
import os

import pytest

from benchmarks import host_attribution as ha
from benchmarks.harness.manifest import Manifest
from benchmarks.trace_reduce import reduce_trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
CUTS = ["host_steps_train_r50_v2.json.gz", "host_steps_train_vit_b16_v3.json.gz"]

# device: two step programs with a 40 ns gap between them and a 10 ns gap inside the second
OPS = [("fusion.1", 0, 100), ("fusion.2", 140, 30), ("copy.3", 180, 20)]
MODS = [("jit_step_fn(1)", 0, 100), ("jit_step_fn(1)", 140, 60)]
WINDOW = (0, 200)
# driver thread: the flush (with its fetch) lies over the first gap, the dispatch over the second
DRIVER = [
    ("train_step", 0, 135, 7), ("data_wait", 2, 10, 7), ("step", 20, 70, 7),
    ("log_flush", 95, 35, 7), ("metrics_fetch", 100, 20, 7),
    ("train_step", 135, 65, 8), ("step", 165, 30, 8),
]
RING = [("transfer", 90, 30, None), ("augment_dispatch", 105, 10, None), ("ring_blocked", 120, 200, None)]


def test_segments_name_the_innermost_span():
    segs = ha.phase_segments(DRIVER[:5])
    assert segs == [
        (0, 2, "train_step"), (2, 12, "data_wait"), (12, 20, "train_step"), (20, 90, "step"),
        (90, 95, "train_step"), (95, 100, "log_flush"), (100, 120, "metrics_fetch"),
        (120, 130, "log_flush"), (130, 135, "train_step"),
    ]
    # the nested child wins over its parent only where asked
    whole = ha.phase_segments(DRIVER[:5], whole=("log_flush",))
    assert (95, 130, "log_flush") in [(s, e, n) for s, e, n in _joined(whole)]
    assert "metrics_fetch" not in {n for _, _, n in whole}


def _joined(segs):
    out = []
    for s, e, n in segs:
        if out and out[-1][2] == n and out[-1][1] == s:
            out[-1] = (out[-1][0], e, n)
        else:
            out.append((s, e, n))
    return out


def test_a_gap_under_log_flush_is_given_to_log_flush():
    got = ha.attribute_idle(OPS, WINDOW, DRIVER, RING)
    assert got["idle_ns"] == 50 == sum(got["by_driver"].values())
    # gap 100..140: 30 ns inside the flush (its fetch included), 5 under train_step alone and 5
    # under the next one; gap 170..180 inside the dispatch
    assert got["by_driver"] == {"log_flush": 30, "none": 10, "step": 10}
    apart = ha.attribute_idle(OPS, WINDOW, DRIVER, RING, whole=())
    assert apart["by_driver"] == {"metrics_fetch": 20, "log_flush": 10, "none": 10, "step": 10}
    # the same idle time by what the ring's thread was in
    assert got["table"]["log_flush"] == {"transfer": 10, "augment_dispatch": 10, "ring_blocked": 10}
    assert sum(sum(row.values()) for row in got["table"].values()) == 50
    assert got["table"]["step"] == {"ring_blocked": 10}


def test_idle_is_the_reducers_idle():
    reduced = reduce_trace(OPS, MODS, "jit_step_fn")
    assert tuple(reduced["window_ns"]) == WINDOW
    got = ha.attribute_idle(OPS, reduced["window_ns"], DRIVER)
    assert got["idle_ns"] / 1e9 == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert got["table"] == {k: {"none": v} for k, v in got["by_driver"].items()}  # no ring line


def test_no_driver_line_reads_nothing():
    assert ha.attribute_idle(OPS, WINDOW, None, RING) is None
    assert ha.pick_line([RING], ha.STEP_SCOPE) is None and ha.pick_line([RING], ha.RING_MARK) == RING


def test_the_account_covers_what_the_drivers_line_covers():
    # the device is traced for longer than the host: idle after the line's last span is not `none`
    late = OPS + [("fusion.9", 900, 50)]
    got = ha.attribute_idle(late, (0, 1000), DRIVER, RING)
    assert got["window_ns"] == [0, 200] and got["idle_ns"] == 50
    assert ha.attribute_idle(OPS, (500, 900), DRIVER) is None  # no overlap at all


def test_clock_check_pairs_steps_through_the_drivers_wait():
    ms = 1_000_000
    # the device runs a step behind: programs of steps 5, 6, 7, 8, a 4 ms augment before each
    mods = [("jit_step_fn(1)", (10 + 100 * i) * ms, 90 * ms) for i in range(4)]
    mods += [("jit__augment(2)", (4 + 100 * i) * ms, 4 * ms) for i in range(4)]
    driver = []
    for k, at in ((7, 50), (8, 150), (9, 250)):  # dispatch k, then wait for k - 2 (depth 2)
        driver += [("train_step", at * ms, 99 * ms, k), ("step", (at + 1) * ms, 20 * ms, k),
                   ("throttle_wait", (at + 21) * ms, 31 * ms, k + 1)]
    paired = ha.pair_steps(ha.step_modules(mods), driver, depth=2)
    assert {k: m[1] // ms for k, m in paired.items()} == {5: 10, 6: 110, 7: 210, 8: 310}
    got = ha.clock_check(mods, driver, depth=2)
    # steps 7 and 8 have both a dispatch span and a program: they start 159 ms after their dispatch did
    assert got["steps"] == 2 and got["min_lag_ns"] == got["max_lag_ns"] == 159 * ms
    assert got["starts_before_dispatch"] == 0 and got["wait_slack_ns"] == [2 * ms, 2 * ms]
    # a device clock 30 ms early: no program ends while the driver waits for it, nothing pairs,
    # and the check reports no step rather than a lag it cannot stand behind
    early = [(n, s - 30 * ms, d) for n, s, d in mods]
    assert ha.clock_check(early, driver, depth=2) == {"steps": 0}
    assert ha.clock_check([], driver) == {"steps": 0}
    # waits that returned at once anchor nothing
    quick = [(n, s, 1000 if n == "throttle_wait" else d, k) for n, s, d, k in driver]
    assert ha.pair_steps(ha.step_modules(mods), quick) == {}


@pytest.mark.parametrize("name", CUTS)
def test_recorded_steps(name):
    path = os.path.join(FIXTURES, name)
    if not os.path.exists(path):
        pytest.skip("no recorded fixture")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    ops = [tuple(e) for e in rec["ops"]]
    mods = [tuple(e) for e in rec["modules"]]
    lines = [[tuple(e) for e in ln] for ln in rec["host_lines"]]
    reduced = reduce_trace(ops, mods, rec["step_module"])
    assert reduced["steps"] >= 2  # the two steps cut, and the programs the device was still behind with
    driver, ring = ha.pick_line(lines, ha.STEP_SCOPE), ha.pick_line(lines, ha.RING_MARK)
    assert driver is not None and ring is not None and driver is not ring
    got = ha.attribute_idle(ops, reduced["window_ns"], driver, ring)
    # every idle nanosecond is given to exactly one driver phase, and it is the reducer's idle
    assert sum(got["by_driver"].values()) == got["idle_ns"]
    assert got["idle_ns"] / 1e9 == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    assert got["idle_ns"] / 1e9 == pytest.approx(reduced["idle_share"] * reduced["window_s"], rel=1e-6)
    assert sum(sum(row.values()) for row in got["table"].values()) == got["idle_ns"]
    want = rec["expected"]
    assert got["by_driver"] == want["by_driver"]
    # what the instrumentation cannot see is a small part of the idle time
    assert got["by_driver"].get("none", 0) <= want["none_share_max"] * got["idle_ns"]
    # from the first step's dispatch on (the cut's start), the device's wait for the host shows:
    # it falls under the driver's `step` span, the call that enqueues the step program
    whole = ha.attribute_idle(ops, (min(e[1] for e in driver), reduced["window_ns"][1]), driver, ring)
    assert whole["idle_ns"] == want["whole_cut"]["idle_ns"] == sum(whole["by_driver"].values())
    assert whole["by_driver"] == want["whole_cut"]["by_driver"]
    assert max(whole["by_driver"], key=whole["by_driver"].get) == "step"
    assert whole["by_driver"].get("none", 0) <= want["none_share_max"] * whole["idle_ns"]
    clock = ha.clock_check(mods, driver, rec["step_module"])
    assert clock["steps"] == 2 and clock["starts_before_dispatch"] == 0
    assert 0 < clock["min_lag_ns"] and clock["wait_slack_ns"][0] >= 0


# -- the readers ------------------------------------------------------------


def _window_lines():
    lines = []
    for i, step in enumerate((31, 41, 51)):
        lines.append({
            "step": step, "time": 1.8 * i, "loss": 7.0, "t_data": 0.004, "t_step": 0.177,
            "t_dispatch": 0.05 + 0.01 * i, "t_probe_step": step, "transfer_bytes": 38535168,
            "phase/data_wait": 0.004, "phase/step": 0.055, "phase/throttle_wait": 0.110 + 0.001 * i,
            "phase/log_flush": 0.008, "phase/metrics_fetch": 0.006, "phase/log_flush_host": 0.002,
            "phase/transfer": 0.03, "phase/augment_dispatch": 0.012, "phase/ring_blocked": 0.12,
            "phase/steps": 10,
        })
    return lines


def _ctx(workdir, lines):
    reduced = reduce_trace(OPS, MODS, "jit_step_fn")
    return {
        "train_lines": lines, "trace": reduced, "trace_ops": OPS, "chips": 1,
        "train_config": {"workdir": str(workdir)},
    }


NEW = ["driver_throttle_ms", "driver_log_flush_ms", "ring_blocked_ms", "augment_dispatch_ms",
       "idle_in_data_wait_ms", "idle_in_dispatch_ms", "idle_in_log_flush_ms", "idle_unattributed_ms",
       "setup_state_init_s", "setup_first_step_s"]


def _read(m, name, ctx):
    spec = m.layer_metric_file(name)
    return m.reader(spec["reader"]).read(spec, ctx)


def test_new_metrics_read_what_the_program_writes(tmp_path, monkeypatch):
    m = Manifest()
    for cell in ("train_r50_v2", "train_vit_b16_v3"):
        assert set(NEW) <= {x["name"] for x in m.metrics_for(cell, "per_layer")}
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"step": 1, "time": 0.0, "event": "setup", "setup/backend_s": 0.01,
                            "setup/state_init_s": 14.0, "setup/checkpoint_s": 0.2,
                            "setup/pipeline_start_s": 8.0, "setup/first_step_s": 12.5}) + "\n")
    os.makedirs(tmp_path / "profile")
    monkeypatch.setattr(ha, "load_host_lines", lambda path: [DRIVER, RING])
    import benchmarks.trace_reduce as tr
    monkeypatch.setattr(tr, "load_events", lambda path, device=0: {"ops": OPS, "modules": MODS, "lines": {}})
    ctx = _ctx(tmp_path, _window_lines())
    got = {name: _read(m, name, ctx) for name in NEW}
    assert got["driver_throttle_ms"] == pytest.approx(111.0)
    assert got["driver_log_flush_ms"] == pytest.approx(2.0)
    assert got["ring_blocked_ms"] == pytest.approx(120.0) and got["augment_dispatch_ms"] == pytest.approx(12.0)
    assert got["setup_state_init_s"] == 14.0 and got["setup_first_step_s"] == 12.5
    # 2 traced steps: idle nanoseconds a step, as milliseconds
    assert got["idle_in_log_flush_ms"] == pytest.approx(30 / 2 * 1e-6)
    assert got["idle_in_dispatch_ms"] == pytest.approx(10 / 2 * 1e-6)
    assert got["idle_unattributed_ms"] == pytest.approx(10 / 2 * 1e-6)
    assert got["idle_in_data_wait_ms"] == 0.0  # a driver line, and no idle under that span
    kept = json.load(open(tmp_path / "host_spans.json"))
    assert kept["idle_ns"] == 50 and kept["idle_s_driver_by_ring"]["step"] == {"ring_blocked": 1e-8}
    assert kept["covered_ns"] == [0, 200] and kept["steps"] == 2
    assert kept["by_driver_leaf_ns"]["metrics_fetch"] == 20
    # the profile is read once: with it gone, the later metrics read the kept account
    monkeypatch.setattr(ha, "load_host_lines", None)
    assert _read(m, "idle_in_log_flush_ms", ctx) == pytest.approx(30 / 2 * 1e-6)


def test_a_program_without_the_spans_reads_nothing(tmp_path, monkeypatch):
    """The parent of PR 26 writes no `phase/*` field, no `setup` line and enters no `moco/` span:
    every new metric is left out of the line, none raises."""
    m = Manifest()
    (tmp_path / "metrics.jsonl").write_text('{"step": 1, "time": 0.0, "event": "preempt"}\n')
    os.makedirs(tmp_path / "profile")  # a profile with no xplane in it, then one with no moco/ span
    old = [{k: v for k, v in ln.items() if not k.startswith("phase/")} for ln in _window_lines()]
    ctx = _ctx(tmp_path, old)
    assert {name: _read(m, name, ctx) for name in NEW} == dict.fromkeys(NEW)
    monkeypatch.setattr(ha, "load_host_lines", lambda path: [])
    import benchmarks.trace_reduce as tr
    monkeypatch.setattr(tr, "load_events", lambda path, device=0: {"ops": OPS, "modules": MODS, "lines": {}})
    assert {name: _read(m, name, ctx) for name in NEW} == dict.fromkeys(NEW)
    assert json.load(open(tmp_path / "host_spans.json"))["driver_line"] is False
    assert _read(m, "idle_unattributed_ms", {"train_lines": old}) is None  # a serve cell's ctx
