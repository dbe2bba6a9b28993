"""Fleet observability layer (ISSUE 4): cross-host aggregation skew
math, comms bytes-moved formulas per collective, alert-rule firing
(including on injected utils/faults.py faults), heartbeats, trace
merging, the per-process sink satellites, and the schema extensions.

Runs under the 8-virtual-device CPU mesh (tests/conftest.py), following
the tests/test_multihost.py pattern of exercising cross-replica code on
a real mesh: collectives are real, processes are simulated (one host),
and the pure reductions are additionally tested on synthetic multi-host
matrices so the skew math is proven for fleets this box can't spawn.
"""

import json
import os
import socket

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from moco_tpu.obs import alerts as alerts_mod
from moco_tpu.obs import comms, schema, sinks
from moco_tpu.obs.alerts import AlertEngine, parse_rules
from moco_tpu.obs.trace import Tracer, set_tracer
from moco_tpu.obs.fleet import (
    FLEET_FIELDS,
    FleetAggregator,
    Heartbeat,
    read_heartbeats,
    reduce_stats,
)
from moco_tpu.parallel import create_mesh
from jax import shard_map


# -- fleet reduction (skew math on synthetic multi-host matrices) --------


# `reduce_stats` has two evaluations of one formula: jitted `jnp` (the
# cross-process collective) and numpy (one process reduces its own row
# on the host, PR 27). Every case runs under both.
def _reduce(backend, m, t_idx):
    m = np.asarray(m, np.float32)
    if backend == "numpy":
        with np.testing.suppress_warnings() as sup:
            sup.filter(RuntimeWarning)  # all-NaN columns
            return reduce_stats(m, t_idx, xp=np)
    return jax.jit(lambda s: reduce_stats(s, t_idx))(jnp.asarray(m))


BACKENDS = pytest.mark.parametrize("backend", ["jit", "numpy"])


@BACKENDS
def test_reduce_stats_min_mean_max_argmax(backend):
    # 3 hosts x 2 fields; t_step is column 1
    out = _reduce(backend, [[1.0, 2.0], [3.0, 4.0], [2.0, 6.0]], 1)
    np.testing.assert_allclose(np.asarray(out["min"]), [1.0, 2.0])
    np.testing.assert_allclose(np.asarray(out["mean"]), [2.0, 4.0])
    np.testing.assert_allclose(np.asarray(out["max"]), [3.0, 6.0])
    assert np.asarray(out["argmax"]).tolist() == [1, 2]
    # skew = (max - mean) / mean over t_step = (6 - 4) / 4
    np.testing.assert_allclose(float(out["straggler_skew"]), 0.5, rtol=1e-6)


@BACKENDS
def test_reduce_stats_uniform_fleet_has_zero_skew(backend):
    out = _reduce(backend, np.full((4, 3), 2.5), 0)
    np.testing.assert_allclose(float(out["straggler_skew"]), 0.0, atol=1e-6)


@BACKENDS
def test_reduce_stats_nan_aware(backend):
    """A host that can't report a field (NaN) must not poison the fleet
    stats; a field NO host reports stays NaN (-> null in the line)."""
    out = _reduce(backend, [[1.0, np.nan, np.nan], [np.nan, 4.0, np.nan]], 0)
    assert float(out["min"][0]) == 1.0 and float(out["max"][1]) == 4.0
    assert np.isnan(float(out["mean"][2]))  # nobody reported column 2
    # skew over a column with one reporter: max == mean -> 0
    np.testing.assert_allclose(float(out["straggler_skew"]), 0.0, atol=1e-6)


@pytest.mark.parametrize(
    "matrix,t_idx",
    [
        # a straggler, a half-reported column, an all-NaN column
        ([[0.1, 0.5, np.nan, 3.0], [0.4, 0.9, np.nan, np.nan], [0.2, 0.6, np.nan, 1.0]], 1),
        # the driver's own case: one row, hbm unknown
        ([[0.004, 0.177, 0.02, 0.05, 0.0, 0.0, np.nan]], 1),
        # t_step itself unknown on one host
        ([[1.0, np.nan], [2.0, 0.25], [0.5, 0.75]], 1),
    ],
    ids=["straggler_and_nan_columns", "one_row", "nan_in_t_step"],
)
def test_reduce_stats_numpy_equals_jitted(matrix, t_idx):
    """Same dtypes, same values, NaNs in the same places: the line's
    fields are what they were when the reduce ran on the device."""
    host, dev = _reduce("numpy", matrix, t_idx), jax.device_get(_reduce("jit", matrix, t_idx))
    assert set(host) == set(dev)
    for k in dev:
        assert np.asarray(host[k]).dtype == dev[k].dtype, k
        assert np.asarray(host[k]).shape == dev[k].shape, k
        np.testing.assert_allclose(np.asarray(host[k]), dev[k], rtol=1e-6, err_msg=k)


def _fleet_vector(f):
    return f.host_vector(
        t_data=0.1, t_step=0.5, dispatch_lag=0.02,
        io_retries=3, decode_failures=0, hbm_live=None,
    )


def test_fleet_aggregator_roundtrip_and_payload():
    f = FleetAggregator()
    assert f.num_hosts == 1  # single process, however many devices
    stats = f.gather(_fleet_vector(f))
    pay = f.payload(stats)
    assert pay["fleet_hosts"] == 1
    assert pay["straggler_skew"] == pytest.approx(0.0)
    # one host: min == mean == max; argmax names host 0
    assert pay["fleet/t_step_min"] == pay["fleet/t_step_max"] == pytest.approx(0.5)
    assert pay["fleet/io_retries_mean"] == pytest.approx(3.0)
    assert pay["fleet/t_step_argmax"] == 0
    # unknown hbm travels as NaN and scrubs to null at the sink
    assert np.isnan(pay["fleet/hbm_live_max"])
    rec = sinks.sanitize(pay)
    assert rec["fleet/hbm_live_max"] is None


@pytest.fixture
def tracer():
    """A tracer installed process-wide for the test, as `train()` does."""
    t = Tracer()
    prev = set_tracer(t)
    try:
        yield t
    finally:
        set_tracer(prev)


def test_one_process_gather_runs_no_device_program(monkeypatch, recwarn, tracer):
    """One process has nothing to gather: the payload is what the
    collective path gives for the one row, and getting it moves nothing
    to the device and runs nothing there (the caller is the driver's log
    flush, where a read of a fresh device program drains the queue)."""
    f = FleetAggregator()
    vec = _fleet_vector(f)
    before = f.payload(f._collective(vec.reshape(1, -1)))  # the path every gather took

    def refuse(*a, **k):
        raise AssertionError("a one-process gather touched the device")

    monkeypatch.setattr(f, "_reduce", refuse)
    monkeypatch.setattr(f, "_collective", refuse)
    with jax.transfer_guard("disallow_explicit"):  # a `device_put` too
        stats = f.gather(vec)
    assert not any(isinstance(v, jax.Array) for v in stats.values())
    assert "fleet_gather" not in tracer.totals()  # the span is the collective's
    after = sinks.sanitize(f.payload(stats))
    assert after == sinks.sanitize(before)
    assert not [w for w in recwarn.list if issubclass(w.category, RuntimeWarning)]


def test_collective_gather_runs_under_its_own_span(monkeypatch, tracer):
    """With more than one process the collective stays, as a child span
    of the flush, so a multi-host line shows the wait that is left."""
    f = FleetAggregator()
    vec = _fleet_vector(f)
    monkeypatch.setattr(f, "num_hosts", 2)  # take the multi-process branch
    called = []
    monkeypatch.setattr(f, "_collective", lambda row: called.append(row) or {"ok": True})
    with tracer.span("log_flush"):
        assert f.gather(vec) == {"ok": True}
    assert len(called) == 1 and called[0].shape == (1, len(FLEET_FIELDS))
    assert tracer.totals()["fleet_gather"][0] == 1
    by_name = {s["name"]: s for s in tracer.snapshot()}
    assert by_name["fleet_gather"]["depth"] == by_name["log_flush"]["depth"] + 1


def test_host_vector_rejects_unknown_field():
    f = FleetAggregator()
    with pytest.raises(ValueError, match="unknown fleet fields"):
        f.host_vector(t_step=1.0, gremlin=2.0)


def test_fleet_fields_include_issue_surface():
    for name in ("t_data", "t_step", "dispatch_lag", "io_retries",
                 "decode_failures", "hbm_live"):
        assert name in FLEET_FIELDS


# -- comms: analytic bytes-moved formulas per collective -----------------


def test_collective_bytes_formulas():
    b, n = 1024, 8
    assert comms.collective_bytes("all_gather", b, n) == b * 7
    assert comms.collective_bytes("all_to_all", b, n) == b * 7 // 8
    assert comms.collective_bytes("psum", b, n) == 2 * b * 7 // 8
    assert comms.collective_bytes("psum_scatter", b, n) == b * 7 // 8
    assert comms.collective_bytes("ppermute", b, n) == b
    assert comms.collective_bytes("broadcast", b, n) == b
    # host->device staging (the input wire): payload crosses once,
    # whatever the axis size — including the degenerate axis of 1
    assert comms.collective_bytes("device_put", b, n) == b
    assert comms.collective_bytes("device_put", b, 1) == b
    # size-1 axis moves nothing — except device_put, which is not a
    # ring collective (the payload crosses the PCIe/DMA wire once
    # regardless of any mesh axis)
    for kind in comms.COLLECTIVES:
        if kind != "device_put":
            assert comms.collective_bytes(kind, b, 1) == 0
    with pytest.raises(ValueError, match="unknown collective"):
        comms.collective_bytes("gossip", b, n)


def test_tree_bytes_counts_pytrees():
    tree = {"a": jnp.zeros((4, 4), jnp.float32), "b": jnp.zeros((8,), jnp.int32)}
    assert comms.tree_bytes(tree) == 4 * 4 * 4 + 8 * 4


def test_tag_records_ledger_inside_shard_map():
    comms.reset()
    mesh = create_mesh(num_data=8)

    def f(x):
        with comms.tag("t.gather", "all_gather", x, 8):
            return lax.all_gather(x, "data", tiled=True)

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False))
    fn(jnp.zeros((16, 4), jnp.float32))  # local shard: (2, 4) f32 = 32 B
    site = comms.snapshot()["t.gather"]
    assert site.operand_bytes == 32
    assert site.bytes_per_call == 32 * 7
    pay = comms.payload()
    assert pay["comms/t.gather"] == 32 * 7
    assert pay["comms/total"] == 32 * 7
    comms.reset()
    assert comms.payload() == {}


def test_tag_calls_per_step_scales_ring():
    comms.reset()
    with comms.tag("r.ring", "ppermute", jnp.zeros((4,), jnp.float32), 8, calls_per_step=8):
        pass
    site = comms.snapshot()["r.ring"]
    assert site.bytes_per_call == 16 and site.bytes_per_step == 16 * 8
    comms.reset()


@pytest.mark.parametrize(
    "shuffle,num_data,expected",
    [
        ("gather_perm", 8, ("shuffle.gather_images", "shuffle.gather_keys", "grad.psum")),
        ("a2a", 4, ("shuffle.a2a", "shuffle.a2a_unshuffle", "queue.enqueue_gather", "grad.psum")),
        ("none", 8, ("queue.enqueue_gather", "grad.psum")),
    ],
)
def test_train_step_registers_comms_sites(shuffle, num_data, expected):
    """One real train step over the mesh must leave the ISSUE's named
    collective sites in the ledger with non-zero analytic bytes."""
    from test_train_step import make_batch, setup, tiny_config

    comms.reset()
    config = tiny_config(shuffle=shuffle)
    _, _, _, state, step = setup(config, num_data=num_data)
    step(state, make_batch(), jax.random.key(1))
    ledger = comms.snapshot()
    for site in expected:
        assert site in ledger, f"missing comms site {site} (have {sorted(ledger)})"
        assert ledger[site].bytes_per_step > 0, site
    # the gradient psum moves the whole trainable tree twice (n-1)/n
    grads_bytes = ledger["grad.psum"].operand_bytes
    n = ledger["grad.psum"].axis_size
    assert ledger["grad.psum"].bytes_per_call == 2 * grads_bytes * (n - 1) // n
    comms.reset()


def test_ring_attention_registers_ppermute_site():
    from moco_tpu.parallel.ring_attention import ring_attention

    comms.reset()
    mesh = create_mesh(num_data=1, num_model=4)
    B, H, S, D = 1, 2, 16, 8

    def f(q, k, v):
        return ring_attention(q, k, v, "model", interpret=True, block_q=4, block_k=4)

    fn = jax.jit(
        shard_map(
            f,
            mesh=mesh,
            in_specs=(P(None, None, "model"), P(None, None, "model"), P(None, None, "model")),
            out_specs=P(None, None, "model"),
            check_vma=False,
        )
    )
    q = jnp.ones((B, H, S, D), jnp.float32)
    fn(q, q, q)
    site = comms.snapshot()["ring_attention.kv_ppermute"]
    # K + V local shards rotate once per ring step, n steps per call
    local_kv_bytes = 2 * B * H * (S // 4) * D * 4
    assert site.operand_bytes == local_kv_bytes
    assert site.calls_per_step == 4
    comms.reset()


def test_zero_registers_reduce_scatter_and_gather_sites():
    import dataclasses

    from moco_tpu.core import create_state, make_train_step, place_state
    from moco_tpu.utils.schedules import build_optimizer
    from test_train_step import IMG, make_batch, tiny_config, tiny_encoder

    comms.reset()
    config = tiny_config(shuffle="none")
    config = dataclasses.replace(
        config, parallel=dataclasses.replace(config.parallel, shard_weight_update=True)
    )
    mesh = create_mesh(num_data=8)
    enc = tiny_encoder()
    tx = build_optimizer(config.optim, steps_per_epoch=10)
    state = create_state(
        jax.random.key(0), config, enc, tx, jnp.zeros((1, IMG, IMG, 3)),
        zero_num_data=8,
    )
    step = make_train_step(config, enc, tx, mesh, state_template=state)
    state = place_state(state, mesh, zero=True)
    step(state, make_batch(), jax.random.key(1))
    ledger = comms.snapshot()
    assert ledger["zero.grad_reduce_scatter"].bytes_per_step > 0
    assert ledger["zero.params_all_gather"].bytes_per_step > 0
    comms.reset()


# -- alert engine --------------------------------------------------------


def test_parse_default_rules_and_extension():
    names = [r.name for r in parse_rules("default")]
    for expected in (
        "step_time_spike", "data_starvation", "straggler_skew_high",
        "ema_drift_runaway", "queue_stale", "nonfinite_loss", "stall",
        "heartbeat_loss",
    ):
        assert expected in names
    extended = parse_rules("default,threshold@name=my_rule:field=loss:value=9")
    assert "my_rule" in [r.name for r in extended]
    assert parse_rules("") == [] and parse_rules("none") == []


def test_parse_rules_rejects_bad_specs():
    with pytest.raises(ValueError, match="unknown alert rule kind"):
        parse_rules("vibes@name=x")
    with pytest.raises(ValueError, match="needs field="):
        parse_rules("threshold@name=x:value=1")
    with pytest.raises(ValueError, match="needs name="):
        parse_rules("threshold@field=loss:value=1")
    with pytest.raises(ValueError, match="duplicate"):
        parse_rules("event@name=x:event=stall,event@name=x:event=stall")


def test_spike_rule_needs_warmup_then_fires():
    eng = AlertEngine(parse_rules("spike@name=s:field=t_step:factor=3:window=8:warmup=4"))
    fired = []
    fired += eng.observe(0, {"t_step": 10.0})  # huge, but inside warmup
    for i in range(1, 6):
        fired += eng.observe(i, {"t_step": 0.1})
    assert fired == []  # warmup absorbed the compile-like first step
    fired += eng.observe(6, {"t_step": 0.9})
    assert [a["rule"] for a in fired] == ["s"]
    assert fired[0]["severity"] == "warn" and fired[0]["step"] == 6


def test_threshold_rule_fires_on_rising_edge_only():
    eng = AlertEngine(parse_rules("threshold@name=t:field=straggler_skew:value=0.5"))
    assert eng.observe(1, {"straggler_skew": 0.2}) == []
    assert len(eng.observe(2, {"straggler_skew": 0.8})) == 1
    assert eng.observe(3, {"straggler_skew": 0.9}) == []  # still over: no re-fire
    assert eng.observe(4, {"straggler_skew": 0.1}) == []  # recovered
    assert len(eng.observe(5, {"straggler_skew": 0.7})) == 1  # new edge


def test_ratio_rule_requires_consecutive_observations():
    eng = AlertEngine(
        parse_rules("ratio@name=starve:num=t_data:den=t_step:value=0.5:consecutive=3")
    )
    fired = []
    fired += eng.observe(1, {"t_data": 0.8, "t_step": 1.0})
    fired += eng.observe(2, {"t_data": 0.8, "t_step": 1.0})
    assert fired == []
    fired += eng.observe(3, {"t_data": 0.8, "t_step": 1.0})
    assert [a["rule"] for a in fired] == ["starve"]
    # a healthy step resets the streak
    eng.observe(4, {"t_data": 0.1, "t_step": 1.0})
    assert eng.observe(5, {"t_data": 0.8, "t_step": 1.0}) == []


def test_queue_staleness_uses_derived_wall_seconds():
    eng = AlertEngine(parse_rules("threshold@name=q:field=queue_stale_seconds:value=100"))
    # 30 steps of queue depth x 2 s/step = 60 s: fine
    assert eng.observe(1, {"queue_age_max": 30.0, "t_step": 2.0}) == []
    # 300 steps x 2 s/step = 600 s: stale
    assert len(eng.observe(2, {"queue_age_max": 300.0, "t_step": 2.0})) == 1


def test_event_rule_fires_on_injected_nan_event(tmp_path):
    """The chaos-harness wiring: a utils/faults.py-injected NaN loss
    produces a nonfinite_loss event payload; the default rules must turn
    it into an alerts.jsonl entry."""
    from moco_tpu.utils import faults

    eng = AlertEngine(parse_rules("default"), workdir=str(tmp_path))
    faults.install("nan@step=5")
    try:
        loss = faults.corrupt_loss(1.0, 5)
        assert loss != loss  # injected NaN
        fired = eng.observe(5, {"event": "nonfinite_loss", "nan_steps": 1})
    finally:
        faults.clear()
    assert [a["rule"] for a in fired] == ["nonfinite_loss"]
    eng.close()
    lines = [json.loads(l) for l in open(tmp_path / "alerts.jsonl")]
    assert lines[0]["rule"] == "nonfinite_loss" and lines[0]["step"] == 5


def test_spike_rule_fires_on_injected_stall(tmp_path, monkeypatch):
    """An injected utils/faults.py stall stretches t_step; the spike rule
    must flag it against the rolling median."""
    from moco_tpu.utils import faults

    sleeps = []
    monkeypatch.setattr(alerts_mod.time, "time", lambda: 0.0)
    import time as _time

    monkeypatch.setattr(_time, "sleep", lambda s: sleeps.append(s))
    faults.install("stall@step=20:seconds=5")
    eng = AlertEngine(
        parse_rules("spike@name=step_time_spike:field=t_step:factor=3:window=16:warmup=4"),
        workdir=str(tmp_path),
    )
    try:
        fired = []
        for step in range(10, 22):
            t0 = 0.1
            faults.maybe_stall(step)  # sleep is stubbed; record the injection
            if sleeps:
                t0 += sleeps.pop()
            fired += eng.observe(step, {"t_step": t0})
    finally:
        faults.clear()
    assert [a["rule"] for a in fired] == ["step_time_spike"]
    assert fired[0]["step"] == 20


def test_heartbeat_loss_rule_names_the_dead_host(tmp_path):
    Heartbeat(str(tmp_path), process_index=1).beat(step=7)
    eng = AlertEngine(
        parse_rules("heartbeat@name=hb:timeout=60:severity=fatal"),
        workdir=str(tmp_path), process_index=0,
    )
    now = read_heartbeats(str(tmp_path))[1]["time"]
    assert eng.observe(1, {}, now=now + 10) == []  # fresh
    fired = eng.observe(2, {}, now=now + 120)
    assert len(fired) == 1 and fired[0]["severity"] == "fatal"
    assert "process 1" in fired[0]["message"]
    # no re-fire while the host stays dead...
    assert eng.observe(3, {}, now=now + 180) == []
    # ...but a revival re-arms the rule
    Heartbeat(str(tmp_path), process_index=1).beat(step=9)
    now2 = read_heartbeats(str(tmp_path))[1]["time"]
    assert eng.observe(4, {}, now=now2 + 1) == []
    assert len(eng.observe(5, {}, now=now2 + 120)) == 1


# -- heartbeats ----------------------------------------------------------


def test_heartbeat_roundtrip_atomic(tmp_path):
    hb = Heartbeat(str(tmp_path), process_index=3, trace_wall_t0=123.5)
    hb.beat(step=42, epoch=2)
    beats = read_heartbeats(str(tmp_path))
    rec = beats[3]
    assert rec["step"] == 42 and rec["epoch"] == 2
    assert rec["trace_wall_t0"] == 123.5
    assert rec["host"] == socket.gethostname()
    assert not os.path.exists(hb.path + ".tmp")  # atomic replace cleaned up
    # junk files are skipped, not fatal
    (tmp_path / "heartbeat.pX.json").write_text("{not json")
    assert set(read_heartbeats(str(tmp_path))) == {3}


# -- trace merging -------------------------------------------------------


def _write_span_stream(path, process, names, t0_us=0.0):
    with open(path, "w") as f:
        for i, name in enumerate(names):
            f.write(json.dumps({
                "name": name, "ts": t0_us + i * 100.0, "dur": 50.0,
                "tid": 1, "thread": "MainThread", "depth": 0, "p": process,
            }) + "\n")


def test_trace_merge_one_track_per_host_with_clock_offsets(tmp_path):
    from tests.conftest import load_script

    _write_span_stream(tmp_path / "trace_events.jsonl", 0, ["epoch", "step"])
    _write_span_stream(tmp_path / "trace_events.p1.jsonl", 1, ["epoch", "step"])
    # host 1's tracer started 2 s after host 0 (wall anchors via heartbeats)
    Heartbeat(str(tmp_path), 0, trace_wall_t0=1000.0).beat(step=2)
    Heartbeat(str(tmp_path), 1, trace_wall_t0=1002.0).beat(step=2)

    tm = load_script("trace_merge.py")
    out = str(tmp_path / "merged_trace.json")
    summary = tm.merge_traces(str(tmp_path), out)
    assert set(summary["processes"]) == {0, 1}
    assert summary["unanchored"] == []
    trace = json.load(open(out))
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    # clock-offset correction: host 1's first span lands 2 s later
    first = {p: min(e["ts"] for e in xs if e["pid"] == p) for p in (0, 1)}
    assert first[1] - first[0] == pytest.approx(2e6)
    # one labeled track group per host
    names = {
        e["pid"]: e["args"]["name"]
        for e in trace["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert set(names) == {0, 1} and names[0].startswith("host 0")


def test_trace_merge_survives_missing_heartbeat(tmp_path):
    from tests.conftest import load_script

    _write_span_stream(tmp_path / "trace_events.jsonl", 0, ["step"])
    tm = load_script("trace_merge.py")
    summary = tm.merge_traces(str(tmp_path), str(tmp_path / "m.json"))
    assert summary["unanchored"] == [0]  # merged with zero offset, flagged


# -- sink satellites: per-process files + prometheus port/host -----------


def test_per_process_filename_derivation():
    assert sinks.per_process_filename("metrics.jsonl", 0) == "metrics.jsonl"
    assert sinks.per_process_filename("metrics.jsonl", 2) == "metrics.p2.jsonl"
    assert sinks.per_process_filename("metrics.csv", 1) == "metrics.p1.csv"
    assert sinks.derive_metrics_port(9090, 3) == 9093
    assert sinks.derive_metrics_port(0, 3) == 0  # disabled stays disabled


def test_build_sinks_per_process_files_dont_clobber(tmp_path):
    ms0 = sinks.build_sinks("jsonl,csv", str(tmp_path), process_index=0)
    ms2 = sinks.build_sinks("jsonl,csv", str(tmp_path), process_index=2)
    ms0.write(1, {"loss": 1.0})
    ms2.write(1, {"loss": 2.0})
    ms0.close()
    ms2.close()
    assert json.loads(open(tmp_path / "metrics.jsonl").read())["loss"] == 1.0
    assert json.loads(open(tmp_path / "metrics.p2.jsonl").read())["loss"] == 2.0
    assert os.path.exists(tmp_path / "metrics.csv")
    assert os.path.exists(tmp_path / "metrics.p2.csv")


def test_prometheus_port_shifted_by_process_and_host_passed(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        base = s.getsockname()[1]
    # process 1 binds base+1 (base itself stays free for "process 0")
    ms = sinks.build_sinks(
        "jsonl", str(tmp_path), metrics_port=base, metrics_host="127.0.0.1",
        process_index=1,
    )
    try:
        assert ms.prometheus is not None
        assert ms.prometheus.port == base + 1
        assert ms.prometheus.host == "127.0.0.1"
    finally:
        ms.close()


# -- obs_report: merged multi-process view --------------------------------


def _train_line(step, **extra):
    rec = {
        "epoch": 0, "lr": 0.03, "loss": 1.0, "acc1": 10.0, "acc5": 20.0,
        "t_data": 0.01, "t_step": 0.2,
    }
    rec.update(extra)
    return rec


def test_obs_report_merges_per_process_metrics(tmp_path):
    from tests.conftest import load_script

    w0 = sinks.JsonlSink(str(tmp_path))
    w0.write(1, _train_line(1, **{"straggler_skew": 0.1, "fleet_hosts": 2,
                                  "fleet/t_step_max": 0.3, "fleet/t_step_mean": 0.2,
                                  "fleet/t_step_argmax": 1,
                                  "comms/grad.psum": 1024, "comms/total": 1024}))
    w0.close()
    w1 = sinks.JsonlSink(str(tmp_path), filename="metrics.p1.jsonl")
    w1.write(1, _train_line(1))
    w1.close()
    Heartbeat(str(tmp_path), 0).beat(step=1)
    Heartbeat(str(tmp_path), 1).beat(step=1)

    rep = load_script("obs_report.py")
    paths = rep.metrics_paths_for(str(tmp_path))
    assert [os.path.basename(p) for p in paths] == ["metrics.jsonl", "metrics.p1.jsonl"]
    report = rep.render_report(paths, workdir=str(tmp_path))
    assert "2 per-process files" in report
    assert "## Fleet" in report and "straggler_skew" in report
    assert "## Comms" in report and "grad.psum" in report
    assert "host 0" in report and "host 1" in report


# -- schema extensions ---------------------------------------------------


def test_schema_accepts_fleet_and_comms_fields():
    line = {
        "step": 1, "time": 1.0, "epoch": 0, "lr": 0.03, "loss": 1.0,
        "acc1": 1.0, "acc5": 2.0,
        "straggler_skew": 0.2, "fleet_hosts": 4,
        "fleet/t_step_min": 0.1, "fleet/t_step_argmax": 3,
        "fleet/hbm_live_max": None,
        "comms/grad.psum": 1024, "comms/total": 2048,
    }
    assert schema.validate_line(line) == []
    alert_line = {
        "step": 2, "time": 1.0, "event": "alert", "alert": "step_time_spike",
        "severity": "warn", "alert/step_time_spike": 1,
    }
    assert schema.validate_line(alert_line) == []


def test_schema_rejects_bad_fleet_and_alert_values():
    bad = {"step": 1, "time": 1.0, "comms/grad.psum": None}
    assert any("comms/grad.psum" in e for e in schema.validate_line(bad))
    bad2 = {"step": 1, "time": 1.0, "fleet/t_step_min": "slow"}
    assert any("fleet/t_step_min" in e for e in schema.validate_line(bad2))
    bad3 = {"step": 1, "time": 1.0, "event": "alert", "severity": "whatever"}
    assert any("severity" in e for e in schema.validate_line(bad3))


def test_schema_validates_fleet_writer_output(tmp_path):
    """Writer and schema lock each other for the new fields too."""
    f = FleetAggregator()
    stats = f.gather(f.host_vector(t_step=0.5, t_data=0.1))
    w = sinks.JsonlSink(str(tmp_path))
    payload = _train_line(1)
    payload.update(f.payload(stats))
    payload.update({"comms/grad.psum": 123, "comms/total": 123})
    w.write(1, payload)
    w.close()
    assert schema.validate_file(w.path) == []
