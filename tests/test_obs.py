"""Telemetry layer (moco_tpu/obs): tracer, sinks, probe, health
reductions, schema — plus the satellite regressions (batched device_get
on the logging path, multi-host print silencing, profiler reentrancy)."""

import json
import os
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu import obs
from moco_tpu.obs import health, schema, sinks
from moco_tpu.obs.stepstats import StepTimeProbe, memory_payload
from moco_tpu.obs.trace import Tracer


# -- span tracer ---------------------------------------------------------


def test_tracer_nesting_and_chrome_export(tmp_path):
    t = Tracer()
    with t.span("epoch", epoch=0):
        with t.span("data_wait"):
            pass
        with t.span("step", step=1):
            pass
    spans = t.snapshot()
    by_name = {s["name"]: s for s in spans}
    # children close before the parent -> parent is last; depth recorded
    assert [s["name"] for s in spans] == ["data_wait", "step", "epoch"]
    assert by_name["epoch"]["depth"] == 0
    assert by_name["data_wait"]["depth"] == 1
    # timestamp containment (what Perfetto renders nesting from)
    e = by_name["epoch"]
    for child in ("data_wait", "step"):
        c = by_name[child]
        assert e["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= e["ts"] + e["dur"] + 1e-3
    assert by_name["step"]["args"] == {"step": 1}

    path = t.export_chrome(str(tmp_path / "trace.json"))
    trace = json.load(open(path))
    names = {ev["name"] for ev in trace["traceEvents"] if ev.get("ph") == "X"}
    assert {"epoch", "data_wait", "step"} <= names
    # thread-name metadata events for Perfetto track labels
    assert any(ev.get("ph") == "M" for ev in trace["traceEvents"])


def test_tracer_span_survives_exception():
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    (s,) = t.snapshot()
    assert s["name"] == "boom" and s["error"] == "RuntimeError"


def test_tracer_threads_get_own_tracks(tmp_path):
    t = Tracer(jsonl_path=str(tmp_path / "spans.jsonl"))

    def worker():
        with t.span("producer_work"):
            pass

    th = threading.Thread(target=worker, name="producer")
    with t.span("main_work"):
        th.start()
        th.join()
    tids = {s["tid"] for s in t.snapshot()}
    assert len(tids) == 2
    # streaming JSONL got every span, even from the worker thread
    lines = [json.loads(l) for l in open(tmp_path / "spans.jsonl")]
    assert {l["name"] for l in lines} == {"producer_work", "main_work"}
    t.close()


def test_module_level_span_noop_without_tracer():
    assert obs.get_tracer() is None
    with obs.span("free"):  # must not raise, must not record anywhere
        pass
    obs.instant("marker")  # likewise


def test_set_tracer_install_and_restore():
    t = Tracer()
    prev = obs.set_tracer(t)
    try:
        with obs.span("wired"):
            pass
    finally:
        obs.set_tracer(prev)
    assert [s["name"] for s in t.snapshot()] == ["wired"]
    assert obs.get_tracer() is prev


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts enters and
    leaves per name and keeps the keyword arguments it was made with."""

    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        self.log.append(("enter", self.name, self.args))
        return self

    def __exit__(self, exc_type, exc, tb):
        self.log.append(("exit", self.name, exc_type))
        return False


@pytest.fixture
def annotations():
    _FakeAnnotation.log = []
    prev = obs.set_annotator(_FakeAnnotation)
    yield _FakeAnnotation.log
    assert obs.set_annotator(prev) is _FakeAnnotation


def test_annotator_entered_and_left_once_per_span(annotations):
    t = Tracer()
    with t.span("epoch", epoch=3):
        with t.span("step", step=7):
            pass
    # the profiler sees `moco/<name>` with the span's own arguments,
    # entered outermost first and left innermost first
    assert annotations == [
        ("enter", "moco/epoch", {"epoch": 3}),
        ("enter", "moco/step", {"step": 7}),
        ("exit", "moco/step", None),
        ("exit", "moco/epoch", None),
    ]


def test_annotator_left_when_the_body_raises(annotations):
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    assert annotations == [("enter", "moco/boom", {}), ("exit", "moco/boom", RuntimeError)]
    assert t.snapshot()[0]["error"] == "RuntimeError"  # and the span was still recorded


def test_annotator_never_sees_stamped_spans_instants_or_counters(annotations):
    t = Tracer()
    t.emit_span("serve_request", 1.0, 2.0, request="r1")  # rendered after the fact
    t.instant("checkpoint_committed")
    t.counter("prefetch_depth_live", depth=2)
    assert annotations == []
    assert t.totals() == {}  # none of them is a phase with a duration of its own


def test_module_level_span_with_annotator_and_no_tracer(annotations):
    assert obs.get_tracer() is None
    with obs.span("serve_embed", bucket=8) as cm:
        assert isinstance(cm, _FakeAnnotation)  # the annotation alone
    assert [a[:2] for a in annotations] == [
        ("enter", "moco/serve_embed"), ("exit", "moco/serve_embed"),
    ]


def test_span_seconds_and_totals_give_per_step_means():
    """A fake driver loop: the phase account of a log line is the
    difference of two `totals()` snapshots over the steps between them,
    and a span's own `seconds` is what the totals add up."""
    from moco_tpu.obs.stepstats import DRIVER_PHASES, phase_account

    t = Tracer()
    before = t.totals()
    waited = 0.0
    for step in range(4):
        with t.span("data_wait", step=step) as w:
            pass
        waited += w.seconds
        with t.span("step", step=step):
            pass
    with t.span("log_flush"):
        with t.span("metrics_fetch") as fetch:
            pass
    now = t.totals()
    assert now["data_wait"][0] == 4 and now["step"][0] == 4 and now["log_flush"][0] == 1
    assert now["data_wait"][1] == pytest.approx(waited)
    pay = phase_account(now, before, steps=4)
    assert pay["phase/steps"] == 4
    assert pay["phase/data_wait"] == pytest.approx(waited / 4)
    assert pay["phase/throttle_wait"] == 0.0  # a driver phase is on the line even at zero
    assert pay["phase/metrics_fetch"] == pytest.approx(fetch.seconds / 4)
    # a child is told from its parent by name; the parent's time holds it
    assert pay["phase/log_flush_host"] == pytest.approx(
        pay["phase/log_flush"] - pay["phase/metrics_fetch"]
    ) and pay["phase/log_flush_host"] >= 0.0
    assert {f"phase/{n}" for n in DRIVER_PHASES} <= set(pay)
    assert "phase/ring_blocked" not in pay  # no ring ran: its phases stay off the line
    # the next line starts from this snapshot and sees nothing of the old window
    again = phase_account(t.totals(), now, steps=2)
    assert again["phase/data_wait"] == 0.0 and again["phase/steps"] == 2
    assert schema.validate_line({"step": 4, "time": 1.0, **pay}) == []


def test_tracer_bounds_memory_not_stream(tmp_path):
    t = Tracer(jsonl_path=str(tmp_path / "s.jsonl"), max_spans=2)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert len(t.snapshot()) == 2  # memory bounded
    assert t._dropped == 3
    assert len(open(tmp_path / "s.jsonl").readlines()) == 5  # stream complete
    t.close()


# -- sinks ---------------------------------------------------------------


def test_jsonl_sink_batches_device_transfers(tmp_path, monkeypatch):
    """Satellite regression: N device-array metrics must cost ONE
    transfer, not N blocking per-field float() syncs."""
    calls = {"n": 0}
    real = sinks._DEVICE_GET

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(sinks, "_DEVICE_GET", counting)
    w = sinks.JsonlSink(str(tmp_path))
    payload = {f"m{i}": jnp.float32(i) for i in range(5)}
    payload["host_val"] = 1.25  # host values must not force a transfer
    w.write(3, payload)
    w.close()
    assert calls["n"] == 1
    rec = json.loads(open(w.path).read())
    assert rec["m4"] == 4.0 and rec["host_val"] == 1.25

    calls["n"] = 0
    w2 = sinks.JsonlSink(str(tmp_path), filename="h.jsonl")
    w2.write(1, {"a": 1.0, "b": 2})  # pure-host payload: zero transfers
    w2.close()
    assert calls["n"] == 0


def test_multisink_gathers_once_for_all_sinks(tmp_path, monkeypatch):
    calls = {"n": 0}
    real = sinks._DEVICE_GET

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(sinks, "_DEVICE_GET", counting)
    ms = sinks.build_sinks("jsonl,csv", str(tmp_path))
    ms.write(1, {f"m{i}": jnp.float32(i) for i in range(4)})
    ms.close()
    assert calls["n"] == 1  # one fetch upstream of the whole fan-out


def test_jsonl_sink_scrubs_arrays_and_nonfinite(tmp_path):
    w = sinks.JsonlSink(str(tmp_path))
    w.write(
        1,
        {
            "hist": np.array([1.0, float("nan"), 3.0]),
            "jarr": jnp.arange(3),
            "bad": float("inf"),
            "none": None,
        },
    )
    w.close()
    rec = schema.loads_strict(open(w.path).read())  # strict: no NaN literals
    assert rec["hist"] == [1.0, None, 3.0]
    assert rec["jarr"] == [0, 1, 2]
    assert rec["bad"] is None and rec["none"] is None


def test_csv_sink_grows_header(tmp_path):
    import csv as csvmod

    s = sinks.CsvSink(str(tmp_path))
    s.write(1, {"loss": 1.0})
    s.write(2, {"loss": 0.9, "ema_drift": 0.01, "queue_age_hist": [1, 0]})
    rows = list(csvmod.DictReader(open(s.path)))
    assert len(rows) == 2
    assert rows[0]["ema_drift"] == ""  # backfilled on rewrite
    assert rows[1]["ema_drift"] == "0.01"
    assert json.loads(rows[1]["queue_age_hist"]) == [1, 0]
    s.close()


def test_build_sinks_always_includes_jsonl(tmp_path):
    ms = sinks.build_sinks("csv", str(tmp_path))
    assert ms.primary is not None and ms.path.endswith("metrics.jsonl")
    ms.write(1, {"loss": 1.0})
    ms.close()
    assert os.path.exists(tmp_path / "metrics.jsonl")
    assert os.path.exists(tmp_path / "metrics.csv")


def test_build_sinks_unknown_name_raises(tmp_path):
    with pytest.raises(ValueError, match="unknown metric sink"):
        sinks.build_sinks("jsonl,grafana", str(tmp_path))


def test_register_sink_plugs_into_spec(tmp_path):
    seen = []

    class Capture(sinks.Sink):
        def __init__(self, workdir):
            pass

        def write(self, step, payload):
            seen.append((step, dict(payload)))

    sinks.register_sink("capture", Capture)
    try:
        ms = sinks.build_sinks("capture", str(tmp_path))
        ms.write(7, {"loss": 0.5})
        ms.close()
    finally:
        del sinks.SINK_REGISTRY["capture"]
    assert seen and seen[0][0] == 7


def test_secondary_sink_failure_never_kills_logging(tmp_path):
    class Broken(sinks.Sink):
        def write(self, step, payload):
            raise IOError("disk full")

    primary = sinks.JsonlSink(str(tmp_path))
    ms = sinks.MultiSink([primary, Broken()], primary=primary)
    ms.write(1, {"loss": 1.0})  # must not raise
    ms.close()
    assert json.loads(open(primary.path).read())["loss"] == 1.0


def test_tensorboard_sink_unavailable_raises_clearly(tmp_path):
    have_tb = True
    try:
        import tensorboardX  # noqa: F401
    except ImportError:
        try:
            import torch.utils.tensorboard  # noqa: F401
        except ImportError:
            have_tb = False
    if have_tb:
        pytest.skip("a tensorboard writer is installed here")
    with pytest.raises(RuntimeError, match="tensorboardX"):
        sinks.TensorBoardSink(str(tmp_path))


# -- prometheus ----------------------------------------------------------


def test_prometheus_sink_serves_text_format():
    s = sinks.PrometheusSink(port=0)  # ephemeral port
    try:
        s.write(5, {"loss": 1.5, "ema_drift/backbone": 0.01, "event": "stall"})
        s.write(6, {"loss": 1.25})
        body = s.render()
        assert "moco_loss 1.25" in body
        assert "moco_ema_drift_backbone 0.01" in body
        assert 'moco_events_total{kind="stall"} 1' in body
        assert "# TYPE moco_loss gauge" in body
        url = f"http://127.0.0.1:{s.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            served = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert served == body
        with pytest.raises(urllib.error.HTTPError):
            # deliberately-undeclared route: asserts the 404 path
            urllib.request.urlopen(
                f"http://127.0.0.1:{s.port}/other", timeout=5
            )  # mocolint: disable=JX016
    finally:
        s.close()


def test_prom_name_sanitization():
    assert sinks.prom_name("ema_drift/backbone") == "moco_ema_drift_backbone"
    assert sinks.prom_name("acc@1") == "moco_acc_1"
    assert sinks.prom_name("0weird") == "moco__0weird"


# -- multi-host console silencing ---------------------------------------


def test_progress_meter_silent_on_nonzero_process(capsys, monkeypatch):
    """Reference behavior (`main_moco.py:~L145`): non-master ranks print
    nothing; the formatted line is still returned for per-process use."""
    from moco_tpu.utils.metrics import AverageMeter, ProgressMeter, print0

    m = AverageMeter("Loss", ":.2f")
    m.update(1.0)
    p = ProgressMeter(10, [m], prefix="Epoch: [0]")

    monkeypatch.setattr(jax, "process_index", lambda: 1)
    line = p.display(3)
    print0("driver info line")
    assert capsys.readouterr().out == ""  # silent, but...
    assert "Loss" in line  # ...the line is still produced

    monkeypatch.setattr(jax, "process_index", lambda: 0)
    p.display(3)
    print0("driver info line")
    out = capsys.readouterr().out
    assert "Loss" in out and "driver info line" in out


# -- profiler reentrancy + windowed capture ------------------------------


class _FakeProfiler:
    """Stands in for jax.profiler: records start/stop calls and can be
    armed to raise on start (the dangling-trace failure mode)."""

    ProfileOptions = jax.profiler.ProfileOptions

    def __init__(self):
        self.calls = []
        self.options = []
        self.active = False

    def start_trace(self, logdir, profiler_options=None):
        self.options.append(profiler_options)
        if self.active:
            self.calls.append(("start_fail", logdir))
            raise RuntimeError("profiler already active")
        self.active = True
        self.calls.append(("start", logdir))

    def stop_trace(self):
        if not self.active:
            self.calls.append(("stop_fail",))
            raise RuntimeError("no active profiler")
        self.active = False
        self.calls.append(("stop",))


@pytest.fixture
def fake_profiler(monkeypatch):
    from moco_tpu.utils import metrics as um

    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    monkeypatch.setitem(um._profiler_state, "active", False)
    return fake


def test_profiler_trace_recovers_from_dangling_trace(fake_profiler):
    from moco_tpu.utils.metrics import profiler_trace

    # someone (a crashed previous region, another library) left a trace
    # running: start will raise once
    fake_profiler.active = True
    with profiler_trace("/tmp/prof"):
        assert fake_profiler.active  # our trace is running now
    assert not fake_profiler.active  # and was stopped
    # the dangler was stopped, then start retried and succeeded
    assert ("start_fail", "/tmp/prof") in fake_profiler.calls
    assert fake_profiler.calls[-2:] == [("start", "/tmp/prof"), ("stop",)]


def test_profiler_starts_without_the_python_tracer(fake_profiler):
    """`--profile-steps` / `--profile-dir` must give a trace whose steps
    take what untraced steps take: device events and the host's annotated
    spans, never the Python tracer (`start_trace`'s default)."""
    from moco_tpu.utils.metrics import ProfilerWindow, profiler_trace

    with profiler_trace("/tmp/prof"):
        pass
    w = ProfilerWindow("/tmp/w", 0, 1)
    w.on_step(0)
    w.close()
    assert len(fake_profiler.options) == 2
    for opts in fake_profiler.options:
        assert opts.python_tracer_level == 0 and opts.host_tracer_level == 1


def test_profiler_trace_reentrant_inner_is_noop(fake_profiler):
    from moco_tpu.utils.metrics import profiler_trace

    with profiler_trace("/tmp/a"):
        with profiler_trace("/tmp/b"):  # inner: no crash, no double-start
            pass
        assert fake_profiler.active  # inner exit didn't stop the outer
    assert not fake_profiler.active
    starts = [c for c in fake_profiler.calls if c[0] == "start"]
    assert len(starts) == 1


def test_profiler_window_captures_half_open_range(fake_profiler):
    from moco_tpu.utils.metrics import ProfilerWindow

    w = ProfilerWindow("/tmp/w", 2, 4)
    for step in range(6):
        w.on_step(step)
        if step < 2 or step >= 4:
            assert not fake_profiler.active
        else:
            assert fake_profiler.active
    w.close()
    assert [c[0] for c in fake_profiler.calls] == ["start", "stop"]


def test_profiler_window_close_stops_open_capture(fake_profiler):
    from moco_tpu.utils.metrics import ProfilerWindow

    w = ProfilerWindow("/tmp/w", 0, 100)
    w.on_step(0)
    assert fake_profiler.active
    w.close()  # early exit / preemption path
    assert not fake_profiler.active
    w.close()  # idempotent


def test_parse_profile_steps():
    from moco_tpu.utils.metrics import parse_profile_steps

    assert parse_profile_steps("10:20") == (10, 20)
    for bad in ("20:10", "5", "a:b", "-1:4"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


# -- step-time probe + memory gauges -------------------------------------


def test_step_probe_sampling_schedule_and_payload():
    p = StepTimeProbe(every=3)
    assert [p.should_sample(s) for s in range(6)] == [True, False, False, True, False, False]
    p.data_wait(0.25)
    p.dispatched(0.03)
    p.step_done(0.5)
    pay = p.payload()
    assert pay == {"t_data": 0.25, "t_step": 0.5}  # no sample yet
    p.device_block(0.4, step=4)
    pay = p.payload()
    assert pay["t_dispatch"] == 0.03 and pay["t_device"] == 0.4
    assert pay["t_probe_step"] == 4  # which step the repeated pair was sampled on
    disabled = StepTimeProbe(every=0)
    assert not any(disabled.should_sample(s) for s in range(10))


def test_memory_payload_schema_locked():
    pay = memory_payload()
    assert set(pay) == {"hbm_live_bytes", "hbm_peak_bytes", "hbm_headroom_bytes"}
    for k, v in pay.items():  # number on real backends, null on CPU hosts
        if k == "hbm_headroom_bytes":
            # headroom may legitimately be negative transiently (limit
            # accounting vs allocator high-water differences)
            assert v is None or isinstance(v, int)
        else:
            assert v is None or (isinstance(v, int) and v >= 0)


def test_tree_shard_bytes_counts_shards_not_replicas():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from moco_tpu.obs.stepstats import tree_shard_bytes
    from moco_tpu.parallel import create_mesh

    mesh = create_mesh(num_data=8)
    full = jnp.zeros((8, 128), jnp.float32)
    replicated = jax.device_put(full, NamedSharding(mesh, P()))
    sharded = jax.device_put(full, NamedSharding(mesh, P("data", None)))
    assert tree_shard_bytes({"a": replicated}) == 8 * 128 * 4
    assert tree_shard_bytes({"a": sharded}) == 8 * 128 * 4 // 8
    # plain numpy leaves count their full bytes
    assert tree_shard_bytes({"a": np.zeros((4,), np.float32)}) == 16


# -- health reductions (jit-compatible by construction) ------------------


def _toy_params(scale=1.0):
    return {
        "backbone": {"w": jnp.full((4, 4), scale), "b": jnp.zeros((4,))},
        "head": {"w": jnp.full((4, 2), scale)},
    }


def test_ema_drift_groups_and_global():
    out = jax.jit(health.ema_drift)(_toy_params(1.0), _toy_params(0.9))
    assert set(out) == {"ema_drift", "ema_drift/backbone", "ema_drift/head"}
    # identical trees -> zero drift
    zero = jax.jit(health.ema_drift)(_toy_params(1.0), _toy_params(1.0))
    assert float(zero["ema_drift"]) == 0.0
    # relative drift of 10% everywhere
    np.testing.assert_allclose(float(out["ema_drift"]), 0.1, rtol=1e-5)


def test_logit_stats_from_dense_matches_mask_computation():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 10)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 10, size=6).astype(np.int32))
    out = jax.jit(health.logit_stats_from_dense)(logits, labels)
    lg = np.asarray(logits)
    mask = np.ones_like(lg, bool)
    mask[np.arange(6), np.asarray(labels)] = False
    np.testing.assert_allclose(float(out["logit_neg_mean"]), lg[mask].mean(), rtol=1e-5)
    np.testing.assert_allclose(
        float(out["logit_neg_std"]), lg[mask].std(), rtol=1e-4
    )
    np.testing.assert_allclose(
        float(out["logit_pos_mean"]), lg[~mask].mean(), rtol=1e-5
    )


def test_feature_stats_detects_collapse():
    rng = np.random.default_rng(1)
    healthy = rng.normal(size=(64, 16)).astype(np.float32)
    healthy /= np.linalg.norm(healthy, axis=1, keepdims=True)
    collapsed = np.tile(healthy[:1], (64, 1))
    h = jax.jit(health.feature_stats)(jnp.asarray(healthy))
    c = jax.jit(health.feature_stats)(jnp.asarray(collapsed))
    assert float(h["feature_std"]) > 10 * float(c["feature_std"])
    assert float(c["feature_dim_active"]) == 0.0
    assert float(h["feature_dim_active"]) == 16.0


def test_queue_age_warmup_and_steady_state():
    f = jax.jit(health.queue_age, static_argnums=(1, 2))
    # steady state: K=64, B=16 -> 4 batches of ages 1..4
    out = f(jnp.int32(100), 64, 16)
    assert float(out["queue_age_mean"]) == 2.5
    assert float(out["queue_age_max"]) == 4.0
    np.testing.assert_allclose(np.asarray(out["queue_age_hist"]).sum(), 1.0, rtol=1e-6)
    # warmup: at step 2 the older slots are capped at the run's age
    out2 = f(jnp.int32(2), 64, 16)
    assert float(out2["queue_age_mean"]) == pytest.approx((1 + 2 + 2 + 2) / 4)
    # step 0: nothing enqueued yet, ages clamp to zero
    out0 = f(jnp.int32(0), 64, 16)
    assert float(out0["queue_age_mean"]) == 0.0


def test_health_summary_runs_fully_jitted():
    """The acceptance bullet's jit-compatibility proof: the whole bundle
    traces and lowers with no host round-trip (a float()/np call inside
    would throw TracerError at trace time)."""
    q = jnp.asarray(np.random.default_rng(2).normal(size=(8, 4)), jnp.float32)

    @jax.jit
    def bundle(params_q, params_k, q, pos, neg, step):
        return health.health_summary(
            params_q, params_k, q, pos, neg, step,
            num_negatives=64, global_batch=16,
        )

    out = bundle(
        _toy_params(1.0), _toy_params(0.95), q, q[:, 0], q @ q.T, jnp.int32(5)
    )
    for k, v in out.items():
        assert np.all(np.isfinite(np.asarray(v))), k
    assert {"ema_drift", "logit_pos_mean", "queue_age_mean", "feature_std"} <= set(out)


# -- schema --------------------------------------------------------------


def _good_train_line():
    return {
        "step": 5, "time": 1.0, "epoch": 0, "lr": 0.03, "loss": 1.0,
        "acc1": 50.0, "acc5": 90.0, "t_data": 0.1, "t_step": 0.5,
        "hbm_live_bytes": None, "hbm_peak_bytes": None,
        "ema_drift": 0.1, "ema_drift/backbone": 0.1,
        "logit_pos_mean": 3.0, "logit_neg_mean": -0.1,
        "queue_age_mean": 2.5, "queue_age_hist": [0.5, 0.5],
    }


def test_schema_accepts_driver_shapes():
    assert schema.validate_line(_good_train_line()) == []
    assert schema.validate_line({"step": 1, "time": 1.0, "event": "stall"}) == []
    assert schema.validate_line({"step": 1, "time": 1.0, "knn_top1": 88.0}) == []
    assert schema.validate_line(
        {"step": 1, "time": 1.0, "event": "nonfinite_loss", "nan_steps": 1}
    ) == []


def test_schema_rejects_bad_lines():
    assert schema.validate_line({"time": 1.0})  # no step
    line = _good_train_line()
    line.pop("lr")
    assert any("missing" in e for e in schema.validate_line(line))
    assert any(
        "unknown event" in e
        for e in schema.validate_line({"step": 1, "time": 1.0, "event": "gremlin"})
    )
    bad = _good_train_line()
    bad["io_retries"] = {"data.read": "three"}
    assert any("io_retries" in e for e in schema.validate_line(bad))
    bad2 = _good_train_line()
    bad2["ema_drift/backbone"] = "high"
    assert any("ema_drift/backbone" in e for e in schema.validate_line(bad2))


def test_schema_knows_the_phase_account_and_the_setup_line():
    line = _good_train_line()
    line.update({"phase/data_wait": 0.004, "phase/log_flush_host": 0.0007, "phase/steps": 10,
                 "t_dispatch": 0.05, "t_device": 0.1, "t_probe_step": 51})
    assert schema.validate_line(line) == []
    setup = {"step": 1, "time": 1.0, "epoch": 0, "event": "setup", "setup/backend_s": 0.01,
             "setup/state_init_s": 14.0, "setup/checkpoint_s": 0.2,
             "setup/pipeline_start_s": 8.0, "setup/first_step_s": 12.5}
    assert schema.validate_line(setup) == []
    for key, bad in (("phase/throttle_wait", "long"), ("phase/steps", None),
                     ("t_probe_step", 2.5), ("setup/first_step_s", "12 s")):
        broken = dict(line if not key.startswith("setup/") else setup, **{key: bad})
        assert any(key in e for e in schema.validate_line(broken)), key


def test_schema_rejects_nonfinite_literals():
    with pytest.raises(ValueError, match="non-finite"):
        schema.loads_strict('{"step": 1, "time": 1.0, "loss": NaN}')
    errors = schema.validate_lines(['{"step": 1, "time": 1.0, "loss": Infinity}'])
    assert errors and "unparseable" in errors[0]


def test_schema_validates_real_writer_output(tmp_path):
    """The writer and the schema lock each other: whatever JsonlSink
    emits for driver-shaped payloads must validate."""
    w = sinks.JsonlSink(str(tmp_path))
    w.write(1, {k: v for k, v in _good_train_line().items() if k not in ("step", "time")})
    w.write(2, {"epoch": 0, "event": "nonfinite_loss", "nan_steps": 1})
    w.write(3, {"epoch": 0, "knn_top1": 42.0})
    w.close()
    assert schema.validate_file(w.path) == []


# -- obs_report ----------------------------------------------------------


def test_obs_report_renders_from_writer_output(tmp_path):
    from tests.conftest import load_script

    w = sinks.JsonlSink(str(tmp_path))
    for s in range(1, 4):
        w.write(
            s,
            {
                "epoch": 0, "lr": 0.03, "loss": 2.0 / s, "acc1": 10.0 * s,
                "acc5": 20.0 * s, "t_data": 0.01, "t_step": 0.2,
                "hbm_live_bytes": None, "hbm_peak_bytes": None,
                "ema_drift": 0.01 * s, "logit_pos_mean": 3.0,
                "logit_neg_mean": -0.1, "queue_age_mean": 1.5,
                "io_retries": {"data.read": 2},
            },
        )
    w.write(4, {"epoch": 0, "event": "nonfinite_loss", "nan_steps": 1})
    w.close()
    t = Tracer()
    with t.span("epoch", epoch=0):
        pass
    t.export_chrome(str(tmp_path / "trace.json"))

    mod = load_script("obs_report.py")
    report = mod.render_report(w.path, str(tmp_path / "trace.json"))
    assert "Step-time breakdown" in report
    assert "ema_drift" in report and "0.01 -> 0.03" in report
    assert "io retries by site" in report
    assert "event @ step 4: nonfinite_loss" in report
    assert "`epoch`: " in report  # trace summary rendered
    # schema-clean input -> no violations section
    assert load_script("obs_report.py").main is not None


def test_obs_report_empty_file(tmp_path):
    from tests.conftest import load_script

    path = tmp_path / "metrics.jsonl"
    path.write_text("")
    report = load_script("obs_report.py").render_report(str(path))
    assert "nothing to report" in report
