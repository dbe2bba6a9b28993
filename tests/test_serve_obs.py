"""Request-scoped serving observability (ISSUE 10): reqtrace stamps +
waterfalls, SLO burn-rate math, the flight recorder, batcher latency
accounting under saturation, the slow@ fault grammar, Prometheus
histogram export with exemplars, schema validators, serve-replica
trace merging, the obs_report Serving section, and the end-to-end
chaos capture (injected slow stage -> burn alert -> attributed flight
dump)."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from moco_tpu.obs.flight import FlightRecorder, read_flight_dumps
from moco_tpu.obs.reqtrace import RequestIdAllocator, RequestTrace
from moco_tpu.obs.slo import SLOBurnTracker, serve_alert_spec
from moco_tpu.serve.batcher import ContinuousBatcher
from moco_tpu.utils import faults

from tests.conftest import load_script


# -- reqtrace ------------------------------------------------------------


def test_request_trace_waterfall_and_stage_sums():
    tr = RequestTrace("r0-000042", rows=3, replica=0)
    t0 = tr.t0
    tr.stamp("ingress", t0, t0 + 0.001)
    tr.stamp("queue_wait", t0 + 0.001, t0 + 0.011)
    tr.stamp("engine_execute", t0 + 0.011, t0 + 0.031)
    tr.stamp("engine_execute", t0 + 0.031, t0 + 0.041)  # repeated: sums
    ms = tr.stage_ms()
    assert ms["queue_wait"] == pytest.approx(10.0, abs=1e-6)
    assert ms["engine_execute"] == pytest.approx(30.0, abs=1e-6)
    assert tr.total_ms() == pytest.approx(41.0, abs=1e-6)
    wf = tr.waterfall()
    assert wf["request_id"] == "r0-000042" and wf["rows"] == 3
    assert [s["stage"] for s in wf["stages"]] == [
        "ingress", "queue_wait", "engine_execute", "engine_execute",
    ]
    assert wf["stages"][1]["start_ms"] == pytest.approx(1.0, abs=1e-3)


def test_request_trace_backdated_ingress():
    """The HTTP handler builds the trace AFTER reading the body; t0
    backdates so the ingress stage never starts before the origin."""
    t_arrival = time.perf_counter()
    time.sleep(0.005)
    tr = RequestTrace("r1-000000", rows=1, replica=1, t0=t_arrival)
    tr.stamp("ingress", t_arrival, time.perf_counter())
    wf = tr.waterfall()
    assert wf["stages"][0]["start_ms"] == 0.0
    assert wf["stages"][0]["dur_ms"] >= 5.0


def test_request_ids_unique_and_replica_scoped():
    ids = RequestIdAllocator(replica=2)
    seen = []
    lock = threading.Lock()

    def grab():
        got = [ids.new_trace().req_id for _ in range(200)]
        with lock:
            seen.extend(got)

    threads = [threading.Thread(target=grab) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(seen)) == 800
    assert all(r.startswith("r2-") for r in seen)


# -- SLO burn rate -------------------------------------------------------


def test_burn_rate_math_multi_window():
    t = SLOBurnTracker(slo_ms=100, objective=0.9, windows=(10, 100))
    # 20 requests over 2s: every 4th violates -> bad fraction 0.25
    for i in range(20):
        t.record(i % 4 != 0, now=1000.0 + i * 0.1)
    rates = t.burn_rates(now=1002.0)
    assert rates[10] == pytest.approx(0.25 / 0.1)
    assert rates[100] == pytest.approx(0.25 / 0.1)


def test_burn_rate_window_eviction_and_empty():
    t = SLOBurnTracker(slo_ms=100, objective=0.99, windows=(10,))
    assert t.burn_rates(now=0.0) == {10: None}  # silent service: no burn
    for i in range(10):
        t.record(False, now=100.0 + i)  # all violations
    assert t.burn_rates(now=109.0)[10] == pytest.approx(1.0 / 0.01)
    # 200s later every bucket aged out of the window
    assert t.burn_rates(now=300.0) == {10: None}
    payload = t.payload(now=109.0)
    assert payload["serve/slo_objective"] == 0.99
    assert payload["serve/burn_rate_10s"] == pytest.approx(100.0)


def test_burn_tracker_rejects_bad_config():
    with pytest.raises(ValueError):
        SLOBurnTracker(100, objective=1.0)
    with pytest.raises(ValueError):
        SLOBurnTracker(100, windows=())
    with pytest.raises(ValueError):
        SLOBurnTracker(100, windows=(10, 10))


def test_serve_alert_spec_parses_and_tightens():
    from moco_tpu.obs.alerts import parse_rules

    rules = parse_rules(serve_alert_spec(250.0, windows=(30, 300)))
    by_name = {r.name: r for r in rules}
    assert by_name["slo_burn_fast"].field == "serve/burn_rate_30s"
    assert by_name["slo_burn_slow"].field == "serve/burn_rate_300s"
    assert by_name["slo_p99_over"].value == 250.0
    # without an slo the p99 rule drops out
    assert "slo_p99_over" not in {
        r.name for r in parse_rules(serve_alert_spec(None))
    }


def test_alert_engine_on_fire_hook(tmp_path):
    from moco_tpu.obs.alerts import AlertEngine, parse_rules

    fired = []
    eng = AlertEngine(
        parse_rules("threshold@name=hot:field=x:value=1"),
        workdir=str(tmp_path),
        on_fire=fired.append,
    )
    eng.observe(1, {"x": 0.5})
    assert not fired
    eng.observe(2, {"x": 2.0})
    assert [a["rule"] for a in fired] == ["hot"]
    eng.close()


# -- flight recorder -----------------------------------------------------


def _wf(rid, total_ms, stage="engine_execute"):
    return {
        "request_id": rid,
        "replica": 0,
        "rows": 1,
        "wall_t0": 0.0,
        "total_ms": total_ms,
        "stages": [{"stage": stage, "start_ms": 0.0, "dur_ms": total_ms}],
    }


def test_flight_recorder_ring_bounds_and_slowest(tmp_path):
    fr = FlightRecorder(max_requests=4, max_metrics=2)
    for i in range(10):
        fr.record_request(_wf(f"r0-{i:06d}", float(i)))
    fr.record_metrics(1, {"serve/qps": 1.0})
    fr.record_metrics(2, {"serve/qps": 2.0})
    fr.record_metrics(3, {"serve/qps": 3.0})
    snap = fr.snapshot(top_n=2)
    assert snap["requests_recorded"] == 4  # ring evicted the rest
    assert [r["request_id"] for r in snap["slowest"]] == ["r0-000009", "r0-000008"]
    assert [m["serve/qps"] for m in snap["metrics"]] == [2.0, 3.0]
    path = fr.dump(str(tmp_path), reason="test", extra={"k": 1})
    assert os.path.basename(path).startswith("flight_")
    rec = json.load(open(path))
    assert rec["reason"] == "test" and rec["k"] == 1
    assert len(rec["requests"]) == 4
    # two dumps in the same second stay distinct files
    path2 = fr.dump(str(tmp_path), reason="again")
    assert path2 != path
    loaded = read_flight_dumps(str(tmp_path))
    assert [os.path.basename(p) for p, _ in loaded] == sorted(
        os.path.basename(p) for p, _ in loaded
    )
    assert loaded[-1][1]["reason"] == "again"


# -- batcher latency accounting (the ISSUE-10 satellite) ----------------


def _echo(images, wn, *, stages=None, engine_s=0.0):
    if engine_s:
        t0 = time.perf_counter()
        time.sleep(engine_s)
        if stages is not None:
            stages["engine_execute"] = (
                stages.get("engine_execute", 0.0) + time.perf_counter() - t0
            )
    emb = np.arange(images.shape[0], dtype=np.float32)[:, None]
    return {"embedding": emb}, [(images.shape[0], images.shape[0])]


def test_latency_accounting_sums_to_wall_under_saturation():
    """Every request's stage durations must sum to within eps of its
    measured wall latency, and under saturation with a slowed engine
    the queue_wait stage must dominate."""
    engine_s = 0.05

    def run_batch(images, wn, *, stages=None):
        return _echo(images, wn, stages=stages, engine_s=engine_s)

    b = ContinuousBatcher(run_batch, max_batch=4, slo_ms=10_000, reqtrace=True)
    try:
        # a burst of 2-row requests: max_batch 4 -> 2 requests/flush,
        # 10 serial flushes at ~50ms each; later requests queue behind
        # earlier flushes, so queue_wait accumulates
        futs = [b.submit(np.zeros((2, 4, 4, 3), np.uint8)) for _ in range(20)]
        for f in futs:
            f.result(30)
        total_queue = total_engine = 0.0
        for f in futs:
            assert f.trace is not None
            lat_ms = f.latency_s * 1e3
            stage_ms = f.trace.stage_ms()
            ssum = sum(stage_ms.values())
            # eps: scheduling gaps between dequeue and flush / between
            # run end and scatter — small next to a 50ms engine stage
            assert abs(ssum - lat_ms) <= max(0.15 * lat_ms, 25.0), (
                f"{f.trace.req_id}: stages {ssum:.1f}ms vs wall {lat_ms:.1f}ms "
                f"({stage_ms})"
            )
            total_queue += stage_ms.get("queue_wait", 0.0)
            total_engine += stage_ms.get("engine_execute", 0.0)
        # saturation: waiting for earlier flushes dwarfs own execution
        assert total_queue > 2.0 * total_engine, (total_queue, total_engine)
    finally:
        b.close()


def test_batcher_stage_split_lands_in_metrics_payload():
    def run_batch(images, wn, *, stages=None):
        return _echo(images, wn, stages=stages, engine_s=0.01)

    b = ContinuousBatcher(run_batch, max_batch=8, slo_ms=1000, reqtrace=True)
    try:
        b.submit(np.zeros((8, 4, 4, 3), np.uint8)).result(10)
        p = b.metrics.payload()
        assert p["serve/trace_requests"] == 1
        assert p["serve/trace_engine_execute_ms"] >= 10.0
        assert p["serve/trace_queue_wait_ms"] >= 0.0
        assert p["serve/p99_exemplar"].startswith("r0-")
        assert p["serve/p99_exemplar_ms"] > 0
        # the window resets: a second payload with no traffic carries no
        # stage means and a null exemplar
        p2 = b.metrics.payload()
        assert "serve/trace_engine_execute_ms" not in p2
        assert p2["serve/p99_exemplar"] is None
    finally:
        b.close()


def test_batcher_tracing_off_is_traceless():
    b = ContinuousBatcher(_echo, max_batch=4, slo_ms=1000)  # reqtrace off
    try:
        fut = b.submit(np.zeros((1, 4, 4, 3), np.uint8))
        fut.result(10)
        assert fut.trace is None
        p = b.metrics.payload()
        assert p["serve/p99_exemplar"] is None
        assert not any(k.startswith("serve/trace_") for k in p)
        # the latency histogram still counts (it needs no per-request id)
        assert p["serve/latency_hist"]["count"] == 1
    finally:
        b.close()


def test_batcher_modes_and_stages_contracts_coexist():
    """A 3-positional-arg callable gets modes; the keyword-only stages
    param must NOT be mistaken for the modes contract (and vice versa)."""
    seen = {}

    def three_arg(images, wn, modes, *, stages=None):
        seen["modes"] = modes
        seen["stages_passed"] = stages is not None
        return _echo(images, wn)

    b = ContinuousBatcher(three_arg, max_batch=2, slo_ms=500, reqtrace=True)
    try:
        b.submit(
            np.zeros((2, 4, 4, 3), np.uint8), want_neighbors=True, mode="ivf"
        ).result(10)
        assert seen["modes"] == ("ivf",)
        assert seen["stages_passed"] is True
    finally:
        b.close()

    def keyword_stages_only(images, wn, *, stages=None):
        seen["kw_only"] = True
        assert not isinstance(stages, tuple)  # never the modes tuple
        return _echo(images, wn)

    b2 = ContinuousBatcher(keyword_stages_only, max_batch=2, slo_ms=500, reqtrace=True)
    try:
        b2.submit(np.zeros((1, 4, 4, 3), np.uint8)).result(10)
        assert seen["kw_only"]
    finally:
        b2.close()


# -- slow@ fault grammar -------------------------------------------------


def test_slow_fault_grammar_parses():
    plan = faults.FaultPlan("slow@site=serve.engine_execute:ms=250:at=2:times=3")
    assert plan.describe() == [
        ("slow", {"site": "serve.engine_execute", "ms": 250.0, "at": 2, "times": 3})
    ]
    with pytest.raises(ValueError):
        faults.FaultPlan("slow@site=x:bogus=1")


def test_slow_fault_fires_at_the_right_calls():
    faults.install("slow@site=serve.test_stage:ms=40:at=2:times=2")
    try:
        durs = []
        for _ in range(4):
            t0 = time.perf_counter()
            faults.maybe_slow("serve.test_stage")
            durs.append(time.perf_counter() - t0)
        assert durs[0] < 0.02  # call 1: clean
        assert durs[1] >= 0.04 and durs[2] >= 0.04  # calls 2-3: slowed
        assert durs[3] < 0.02  # call 4: clean again
        # other sites never sleep
        t0 = time.perf_counter()
        faults.maybe_slow("serve.other")
        assert time.perf_counter() - t0 < 0.02
    finally:
        faults.clear()


# -- Prometheus histogram + exemplar ------------------------------------


def test_prometheus_renders_cumulative_histogram_with_exemplar():
    from moco_tpu.obs.sinks import PrometheusSink

    sink = PrometheusSink(port=0)
    try:
        sink.write(1, {
            "serve/qps": 5.0,
            "serve/latency_hist": {
                "le": [10.0, 100.0, 1000.0],
                "counts": [3, 2, 1, 1],  # per-bucket; +Inf slot last
                "sum": 1500.0,
                "count": 7,
                "exemplar": {"request_id": "r0-000007", "latency_ms": 42.0},
            },
        })
        body = sink.render()
        assert "# TYPE moco_serve_latency_ms histogram" in body
        assert 'moco_serve_latency_ms_bucket{le="10"} 3' in body
        # cumulative counts, exemplar attached to the bucket it falls in
        assert (
            'moco_serve_latency_ms_bucket{le="100"} 5 '
            '# {request_id="r0-000007"} 42' in body
        )
        assert 'moco_serve_latency_ms_bucket{le="1000"} 6' in body
        assert 'moco_serve_latency_ms_bucket{le="+Inf"} 7' in body
        assert "moco_serve_latency_ms_sum 1500.0" in body
        assert "moco_serve_latency_ms_count 7" in body
        assert "moco_serve_qps 5.0" in body  # gauges still render
        # a scrape parses: every non-comment line is "name{...} value"
        for line in body.strip().splitlines():
            if line.startswith("#"):
                continue
            name_part = line.split(" # ")[0]
            assert len(name_part.rsplit(" ", 1)) == 2, line
    finally:
        sink.close()


# -- schema --------------------------------------------------------------


def test_schema_validates_new_serve_fields():
    from moco_tpu.obs import schema

    good = {
        "step": 1,
        "time": 0.0,
        "serve/burn_rate_60s": 2.5,
        "serve/burn_rate_600s": None,
        "serve/slo_objective": 0.99,
        "serve/trace_engine_execute_ms": 12.5,
        "serve/trace_requests": 4,
        "serve/p99_exemplar": "r0-000123",
        "serve/p99_exemplar_ms": 812.0,
        "serve/latency_hist": {
            "le": [1.0, 10.0],
            "counts": [1, 2, 0],
            "sum": 21.0,
            "count": 3,
        },
    }
    assert schema.validate_line(good) == []
    # exemplar is a string INSIDE the numeric serve/ family: the
    # explicit validator must win over the prefix check
    bad_exemplar = dict(good, **{"serve/p99_exemplar": 17})
    assert schema.validate_line(bad_exemplar)
    # burn rates: longest-prefix validator (non-negative) shadows serve/
    bad_burn = dict(good, **{"serve/burn_rate_60s": -1.0})
    assert schema.validate_line(bad_burn)
    bad_stage = dict(good, **{"serve/trace_scatter_ms": -0.1})
    assert schema.validate_line(bad_stage)
    for mutilation in (
        {"le": [10.0, 1.0], "counts": [1, 1, 1], "sum": 1.0, "count": 3},  # unsorted
        {"le": [1.0], "counts": [1], "sum": 1.0, "count": 1},  # missing +Inf slot
        {"le": [1.0], "counts": [1, -1], "sum": 1.0, "count": 0},  # negative
        "nope",
    ):
        assert schema.validate_line(
            dict(good, **{"serve/latency_hist": mutilation})
        ), mutilation


# -- trace merge: serving replicas join the timeline --------------------


def test_trace_merge_aligns_serve_replica_tracks(tmp_path):
    tm = load_script("trace_merge.py")
    wd = str(tmp_path)
    # training process 0: anchor at wall 1000.0
    with open(os.path.join(wd, "trace_events.jsonl"), "w") as f:
        f.write(json.dumps({"name": "step", "ts": 0.0, "dur": 5.0, "tid": 1,
                            "thread": "main", "p": 0}) + "\n")
    with open(os.path.join(wd, "heartbeat.p0.json"), "w") as f:
        json.dump({"process": 0, "host": "trainhost", "time": 1000.0,
                   "trace_wall_t0": 1000.0}, f)
    # serve replica 1: started 2.5s later; request span on a lane
    with open(os.path.join(wd, "trace_events.s1.jsonl"), "w") as f:
        f.write(json.dumps({"name": "request", "ts": 10.0, "dur": 3.0, "tid": 1,
                            "thread": "requests-0", "p": 1,
                            "args": {"request_id": "r1-000000"}}) + "\n")
    with open(os.path.join(wd, "heartbeat.s1.json"), "w") as f:
        json.dump({"process": 1, "role": "serve", "host": "servehost",
                   "time": 1002.5, "trace_wall_t0": 1002.5}, f)
    out = os.path.join(wd, "merged.json")
    summary = tm.merge_traces(wd, out)
    assert summary["serve_replicas"][1]["offset_us"] == pytest.approx(2.5e6)
    merged = json.load(open(out))
    by_pid = {}
    for ev in merged["traceEvents"]:
        by_pid.setdefault(ev["pid"], []).append(ev)
    assert 0 in by_pid and tm.SERVE_PID_BASE + 1 in by_pid
    req = next(e for e in by_pid[tm.SERVE_PID_BASE + 1] if e.get("ph") == "X")
    assert req["ts"] == pytest.approx(2.5e6 + 10.0)  # clock-aligned
    name_meta = next(
        e for e in by_pid[tm.SERVE_PID_BASE + 1] if e.get("ph") == "M"
        and e["name"] == "process_name"
    )
    assert "serve replica 1" in name_meta["args"]["name"]
    assert merged["otherData"]["serve_replicas"] == [1]


# -- obs_report: the Serving section ------------------------------------


def test_obs_report_serving_section(tmp_path):
    rep = load_script("obs_report.py")
    wd = str(tmp_path)
    lines = []
    for i in range(6):
        lines.append({
            "step": i + 1, "time": 100.0 + i,
            "serve/qps": 10.0 + i, "serve/p99_ms": 90.0 + i,
            "serve/p50_ms": 40.0, "serve/requests": 10 * (i + 1),
            "serve/slo_ms": 100.0, "serve/slo_objective": 0.99,
            "serve/slo_violations": i,
            "serve/burn_rate_60s": 0.5 * i,
            "serve/trace_queue_wait_ms": 30.0,
            "serve/trace_engine_execute_ms": 55.0,
            "serve/trace_scatter_ms": 5.0,
            "serve/p99_exemplar": f"r0-{i:06d}",
        })
    with open(os.path.join(wd, "metrics.jsonl"), "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    fr = FlightRecorder()
    fr.record_request(_wf("r0-000005", 500.0))
    fr.dump(wd, reason="alert:slo_burn_fast")
    report = rep.render_report(
        os.path.join(wd, "metrics.jsonl"), workdir=wd
    )
    assert "## Serving" in report
    assert "stage waterfall" in report
    assert "engine_execute" in report
    assert "serve/burn_rate_60s" in report
    assert "r0-000005" in report  # slowest request from the flight dump
    assert "p99 exemplar" in report


def test_obs_report_fleet_tracing_section(tmp_path):
    rep = load_script("obs_report.py")
    wd = str(tmp_path)
    lines = []
    for i in range(4):
        lines.append({
            "step": i + 1, "time": 100.0 + i,
            "fleet_serve/requests": 20 * (i + 1),
            "fleet_serve/slo_ms": 1000.0, "fleet_serve/p99_ms": 400.0,
            "fleet_serve/hedges": 6, "fleet_serve/hedge_wins": 3,
            "fleet_serve/hedge_wasted_ms": 1234.5,
            "fleet_serve/retries": 2,
            "fleet_serve/critpath_router_admission_ms": 1.0,
            "fleet_serve/critpath_net_send_ms": 4.0,
            "fleet_serve/critpath_replica_engine_execute_ms": 80.0,
            "fleet_serve/critpath_retry_failed_ms": 12.0,
            "fleet_serve/critpath_router_other_ms": 3.0,
        })
    with open(os.path.join(wd, "metrics.jsonl"), "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    # a router flight dump with one stitched multi-hop waterfall
    fr = FlightRecorder()
    fr.record_request({
        "trace_id": "ab" * 16, "request_id": "r2-000009", "status": 200,
        "total_ms": 950.0,
        "attempts": [{"outcome": "failed"}, {"outcome": "ok", "winner": True}],
        "stages": [
            {"stage": "router_admission", "start_ms": 0.0, "dur_ms": 1.0},
            {"stage": "replica_engine_execute", "start_ms": 10.0, "dur_ms": 900.0},
        ],
    })
    fr.dump(wd, reason="alert:slo_burn_fast", extra={"role": "router"})
    report = rep.render_report(os.path.join(wd, "metrics.jsonl"), workdir=wd)
    assert "## Fleet tracing" in report
    assert "critical path" in report
    assert "replica_engine_execute" in report
    assert "win rate 50%" in report
    assert "retries: 2" in report
    assert "slowest distributed waterfalls" in report
    assert "ab" * 16 in report and "r2-000009" in report
    # the router dump must NOT leak into the per-replica Serving section
    assert "slowest requests (flight recorder" not in report


# -- end-to-end chaos: slow stage -> burn alert -> attributed dump ------


class _TinyEngine:
    """Engine-shaped stub with the REAL fault hook discipline: the
    injected slow@serve.engine_execute sleep happens inside the stage's
    own timing window, like InferenceEngine._run_bucket."""

    buckets = (1, 4)
    recompiles_after_warmup = 0
    num_features = 4
    image_size = 4

    def warmup(self):
        pass

    def embed(self, images, stages=None):
        t0 = time.perf_counter()
        faults.maybe_slow("serve.engine_execute")
        emb = np.ones((images.shape[0], 4), np.float32) / 2.0
        if stages is not None:
            stages["engine_execute"] = (
                stages.get("engine_execute", 0.0) + time.perf_counter() - t0
            )
        return emb, [(images.shape[0], images.shape[0])]


def test_server_chaos_flight_capture(tmp_path):
    """The serve_smoke SLO leg's story at unit scale: an injected
    slow@serve.engine_execute request trips the burn-rate alert and the
    flight dump attributes its tail to exactly that stage."""
    from moco_tpu.obs import schema
    from moco_tpu.obs.sinks import JsonlSink
    from moco_tpu.serve.server import ServeServer

    wd = str(tmp_path)
    sink = JsonlSink(wd)
    server = ServeServer(
        _TinyEngine(), index=None, port=0, slo_ms=100.0,
        sink=sink, metrics_flush_s=0.1, workdir=wd,
        slo_objective=0.9, burn_windows=(10, 60),
        alert_spec="threshold@name=slo_burn_fast:field=serve/burn_rate_10s:value=1.0",
    )
    imgs = np.zeros((2, 4, 4, 3), np.uint8)

    def post(path="/embed"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}", data=imgs.tobytes(),
            headers={"X-Image-Shape": "2,4,4,3"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    try:
        for _ in range(10):
            post()
        faults.install("slow@site=serve.engine_execute:ms=400:at=1:times=2")
        try:
            slowed = [post()["request_id"] for _ in range(2)]
        finally:
            faults.clear()
        for _ in range(4):
            post()
        deadline = time.time() + 8.0
        while time.time() < deadline and not read_flight_dumps(wd):
            time.sleep(0.05)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/debug/flight", timeout=10
        ) as r:
            debug = json.loads(r.read())
    finally:
        server.close()
        sink.close()
    from moco_tpu.obs.alerts import read_alerts

    alerts = read_alerts(os.path.join(wd, "alerts.jsonl"))
    assert any(a["rule"] == "slo_burn_fast" for a in alerts), alerts
    dumps = read_flight_dumps(wd)
    assert dumps, "alert fired but no flight dump landed"
    alert_dump = next(
        rec for _, rec in dumps if str(rec.get("reason", "")).startswith("alert:")
    )
    dumped = {r["request_id"]: r for r in alert_dump["requests"]}
    assert slowed[0] in dumped
    stage_ms = {s["stage"]: s["dur_ms"] for s in dumped[slowed[0]]["stages"]}
    assert max(stage_ms, key=stage_ms.get) == "engine_execute"
    assert stage_ms["engine_execute"] >= 400.0
    # the on-demand endpoint dumped too, and holds both offenders
    assert debug["dump_path"]
    debug_ids = {r["request_id"] for r in debug["requests"]}
    assert set(slowed) <= debug_ids
    # the metrics stream is schema-strict with the whole new surface on it
    errors = schema.validate_file(os.path.join(wd, "metrics.jsonl"))
    assert not errors, errors[:5]
    lines = schema.read_metrics(os.path.join(wd, "metrics.jsonl"))
    assert any(r.get("serve/burn_rate_10s") is not None for r in lines)
    assert any(r.get("serve/p99_exemplar") in slowed for r in lines)
    assert any(r.get("event") == "alert" for r in lines)
    # request spans + the clock anchor reached the replica stream
    spans = [json.loads(l) for l in open(os.path.join(wd, "trace_events.s0.jsonl"))]
    names = {s["name"] for s in spans}
    assert {"request", "req/engine_execute", "req/queue_wait"} <= names
    anchor = json.load(open(os.path.join(wd, "heartbeat.s0.json")))
    assert anchor["role"] == "serve" and "trace_wall_t0" in anchor
