"""Serving fleet front door (ISSUE 16): the circuit-breaker state
machine, health/load-aware dispatch with replica attribution, retry
failover past a dead replica, tail-latency hedging (first success
wins), load shedding at the in-flight budget, graceful drain/undrain
under live traffic, the batcher + server drain paths, the
`kill@replica` fault grammar, the ReplicaSupervisor's crash-respawn +
warm-replay loop (against a stdlib-only fake replica process), and the
`serve_ingest --fanout` discovery/ingest path.
"""

import http.server
import json
import os
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from moco_tpu.obs import ctxprop
from moco_tpu.serve.batcher import BatcherClosedError, ContinuousBatcher
from moco_tpu.serve.fleet import ReplicaSupervisor, free_port
from moco_tpu.serve.router import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    CircuitBreaker,
    FleetRouter,
)
from moco_tpu.utils import faults, retry

from tests.conftest import load_script


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    faults.clear()
    yield
    faults.clear()


# -- circuit breaker -----------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_breaker_trips_after_consecutive_failures():
    clk = _Clock()
    b = CircuitBreaker(fail_threshold=3, cooldown_s=2.0, now=clk)
    assert b.state == BREAKER_CLOSED and b.try_acquire()
    b.record_failure()
    b.record_failure()
    assert b.state == BREAKER_CLOSED  # not yet: needs 3 consecutive
    b.record_success()
    b.record_failure()
    b.record_failure()
    assert b.state == BREAKER_CLOSED  # the success reset the streak
    b.record_failure()
    assert b.state == BREAKER_OPEN and b.trips == 1
    assert not b.try_acquire()  # open: nothing dispatches


def test_breaker_half_open_single_probe_and_recovery():
    clk = _Clock()
    b = CircuitBreaker(fail_threshold=1, cooldown_s=2.0, now=clk)
    b.record_failure()
    assert b.state == BREAKER_OPEN
    clk.t = 1.9
    assert not b.try_acquire()  # still cooling down
    clk.t = 2.1
    assert b.try_acquire()  # the single half-open probe
    assert b.state == BREAKER_HALF_OPEN
    assert not b.try_acquire()  # a second caller is NOT admitted
    b.record_success()
    assert b.state == BREAKER_CLOSED
    assert b.try_acquire() and b.try_acquire()  # closed again: all flow


def test_breaker_failed_probe_retrips_with_exponential_cooldown():
    clk = _Clock()
    b = CircuitBreaker(fail_threshold=1, cooldown_s=2.0, cooldown_cap_s=30.0, now=clk)
    b.record_failure()  # trip 1: cooldown 2s
    clk.t = 2.5
    assert b.try_acquire()
    b.record_failure()  # probe failed -> trip 2: cooldown 4s
    assert b.state == BREAKER_OPEN and b.trips == 2
    clk.t = 2.5 + 3.9
    assert not b.try_acquire()
    clk.t = 2.5 + 4.1
    assert b.try_acquire()
    b.record_success()  # recovery resets the streak
    b.record_failure()  # trip 3 after recovery: back to the base 2s
    clk.t += 2.1
    assert b.try_acquire()


def test_breaker_stale_success_does_not_close_open():
    b = CircuitBreaker(fail_threshold=1, now=_Clock())
    b.record_failure()
    b.record_success()  # a straggler from before the trip
    assert b.state == BREAKER_OPEN


# -- fake replica (in-process HTTP server with the ServeServer API) ------


class FakeReplica:
    """Replica-shaped stdlib HTTP server: /healthz, /stats, /embed,
    /neighbors (replica-scoped request ids), /ingest, /admin/drain —
    with injectable latency and fail-next-N knobs. All mutable state is
    guarded by one lock (handler threads race the test thread)."""

    def __init__(self, index: int, latency_s: float = 0.0):
        self.index = index
        self._lock = threading.Lock()
        self.latency_s = latency_s
        self.fail_next = 0
        self.requests = 0
        self.traced = 0
        self.ingested = 0
        self.ingest_ckpt_step = None
        self.draining = False
        self.stats_extra: dict = {}
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                path = self.path.split("?")[0]
                if path == "/healthz":
                    with outer._lock:
                        draining = outer.draining
                    self._json(200, {
                        "ok": not draining, "warm": True,
                        "draining": draining, "replica": outer.index,
                    })
                elif path == "/stats":
                    with outer._lock:
                        st = {"serve/requests": outer.requests, **outer.stats_extra}
                    self._json(200, st)
                else:
                    self.send_error(404)

            def do_POST(self):  # noqa: N802
                path = self.path.split("?")[0]
                body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
                if path in ("/embed", "/neighbors"):
                    t_wall0 = time.time()
                    t0 = time.perf_counter()
                    with outer._lock:
                        outer.requests += 1
                        seq = outer.requests
                        fail = outer.fail_next > 0
                        if fail:
                            outer.fail_next -= 1
                        latency = outer.latency_s
                    if fail:
                        self.send_error(500)
                        return
                    if latency:
                        time.sleep(latency)
                    rid = f"r{outer.index}-{seq:06d}"
                    out = {"request_id": rid, "rows": 0, "embeddings": []}
                    # in-band trace echo, like ServeServer: a propagated
                    # context comes back as the replica-side waterfall
                    trace_id = self.headers.get("X-Trace-Id")
                    parent = self.headers.get("X-Parent-Span")
                    if trace_id:
                        with outer._lock:
                            outer.traced += 1
                        dt = (time.perf_counter() - t0) * 1e3
                        out["trace"] = {
                            "request_id": rid, "replica": outer.index,
                            "rows": 0, "wall_t0": t_wall0,
                            "total_ms": round(dt, 3),
                            "trace_id": trace_id,
                            "span_id": ctxprop.new_span_id(),
                            "parent_span": parent,
                            "stages": [{
                                "stage": "engine_execute",
                                "start_ms": 0.0, "dur_ms": round(dt, 3),
                            }],
                        }
                    self._json(200, out)
                elif path == "/ingest":
                    shape = self.headers.get("X-Rows-Shape", "0,0").split(",")
                    ckpt_step = self.headers.get("X-Ckpt-Step")
                    with outer._lock:
                        outer.ingested += int(shape[0])
                        n = outer.ingested
                        if ckpt_step is not None:
                            outer.ingest_ckpt_step = int(ckpt_step)
                    self._json(200, {"index_rows": n, "ingested_rows": n})
                elif path == "/admin/drain":
                    with outer._lock:
                        outer.draining = True
                    self._json(200, {"draining": True, "drained": True})
                else:
                    self.send_error(404)

            def _json(self, code, obj):
                payload = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, *a):
                pass

        self.server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.thread = threading.Thread(
            target=self.server.serve_forever, name=f"fake_replica_{index}", daemon=True
        )
        self.thread.start()

    def set(self, **kv):
        with self._lock:
            for k, v in kv.items():
                setattr(self, k, v)

    def count(self, name: str) -> int:
        with self._lock:
            return getattr(self, name)

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5)


def _post(url: str, path: str = "/embed", body: bytes = b"x", timeout: float = 30.0):
    req = urllib.request.Request(url + path, data=body)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url: str, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.fixture()
def fleet():
    """(router, fakes) — two fake replicas behind a fast-polling
    router; hedging off by default (tests opt in per-router)."""
    fakes = [FakeReplica(0), FakeReplica(1)]
    router = FleetRouter(
        replica_urls=[f.url for f in fakes],
        slo_ms=1000.0,
        health_interval_s=0.1,
        retry_attempts=3,
        retry_base_delay_s=0.01,
        retry_max_delay_s=0.05,
        hedge=False,
        breaker_fail_threshold=2,
        breaker_cooldown_s=0.2,
        drain_timeout_s=5.0,
    )
    try:
        yield router, fakes
    finally:
        router.close()
        for f in fakes:
            f.close()


# -- dispatch ------------------------------------------------------------


def test_router_dispatches_and_attributes_replica(fleet):
    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    seen = set()
    for _ in range(8):
        status, body = _post(url)
        assert status == 200
        # the response carries BOTH the replica-scoped request id the
        # replica minted and the router's replica attribution, agreeing
        assert body["request_id"].startswith(f"r{body['replica']}-")
        seen.add(body["replica"])
    # least-loaded dispatch over two idle replicas alternates: both serve
    assert seen == {0, 1}
    assert fakes[0].count("requests") + fakes[1].count("requests") == 8
    h = _get(url, "/healthz")
    assert h["ok"] and h["replicas_healthy"] == 2


def test_router_retries_past_dead_replica_and_trips_breaker(fleet):
    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    retry.snapshot(reset=True)
    fakes[0].set(fail_next=100)  # replica 0 answers 500 to everything
    for _ in range(8):
        status, body = _post(url)
        assert status == 200
        assert body["replica"] == 1  # every request lands on the survivor
    stats = router.stats()
    assert stats["fleet_serve/breaker_trips"] >= 1
    assert stats["fleet_serve/retries"] >= 1
    assert stats["fleet_serve/failed"] == 0
    snaps = _get(url, "/admin/replicas")["replicas"]
    assert {s["index"] for s in snaps} == {0, 1}
    assert any(s["breaker"] == BREAKER_OPEN for s in snaps if s["index"] == 0)


def test_router_breaker_recovers_via_half_open_probe(fleet):
    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    fakes[0].set(fail_next=100)
    for _ in range(6):
        _post(url)
    assert router.stats()["fleet_serve/breaker_open"] == 1
    fakes[0].set(fail_next=0)  # replica 0 heals
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        _post(url)
        if router.stats()["fleet_serve/breaker_open"] == 0:
            break
        time.sleep(0.05)
    assert router.stats()["fleet_serve/breaker_open"] == 0
    # and it takes traffic again
    before = fakes[0].count("requests")
    for _ in range(10):
        _post(url)
    assert fakes[0].count("requests") > before


# -- hedging -------------------------------------------------------------


def test_hedge_first_winner_beats_slow_primary():
    fakes = [FakeReplica(0, latency_s=1.5), FakeReplica(1)]
    router = FleetRouter(
        replica_urls=[f.url for f in fakes],
        slo_ms=1000.0,
        health_interval_s=0.1,
        hedge=True,
        hedge_min_ms=100.0,
        retry_base_delay_s=0.01,
    )
    url = f"http://127.0.0.1:{router.port}"
    try:
        t0 = time.perf_counter()
        status, body = _post(url)
        elapsed = time.perf_counter() - t0
        assert status == 200
        # the hedge (replica 1, fast) won; the slow primary was discarded
        assert body["replica"] == 1
        assert elapsed < 1.2, f"hedge did not shortcut the slow primary ({elapsed:.2f}s)"
        stats = router.stats()
        assert stats["fleet_serve/hedges"] >= 1
        assert stats["fleet_serve/hedge_wins"] >= 1
    finally:
        router.close()
        for f in fakes:
            f.close()


# -- load shedding -------------------------------------------------------


def test_shed_past_inflight_budget_is_loud_503():
    fakes = [FakeReplica(0, latency_s=0.6), FakeReplica(1, latency_s=0.6)]
    router = FleetRouter(
        replica_urls=[f.url for f in fakes],
        slo_ms=5000.0,
        health_interval_s=0.1,
        hedge=False,
        max_inflight=2,
        shed_retry_after_s=2.0,
    )
    url = f"http://127.0.0.1:{router.port}"
    outcomes = []
    lock = threading.Lock()

    def worker():
        try:
            status, _ = _post(url)
            with lock:
                outcomes.append(("ok", status, None))
        except urllib.error.HTTPError as e:
            with lock:
                outcomes.append(("shed", e.code, e.headers.get("Retry-After")))

    try:
        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        ok = [o for o in outcomes if o[0] == "ok"]
        shed = [o for o in outcomes if o[0] == "shed"]
        assert len(ok) + len(shed) == 6  # every request got an answer
        assert len(shed) >= 1, "budget of 2 never shed with 6 concurrent"
        assert all(code == 503 and ra == "2" for _, code, ra in shed)
        stats = router.stats()
        assert stats["fleet_serve/shed"] == len(shed)
    finally:
        router.close()
        for f in fakes:
            f.close()


# -- drain / undrain -----------------------------------------------------


def test_drain_under_load_drops_nothing_and_undrain_readmits(fleet):
    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    failures = []
    stop = threading.Event()
    lock = threading.Lock()

    def traffic():
        while not stop.is_set():
            try:
                _post(url)
            except Exception as e:
                with lock:
                    failures.append(repr(e))
            time.sleep(0.01)

    threads = [threading.Thread(target=traffic) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)
        req = urllib.request.Request(url + "/admin/drain?replica=0&restart=0", data=b"")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 202
            assert json.loads(r.read())["accepted"] is True
        # wait for the drain worker: in-flight waited out, replica's own
        # /admin/drain called, parked out of rotation
        deadline = time.monotonic() + 10.0
        snap = None
        while time.monotonic() < deadline:
            snap = next(
                s for s in _get(url, "/admin/replicas")["replicas"] if s["index"] == 0
            )
            if snap["drain_phase"] == "drained":
                break
            time.sleep(0.05)
        assert snap and snap["drain_phase"] == "drained", snap
        assert fakes[0].count("draining") is True
        # drained replica gets no new dispatch; traffic continues on r1
        settled = fakes[0].count("requests")
        time.sleep(0.3)
        assert fakes[0].count("requests") == settled
        # undrain re-admits once the replica reports healthy again
        fakes[0].set(draining=False)
        req = urllib.request.Request(url + "/admin/undrain?replica=0", data=b"")
        with urllib.request.urlopen(req, timeout=10):
            pass
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if fakes[0].count("requests") > settled:
                break
            time.sleep(0.05)
        assert fakes[0].count("requests") > settled, "undrained replica got no traffic"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert failures == [], f"requests failed during drain: {failures[:3]}"
    assert router.stats()["fleet_serve/drains"] == 1


def test_drain_rejects_bad_replica_and_double_drain(fleet):
    router, _ = fleet
    url = f"http://127.0.0.1:{router.port}"
    with pytest.raises(urllib.error.HTTPError) as ei:
        req = urllib.request.Request(url + "/admin/drain?replica=7", data=b"")
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 400
    assert router.drain_replica(0, restart=False) is True
    assert router.drain_replica(0, restart=False) is False  # already draining


# -- stats / schema ------------------------------------------------------


def test_stats_aggregates_replica_burn_and_validates(fleet):
    from moco_tpu.obs import schema

    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    fakes[0].set(stats_extra={"serve/burn_rate_60s": 0.5, "serve/burn_rate_600s": 0.2})
    fakes[1].set(stats_extra={"serve/burn_rate_60s": 1.5, "serve/burn_rate_600s": 0.4})
    for _ in range(4):
        _post(url)
    deadline = time.monotonic() + 5.0
    stats = {}
    while time.monotonic() < deadline:  # poller must re-read /stats
        stats = _get(url, "/stats")
        if stats.get("fleet_serve/burn_rate_60s_mean") is not None:
            break
        time.sleep(0.05)
    assert stats["fleet_serve/burn_rate_60s_min"] == 0.5
    assert stats["fleet_serve/burn_rate_60s_max"] == 1.5
    assert stats["fleet_serve/burn_rate_60s_mean"] == pytest.approx(1.0)
    assert stats["fleet_serve/replicas"] == 2
    assert stats["fleet_serve/replicas_healthy"] == 2
    assert stats["fleet_serve/requests"] == 4
    assert stats["fleet_serve/dispatch_0"] + stats["fleet_serve/dispatch_1"] >= 4
    assert 0.0 < stats["fleet_serve/slo_objective"] < 1.0
    problems = schema.validate_line({"step": 1, "time": 0.0, **stats})
    assert problems == [], problems


def test_router_needs_at_least_one_replica():
    with pytest.raises(ValueError):
        FleetRouter(replica_urls=[])
    with pytest.raises(ValueError):
        FleetRouter()


# -- batcher drain -------------------------------------------------------


def _echo_run_batch(images, want_neighbors):
    return {"embeddings": np.ones((images.shape[0], 4), np.float32)}, [
        (images.shape[0], images.shape[0])
    ]


def test_batcher_drain_flushes_accepted_riders():
    # an SLO so lax nothing would flush for 30s on its own: the flushes
    # below can only come from drain()
    b = ContinuousBatcher(_echo_run_batch, max_batch=64, slo_ms=60000.0)
    imgs = np.zeros((1, 4, 4, 3), np.uint8)
    futs = [b.submit(imgs) for _ in range(3)]
    t0 = time.perf_counter()
    assert b.drain(timeout=10.0) is True
    assert time.perf_counter() - t0 < 5.0  # not the coalescing deadline
    for f in futs:
        out = f.result(timeout=1.0)
        assert out["embeddings"].shape == (1, 4)
    with pytest.raises(BatcherClosedError):
        b.submit(imgs)
    assert b.closed


def test_batcher_drain_idempotent_and_empty():
    b = ContinuousBatcher(_echo_run_batch, max_batch=8, slo_ms=100.0)
    assert b.drain(timeout=5.0) is True
    assert b.drain(timeout=5.0) is True


# -- server drain --------------------------------------------------------


class _FakeEngine:
    buckets = (1, 4)
    recompiles_after_warmup = 0
    num_features = 4
    image_size = 4

    def warmup(self):
        pass

    def embed(self, images, stages=None):
        return np.ones((images.shape[0], 4), np.float32), [
            (images.shape[0], images.shape[0])
        ]


def test_server_admin_drain_flips_healthz_and_rejects_new_work():
    from moco_tpu.serve.server import ServeServer

    server = ServeServer(_FakeEngine(), index=None, port=0, slo_ms=500.0)
    url = f"http://127.0.0.1:{server.port}"
    imgs = np.zeros((1, 4, 4, 3), np.uint8)
    try:
        req = urllib.request.Request(
            url + "/embed", data=imgs.tobytes(),
            headers={"X-Image-Shape": "1,4,4,3"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["request_id"].startswith("r0-")
        h = _get(url, "/healthz")
        # (the platform this process resolved: chip_smoke.py refuses a
        # replica that came up anywhere but on the TPU)
        assert h["ok"] is True and h["platform"] == "cpu"
        drain_req = urllib.request.Request(url + "/admin/drain?timeout=10", data=b"")
        with urllib.request.urlopen(drain_req, timeout=30) as r:
            body = json.loads(r.read())
        assert body["draining"] is True and body["drained"] is True
        h = _get(url, "/healthz")
        assert h["ok"] is False and h["draining"] is True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
    finally:
        server.close()


# -- kill@replica fault grammar ------------------------------------------


def test_kill_replica_grammar():
    faults.install("kill@replica=1:at=3")
    assert faults.describe() == [("kill", {"replica": 1, "at": 3})]
    faults.clear()
    with pytest.raises(ValueError, match="host"):
        faults.install("kill@at=2")
    with pytest.raises(ValueError, match="mutually exclusive"):
        faults.install("kill@host=2:replica=1")


def test_kill_replica_fires_on_kth_request(monkeypatch):
    exits = []
    monkeypatch.setattr(faults.os, "_exit", lambda code: exits.append(code))
    faults.install("kill@replica=1:at=3")
    for _ in range(5):
        faults.maybe_kill_replica(0)  # a different replica: never fires
    assert exits == []
    faults.maybe_kill_replica(1)
    faults.maybe_kill_replica(1)
    assert exits == []
    faults.maybe_kill_replica(1)
    assert exits == [faults.KILL_EXIT_CODE]


def test_kill_host_path_ignores_replica_rules(tmp_path):
    faults.install("kill@replica=0")
    faults.maybe_kill_host(5, str(tmp_path), 0, 1)
    assert os.listdir(tmp_path) == []  # no heartbeat stamped, no exit


def test_strip_replica_kills_preserves_other_rules():
    spec = "slow@site=x:ms=5,kill@replica=1:at=3,kill@host=2,io@site=y:at=1"
    assert faults.strip_replica_kills(spec) == "slow@site=x:ms=5,kill@host=2,io@site=y:at=1"
    assert faults.strip_replica_kills("kill@replica=0") == ""
    assert faults.strip_replica_kills("") == ""
    assert faults.strip_replica_kills(None) == ""


def test_supervisor_child_env_scrubs_kill_rules():
    sup = ReplicaSupervisor(
        1, argv_for=lambda i, p: ["true"],
        env={"PATH": os.environ.get("PATH", ""),
             "MOCO_FAULTS": "kill@replica=0:at=2,slow@site=x:ms=1"},
    )
    assert sup._child_env(0, scrub_kills=False)["MOCO_FAULTS"] == (
        "kill@replica=0:at=2,slow@site=x:ms=1"
    )
    assert sup._child_env(0, scrub_kills=True)["MOCO_FAULTS"] == "slow@site=x:ms=1"
    sup2 = ReplicaSupervisor(
        1, argv_for=lambda i, p: ["true"],
        env={"MOCO_FAULTS": "kill@replica=0"},
    )
    assert "MOCO_FAULTS" not in sup2._child_env(0, scrub_kills=True)


def test_supervisor_refuses_more_accelerator_replicas_than_it_can_place():
    """One process per chip host: unpinned replica_main children would
    each take every chip. No device assignment yet (ROADMAP W1), so the
    supervisor refuses up front with a plain message."""
    unpinned = {"PATH": os.environ.get("PATH", "")}
    with pytest.raises(RuntimeError, match="only one process can hold them"):
        ReplicaSupervisor(2, ckpt_dir="/nonexistent", env=unpinned)
    # one unpinned replica is placeable; a CPU-pinned fleet of any size
    # never touches the chip; per-replica pins count per replica
    ReplicaSupervisor(1, ckpt_dir="/nonexistent", env=unpinned)
    ReplicaSupervisor(3, ckpt_dir="/nonexistent", env={**unpinned, "JAX_PLATFORMS": "cpu"})
    ReplicaSupervisor(
        2, ckpt_dir="/nonexistent", env=unpinned,
        extra_env={1: {"JAX_PLATFORMS": "cpu"}},
    )


# -- supervisor (real subprocesses, stdlib-only fake replica) ------------


_FAKE_REPLICA_SRC = textwrap.dedent(
    """
    import json, sys
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    state = {"rows": 0}

    class H(BaseHTTPRequestHandler):
        def _json(self, code, obj):
            b = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Length", str(len(b)))
            self.end_headers()
            self.wfile.write(b)

        def do_GET(self):
            if self.path.startswith("/healthz"):
                self._json(200, {"ok": True, "warm": state["rows"] > 0})
            elif self.path.startswith("/stats"):
                self._json(200, {"serve/ingested_rows": state["rows"]})
            else:
                self.send_error(404)

        def do_POST(self):
            body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
            if self.path.startswith("/ingest"):
                shape = self.headers.get("X-Rows-Shape", "0,0").split(",")
                state["rows"] += int(shape[0])
                self._json(200, {"index_rows": state["rows"]})
            else:
                self.send_error(404)

        def log_message(self, *a):
            pass

    ThreadingHTTPServer(("127.0.0.1", int(sys.argv[1])), H).serve_forever()
    """
)


@pytest.mark.slow
def test_supervisor_respawns_crashed_child_and_rewarms(tmp_path):
    script = tmp_path / "fake_replica.py"
    script.write_text(_FAKE_REPLICA_SRC)
    sup = ReplicaSupervisor(
        2,
        argv_for=lambda i, port: [sys.executable, str(script), str(port)],
        warm_rows_fn=lambda: np.ones((5, 4), np.float32),
        boot_timeout_s=30.0,
        term_timeout_s=10.0,
        monitor_interval_s=0.1,
        restart_backoff_s=0.05,
    )
    try:
        sup.start()
        for i in range(2):
            assert _get(sup.url(i), "/healthz")["ok"]
        # sudden death: SIGKILL replica 1 — the monitor must respawn it
        # on the SAME port and re-play the warm ingest
        sup._children[1].proc.kill()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            kinds = [(e["kind"], e["replica"]) for e in sup.events()]
            if ("restart", 1) in kinds:
                break
            time.sleep(0.1)
        events = sup.events()
        crash = [e for e in events if e["kind"] == "exit" and e["replica"] == 1]
        assert crash and crash[0]["reason"] == "crash"
        warm = [e for e in events if e["kind"] == "warm" and e["replica"] == 1]
        assert warm and warm[0]["rows"] == 5
        assert ("restart", 1) in [(e["kind"], e["replica"]) for e in events]
        # reborn on the same port, warm dictionary replayed
        assert _get(sup.url(1), "/stats")["serve/ingested_rows"] == 5
        # graceful restart path (the drain worker's call)
        sup.restart_replica(0, graceful=True)
        events = sup.events()
        g_exit = [
            e for e in events
            if e["kind"] == "exit" and e["replica"] == 0 and e["reason"] == "restart"
        ]
        assert g_exit
        assert _get(sup.url(0), "/healthz")["ok"]
    finally:
        sup.close()
    for child in sup._children:
        assert child.proc.poll() is not None  # everything reaped


# -- distributed tracing (ISSUE 18) --------------------------------------


def _flight_requests(url: str) -> list:
    """Drain + snapshot the router's fleet flight ring."""
    return _get(url, "/debug/flight")["requests"]


def test_trace_stitches_failed_and_winning_attempts(fleet):
    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    # first attempt fails WHEREVER it lands; the retry's sibling succeeds
    fakes[0].set(fail_next=1)
    fakes[1].set(fail_next=1)
    status, body = _post(url)
    assert status == 200
    assert ctxprop.parse(body.get("trace_id")) is not None  # well-formed id
    recs = [r for r in _flight_requests(url) if r["trace_id"] == body["trace_id"]]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["status"] == 200 and rec["request_id"] == body["request_id"]
    outcomes = [(a["outcome"], a["winner"]) for a in rec["attempts"]]
    assert ("failed", False) in outcomes and ("ok", True) in outcomes
    failed = next(a for a in rec["attempts"] if a["outcome"] == "failed")
    winner = next(a for a in rec["attempts"] if a["winner"])
    # the retry is a distinct round of the SAME trace
    assert failed["retry_index"] < winner["retry_index"]
    assert failed["error"]
    # the winning attempt stitched the replica's in-band waterfall in
    assert winner["remote"]["request_id"] == body["request_id"]
    assert any(
        s["stage"] == "engine_execute" for s in winner["remote"]["stages"]
    )
    assert winner["net_send_ms"] is not None and winner["net_recv_ms"] is not None
    # critical-path attribution lands in the metrics line, schema-clean
    from moco_tpu.obs import schema

    stats = router.stats()
    assert stats["fleet_serve/critpath_retry_failed_ms"] > 0
    assert schema.validate_line({"step": 1, "time": 0.0, **stats}) == []


def test_hedge_loser_cancelled_with_wasted_ms_and_pure_p99():
    fakes = [FakeReplica(0, latency_s=1.5), FakeReplica(1)]
    router = FleetRouter(
        replica_urls=[f.url for f in fakes],
        slo_ms=1000.0,
        health_interval_s=0.1,
        hedge=True,
        hedge_min_ms=100.0,
        retry_base_delay_s=0.01,
    )
    url = f"http://127.0.0.1:{router.port}"
    try:
        status, body = _post(url)
        assert status == 200 and body["replica"] == 1
        # drain-under-load holdback: the loser lane is still in flight,
        # so the trace is HELD rather than emitted with a pending lane
        assert _flight_requests(url) == []
        deadline = time.monotonic() + 10.0
        recs = []
        while time.monotonic() < deadline:
            recs = [
                r for r in _flight_requests(url)
                if r["trace_id"] == body["trace_id"]
            ]
            if recs:
                break
            time.sleep(0.1)
        assert len(recs) == 1, "held-back trace never emitted"
        rec = recs[0]
        winner = next(a for a in rec["attempts"] if a["winner"])
        loser = next(a for a in rec["attempts"] if not a["winner"])
        assert winner["lane"] == "hedge" and winner["replica"] == 1
        assert loser["outcome"] == "cancelled"
        assert loser["wasted_ms"] >= 1000.0  # the slow lane's real cost
        # the cancelled lane shows up in the flattened waterfall too
        assert any(
            s["stage"] == "cancelled_hedge_r0" for s in rec["stages"]
        )
        stats = router.stats()
        assert stats["fleet_serve/hedge_wasted_ms"] >= 1000.0
        # p99 purity: only the CLIENT-OBSERVED latency entered the
        # histogram — the discarded 1.5s lane must not poison it
        assert stats["fleet_serve/p99_ms"] < 1200.0
    finally:
        router.close()
        for f in fakes:
            f.close()


def test_burst_hop_sum_matches_client_wall(fleet):
    from moco_tpu.obs import critpath

    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    fakes[0].set(latency_s=0.05)
    fakes[1].set(latency_s=0.05)
    walls = {}
    lock = threading.Lock()

    def worker():
        for _ in range(3):
            t0 = time.perf_counter()
            status, body = _post(url)
            wall_ms = (time.perf_counter() - t0) * 1e3
            assert status == 200
            with lock:
                walls[body["trace_id"]] = wall_ms

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    recs = {r["trace_id"]: r for r in _flight_requests(url)}
    assert set(walls) <= set(recs), "some traces never reached the flight ring"
    for trace_id, wall_ms in walls.items():
        attr = critpath.attribute(recs[trace_id])
        ssum = sum(attr["hops"].values())
        # hop sum == router total BY CONSTRUCTION...
        assert ssum == pytest.approx(attr["total_ms"], abs=0.01)
        # ...and the router total accounts for the client's wall (floor
        # widened vs the smoke's gate: these requests are ~50ms, where
        # one slow TCP setup is a visible fraction)
        assert abs(ssum - wall_ms) <= max(0.15 * wall_ms, 50.0), (
            f"{trace_id}: hops {ssum:.1f}ms vs wall {wall_ms:.1f}ms"
        )
        # every replica served through the front door echoed a waterfall
        assert any(h.startswith("replica_") for h in attr["hops"])


def test_router_workdir_emits_stream_anchor_and_flight_dump(tmp_path):
    from moco_tpu.obs.flight import read_flight_dumps

    fakes = [FakeReplica(0)]
    router = FleetRouter(
        replica_urls=[fakes[0].url],
        slo_ms=1000.0,
        health_interval_s=0.1,
        hedge=False,
        workdir=str(tmp_path),
    )
    url = f"http://127.0.0.1:{router.port}"
    try:
        for _ in range(3):
            _post(url)
        body = _get(url, "/debug/flight")
        assert body["requests_recorded"] >= 3
        assert body["dump_path"] and os.path.exists(body["dump_path"])
    finally:
        router.close()
        for f in fakes:
            f.close()
    # the on-demand dump is a readable flight artifact with router role
    dumps = read_flight_dumps(str(tmp_path))
    assert dumps and dumps[-1][1]["role"] == "router"
    # the Perfetto stream + clock anchor landed for trace_merge
    anchor = json.load(open(tmp_path / "heartbeat.r0.json"))
    assert anchor["role"] == "router" and anchor["trace_wall_t0"] > 0
    spans = [
        json.loads(line)
        for line in open(tmp_path / "trace_events.r0.jsonl")
        if line.strip()
    ]
    names = {s["name"] for s in spans}
    assert {"request", "router/attempt", "router/respond"} <= names
    # every attempt span carries the propagated ids the stitcher joins on
    for s in spans:
        if s["name"] == "router/attempt":
            assert ctxprop.parse(s["args"]["trace_id"]) is not None
            assert len(s["args"]["span_id"]) == ctxprop.SPAN_ID_HEX_LEN


def test_trace_disabled_router_serves_untraced():
    fakes = [FakeReplica(0)]
    router = FleetRouter(
        replica_urls=[fakes[0].url],
        slo_ms=1000.0,
        health_interval_s=0.1,
        hedge=False,
        reqtrace=False,
    )
    url = f"http://127.0.0.1:{router.port}"
    try:
        status, body = _post(url)
        assert status == 200 and "trace_id" not in body
        assert _flight_requests(url) == []
    finally:
        router.close()
        for f in fakes:
            f.close()


# -- trace_merge: the router joins the fleet timeline ---------------------


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_trace_merge_router_track_flow_events_and_offline_stitch(tmp_path):
    tm = load_script("trace_merge.py")
    wd = str(tmp_path)
    trace_id = "ab" * 16
    attempt_span = "cd" * 8
    # router 0: anchor wall 1000.0; one request with one attempt
    _write_jsonl(os.path.join(wd, "trace_events.r0.jsonl"), [
        {"name": "request", "ts": 0.0, "dur": 50_000.0, "tid": 1,
         "thread": "requests-0", "p": 0,
         "args": {"trace_id": trace_id, "span_id": "11" * 8,
                  "path": "/embed", "status": 200,
                  "request_id": "r1-000007"}},
        {"name": "router/ingress", "ts": 0.0, "dur": 1_000.0, "tid": 1,
         "thread": "requests-0", "p": 0, "args": {"trace_id": trace_id}},
        {"name": "router/attempt", "ts": 2_000.0, "dur": 40_000.0, "tid": 1,
         "thread": "requests-0", "p": 0,
         "args": {"trace_id": trace_id, "span_id": attempt_span,
                  "replica": 1, "retry_index": 0, "lane": "primary",
                  "breaker": "closed", "outcome": "ok", "winner": True,
                  "wasted_ms": 0.0, "error": None}},
        {"name": "router/respond", "ts": 48_000.0, "dur": 2_000.0, "tid": 1,
         "thread": "requests-0", "p": 0, "args": {"trace_id": trace_id}},
    ])
    with open(os.path.join(wd, "heartbeat.r0.json"), "w") as f:
        json.dump({"process": 0, "role": "router", "host": "routerhost",
                   "time": 1000.0, "trace_wall_t0": 1000.0}, f)
    # replica 1 in a fleet-style subdir: clock starts 0.01s later; its
    # request span parents under the router's attempt span
    sub = tmp_path / "replica1"
    sub.mkdir()
    _write_jsonl(str(sub / "trace_events.s1.jsonl"), [
        {"name": "request", "ts": 0.0, "dur": 30_000.0, "tid": 1,
         "thread": "requests-0", "p": 1,
         "args": {"request_id": "r1-000007", "rows": 1, "replica": 1,
                  "trace_id": trace_id, "span_id": "22" * 8,
                  "parent_span": attempt_span}},
        {"name": "req/engine_execute", "ts": 5_000.0, "dur": 20_000.0,
         "tid": 1, "thread": "requests-0", "p": 1,
         "args": {"request_id": "r1-000007"}},
    ])
    with open(sub / "heartbeat.s1.json", "w") as f:
        json.dump({"process": 1, "role": "serve", "host": "servehost",
                   "time": 1000.01, "trace_wall_t0": 1000.01}, f)

    out = os.path.join(wd, "merged.json")
    summary = tm.merge_traces(wd, out)
    assert summary["routers"][0]["spans"] == 4
    assert summary["serve_replicas"][1]["offset_us"] == pytest.approx(10_000.0)
    assert summary["flow_events"] == 1
    merged = json.load(open(out))
    flows = [e for e in merged["traceEvents"] if e.get("ph") in ("s", "f")]
    start = next(e for e in flows if e["ph"] == "s")
    finish = next(e for e in flows if e["ph"] == "f")
    assert start["id"] == finish["id"] == attempt_span
    assert start["pid"] == tm.ROUTER_PID_BASE
    assert finish["pid"] == tm.SERVE_PID_BASE + 1
    assert finish["bp"] == "e"
    # the arrow points forward in the aligned clock
    assert finish["ts"] > start["ts"]

    stitched = tm.stitch_traces(wd)
    assert set(stitched) == {trace_id}
    rec = stitched[trace_id]
    assert rec["total_ms"] == pytest.approx(50.0)
    assert rec["router"]["ingress_ms"] == pytest.approx(1.0)
    assert rec["router"]["respond_ms"] == pytest.approx(2.0)
    (att,) = rec["attempts"]
    assert att["winner"] and att["outcome"] == "ok"
    # clock-aligned network split: replica ingress at wall +10ms, the
    # attempt dispatched at +2ms -> 8ms send; 40 - 8 - 30 = 2ms recv
    assert att["net_send_ms"] == pytest.approx(8.0)
    assert att["net_recv_ms"] == pytest.approx(2.0)
    assert att["remote"]["request_id"] == "r1-000007"
    assert att["remote"]["stages"][0]["stage"] == "engine_execute"
    # the stitched record feeds critpath cleanly: hop sum == total
    from moco_tpu.obs import critpath

    attr = critpath.attribute(rec)
    assert sum(attr["hops"].values()) == pytest.approx(rec["total_ms"])


# -- serve_ingest --fanout -----------------------------------------------


def test_serve_ingest_fanout_discovers_and_ingests_everywhere(fleet, monkeypatch):
    router, fakes = fleet
    url = f"http://127.0.0.1:{router.port}"
    mod = load_script("serve_ingest.py")
    topo = mod.discover_replicas(url)
    assert topo == {0: fakes[0].url, 1: fakes[1].url}
    rows = np.ones((7, 4), np.float32)
    results = mod.fanout_rows(url, rows)
    assert results == {0: 7, 1: 7}
    assert fakes[0].count("ingested") == 7 and fakes[1].count("ingested") == 7
    # one replica down: its block is lost LOUDLY (None), others still land
    monkeypatch.setenv("MOCO_IO_RETRIES", "2")
    monkeypatch.setenv("MOCO_IO_RETRY_BASE", "0.01")
    fakes[1].close()
    results = mod.fanout_rows(url, rows)
    assert results[0] == 14 and results[1] is None
    fakes[1] = FakeReplica(1)  # the fixture's close() needs a live handle
