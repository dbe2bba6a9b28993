"""The schedule is a pure function of the seed, every seed offers the same
work, and the client times each request from when it was due."""

import http.server
import json
import threading
import time

import numpy as np

from benchmarks.loadgen import client
from benchmarks.loadgen.schedule import build_schedule, structured_images

TRAFFIC = {
    "rate_rps": 50.0, "arrivals": "poisson", "gap_seed": 3,
    "images_per_request": {"1": 0.7, "2": 0.1, "4": 0.08, "8": 0.06, "16": 0.04, "32": 0.02},
    "routes": {"/embed": 0.7, "/neighbors?k=5": 0.3},
}


def test_schedule_is_pure_and_seeds_offer_the_same_work():
    a, b = build_schedule(TRAFFIC, 7, 20.0), build_schedule(TRAFFIC, 7, 20.0)
    c = build_schedule(TRAFFIC, 2**31 + 5, 20.0)
    assert np.array_equal(a["due_s"], b["due_s"]) and np.array_equal(a["size"], b["size"])
    assert a["route"] == b["route"]
    assert len(a["due_s"]) == 1000 and a["due_s"][0] == 0.0 and a["due_s"][-1] < 20.0
    assert not np.array_equal(a["size"], c["size"])
    assert sorted(a["size"]) == sorted(c["size"]) and sorted(a["route"]) == sorted(c["route"])
    # the same gaps in another order (the last one runs to the window's end)
    gaps = lambda s: np.sort(np.append(np.diff(s["due_s"]), 20.0 - s["due_s"][-1]))
    assert np.allclose(gaps(a), gaps(c))
    assert int(a["size"].sum()) == int(c["size"].sum()) == 2980  # 1000 requests, mean 2.98
    assert sum(1 for r in a["route"] if r == "/embed") == 700
    assert np.all(np.diff(a["due_s"]) >= 0)


def test_bursts_keep_the_mean_rate():
    s = build_schedule({**TRAFFIC, "burst": {"period_s": 2.0, "on_share": 0.25}}, 1, 20.0)
    assert len(s["due_s"]) == 1000 and np.all((s["due_s"] % 2.0) <= 0.5 + 1e-9)


def test_structured_images_differ_and_repeat():
    a, b = structured_images(5, 4, 32), structured_images(5, 4, 32)
    assert a.dtype == np.uint8 and a.shape == (4, 32, 32, 3) and np.array_equal(a, b)
    assert np.abs(a[0].astype(int) - a[1].astype(int)).mean() > 10


class _Slow(http.server.BaseHTTPRequestHandler):
    """Serial stub: every POST takes 40 ms, one at a time."""

    lock = threading.Lock()

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers["Content-Length"]))
        with self.lock:
            time.sleep(0.04)
        body = json.dumps({"embedding": [[0.0]]}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_latency_runs_from_the_due_time():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        spec = {
            "host": "127.0.0.1", "port": srv.server_address[1], "seed": 1, "timeout_s": 10.0,
            "workers": 1, "window_file": None,
            "traffic": {"rate_rps": 50.0, "arrivals": "uniform",
                        "images_per_request": {"1": 1.0}, "routes": {"/embed": 1.0}},
        }
        pool = client.image_pool(1, 4, 8)
        win = client.run_window(spec, pool, seconds=1.0)
    finally:
        srv.shutdown()
        srv.server_close()
    # offered every 20 ms, served every 40 ms by one worker: the backlog
    # grows, and a request's latency counts the wait the stall imposed
    lat, late = np.array(win["latency_ms"]), np.array(win["late_ms"])
    assert len(lat) == 50 and all(s == 200 for s in win["status"])
    assert lat[-1] > 800 and lat[0] < 200
    assert np.all(np.diff(lat) > 0)
    assert late[-1] > 700  # and the generator says how late it sent
    assert np.allclose(lat - late, 40, atol=25)  # service time alone is ~40 ms
