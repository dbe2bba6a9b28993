"""The load generator: a process of its own that never imports JAX.

    python benchmarks/loadgen/client.py --spec <spec.json>

`spec.json` (written by the harness) names the server, the traffic file's
content, the seed, the window length, where to write results, and which
phases to run. Phases, in order:

1. wait for `GET /healthz` to answer ok (the replica binds only after its
   AOT warm-up, so a refused connection means "still compiling");
2. warm-up: every size of the mix on every route, twice, over the same
   persistent connections the window will use;
3. the correctness sample: fixed seeded images on both routes, answers
   kept whole for the harness to compare with the plain reference;
4. the window: the open-loop schedule, each request sent at its due time
   by the first free worker and TIMED FROM ITS DUE TIME, so a stall is
   charged to every request it delayed; send lateness is recorded.

Results go to `out` as JSON; `window_file` is written (atomically) the
moment the window opens so the harness can stamp set-up and start a trace.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from benchmarks.loadgen.schedule import build_schedule, structured_images  # noqa: E402


def image_pool(seed: int, n: int, image_size: int) -> np.ndarray:
    return np.random.default_rng(int(seed) ^ 0x5EED).integers(
        0, 256, (n, image_size, image_size, 3), dtype=np.uint8
    )


def _write_atomic(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


class Client:
    """One persistent connection; reconnects once on a dropped socket."""

    def __init__(self, host: str, port: int, timeout: float):
        self.host, self.port, self.timeout = host, port, timeout
        self.conn = None

    def post(self, route: str, images: np.ndarray) -> tuple[int, bytes]:
        body = memoryview(np.ascontiguousarray(images)).cast("B")
        headers = {
            "X-Image-Shape": ",".join(str(s) for s in images.shape),
            "Content-Type": "application/octet-stream",
        }
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout)
            try:
                self.conn.request("POST", route, body=body, headers=headers)
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (http.client.RemoteDisconnected, BrokenPipeError, ConnectionResetError):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def wait_healthy(host: str, port: int, deadline_s: float) -> dict:
    t_end = time.time() + deadline_s
    while time.time() < t_end:
        try:
            conn = http.client.HTTPConnection(host, port, timeout=5.0)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = json.loads(resp.read())
            conn.close()
            if resp.status == 200 and body.get("ok"):
                return body
        except (OSError, http.client.HTTPException, ValueError):
            pass
        time.sleep(0.5)
    raise TimeoutError(f"no healthy server on {host}:{port} within {deadline_s}s")


def run_window(spec: dict, pool: np.ndarray, seconds: float, rate_rps=None) -> dict:
    traffic = dict(spec["traffic"])
    if rate_rps is not None:
        traffic["rate_rps"] = rate_rps
    sched = build_schedule(traffic, spec["seed"], seconds)
    due, sizes, routes = sched["due_s"], sched["size"], sched["route"]
    n = len(due)
    offsets = np.random.default_rng(int(spec["seed"]) + 1).integers(0, len(pool) - int(sizes.max()) + 1, n)
    lat_ms = np.full(n, np.nan)
    late_ms = np.full(n, np.nan)
    done_s = np.full(n, np.nan)
    status = np.zeros(n, np.int32)
    cursor = {"i": 0}
    lock = threading.Lock()
    t0 = time.perf_counter() + 0.05
    wall0 = time.time() + 0.05

    def worker():
        c = Client(spec["host"], spec["port"], spec["timeout_s"])
        try:
            while True:
                with lock:
                    i = cursor["i"]
                    cursor["i"] = i + 1
                if i >= n:
                    return
                wait = t0 + due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent = time.perf_counter()
                late_ms[i] = (sent - t0 - due[i]) * 1e3
                try:
                    code, _ = c.post(routes[i], pool[offsets[i] : offsets[i] + sizes[i]])
                except (OSError, http.client.HTTPException):
                    code = -1
                    c.close()
                end = time.perf_counter()
                status[i] = code
                done_s[i] = end - t0
                lat_ms[i] = (end - t0 - due[i]) * 1e3
        finally:
            c.close()

    threads = [threading.Thread(target=worker, daemon=True) for _ in range(int(spec["workers"]))]
    if spec.get("window_file"):
        _write_atomic(spec["window_file"], {"wall_start": wall0, "seconds": seconds})
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {
        "seconds": seconds,
        "rate_rps": float(traffic["rate_rps"]),
        "wall_start": wall0,
        "due_s": due.tolist(),
        "size": sizes.tolist(),
        "route": routes,
        "status": status.tolist(),
        "latency_ms": lat_ms.tolist(),
        "late_ms": late_ms.tolist(),
        "done_s": done_s.tolist(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    spec = json.load(open(ap.parse_args(argv).spec))
    wait_healthy(spec["host"], spec["port"], spec["boot_deadline_s"])
    pool = image_pool(spec["seed"], spec["pool_size"], spec["image_size"])
    out: dict = {}
    c = Client(spec["host"], spec["port"], spec["timeout_s"])
    for _ in range(2):
        for size in sorted(int(s) for s in spec["traffic"]["images_per_request"]):
            for route in sorted(spec["traffic"]["routes"]):
                code, _ = c.post(route, pool[:size])
                if code != 200:
                    raise RuntimeError(f"warm-up {route} x{size} answered {code}")
    sample = spec.get("sample")
    if sample:
        imgs = structured_images(spec["seed"], sample["n"], spec["image_size"])
        answers = {}
        for route in sorted(spec["traffic"]["routes"]):
            code, body = c.post(route, imgs)
            if code != 200:
                raise RuntimeError(f"sample {route} answered {code}")
            answers[route] = json.loads(body)
        out["sample"] = answers
    c.close()
    if spec.get("sweep_rates"):
        out["sweep"] = [
            run_window({**spec, "window_file": None}, pool, spec["sweep_seconds"], r)
            for r in spec["sweep_rates"]
        ]
    else:
        out["window"] = run_window(spec, pool, spec["seconds"])
    _write_atomic(spec["out"], out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
