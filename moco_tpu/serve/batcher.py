"""Continuous batching under a latency SLO.

The device is efficient on the engine's padded buckets; users arrive
one-at-a-time. The batcher is the adapter: requests enqueue from any
number of client threads, a single batcher thread coalesces them into
micro-batches — flushing when the pending rows reach `max_batch` (the
engine's largest bucket) **or** when the oldest pending request has
waited `slo_ms / 2` (half the budget queued, half for compute; the
classic continuous-batching deadline split) — runs the engine call on
its own thread (the wire/compute never touches a client thread, the
same discipline as the device prefetch ring), and scatters result rows
back to each request's future.

Thread hygiene is the JX011 contract (`data/pipeline.py` /
`device_prefetch.py` lineage): the submit queue is bounded, every
blocking put polls a stop flag (`_responsive_put`), `close()` drains
the queue, fails all pending futures with :class:`BatcherClosedError`
(so put-blocked producers and result-blocked clients both unblock), and
joins the batcher thread.

Shutdown comes in two flavors. `close()` is the abort path: anything
still pending fails fast with BatcherClosedError. `drain()` is the
graceful one — intake shuts (new submits raise), but every rider
already accepted is FLUSHED, not failed, and only then does the thread
stop. The server's SIGTERM path and `/admin/drain` ride drain(), which
is what makes a fleet-router drain/restart a zero-dropped-requests
operation rather than a burst of 503s.

Metrics (`ServeMetrics`): per-request latency reservoir → p50/p99,
completed-request QPS, batch occupancy (valid rows / padded bucket
rows — the padding tax), a per-bucket execution histogram, per-tier
request counts (`serve/mode_<tier>` — explicit `?mode=` riders under
their tier, the rest under "default"), SLO violation counts, a
cumulative latency histogram with the p99 exemplar request id,
per-stage request-trace means, and (when a `SLOBurnTracker` is
attached) the multi-window burn-rate family.
`payload()` emits the `serve/*` metric family the obs schema validates
and the Prometheus sink exposes as gauges + a real
`_bucket{le=...}` histogram.

Request tracing (obs/reqtrace.py): a future may carry a
`RequestTrace`; the batcher thread stamps `queue_wait` (per request)
and the shared flush stages (`batch_assemble` / `engine_execute` /
`index_query` / `scatter`) onto it — perf_counter pairs only, the
expensive rendering happens off-path. With `reqtrace=True` the batcher
allocates traces itself for trace-less submits (standalone use);
with tracing off the per-request cost is a `None` check.
"""

from __future__ import annotations

import inspect
import queue
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Callable, Optional

import numpy as np

from moco_tpu.analysis import tsan
from moco_tpu.obs.reqtrace import RequestIdAllocator, RequestTrace
from moco_tpu.utils import faults

# Cumulative latency bucket bounds (ms) for the exported histogram —
# wide enough to cover a TPU replica at a tight SLO and the CPU smoke's
# multi-second tail in the same ladder.
DEFAULT_LATENCY_BUCKETS_MS = (
    1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
)


class BatcherClosedError(RuntimeError):
    """The batcher shut down before (or while) handling this request."""


def _responsive_put(q: queue.Queue, stop: threading.Event, item) -> bool:
    """Bounded put that stays responsive to a stop flag; False = stopped
    (the JX011-idiomatic put — see data/device_prefetch.py)."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


class ServeFuture:
    """Single-assignment result handle: `result(timeout)` blocks until
    the batcher scatters this request's rows back (or fails it)."""

    def __init__(
        self,
        num_rows: int,
        submitted_at: float,
        want_neighbors: bool,
        mode: Optional[str] = None,
        trace: Optional[RequestTrace] = None,
    ):
        self.num_rows = num_rows
        self.submitted_at = submitted_at
        self.want_neighbors = want_neighbors
        self.mode = mode  # neighbor tier this rider asked for (None = default)
        self.trace = trace  # request-scoped waterfall (None = tracing off)
        self._done = threading.Event()
        self._value: Optional[dict] = None
        self._error: Optional[BaseException] = None
        self.latency_s: Optional[float] = None

    def _resolve(self, value: dict) -> None:
        self.latency_s = time.perf_counter() - self.submitted_at
        self._value = value
        self._done.set()

    def _fail(self, error: BaseException) -> None:
        self.latency_s = time.perf_counter() - self.submitted_at
        self._error = error
        self._done.set()

    def result(self, timeout: Optional[float] = None) -> dict:
        if not self._done.wait(timeout):
            raise TimeoutError("serve request still pending")
        if self._error is not None:
            raise self._error
        return self._value


class ServeMetrics:
    """Thread-safe serving gauges; `payload()` is the schema'd
    `serve/*` line (README "metrics.jsonl line format")."""

    def __init__(
        self,
        slo_ms: float,
        window: int = 2048,
        burn=None,
        latency_buckets_ms=DEFAULT_LATENCY_BUCKETS_MS,
    ):
        self.slo_ms = float(slo_ms)
        # tsan factory: the serving gauges' lock is the INNER lock of the
        # sanctioned serve.index -> serve.metrics nesting (server.stats);
        # --sanitize-threads smoke runs watch its acquisition order
        self._lock = tsan.make_lock("serve.metrics")
        self._latencies_ms: deque = deque(maxlen=window)
        self._recalls: deque = deque(maxlen=window)
        self._bucket_counts: dict[int, int] = {}
        self._valid_rows = 0
        self._padded_rows = 0
        self._completed = 0
        self._violations = 0
        self._started_at = time.perf_counter()
        self._win_t0 = self._started_at
        self._win_completed = 0
        # multi-window SLO burn-rate tracker (obs/slo.py); fed one
        # ok/violation observation per completed request
        self.burn = burn
        # cumulative latency histogram (lifetime counters, Prometheus
        # semantics) + the window's worst request as the p99 exemplar
        self._hist_le = tuple(float(b) for b in latency_buckets_ms)
        self._hist_counts = [0] * (len(self._hist_le) + 1)
        self._hist_sum_ms = 0.0
        self._hist_count = 0
        self._exemplar: Optional[tuple[float, str]] = None  # (ms, request_id)
        # per-tier request counts (serve/mode_<tier>): which retrieval
        # mode answered the traffic — explicit ?mode= riders under their
        # tier name, everything else under "default" (the server's
        # neighbors_mode). The tier A/B and the fleet router both read
        # this to see where load actually lands.
        self._mode_counts: dict[str, int] = {}
        # per-stage request-trace sums over the current payload window
        self._stage_sums_ms: dict[str, float] = {}
        self._stage_reqs = 0

    def record_recall(self, recall: float) -> None:
        """One sampled online recall@k observation (approximate tier vs
        the exact oracle, same queries) — `serve/recall_estimate` is the
        window mean, the gauge the smoke's recall floor gates."""
        with self._lock:
            self._recalls.append(float(recall))

    def record_request(
        self,
        latency_s: float,
        request_id: Optional[str] = None,
        trace: Optional[RequestTrace] = None,
        mode: Optional[str] = None,
    ) -> None:
        ms = latency_s * 1e3
        with self._lock:
            key = mode or "default"
            self._mode_counts[key] = self._mode_counts.get(key, 0) + 1
            self._latencies_ms.append(ms)
            self._completed += 1
            self._win_completed += 1
            if ms > self.slo_ms:
                self._violations += 1
            self._hist_counts[bisect_left(self._hist_le, ms)] += 1
            self._hist_sum_ms += ms
            self._hist_count += 1
            if request_id is not None and (
                self._exemplar is None or ms > self._exemplar[0]
            ):
                self._exemplar = (ms, request_id)
            if trace is not None:
                for stage, dur_ms in trace.stage_ms().items():
                    self._stage_sums_ms[stage] = (
                        self._stage_sums_ms.get(stage, 0.0) + dur_ms
                    )
                self._stage_reqs += 1
        if self.burn is not None:
            self.burn.record(ms <= self.slo_ms)

    def record_flush(self, executed: list[tuple[int, int]]) -> None:
        with self._lock:
            for bucket, valid in executed:
                self._bucket_counts[bucket] = self._bucket_counts.get(bucket, 0) + 1
                self._padded_rows += bucket
                self._valid_rows += valid

    def payload(self) -> dict:
        """`serve/*` fields; qps is computed over the window since the
        previous payload() call (the sink-flush cadence), falling back
        to the lifetime rate on the first call."""
        with self._lock:
            now = time.perf_counter()
            dt = max(now - self._win_t0, 1e-9)
            qps = self._win_completed / dt
            self._win_t0, self._win_completed = now, 0
            lat = sorted(self._latencies_ms)
            pct = lambda p: (
                lat[min(int(p * (len(lat) - 1) + 0.5), len(lat) - 1)] if lat else None
            )
            out = {
                "serve/p50_ms": pct(0.50),
                "serve/p99_ms": pct(0.99),
                "serve/qps": qps,
                "serve/occupancy": (
                    self._valid_rows / self._padded_rows if self._padded_rows else None
                ),
                "serve/requests": self._completed,
                "serve/slo_violations": self._violations,
                "serve/slo_ms": self.slo_ms,
                # sampled-online recall of the approximate tier vs the
                # exact oracle; null until the first sample (or with the
                # estimator off / exact-only serving)
                "serve/recall_estimate": (
                    sum(self._recalls) / len(self._recalls) if self._recalls else None
                ),
                # cumulative latency histogram (lifetime, per-bucket
                # counts — the Prometheus sink cumulates at render) with
                # the window's worst request attached as the exemplar
                "serve/latency_hist": {
                    "le": list(self._hist_le),
                    "counts": list(self._hist_counts),
                    "sum": round(self._hist_sum_ms, 3),
                    "count": self._hist_count,
                    **(
                        {
                            "exemplar": {
                                "request_id": self._exemplar[1],
                                "latency_ms": round(self._exemplar[0], 3),
                            }
                        }
                        if self._exemplar is not None
                        else {}
                    ),
                },
                # the p99 exemplar: WHICH request the latency gauges
                # blame (the window's worst; null with tracing off)
                "serve/p99_exemplar": (
                    self._exemplar[1] if self._exemplar is not None else None
                ),
                "serve/p99_exemplar_ms": (
                    round(self._exemplar[0], 3) if self._exemplar is not None else None
                ),
            }
            # stage waterfall means over the window (request tracing on)
            if self._stage_reqs:
                for stage, total in sorted(self._stage_sums_ms.items()):
                    out[f"serve/trace_{stage}_ms"] = round(
                        total / self._stage_reqs, 3
                    )
                out["serve/trace_requests"] = self._stage_reqs
            self._exemplar = None
            self._stage_sums_ms = {}
            self._stage_reqs = 0
            for bucket, count in sorted(self._bucket_counts.items()):
                out[f"serve/bucket_{bucket}"] = count
            # cumulative per-tier counts, like the bucket histogram
            for m, count in sorted(self._mode_counts.items()):
                out[f"serve/mode_{m}"] = count
        if self.burn is not None:
            out.update(self.burn.payload())
        return out


class ContinuousBatcher:
    """Micro-batch coalescing front end over an engine-shaped callable
    (module docstring).

    `run_batch(images, want_neighbors) -> (dict of row-arrays, executed)`
    — the server wires this to `engine.embed` / `engine.embed_and_query`;
    every returned array's rows align with the input rows so the scatter
    is a pure slice. `max_batch` defaults to the engine's largest bucket.
    """

    def __init__(
        self,
        run_batch: Callable,
        max_batch: int,
        slo_ms: float = 100.0,
        queue_depth: int = 256,
        metrics: Optional[ServeMetrics] = None,
        reqtrace: bool = False,
        replica_index: int = 0,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self._run_batch = run_batch
        # a run_batch with >= 3 POSITIONAL params additionally receives
        # the sorted tuple of per-request neighbor modes in the
        # micro-batch (the IVF server path); 2-arg callables keep the
        # original contract. A keyword-only `stages` param opts into
        # per-stage timing (the engine splits engine_execute /
        # index_query there) — keyword-only so a stages-aware 2-arg
        # callable is not mistaken for the modes contract.
        try:
            params = inspect.signature(run_batch).parameters
            positional = [
                p for p in params.values()
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
            ]
            self._pass_modes = len(positional) >= 3
            self._pass_stages = "stages" in params
        except (TypeError, ValueError):
            self._pass_modes = False
            self._pass_stages = False
        # reqtrace=True: allocate a RequestTrace for trace-less submits
        # (standalone batcher use — tests); the server passes
        # traces explicitly so the ingress stage is already stamped
        self._ids = RequestIdAllocator(replica_index) if reqtrace else None
        self.max_batch = int(max_batch)
        self.slo_ms = float(slo_ms)
        # half the SLO budget may be spent coalescing; the rest belongs
        # to the compute + scatter
        self.deadline_s = self.slo_ms / 2e3
        self.metrics = metrics or ServeMetrics(slo_ms)
        self._q: queue.Queue = queue.Queue(maxsize=queue_depth)
        self._stop = threading.Event()
        # graceful-drain pair: _draining gates intake (submit raises,
        # accepted work still flushes), _drained flips when the loop has
        # flushed everything and exited — drain() waits on it
        self._draining = threading.Event()
        self._drained = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="serve_batcher", daemon=True
        )
        self._thread.start()

    # -- client side -----------------------------------------------------

    def submit(
        self,
        images: np.ndarray,
        want_neighbors: bool = False,
        mode: Optional[str] = None,
        trace: Optional[RequestTrace] = None,
    ) -> ServeFuture:
        """Enqueue an (n, H, W, C) uint8 request; returns its future.
        `mode` names the neighbor tier this rider wants (exact/ivf/...;
        None = the server default); `trace` is an optional ingress
        -stamped RequestTrace (auto-allocated under reqtrace=True).
        Raises BatcherClosedError when the batcher is shut (including a
        producer that was blocked on a full queue during close)."""
        images = np.asarray(images, np.uint8)
        if images.ndim != 4 or images.shape[0] < 1:
            raise ValueError(f"request must be (n>=1, H, W, C) uint8, got {images.shape}")
        if trace is None and self._ids is not None:
            trace = self._ids.new_trace(images.shape[0])
        fut = ServeFuture(
            images.shape[0], time.perf_counter(), want_neighbors, mode, trace
        )
        if self._draining.is_set():
            raise BatcherClosedError("batcher is draining")
        if self._stop.is_set() or not _responsive_put(self._q, self._stop, (images, fut)):
            raise BatcherClosedError("batcher is closed")
        return fut

    # -- batcher thread --------------------------------------------------

    def _flush(self, pending: list) -> None:
        if not pending:
            return
        # queue_wait closes for every rider the moment its flush begins;
        # the remaining stages are flush-shared (reqtrace.py semantics)
        t_flush = time.perf_counter()
        tracing = any(f.trace is not None for _, f in pending)
        if tracing:
            for _, fut in pending:
                if fut.trace is not None:
                    fut.trace.stamp("queue_wait", fut.submitted_at, t_flush)
        faults.maybe_slow("serve.batch_assemble")
        images = np.concatenate([img for img, _ in pending])
        t_assembled = time.perf_counter()
        want_neighbors = any(f.want_neighbors for _, f in pending)
        stages: Optional[dict] = {} if (tracing and self._pass_stages) else None
        try:
            t_run0 = time.perf_counter()
            if self._pass_modes:
                modes = tuple(sorted(
                    {f.mode for _, f in pending if f.want_neighbors and f.mode}
                ))
                if stages is not None:
                    results, executed = self._run_batch(
                        images, want_neighbors, modes, stages=stages
                    )
                else:
                    results, executed = self._run_batch(images, want_neighbors, modes)
            elif stages is not None:
                results, executed = self._run_batch(
                    images, want_neighbors, stages=stages
                )
            else:
                results, executed = self._run_batch(images, want_neighbors)
            t_run1 = time.perf_counter()
        except BaseException as e:
            for _, fut in pending:
                fut._fail(e)
            return
        self.metrics.record_flush(executed)
        if tracing:
            # synthesize contiguous engine/query intervals from the run
            # window: durations are exact, starts are stacked (the real
            # device work interleaves per chunk — reqtrace.py docstring)
            if stages:
                engine_s = stages.get("engine_execute", 0.0)
                query_s = stages.get("index_query", 0.0)
                untimed = max((t_run1 - t_run0) - engine_s - query_s, 0.0)
                engine_s += untimed  # residual host work rides the engine stage
            else:
                engine_s, query_s = t_run1 - t_run0, 0.0
        faults.maybe_slow("serve.scatter")
        t_scatter = time.perf_counter()
        offset = 0
        for _, fut in pending:
            rows = slice(offset, offset + fut.num_rows)
            if fut.trace is not None:
                tr = fut.trace
                tr.stamp("batch_assemble", t_flush, t_assembled)
                tr.stamp("engine_execute", t_run0, t_run0 + engine_s)
                if query_s > 0.0:
                    tr.stamp(
                        "index_query", t_run0 + engine_s, t_run0 + engine_s + query_s
                    )
                # scatter closes at THIS request's resolve, so the
                # per-request stage sum tracks its measured latency
                tr.stamp("scatter", t_scatter, time.perf_counter())
            fut._resolve({k: v[rows] for k, v in results.items()})
            offset += fut.num_rows
            self.metrics.record_request(
                fut.latency_s,
                request_id=fut.trace.req_id if fut.trace is not None else None,
                trace=fut.trace,
                mode=fut.mode,
            )

    def _loop(self) -> None:
        pending: list = []
        rows = 0
        while not self._stop.is_set():
            draining = self._draining.is_set()
            if pending:
                timeout = self.deadline_s - (
                    time.perf_counter() - pending[0][1].submitted_at
                )
                # draining with an empty queue: intake is shut, nobody
                # else is coming — flush now instead of idling out the
                # coalescing deadline on riders already in hand
                if timeout <= 0 or rows >= self.max_batch or (
                    draining and self._q.empty()
                ):
                    self._flush(pending)
                    pending, rows = [], 0
                    continue
            elif draining and self._q.empty():
                break  # graceful exit: everything accepted was flushed
            else:
                timeout = 0.05  # idle poll so close() never waits long
            try:
                # the get poll is capped so a drain()/close() raised while
                # the thread is blocked here is noticed within 50ms, not
                # after the full coalescing deadline
                item = self._q.get(timeout=min(timeout, 0.05))
            except queue.Empty:
                continue
            images, fut = item
            pending.append((images, fut))
            rows += fut.num_rows
            if rows >= self.max_batch:
                self._flush(pending)
                pending, rows = [], 0
        # drain-on-stop: everything still queued or pending fails fast
        # so no client blocks on a future that will never resolve
        for _, fut in pending:
            fut._fail(BatcherClosedError("batcher closed with request pending"))
        while True:
            try:
                _, fut = self._q.get_nowait()
            except queue.Empty:
                break
            fut._fail(BatcherClosedError("batcher closed with request queued"))
        self._drained.set()

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful shutdown: stop intake (new submits raise
        BatcherClosedError), flush every already-accepted rider, then
        close. Returns True when the flush finished inside `timeout`
        (False = close() fell back to failing the stragglers). Safe
        from any thread, idempotent, and close()-compatible."""
        self._draining.set()
        drained = self._drained.wait(timeout)
        self.close()
        return drained

    def close(self, timeout: float = 10.0) -> None:
        """Stop coalescing, fail all pending/queued futures, join the
        thread. Safe from any thread, idempotent; put-blocked producers
        unblock via their responsive-put stop poll."""
        self._stop.set()
        self._thread.join(timeout=timeout)
        # a producer may have enqueued between the thread's drain and
        # its exit — fail those too (the thread is gone; nobody else
        # will ever take them)
        while True:
            try:
                _, fut = self._q.get_nowait()
            except queue.Empty:
                break
            fut._fail(BatcherClosedError("batcher is closed"))

    @property
    def closed(self) -> bool:
        return self._stop.is_set()

    def __del__(self):
        self._stop.set()


__all__ = [
    "BatcherClosedError",
    "ContinuousBatcher",
    "DEFAULT_LATENCY_BUCKETS_MS",
    "ServeFuture",
    "ServeMetrics",
]
