"""The embedding service's HTTP front end (stdlib, like the Prometheus
sink it runs alongside).

Endpoints:

- `POST /embed` — body: raw uint8 pixels, `X-Image-Shape: n,h,w,c`
  header (h/w/c must match the engine). Response JSON:
  `{"embedding": [[...f32...]]}` (L2-normalized backbone features).
- `POST /neighbors` — same body; `?k=5` (default 5, capped at the
  prepared k) and `?mode=exact|ivf|exact_i8|ivf_i8` (default: the
  server's `neighbors_mode`). Response adds
  `{"indices": [[...]], "scores": [[...]], "mode": "..."}` — top-k
  cosine rows of the sharded EmbeddingIndex, i.e. the MoCo dictionary
  look-up as a product; `ivf` scans only the `nprobe` nearest cells
  (sub-linear — serve/index.py), the int8 modes score quantized.
- `POST /ingest` — body: raw float32 rows, `X-Rows-Shape: n,d` header
  (plus the propagated `X-Ckpt-Step` header naming the source training
  checkpoint step, so encoder/index provenance is visible).
  FIFO-ingests a block into the live index (the streaming-updates path
  `scripts/serve_ingest.py` drives from a training checkpoint dir);
  IVF cell membership and the int8 mirror follow incrementally, and
  every written row gets a wall-clock ingest stamp (the freshness SLO's
  raw signal). `delay@site=ingest` is the chaos hook that stalls this
  path.
- `GET /admin/model` — the served-model identity: checkpoint step +
  params digest of the encoder answering on this replica, and the last
  ingest's source checkpoint step (encoder/index skew at a glance).
- `GET /stats` — the live `serve/*` gauge snapshot as JSON.
- `GET /healthz` — `{"ok": true, "warm": ..., "draining": false,
  "platform": "tpu"}` once the AOT warmup ran; `ok` flips false while draining so a fleet router
  stops dispatching here before the batcher's intake actually shuts.
- `POST /admin/drain` — graceful shutdown of THIS replica: healthz goes
  not-ok, the batcher flushes every accepted request (`drain()`, zero
  failed futures), then intake closes. The fleet router calls this (or
  the SIGTERM path does, via `replica_main`) before a restart.

Recall estimation: with an approximate `neighbors_mode`, every
`recall_sample_every`-th neighbors micro-batch ALSO runs the exact
oracle on the same device features and records the top-k overlap —
`serve/recall_estimate` in the metric flush, the gauge the smoke's
recall floor (and the CONTRIBUTING review gate) reads.

Request rows flow through the ContinuousBatcher (coalescing under the
SLO), so concurrent clients share padded-bucket executions; handler
threads only block on their own future. Metrics flow into the standard
obs sinks: a flusher thread writes `ServeMetrics.payload()` every
`metrics_flush_s` (schema-validated `serve/*` family; with a Prometheus
sink attached each gauge is scraped as `moco_serve_<name>`).

Ports: `resolve_serve_port` (obs/sinks.py) applies the offset rule so
a process running both the server and `--metrics-port` can't collide —
Prometheus owns `metrics_port + process_index`, the server claims
`serve_port + process_index` and shifts by SERVE_PORT_STRIDE when the
two meet.

Request-scoped observability (PR 10, obs/{reqtrace,slo,flight}.py):
with `reqtrace=True` (the default) every request gets a replica-scoped
id and a stage-stamped waterfall (`ingress -> queue_wait ->
batch_assemble -> engine_execute -> index_query -> scatter ->
respond`); completed waterfalls feed a bounded flight-recorder ring,
the `serve/trace_<stage>_ms` window means, the latency histogram's p99
exemplar, and — when a `workdir` is given — Perfetto request spans on
virtual "requests" lanes in `trace_events.s<replica>.jsonl` (the
`heartbeat.s<replica>.json` anchor lets scripts/trace_merge.py align
them with the training timeline). An `SLOBurnTracker` turns the
declared `slo_ms` into multi-window `serve/burn_rate_<w>s` gauges; an
`AlertEngine` over the flush stream (`alert_spec="serve_default"` =
obs/slo.py's threshold rules) dumps the flight recorder to
`flight_<ts>.json` the moment a rule fires, and `GET /debug/flight`
dumps it on demand.

Thread hygiene (JX011): the HTTP server thread and the metrics flusher
are both joined in `close()`, the flusher polls a stop event, and the
batcher's own close fails stragglers loudly.
"""

from __future__ import annotations

import http.server
import json
import os
import socket
import sys
import threading
import time
from collections import deque

import jax
import numpy as np

from moco_tpu.obs import ctxprop
from moco_tpu.obs.alerts import AlertEngine, parse_rules
from moco_tpu.obs.flight import FlightRecorder
from moco_tpu.obs.reqtrace import RequestIdAllocator, emit_request_spans
from moco_tpu.obs.sinks import resolve_serve_port  # noqa: F401  (public API)
from moco_tpu.obs.slo import (
    DEFAULT_WINDOWS,
    FreshnessBurnTracker,
    SLOBurnTracker,
    fresh_alert_spec,
    serve_alert_spec,
)
from moco_tpu.obs.trace import Tracer, get_tracer
from moco_tpu.analysis import tsan
from moco_tpu.analysis.contracts import record_route
from moco_tpu.serve.batcher import BatcherClosedError, ContinuousBatcher, ServeMetrics
from moco_tpu.serve.index import QUERY_MODES
from moco_tpu.utils import faults

DEFAULT_NEIGHBORS_K = 5
DEFAULT_RECALL_SAMPLE_EVERY = 8


class _QuietHTTPServer(http.server.ThreadingHTTPServer):
    """ThreadingHTTPServer that stays quiet when a CLIENT abandons the
    connection mid-response — routine under a fleet router (a hedge
    loser's response is discarded, a health probe times out and hangs
    up), not worth a traceback per occurrence."""

    def handle_error(self, request, client_address):
        exc = sys.exc_info()[1]
        if isinstance(exc, (BrokenPipeError, ConnectionResetError)):
            return
        super().handle_error(request, client_address)


class ServeServer:
    """HTTP front end binding engine + index + batcher (module
    docstring). `port=0` binds ephemeral (tests/smoke); `self.port` is
    the actual one. `index=None` serves `/embed` only (`/neighbors`
    answers 503). `sink=None` keeps metrics in-process (`/stats` only).
    """

    def __init__(
        self,
        engine,
        index=None,
        host: str = "127.0.0.1",
        port: int = 0,
        metrics_port: int = 0,
        process_index: int = 0,
        slo_ms: float = 100.0,
        neighbors_k: int = DEFAULT_NEIGHBORS_K,
        neighbors_mode: str = "exact",
        nprobe: int = 0,
        recall_sample_every: int = DEFAULT_RECALL_SAMPLE_EVERY,
        sink=None,
        metrics_flush_s: float = 1.0,
        warmup: bool = True,
        workdir: str = None,
        replica_index: int = 0,
        reqtrace: bool = True,
        slo_objective: float = 0.99,
        burn_windows=DEFAULT_WINDOWS,
        alert_spec: str = "serve_default",
        flight_requests: int = 512,
        model_step: int = None,
        model_digest: str = None,
        fresh_max_age_s: float = None,
        fresh_objective: float = 0.99,
    ):
        if neighbors_mode not in QUERY_MODES:
            raise ValueError(
                f"neighbors_mode must be one of {QUERY_MODES}, got {neighbors_mode!r}"
            )
        self.engine = engine
        self.index = index
        self.neighbors_k = int(neighbors_k)
        self.neighbors_mode = neighbors_mode
        self.nprobe = int(nprobe) or None
        self.recall_sample_every = int(recall_sample_every)
        self.workdir = workdir
        self.replica_index = int(replica_index)
        # the backend this process resolved, on /healthz: a replica that
        # lost its chip and came up on the CPU answers correctly and
        # slowly, so whoever waits on /healthz can see it
        self.platform = jax.default_backend()
        # served-model identity (obs/quality.py mints the digest): which
        # encoder answers on this replica — /stats and /admin/model
        # expose it so fleet version skew is a gauge, not an incident
        self.model_step = int(model_step) if model_step is not None else None
        self.model_digest = model_digest
        # source checkpoint step of the last /ingest block (X-Ckpt-Step
        # header) — encoder/index provenance skew, replica-side
        self.ingest_ckpt_step = None
        # request-scoped observability: replica-tagged ids + waterfalls,
        # burn-rate accounting over the declared SLO, flight recorder,
        # and the alert engine that trips the flight dump (module
        # docstring). All off the request path except the stamps.
        self._ids = RequestIdAllocator(self.replica_index) if reqtrace else None
        burn = SLOBurnTracker(slo_ms, objective=slo_objective, windows=burn_windows)
        self.metrics = ServeMetrics(slo_ms, burn=burn)
        self.flight = FlightRecorder(
            max_requests=flight_requests, replica=self.replica_index
        )
        # freshness SLO (obs/slo.py): declared max index-row age in wall
        # seconds; each metrics flush records one observation off the
        # index's ingest stamps, so a stalled ingest burns budget
        self.fresh = (
            FreshnessBurnTracker(
                fresh_max_age_s, objective=fresh_objective, windows=burn_windows
            )
            if fresh_max_age_s
            else None
        )
        spec = (
            serve_alert_spec(slo_ms, windows=burn.windows)
            if alert_spec == "serve_default"
            else alert_spec
        )
        if self.fresh is not None and alert_spec == "serve_default":
            # a declared freshness objective arms its burn alerts too
            spec = ",".join(s for s in (spec, fresh_alert_spec(windows=burn.windows)) if s)
        self._alerts = (
            AlertEngine(
                parse_rules(spec),
                workdir=workdir,
                process_index=self.replica_index,
                on_fire=self._on_alert,
            )
            if spec
            else None
        )
        # per-replica Perfetto stream for request spans: reuse the
        # installed process tracer when one exists (co-hosted with a
        # training driver); otherwise open our own replica stream next
        # to the training family, with a serve heartbeat anchor so
        # trace_merge can clock-align it
        self._tracer = get_tracer()
        self._own_tracer = None
        if self._tracer is None and workdir:
            self._own_tracer = self._tracer = Tracer(
                jsonl_path=os.path.join(
                    workdir, f"trace_events.s{self.replica_index}.jsonl"
                ),
                process_index=self.replica_index,
            )
        if workdir and self._tracer is not None:
            self._write_serve_anchor()
        # completed traces awaiting span emission — drained by the
        # metrics flusher thread, bounded so a stalled flusher degrades
        # to dropped spans rather than unbounded memory
        self._span_pending: deque = deque(maxlen=4 * flight_requests)
        self._lane = 0
        self._sink = sink
        self._flush_step = 0
        self._neighbor_flushes = 0
        self.ingested_rows = 0
        # one lock covers every index touch: a donated ingest write must
        # never invalidate a rows buffer a query is reading mid-flight.
        # tsan factory (analysis/tsan.py) so --sanitize-threads smoke
        # runs see its acquisition order; zero-cost otherwise
        self._index_lock = tsan.make_lock("serve.index")
        if warmup:
            engine.warmup()
            if index is not None:
                # the exact tier is always prepared: it is the oracle the
                # recall estimator scores against and the fallback tier
                modes = {"exact", neighbors_mode}
                index.prepare(
                    engine.buckets, self.neighbors_k,
                    nprobe=self.nprobe, modes=sorted(modes),
                )
                index.freeze()
                self._prepared_modes = modes
        if not hasattr(self, "_prepared_modes"):
            # warmup=False: the caller prepared the index; accept any mode
            self._prepared_modes = set(QUERY_MODES)
        self.batcher = ContinuousBatcher(
            self._run_batch,
            max_batch=engine.buckets[-1],
            slo_ms=slo_ms,
            metrics=self.metrics,
        )
        # drain flag (an Event: set from any thread — the /admin/drain
        # handler or the SIGTERM path — read by every healthz handler)
        self._draining = threading.Event()
        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                path = self.path.split("?")[0]
                record_route("GET", path)
                if path == "/healthz":
                    draining = server._draining.is_set()
                    self._json(200, {
                        "ok": not draining,
                        "warm": server.engine.recompiles_after_warmup == 0,
                        "draining": draining,
                        "replica": server.replica_index,
                        "platform": server.platform,
                    })
                elif path == "/stats":
                    self._json(200, server.stats())
                elif path == "/admin/model":
                    # served-model identity: the promotion pipeline and
                    # the router's skew gauge read this (and /stats)
                    with server._index_lock:
                        ingest_step = server.ingest_ckpt_step
                    self._json(200, {
                        "model_step": server.model_step,
                        "model_digest": server.model_digest,
                        "ingest_ckpt_step": ingest_step,
                        "replica": server.replica_index,
                    })
                elif path == "/debug/flight":
                    # on-demand flight dump: write the ring to disk when
                    # a workdir exists, and return the snapshot either
                    # way (the live-debugging path)
                    body = server.flight.snapshot()
                    if server.workdir:
                        body["dump_path"] = server.flight.dump(
                            server.workdir, reason="debug_request",
                            extra={"slo_ms": server.metrics.slo_ms},
                        )
                    self._json(200, body)
                else:
                    self.send_error(404)

            def do_POST(self):  # noqa: N802
                t_arrival = time.perf_counter()
                path, _, query = self.path.partition("?")
                record_route("POST", path)
                if path == "/ingest":
                    self._handle_ingest()
                    return
                if path == "/admin/drain":
                    self._handle_drain(query)
                    return
                if path not in ("/embed", "/neighbors"):
                    self.send_error(404)
                    return
                # chaos hook: kill@replica=i[:at=K] dies HERE, with the
                # request (and any coalesced riders) in flight — the
                # router's breaker + retry path must absorb the reset
                faults.maybe_kill_replica(server.replica_index)
                faults.maybe_slow("serve.ingress")
                try:
                    images = self._read_images()
                except ValueError as e:
                    self._json(400, {"error": str(e)})
                    return
                want_neighbors = path == "/neighbors"
                if want_neighbors and server.index is None:
                    self._json(503, {"error": "no embedding index attached"})
                    return
                mode = None
                if want_neighbors:
                    mode = _query_param(query, "mode")
                    if mode is not None and (
                        mode not in QUERY_MODES or mode not in server._prepared_modes
                    ):
                        self._json(400, {
                            "error": f"mode {mode!r} not prepared on this replica "
                            f"(serving: {sorted(server._prepared_modes)})"
                        })
                        return
                # adopt the propagated trace context when the fleet
                # front door sent one — this replica's waterfall becomes
                # a child of the router's dispatch-attempt span
                ctx = ctxprop.parse(
                    self.headers.get("X-Trace-Id"),
                    self.headers.get("X-Parent-Span"),
                )
                trace = None
                if server._ids is not None:
                    # backdated to arrival so the ingress stage covers
                    # the body read + parse above
                    trace = server._ids.new_trace(
                        images.shape[0], t0=t_arrival, ctx=ctx
                    )
                    trace.stamp("ingress", t_arrival, time.perf_counter())
                try:
                    fut = server.batcher.submit(
                        images, want_neighbors=want_neighbors, mode=mode, trace=trace
                    )
                    out = fut.result(timeout=30.0)
                except (BatcherClosedError, TimeoutError) as e:
                    self._json(503, {"error": str(e)})
                    return
                faults.maybe_slow("serve.respond")
                t_respond = time.perf_counter()
                body = {"embedding": out["embedding"].tolist()}
                if want_neighbors:
                    k = _query_k(query, server.neighbors_k)
                    eff = mode or server.neighbors_mode
                    body["indices"] = out[f"indices:{eff}"][:, :k].tolist()
                    body["scores"] = out[f"scores:{eff}"][:, :k].tolist()
                    body["mode"] = eff
                if trace is not None:
                    body["request_id"] = trace.req_id
                    if trace.trace_id is not None:
                        # in-band stitching: ship the stage waterfall (as
                        # stamped so far — respond lands in the router's
                        # net_recv slack) back to the router with the
                        # response, so the router can attribute this hop
                        # without waiting for an offline trace merge
                        body["trace"] = trace.waterfall()
                self._json(200, body)
                if trace is not None:
                    trace.stamp("respond", t_respond, time.perf_counter())
                    server._complete(trace)

            def _handle_drain(self, query):
                """Graceful drain of this replica, synchronously: the
                response does not land until every accepted request has
                flushed (or the timeout passed) — the caller can treat a
                200 with drained=true as 'safe to SIGTERM/restart'."""
                try:
                    timeout = float(_query_param(query, "timeout") or 30.0)
                except ValueError:
                    self._json(400, {"error": "bad timeout parameter"})
                    return
                drained = server.drain(timeout=timeout)
                self._json(200, {
                    "draining": True,
                    "drained": drained,
                    "replica": server.replica_index,
                })

            def _handle_ingest(self):
                """FIFO-ingest a raw f32 row block into the live index —
                the wire the streaming updater (scripts/serve_ingest.py)
                pushes fresh training-queue rows over."""
                if server.index is None:
                    self._json(503, {"error": "no embedding index attached"})
                    return
                # chaos hook: delay@site=ingest stalls the freshness
                # pipeline HERE (before the body read, outside the index
                # lock) — row ages keep growing while the block is stuck,
                # which is exactly what the fresh-burn alert must catch
                faults.maybe_delay("ingest")
                try:
                    shape_hdr = self.headers.get("X-Rows-Shape", "")
                    try:
                        n, d = (int(s) for s in shape_hdr.split(","))
                    except ValueError:
                        raise ValueError(f"bad X-Rows-Shape header {shape_hdr!r}")
                    # propagated provenance header: which training
                    # checkpoint step produced these rows
                    ckpt_hdr = self.headers.get("X-Ckpt-Step")
                    ckpt_step = None
                    if ckpt_hdr:
                        try:
                            ckpt_step = int(ckpt_hdr)
                        except ValueError:
                            raise ValueError(
                                f"bad X-Ckpt-Step header {ckpt_hdr!r}"
                            )
                    length = int(self.headers.get("Content-Length", 0))
                    if length != n * d * 4:
                        raise ValueError(
                            f"Content-Length {length} != n*d*4 = {n * d * 4}"
                        )
                    # the socket read stays OUTSIDE the lock (JX013: no
                    # blocking I/O under _index_lock); the dim check and
                    # the response counters move INSIDE it so concurrent
                    # ingests can't interleave a torn snapshot (JX012)
                    rows = np.frombuffer(
                        self.rfile.read(length), np.float32
                    ).reshape(n, d)
                    with server._index_lock:
                        if d != server.index.dim:
                            raise ValueError(
                                f"row dim {d} != index dim {server.index.dim}"
                            )
                        server.index.add(rows)
                        server.ingested_rows += n
                        if ckpt_step is not None:
                            server.ingest_ckpt_step = ckpt_step
                        index_rows = server.index.count
                        total_ingested = server.ingested_rows
                except ValueError as e:
                    self._json(400, {"error": str(e)})
                    return
                self._json(200, {
                    "ingested": n,
                    "index_rows": index_rows,
                    "total_ingested": total_ingested,
                })

            def _read_images(self) -> np.ndarray:
                shape_hdr = self.headers.get("X-Image-Shape", "")
                try:
                    shape = tuple(int(s) for s in shape_hdr.split(","))
                except ValueError:
                    raise ValueError(f"bad X-Image-Shape header {shape_hdr!r}")
                if len(shape) != 4:
                    raise ValueError("X-Image-Shape must be 'n,h,w,c'")
                n = int(self.headers.get("Content-Length", 0))
                expected = 1
                for s in shape:
                    expected *= s
                if n != expected:
                    raise ValueError(
                        f"Content-Length {n} != prod(X-Image-Shape) {expected}"
                    )
                return np.frombuffer(self.rfile.read(n), np.uint8).reshape(shape)

            def _json(self, code: int, obj: dict) -> None:
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # silence per-request stderr lines
                pass

        resolved = resolve_serve_port(port, metrics_port, process_index)
        self._server = _QuietHTTPServer((host, resolved), Handler)
        self.host = host
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="serve_http", daemon=True
        )
        self._thread.start()
        self._stop = threading.Event()
        self._flusher = threading.Thread(
            target=self._flush_loop, args=(float(metrics_flush_s),),
            name="serve_metrics_flush", daemon=True,
        )
        self._flusher.start()

    # -- request path ----------------------------------------------------

    def _run_batch(self, images, want_neighbors, modes=(), *, stages=None):
        """Batcher thread body: ONE padded engine execution per flush,
        then one index query per requested tier on the same features
        (the scans are small matmuls next to the encoder forward);
        /embed riders just drop the extra keys at scatter. With an
        approximate default tier, every `recall_sample_every`-th
        neighbors flush also runs the exact oracle and records the
        top-k overlap (`serve/recall_estimate`). `stages` (keyword-only,
        the batcher's request-trace contract) splits engine_execute /
        index_query seconds for the waterfall."""
        if want_neighbors and self.index is not None:
            requested = {self.neighbors_mode} | set(modes)
            approx = next(
                (m for m in (self.neighbors_mode, *sorted(requested))
                 if m.startswith("ivf")),
                None,
            )
            sample_recall = False
            if approx is not None and self.recall_sample_every > 0:
                self._neighbor_flushes += 1
                if self._neighbor_flushes % self.recall_sample_every == 0:
                    sample_recall = True
                    requested.add("exact")
            with self._index_lock:
                emb, per_mode, executed = self.engine.embed_and_query_modes(
                    images, self.index, self.neighbors_k,
                    modes=tuple(sorted(requested)), nprobe=self.nprobe,
                    stages=stages,
                )
            if sample_recall:
                _, exact_idx = per_mode["exact"]
                _, approx_idx = per_mode[approx]
                k = exact_idx.shape[1]
                overlap = np.asarray([
                    len(set(exact_idx[i]) & set(approx_idx[i]))
                    for i in range(exact_idx.shape[0])
                ])
                self.metrics.record_recall(float(overlap.mean()) / k)
            results = {"embedding": emb}
            for m, (scores, idx) in per_mode.items():
                results[f"scores:{m}"] = scores
                results[f"indices:{m}"] = idx
            return results, executed
        emb, executed = self.engine.embed(images, stages=stages)
        return {"embedding": emb}, executed

    # -- request-scoped observability ------------------------------------

    def _complete(self, trace) -> None:
        """A request finished responding: file its waterfall in the
        flight ring and queue it for span emission (both O(1); the
        rendering happens on the flusher thread)."""
        self.flight.record_request(trace.waterfall())
        self._span_pending.append(trace)

    def _drain_spans(self) -> None:
        """Flusher-thread side of `_complete`: render queued request
        waterfalls as Perfetto spans on the virtual request lanes."""
        if self._tracer is None:
            self._span_pending.clear()
            return
        while True:
            try:
                trace = self._span_pending.popleft()
            except IndexError:
                break
            emit_request_spans(self._tracer, trace, self._lane)
            self._lane += 1  # mocolint: disable=JX012  (flusher-thread only during the run; close() joins the flusher BEFORE its final _write_metrics call, so the two writers are join-serialized, never concurrent)

    def _on_alert(self, alert: dict) -> None:
        """AlertEngine on_fire hook: an SLO-burn (or any serving) alert
        dumps the flight recorder AT the firing edge and lands an
        in-band alert event line, so scrapers see `moco_alert_<rule>`
        and the postmortem file already exists when a human arrives."""
        if self.workdir:
            try:
                self.flight.dump(
                    self.workdir,
                    reason=f"alert:{alert['rule']}",
                    extra={
                        "alert": alert,
                        "slo_ms": self.metrics.slo_ms,
                        "replica": self.replica_index,
                    },
                )
            except Exception as e:  # the dump must never take serving down
                print(f"WARNING: flight dump failed: {e!r}", flush=True)
        if self._sink is not None:
            self._sink.write(
                self._flush_step,
                {
                    "event": "alert",
                    "alert": alert["rule"],
                    "severity": alert["severity"],
                    f"alert/{alert['rule']}": 1.0,
                },
            )

    def _write_serve_anchor(self) -> None:
        """Atomic `heartbeat.s<replica>.json` with the tracer's wall
        anchor — scripts/trace_merge.py reads it to clock-align this
        replica's request spans with the training timeline."""
        rec = {
            "process": self.replica_index,
            "role": "serve",
            "host": socket.gethostname(),
            "pid": os.getpid(),
            "time": time.time(),
            "trace_wall_t0": self._tracer.wall_t0,
        }
        path = os.path.join(self.workdir, f"heartbeat.s{self.replica_index}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, path)

    # -- metrics ---------------------------------------------------------

    def stats(self) -> dict:
        # the whole snapshot sits under _index_lock so the gauge line is
        # CONSISTENT: index_rows/ingested_rows/ivf gauges can't interleave
        # with a concurrent /ingest mid-read (JX012). This nests
        # serve.index -> serve.metrics (payload takes the metrics lock
        # inside) — the one sanctioned order; tsan's runtime order graph
        # watches it and the deadlock@site chaos leg inverts it on purpose.
        with self._index_lock:
            return self._stats_locked()

    def _stats_locked(self) -> dict:
        out = self.metrics.payload()
        out["serve/recompiles_after_warmup"] = self.engine.recompiles_after_warmup
        # retrieval-tier gauges: which path answers /neighbors by default
        # (schema: numbers only — nprobe null on exact tiers) and whether
        # scoring runs quantized anywhere (index int8 mirror or engine PTQ)
        out["serve/nprobe"] = (
            (self.nprobe or (self.index._ivf or {}).get("nprobe"))
            if self.index is not None and self.neighbors_mode.startswith("ivf")
            else None
        )
        out["serve/int8"] = int(
            self.neighbors_mode.endswith("_i8") or getattr(self.engine, "int8", False)
        )
        # engine quantization tier as a scraped gauge: 0=off, 1=w8
        # (weight-only PTQ), 2=w8a8 (activation-quantized int8)
        out["serve/quant_tier"] = {"off": 0, "w8": 1, "w8a8": 2}.get(
            getattr(self.engine, "quant", "off"), 0
        )
        # served-model identity + ingest provenance (obs/quality.py):
        # the model plane's version gauges — the router's skew gauge
        # and the promotion pipeline's evidence both read these
        out["serve/model_step"] = self.model_step
        out["serve/model_digest"] = self.model_digest
        out["serve/ingest_ckpt_step"] = self.ingest_ckpt_step
        if self.fresh is not None:
            out.update(self.fresh.payload())
        if self.index is not None:
            ages = self.index.row_age_stats()
            out["serve/row_age_max_s"] = ages["row_age_max_s"]
            out["serve/row_age_mean_s"] = ages["row_age_mean_s"]
            out["serve/index_rows"] = self.index.count
            out["serve/ingested_rows"] = self.ingested_rows
            out["serve/recompiles_after_warmup"] += self.index.recompiles_after_warmup
            # coarse-quantizer health (ROADMAP's future re-fit trigger):
            # rows the IVF could not place (served exactly instead) and
            # mean cell fill — null until train_ivf has run
            ivf_stats = self.index.ivf_stats()
            out["serve/ivf_spill"] = (
                ivf_stats["spilled"] if ivf_stats.get("trained") else None
            )
            out["serve/ivf_occupancy"] = (
                ivf_stats["occupancy"] if ivf_stats.get("trained") else None
            )
        return out

    def _flush_loop(self, interval: float) -> None:
        while not self._stop.wait(interval):
            self._write_metrics()

    def _write_metrics(self) -> None:
        """One off-path observability turn: snapshot the gauges, feed
        the flight ring + alert engine (a fired rule dumps the ring via
        `_on_alert`), render pending request spans, then fan the line
        out to the sink."""
        self._flush_step += 1  # mocolint: disable=JX012  (same join-serialization as _lane: the alert hook fires ON the flusher thread, and close() joins the flusher before the final flush — one writer at a time by construction)
        try:
            if self.fresh is not None:
                # one freshness observation per flush: the index's max
                # row age vs the declared objective (None = empty index,
                # not stale). Sampled under the index lock, recorded
                # outside it (obs.slo after serve.index is NOT a
                # sanctioned nesting — keep them disjoint).
                age = None
                if self.index is not None:
                    with self._index_lock:
                        age = self.index.row_age_stats()["row_age_max_s"]
                self.fresh.record(age)
            payload = self.stats()
            self.flight.record_metrics(self._flush_step, payload)
            if self._alerts is not None:
                self._alerts.observe(self._flush_step, payload)
            self._drain_spans()
            if self._sink is not None:
                self._sink.write(self._flush_step, payload)
        except Exception as e:  # metrics must never take serving down
            print(f"WARNING: serve metrics sink failed: {e!r}", flush=True)

    # -- lifecycle -------------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful shutdown, phase one: healthz flips not-ok (a fleet
        router stops dispatching here), then the batcher drains — every
        request already accepted is flushed, not failed. The HTTP server
        itself stays up (healthz must answer mid-drain); follow with
        `close()`. Idempotent; True = the flush finished in time. This
        is the server half of the SIGTERM path (`replica_main`) and of
        `POST /admin/drain`."""
        already = self._draining.is_set()
        self._draining.set()
        if already and self.batcher.closed:
            return True  # second drain call: nothing left to flush
        return self.batcher.drain(timeout=timeout)

    def close(self) -> None:
        """Shut down HTTP, batcher, and flusher; join all three threads
        (the obs/sinks.py PrometheusSink close discipline). A final
        metrics flush lands the run's last gauges in the sink."""
        self._stop.set()
        self._flusher.join(timeout=5.0)
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5.0)
        self.batcher.close()
        self._write_metrics()
        if self._alerts is not None:
            self._alerts.close()
        if self._own_tracer is not None:
            self._own_tracer.close()


def _query_param(query: str, name: str) -> str | None:
    for part in query.split("&"):
        if part.startswith(name + "="):
            return part[len(name) + 1 :] or None
    return None


def _query_k(query: str, default: int) -> int:
    val = _query_param(query, "k")
    if val is not None:
        try:
            return max(1, min(int(val), default))
        except ValueError:
            pass
    return default


__all__ = [
    "DEFAULT_NEIGHBORS_K",
    "DEFAULT_RECALL_SAMPLE_EVERY",
    "ServeServer",
    "resolve_serve_port",
]
