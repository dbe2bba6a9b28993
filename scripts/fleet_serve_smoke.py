#!/usr/bin/env python
"""Serving-fleet chaos smoke: the router + supervisor story (ISSUE 16),
asserted hard.

    JAX_PLATFORMS=cpu python scripts/fleet_serve_smoke.py [--workdir DIR]

The story, executable:

1. a toy pretraining checkpoint is written (serve_smoke's maker) and a
   `ReplicaSupervisor` boots THREE `replica_main` processes from it,
   each binding its pre-claimed port only after AOT warmup;
2. per-replica chaos is planted through the supervisor's `extra_env`:
   replica 1 carries `kill@replica=1:at=5` (sudden `os._exit` mid-
   request on its 5th data POST) and replica 2 carries a PERMANENT
   `slow@site=serve.engine_execute:ms=2500` (every request there
   outlives the router's hedge delay — the deterministic tail);
3. a `FleetRouter` fronts the fleet and a mixed `/embed` +
   `/neighbors` burst fires from concurrent closed-loop clients —
   asserts ZERO failed client requests: the kill is absorbed by
   breaker + bounded retry (counted: `fleet_serve/retries`,
   `fleet_serve/breaker_trips`), the injected tail by hedging
   (counted: `fleet_serve/hedges`, `fleet_serve/hedge_wins` — first
   success wins), and every response's `replica` attribution matches
   its replica-minted `r<i>-` request id;
4. the supervisor's monitor respawns the corpse (exactly one `exit`
   event with rc=KILL_EXIT_CODE, reason "crash"), scrubs the kill rule
   from the reborn env, waits out the AOT re-warmup, re-plays the warm
   rows through `/ingest` (the reborn replica reports them in
   `serve/ingested_rows` — a WARM rejoin, not an empty index), and the
   router re-admits it into live rotation;
5. the drain leg: `POST /admin/drain?replica=0` under live traffic —
   dispatch stops, in-flight waits out, the supervisor restarts the
   replica gracefully (SIGTERM → batcher drain → respawn → re-warm),
   the router re-admits on healthy, and NOT ONE background request
   failed across the whole cycle;
6. the fanout-ingest leg: `scripts/serve_ingest.py`'s `--fanout` path
   discovers the topology from `/admin/replicas` and lands a fresh
   block on EVERY replica (per-replica `ingest.post.r<i>` retry
   sites);
7. the tracing leg (ISSUE 18): every burst response carries the
   router-minted `trace_id`, and the router's `/debug/flight` ring must
   hold a stitched multi-hop waterfall for 100% of them — with the
   critical-path hop sum (obs/critpath.py) within eps of the CLIENT-
   measured wall, and every 200's winning attempt joined to a real
   replica waterfall. After teardown `scripts/trace_merge.py` merges
   the router stream (pid 200) with every replica stream into
   `merged_fleet_trace.json` — flow arrows (`ph:"s"`/`ph:"f"`) must
   link router attempts to replica requests — and the offline
   `stitch_traces()` twin must reproduce stitched records from the
   on-disk artifacts alone;
8. the promotion leg (ISSUE 19): every replica declares a freshness
   objective and serves its model identity (step + params digest), so
   the router's `fleet_serve/model_skew` gauge is live. A SKEWED
   candidate checkpoint (a re-initialized encoder posing as step 1)
   must be REJECTED by the gate battery — the append-only
   `promotions.jsonl` ledger names the failing gate with its measured
   value vs floor — and a compatible candidate must clear the gates
   and roll out through `POST /admin/promote` one replica at a time
   under live traffic with ZERO dropped requests, the skew gauge
   visibly passing through >= 1 mid-rollout and landing back at 0 with
   every replica reporting the candidate's step and digest;
9. the freshness leg: an in-process replica with a 1s freshness
   objective ingests a block, then a `delay@site=ingest` fault stalls
   the next block inside the handler while the resident rows age past
   the objective — `serve/row_age_max_s` breaches,
   `serve/fresh_burn_rate_5s` climbs over the fast-burn threshold, and
   the `fresh_burn_fast` alert fires (flight dump attached), all on a
   schema-strict metrics stream;
10. final gates: `fleet_serve/burn_rate_60s` < 1.0 (the chaos never
    exhausted the client-observed error budget), the flushed
    `fleet_serve/*` metrics lines schema-strict (including the
    `fleet_serve/critpath_<hop>_ms` family), and mocolint clean on the
    fleet + promotion modules (JX011/JX012/JX013 — the threaded router
    must lint clean, not just run clean).

CI runs this in the tier-1 job; the router metrics stream, the merged
fleet trace, the router flight dump, the promotion ledger, the summary
JSON, and the supervisor event log upload as artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

NUM_REPLICAS = 3
KILLED_REPLICA = 1
SLOWED_REPLICA = 2
DRAINED_REPLICA = 0
BUCKETS = (1, 8)
REQUEST_SIZES = (1, 2, 4)
BURST_REQUESTS = 72
BURST_CLIENTS = 6
WARM_ROWS = 32
# per-replica batcher SLO (coalescing deadline) vs the router's client-
# observed SLO: same two-knob split as serve_smoke — the router bar is
# generous because its latency includes a replica flush AND (for the
# slowed replica) a full hedge delay before the fast twin answers.
SERVER_SLO_MS = float(os.environ.get("FLEET_SMOKE_SERVER_SLO_MS", 1000.0))
ROUTER_SLO_MS = float(os.environ.get("FLEET_SMOKE_ROUTER_SLO_MS", 4000.0))
# hedge floor: above the healthy replicas' worst latency (~one flush),
# well under the slowed replica's injected 2.5s stage — healthy traffic
# never hedges, slowed traffic always does
HEDGE_MIN_MS = float(os.environ.get("FLEET_SMOKE_HEDGE_MIN_MS", 1500.0))
SLOW_MS = 2500.0
KILL_AT = 5  # replica 1 dies handling its 5th data POST — mid-burst
RESPAWN_DEADLINE_S = 420.0
DRAIN_DEADLINE_S = 420.0
# freshness SLO declared fleet-wide: generous vs the smoke's own wall
# time so the MAIN fleet never burns it — the tight-objective burn
# story runs in the dedicated freshness leg instead
FRESH_MAX_AGE_S = 600.0
# promotion leg: probe batch for the gate battery, plus the collapse
# floor the UNTRAINED toy encoder actually clears (~0.08 measured —
# the 0.25 default calibrates to trained encoders; the floor is a
# deployment knob and the smoke's deployment is a random init)
PROMOTE_PROBES = 16
PROMOTE_FEATURE_STD_FLOOR = 0.05
# freshness leg: a 1s objective, and an ingest stall long enough that
# the resident rows age past it while the handler is stuck
STALL_FRESH_MAX_AGE_S = 1.0
STALL_DELAY_S = 2.5
STALL_DEADLINE_S = 60.0
# stitched hop-sum vs client wall: relative eps dominates at the smoke's
# realistic latencies; the absolute floor covers the fast path
TRACE_EPS_FRAC = 0.15
TRACE_EPS_FLOOR_MS = 25.0
STITCH_DEADLINE_S = 120.0  # hedge losers (the 2.5s lane) must land first


def _get(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _make_compatible_candidate(live_dir: str, out_dir: str, step: int = 1) -> None:
    """The live encoder nudged by a uniform 1e-3 weight scale, saved as
    a step-`step` checkpoint: the params digest changes (the rollout's
    landing signal needs a NEW digest to wait on) but the normalized
    embeddings barely move — the 'one more epoch' stand-in the gate
    battery must wave through."""
    import jax

    from moco_tpu.lincls import restore_pretrain_state
    from moco_tpu.utils.checkpoint import CheckpointManager
    from moco_tpu.utils.config import config_to_dict

    state, config = restore_pretrain_state(live_dir)
    nudge = lambda t: jax.tree_util.tree_map(lambda x: x * (1.0 + 1e-3), t)
    state = state.replace(
        params_q=nudge(state.params_q), params_k=nudge(state.params_k)
    )
    mgr = CheckpointManager(out_dir)
    mgr.save(
        step, state,
        extra={"epoch": 0, "config": config_to_dict(config), "num_data": 1},
        force=True,
    )
    mgr.close()


def _freshness_stall_leg(workdir: str) -> dict:
    """The freshness-SLO story at smoke scale, on a dedicated in-process
    replica (its own `workdir/freshness` stream — a tight 1s objective
    on the MAIN fleet would burn on wall time alone): ingest a block,
    watch it stay fresh, then stall the next `/ingest` inside the
    handler with `delay@site=ingest` while the resident rows age out —
    the fresh-burn gauge must breach and the `fresh_burn_fast` alert
    must fire."""
    import numpy as np

    from moco_tpu.obs import schema
    from moco_tpu.obs.alerts import read_alerts
    from moco_tpu.obs.sinks import JsonlSink
    from moco_tpu.obs.slo import DEFAULT_FAST_BURN
    from moco_tpu.serve.index import EmbeddingIndex
    from moco_tpu.serve.server import ServeServer
    from moco_tpu.utils import faults

    class _IngestOnlyEngine:
        """Engine-shaped stub: this leg exercises the ingest/freshness
        plane, never the embed path."""

        buckets = (1,)
        recompiles_after_warmup = 0
        num_features = 4
        image_size = 4

        def warmup(self):
            pass

        def embed(self, images, stages=None):
            emb = np.zeros((images.shape[0], 4), np.float32)
            return emb, [(images.shape[0], images.shape[0])]

    wd = os.path.join(workdir, "freshness")
    os.makedirs(wd, exist_ok=True)
    sink = JsonlSink(wd)
    server = ServeServer(
        _IngestOnlyEngine(),
        index=EmbeddingIndex(64, 4),
        port=0,
        sink=sink,
        metrics_flush_s=0.1,
        workdir=wd,
        fresh_max_age_s=STALL_FRESH_MAX_AGE_S,
        burn_windows=(5, 60),
    )
    stall_base = f"http://127.0.0.1:{server.port}"

    def _ingest(block, step: int) -> None:
        req = urllib.request.Request(
            stall_base + "/ingest",
            data=block.astype(np.float32).tobytes(),
            headers={
                "X-Rows-Shape": ",".join(map(str, block.shape)),
                "X-Ckpt-Step": str(step),
            },
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            r.read()

    try:
        rng = np.random.default_rng(7)
        _ingest(rng.standard_normal((8, 4)), 0)
        time.sleep(0.4)  # a few fresh observations land
        st = _get(stall_base + "/stats")
        assert st["serve/fresh_max_age_s"] == STALL_FRESH_MAX_AGE_S, st
        assert (st.get("serve/fresh_burn_rate_5s") or 0.0) == 0.0, (
            f"freshness burned before the stall: {st}"
        )
        # the stall: the NEXT block sticks in the handler while the
        # resident rows age past the declared objective
        faults.install(f"delay@site=ingest:seconds={STALL_DELAY_S}")
        try:
            _ingest(rng.standard_normal((8, 4)), 1)
        finally:
            faults.clear()
        deadline = time.monotonic() + STALL_DEADLINE_S
        burn, fired = None, []
        while time.monotonic() < deadline:
            st = _get(stall_base + "/stats")
            burn = st.get("serve/fresh_burn_rate_5s")
            fired = [
                a for a in read_alerts(os.path.join(wd, "alerts.jsonl"))
                if a["rule"] == "fresh_burn_fast"
            ]
            if burn is not None and burn > DEFAULT_FAST_BURN and fired:
                break
            time.sleep(0.1)
        assert burn is not None and burn > DEFAULT_FAST_BURN, (
            f"the ingest stall never breached the fresh burn gauge: {burn}"
        )
        assert fired, "fresh_burn_fast never fired despite the breach"
        st = _get(stall_base + "/stats")
        assert st["serve/row_age_max_s"] > STALL_FRESH_MAX_AGE_S, st
        assert st["serve/ingest_ckpt_step"] == 1, st
    finally:
        server.close()
        sink.close()
    problems = schema.validate_file(os.path.join(wd, "metrics.jsonl"))
    assert not problems, f"freshness leg schema violations: {problems[:5]}"
    return {
        "fresh_burn_rate_5s": burn,
        "fresh_alerts": len(fired),
        "row_age_max_s": st["serve/row_age_max_s"],
    }


def run_smoke(workdir: str, contract_coverage: bool = False) -> dict:
    import numpy as np

    import serve_smoke
    from moco_tpu.analysis import contracts as contract_cov
    from moco_tpu.obs import critpath, schema
    from moco_tpu.obs.sinks import JsonlSink
    from moco_tpu.serve.fleet import ReplicaSupervisor
    from moco_tpu.serve.router import FleetRouter
    from moco_tpu.utils import contracts as decl
    from moco_tpu.utils.faults import KILL_EXIT_CODE

    ckpt_dir = os.path.join(workdir, "toy_ckpt")
    serve_smoke.make_toy_checkpoint(ckpt_dir)
    rng = np.random.default_rng(0)
    warm_rows = rng.standard_normal((WARM_ROWS, 16)).astype(np.float32)

    recorder = None
    if contract_coverage:
        # plant the env var BEFORE the supervisor spawns: replicas
        # inherit it, install their own recorder, and dump
        # replica<i>/contract_coverage.json on graceful exit; this
        # (router) process records its own routes/validators directly
        os.environ["MOCO_CONTRACT_COVERAGE"] = "1"
        recorder = contract_cov.install_recorder()

    sup = ReplicaSupervisor(
        NUM_REPLICAS,
        ckpt_dir=ckpt_dir,
        workdir=workdir,
        buckets=BUCKETS,
        slo_ms=SERVER_SLO_MS,
        extra_env={
            KILLED_REPLICA: {"MOCO_FAULTS": f"kill@replica={KILLED_REPLICA}:at={KILL_AT}"},
            SLOWED_REPLICA: {
                "MOCO_FAULTS": f"slow@site=serve.engine_execute:ms={SLOW_MS:.0f}"
            },
        },
        warm_rows_fn=lambda: warm_rows,
        boot_timeout_s=RESPAWN_DEADLINE_S,
        monitor_interval_s=0.25,
        restart_backoff_s=0.5,
        # every replica declares the freshness objective: the fresh-burn
        # gauge family + row-age gauges go live on every /stats
        fresh_max_age_s=FRESH_MAX_AGE_S,
    )
    print(f"booting {NUM_REPLICAS} replicas (AOT warmup each)...", flush=True)
    t_boot = time.monotonic()
    sup.start()
    print(f"fleet healthy in {time.monotonic() - t_boot:.1f}s: {sup.urls()}", flush=True)

    sink = JsonlSink(workdir)
    router = FleetRouter(
        supervisor=sup,
        slo_ms=ROUTER_SLO_MS,
        slo_objective=0.9,
        sink=sink,
        metrics_flush_s=0.5,
        health_interval_s=0.25,
        retry_attempts=4,
        retry_base_delay_s=0.25,
        hedge_min_ms=HEDGE_MIN_MS,
        max_inflight=32,
        # one connection-reset is a trip: the smoke wants the breaker
        # OBSERVABLY in the story (fleet_serve/breaker_trips >= 1), and
        # a killed replica fails hard anyway
        breaker_fail_threshold=1,
        breaker_cooldown_s=1.0,
        drain_timeout_s=60.0,
        readmit_timeout_s=DRAIN_DEADLINE_S,
        # distributed tracing: per-router Perfetto stream + clock anchor
        # land next to the replicas' streams for the offline merge
        workdir=workdir,
    )
    base = f"http://127.0.0.1:{router.port}"
    canned = {
        n: rng.integers(0, 255, (n, serve_smoke.IMAGE_SIZE, serve_smoke.IMAGE_SIZE, 3),
                        np.uint8)
        for n in REQUEST_SIZES
    }
    failures: list[str] = []
    replicas_seen: set = set()
    traced: dict = {}  # trace_id -> client-measured wall ms (burst only)
    lock = threading.Lock()

    def post(path: str, imgs, record_trace: bool = False) -> dict:
        req = urllib.request.Request(
            base + path,
            data=imgs.tobytes(),
            headers={"X-Image-Shape": ",".join(map(str, imgs.shape))},
        )
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        if record_trace and isinstance(out, dict) and out.get("trace_id"):
            with lock:
                traced[out["trace_id"]] = (time.perf_counter() - t0) * 1e3
        return out

    def check_response(out: dict, n: int) -> None:
        emb = np.asarray(out["embedding"], np.float32)
        if emb.shape[0] != n:
            raise ValueError(f"expected {n} rows, got {emb.shape}")
        # replica attribution: the router's blame matches the replica-
        # scoped request id the replica itself minted
        rid, rep = out["request_id"], out["replica"]
        if not rid.startswith(f"r{rep}-"):
            raise ValueError(f"attribution mismatch: id {rid} vs replica {rep}")
        with lock:
            replicas_seen.add(rep)

    def client(ci: int, num: int) -> None:
        crng = np.random.default_rng(1000 + ci)
        for j in range(num):
            n = int(crng.choice(REQUEST_SIZES))
            path = "/neighbors?k=3" if (ci + j) % 2 == 0 else "/embed"
            try:
                check_response(post(path, canned[n], record_trace=True), n)
            except Exception as e:
                with lock:
                    failures.append(f"client {ci} req {j}: {e!r}")
                return

    summary: dict = {"workdir": workdir}
    try:
        # -- the chaos burst: kill@replica fires mid-burst -----------------
        print(f"burst: {BURST_REQUESTS} requests from {BURST_CLIENTS} clients "
              f"(kill@replica={KILLED_REPLICA}:at={KILL_AT} armed, replica "
              f"{SLOWED_REPLICA} permanently slowed {SLOW_MS:.0f}ms)", flush=True)
        t0 = time.monotonic()
        threads = [
            threading.Thread(target=client, args=(ci, BURST_REQUESTS // BURST_CLIENTS))
            for ci in range(BURST_CLIENTS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        burst_s = time.monotonic() - t0
        assert not failures, f"{len(failures)} requests failed: {failures[:5]}"
        print(f"burst clean in {burst_s:.1f}s; replicas seen: {sorted(replicas_seen)}",
              flush=True)

        # -- 100% stitched traces, hop sums within eps of client walls -----
        assert len(traced) == BURST_REQUESTS, (
            f"only {len(traced)}/{BURST_REQUESTS} responses carried a trace_id"
        )
        deadline = time.monotonic() + STITCH_DEADLINE_S
        flight_body: dict = {}
        flight_recs: dict = {}
        while time.monotonic() < deadline:
            # /debug/flight drains pending traces; held-back hedge
            # losers (the 2.5s slowed lane) land within their grace
            flight_body = _get(base + "/debug/flight", timeout=60)
            flight_recs = {
                r["trace_id"]: r
                for r in flight_body.get("requests", ())
                if r.get("trace_id")
            }
            if set(traced) <= set(flight_recs):
                break
            time.sleep(1.0)
        missing_traces = sorted(set(traced) - set(flight_recs))
        assert not missing_traces, (
            f"{len(missing_traces)}/{len(traced)} burst traces never "
            f"stitched into the flight ring: {missing_traces[:3]}"
        )
        hop_errs = []
        hedged_traces = retried_traces = 0
        for tid, wall_ms in traced.items():
            rec = flight_recs[tid]
            attr = critpath.attribute(rec)
            ssum = sum(attr["hops"].values())
            eps = max(TRACE_EPS_FRAC * wall_ms, TRACE_EPS_FLOOR_MS)
            if abs(ssum - wall_ms) > eps:
                hop_errs.append(
                    f"{tid}: hop sum {ssum:.1f}ms vs client wall "
                    f"{wall_ms:.1f}ms (eps {eps:.1f}ms)"
                )
            winner = next(
                (a for a in rec["attempts"] if a.get("winner")), None
            )
            if rec.get("status") == 200 and (
                winner is None or not winner.get("remote")
            ):
                hop_errs.append(
                    f"{tid}: 200 with no replica waterfall stitched in"
                )
            hedged_traces += 1 if attr["hedged"] else 0
            retried_traces += 1 if attr["retry_failed_ms"] else 0
        assert not hop_errs, (
            f"{len(hop_errs)} stitched traces failed the hop-sum/"
            f"stitching gate: {hop_errs[:5]}"
        )
        print(f"tracing: {len(traced)} burst traces 100% stitched "
              f"({hedged_traces} hedged, {retried_traces} with a failed "
              f"attempt on the critical path); hop sums within eps of "
              f"client walls", flush=True)
        summary["router_flight_dump"] = flight_body.get("dump_path")

        # -- the corpse respawns, scrubbed and WARM ------------------------
        deadline = time.monotonic() + RESPAWN_DEADLINE_S
        while time.monotonic() < deadline:
            kinds = [(e["kind"], e["replica"]) for e in sup.events()]
            if ("restart", KILLED_REPLICA) in kinds:
                break
            time.sleep(0.25)
        events = sup.events()
        crashes = [
            e for e in events
            if e["kind"] == "exit" and e["replica"] == KILLED_REPLICA
            and e.get("reason") == "crash"
        ]
        assert crashes, f"no crash event for replica {KILLED_REPLICA}: {events}"
        assert crashes[0]["rc"] == KILL_EXIT_CODE, crashes
        warms = [
            e for e in events if e["kind"] == "warm" and e["replica"] == KILLED_REPLICA
        ]
        assert warms and warms[0]["rows"] == WARM_ROWS, warms
        reborn = _get(sup.url(KILLED_REPLICA) + "/healthz")
        assert reborn["ok"] and reborn["warm"], reborn
        reborn_stats = _get(sup.url(KILLED_REPLICA) + "/stats")
        assert reborn_stats["serve/ingested_rows"] == WARM_ROWS, (
            f"reborn replica not warm: {reborn_stats.get('serve/ingested_rows')}"
        )
        print(f"replica {KILLED_REPLICA} respawned warm "
              f"(rc={crashes[0]['rc']}, {WARM_ROWS} rows replayed)", flush=True)
        # ...and the ROUTER re-admits it into live rotation
        deadline = time.monotonic() + 60.0
        readmitted = False
        while time.monotonic() < deadline and not readmitted:
            out = post("/embed", canned[1])
            readmitted = out["replica"] == KILLED_REPLICA
        assert readmitted, "reborn replica never took router traffic again"

        # -- drain/restart under live traffic: zero dropped ---------------
        stop = threading.Event()
        drain_failures: list[str] = []

        def background() -> None:
            while not stop.is_set():
                try:
                    check_response(post("/embed", canned[1]), 1)
                except Exception as e:
                    with lock:
                        drain_failures.append(repr(e))
                time.sleep(0.05)

        bg = [threading.Thread(target=background) for _ in range(2)]
        for t in bg:
            t.start()
        try:
            time.sleep(1.0)
            req = urllib.request.Request(
                base + f"/admin/drain?replica={DRAINED_REPLICA}", data=b""
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 202 and json.loads(r.read())["accepted"]
            deadline = time.monotonic() + DRAIN_DEADLINE_S
            snap = None
            while time.monotonic() < deadline:
                snap = next(
                    s for s in _get(base + "/admin/replicas")["replicas"]
                    if s["index"] == DRAINED_REPLICA
                )
                if not snap["draining"] and snap["healthy"]:
                    break
                time.sleep(0.5)
            assert snap and snap["healthy"] and not snap["draining"], (
                f"replica {DRAINED_REPLICA} never rejoined after drain: {snap}"
            )
        finally:
            stop.set()
            for t in bg:
                t.join(timeout=60)
        assert not drain_failures, (
            f"{len(drain_failures)} requests failed during the drain/restart "
            f"cycle: {drain_failures[:5]}"
        )
        graceful = [
            e for e in sup.events()
            if e["kind"] == "exit" and e["replica"] == DRAINED_REPLICA
            and e.get("reason") == "restart"
        ]
        assert graceful, "drain leg produced no graceful restart event"
        print(f"drain/restart of replica {DRAINED_REPLICA} clean under live traffic",
              flush=True)

        # -- fanout ingest: the block reaches EVERY replica ----------------
        import serve_ingest

        fresh = rng.standard_normal((10, 16)).astype(np.float32)
        # the rows' source checkpoint step rides the X-Ckpt-Step header:
        # every replica's serve/ingest_ckpt_step gauge picks it up
        results = serve_ingest.fanout_rows(base, fresh, ckpt_step=0)
        assert set(results) == set(range(NUM_REPLICAS)) and all(
            v is not None for v in results.values()
        ), f"fanout dropped a replica: {results}"
        provenance = _get(sup.url(0) + "/admin/model")
        assert provenance["ingest_ckpt_step"] == 0, (
            f"X-Ckpt-Step never reached the ingest gauge: {provenance}"
        )
        print(f"fanout ingest landed on all {NUM_REPLICAS} replicas: {results}",
              flush=True)

        # -- promotion: gate battery + audit ledger + staged rollout -------
        import serve_promote

        # heal the fleet first: the burst leg's slowed replica carries
        # its slow@ fault in the spawn env, so every request it serves
        # blows the replica SLO and pins its latency burn at the cap —
        # and the rollout soak (correctly) refuses to promote into a
        # burning fleet. Clear the fault env, cycle the replica clean,
        # and wait for every fleet burn gauge to settle under the
        # rollout ceiling before any candidate goes near traffic.
        sup.clear_extra_env(SLOWED_REPLICA)
        assert router.drain_replica(SLOWED_REPLICA), (
            "slowed replica was already draining at heal time"
        )
        deadline = time.monotonic() + RESPAWN_DEADLINE_S
        while time.monotonic() < deadline:
            snap = next(
                s for s in _get(base + "/admin/replicas")["replicas"]
                if s["index"] == SLOWED_REPLICA
            )
            if snap["healthy"] and not snap["draining"]:
                break
            time.sleep(0.25)
        assert snap["healthy"] and not snap["draining"], (
            f"slowed replica never re-admitted after heal: {snap}"
        )
        deadline = time.monotonic() + 75.0  # the slow burn window is 60s
        while time.monotonic() < deadline:
            burn = serve_promote.fleet_burn(base)
            if burn is None or burn <= 14.4:
                break
            time.sleep(1.0)
        assert burn is None or burn <= 14.4, (
            f"fleet burn never settled after healing the slowed replica: {burn}"
        )
        print(f"healed replica {SLOWED_REPLICA} (slow fault cleared, "
              f"fleet burn settled at {0.0 if burn is None else burn:.2f})",
              flush=True)

        from moco_tpu.serve.promote import PromotionLedger

        cand_bad = os.path.join(workdir, "cand_skewed")
        cand_good = os.path.join(workdir, "cand_good")
        print("promotion: building candidates (skewed re-init + compatible "
              "nudge, both posing as step 1)...", flush=True)
        # skewed: a different random init saved as "step 1" — embeds the
        # probes into an unrelated space, the compat gates must catch it
        serve_smoke.make_toy_checkpoint(cand_bad, seed=1, step=1)
        _make_compatible_candidate(ckpt_dir, cand_good, step=1)

        ledger_path = os.path.join(workdir, "promotions.jsonl")
        ledger = PromotionLedger(ledger_path)
        pargs = argparse.Namespace(
            candidate_dir=cand_bad, live_dir=ckpt_dir, router=base,
            probes=PROMOTE_PROBES, k=5,
            floor_cosine=0.90, floor_overlap=0.60,
            floor_feature_std=PROMOTE_FEATURE_STD_FLOOR,
            max_ema_drift=0.50, floor_live_recall=None,
            soak_s=1.0, swap_timeout_s=RESPAWN_DEADLINE_S,
            burn_ceiling=14.4, poll_s=0.5,
        )
        verdict = serve_promote.promote_once(pargs, ledger)
        assert verdict == "rejected", (
            f"the skewed candidate cleared the gate battery: {verdict}"
        )
        rejected = [
            r for r in ledger.read() if r["promotion/verdict"] == "rejected"
        ]
        assert rejected and rejected[-1]["promotion/failed_gate"] == "compat_cosine", (
            f"rejection did not name the compat gate: {rejected}"
        )
        # the evidence is IN the ledger line: measured value vs floor
        assert rejected[-1]["promotion/gate/compat_cosine"] < rejected[-1][
            "promotion/floor/compat_cosine"
        ], rejected[-1]
        # ...and a rejected candidate never touched traffic
        assert not _get(base + "/stats").get("fleet_serve/promotions"), (
            "a rejected candidate reached the fleet"
        )
        print("promotion: skewed candidate rejected at the "
              f"compat_cosine gate ({rejected[-1]['promotion/gate/compat_cosine']:.3f} "
              f"vs floor {rejected[-1]['promotion/floor/compat_cosine']})", flush=True)

        # the compatible candidate rolls out replica-by-replica under
        # live traffic: zero dropped requests, and the version-skew
        # gauge must pass through a mixed-fleet reading before settling
        stop = threading.Event()
        promo_failures: list[str] = []
        skew_seen: list = []

        def promo_background(ci: int) -> None:
            j = 0
            while not stop.is_set():
                path = "/neighbors?k=3" if (ci + j) % 2 == 0 else "/embed"
                j += 1
                try:
                    check_response(post(path, canned[1]), 1)
                except Exception as e:
                    with lock:
                        promo_failures.append(repr(e))
                time.sleep(0.05)

        def skew_watcher() -> None:
            while not stop.is_set():
                try:
                    s = _get(base + "/stats").get("fleet_serve/model_skew")
                except Exception:
                    s = None
                if s is not None:
                    with lock:
                        skew_seen.append(int(s))
                time.sleep(0.25)

        pargs.candidate_dir = cand_good
        promo_threads = [
            threading.Thread(target=promo_background, args=(ci,)) for ci in range(2)
        ] + [threading.Thread(target=skew_watcher)]
        for t in promo_threads:
            t.start()
        try:
            verdict = serve_promote.promote_once(pargs, ledger)
        finally:
            stop.set()
            for t in promo_threads:
                t.join(timeout=60)
        assert verdict == "promoted", (
            f"the compatible candidate did not promote: {verdict}"
        )
        assert not promo_failures, (
            f"{len(promo_failures)} requests dropped during the staged "
            f"rollout: {promo_failures[:5]}"
        )
        assert max(skew_seen, default=0) >= 1, (
            "the rollout never showed a mixed-version fleet on "
            "fleet_serve/model_skew"
        )
        promoted = [
            r for r in ledger.read() if r["promotion/verdict"] == "promoted"
        ]
        assert promoted and promoted[-1]["promotion/step"] == 1, promoted
        target_digest = promoted[-1]["promotion/digest"]
        # every replica now serves the candidate (step + digest), and
        # the router's skew gauge settles back to 0
        for i in range(NUM_REPLICAS):
            m = _get(sup.url(i) + "/admin/model")
            assert m["model_step"] == 1 and m["model_digest"] == target_digest, (
                f"replica {i} is not on the promoted encoder: {m}"
            )
        deadline = time.monotonic() + 60.0
        skew = None
        while time.monotonic() < deadline:
            skew = _get(base + "/stats").get("fleet_serve/model_skew")
            if skew == 0:
                break
            time.sleep(0.5)
        assert skew == 0, f"fleet_serve/model_skew stuck at {skew} post-rollout"
        stats = _get(base + "/stats")
        assert stats.get("fleet_serve/promotions") == NUM_REPLICAS, stats
        print(f"promotion: candidate {target_digest} promoted across "
              f"{NUM_REPLICAS} replicas (skew peaked at "
              f"{max(skew_seen)}, settled at 0, zero dropped requests)",
              flush=True)
        summary["promotion"] = {
            "ledger": ledger_path,
            "rejected_gate": rejected[-1]["promotion/failed_gate"],
            "promoted_digest": target_digest,
            "promoted_step": 1,
            "skew_peak": max(skew_seen),
        }

        # -- final gates ---------------------------------------------------
        stats = _get(base + "/stats")
        assert stats["fleet_serve/replicas_healthy"] == NUM_REPLICAS, stats
        assert stats["fleet_serve/failed"] == 0, stats
        assert stats["fleet_serve/shed"] == 0, stats
        assert stats["fleet_serve/breaker_trips"] >= 1, (
            "the kill never tripped a breaker"
        )
        assert stats["fleet_serve/retries"] >= 1, (
            "the kill never exercised the retry path"
        )
        assert stats["fleet_serve/hedges"] >= 1, (
            "the slowed replica never triggered a hedge"
        )
        assert stats["fleet_serve/hedge_wins"] >= 1, (
            "no hedge ever beat the slow primary"
        )
        burn = stats.get("fleet_serve/burn_rate_60s")
        assert burn is not None and burn < 1.0, (
            f"fleet_serve/burn_rate_60s={burn}: the chaos burned the whole "
            f"client-observed error budget"
        )
        summary.update({
            "burst_requests": BURST_REQUESTS,
            "burst_seconds": round(burst_s, 2),
            "failed_requests": 0,
            "replicas_seen": sorted(replicas_seen),
            "kill_exit_code": crashes[0]["rc"],
            "warm_rows_replayed": WARM_ROWS,
            "burn_rate_60s": burn,
            "breaker_trips": stats["fleet_serve/breaker_trips"],
            "retries": stats["fleet_serve/retries"],
            "hedges": stats["fleet_serve/hedges"],
            "hedge_wins": stats["fleet_serve/hedge_wins"],
            "drains": stats["fleet_serve/drains"],
            "requests_total": stats["fleet_serve/requests"],
        })

        if contract_coverage:
            # one-shot probes for the admin/debug routes the chaos story
            # itself never needs — the coverage gate below demands EVERY
            # declared route, not just the busy ones
            _get(base + "/healthz")
            _get(sup.url(0) + "/debug/flight")
            req = urllib.request.Request(
                base + f"/admin/undrain?replica={DRAINED_REPLICA}", data=b""
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
            # HTTP drain of a replica directly (the supervisor's own
            # graceful path is SIGTERM): last thing before teardown
            req = urllib.request.Request(
                sup.url(SLOWED_REPLICA) + "/admin/drain", data=b""
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                r.read()
    finally:
        router.close()
        sup.close()
        sink.close()
        if contract_coverage:
            os.environ.pop("MOCO_CONTRACT_COVERAGE", None)
        with open(os.path.join(workdir, "supervisor_events.json"), "w") as f:
            json.dump(sup.events(), f, indent=2)

    # flushed fleet_serve/* lines must be schema-strict
    problems = schema.validate_file(os.path.join(workdir, "metrics.jsonl"))
    assert not problems, f"router metrics schema violations: {problems[:5]}"

    # -- offline merge: router + replica streams on one clock --------------
    # trace_merge must find the router track and link at least one
    # router/attempt -> replica request flow arrow; its offline stitcher
    # (heartbeat-anchored, no in-band echo) must reproduce waterfalls.
    # The killed replica's stream dies with it, so the offline gate is
    # "non-empty and consistent", while the in-band gate above is 100%.
    import trace_merge

    merged_path = os.path.join(workdir, "merged_fleet_trace.json")
    tm_summary = trace_merge.merge_traces(workdir, merged_path)
    assert 0 in tm_summary["routers"], (
        f"trace_merge never found the router stream: {tm_summary}"
    )
    assert tm_summary["flow_events"] >= 1, (
        "trace_merge linked no router attempt -> replica request flows"
    )
    offline = trace_merge.stitch_traces(workdir)
    assert offline, "offline stitcher reconstructed no traces"
    summary["merged_trace"] = merged_path
    summary["flow_pairs"] = tm_summary["flow_events"]
    summary["offline_stitched"] = len(offline)
    print(f"offline merge: {len(tm_summary['routers'])} router + "
          f"{len(tm_summary['serve_replicas'])} replica streams on one clock, "
          f"{tm_summary['flow_events']} flow arrows, "
          f"{len(offline)} traces re-stitched offline", flush=True)

    # -- freshness: an ingest stall must trip the fresh-burn alert ---------
    # (after the trace merge: this leg's own trace stream lives in a
    # subdir and must not enter the fleet's merged timeline)
    summary["freshness"] = _freshness_stall_leg(workdir)
    print(f"freshness: ingest stall tripped fresh_burn_fast "
          f"(burn {summary['freshness']['fresh_burn_rate_5s']:.1f}, "
          f"row age {summary['freshness']['row_age_max_s']:.1f}s)", flush=True)

    if recorder is not None:
        # validate each replica's serve/* stream too — with the recorder
        # still wired into obs/schema this doubles as validator coverage
        for i in range(NUM_REPLICAS):
            rp = os.path.join(workdir, f"replica{i}", "metrics.jsonl")
            if os.path.exists(rp):
                rproblems = schema.validate_file(rp)
                assert not rproblems, (
                    f"replica {i} metrics schema violations: {rproblems[:5]}"
                )
        snaps = [recorder.snapshot()]
        for i in range(NUM_REPLICAS):
            p = os.path.join(workdir, f"replica{i}", "contract_coverage.json")
            if os.path.exists(p):
                with open(p) as fh:
                    snaps.append(json.load(fh))
        contract_cov.uninstall_recorder()
        cov = contract_cov.merge_coverage(snaps)
        gate_routes = list(dict.fromkeys(
            contract_cov.declared_route_gates("replica")
            + contract_cov.declared_route_gates("router")
        ))
        gate_faults = [f"slow@{s}" for s in decl.SERVE_STAGE_SITES] + [
            "kill@replica",
            # the freshness leg's chaos lever: the /ingest stall hook
            "delay@ingest",
        ]
        gate_validators = (
            tuple(decl.SERVE_GATED_VALIDATORS)
            + tuple(decl.FLEET_GATED_VALIDATORS)
            # model identity / freshness gauges (every replica declares
            # the objective) + the promotion ledger's verdict fields
            + tuple(decl.QUALITY_GATED_VALIDATORS)
            + tuple(decl.PROMOTION_GATED_VALIDATORS)
        )
        missing = contract_cov.check_coverage(
            cov,
            routes=gate_routes,
            fault_sites=gate_faults,
            validators=gate_validators,
            headers=decl.TRACE_HEADERS,
        )
        with open(os.path.join(workdir, "contract_coverage.json"), "w") as f:
            json.dump({
                "coverage": cov,
                "gates": {
                    "routes": gate_routes,
                    "fault_sites": gate_faults,
                    "validators": list(gate_validators),
                    "headers": list(decl.TRACE_HEADERS),
                },
                "missing": missing,
            }, f, indent=2, sort_keys=True)
        assert not missing, (
            f"newly-dead contracts (registered but never fired): {missing}"
        )
        summary["contract_coverage"] = {
            "routes": len(cov["routes"]),
            "fault_hooks": len(cov["fault_hooks"]),
            "validators": len(cov["validators"]),
            "headers": len(cov.get("headers", {})),
            "missing": 0,
        }

    # the threaded fleet modules must LINT clean, not just run clean
    # (JX011 join discipline, JX012 shared-state, JX013 lock ordering)
    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    lint = subprocess.run(
        [
            sys.executable, "-m", "moco_tpu.analysis",
            "moco_tpu/serve/router.py", "moco_tpu/serve/fleet.py",
            "moco_tpu/serve/replica_main.py", "moco_tpu/serve/batcher.py",
            "moco_tpu/serve/promote.py",
            "scripts/fleet_serve_smoke.py", "scripts/serve_promote.py",
            "--no-baseline",
        ],
        cwd=repo, capture_output=True, text=True,
    )
    assert lint.returncode == 0, (
        f"mocolint findings in the fleet modules:\n{lint.stdout}\n{lint.stderr}"
    )
    summary["mocolint_clean"] = True

    with open(os.path.join(workdir, "fleet_serve_smoke.json"), "w") as f:
        json.dump(summary, f, indent=2)
    return summary


def main() -> int:
    from moco_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        pin_platform_from_env,
    )

    pin_platform_from_env()
    enable_persistent_compilation_cache()
    ap = argparse.ArgumentParser(description="serving-fleet router chaos smoke")
    ap.add_argument("--workdir", default=None)
    ap.add_argument(
        "--contract-coverage", action="store_true",
        help="mocolint v4 runtime arm: record which declared routes, "
        "fault sites, and schema validators actually fire (router + "
        "every replica process), merge into contract_coverage.json, and "
        "FAIL on any registered contract that never fired",
    )
    args = ap.parse_args()
    workdir = args.workdir or tempfile.mkdtemp(prefix="fleet_serve_smoke_")
    os.makedirs(workdir, exist_ok=True)
    summary = run_smoke(workdir, contract_coverage=args.contract_coverage)
    print("\n== fleet serve smoke PASS ==")
    for k, v in summary.items():
        print(f"  {k}: {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
