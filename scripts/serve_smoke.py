#!/usr/bin/env python
"""Serving smoke: boot the embedding service on a toy checkpoint and
prove the whole serving contract, asserted hard.

    JAX_PLATFORMS=cpu python scripts/serve_smoke.py [--workdir DIR]

The story (the ISSUE-8 acceptance bullet, executable):

1. a toy pretraining checkpoint (tiny ResNet, 64-key queue) is written
   the way the train driver writes them (config-carrying extras);
2. `load_serving_encoder` restores the KEY (EMA) encoder + the queue,
   the queue rows load into a sharded-capable `EmbeddingIndex`, and the
   engine AOT-compiles every padded bucket {1, 8, 32, 128};
3. the HTTP server boots (ephemeral port) with a JSONL metrics sink and
   `NUM_REQUESTS` mixed-size requests fire from concurrent clients —
   `/embed` and `/neighbors` interleaved;
4. asserts: every response well-formed (shapes, L2-normalized rows,
   neighbor indices inside the queue), ZERO recompiles after warmup
   across all request sizes, p99 latency ≤ the smoke SLO, batch
   occupancy in (0, 1], multiple buckets exercised, and the flushed
   `serve/*` metrics lines schema-strict;
5. the STREAMING-INGEST leg (ISSUE 9): a second checkpoint lands in the
   same workdir with fresh queue rows, `scripts/serve_ingest.py` tails
   it once into the still-running replica over `/ingest`, and the
   serving count (`serve/ingested_rows`, and retrievability of the new
   rows) advances without a restart;
6. the IVF leg (ISSUE 9): a second server boots with
   `neighbors_mode="ivf"` over a clustered dictionary (k-means cells,
   `nprobe` of `nlist` probed per query, recall sampled on EVERY
   neighbors flush) — asserts ZERO recompiles after warmup on the IVF
   path, the online `serve/recall_estimate` at or above the recall
   floor, p99 ≤ the smoke SLO, and the `serve/nprobe`/`serve/int8`
   gauges schema-strict;
7. the SLO-violation leg (ISSUE 10): a third server boots with request
   tracing, a tight SLO, short burn windows, and a tightened burn
   threshold; after a healthy baseline, `slow@site=serve.engine_execute`
   injects a deterministic tail — asserts the burn-rate alert FIRES
   (alerts.jsonl), the flight recorder DUMPED (`flight_*.json` under
   `slo_leg/`, a CI artifact), the dump contains the slowed requests'
   full stage waterfalls with `engine_execute` correctly dominating,
   `/debug/flight` answers on demand, and the flushed
   `serve/burn_rate_*` + `serve/trace_*` lines are schema-strict;
8. the W8A8 + FUSED-IVF leg (ISSUE 11): activation ranges are
   calibrated from a held-out sample at the checkpoint, the artifact
   round-trips through disk (`quant_calib.json` next to the
   checkpoint), a `engine_quant="w8a8"` engine boots serving
   `/neighbors` through the FUSED IVF gather-scan
   (`neighbors_mode="ivf_fused"`, recall sampled on every flush) —
   asserts ZERO recompiles after warmup across the new (mode, quant)
   bucket keys, embedding cosine ≥ 0.99 vs the f32 engine, the online
   recall estimate at the floor, p99 ≤ the smoke SLO, the donation
   audit clean on the quantized trees (no False — a consumed qtree
   buffer would be a use-after-free on the next request), and the
   `serve/quant_tier`/`serve/ivf_spill`/`serve/ivf_occupancy` gauges
   schema-strict.

CI runs this in the tier-1 job and uploads the workdir (metrics.jsonl +
serve_smoke.json summary + the SLO leg's flight dump) as an artifact.
Wall cost: one tiny-model AOT warmup + ~300 small requests, well under
a minute on a CPU host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

NUM_REQUESTS = 200
NUM_CLIENTS = 8
# Two latency knobs on purpose: the BATCHER runs at a tight production-
# shaped SLO (sets the slo/2 coalescing deadline; violations are counted,
# not asserted zero), while the smoke's pass/fail bar is the generous
# SMOKE_SLO_MS — shared CI runners jitter, and the smoke's job is "the
# SLO machinery works and latency is sane", not a perf bar (a rate is
# the benchmark's to measure, on the chip: PERF.md).
SERVER_SLO_MS = float(os.environ.get("SERVE_SMOKE_SERVER_SLO_MS", 1000.0))
SMOKE_SLO_MS = float(os.environ.get("SERVE_SMOKE_SLO_MS", 4000.0))
# capped at 16 rows: 8 closed-loop clients x 16 keeps the coalesced
# micro-batch ≤ one 128-bucket execution, so p99 stays bounded by ONE
# flush even on a 1-core host (32-row requests pushed it to two)
REQUEST_SIZES = (1, 2, 4, 8, 16)
# NB: 32px, not the obs-smoke's 16px — XLA:CPU hits a tiny-spatial-dim
# conv slow path at 16px (measured 10x fewer imgs/s than 32px for the
# SAME ResNet-18 on this host), which would turn the smoke into a
# 10-minute run for no extra coverage
IMAGE_SIZE = 32
# IVF leg: a clustered dictionary (nlist cells), nprobe of them probed
# per query, recall sampled on every neighbors flush and gated at the
# floor. The smoke proves the WIRING + freeze discipline; speed at real
# dictionary sizes is not measured on the chip yet (PERF.md §7).
IVF_REQUESTS = 60
IVF_DICT_ROWS = 256
IVF_NLIST = 16
IVF_NPROBE = 12
RECALL_FLOOR = float(os.environ.get("SERVE_SMOKE_RECALL_FLOOR", 0.95))
# SLO leg (ISSUE 10). Sizing: sequential 1-request traffic flushes at
# the batcher's slo/2 coalescing deadline, so baseline latency is
# ~slo/2 + compute — the 800ms SLO leaves CI-jitter headroom for the
# baseline while the injected 3x-SLO sleep violates decisively. Short
# burn windows so the smoke's seconds of traffic fill them, and a burn
# threshold of 1.0 (= "budget exhausts before the period ends")
# instead of the production 14.4 pager so a short run can trip it:
# 4 slowed among ~16 window requests at objective 0.9 burns at ~2.5.
SLO_LEG_SLO_MS = float(os.environ.get("SERVE_SMOKE_SLO_LEG_SLO_MS", 800.0))
SLO_LEG_SLOW_MS = 3.0 * SLO_LEG_SLO_MS
SLO_LEG_REQUESTS = 12
SLO_LEG_SLOWED = 4
# W8A8 + fused-IVF leg (ISSUE 11): calibration sample size, request
# count, and the cosine floor the quantized embeddings must hold vs the
# f32 engine (tests/test_serve_quant.py holds the same floor per bucket)
QUANT_CALIB_SAMPLES = 32
QUANT_REQUESTS = 40
QUANT_COSINE_FLOOR = float(os.environ.get("SERVE_SMOKE_QUANT_COSINE_FLOOR", 0.99))


def make_toy_checkpoint(workdir: str, seed: int = 0, step: int = 0):
    """A pretraining checkpoint exactly as the train driver saves them
    (config-carrying extras), from a freshly-initialized tiny model —
    serving correctness doesn't need trained weights. `seed`/`step` let
    the fleet smoke mint deliberately-incompatible candidates (a
    different init posing as a later step) for the promotion gates."""
    import jax
    import jax.numpy as jnp

    from moco_tpu.core import build_encoder, create_state
    from moco_tpu.utils.checkpoint import CheckpointManager
    from moco_tpu.utils.config import (
        DataConfig,
        MocoConfig,
        OptimConfig,
        TrainConfig,
        config_to_dict,
    )
    from moco_tpu.utils.schedules import build_optimizer

    config = TrainConfig(
        moco=MocoConfig(
            arch="resnet18",
            dim=16,
            num_negatives=64,
            mlp=True,
            shuffle="none",
            cifar_stem=True,
            compute_dtype="float32",
        ),
        optim=OptimConfig(lr=0.03, epochs=1),
        data=DataConfig(dataset="synthetic", image_size=IMAGE_SIZE, global_batch=8),
        workdir=workdir,
    )
    encoder = build_encoder(config.moco)
    tx = build_optimizer(config.optim, steps_per_epoch=1)
    state = create_state(
        jax.random.PRNGKey(seed), config, encoder, tx,
        jnp.zeros((1, IMAGE_SIZE, IMAGE_SIZE, 3), jnp.float32),
    )
    mgr = CheckpointManager(workdir)
    mgr.save(
        step, state,
        extra={"epoch": 0, "config": config_to_dict(config), "num_data": 1},
        force=True,
    )
    mgr.close()
    return config


def run_smoke(
    workdir: str,
    sanitize_threads: bool = False,
    contract_coverage: bool = False,
) -> dict:
    """Boot → fire → tear down; returns the summary dict (also written
    to workdir/serve_smoke.json). Split from the assertions so tests
    can reuse the run.

    `sanitize_threads` (mocolint v3, analysis/tsan.py) wraps the whole
    run in a lock-order recorder — every tsan-factory lock's nesting is
    traced, and the pass is CLEAN only with zero order cycles and the
    sanctioned serve.index -> serve.metrics edge observed; then a chaos
    leg re-boots a replica under `deadlock@site=serve.metrics` and
    asserts the forced inversion IS caught, with the per-thread stack
    diff artifact (lock_order_diff.json) dumped. Recording only — the
    profile hook stays off here so the latency assertions stay honest.
    """
    import numpy as np

    from moco_tpu.analysis import contracts as contract_cov
    from moco_tpu.obs import schema
    from moco_tpu.obs.sinks import JsonlSink
    from moco_tpu.serve.engine import InferenceEngine, load_serving_encoder
    from moco_tpu.serve.index import EmbeddingIndex
    from moco_tpu.serve.server import ServeServer
    from moco_tpu.utils import contracts as decl

    tsan_sanitizer = None
    if sanitize_threads:
        from moco_tpu.analysis.tsan import ThreadSanitizer

        tsan_sanitizer = ThreadSanitizer(
            workdir=workdir, strict=False, profile=False
        )

    recorder = None
    if contract_coverage:
        recorder = contract_cov.install_recorder()

    ckpt_dir = os.path.join(workdir, "toy_ckpt")
    make_toy_checkpoint(ckpt_dir)
    module, params, stats, queue, queue_ptr, config = load_serving_encoder(ckpt_dir)
    engine = InferenceEngine(
        module, params, stats, image_size=config.data.image_size
    )
    index = EmbeddingIndex.from_train_queue(queue, queue_ptr)
    sink = JsonlSink(workdir)
    server = ServeServer(
        engine,
        index=index,
        port=0,
        slo_ms=SERVER_SLO_MS,
        neighbors_k=5,
        sink=sink,
        metrics_flush_s=0.5,
    )
    base = f"http://127.0.0.1:{server.port}"
    rng = np.random.default_rng(0)
    canned = {
        n: rng.integers(0, 255, (n, IMAGE_SIZE, IMAGE_SIZE, 3), np.uint8)
        for n in REQUEST_SIZES
    }
    failures: list[str] = []
    done = threading.Lock()

    def post(path: str, imgs) -> dict:
        req = urllib.request.Request(
            base + path,
            data=imgs.tobytes(),
            headers={"X-Image-Shape": ",".join(map(str, imgs.shape))},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def client(ci: int, num: int) -> None:
        crng = np.random.default_rng(1000 + ci)
        for j in range(num):
            n = int(crng.choice(REQUEST_SIZES))
            imgs = canned[n]
            want_neighbors = (ci + j) % 2 == 0
            try:
                out = post("/neighbors?k=3" if want_neighbors else "/embed", imgs)
                emb = np.asarray(out["embedding"], np.float32)
                ok = emb.shape[0] == n and np.allclose(
                    np.linalg.norm(emb, axis=1), 1.0, atol=1e-3
                )
                if want_neighbors:
                    idx = np.asarray(out["indices"])
                    ok = ok and idx.shape == (n, 3) and (idx >= 0).all() and (
                        idx < index.capacity
                    ).all()
                if not ok:
                    raise ValueError(f"malformed response for n={n}: {out.keys()}")
            except Exception as e:
                with done:
                    failures.append(f"client {ci} req {j} (n={n}): {e!r}")
                return

    per_client = NUM_REQUESTS // NUM_CLIENTS
    threads = [
        threading.Thread(target=client, args=(i, per_client)) for i in range(NUM_CLIENTS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)

    # -- leg 5: streaming ingest from a "live" training run -------------
    # A fresh checkpoint (same dir, fresh queue rows at the write head)
    # appears while the replica serves; serve_ingest tails it once over
    # /ingest and the serving count advances — no restart, no reload.
    ingest_summary = _ingest_leg(ckpt_dir, server, index)

    stats_out = server.stats()

    if contract_coverage:
        # one-shot probes: the health/stats/drain routes the load legs
        # never touch, so the coverage gate can demand every declared
        # replica route (drain last — the server is done serving here)
        for probe in ("/healthz", "/stats"):
            with urllib.request.urlopen(base + probe, timeout=30) as r:
                r.read()
        drain_req = urllib.request.Request(base + "/admin/drain", data=b"")
        with urllib.request.urlopen(drain_req, timeout=60) as r:
            r.read()

    server.close()

    # -- leg 6: the IVF retrieval tier ----------------------------------
    ivf_summary = _ivf_leg(engine, sink, canned)

    # -- leg 7: SLO burn-rate alert + flight recorder -------------------
    slo_summary = _slo_leg(engine, workdir, canned)

    # -- leg 8: w8a8 engine + fused IVF scan ----------------------------
    quant_summary = _quant_leg(ckpt_dir, engine, sink, canned)

    # -- leg 9: thread sanitizer (mocolint v3) --------------------------
    # clean report over everything above, then the deadlock@site chaos
    # arm proving the detector catches a forced inversion end-to-end
    tsan_summary = None
    if tsan_sanitizer is not None:
        clean = tsan_sanitizer.close()
        tsan_summary = {
            "acquisitions": clean["acquisitions"],
            "edges": clean["edges"],
            "cycles": len(clean["cycles"]),
            "blocking_ops": len(clean["blocking_ops_under_lock"]),
        }
        tsan_summary["chaos"] = _tsan_chaos_leg(engine, index, workdir)

    sink.close()

    contract_summary = None
    if recorder is not None:
        # re-validating the flushed stream with the recorder still wired
        # into obs/schema records validator coverage (assert_serve_surface
        # re-checks the same file later for correctness)
        problems = schema.validate_file(os.path.join(workdir, "metrics.jsonl"))
        assert not problems, f"metrics schema violations: {problems[:5]}"
        cov = recorder.snapshot()
        contract_cov.uninstall_recorder()
        missing = contract_cov.check_coverage(
            cov,
            routes=contract_cov.declared_route_gates("replica"),
            fault_sites=[f"slow@{s}" for s in decl.SERVE_STAGE_SITES],
            validators=decl.SERVE_GATED_VALIDATORS,
        )
        with open(os.path.join(workdir, "contract_coverage.json"), "w") as f:
            json.dump({
                "coverage": cov,
                "gates": {
                    "routes": contract_cov.declared_route_gates("replica"),
                    "fault_sites": [
                        f"slow@{s}" for s in decl.SERVE_STAGE_SITES
                    ],
                    "validators": list(decl.SERVE_GATED_VALIDATORS),
                },
                "missing": missing,
            }, f, indent=2, sort_keys=True)
        assert not missing, (
            f"newly-dead contracts (registered but never fired): {missing}"
        )
        contract_summary = {
            "routes": len(cov["routes"]),
            "fault_hooks": len(cov["fault_hooks"]),
            "validators": len(cov["validators"]),
            "missing": 0,
        }

    summary = {
        "tsan": tsan_summary,
        "contract_coverage": contract_summary,
        "requests_sent": per_client * NUM_CLIENTS,
        "failures": failures,
        "smoke_slo_ms": SMOKE_SLO_MS,
        "stats": stats_out,
        "donation_audit": {str(k): v for k, v in engine.donation_audit().items()},
        "buckets": list(engine.buckets),
        "ingest": ingest_summary,
        "ivf": ivf_summary,
        "slo": slo_summary,
        "quant": quant_summary,
    }
    with open(os.path.join(workdir, "serve_smoke.json"), "w") as f:
        json.dump(summary, f, indent=2)
    return summary


def _tsan_chaos_leg(engine, index, workdir: str) -> dict:
    """`deadlock@site=serve.metrics` chaos arm: re-boot a replica on the
    already-warm engine, hit /stats once — the handler nests serve.index
    -> serve.metrics (the sanctioned order), the fault records the
    inverted edge as if a second thread raced it backwards, and the
    recorder must catch the cycle and dump lock_order_diff.json with
    BOTH acquisition stacks. Non-strict: serving keeps answering; the
    artifact is the proof."""
    from moco_tpu.analysis.tsan import ThreadSanitizer
    from moco_tpu.serve.server import ServeServer
    from moco_tpu.utils import faults

    chaos_dir = os.path.join(workdir, "tsan_chaos")
    os.makedirs(chaos_dir, exist_ok=True)
    faults.install("deadlock@site=serve.metrics")
    san = ThreadSanitizer(workdir=chaos_dir, strict=False, profile=False)
    try:
        server = ServeServer(
            engine, index=index, port=0, warmup=False, metrics_flush_s=30.0,
            reqtrace=False, alert_spec="",
        )
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/stats", timeout=60
            ) as r:
                json.loads(r.read())
        finally:
            server.close()
    finally:
        report = san.close()
        faults.clear()
    diff_path = os.path.join(chaos_dir, "lock_order_diff.json")
    diff = None
    if os.path.isfile(diff_path):
        with open(diff_path) as f:
            diff = json.load(f)
    return {
        "cycles_caught": len(report["cycles"]),
        "diff_path": diff_path if diff is not None else None,
        "diff_cycle": (diff or {}).get("cycle"),
        "diff_has_both_stacks": bool(diff) and all(
            e.get("stack") for e in diff.get("edges", [])
        ) and bool((diff or {}).get("acquiring", {}).get("stack")),
        "injected_edges": sum(
            1 for e in (diff or {}).get("edges", []) if e.get("injected")
        ),
    }


def _ingest_leg(ckpt_dir: str, server, index) -> dict:
    """Write checkpoint step 1 with fresh queue rows, tail it once with
    scripts/serve_ingest.py machinery, return what advanced."""
    import numpy as np

    from moco_tpu.lincls import restore_pretrain_state
    from moco_tpu.utils.checkpoint import CheckpointManager
    from moco_tpu.utils.config import config_to_dict

    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_ingest", os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_ingest.py")
    )
    ingest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ingest)

    state, config = restore_pretrain_state(ckpt_dir)
    fresh_n = 16
    rng = np.random.default_rng(42)
    fresh = rng.normal(size=(fresh_n, state.queue.shape[1])).astype(np.float32)
    fresh /= np.linalg.norm(fresh, axis=1, keepdims=True)
    queue = np.asarray(state.queue).copy()
    queue[:fresh_n] = fresh
    import jax.numpy as jnp

    state = state.replace(queue=jnp.asarray(queue), queue_ptr=jnp.int32(fresh_n))
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(1, state, extra={"epoch": 0, "config": config_to_dict(config), "num_data": 1})
    mgr.close()

    before = server.ingested_rows
    # seen pre-seeded at (step 0, head 0): only the fresh region ingests
    seen = {"step": 0, "ptr": 0}
    ingested = ingest.poll_once(ckpt_dir, f"http://127.0.0.1:{server.port}", seen)
    # the freshly ingested rows must be retrievable at the write head
    # (k=5 / bucket 1 is a prepared shape on the frozen index)
    scores, idx = index.query(fresh[:1], 5)
    return {
        "ingested": int(ingested),
        "counter_before": int(before),
        "counter_after": int(server.ingested_rows),
        "head_hit": bool(idx[0, 0] == 0 and scores[0, 0] > 0.999),
    }


def _ivf_leg(engine, sink, canned) -> dict:
    """Second server, approximate tier: clustered dictionary, IVF cells,
    per-flush recall sampling against the exact oracle."""
    import numpy as np

    from moco_tpu.serve.index import EmbeddingIndex
    from moco_tpu.serve.server import ServeServer

    rng = np.random.default_rng(5)
    dim = engine.num_features or 16
    per = IVF_DICT_ROWS // IVF_NLIST
    centers = rng.normal(size=(IVF_NLIST, dim)).astype(np.float32)
    rows = np.repeat(centers, per, axis=0) + 0.2 * rng.normal(
        size=(IVF_DICT_ROWS, dim)
    ).astype(np.float32)
    rows /= np.linalg.norm(rows, axis=1, keepdims=True)
    index = EmbeddingIndex(IVF_DICT_ROWS, dim)
    index.snapshot(rows)
    index.train_ivf(nlist=IVF_NLIST, nprobe=IVF_NPROBE)
    server = ServeServer(
        engine,
        index=index,
        port=0,
        slo_ms=SERVER_SLO_MS,
        neighbors_k=5,
        neighbors_mode="ivf",
        nprobe=IVF_NPROBE,
        recall_sample_every=1,  # sample the oracle on EVERY neighbors flush
        sink=sink,
        metrics_flush_s=0.5,
    )
    base = f"http://127.0.0.1:{server.port}"
    failures: list[str] = []
    try:
        for j in range(IVF_REQUESTS):
            n = int(rng.choice(REQUEST_SIZES))
            imgs = canned[n]
            # 2/3 of requests name the tier explicitly, the rest ride
            # the server default — both must resolve to ivf
            path = "/neighbors?k=5&mode=ivf" if j % 3 else "/neighbors?k=5"
            req = urllib.request.Request(
                base + path,
                data=imgs.tobytes(),
                headers={"X-Image-Shape": ",".join(map(str, imgs.shape))},
            )
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    out = json.loads(r.read())
                idx = np.asarray(out["indices"])
                if out.get("mode") != "ivf" or idx.shape != (n, 5) or (
                    idx >= IVF_DICT_ROWS
                ).any():
                    failures.append(f"ivf req {j}: malformed {out.get('mode')}")
            except Exception as e:
                failures.append(f"ivf req {j}: {e!r}")
        stats = server.stats()
    finally:
        server.close()
    return {
        "failures": failures,
        "stats": stats,
        "recall_floor": RECALL_FLOOR,
        "ivf_stats": index.ivf_stats(),
    }


def _slo_leg(engine, workdir: str, canned) -> dict:
    """Third server: request tracing on, tight SLO, short burn windows,
    tightened burn threshold; a deterministic `slow@` fault injects the
    tail. The acceptance bullet, executable: the slowed requests trip
    the burn-rate alert and the flight dump attributes their latency to
    exactly the slowed stage."""
    import glob as globmod
    import urllib.request

    import numpy as np

    from moco_tpu.obs.sinks import JsonlSink
    from moco_tpu.serve.server import ServeServer
    from moco_tpu.utils import faults

    slo_dir = os.path.join(workdir, "slo_leg")
    os.makedirs(slo_dir, exist_ok=True)
    sink = JsonlSink(slo_dir)
    server = ServeServer(
        engine,
        index=None,
        port=0,
        slo_ms=SLO_LEG_SLO_MS,
        sink=sink,
        metrics_flush_s=0.25,
        warmup=False,  # the shared engine is already warm
        workdir=slo_dir,
        reqtrace=True,
        slo_objective=0.9,
        burn_windows=(30, 120),
        alert_spec=(
            "threshold@name=slo_burn_fast:field=serve/burn_rate_30s:value=1.0"
        ),
    )
    base = f"http://127.0.0.1:{server.port}"

    def post(imgs) -> dict:
        req = urllib.request.Request(
            base + "/embed",
            data=imgs.tobytes(),
            headers={"X-Image-Shape": ",".join(map(str, imgs.shape))},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    imgs = canned[2]
    slowed_ids: list[str] = []
    try:
        for _ in range(SLO_LEG_REQUESTS):  # healthy baseline
            post(imgs)
        # deterministic tail: the NEXT engine executions sleep; a fresh
        # plan install resets the site counters so at=1 means "from the
        # next call" regardless of warmup/baseline execution counts
        faults.install(
            f"slow@site=serve.engine_execute:ms={SLO_LEG_SLOW_MS:g}"
            f":at=1:times={SLO_LEG_SLOWED}"
        )
        try:
            for _ in range(SLO_LEG_SLOWED):
                slowed_ids.append(post(imgs)["request_id"])
        finally:
            faults.clear()
        for _ in range(6):  # post-incident traffic keeps the window live
            post(imgs)
        # give the flusher a turn: burn-rate computed, alert fired,
        # flight dumped via the on_fire hook
        deadline = time.time() + 10.0
        while time.time() < deadline and not globmod.glob(
            os.path.join(slo_dir, "flight_*.json")
        ):
            time.sleep(0.1)
        with urllib.request.urlopen(base + "/debug/flight", timeout=30) as r:
            debug_flight = json.loads(r.read())
        stats = server.stats()
        server._write_metrics()  # land the incident's gauges before close
    finally:
        server.close()
        sink.close()
    from moco_tpu.obs.alerts import read_alerts

    alerts = read_alerts(os.path.join(slo_dir, "alerts.jsonl"))
    dumps = sorted(globmod.glob(os.path.join(slo_dir, "flight_*.json")))
    alert_dump = None
    for path in dumps:
        with open(path) as f:
            rec = json.load(f)
        if str(rec.get("reason", "")).startswith("alert:"):
            alert_dump = rec
    return {
        "slo_ms": SLO_LEG_SLO_MS,
        "slow_ms": SLO_LEG_SLOW_MS,
        "slowed_ids": slowed_ids,
        "alerts": alerts,
        "dumps": [os.path.basename(p) for p in dumps],
        "alert_dump": alert_dump,
        "debug_flight": debug_flight,
        "stats": stats,
    }


def _quant_leg(ckpt_dir: str, engine_f32, sink, canned) -> dict:
    """Fourth server: the w8a8 engine behind the fused IVF scan
    (module docstring leg 8). Calibration is captured from a held-out
    sample at the checkpoint, saved as `quant_calib.json` NEXT TO the
    checkpoint, and loaded back through disk — the exact boot path a
    production replica takes — before the quantized engine compiles its
    buckets. Traffic mixes explicit `?mode=ivf_fused` riders with the
    server default; recall samples on every flush."""
    import numpy as np

    from moco_tpu.serve import quant
    from moco_tpu.serve.engine import InferenceEngine, load_serving_encoder
    from moco_tpu.serve.index import EmbeddingIndex
    from moco_tpu.serve.server import ServeServer

    module, params, stats, _queue, _ptr, _config = load_serving_encoder(ckpt_dir)
    rng = np.random.default_rng(11)
    sample = rng.integers(
        0, 255, (QUANT_CALIB_SAMPLES, IMAGE_SIZE, IMAGE_SIZE, 3), np.uint8
    )
    calib = quant.calibrate_encoder(module, params, stats, sample, IMAGE_SIZE)
    calib_path = quant.save_calibration(ckpt_dir, calib)
    loaded = quant.load_calibration(ckpt_dir)
    engine = InferenceEngine(
        module, params, stats,
        image_size=IMAGE_SIZE, buckets=(1, 8, 32),
        engine_quant="w8a8", calibration=loaded,
    )
    # quantized embeddings must stay in the f32 engine's space
    probe = canned[16]
    emb_q, _ = engine.embed(probe)
    emb_f, _ = engine_f32.embed(probe)
    cosine = float(np.mean(np.sum(
        emb_q.astype(np.float64) * emb_f.astype(np.float64), axis=-1
    )))
    # clustered dictionary, served through the fused scan
    dim = engine.num_features or 16
    per = IVF_DICT_ROWS // IVF_NLIST
    centers = rng.normal(size=(IVF_NLIST, dim)).astype(np.float32)
    rows = np.repeat(centers, per, axis=0) + 0.2 * rng.normal(
        size=(IVF_DICT_ROWS, dim)
    ).astype(np.float32)
    rows /= np.linalg.norm(rows, axis=1, keepdims=True)
    index = EmbeddingIndex(IVF_DICT_ROWS, dim)
    index.snapshot(rows)
    index.train_ivf(nlist=IVF_NLIST, nprobe=IVF_NPROBE)
    server = ServeServer(
        engine,
        index=index,
        port=0,
        slo_ms=SERVER_SLO_MS,
        neighbors_k=5,
        neighbors_mode="ivf_fused",
        nprobe=IVF_NPROBE,
        recall_sample_every=1,
        sink=sink,
        metrics_flush_s=0.5,
    )
    base = f"http://127.0.0.1:{server.port}"
    failures: list[str] = []
    try:
        for j in range(QUANT_REQUESTS):
            n = int(rng.choice(REQUEST_SIZES))
            imgs = canned[n]
            path = "/neighbors?k=5&mode=ivf_fused" if j % 3 else "/neighbors?k=5"
            req = urllib.request.Request(
                base + path,
                data=imgs.tobytes(),
                headers={"X-Image-Shape": ",".join(map(str, imgs.shape))},
            )
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    out = json.loads(r.read())
                idx = np.asarray(out["indices"])
                if out.get("mode") != "ivf_fused" or idx.shape != (n, 5) or (
                    idx >= IVF_DICT_ROWS
                ).any():
                    failures.append(f"quant req {j}: malformed {out.get('mode')}")
            except Exception as e:
                failures.append(f"quant req {j}: {e!r}")
        stats_out = server.stats()
    finally:
        server.close()
    return {
        "failures": failures,
        "stats": stats_out,
        "cosine_vs_f32": cosine,
        "cosine_floor": QUANT_COSINE_FLOOR,
        "calib_path": os.path.basename(calib_path),
        "calib_layers": calib["num_layers"],
        "calib_roundtrip": loaded == calib,
        "recall_floor": RECALL_FLOOR,
        "donation_audit": {str(k): v for k, v in engine.donation_audit().items()},
        "ivf_stats": index.ivf_stats(),
    }


def assert_serve_surface(workdir: str, summary: dict) -> None:
    from moco_tpu.obs import schema

    # leg 9 (--sanitize-threads): the clean pass saw real lock traffic
    # including the sanctioned serve.index -> serve.metrics nesting and
    # recorded ZERO order cycles; the chaos arm's forced inversion was
    # caught with a both-stacks diff artifact
    tsan = summary.get("tsan")
    if tsan is not None:
        assert tsan["cycles"] == 0, f"lock-order cycles on the clean pass: {tsan}"
        assert tsan["acquisitions"] > 0, "sanitizer saw no lock traffic"
        edges = {(e["held"], e["acquired"]) for e in tsan["edges"]}
        assert ("serve.index", "serve.metrics") in edges, (
            f"sanctioned stats() nesting not observed: {sorted(edges)}"
        )
        chaos = tsan["chaos"]
        assert chaos["cycles_caught"] >= 1, f"injected inversion not caught: {chaos}"
        assert chaos["diff_path"] and chaos["diff_has_both_stacks"], chaos
        assert chaos["injected_edges"] >= 1, chaos

    stats = summary["stats"]
    assert not summary["failures"], f"request failures: {summary['failures'][:5]}"
    assert stats["serve/requests"] >= summary["requests_sent"], stats
    # the headline contract: mixed request sizes, ZERO recompiles after
    # the AOT warmup (every shape served by a precompiled bucket)
    assert stats["serve/recompiles_after_warmup"] == 0, stats
    assert stats["serve/p99_ms"] is not None and stats["serve/p99_ms"] <= SMOKE_SLO_MS, (
        f"p99 {stats['serve/p99_ms']}ms over the smoke SLO {SMOKE_SLO_MS}ms"
    )
    assert stats["serve/occupancy"] is not None and 0 < stats["serve/occupancy"] <= 1
    buckets_hit = [k for k in stats if k.startswith("serve/bucket_")]
    assert len(buckets_hit) >= 2, f"mixed sizes should exercise >1 bucket: {stats}"
    assert stats["serve/index_rows"] == 64, stats
    # leg 5: streaming ingest advanced the serving count, no restart
    ingest = summary["ingest"]
    assert ingest["ingested"] > 0, ingest
    assert ingest["counter_after"] == ingest["counter_before"] + ingest["ingested"]
    assert stats["serve/ingested_rows"] == ingest["counter_after"], stats
    assert ingest["head_hit"], "freshly ingested rows not retrievable at the head"
    # leg 6: the IVF path — zero recompiles after warmup, the online
    # recall estimate at/above the floor, p99 under the smoke SLO
    ivf = summary["ivf"]
    assert not ivf["failures"], f"ivf request failures: {ivf['failures'][:5]}"
    istats = ivf["stats"]
    assert istats["serve/recompiles_after_warmup"] == 0, istats
    assert istats["serve/recall_estimate"] is not None, istats
    assert istats["serve/recall_estimate"] >= RECALL_FLOOR, (
        f"online recall {istats['serve/recall_estimate']} below the "
        f"{RECALL_FLOOR} floor (nprobe={istats.get('serve/nprobe')})"
    )
    assert istats["serve/p99_ms"] is not None and istats["serve/p99_ms"] <= SMOKE_SLO_MS
    assert istats["serve/nprobe"] == IVF_NPROBE and istats["serve/int8"] == 0, istats
    # leg 7: the SLO-violation story end-to-end (ISSUE 10 acceptance):
    # injected slow@serve.engine_execute -> burn-rate alert fired ->
    # flight dump contains the slowed requests' waterfalls with the
    # slowed stage correctly attributed
    slo = summary["slo"]
    assert any(a["rule"] == "slo_burn_fast" for a in slo["alerts"]), (
        f"burn-rate alert never fired: {slo['alerts']}"
    )
    assert slo["slowed_ids"], "slowed requests carried no request ids"

    def _assert_attributed(wf, rid):
        stage_ms = {s["stage"]: s["dur_ms"] for s in wf["stages"]}
        for stage in ("ingress", "queue_wait", "batch_assemble", "engine_execute",
                      "scatter", "respond"):
            assert stage in stage_ms, f"{rid}: stage {stage} missing: {stage_ms}"
        worst = max(stage_ms, key=stage_ms.get)
        assert worst == "engine_execute" and stage_ms[worst] >= slo["slow_ms"], (
            f"{rid}: injected tail misattributed — {stage_ms}"
        )

    # the alert-edge dump already holds (at least) the first offender
    # with the slowed stage attributed — the alert fires mid-incident
    assert slo["alert_dump"] is not None, f"no alert-triggered flight dump: {slo['dumps']}"
    alert_dumped = {r["request_id"]: r for r in slo["alert_dump"]["requests"]}
    caught = [rid for rid in slo["slowed_ids"] if rid in alert_dumped]
    assert caught, (
        f"no slowed request in the alert dump: {sorted(alert_dumped)[-8:]}"
    )
    for rid in caught:
        _assert_attributed(alert_dumped[rid], rid)
    # the on-demand dump at the end holds the FULL incident
    debug = slo["debug_flight"]
    assert debug.get("dump_path"), "/debug/flight did not dump on demand"
    debug_dumped = {r["request_id"]: r for r in debug["requests"]}
    for rid in slo["slowed_ids"]:
        assert rid in debug_dumped, f"slowed request {rid} missing from /debug/flight"
        _assert_attributed(debug_dumped[rid], rid)
    # the p99 exemplar names one of the offenders
    sstats = slo["stats"]
    assert sstats["serve/slo_violations"] >= len(slo["slowed_ids"]), sstats
    assert any(
        k.startswith("serve/burn_rate_") and sstats[k] is not None for k in sstats
    ), f"no burn-rate gauge in stats: {sorted(sstats)}"
    slowest = debug["slowest"][0]
    assert slowest["request_id"] in slo["slowed_ids"], slowest
    slo_metrics = os.path.join(workdir, "slo_leg", "metrics.jsonl")
    errors = schema.validate_file(slo_metrics)
    assert not errors, f"slo leg schema violations: {errors[:5]}"
    slo_lines = schema.read_metrics(slo_metrics)
    assert any(
        r.get("serve/trace_engine_execute_ms") is not None for r in slo_lines
    ), "no stage-trace means reached the sink"
    assert any(r.get("event") == "alert" for r in slo_lines), (
        "no in-band alert event line"
    )
    # the p99 exemplar on the incident's metrics lines blames an
    # injected-slow request id — the gauge-to-request link, on the wire
    assert any(
        r.get("serve/p99_exemplar") in slo["slowed_ids"] for r in slo_lines
    ), "no metrics line exemplar blames a slowed request"
    # request spans reached the replica's Perfetto stream
    assert os.path.exists(os.path.join(workdir, "slo_leg", "trace_events.s0.jsonl"))
    assert os.path.exists(os.path.join(workdir, "slo_leg", "heartbeat.s0.json"))

    # leg 8: the w8a8 engine behind the fused IVF scan (ISSUE 11) —
    # zero recompiles across the new (mode, quant) bucket keys, the
    # quantized embeddings pinned to the f32 space, the recall floor
    # held through the fused tier, and the donation audit clean on the
    # quantized trees (fail LOUDLY on any False: a consumed qtree
    # buffer is a use-after-free on the next request)
    qleg = summary["quant"]
    assert not qleg["failures"], f"quant request failures: {qleg['failures'][:5]}"
    assert qleg["calib_roundtrip"], "calibration artifact did not roundtrip"
    assert qleg["cosine_vs_f32"] >= qleg["cosine_floor"], (
        f"w8a8 cosine {qleg['cosine_vs_f32']:.5f} below the "
        f"{qleg['cosine_floor']} floor"
    )
    qstats = qleg["stats"]
    assert qstats["serve/recompiles_after_warmup"] == 0, qstats
    assert qstats["serve/quant_tier"] == 2, qstats
    assert qstats["serve/recall_estimate"] is not None, qstats
    assert qstats["serve/recall_estimate"] >= qleg["recall_floor"], (
        f"fused-tier online recall {qstats['serve/recall_estimate']} below "
        f"the {qleg['recall_floor']} floor under the w8a8 engine"
    )
    assert qstats["serve/p99_ms"] is not None and qstats["serve/p99_ms"] <= SMOKE_SLO_MS
    # ivf_stats exported: spill + occupancy gauges (the re-fit trigger)
    assert qstats["serve/ivf_spill"] is not None and qstats["serve/ivf_spill"] >= 0
    assert qstats["serve/ivf_occupancy"] is not None and 0 < qstats["serve/ivf_occupancy"] <= 1
    bad_audit = {k: v for k, v in qleg["donation_audit"].items() if v is False}
    assert not bad_audit, (
        f"donation audit failed on the quantized engine: {bad_audit} — "
        "a donated-but-surviving input leaks memory per request; a "
        "consumed quantized tree is a use-after-free on the next one"
    )

    # metrics flushed through the sink are schema-strict
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    assert os.path.exists(metrics_path), "server flushed no metrics.jsonl"
    errors = schema.validate_file(metrics_path)
    assert not errors, f"schema violations: {errors[:5]}"
    lines = schema.read_metrics(metrics_path)
    assert any("serve/qps" in r for r in lines), "no serve/* line reached the sink"
    assert any(
        r.get("serve/recall_estimate") is not None for r in lines
    ), "no recall estimate reached the sink"


def main() -> int:
    from moco_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        pin_platform_from_env,
    )

    pin_platform_from_env()
    enable_persistent_compilation_cache()
    ap = argparse.ArgumentParser(description="embedding-service smoke")
    ap.add_argument("--workdir", default=None, help="default: a fresh temp dir")
    ap.add_argument(
        "--sanitize-threads", action="store_true",
        help="mocolint v3 runtime arm: trace lock acquisition order over "
        "the whole run (clean = zero cycles), then prove the detector on "
        "a deadlock@site=serve.metrics chaos leg (lock_order_diff.json "
        "with both stacks uploads as a CI artifact)",
    )
    ap.add_argument(
        "--contract-coverage", action="store_true",
        help="mocolint v4 runtime arm: record which declared routes, "
        "fault sites, and schema validators actually fire during the "
        "run, write contract_coverage.json, and FAIL on any registered "
        "contract that never fired",
    )
    args = ap.parse_args()
    workdir = args.workdir or tempfile.mkdtemp(prefix="serve_smoke_")
    os.makedirs(workdir, exist_ok=True)
    summary = run_smoke(
        workdir,
        sanitize_threads=args.sanitize_threads,
        contract_coverage=args.contract_coverage,
    )
    assert_serve_surface(workdir, summary)
    s = summary["stats"]
    iv = summary["ivf"]["stats"]
    slo = summary["slo"]
    print(
        f"serve smoke OK: {s['serve/requests']} requests, "
        f"p50={s['serve/p50_ms']:.1f}ms p99={s['serve/p99_ms']:.1f}ms "
        f"qps={s['serve/qps']:.1f} occupancy={s['serve/occupancy']:.3f} "
        f"recompiles_after_warmup={s['serve/recompiles_after_warmup']} | "
        f"ingested={summary['ingest']['ingested']} | "
        f"ivf: {iv['serve/requests']} requests "
        f"recall={iv['serve/recall_estimate']:.3f} "
        f"nprobe={iv['serve/nprobe']}/{IVF_NLIST} "
        f"p99={iv['serve/p99_ms']:.1f}ms "
        f"recompiles={iv['serve/recompiles_after_warmup']} | "
        f"slo leg: {len(slo['slowed_ids'])} slowed requests -> "
        f"{len(slo['alerts'])} alert(s), {len(slo['dumps'])} flight dump(s), "
        f"p99 exemplar {slo['stats'].get('serve/p99_exemplar')} | "
        f"quant leg: w8a8 cos={summary['quant']['cosine_vs_f32']:.5f} "
        f"fused recall={summary['quant']['stats']['serve/recall_estimate']:.3f} "
        f"recompiles={summary['quant']['stats']['serve/recompiles_after_warmup']} "
        f"spill={summary['quant']['stats']['serve/ivf_spill']}"
        + (
            " | tsan: {a} acquisitions, 0 cycles clean, chaos caught "
            "{c} cycle(s)".format(
                a=summary["tsan"]["acquisitions"],
                c=summary["tsan"]["chaos"]["cycles_caught"],
            )
            if summary.get("tsan")
            else ""
        )
        + f" — artifacts in {workdir}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
