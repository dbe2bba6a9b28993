"""One serving replica as a process: the unit the fleet supervisor
spawns, kills, and resurrects.

    python -m moco_tpu.serve.replica_main --ckpt-dir /run/workdir \
        --port 8001 --replica-index 1 [--workdir /fleet/replica1] \
        [--buckets 1,8,32] [--slo-ms 1000] [--neighbors-mode exact]

Loads the checkpoint's key encoder (`load_serving_encoder`), wraps the
checkpoint queue as the serving index, and boots a `ServeServer` on the
given port — which binds ONLY after AOT warmup, so the supervisor's
healthz wait doubles as a warmup barrier (connection refused = still
compiling, never a cold replica in rotation).

Faults install from `MOCO_FAULTS` (the supervisor plants per-replica
specs for the chaos smoke; `kill@replica=i` dies here mid-request).

SIGTERM/SIGINT is the graceful-drain path (the supervisor's
`restart_replica` and fleet shutdown both use it): stop intake, FLUSH
every accepted request (`ServeServer.drain` → the batcher's drain),
then tear down and exit 0 — a drained replica never fails a request it
already accepted.
"""

from __future__ import annotations

import argparse
import signal
import threading


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="one serving-fleet replica process")
    ap.add_argument("--ckpt-dir", required=True, help="pretraining checkpoint workdir")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--replica-index", type=int, default=0)
    ap.add_argument("--workdir", default=None, help="metrics/trace output dir")
    ap.add_argument("--buckets", default="1,8,32", help="comma-separated AOT buckets")
    ap.add_argument("--slo-ms", type=float, default=1000.0)
    ap.add_argument("--neighbors-mode", default="exact")
    ap.add_argument("--neighbors-k", type=int, default=5)
    ap.add_argument("--metrics-flush-s", type=float, default=1.0)
    ap.add_argument("--drain-timeout-s", type=float, default=30.0)
    ap.add_argument(
        "--fresh-max-age-s", type=float, default=0.0,
        help="freshness SLO: max index-row age in wall seconds "
        "(0 = no freshness objective declared)",
    )
    return ap


def main(argv=None) -> int:
    from moco_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        log_devices,
        pin_platform_from_env,
    )

    args = build_argparser().parse_args(argv)
    pin_platform_from_env()
    # every boot AOT-compiles one encoder program per bucket: share them
    # with the next boot (restart, rollout, the neighbouring replica)
    enable_persistent_compilation_cache()
    log_devices(f"replica {args.replica_index}")

    import os

    import jax

    from moco_tpu.analysis import contracts as contract_cov
    from moco_tpu.obs import quality
    from moco_tpu.obs.sinks import JsonlSink
    from moco_tpu.obs.trace import set_annotator
    from moco_tpu.serve.engine import InferenceEngine, load_serving_encoder
    from moco_tpu.serve.index import EmbeddingIndex
    from moco_tpu.serve.server import ServeServer
    from moco_tpu.utils import faults
    from moco_tpu.utils.checkpoint import CheckpointManager

    faults.install_from_env()
    # the engine's spans (`serve_aot_compile`, `serve_embed`,
    # `serve_query`) as `moco/<name>` on the profiler's clock: a device
    # trace of this replica then says what the host was doing
    prev_annotator = set_annotator(jax.profiler.TraceAnnotation)
    # contract-coverage arm: MOCO_CONTRACT_COVERAGE=1 (planted by a
    # smoke script before the supervisor spawns us) installs a recorder;
    # the snapshot dumps on graceful exit below. A killed replica never
    # dumps — its respawn covers the same contracts.
    recorder = contract_cov.maybe_install_from_env()
    buckets = tuple(int(b) for b in args.buckets.split(","))
    module, params, stats, queue, queue_ptr, config = load_serving_encoder(
        args.ckpt_dir
    )
    engine = InferenceEngine(
        module, params, stats,
        image_size=config.data.image_size, buckets=buckets,
    )
    index = EmbeddingIndex.from_train_queue(queue, queue_ptr)
    # served-model identity: which checkpoint step this encoder came
    # from + a content digest of its params — /stats and /admin/model
    # expose both, so the router's version-skew gauge has real data
    mgr = CheckpointManager(args.ckpt_dir)
    model_step = mgr.latest_step()
    mgr.close()
    model_digest = quality.params_digest(params)
    sink = None
    if args.workdir:
        os.makedirs(args.workdir, exist_ok=True)
        sink = JsonlSink(args.workdir)
    server = ServeServer(
        engine,
        index=index,
        host=args.host,
        port=args.port,
        slo_ms=args.slo_ms,
        neighbors_k=args.neighbors_k,
        neighbors_mode=args.neighbors_mode,
        sink=sink,
        metrics_flush_s=args.metrics_flush_s,
        workdir=args.workdir,
        replica_index=args.replica_index,
        model_step=model_step,
        model_digest=model_digest,
        fresh_max_age_s=args.fresh_max_age_s or None,
    )
    print(
        f"replica {args.replica_index} serving on "
        f"http://{args.host}:{server.port} (buckets={buckets})",
        flush=True,
    )

    stop = threading.Event()

    def _graceful(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    while not stop.wait(0.25):
        pass
    # graceful drain: intake shuts, every accepted request flushes —
    # then the ordinary close (final metrics flush included)
    drained = server.drain(timeout=args.drain_timeout_s)
    server.close()
    set_annotator(prev_annotator)
    if sink is not None:
        sink.close()
    if recorder is not None and args.workdir:
        recorder.dump(os.path.join(args.workdir, "contract_coverage.json"))
    print(
        f"replica {args.replica_index} drained "
        f"({'clean' if drained else 'timed out'}) and exited",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
