"""A serve cell: the replica's own entry point,
`moco_tpu.serve.replica_main.main`, on the main thread of the process that
holds the chip, booted from a checkpoint made from the seed with the
program's `create_state` and `CheckpointManager` (the same `extra` the
train driver writes). The load generator is a child process that never
imports JAX. A harness thread waits for the child's window to open
(set-up ends there), traces a few seconds inside it when asked, and when
the child has written its results sends the process SIGTERM: the
replica's graceful drain.

Stage means come from the replica's own `metrics.jsonl` flusher lines
inside the window, not from `/stats`: `ServeMetrics.payload()` resets its
stage window on every call, so a `/stats` read would steal that second's
requests from the sink (PERF.md section 7).
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

from benchmarks.harness import common, correct
from benchmarks.harness.common import log
from benchmarks.harness.manifest import BENCH_DIR
from benchmarks.harness.stats import percentile


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_checkpoint(config, seed: int, ckpt_dir: str, inputs):
    """Weights from the seed, saved as the train driver saves them.
    `inputs`: the family's input module (the sample row's shape)."""
    from moco_tpu.utils.checkpoint import CheckpointManager
    from moco_tpu.utils.config import config_to_dict

    state, _, _ = correct.seeded_state(config, seed, inputs)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    mgr = CheckpointManager(ckpt_dir, keep=1)
    mgr.save(0, state, extra={"epoch": 0, "config": config_to_dict(config), "num_data": 1},
             force=True)
    mgr.wait()
    mgr.close()
    return state


class Conductor(threading.Thread):
    """Follows the load generator: stamps the window, traces inside it,
    and ends the replica when the child is done."""

    def __init__(self, child, window_file: str, trace_dir, trace_after_s, trace_seconds, deadline_s):
        super().__init__(name="bench_conductor", daemon=True)
        self.child, self.window_file = child, window_file
        self.trace_dir, self.trace_after_s, self.trace_seconds = trace_dir, trace_after_s, trace_seconds
        self.deadline = time.time() + deadline_s
        self.window = None
        self.error = None

    def run(self) -> None:
        try:
            while self.window is None and self.child.poll() is None and time.time() < self.deadline:
                if os.path.exists(self.window_file):
                    self.window = json.load(open(self.window_file))
                    log("window open")
                else:
                    time.sleep(0.05)
            if self.window is not None and self.trace_dir:
                import jax

                time.sleep(max(self.window["wall_start"] + self.trace_after_s - time.time(), 0))
                common.start_device_trace(self.trace_dir)
                time.sleep(self.trace_seconds)
                jax.profiler.stop_trace()
                log("trace stopped")
            self.child.wait(timeout=max(self.deadline - time.time(), 1.0))
        except Exception as e:  # report, and still end the replica below
            self.error = e
        finally:
            os.kill(os.getpid(), signal.SIGTERM)


def run(manifest, cell: dict, args, t_start: float) -> dict:
    from moco_tpu.serve import replica_main

    rehearse = args.rehearse
    cfg_file = manifest.config_file(cell["config"])
    ref, inputs = manifest.family(cfg_file)
    traffic_file = manifest.traffic_file(cell["traffic"])
    traffic = common.merged(traffic_file, rehearse)
    serve_cfg = common.merged(cfg_file["serve"], rehearse)
    device = common.require_devices(cell["chips"], rehearse)
    workdir = os.path.join(common.OUT_DIR, f"{cell['name']}-{args.seed}-{args.trace}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ckpt_dir = os.path.join(common.OUT_DIR, "cache", f"ckpt-{cell['name']}")
    config = common.build_train_config(cfg_file, traffic_file, args.seed, ckpt_dir, rehearse)
    state = make_checkpoint(config, args.seed, ckpt_dir, inputs)
    log("checkpoint made from the seed")

    port = _free_port()
    spec = {
        "host": "127.0.0.1", "port": port, "seed": args.seed, "seconds": args.seconds,
        "traffic": traffic, "image_size": config.data.image_size,
        "pool_size": traffic["pool_images"], "workers": traffic["client_threads"],
        "timeout_s": traffic["client_timeout_s"], "boot_deadline_s": traffic["boot_deadline_s"],
        "sample": {"n": traffic["correct_sample"]},
        "window_file": os.path.join(workdir, "window.json"),
        "out": os.path.join(workdir, "loadgen.json"),
    }
    if args.sweep:
        spec["sweep_rates"] = [float(r) for r in args.sweep.split(",")]
        spec["sweep_seconds"] = args.sweep_seconds
    spec_path = os.path.join(workdir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    child_env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "loadgen", "client.py"), "--spec", spec_path],
        env=child_env, stdout=sys.stderr,
    )
    trace_dir = os.path.join(workdir, "profile") if args.trace else None
    conductor = Conductor(
        child, spec["window_file"], trace_dir, float(traffic["trace_after_s"]),
        float(traffic["trace_seconds"]),
        deadline_s=traffic["boot_deadline_s"] + 2 * args.seconds + 120
        + (len(spec.get("sweep_rates", [])) * (args.sweep_seconds + 40)),
    )
    conductor.start()
    try:
        rc = replica_main.main([
            "--ckpt-dir", ckpt_dir, "--port", str(port), "--workdir", workdir,
            "--buckets", ",".join(str(b) for b in serve_cfg["buckets"]),
            "--slo-ms", str(serve_cfg["slo_ms"]),
            "--neighbors-mode", serve_cfg["neighbors_mode"],
            "--neighbors-k", str(serve_cfg["neighbors_k"]),
        ])
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
        conductor.join(timeout=10.0)
    log(f"replica exited {rc}, load generator {child.returncode}")
    if child.returncode != 0 or conductor.error is not None:
        raise SystemExit(f"load generator failed: rc={child.returncode} {conductor.error!r}")
    gen = json.load(open(spec["out"]))
    peak = common.memory_peak_bytes()
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    if args.sweep:
        table = [summarise(w, serve_cfg["slo_ms"], traffic["client_timeout_s"]) for w in gen["sweep"]]
        for row in table:
            log(f"sweep {row}")
        return {"sweep": table, "device": {**device, "memory_peak_bytes": peak},
                "_detail": {"cell": cell["name"], "sweep": table}}

    win = gen["window"]
    t_open, t_close = win["wall_start"], win["wall_start"] + win["seconds"]
    serve_lines = [
        ln for ln in common.read_jsonl(os.path.join(workdir, "metrics.jsonl"))
        if t_open <= ln["time"] <= t_close
    ]
    summary = summarise(win, serve_cfg["slo_ms"], traffic["client_timeout_s"])
    check = correct.check_serve(
        state, config, ref, inputs, args.seed, gen["sample"], serve_cfg["neighbors_k"]
    )
    log(f"correct: {check}")
    log(f"window: {summary}")
    recompiles = serve_lines[-1].get("serve/recompiles_after_warmup") if serve_lines else None
    compared = {
        **correct.compared(check, ref),
        "serve_recompiles": {"value": recompiles, "at_most": 0},
        "failed_requests": {"value": summary["failed"], "at_most": 0},
        "gen_late_p95_ms": {"value": summary["late_p95_ms"], "at_most": float(traffic["late_p95_cap_ms"])},
    }
    result = {
        "correct": bool(check["ok"]) and all(correct.holds(c) for c in compared.values()),
        "attempted": summary["attempted"],
        "failed": summary["failed"],
        "metrics": {},
        "device": {**device, "memory_peak_bytes": peak},
        "compared": compared,
    }
    detail = {
        "cell": cell["name"], "seed": args.seed, "trace": args.trace, "summary": summary,
        "correct_detail": check, "serve_lines": serve_lines,
        "latency_ms": win["latency_ms"], "late_ms": win["late_ms"],
    }
    if rehearse:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        return {**result, "rehearsal": True, "_detail": detail}
    if not args.trace:
        result["metrics"] = common.end_to_end_metrics(manifest, cell["name"], {
            "serve_p95_ms": summary["p95_ms"],
            "serve_img_per_s": summary["img_per_s"],
            "setup_s": t_open - t_start,
        })
    else:
        from benchmarks.harness.peaks import peaks_for
        from benchmarks.trace_reduce import load_events, reduce_trace

        loaded = load_events(trace_dir, 0)
        reduced = reduce_trace(loaded["ops"], loaded["modules"], None)
        # the traced window is the seconds asked for, not the span of the
        # ops in it: a device that sat idle at either end was idle
        if reduced["busy_s"]:
            reduced["window_s"] = max(float(traffic["trace_seconds"]), reduced["window_s"])
            reduced["idle_share"] = 1.0 - reduced["busy_s"] / reduced["window_s"]
        ctx = {
            "serve_lines": serve_lines, "loadgen": win, "trace": reduced,
            "trace_ops": loaded["ops"], "memory_peak_bytes": peak,
            "peaks": peaks_for(device["kind"]), "chips": cell["chips"],
            "serve_config": serve_cfg,
        }
        common.add_traced(result, detail, manifest, cell["name"], ctx, loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB a run
    result["_detail"] = detail
    return result


def summarise(win: dict, slo_ms: float, timeout_s: float) -> dict:
    """The window as its users saw it. Latency runs from each request's
    due time; a failed, refused or timed-out request counts as the
    largest value there is (the client's timeout, or a slower success)."""
    n = len(win["status"])
    good = [s == 200 for s in win["status"]]
    worst = max([timeout_s * 1e3] + [l for l, g in zip(win["latency_ms"], good) if g])
    lat = [l if g else worst for l, g in zip(win["latency_ms"], good)]
    done_in = sum(
        size for size, g, d in zip(win["size"], good, win["done_s"]) if g and d <= win["seconds"]
    )
    return {
        "rate_rps": win["rate_rps"],
        "attempted": n,
        "failed": n - sum(good),
        "p50_ms": percentile(lat, 50),
        "p95_ms": percentile(lat, 95),
        "p99_ms": percentile(lat, 99),
        "img_per_s": done_in / win["seconds"],
        "offered_img_per_s": sum(win["size"]) / win["seconds"],
        "completed_share": sum(1 for g, d in zip(good, win["done_s"]) if g and d <= win["seconds"]) / n,
        "within_slo_share": sum(1 for l, g in zip(win["latency_ms"], good) if g and l <= slo_ms) / n,
        "late_p95_ms": percentile(win["late_ms"], 95),
    }
