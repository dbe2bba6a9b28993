"""A train cell: the program's own driver, `moco_tpu.train.train`, on the
main thread with the preset's defaults, fed by the benchmark's pool
dataset. A watcher thread tails the driver's `metrics.jsonl`; the window
opens at the first log line at or after the traffic file's `warmup_steps`
and, `--seconds` later, the harness sends itself SIGTERM: the driver's own
preemption path (save first, exit clean). Nothing of the loop is
re-implemented here.
"""

from __future__ import annotations

import dataclasses
import math
import os
import shutil
import signal
import threading
import time

from benchmarks.harness import common, correct, flops
from benchmarks.harness.common import log
from benchmarks.harness.stats import line_rate


class WindowWatcher(threading.Thread):
    """Opens the window on the driver's log lines and ends the run."""

    def __init__(self, metrics_path: str, warmup_steps: int, seconds: float, deadline_s: float,
                 trace: dict | None = None):
        super().__init__(name="bench_window", daemon=True)
        self.path, self.warmup_steps, self.seconds = metrics_path, warmup_steps, seconds
        self.deadline = time.time() + deadline_s
        self.t_open = None
        self.done = threading.Event()
        # {"dir", "after_s", "seconds"}: a device trace of a few seconds
        # inside the window, taken by a thread of its own (stopping a
        # trace takes the profiler minutes of post-processing here, and
        # the window must still close on time)
        self.trace = trace
        self.tracer: threading.Thread | None = None

    def _trace(self) -> None:
        """Not the driver's own windowed profiler (`profile_steps`): that
        calls `start_trace` with the defaults (`common.start_device_trace`
        says what they cost)."""
        import jax

        time.sleep(max(self.t_open + self.trace["after_s"] - time.time(), 0.0))
        common.start_device_trace(self.trace["dir"])
        log("trace started")
        time.sleep(self.trace["seconds"])
        jax.profiler.stop_trace()
        log("trace stopped")

    def run(self) -> None:
        pos = 0
        while not self.done.wait(0.05):
            now = time.time()
            if self.t_open is None and os.path.exists(self.path):
                with open(self.path) as f:
                    f.seek(pos)
                    chunk = f.read()
                end = chunk.rfind("\n")
                if end >= 0:
                    pos += end + 1
                    for rec in map(_parse, chunk[: end + 1].splitlines()):
                        if rec and "loss" in rec and rec["step"] >= self.warmup_steps:
                            self.t_open = rec["time"]
                            log(f"window open at step {rec['step']}")
                            break
            if self.t_open is not None and self.trace and self.tracer is None:
                self.tracer = threading.Thread(target=self._trace, name="bench_trace", daemon=True)
                self.tracer.start()
            if (self.t_open is not None and now >= self.t_open + self.seconds) or (
                self.t_open is None and now >= self.deadline
            ):
                os.kill(os.getpid(), signal.SIGTERM)
                return


def _parse(line: str):
    import json

    try:
        return json.loads(line)
    except ValueError:
        return None


def run(manifest, cell: dict, args, t_start: float) -> dict:
    import jax

    from benchmarks.trace_reduce import cut_fixture, load_events, ops_inside, reduce_trace
    from moco_tpu.train import train
    from moco_tpu.utils.config import config_to_dict

    rehearse = args.rehearse
    cfg_file = manifest.config_file(cell["config"])
    ref, inputs = manifest.family(cfg_file)
    traffic_file = manifest.traffic_file(cell["traffic"])
    traffic = common.merged(traffic_file, rehearse)
    device = common.require_devices(cell["chips"], rehearse)
    compiles = common.CompileCounter()
    workdir = os.path.join(common.OUT_DIR, f"{cell['name']}-{args.seed}-{args.trace}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    config = common.build_train_config(cfg_file, traffic_file, args.seed, workdir, rehearse)
    chips = config.parallel.num_data or len(jax.devices())
    if not rehearse and chips != cell["chips"]:
        raise SystemExit(f"cell asks {cell['chips']} chips, configuration resolves {chips}")
    batch = config.data.global_batch
    dataset = inputs.dataset(args.seed, traffic, config)
    log(f"dataset ready ({ref.INPUT})")

    warmup = int(traffic["warmup_steps"])
    profile_dir = os.path.join(workdir, "profile")
    watcher = WindowWatcher(
        os.path.join(workdir, "metrics.jsonl"), warmup, args.seconds,
        deadline_s=float(traffic["open_deadline_s"]),
        trace={"dir": profile_dir, "after_s": float(traffic["trace_after_s"]),
               "seconds": float(traffic["trace_seconds"])} if args.trace else None,
    )
    watcher.start()
    try:
        train(config, dataset=dataset)
    finally:
        watcher.done.set()
        watcher.join(timeout=5.0)
        if watcher.tracer is not None:
            watcher.tracer.join(timeout=float(traffic["trace_stop_deadline_s"]))
            if watcher.tracer.is_alive():
                raise SystemExit("the profiler did not stop in time")
    log("driver returned")
    if watcher.t_open is None:
        raise SystemExit("the window never opened: no log line at the warm-up step")

    t_open, t_close = watcher.t_open, watcher.t_open + args.seconds
    all_lines = common.read_jsonl(os.path.join(workdir, "metrics.jsonl"))
    in_window = [ln for ln in all_lines if t_open <= ln["time"] <= t_close]
    lines = [ln for ln in in_window if "loss" in ln and "event" not in ln]
    nonfinite = [ln for ln in in_window if ln.get("event") == "nonfinite_loss"]
    finite = all(ln["loss"] is not None and math.isfinite(ln["loss"]) for ln in lines)
    steps = lines[-1]["step"] - lines[0]["step"] if len(lines) >= 2 else 0
    compiled_in_window = compiles.between(t_open, t_close)
    peak = common.memory_peak_bytes()
    memory_stats = {
        k: int(v) for k, v in (jax.local_devices()[0].memory_stats() or {}).items()
    }
    # the preemption checkpoint proved the exit path; it is gigabytes a run
    for name in os.listdir(workdir):
        if name.isdigit() or name.startswith("quarantine"):
            shutil.rmtree(os.path.join(workdir, name), ignore_errors=True)

    check = correct.check_train(
        dataclasses.replace(config, parallel=dataclasses.replace(config.parallel, num_data=1)),
        ref, inputs, args.seed,
        sample_n=int(traffic["correct_sample"]), gradient=bool(traffic.get("correct_gradient")),
    )
    log(f"correct: {check}")
    compared = {
        **correct.compared(check, ref),
        "nonfinite_losses": {"value": len(nonfinite) + (0 if finite else 1), "at_most": 0},
        "compiled_in_window": {"value": compiled_in_window, "at_most": 0},
        "window_log_lines": {"value": len(lines), "at_least": 2},
    }
    result = {
        "correct": all(correct.holds(c) for c in compared.values()),
        "attempted": int(steps),
        "failed": len(nonfinite),
        "metrics": {},
        "device": {**device, "memory_peak_bytes": peak},
        # what drew the inputs and `correct`'s sample, and what drew the weights (timed and compared)
        "seed": args.seed, "weights_seed": config.seed,
        "compared": compared,
    }
    detail = {
        "cell": cell["name"], "seed": args.seed, "weights_seed": config.seed, "trace": args.trace,
        "window": {"t_open": t_open, "seconds": args.seconds, "lines": lines},
        "compiled_in_window": compiled_in_window, "correct_detail": check,
        "train_config": config_to_dict(config),
        "memory_stats": memory_stats,
    }
    if rehearse:
        shutil.rmtree(profile_dir, ignore_errors=True)
        return {**result, "rehearsal": True, "_detail": detail}

    if not args.trace:
        rate = line_rate(lines, batch)
        result["metrics"] = common.end_to_end_metrics(manifest, cell["name"], {
            "train_img_per_s_chip": None if rate is None else rate / chips,
            "setup_s": t_open - t_start,
        })
    else:
        from benchmarks.harness.peaks import peaks_for

        loaded = load_events(profile_dir, 0)
        reduced = reduce_trace(loaded["ops"], loaded["modules"], traffic["step_module"])
        ctx = {
            "train_lines": lines,
            "trace": reduced,
            "trace_ops": ops_inside(loaded["ops"], reduced),
            "memory_peak_bytes": peak,
            "peaks": peaks_for(device["kind"]),
            "chips": chips,
            "train_config": config_to_dict(config),
            "step_flops": _step_flops(config, ref, inputs),
        }
        common.add_traced(result, detail, manifest, cell["name"], ctx, loaded)
        if args.dump_trace_events:  # whole steps, compressed: the stuff of a test fixture
            detail["trace_cut"] = cut_fixture(
                loaded["ops"], loaded["modules"], traffic["step_module"], args.dump_trace_events
            )
        shutil.rmtree(profile_dir, ignore_errors=True)  # tens of MB a run
    result["_detail"] = detail
    return result


def _step_flops(config, ref, inputs) -> float:
    """Operations one step needs, from the parameter shapes alone
    (`jax.eval_shape`: nothing is allocated but one sample row). What a
    row costs forward is the family's count (`ref.forward_flops`); what
    a step makes of it is momentum contrast's (`flops.train_step_flops`)."""
    import jax
    import jax.numpy as jnp

    from moco_tpu.core import build_encoder, build_predictor

    enc, pred = build_encoder(config.moco), build_predictor(config.moco)
    sample = inputs.sample_input(config)
    shapes = jax.eval_shape(
        lambda r: enc.init(r, sample, train=False), jax.random.PRNGKey(0)
    )["params"]
    pred_shapes = {}
    if pred is not None:
        pred_shapes = jax.eval_shape(
            lambda r: pred.init(r, jnp.zeros((1, config.moco.dim), jnp.float32), train=False),
            jax.random.PRNGKey(0),
        )["params"]
    return flops.train_step_flops(
        ref.forward_flops(shapes, config), pred_shapes, config.data.global_batch,
        v3=config.moco.v3, dim=config.moco.dim, num_negatives=config.moco.num_negatives,
    )
