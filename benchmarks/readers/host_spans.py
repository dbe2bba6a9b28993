"""Reader: device idle time per traced step, by the host phase it fell under.

spec: {"reader": "host_spans", "phase": a driver span's name | "none",
       "scale": number}   (nanoseconds a step times `scale`)

A step is a step program started inside the part of the reduced trace's
window that the driver's line covers: the profiler traces the host for
the seconds asked and the device for longer (`host_attribution.coverage`).

The program enters its spans as `moco/<name>` on the profiler's clock
(`moco_tpu/obs/trace.py`); `benchmarks/host_attribution.py` gives each idle
nanosecond of the device, inside the window the reduced trace reports, to
the innermost span open on the driver thread (the thread that holds
`moco/train_step`). `log_flush` counts its child `metrics_fetch` as itself;
`none` is idle under no span below `train_step`.

The profile is read once a run, from `<workdir>/profile` (readers run
before the harness removes it); the whole account (every driver span, the
driver x ring table in seconds, the clock check) is kept as
`<workdir>/host_spans.json`, which the later metrics of the run read. A
program that enters no `moco/` span leaves no driver line: nothing to read.
"""

import json
import os

from benchmarks import host_attribution as ha
from benchmarks.trace_reduce import load_events


def _account(ctx: dict):
    trace = ctx.get("trace") or {}
    config = ctx.get("train_config") or {}
    workdir = config.get("workdir")
    if not workdir or not trace.get("window_ns") or not trace.get("steps"):
        return None
    kept = os.path.join(workdir, "host_spans.json")
    if os.path.exists(kept):
        with open(kept) as f:
            return json.load(f)
    profile = os.path.join(workdir, "profile")
    try:
        lines = ha.load_host_lines(profile)
    except FileNotFoundError:
        return None
    loaded = load_events(profile, 0)
    account = ha.account(loaded["ops"], loaded["modules"], lines, trace["window_ns"],
                         depth=int(config.get("prefetch_depth", 2)))
    account["window_steps"] = trace["steps"]
    with open(kept, "w") as f:
        json.dump(account, f, indent=1)
    return account


def read(spec: dict, ctx: dict):
    account = _account(ctx)
    if not account or not account.get("steps"):
        return None
    return account["by_driver_ns"].get(spec["phase"], 0) / account["steps"] * spec.get("scale", 1.0)
