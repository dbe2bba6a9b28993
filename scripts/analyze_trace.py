"""Summarize a jax.profiler trace: per-op device time + roofline check.

    BENCH_TRACE_DIR=/tmp/trace python bench.py          # capture
    python scripts/analyze_trace.py /tmp/trace [--steps 20] \
        [--flops 8.18e12 --bytes 100e9 --device-kind "TPU v5 lite"]

Reads the newest `*.trace.json.gz` under the directory (the Perfetto
JSON the profiler writes next to the xplane proto), aggregates X events
on the device track by fusion-name bucket, and — when the XLA
cost-analysis numbers are passed — prints the compute/HBM rooflines the
way PROFILE.md reports them. This is the exact analysis behind
PROFILE.md, packaged so the next profiling pass is one command.

A roofline needs the peaks of the chip that PRODUCED the trace: name it
with --device-kind (as jax reports `device_kind`; must be in
KNOWN_PEAKS) or pass --peak-tflops and --hbm-gbs. Nothing is assumed.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys


# device_kind -> (peak bf16 TFLOP/s, HBM GB/s). Source: Google Cloud
# documentation, "TPU v5e". The full table belongs to the benchmark
# (ROADMAP S0); this script only refuses to guess.
KNOWN_PEAKS = {"TPU v5 lite": (197.0, 819.0)}


def resolve_peaks(device_kind, peak_tflops, hbm_gbs) -> tuple[float, float]:
    """(peak TFLOP/s, HBM GB/s) from the two explicit numbers, else from
    a device kind this script knows; otherwise exit."""
    if peak_tflops is not None and hbm_gbs is not None:
        return peak_tflops, hbm_gbs
    if device_kind in KNOWN_PEAKS:
        return KNOWN_PEAKS[device_kind]
    sys.exit(
        "roofline requested (--flops/--bytes) but the chip's peaks are unknown: "
        "pass --peak-tflops AND --hbm-gbs, or a --device-kind in "
        f"{sorted(KNOWN_PEAKS)} (got {device_kind!r})"
    )


def load_trace(path: str) -> dict:
    if os.path.isdir(path):
        hits = sorted(
            glob.glob(os.path.join(path, "**", "*.trace.json.gz"), recursive=True),
            key=os.path.getmtime,
        )
        if not hits:
            sys.exit(f"no *.trace.json.gz under {path}")
        path = hits[-1]
    print(f"# {path}")
    with gzip.open(path) as f:
        return json.load(f)


def device_pids(trace: dict) -> dict:
    names = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            names[e["pid"]] = e["args"].get("name", "")
    return {pid: n for pid, n in names.items() if "TPU" in n or "GPU" in n}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="trace dir (or a .trace.json.gz file)")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps captured, for ms/step (default: inferred from "
                    "the jit_* umbrella event count)")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--flops", type=float, default=None, help="per-step FLOPs (cost analysis)")
    ap.add_argument("--bytes", type=float, default=None, help="per-step bytes accessed")
    ap.add_argument("--device-kind", default=None,
                    help=f"jax device_kind of the traced chip, one of {sorted(KNOWN_PEAKS)}")
    ap.add_argument("--peak-tflops", type=float, default=None, help="chip peak bf16 TFLOP/s")
    ap.add_argument("--hbm-gbs", type=float, default=None, help="chip HBM GB/s")
    args = ap.parse_args()
    if args.flops or args.bytes:
        args.peak_tflops, args.hbm_gbs = resolve_peaks(
            args.device_kind, args.peak_tflops, args.hbm_gbs
        )

    trace = load_trace(args.trace)
    devs = device_pids(trace)
    if not devs:
        sys.exit("no device track in trace (CPU-only capture?)")
    # aggregate ONE device track: SPMD devices run the same program, and
    # summing across pids would silently inflate every ms/step figure by
    # the device count
    pid = sorted(devs)[0]
    if len(devs) > 1:
        print(f"({len(devs)} device tracks; analyzing {devs[pid]})")

    umbrella = re.compile(r"^jit_\w+")
    buckets: collections.Counter = collections.Counter()
    counts: collections.Counter = collections.Counter()
    umbrella_total = 0.0
    umbrella_n = 0
    for e in trace["traceEvents"]:
        if e.get("ph") != "X" or e.get("pid") != pid or "dur" not in e:
            continue
        name = e.get("name", "?")
        if umbrella.match(name):
            umbrella_total += e["dur"]
            umbrella_n += 1
            continue
        if re.fullmatch(r"\d+", name):  # per-step marker rows
            continue
        b = re.sub(r"\.\d+$", "", name)
        buckets[b] += e["dur"]
        counts[b] += 1

    steps = args.steps or max(umbrella_n, 1)
    total = sum(buckets.values())
    print(f"device: {devs[pid]}")
    print(f"steps: {steps}   umbrella (jit_*) total: {umbrella_total / 1e3:.1f} ms "
          f"-> {umbrella_total / steps / 1e3:.2f} ms/step")
    print(f"attributed op time: {total / steps / 1e3:.2f} ms/step\n")
    print(f"{'ms/step':>9}  {'%':>5}  {'ops/step':>8}  bucket")
    for b, d in buckets.most_common(args.top):
        print(f"{d / steps / 1e3:9.3f}  {100 * d / total:5.1f}  {counts[b] / steps:8.1f}  {b[:70]}")

    if args.flops or args.bytes:
        print()
        step_ms = umbrella_total / steps / 1e3
        # no jit_* umbrella in this capture: print absolute rooflines only
        pct = (lambda ms: f" ({100 * ms / step_ms:.0f}% of step)") if step_ms else (lambda ms: "")
        if args.flops:
            c_ms = args.flops / (args.peak_tflops * 1e12) * 1e3
            print(f"compute roofline @{args.peak_tflops:.0f} TFLOPS: {c_ms:.1f} ms{pct(c_ms)}")
        if args.bytes:
            m_ms = args.bytes / (args.hbm_gbs * 1e9) * 1e3
            print(f"HBM roofline @{args.hbm_gbs:.0f} GB/s: {m_ms:.1f} ms{pct(m_ms)}")


if __name__ == "__main__":
    main()
