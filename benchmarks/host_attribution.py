"""Device idle time, named by what the host was doing.

The program enters each of its spans as `moco/<name>` on jax's profiler
(`moco_tpu/obs/trace.py`), so a `.xplane.pb` holds the device's ops and
the host's phases on one clock. `attribute_idle` is a pure function on
plain `(name, start_ns, dur_ns)` tuples, like `trace_reduce`: the device's
idle intervals (the complement of the busy union inside the window that
`reduce_trace` reports as `window_ns`) crossed with the driver thread's
`moco/` spans, each idle nanosecond given to the innermost span open at
that instant. `train_step` and `epoch` enclose everything and name no
phase: idle under them alone, or under nothing, is `none`: what the
instrumentation cannot see yet. The same idle time is crossed with the
ring thread's spans into a driver x ring table.

The profiler keeps a host span only if it opened and closed inside the
traced time, and traces the device for longer than the host: the account
covers the part of the window that the driver's line covers (`coverage`).

`clock_check` proves the one-clock claim on a trace: the device never
starts a step's program before the host entered the `moco/step` span that
dispatched it.

    python benchmarks/host_attribution.py <profile dir or .xplane.pb> [--step-module jit_step_fn]
    python benchmarks/host_attribution.py <profile> --dump 2 > fixture.json    # two whole steps, compressed

Only `load_host_lines` touches the `.xplane.pb`.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
from collections import defaultdict
from typing import Collection, Optional, Sequence

if not __package__:  # run as a script: the repo root is not on the path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.trace_reduce import (  # noqa: E402
    _subtract, _total, compress_text, find_xplane, load_events, merge_intervals, reduce_trace,
)

Event = tuple  # (name, start_ns, dur_ns)
HostEvent = tuple  # (name, start_ns, dur_ns, step or None): name without the `moco/` prefix

PREFIX = "moco/"
STEP_SCOPE = "train_step"  # the StepTraceAnnotation around one iteration of the driver's loop
DISPATCH = "step"  # the driver's span around the call that enqueues the step program
THROTTLE = "throttle_wait"  # the driver's wait for the oldest step in flight
RING_MARK = "transfer"  # the span only the prefetch ring's thread enters
STEP_MODULE = "jit_step_fn"  # the step program on the XLA Modules line, as the traffic files name it
NOT_A_PHASE = (STEP_SCOPE, "epoch")
NONE = "none"


def load_host_lines(path: str) -> list[list[HostEvent]]:
    """The `moco/` events of every host thread, a list per thread (line
    of the `/host:CPU` plane), each sorted by start. `step` is the event's
    `step_num` or `step` stat where it has one."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = []
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                stats = dict(e.stats)
                step = stats.get("step_num", stats.get("step"))
                evs.append((e.name[len(PREFIX):], int(e.start_ns), int(e.duration_ns),
                            None if step is None else int(step)))
            if evs:
                out.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return out


def pick_line(lines: Sequence[Sequence[HostEvent]], mark: str) -> Optional[list]:
    """The thread that entered `mark`: the driver holds `train_step`, the
    ring `transfer`. With several (an earlier epoch's ring), the busiest."""
    hits = [ln for ln in lines if any(e[0] == mark for e in ln)]
    return max(hits, key=len) if hits else None


def phase_segments(spans: Sequence[tuple], whole: Collection[str] = ()) -> list[tuple]:
    """One thread's nested spans flattened to disjoint `(start, end, name)`
    pieces named by the innermost span open there. A span named in `whole`
    keeps its children's time under its own name (`log_flush` with its
    `metrics_fetch`)."""
    segs: list[tuple] = []
    stack: list[tuple] = []  # (label, end)
    cursor = 0

    def emit(until: int) -> None:
        nonlocal cursor
        if until > cursor:
            segs.append((cursor, until, stack[-1][0]))
            cursor = until

    for name, start, dur, *_ in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(start)
        cursor = max(cursor, start)
        label = stack[-1][0] if stack and stack[-1][0] in whole else name
        stack.append((label, start + dur))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return segs


def label_intervals(intervals: Sequence[tuple], segs: Sequence[tuple]) -> list[tuple]:
    """Cut the sorted disjoint `(start, end)` intervals by the sorted
    disjoint segments: `(start, end, name)` pieces that cover the intervals
    exactly, `none` where no segment (or only one that names no phase) lies."""
    out, j = [], 0
    for s, e in intervals:
        cur = s
        while j < len(segs) and segs[j][1] <= cur:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            s0, e0, name = segs[k]
            if s0 > cur:
                out.append((cur, s0, NONE))
            piece_end = min(e0, e)
            if piece_end > max(cur, s0):
                out.append((max(cur, s0), piece_end, NONE if name in NOT_A_PHASE else name))
            cur = max(cur, piece_end)
            k += 1
        if cur < e:
            out.append((cur, e, NONE))
    return out


def idle_intervals(ops: Sequence[Event], window_ns: Sequence[int]) -> list[tuple]:
    """The parts of the window in which no device op runs."""
    lo, hi = window_ns
    busy = merge_intervals(
        (max(s, lo), min(s + d, hi)) for _, s, d in ops if d > 0 and s < hi and s + d > lo
    )
    return _subtract([(lo, hi)], busy)


def coverage(spans: Sequence[tuple]) -> tuple:
    """(start of the first, end of the last) span a thread's line holds.
    The profiler keeps a span only if it opened and closed while the host
    was traced, and it traces the device for longer than the host (on the
    v5e the device plane of a 1.5 s trace ran on for 2.7 to 6 s, PERF.md
    section 6, PR 26): outside this interval the line says nothing."""
    return min(e[1] for e in spans), max(e[1] + e[2] for e in spans)


def attribute_idle(
    ops: Sequence[Event],
    window_ns: Sequence[int],
    driver: Optional[Sequence[tuple]],
    ring: Optional[Sequence[tuple]] = None,
    whole: Collection[str] = ("log_flush",),
) -> Optional[dict]:
    """{"window_ns", "idle_ns", "by_driver": {span: idle_ns}, "table":
    {driver span: {ring span: idle_ns}}} over the part of the window that
    the driver's line covers; None without a driver line (a program that
    enters no `moco/` span: every metric read from this stays silent)."""
    if not driver:
        return None
    lo, hi = coverage(driver)
    lo, hi = max(lo, window_ns[0]), min(hi, window_ns[1])
    if hi <= lo:
        return None
    idle = idle_intervals(ops, (lo, hi))
    by_driver: dict = defaultdict(int)
    table: dict = defaultdict(lambda: defaultdict(int))
    ring_segs = phase_segments(ring or (), whole)
    for s, e, name in label_intervals(idle, phase_segments(driver, whole)):
        by_driver[name] += e - s
        for s1, e1, ring_name in label_intervals([(s, e)], ring_segs):
            table[name][ring_name] += e1 - s1
    return {
        "window_ns": [lo, hi],
        "idle_ns": _total(idle),
        "by_driver": dict(by_driver),
        "table": {k: dict(v) for k, v in table.items()},
    }


def step_modules(modules: Sequence[Event], step_module: str = STEP_MODULE) -> list[Event]:
    rx = re.compile(step_module)
    return sorted((m for m in modules if rx.search(m[0])), key=lambda m: m[1])


def driver_waits(mods: Sequence[Event], driver: Sequence[tuple]) -> list[tuple]:
    """[(label, index of the step program that ended while the driver
    waited for it, nanoseconds from that end to the wait's return)] for
    every `throttle_wait` that really waited (a millisecond or more)."""
    ends = [m[1] + m[2] for m in mods]
    out = []
    for name, start, dur, label in driver:
        if name != THROTTLE or label is None or dur < 1_000_000:
            continue
        i = bisect.bisect_right(ends, start + dur) - 1
        if i >= 0 and ends[i] >= start:
            out.append((label, i, start + dur - ends[i]))
    return out


def pair_steps(mods: Sequence[Event], driver: Sequence[tuple], depth: int = 2) -> dict:
    """{step number: its program on the device}, the numbers being those
    the driver's `step` spans carry. A module event has no step number,
    and at the trace's start the device is up to `depth` + 1 steps behind
    the host, so the count is anchored where the driver waited for the
    device: a `throttle_wait` labelled s follows the dispatch of step
    s - 1 and returns when step s - 1 - `depth` has ended (the driver's
    bounded in-flight window, `prefetch_depth`). The step program that
    ended while it waited is that step; all anchors have to agree."""
    firsts = {label - 1 - depth - i for label, i, _ in driver_waits(mods, driver)}
    if len(firsts) != 1:
        return {}
    (first,) = firsts
    return {first + i: m for i, m in enumerate(mods)}


def clock_check(modules: Sequence[Event], driver: Sequence[tuple], step_module: str = STEP_MODULE,
                depth: int = 2) -> dict:
    """For every traced step whose dispatch the trace holds: the lag from
    the start (and the end) of the host's `moco/step` span to the start
    of the device's step program for that step, in nanoseconds. Host and
    device are on one clock if no program starts before its dispatch
    span does; `wait_slack_ns` bounds the clocks' offset from the other
    side (how long after a program's end the driver's wait for it
    returned)."""
    mods = step_modules(modules, step_module)
    paired = pair_steps(mods, driver, depth)
    lags = sorted(
        (paired[step][1] - start, paired[step][1] - (start + dur))
        for name, start, dur, step in driver if name == DISPATCH and step in paired
    )
    if not lags:
        return {"steps": 0}
    slack = sorted(ns for _, _, ns in driver_waits(mods, driver))
    return {
        "steps": len(lags),
        "min_lag_ns": lags[0][0],
        "median_lag_ns": lags[len(lags) // 2][0],
        "max_lag_ns": lags[-1][0],
        "starts_before_dispatch": sum(1 for from_start, _ in lags if from_start < 0),
        "starts_before_dispatch_ends": sum(1 for _, from_end in lags if from_end < 0),
        "wait_slack_ns": [slack[0], slack[-1]],
    }


def cut_steps(ops, modules, lines, step_module: str = STEP_MODULE, steps: int = 2,
              depth: int = 2) -> dict:
    """`steps` whole consecutive steps of a trace, compressed, for a test
    fixture: the first ones whose dispatch the driver's line holds, from
    the first one's dispatch to the last one's end on the device. Device
    ops and modules as `trace_reduce.cut_fixture` keeps them, and every
    host line's `moco/` events that overlap the cut, clipped to it."""
    driver = pick_line(lines, STEP_SCOPE) or []
    paired = pair_steps(step_modules(modules, step_module), driver, depth)
    starts = {step: s for name, s, _, step in driver if name == DISPATCH}
    first = min((k for k in paired if k in starts and all(k + j in paired for j in range(steps))),
                default=None)
    if first is None:
        return {"ops": [], "modules": [], "host_lines": []}
    last = paired[first + steps - 1]
    lo, hi = min(starts[first], paired[first][1]), last[1] + last[2]
    clip = lambda s, d: (max(s, lo), min(s + d, hi) - max(s, lo))
    return {
        "step_module": step_module,
        "ops": [[compress_text(n), s, d] for n, s, d in ops if lo <= s < hi],
        "modules": [[n, s, d] for n, s, d in modules if lo <= s < hi],
        "host_lines": [
            [[n, *clip(s, d), step] for n, s, d, step in ln if s < hi and s + d > lo]
            for ln in lines if any(s < hi and s + d > lo for _, s, d, _ in ln)
        ],
    }


def account(ops, modules, lines, window_ns: Sequence[int], step_module: str = STEP_MODULE,
            depth: int = 2) -> dict:
    """Everything this module can say of one trace, as `host_spans.json`
    keeps it: whether the driver's and the ring's lines were found, the
    part of the window the driver's line covers and the step programs
    started in it, the device's idle there by driver span (a span in
    `whole` with its children, and leaf by leaf), the driver x ring table
    in seconds, and the clock check."""
    driver, ring = pick_line(lines, STEP_SCOPE), pick_line(lines, RING_MARK)
    out = {"window_ns": list(window_ns), "host_lines": len(lines),
           "driver_line": driver is not None, "ring_line": ring is not None}
    idle = attribute_idle(ops, window_ns, driver, ring)
    if idle is None:
        return out
    lo, hi = idle["window_ns"]
    out.update(
        covered_ns=[lo, hi],
        steps=sum(1 for m in step_modules(modules, step_module) if lo <= m[1] < hi),
        idle_ns=idle["idle_ns"], by_driver_ns=idle["by_driver"],
        by_driver_leaf_ns=attribute_idle(ops, window_ns, driver, whole=())["by_driver"],
        idle_s_driver_by_ring={k: {r: ns / 1e9 for r, ns in row.items()}
                               for k, row in idle["table"].items()},
        clock=clock_check(modules, driver, step_module, depth),
    )
    return out


def report(path: str, step_module: str = STEP_MODULE, device: int = 0, depth: int = 2) -> dict:
    """`account` of one profile, over the window `reduce_trace` gives it."""
    loaded = load_events(path, device)
    reduced = reduce_trace(loaded["ops"], loaded["modules"], step_module)
    if not reduced.get("window_ns"):
        return {"steps": 0}
    out = account(loaded["ops"], loaded["modules"], load_host_lines(path), reduced["window_ns"],
                  step_module, depth)
    return {"window_steps": reduced["steps"], "idle_share": reduced["idle_share"], **out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--device", type=int, default=0)
    ap.add_argument("--step-module", default=STEP_MODULE)
    ap.add_argument("--depth", type=int, default=2,
                    help="the driver's bounded in-flight window (`prefetch_depth`)")
    ap.add_argument("--dump", type=int, default=0,
                    help="print N whole traced steps (device ops, modules, host lines), "
                    "compressed, instead of the report: the stuff of a test fixture")
    args = ap.parse_args(argv)
    if args.dump:
        loaded = load_events(args.trace, args.device)
        cut = cut_steps(loaded["ops"], loaded["modules"], load_host_lines(args.trace),
                        args.step_module, args.dump, args.depth)
        print(json.dumps(cut, separators=(",", ":")))
    else:
        print(json.dumps(report(args.trace, args.step_module, args.device, args.depth), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
