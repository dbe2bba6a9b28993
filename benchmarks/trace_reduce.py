"""From a profiler trace to metrics: device busy and idle share, device
time per step, an op-class table, collective time and its exposed part,
the top device operations and the longest idle gaps.

    python benchmarks/trace_reduce.py <profile dir or .xplane.pb> [--device 0]

The reduction works on plain `(name, start_ns, dur_ns)` tuples, so it is
checked on a small recorded trace kept as JSON
(`benchmarks/tests/fixtures/`); only `load_events` touches the
`.xplane.pb` (through `jax.profiler.ProfileData`, nothing but JAX).

Extends `scripts/analyze_trace.py` (which buckets by fusion name and
stops there) with the busy/idle union, self-time attribution for nested
events, collective exposure and the gaps.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
from collections import defaultdict
from typing import Iterable, Optional, Sequence

Event = tuple  # (name, start_ns, dur_ns)

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter", re.I
)

# On a TPU the XLA Ops line names each event by its whole HLO instruction:
# `%convert_reduce_fusion.8 = (f32[256]{..}, ..) fusion(..), kind=kOutput, ..`.
# The short name is what stands before ` = `; the opcode follows the result
# shape. The program puts no `named_scope` on its layers yet (PERF.md
# section 7), so classes go by what the compiler says an op is: the kind of
# a fusion (kOutput: rooted in a convolution or dot, with its epilogue, e.g.
# the BN statistics; kInput: rooted in a reduction; kLoop: elementwise), the
# opcode otherwise.
_OPCODE_RE = re.compile(r"[\]})]\s([a-z][a-z\-]*)\(")
_COPY_OPS = {
    "copy", "copy-start", "copy-done", "slice", "async-start", "async-done", "transpose",
    "reverse", "pad", "bitcast", "concatenate", "dynamic-update-slice", "dynamic-slice",
    "broadcast", "convert", "iota", "reshape",
}


def short_name(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


def opcode(text: str) -> str:
    if " = " not in text:
        return ""
    m = _OPCODE_RE.search(text.split(" = ", 1)[1])
    return m.group(1) if m else ""


def op_class(text: str) -> str:
    name, op = short_name(text), opcode(text)
    if COLLECTIVE_RE.search(op) or (not op and COLLECTIVE_RE.search(name)):
        return "collective"
    if 'custom_call_target="tpu_custom_call"' in text:
        return "pallas_kernel"
    if "kind=kOutput" in text or op in ("convolution", "dot"):
        return "conv_matmul_fusion"
    if "kind=kInput" in text or op in ("reduce", "reduce-window", "select-and-scatter", "sort"):
        return "reduce_fusion"
    if "kind=kLoop" in text or op == "fusion" or (not op and "fusion" in name):
        return "elementwise_fusion"
    if op in _COPY_OPS or (not op and re.match(r"copy|slice|transpose|pad", name)):
        return "copy_layout"
    return "other"


def bucket_name(text: str) -> str:
    """`%fusion.123 = ...` -> `fusion`: instances of one fusion kind
    together, as scripts/analyze_trace.py buckets them."""
    return re.sub(r"[.\-_]\d+$", "", short_name(text))


def compress_text(text: str) -> str:
    """An event's HLO text cut down to what the reduction reads (short
    name, opcode, fusion kind, custom-call target; a custom call keeps
    its operands, by which a kernel is found): for recorded fixtures."""
    op = opcode(text)
    if op == "custom-call" or " = " not in text:
        return text[:600]
    tail = "".join(re.findall(r", kind=k\w+", text)[:1])
    return f"%{short_name(text)} = {op}(...){tail}"


def cut_fixture(ops: Sequence[Event], modules: Sequence[Event], step_module: str, steps: int) -> dict:
    """The ops and modules of `steps` whole consecutive steps (from the
    second matching module on, so the first is never a partial one)."""
    rx = re.compile(step_module)
    hits = sorted((m for m in modules if rx.search(m[0])), key=lambda m: m[1])[1 : steps + 1]
    if not hits:
        return {"ops": [], "modules": []}
    lo, hi = hits[0][1], hits[-1][1] + hits[-1][2]
    return {
        "ops": [[compress_text(n), s, d] for n, s, d in ops if lo <= s < hi],
        "modules": [[n, s, d] for n, s, d in modules if lo <= s < hi],
    }


def module_name(text: str) -> str:
    """`jit__augment(1545...)` -> `augment`."""
    return re.sub(r"^jit_+|\(.*$", "", text)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime
    )
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return hits[-1]


def load_events(path: str, device: Optional[int] = 0) -> dict:
    """{"ops": [Event], "modules": [Event], "planes": [names], "devices": n}
    of device plane number `device` (in sorted plane-name order)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = list(data.planes)
    dev_planes = sorted(
        (p for p in planes if re.match(r"^/device:(TPU|GPU):\d+$", p.name)),
        key=lambda p: int(p.name.rsplit(":", 1)[1]),
    )
    out = {"ops": [], "modules": [], "planes": [p.name for p in planes],
           "devices": len(dev_planes), "lines": {}}
    if not dev_planes:
        return out
    plane = dev_planes[device or 0]
    for line in plane.lines:
        evs = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
        out["lines"][line.name] = len(evs)
        if line.name == "XLA Ops":
            out["ops"] = evs
        elif line.name == "XLA Modules":
            out["modules"] = evs
    return out


def merge_intervals(intervals: Iterable[tuple]) -> list[tuple]:
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    merged: list[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _total(intervals: Sequence[tuple]) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a: Sequence[tuple], b: Sequence[tuple]) -> list[tuple]:
    """Parts of the disjoint sorted intervals `a` not covered by `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Event]) -> list[tuple]:
    """[(name, start_ns, self_ns)]: each event's duration minus what its nested
    children cover (a `while` op contains its body's ops on the same
    line), so class totals do not count a nanosecond twice."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: list[list] = []  # [name, end, self, start]
    for name, start, dur in order:
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[3], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur, start])
    while stack:
        done = stack.pop()
        out.append((done[0], done[3], done[2]))
    return out


def leaf_events(events: Sequence[Event]) -> list[Event]:
    """The events that contain no other event: a parent such as `while`
    spans its children and would otherwise hide every gap and every
    exposed collective inside it."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        if nxt is None or nxt[1] >= start + dur:
            out.append((name, start, dur))
    return out


def reduce_trace(
    ops: Sequence[Event],
    modules: Sequence[Event] = (),
    step_module: Optional[str] = None,
    top: int = 10,
) -> dict:
    """All the numbers, in seconds. With `step_module` (a regex on the
    XLA Modules line, e.g. `jit_step_fn`), the window runs from the first
    matching module's start to the last one's end and `steps` counts
    them; without it the window is the span of the ops themselves."""
    ops = [e for e in ops if e[2] > 0]
    if not ops:
        return {"busy_s": 0.0, "window_s": 0.0, "steps": 0}
    steps, step_module_ns = 0, None
    lo, hi = min(e[1] for e in ops), max(e[1] + e[2] for e in ops)
    if step_module:
        rx = re.compile(step_module)
        hits = [m for m in modules if rx.search(m[0])]
        if hits:
            steps = len(hits)
            lo, hi = min(m[1] for m in hits), max(m[1] + m[2] for m in hits)
            step_module_ns = sum(m[2] for m in hits) / steps
    inside = [(n, max(s, lo), min(s + d, hi) - max(s, lo)) for n, s, d in ops
              if s < hi and s + d > lo]
    busy = merge_intervals((s, s + d) for _, s, d in inside)
    busy_ns, window_ns = _total(busy), hi - lo

    # which program an op belongs to: the module event that contains its start
    mods = sorted((m for m in modules if m[2] > 0), key=lambda m: m[1])
    mod_starts = [m[1] for m in mods]

    def owner(start: int) -> str:
        i = bisect.bisect_right(mod_starts, start) - 1
        if i >= 0 and start < mods[i][1] + mods[i][2]:
            return module_name(mods[i][0])
        return "?"

    by_bucket: dict = defaultdict(int)
    by_class: dict = defaultdict(int)
    by_module: dict = defaultdict(int)
    for name, start, self_ns in self_times(inside):
        prog = owner(start)
        by_bucket[f"{prog}/{bucket_name(name)}"] += self_ns
        by_class[op_class(name)] += self_ns
        by_module[prog] += self_ns

    is_coll = lambda text: op_class(text) == "collective"
    coll = merge_intervals((s, s + d) for n, s, d in inside if is_coll(n))
    other = merge_intervals((s, s + d) for n, s, d in leaf_events(inside) if not is_coll(n))
    exposed_ns = _total(_subtract(coll, other))

    # idle gaps, labelled by the device operations on either side: the
    # program's host spans are not on the profiler's clock yet
    ends = sorted(inside, key=lambda e: e[1] + e[2])
    starts = sorted(inside, key=lambda e: e[1])
    gap_sum: dict = defaultdict(int)

    end_keys = [e[1] + e[2] for e in ends]
    start_keys = [e[1] for e in starts]
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        before = ends[bisect.bisect_right(end_keys, e0) - 1][0]
        after = starts[bisect.bisect_left(start_keys, s1)][0]
        gap_sum[f"after {bucket_name(before)} before {bucket_name(after)}"] += s1 - e0
    if busy and busy[0][0] > lo:
        gap_sum["window start"] += busy[0][0] - lo
    if busy and busy[-1][1] < hi:
        gap_sum["window end"] += hi - busy[-1][1]

    sec = lambda ns: ns / 1e9
    ranked = lambda d: [[k, sec(v)] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": sec(busy_ns),
        "window_s": sec(window_ns),
        "window_ns": [lo, hi],
        "idle_share": 1.0 - busy_ns / window_ns if window_ns else None,
        "steps": steps,
        "step_device_s": sec(busy_ns) / steps if steps else None,
        # the step program's own mean duration, start to end on the device
        "step_module_s": sec(step_module_ns) if step_module_ns else None,
        "op_classes": ranked(by_class),
        "modules": ranked(by_module),
        "device_ops": ranked(by_bucket),
        "idle_gaps": ranked(gap_sum),
        "collective_s": sec(_total(coll)),
        "collective_exposed_s": sec(exposed_ns),
        "longest_gap_s": sec(max((s1 - e0 for (_, e0), (s1, _) in zip(busy, busy[1:])), default=0)),
    }


def ops_inside(ops: Sequence[Event], reduced: dict) -> list[Event]:
    """The ops that start inside the reduced trace's window."""
    lo, hi = reduced.get("window_ns", (0, 0))
    return [e for e in ops if lo <= e[1] < hi]


def kernel_seconds(ops: Sequence[Event], pattern: str) -> tuple[float, int]:
    """(summed device seconds, event count) of the ops matching `pattern`."""
    rx = re.compile(pattern)
    hits = [d for n, _, d in ops if rx.search(n)]
    return sum(hits) / 1e9, len(hits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--device", type=int, default=0)
    ap.add_argument("--step-module", default=None)
    args = ap.parse_args(argv)
    loaded = load_events(args.trace, args.device)
    report = reduce_trace(loaded["ops"], loaded["modules"], args.step_module)
    report["planes"], report["lines"] = loaded["planes"], loaded["lines"]
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
