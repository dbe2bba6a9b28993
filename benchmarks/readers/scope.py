"""Reader: device time per whole step by the `moco.` scope an op ran under.

spec: {"reader": "scope", "scope": "moco.<part>" | "none", "scale": number}
      (nanoseconds a whole step times `scale`; "none" is the time of the
       ops under no `moco.` scope)

The program runs each part of its step, of an expert layer's dispatch and
of the augmentation program under a `jax.named_scope` named in
`moco_tpu/obs/trace.py::STEP_SCOPES`. XLA keeps the scopes in each
instruction's `op_name` metadata. On a TPU the op events of the device's
`XLA Ops` line carry no such stat: the path lives in the program's HLO,
which the profiler keeps in its `/host:metadata` plane (an `Hlo Proto`
stat for each program, named as the `XLA Modules` line names it). An
op's path is its instruction's, joined on the program it ran in and its
instruction name. Its self time (`trace_reduce.self_times`) goes to the
innermost `moco.` segment of that path, wherever it stands: inside
`jvp(...)`, `transpose(...)` (the backward pass) or a rematerialised
computation too.

Only whole programs count: each step program (`step_module`) that the
trace's two ends do not clip and that follows another, with the
augmentation programs (`augment_module`) that ran since that other one;
the time is divided by the number of steps counted. The account
keeps its own busy time (the union of the counted ops) beside the sum of
the scopes, which the scopes plus "none" make up.

The profile is read once a run, from `<workdir>/profile` (readers run
before the harness removes it); the whole account (each scope's time and
its five largest ops, the whole steps, the busy time) is kept as
`<workdir>/scopes.json`, which the later metrics of the run read. A
program that names no `moco.` scope leaves nothing to read.
"""

import bisect
import json
import os
import re
from collections import Counter, defaultdict

from benchmarks.trace_reduce import (
    bucket_name, find_xplane, load_events, merge_intervals, module_name, self_times, short_name,
)

SCOPE_RE = re.compile(r"moco\.[A-Za-z_]+(?:\.[A-Za-z_]+)*")
STEP_MODULE, AUGMENT_MODULE = "jit_step_fn", "jit__augment"


def innermost(path: str) -> str:
    """The innermost `moco.` scope of an op_name path, or "none"."""
    found = SCOPE_RE.findall(path or "")
    return found[-1] if found else "none"


def _fields(buf):
    """(field number, value) of a protobuf message's wire bytes: an int for
    a varint, a memoryview for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:  # fixed 64 / 32 bits
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _first(buf, number):
    return next((v for f, v in _fields(buf) if f == number), b"")


def op_paths(xplane: str) -> dict:
    """{program name: {instruction name: op_name path}} from the HLO the
    profiler keeps in the `/host:metadata` plane. The fields read:
    XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5 (map
    entries: key 1, value 2); XEventMetadata.name 2, stats 5; XStat
    metadata_id 1, bytes_value 6; XStatMetadata id 1, name 2; HloProto
    hlo_module 1; HloModuleProto computations 3; HloComputationProto
    instructions 2; HloInstructionProto name 1, metadata 7; OpMetadata
    op_name 2."""
    with open(xplane, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1 or bytes(_first(plane, 2)) != b"/host:metadata":
            continue
        stat_names = {}
        for f, entry in _fields(plane):
            if f == 5:
                meta = dict(_fields(_first(entry, 2)))
                stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        for f, entry in _fields(plane):
            if f != 4:
                continue
            event = _first(entry, 2)
            hlo = next((dict(_fields(st)).get(6) for n, st in _fields(event) if n == 5
                        and stat_names.get(dict(_fields(st)).get(1)) == "Hlo Proto"), None)
            if hlo is None:
                continue
            paths = out.setdefault(bytes(_first(event, 2)).decode(), {})
            for c, comp in _fields(_first(hlo, 1)):
                for i, inst in (_fields(comp) if c == 3 else ()):
                    if i == 2:
                        fields = dict(_fields(inst))
                        paths[bytes(fields.get(1, b"")).decode()] = bytes(
                            _first(fields.get(7, b""), 2)
                        ).decode()
    return out


def load_scoped_ops(path: str, device: int = 0) -> dict:
    """{"ops": [(text, start_ns, dur_ns, op_name path)], "modules": [Event]}
    of device plane number `device` (`trace_reduce.load_events`), each op's
    path that of its instruction in the program it ran in."""
    xplane = find_xplane(path)
    loaded = load_events(xplane, device)
    paths = op_paths(xplane)
    mods = sorted((m for m in loaded["modules"] if m[2] > 0), key=lambda m: m[1])
    starts = [m[1] for m in mods]
    ops = []
    for text, start, dur in loaded["ops"]:
        i = bisect.bisect_right(starts, start) - 1
        program = mods[i][0] if i >= 0 and start < mods[i][1] + mods[i][2] else ""
        ops.append((text, start, dur, paths.get(program, {}).get(short_name(text), "")))
    return {"ops": ops, "modules": loaded["modules"]}


def whole_programs(modules, step_module: str = STEP_MODULE,
                   augment_module: str = AUGMENT_MODULE) -> tuple[list, int]:
    """(the programs whose ops count, the number of whole steps). Only the
    first and last events of the modules line can be cut by the trace's
    ends. A step counts when a step program ran before it and it is not
    the line's last event: then it and every augmentation program that ran
    since the step program before it are whole."""
    mods = sorted((m for m in modules if m[2] > 0), key=lambda m: m[1])
    step_rx, aug_rx = re.compile(step_module), re.compile(augment_module)
    counted, steps, since_step = [], 0, None  # None: no step program yet
    for i, m in enumerate(mods):
        if step_rx.search(m[0]):
            if since_step is not None and i < len(mods) - 1:
                counted += [m] + since_step
                steps += 1
            since_step = []
        elif aug_rx.search(m[0]) and since_step is not None:
            since_step.append(m)
    return sorted(counted, key=lambda m: m[1]), steps


def account(ops, modules, step_module: str = STEP_MODULE,
            augment_module: str = AUGMENT_MODULE, top: int = 5) -> dict | None:
    """Each scope's ns per whole step, its `top` largest ops, and the busy
    time of the counted ops; None where no counted op names a scope."""
    programs, steps = whole_programs(modules, step_module, augment_module)
    if not steps:
        return None
    starts = [m[1] for m in programs]

    def program_of(start: int):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < programs[i][1] + programs[i][2]:
            return programs[i]
        return None

    inside, path_of = [], {}
    for text, start, dur, path in ops:
        prog = program_of(start)
        if prog is not None and dur > 0:
            end = min(start + dur, prog[1] + prog[2])
            inside.append((text, start, end - start))
            path_of[(text, start)] = (path, module_name(prog[0]))
    if not any(SCOPE_RE.search(p) for p, _ in path_of.values()):
        return None
    by_scope, by_op = defaultdict(int), defaultdict(lambda: defaultdict(int))
    for text, start, self_ns in self_times(inside):
        path, prog = path_of[(text, start)]
        scope = innermost(path)
        by_scope[scope] += self_ns
        by_op[scope][f"{prog}/{bucket_name(text)}"] += self_ns
    busy_ns = sum(e - s for s, e in merge_intervals((s, s + d) for _, s, d in inside))
    per_step = lambda ns: ns / steps
    return {
        "steps": steps,
        "programs": dict(Counter(module_name(m[0]) for m in programs)),
        "busy_ns_per_step": per_step(busy_ns),
        "scoped_ns_per_step": per_step(sum(by_scope.values())),
        "scopes": {
            scope: {
                "ns_per_step": per_step(ns),
                "top": [[op, per_step(t)] for op, t in
                        sorted(by_op[scope].items(), key=lambda kv: -kv[1])[:top]],
            }
            for scope, ns in sorted(by_scope.items(), key=lambda kv: -kv[1])
        },
    }


def _account(ctx: dict):
    workdir = (ctx.get("train_config") or {}).get("workdir")
    if not workdir or not (ctx.get("trace") or {}).get("steps"):
        return None
    kept = os.path.join(workdir, "scopes.json")
    if os.path.exists(kept):
        with open(kept) as f:
            return json.load(f)
    try:
        loaded = load_scoped_ops(os.path.join(workdir, "profile"))
    except FileNotFoundError:
        return None
    result = account(loaded["ops"], loaded["modules"])
    with open(kept, "w") as f:
        json.dump(result, f, indent=1)
    return result


def read(spec: dict, ctx: dict):
    acc = _account(ctx)
    if not acc:
        return None
    ns = acc["scopes"].get(spec["scope"], {}).get("ns_per_step", 0.0)
    return ns * spec.get("scale", 1.0)
