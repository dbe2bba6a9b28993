"""Reader: one field of an event line of the program's own `metrics.jsonl`.

spec: {"reader": "event_line", "event": the line's `event`, "key": field,
       "scale": number}

An event line is written once, when the thing happens (the train driver's
`setup` line when its first step's outputs are ready), so it lies outside
the window and is read from `<workdir>/metrics.jsonl` itself; the last
such line counts. A program that writes no such line reads nothing.
"""

import os

from benchmarks.harness.common import read_jsonl


def read(spec: dict, ctx: dict):
    workdir = (ctx.get("train_config") or {}).get("workdir")
    if not workdir:
        return None
    hits = [ln for ln in read_jsonl(os.path.join(workdir, "metrics.jsonl"))
            if ln.get("event") == spec["event"] and ln.get(spec["key"]) is not None]
    return hits[-1][spec["key"]] * spec.get("scale", 1.0) if hits else None
