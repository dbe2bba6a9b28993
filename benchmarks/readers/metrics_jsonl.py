"""Reader: fields of the program's own `metrics.jsonl` lines inside the
window (the train driver's, or a serving replica's flusher lines).

spec: {"reader": "metrics_jsonl", "lines": "train" | "serve",
       "keys": [field, ...]   (summed per line; a line missing one is skipped),
       "reduce": "mean" | "mean_of_changes",
                 (mean_of_changes: a field that is sampled rarely and repeated on
                  every line until the next sample, such as the probe's
                  `t_dispatch`: the mean of the values it changed TO inside the
                  window, so a sample taken before the window is left out)
       "weight_key": field    (optional: weighted mean, e.g. by
                               serve/trace_requests, since a serve line's
                               stage means cover that many requests),
       "scale": number}
"""

from benchmarks.harness.stats import mean, weighted_mean


def read(spec: dict, ctx: dict):
    lines = ctx.get(f"{spec['lines']}_lines") or []
    vals = []
    for ln in lines:
        parts = [ln.get(k) for k in spec["keys"]]
        if any(p is None for p in parts):
            continue
        vals.append((sum(parts), ln.get(spec["weight_key"], 0) if "weight_key" in spec else 1))
    if not vals:
        return None
    how = spec.get("reduce", "mean")
    if how == "mean":
        v = weighted_mean(vals) if "weight_key" in spec else mean([x for x, _ in vals])
    elif how == "mean_of_changes":
        seq = [x for x, _ in vals]
        v = mean([b for a, b in zip(seq, seq[1:]) if b != a])
    else:
        raise ValueError(f"unknown reduce {how!r}")
    return None if v is None else v * spec.get("scale", 1.0)
