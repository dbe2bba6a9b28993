"""Reader: counters a replica only publishes as lifetime totals, differenced
over the window's first and last flusher lines.

spec: {"reader": "serve_counters", "what": "occupancy_pct" | "recompiles"}

occupancy: `serve/occupancy` is valid rows / padded rows since boot and
`serve/bucket_<n>` counts executions per bucket, so padded = sum(n x
count) and valid = occupancy x padded at either end; the window's share
is the ratio of the differences.
"""


def _padded(line: dict) -> float:
    return float(sum(
        int(k.rsplit("_", 1)[1]) * v for k, v in line.items()
        if k.startswith("serve/bucket_") and v is not None
    ))


def read(spec: dict, ctx: dict):
    lines = ctx.get("serve_lines") or []
    if len(lines) < 2:
        return None
    first, last = lines[0], lines[-1]
    if spec["what"] == "recompiles":
        return float(last["serve/recompiles_after_warmup"])
    if spec["what"] == "occupancy_pct":
        p0, p1 = _padded(first), _padded(last)
        if p1 <= p0 or first.get("serve/occupancy") is None:
            return None
        valid = last["serve/occupancy"] * p1 - first["serve/occupancy"] * p0
        return 100.0 * valid / (p1 - p0)
    raise ValueError(f"unknown serve counter {spec['what']!r}")
