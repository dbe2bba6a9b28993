"""Reader: a number of the reduced device trace (`trace_reduce.reduce_trace`).

spec: {"reader": "trace", "key": "idle_share" | "step_device_s" |
       "collective_s" | "collective_exposed_s" | ..., "per_step": bool,
       "scale": number}
"""


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    v = trace.get(spec["key"])
    if v is None:
        return None
    if spec.get("per_step"):
        if not trace.get("steps"):
            return None
        v = v / trace["steps"]
    return v * spec.get("scale", 1.0)
