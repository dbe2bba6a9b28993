"""Reader: one kernel's device time per step, or its share of its roofline.

spec: {"reader": "kernel", "pattern": regex on the XLA Ops line's names,
       "what": "ms_per_step" | "roofline",
       "required": name}  (`required/<name>.py` beside `readers/`: its
                           `required(ctx)` gives the {"flops", "bytes"}
                           the algorithm needs of one chip in one step,
                           or None where the run has no such work)

The roofline share is the least time the chip could take for what the
algorithm needs (the larger of operations / peak FLOP/s and bytes / peak
bytes/s) over the kernel's measured time, in per cent. Finds nothing when
no event matches: a kernel nobody can find by name reports no number. A
`required` that names no file is an error.
"""

import os

from benchmarks.harness.flops import roofline_seconds
from benchmarks.harness.manifest import load_module
from benchmarks.trace_reduce import kernel_seconds

# the benchmark directory this reader was loaded from: a temporary copy's
# reader finds the temporary copy's required-work modules
_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace") or {}
    steps = trace.get("steps")
    if not steps or not ctx.get("trace_ops"):
        return None
    seconds, count = kernel_seconds(ctx["trace_ops"], spec["pattern"])
    if not count:
        return None
    per_step = seconds / steps
    if spec["what"] == "ms_per_step":
        return per_step * 1e3
    need = load_module(_BENCH_DIR, "required", spec["required"]).required(ctx)
    if need is None:
        return None
    least_s, _ = roofline_seconds(need, ctx["peaks"])
    return 100.0 * least_s / per_step
