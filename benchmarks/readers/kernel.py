"""Reader: one kernel's device time per step, or its share of its roofline.

spec: {"reader": "kernel", "pattern": regex on the XLA Ops line's names,
       "what": "ms_per_step" | "roofline",
       "required": "infonce"}  (which function of harness/flops.py gives
                                the operations and bytes the call needs)

The roofline share is the least time the chip could take for what the
algorithm needs (the larger of operations / peak FLOP/s and bytes / peak
bytes/s) over the kernel's measured time, in per cent. Finds nothing when
no event matches: a kernel nobody can find by name reports no number.
"""

from benchmarks.harness import flops
from benchmarks.trace_reduce import kernel_seconds


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
    if spec["required"] != "infonce":
        raise ValueError(f"no required-work function {spec['required']!r}")
    moco = ctx["train_config"]["moco"]
    if not moco["num_negatives"]:
        return None
    need = flops.infonce_required(
        ctx["train_config"]["data"]["global_batch"] // ctx["chips"],
        moco["dim"], moco["num_negatives"],
    )
    least_s, _ = flops.roofline_seconds(need, ctx["peaks"])
    return 100.0 * least_s / per_step
