"""Reader: whole-step model FLOP/s utilisation of one chip, in per cent.

The operations one step needs over the global batch (`harness/flops.py`,
from the parameter shapes; nothing recomputed counts), divided among the
chips, over the device-busy time per step on device 0 times the chip's
peak. A whole-step utilisation, not a kernel's roofline share.

spec: {"reader": "mfu"}
"""


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace") or {}
    step_s, flops = trace.get("step_device_s"), ctx.get("step_flops")
    if not step_s or not flops:
        return None
    return 100.0 * flops / ctx["chips"] / (step_s * ctx["peaks"]["flops_per_s"])
