"""One chip-side check: is `block_until_ready` a sufficient sync on this
runtime, or does a timing need a host transfer of the result?

    python3 benchmarks/sync_check.py        (on the chip; PERF.md section 7)

Times the same chain of dependent steps (a ResNet-50 forward and backward
at batch 128, each step consuming the last one's parameters) twice over,
alternating: once ended by `jax.block_until_ready` on the outputs, as the
driver's `StepTimeProbe` does, and once by fetching the last loss to the
host, as the driver's log line does (the `device_get` the benchmark's rate
is stamped after). If the runtime returned from `block_until_ready` before
the device had finished, the first would read shorter.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmarks.harness import common

    common.setup_compile_cache()
    import jax
    import jax.numpy as jnp

    common.tune_compile_cache()
    device = common.require_devices(1, rehearse=False)
    from moco_tpu.core import build_encoder
    from moco_tpu.utils.config import PRESETS

    enc = build_encoder(PRESETS["imagenet_v2"].moco)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 224, 224, 3), jnp.float32)
    variables = jax.jit(lambda r: enc.init(r, x[:1], train=False))(jax.random.PRNGKey(0))
    params, stats = variables["params"], variables["batch_stats"]

    @jax.jit
    def step(params):
        def loss_fn(p):
            out, _ = enc.apply({"params": p, "batch_stats": stats}, x, train=True,
                               mutable=["batch_stats"])
            return jnp.mean(jnp.square(out))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads), loss

    params, loss = step(params)
    float(loss)
    steps, rounds = 20, 6
    timings = {"block_until_ready": [], "host_transfer": []}
    for r in range(rounds):
        for how in (("block_until_ready", "host_transfer") if r % 2 == 0
                    else ("host_transfer", "block_until_ready")):
            t0 = time.perf_counter()
            for _ in range(steps):
                params, loss = step(params)
            if how == "block_until_ready":
                jax.block_until_ready((params, loss))
            else:
                float(loss)
            timings[how].append((time.perf_counter() - t0) / steps * 1e3)
            float(loss)  # drain whatever either way left behind
    out = {
        "device": device, "steps": steps, "rounds": rounds,
        "ms_per_step": {k: {"median": statistics.median(v), "all": v} for k, v in timings.items()},
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
