"""`correct`'s two readings at a train cell's own size.

    chiprun -- python3 benchmarks/control.py <cell> <seed>[,<seed>...]

For each seed, in one process: the program's own modules against the
plain reference (`check_train` as every run makes it: the lower reading),
then the control for each type of `CONTROLS`: the reference in the
program's place with its matmul and convolution operands rounded to that
type, one step under the configurations' bfloat16 (the upper reading).
Prints one JSON line a reading and writes them all to
`benchmarks/out/control-<cell>.json`. The limits in the reference modules
(`TOLERANCES`) lie between these readings (PERF.md section 2); no run of
the benchmark calls this, `tests/test_control.py` drives `readings` at a
test's size.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# one step under bfloat16, the compute type every configuration states
CONTROLS = ("float8_e4m3fn", "int8")


def readings(manifest, cell_name: str, seeds, rehearse: bool = False):
    """One row a seed and side (`control` None: the program itself): every
    number `correct` compares, and `ok` as a run would judge it."""
    import jax.numpy as jnp

    from benchmarks.harness import common, correct

    cell = manifest.cell(cell_name)
    cfg_file = manifest.config_file(cell["config"])
    traffic_file = manifest.traffic_file(cell["traffic"])
    sample_n = int(common.merged(traffic_file, rehearse)["correct_sample"])
    ref, inputs = manifest.family(cfg_file)
    for seed in seeds:
        config = common.build_train_config(cfg_file, traffic_file, seed, "/nonexistent", rehearse)
        config = dataclasses.replace(config, parallel=dataclasses.replace(config.parallel, num_data=1))
        for control in (None, *CONTROLS):
            out = correct.check_train(
                config, ref, inputs, seed, sample_n=sample_n, gradient=False,
                control=control and getattr(jnp, control),
            )
            yield {"cell": cell_name, "seed": seed, "control": control, "ok": out["ok"],
                   **{k: v["value"] for k, v in correct.compared(out, ref).items()}}


def main(argv) -> int:
    cell_name, seeds = argv
    from benchmarks.harness import common
    from benchmarks.harness.manifest import Manifest

    common.setup_compile_cache()  # before jax is imported
    common.tune_compile_cache()
    device = common.require_devices(1, False)
    rows = []
    for row in readings(Manifest(), cell_name, [int(s) for s in seeds.split(",")]):
        rows.append({**row, "device": device["kind"]})
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, f"control-{cell_name}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
