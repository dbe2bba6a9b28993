"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json, finds its configuration, traffic and
per-layer metric files by name, and runs it through the program's normal
entry point (`moco_tpu.train.train`, or a serving replica's `main`). The
last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with `--trace 0`, its
per-layer metrics with `--trace 1`), `device`, with `--trace 1`
`breakdown`, and last `compared`: every number `correct` rests on beside
its limit, which are also the last lines of standard error. Everything
else (the window's log lines, the latency list, the reduced trace) goes
to `benchmarks/out/<cell>-<seed>-<trace>.json`.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result. `--rehearse` is for the CPU: a tiny preset, counts and
`correct` only, never a rate or a device metric.
"""

from __future__ import annotations

import time

_T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size: counts and `correct` only")
    ap.add_argument("--sweep", default="",
                    help="serve cells: comma-separated offered rates (req/s) to try in "
                    "one boot, to find the knee; prints a table, no result line")
    ap.add_argument("--sweep-seconds", type=float, default=10.0)
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json: a candidate cell that is "
                    "not admitted yet (benchmarks/candidates/)")
    ap.add_argument("--dump-trace-events", type=int, default=0,
                    help="keep N whole traced steps, compressed, in the detail file (to cut a fixture)")
    args = ap.parse_args(argv)

    from benchmarks.harness import common
    from benchmarks.harness.manifest import Manifest

    common.set_process_start(_T_START)
    manifest = Manifest(manifest_path=args.manifest)
    cell = manifest.cell(args.workload)
    if args.seconds is None:
        args.seconds = float(manifest.raw["run_seconds"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell['chips']}"
            )
    else:
        common.setup_compile_cache()
    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    else:
        common.tune_compile_cache()
    kind = manifest.traffic_file(cell["traffic"])["kind"]
    if kind == "train":
        from benchmarks.harness import train_cell as runner
    elif kind == "serve":
        from benchmarks.harness import serve_cell as runner
    else:
        raise SystemExit(f"traffic file of {cell['traffic']!r} has unknown kind {kind!r}")
    result = runner.run(manifest, cell, args, _T_START)

    detail = result.pop("_detail", {})
    compared = result.pop("compared", None)
    if compared is not None:
        result["compared"] = compared  # last in the line
    os.makedirs(common.OUT_DIR, exist_ok=True)
    out_path = os.path.join(common.OUT_DIR, f"{cell['name']}-{args.seed}-{args.trace}.json")
    with open(out_path, "w") as f:
        json.dump({"result": result, **detail}, f)
    # the driver's contract: each number compared beside its limit "as its last lines on
    # standard error, and in the result's line too, under a key of its own that comes last
    # there" (of a run that is not correct the driver keeps the end of each and nothing else)
    for name, c in (compared or {}).items():
        limits = " ".join(f"{k}={v}" for k, v in c.items() if k != "value")
        print(f"compared {name}: value={c['value']} {limits}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
