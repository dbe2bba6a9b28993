#!/usr/bin/env python
"""Collective-schedule sanitizer smoke: prove the runtime divergence
detector end-to-end on a fake-8-device mesh, asserted hard.

    python scripts/sanitizer_smoke.py [--workdir DIR]

Two legs over the SAME real collective schedule (the a2a Shuffle-BN
exchange + a grad-style psum + the queue's key all_gather, traced
through `obs/comms.py` tags on an 8-virtual-device mesh):

1. **control** — two simulated processes record the schedule cleanly;
   their hashes agree, `ScheduleSanitizer.check()` passes, and the
   driver-level run (`--sanitize-collectives` equivalent) writes
   `collective_schedule_hash` on its metrics lines. Exit contribution:
   0.
2. **chaos** — process 1 re-records under an injected
   `diverge@site=shuffle.a2a` fault (`utils/faults.py`). Its hash must
   differ, `check()` must raise `ScheduleDivergenceError`, the message
   must carry a PER-SITE diff naming `shuffle.a2a`, and
   `schedule_diff.json` must land on disk (the CI artifact).
3. **zero23** — the ZeRO-2/3 bucketed collective schedule
   (parallel/zero.py `BucketPlan`: per-bucket `zero.gather_q.b<i>` /
   `zero.scatter.b<i>` sites): two clean processes agree on the
   bucketed schedule, and an injected `diverge@site=zero.gather_q.b0`
   is caught with the bucket named in the per-site diff.

The smoke exits nonzero if the detector misses the divergence OR
false-positives on the clean leg.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# 8 virtual CPU devices, pinned BEFORE jax initializes (same trick as
# tests/conftest.py and scripts/fleet_smoke.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

DIVERGE_SITE = "shuffle.a2a"


def trace_schedule(process_index: int) -> "ScheduleRecorder":
    """Trace the real collective schedule into a fresh recorder
    simulating one process: shuffle a2a + unshuffle + key all_gather +
    grad psum, all comms-tagged, on the 8-device mesh. A fresh
    shard_map closure per call forces a fresh trace so the tags
    re-fire."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from moco_tpu.analysis.sanitizer import ScheduleRecorder, install_recorder
    from moco_tpu.obs import comms
    from jax import shard_map
    from moco_tpu.parallel.shuffle import (
        balanced_shuffle,
        balanced_unshuffle,
        unshuffle_gather,
    )

    recorder = ScheduleRecorder(process_index=process_index)
    prev = install_recorder(recorder)
    try:
        import numpy as np

        devices = jax.devices()
        mesh = Mesh(np.array(devices), ("data",))
        n = len(devices)

        def step(x, rng):
            y = balanced_shuffle(rng, x, "data")
            k = y * 2.0
            k = balanced_unshuffle(rng, k, "data")  # mocolint: disable=JX003  (involution reuses the key on purpose, same contract as parallel/shuffle.py)
            _, k_global = unshuffle_gather(k, jnp.argsort(jnp.arange(x.shape[0] * n)), "data")
            with comms.tag("grad.psum", "psum", k, n):
                g = lax.psum(k, "data")
            return g + k_global.sum()

        fn = shard_map(
            step, mesh=mesh,
            in_specs=(P("data"), P()), out_specs=P("data"),
            check_vma=False,
        )
        x = jnp.arange(16 * n * 4, dtype=jnp.float32).reshape(16 * n, 4)
        rng = jax.random.PRNGKey(0)
        jax.block_until_ready(jax.jit(fn)(x, rng))
    finally:
        install_recorder(prev)
    return recorder


ZERO_DIVERGE_SITE = "zero.gather_q.b0"


def trace_zero_schedule(process_index: int) -> "ScheduleRecorder":
    """Trace the ZeRO-2/3 bucketed collective schedule into a fresh
    recorder simulating one process: a BucketPlan gather + scatter over
    a toy two-leaf tree (small bucket size forces >1 bucket) on the
    8-device mesh, every bucket comms-tagged."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from moco_tpu.analysis.sanitizer import ScheduleRecorder, install_recorder
    from jax import shard_map
    from moco_tpu.parallel.zero import BucketPlan, shard_tree

    recorder = ScheduleRecorder(process_index=process_index)
    prev = install_recorder(recorder)
    try:
        devices = jax.devices()
        mesh = Mesh(np.array(devices), ("data",))
        n = len(devices)
        tree = {
            "a": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
            "b": jnp.arange(100, dtype=jnp.float32),
        }
        plan = BucketPlan(jax.tree.leaves(tree), n, bucket_bytes=1024)
        sharded = shard_tree(tree, n)

        def fn(sh):
            local = jax.tree.map(lambda x: x[0], sh)
            leaves, treedef = jax.tree.flatten(local)
            full = jax.tree.unflatten(
                treedef, plan.gather(leaves, site="zero.gather_q")
            )
            grads_sh = plan.scatter_mean(jax.tree.leaves(full), site="zero.scatter")
            return sum(jnp.sum(g) for g in grads_sh)

        mapped = shard_map(
            fn, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("data", None), sharded),),
            out_specs=P(), check_vma=False,
        )
        jax.block_until_ready(jax.jit(mapped)(sharded))
    finally:
        install_recorder(prev)
    return recorder


def run_smoke(workdir: str) -> dict:
    from moco_tpu.analysis.sanitizer import (
        ScheduleDivergenceError,
        ScheduleSanitizer,
    )
    from moco_tpu.utils import faults

    report: dict = {"workdir": workdir}

    # ---- leg 1: clean control ----------------------------------------
    faults.clear()
    rec0 = trace_schedule(0)
    rec1 = trace_schedule(1)
    assert rec0.entries(), "no collective sites recorded — tag hook broken"
    sites = [e[0] for e in rec0.entries()]
    assert DIVERGE_SITE in sites, f"expected {DIVERGE_SITE!r} in {sites}"
    assert rec0.schedule_hash() == rec1.schedule_hash(), (
        "clean re-trace hashed differently — recorder is not deterministic"
    )
    san0 = ScheduleSanitizer(workdir, process_index=0, num_processes=2, recorder=rec0)
    san1 = ScheduleSanitizer(workdir, process_index=1, num_processes=2, recorder=rec1)
    san1.publish(step=0)
    san0.check(step=0)  # must NOT raise
    san1.check(step=0)
    report["control"] = {
        "hash": rec0.schedule_hash()[:12],
        "sites": sites,
        "ok": True,
    }
    print(f"control: {len(sites)} sites agree, hash {rec0.schedule_hash()[:12]}")

    # ---- leg 2: injected divergence ----------------------------------
    faults.install(f"diverge@site={DIVERGE_SITE}")
    try:
        rec1_div = trace_schedule(1)
    finally:
        faults.clear()
    assert rec1_div.schedule_hash() != rec0.schedule_hash(), (
        "diverge@ fault did not change the schedule hash"
    )
    san1_div = ScheduleSanitizer(
        workdir, process_index=1, num_processes=2, recorder=rec1_div
    )
    caught = None
    try:
        san1_div.check(step=1)
    except ScheduleDivergenceError as e:
        caught = str(e)
    assert caught is not None, "sanitizer MISSED the injected divergence"
    assert DIVERGE_SITE in caught, (
        f"divergence message lacks the per-site diff naming {DIVERGE_SITE!r}:\n{caught}"
    )
    diff_path = os.path.join(workdir, "schedule_diff.json")
    assert os.path.exists(diff_path), "schedule_diff.json artifact missing"
    with open(diff_path) as f:
        diff = json.load(f)
    assert diff["divergent_peers"] == [0], diff["divergent_peers"]
    assert any(DIVERGE_SITE in line for line in diff["diff"]), diff["diff"]
    report["chaos"] = {
        "hash": rec1_div.schedule_hash()[:12],
        "caught": True,
        "diff_lines": diff["diff"],
    }
    print(f"chaos: divergence at {DIVERGE_SITE!r} caught with per-site diff:")
    for line in diff["diff"]:
        print(f"  {line}")

    # ---- leg 3: ZeRO-2/3 bucketed collective schedule ----------------
    faults.clear()
    zdir = os.path.join(workdir, "zero23")
    os.makedirs(zdir, exist_ok=True)
    z0 = trace_zero_schedule(0)
    z1 = trace_zero_schedule(1)
    zsites = [e[0] for e in z0.entries()]
    gather_sites = [s for s in zsites if s.startswith("zero.gather_q.b")]
    assert len(gather_sites) > 1, (
        f"bucketed schedule should carry >1 gather bucket site, got {zsites}"
    )
    assert ZERO_DIVERGE_SITE in zsites, f"{ZERO_DIVERGE_SITE!r} not in {zsites}"
    assert z0.schedule_hash() == z1.schedule_hash(), (
        "clean zero23 re-trace hashed differently"
    )
    szan0 = ScheduleSanitizer(zdir, process_index=0, num_processes=2, recorder=z0)
    szan1 = ScheduleSanitizer(zdir, process_index=1, num_processes=2, recorder=z1)
    szan1.publish(step=0)
    szan0.check(step=0)  # must NOT raise on the bucketed schedule
    szan1.check(step=0)
    faults.install(f"diverge@site={ZERO_DIVERGE_SITE}")
    try:
        z1_div = trace_zero_schedule(1)
    finally:
        faults.clear()
    szan1_div = ScheduleSanitizer(
        zdir, process_index=1, num_processes=2, recorder=z1_div
    )
    caught = None
    try:
        szan1_div.check(step=1)
    except ScheduleDivergenceError as e:
        caught = str(e)
    assert caught is not None, "sanitizer MISSED the bucketed-gather divergence"
    assert ZERO_DIVERGE_SITE in caught, (
        f"divergence message lacks the bucket site {ZERO_DIVERGE_SITE!r}:\n{caught}"
    )
    report["zero23"] = {"sites": zsites, "caught": True}
    print(
        f"zero23: bucketed schedule agrees ({len(zsites)} sites); "
        f"diverge at {ZERO_DIVERGE_SITE!r} caught"
    )
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--workdir", default=None,
        help="artifact directory (default: a fresh temp dir)",
    )
    args = ap.parse_args()
    workdir = args.workdir or tempfile.mkdtemp(prefix="sanitizer_smoke_")
    os.makedirs(workdir, exist_ok=True)
    report = run_smoke(workdir)
    with open(os.path.join(workdir, "sanitizer_smoke.json"), "w") as f:
        json.dump(report, f, indent=2)
    print(f"sanitizer smoke OK — artifacts in {workdir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
