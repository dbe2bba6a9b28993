#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # on a TPU machine, from the repo root

Drives the main path once through the entry points a user calls, at the
full width of the one model every record is about — ResNet-50, MoCo v2
head, dim 128, K = 65536, 224 px, global batch 256, bf16 — with random
weights and synthetic data made from seeds:

  train  `train.py --preset imagenet_v2 --data synthetic`, 8 steps, the
         device prefetch ring on, fused InfoNCE on auto; checked from the
         run's own metrics.jsonl and checkpoint, plus where the batch and
         the state actually sat on the devices.
  serve  `python -m moco_tpu.serve.replica_main` on that checkpoint:
         /healthz, /embed, /neighbors, /stats, SIGTERM drain.
  cache  the train command again in a new process: the compile cache
         must already hold the train step.

There is no CPU mode: with no TPU it fails and says which platform jax
resolved. One process per chip host — this parent never imports jax and
runs each phase as a child that is gone before the next starts (serving
is one replica process on one device, however many chips the host has).

The last line of stdout is `{"ok": true, "device": {...}}`; any failed
phase exits non-zero at once and prints no result.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# the imagenet_v2 preset at full width (utils/config.py PRESETS)
BATCH, QUEUE, DIM, IMAGE = 256, 65536, 128, 224
# `--data synthetic` holds 1024 images = 4 full batches an epoch, and the
# trainer refuses a --steps-per-epoch the dataset cannot fill: 2 x 4
EPOCHS, STEPS_PER_EPOCH = 2, 4
STEPS = EPOCHS * STEPS_PER_EPOCH
TRAIN_ARGS = [
    "--preset", "imagenet_v2", "--data", "synthetic",
    "--epochs", str(EPOCHS), "--steps-per-epoch", str(STEPS_PER_EPOCH),
    "--print-freq", "1",
]
BUCKETS = "1,8,32,128"
EMBED_SIZES = (1, 5, 32, 100)
EXPECT_PLATFORM = "tpu"
# the jitted train step's entries in the compile cache: jax names them
# after the function it compiled, core/moco.py make_train_step's `step_fn`
STEP_CACHE_PREFIX = "jit_step_fn-"

TRAIN_TIMEOUT_S, BOOT_TIMEOUT_S, DRAIN_TIMEOUT_S = 600, 420, 120


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ---------------------------------------------------------------- children


def child_train(workdir: str, verify_checkpoint: bool) -> int:
    """The train phase's process: `train.py`'s own main() on the CLI
    flags, watched by a spy that records where the first batch and the
    state sit, then the per-device memory peaks and (first run only) the
    checkpoint read back through the eval-side restore path."""
    sys.path.insert(0, ROOT)
    from moco_tpu.utils.platform import log_devices, pin_platform_from_env

    pin_platform_from_env()
    import jax

    dev = log_devices("chip_smoke")
    if dev["platform"] != EXPECT_PLATFORM:
        print(
            f"chip_smoke: no TPU — jax resolved platform {dev['platform']!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). This script has "
            "no CPU mode.",
            file=sys.stderr, flush=True,
        )
        return 2
    os.makedirs(workdir, exist_ok=True)
    with open(os.path.join(workdir, "device.json"), "w") as f:
        json.dump(dev, f)

    import moco_tpu.train as driver

    seen: dict = {}
    real_make_train_step = driver.make_train_step

    def spying_make_train_step(config, encoder, tx, mesh, **kw):
        step = real_make_train_step(config, encoder, tx, mesh, **kw)
        seen["mesh"] = {k: int(v) for k, v in mesh.shape.items()}

        def spied_step(state, batch, rng):
            if "im_q_rows_per_device" not in seen:
                im_q = batch["im_q"]
                seen["im_q_shape"] = list(im_q.shape)
                seen["im_q_rows_per_device"] = sorted(
                    s.data.shape[0] for s in im_q.addressable_shards
                )
                leaves = jax.tree.leaves(state)
                seen["state_leaves"] = len(leaves)
                seen["state_leaf_device_counts"] = sorted(
                    {len(x.sharding.device_set) for x in leaves}
                )
                seen["state_fully_addressable"] = all(
                    x.is_fully_addressable for x in leaves
                )
            return step(state, batch, rng)

        return spied_step

    driver.make_train_step = spying_make_train_step

    import train as cli

    sys.argv = ["train.py", *TRAIN_ARGS, "--workdir", workdir]
    cli.main()

    seen["peak_bytes_in_use"] = [
        int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.local_devices()
    ]
    if verify_checkpoint:
        from moco_tpu.lincls import restore_pretrain_state
        from moco_tpu.utils.checkpoint import CheckpointManager

        mgr = CheckpointManager(workdir)
        seen["checkpoint_steps"] = mgr.all_steps()
        mgr.close()
        state, _ = restore_pretrain_state(workdir)
        seen["restored_step"] = int(state.step)
        seen["restored_queue_ptr"] = int(state.queue_ptr)
        seen["restored_queue_shape"] = list(state.queue.shape)
    with open(os.path.join(workdir, "placement.json"), "w") as f:
        json.dump(seen, f)
    return 0


# ------------------------------------------------------------------ parent


def run_train_child(workdir: str, verify_checkpoint: bool) -> dict:
    """Run one train child to the end; return what it left behind."""
    t_spawn = time.time()
    argv = [sys.executable, os.path.abspath(__file__), "--child", "train", workdir]
    if verify_checkpoint:
        argv.append("--verify-checkpoint")
    proc = subprocess.Popen(argv, cwd=ROOT, env=child_env())
    try:
        rc = proc.wait(timeout=TRAIN_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"train child still running after {TRAIN_TIMEOUT_S}s")
    finally:
        stop(proc)
    check(rc == 0, f"train child exited {rc}")
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    with open(os.path.join(workdir, "device.json")) as f:
        device = json.load(f)
    with open(os.path.join(workdir, "placement.json")) as f:
        placement = json.load(f)
    steps = [r for r in lines if "loss" in r]
    check(bool(steps), "no training lines in metrics.jsonl")
    return {
        "device": device,
        "placement": placement,
        "steps": steps,
        "ttfs_s": steps[0]["time"] - t_spawn,
        "wall_s": time.time() - t_spawn,
    }


def step_cache_entries() -> list[str]:
    return sorted(e for e in cache_entries() if e.startswith(STEP_CACHE_PREFIX))


def phase_train(workdir: str) -> dict:
    # an earlier run in this checkout leaves the step in the compile cache:
    # the first run's time-to-first-step is then a warm one, and is named so
    step_cached_before = bool(step_cache_entries())
    run = run_train_child(workdir, verify_checkpoint=True)
    run["cold"] = not step_cached_before
    dev, place, steps = run["device"], run["placement"], run["steps"]
    n = dev["count"]  # (the child already refused any platform but EXPECT_PLATFORM)
    check(
        [r["step"] for r in steps] == list(range(1, STEPS + 1)),
        f"expected steps 1..{STEPS}, metrics.jsonl has {[r['step'] for r in steps]}",
    )
    losses = [r["loss"] for r in steps]
    check(
        all(isinstance(x, float) and math.isfinite(x) for x in losses),
        f"non-finite loss in {losses}",
    )
    peaks = [r.get("hbm_peak_bytes") for r in steps]
    check(
        all(isinstance(p, int) and p > 0 for p in peaks),
        f"hbm_peak_bytes is not a number on every line: {peaks}",
    )
    check(STEPS in place["checkpoint_steps"], f"no step-{STEPS} checkpoint: {place['checkpoint_steps']}")
    check(place["restored_step"] == STEPS, f"checkpoint restores to step {place['restored_step']}")
    check(
        place["restored_queue_ptr"] == (STEPS * BATCH) % QUEUE,
        f"queue_ptr {place['restored_queue_ptr']} != {STEPS}*{BATCH} mod {QUEUE}",
    )
    check(place["restored_queue_shape"] == [QUEUE, DIM], f"queue {place['restored_queue_shape']}")
    # where things sat: the mesh, the batch, the state, the memory
    check(place["mesh"] == {"data": n, "model": 1}, f"mesh {place['mesh']} on {n} devices")
    check(place["im_q_shape"] == [BATCH, IMAGE, IMAGE, 3], f"im_q {place['im_q_shape']}")
    check(
        place["im_q_rows_per_device"] == [BATCH // n] * n,
        f"im_q rows per device {place['im_q_rows_per_device']}, want {BATCH // n} x {n}",
    )
    check(
        place["state_leaf_device_counts"] == [n] and place["state_fully_addressable"],
        f"state leaves span {place['state_leaf_device_counts']} devices, want all {n}",
    )
    dev_peaks = place["peak_bytes_in_use"]
    check(len(dev_peaks) == n and min(dev_peaks) > 0, f"per-device peaks {dev_peaks}")
    check(
        max(dev_peaks) <= 1.5 * min(dev_peaks),
        f"per-device peak_bytes_in_use not within 1.5x of one another: {dev_peaks}",
    )
    print(
        f"chip_smoke train: OK — {STEPS} steps on mesh {place['mesh']}, im_q "
        f"{BATCH // n} rows/device, {place['state_leaves']} state leaves on all {n} "
        f"device(s), loss {losses[0]:.4f} -> {losses[-1]:.4f}, queue_ptr "
        f"{place['restored_queue_ptr']}, per-device peak bytes {dev_peaks}, "
        f"time-to-first-step {run['ttfs_s']:.1f}s ({first_run_label(run)}), "
        f"phase wall {run['wall_s']:.1f}s",
        flush=True,
    )
    return run


def first_run_label(run: dict) -> str:
    return "cold" if run["cold"] else "warm: the compile cache already held the train step"


def http(method: str, url: str, body: bytes | None = None, headers: dict | None = None,
         timeout: float = 120.0) -> tuple[int, dict]:
    req = urllib.request.Request(url, data=body, method=method, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode("utf-8", "replace")[:500]}


def phase_serve(ckpt_dir: str, workdir: str, n_devices: int) -> dict:
    import numpy as np

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    t0 = time.time()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "moco_tpu.serve.replica_main",
            "--ckpt-dir", ckpt_dir, "--port", str(port),
            "--buckets", BUCKETS, "--workdir", workdir,
        ],
        cwd=ROOT, env=child_env(),
    )
    try:
        print(
            f"chip_smoke serve: one replica process on one device "
            f"(of {n_devices}); waiting on {base}/healthz",
            flush=True,
        )
        while True:  # the server binds only after AOT warm-up
            check(proc.poll() is None, f"replica exited {proc.returncode} during boot")
            check(time.time() - t0 < BOOT_TIMEOUT_S, f"replica not healthy after {BOOT_TIMEOUT_S}s")
            try:
                status, health = http("GET", base + "/healthz", timeout=2.0)
                if status == 200 and health.get("ok"):
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        boot_s = time.time() - t0
        # the host's JAX_PLATFORMS may list the CPU after the TPU: a replica
        # that could not take the chip would still boot and answer, slowly
        check(
            health.get("platform") == EXPECT_PLATFORM,
            f"replica came up on platform {health.get('platform')!r}, not {EXPECT_PLATFORM!r}",
        )

        rng = np.random.default_rng(0)
        images = rng.integers(0, 256, (max(EMBED_SIZES), IMAGE, IMAGE, 3), dtype=np.uint8)

        def post(route: str, n: int) -> dict:
            status, body = http(
                "POST", base + route, images[:n].tobytes(),
                {"X-Image-Shape": f"{n},{IMAGE},{IMAGE},3"},
            )
            check(status == 200, f"{route} n={n}: HTTP {status} {body}")
            emb = np.asarray(body["embedding"], np.float32)
            check(emb.shape == (n, DIM), f"{route} n={n}: embedding shape {emb.shape}")
            check(bool(np.isfinite(emb).all()), f"{route} n={n}: non-finite embedding")
            norms = np.linalg.norm(emb, axis=1)
            check(
                bool(np.allclose(norms, 1.0, atol=1e-3)),
                f"{route} n={n}: row norms in [{norms.min():.5f}, {norms.max():.5f}]",
            )
            body["embedding"] = emb
            return body

        embeds = {n: post("/embed", n)["embedding"] for n in EMBED_SIZES}
        # the same image through two different compiled buckets (1 and 8)
        cos = float(embeds[1][0] @ embeds[5][0])
        check(cos > 0.99, f"image 0 embeds differently in buckets 1 and 8: cosine {cos:.4f}")
        for n in (1, 5):
            body = post("/neighbors?k=5", n)
            idx = np.asarray(body["indices"])
            scores = np.asarray(body["scores"], np.float32)
            check(idx.shape == (n, 5) and scores.shape == (n, 5), f"/neighbors n={n}: {idx.shape}")
            check(
                bool(((idx >= 0) & (idx < QUEUE)).all()),
                f"/neighbors n={n}: ids outside the {QUEUE}-row dictionary: {idx.tolist()}",
            )
            check(
                bool(np.isfinite(scores).all() and (np.abs(scores) <= 1.0 + 1e-3).all()
                     and (np.diff(scores, axis=1) <= 1e-6).all()),
                f"/neighbors n={n}: scores not sorted cosines: {scores.tolist()}",
            )
        status, stats = http("GET", base + "/stats")
        check(status == 200, f"/stats: HTTP {status}")
        check(
            stats.get("serve/recompiles_after_warmup") == 0,
            f"serve/recompiles_after_warmup = {stats.get('serve/recompiles_after_warmup')}",
        )
        check(stats.get("serve/index_rows") == QUEUE, f"index rows {stats.get('serve/index_rows')}")
        check(stats.get("serve/model_step") == STEPS, f"model step {stats.get('serve/model_step')}")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=DRAIN_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"replica did not drain within {DRAIN_TIMEOUT_S}s of SIGTERM")
        check(rc == 0, f"replica exited {rc} after SIGTERM")
    finally:
        stop(proc)
    print(
        f"chip_smoke serve: OK — replica on platform {health['platform']!r}, "
        f"boot+warm-up {boot_s:.1f}s, /embed sizes "
        f"{list(EMBED_SIZES)} and 2 /neighbors calls answered, 0 recompiles after "
        f"warm-up, clean drain on SIGTERM",
        flush=True,
    )
    return {"boot_s": boot_s}


def cache_dir() -> str:
    """Where moco_tpu.utils.platform puts the compile cache (kept in
    step by tests/test_platform.py)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")


def cache_entries() -> dict:
    d = cache_dir()
    if not os.path.isdir(d):
        return {}
    return {
        name: os.path.getsize(os.path.join(d, name))
        for name in os.listdir(d)
        if name.endswith("-cache")
    }


def phase_cache(workdir: str, first: dict) -> dict:
    """The same train command in a new process and a fresh workdir (the
    LR schedule's total step count is a compile-time constant of the
    step, so a shorter run would be a different program): the compile
    cache must already hold the train step."""
    before = cache_entries()
    step_entries = step_cache_entries()
    check(
        bool(step_entries),
        f"the first train run left no {STEP_CACHE_PREFIX}* entry in {cache_dir()}: "
        f"{sorted(before)}",
    )
    warm = run_train_child(workdir, verify_checkpoint=False)
    after = cache_entries()
    new = sorted(set(after) - set(before))
    new_steps = [e for e in new if e.startswith(STEP_CACHE_PREFIX)]
    check(not new_steps, f"the train step was compiled again: new cache entries {new_steps}")
    # (programs that compile in about a second sit on jax's 1 s caching
    # threshold and may enter the cache on either run: listed, not failed)
    print(
        f"chip_smoke cache: OK — {len(before)} entries in {cache_dir()} before the second "
        f"train run, {len(after)} after it; none new for the train step ("
        + ", ".join(f"{e[:24]}… {before[e] / 1e6:.1f} MB" for e in step_entries)
        + f"); other new entries: {[e.rsplit('-', 2)[0] for e in new]}; "
        f"time-to-first-step {first['ttfs_s']:.1f}s first run ({first_run_label(first)}), "
        f"{warm['ttfs_s']:.1f}s second run (warm)",
        flush=True,
    )
    return {"ttfs_warm_s": warm["ttfs_s"], "entries_before": len(before), "entries_after": len(after)}


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    return env


def stop(proc: subprocess.Popen) -> None:
    """Nothing this script starts outlives it."""
    if proc.poll() is None:
        proc.kill()
        proc.wait()


def main() -> int:
    missing = [p for p in ("train.py", "moco_tpu") if not os.path.exists(os.path.join(ROOT, p))]
    if missing:
        print(f"chip_smoke: {missing} not found beside {__file__} — run it from "
              "a checkout of the repo", file=sys.stderr)
        return 2
    shutil.rmtree(OUT, ignore_errors=True)  # a leftover checkpoint would be resumed
    os.makedirs(OUT)
    train_dir, serve_dir, retrain_dir = (os.path.join(OUT, d) for d in ("train", "serve", "retrain"))
    t0 = time.time()
    phase = "train"
    try:
        first = phase_train(train_dir)
        phase = "serve"
        serve = phase_serve(train_dir, serve_dir, first["device"]["count"])
        phase = "cache"
        cache = phase_cache(retrain_dir, first)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED in phase {phase}: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        # checkpoints are hundreds of MB; keep the logs and the JSON
        for d in (train_dir, retrain_dir):
            if os.path.isdir(d):
                for name in os.listdir(d):
                    if name.isdigit():
                        shutil.rmtree(os.path.join(d, name), ignore_errors=True)
    dev = first["device"]
    summary = {
        "phases": {"train": "ok", "serve": "ok", "cache": "ok"},
        "steps": STEPS,
        "mesh": first["placement"]["mesh"],
        # null: the compile cache held the train step before the first run
        "ttfs_cold_s": round(first["ttfs_s"], 1) if first["cold"] else None,
        "ttfs_warm_s": round(cache["ttfs_warm_s"], 1),
        "serve_boot_s": round(serve["boot_s"], 1),
        "wall_s": round(time.time() - t0, 1),
    }
    print(f"chip_smoke summary: {json.dumps(summary)}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev["platform"], "kind": dev["device_kind"], "count": dev["count"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1:3] == ["--child", "train"]:
        sys.exit(child_train(sys.argv[3], "--verify-checkpoint" in sys.argv[4:]))
    sys.exit(main())
