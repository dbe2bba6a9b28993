"""Throughput benchmark — prints ONE JSON line.

Measures the full MoCo v2 ResNet-50 pretraining step (two encoder
forwards, one backward, EMA, Shuffle-BN handling, InfoNCE vs the 65536-key
queue, optimizer) on the available accelerator, in imgs/sec/chip. Two
rates are reported:

- ``value`` (the headline): device-only steady-state rate, pre-staged
  batches — isolates the compiled step, comparable across rounds.
- ``with_data_imgs_per_sec_per_chip``: sustained rate with the real input
  pipeline in the loop (JPEG ImageFolder decode via the native C++ pool +
  on-device two-crop augmentation), per VERDICT round-1 item 4. NB: this
  host exposes a single CPU core (the reference assumed 32 DataLoader
  workers/GPU), so this number is host-decode-bound here; the split
  between the two rates is exactly the signal it exists to expose.

Also reported: ``mfu`` (model FLOP utilization; FLOPs from XLA cost
analysis when available, else an analytic R50 estimate) against the
chip's peak bf16 TFLOPS.

Baseline: the reference trains 200 epochs of ImageNet (1.281M imgs) in
~53h on 8×V100 ⇒ ≈168 imgs/s/GPU (SURVEY.md §6, BASELINE.md).
`vs_baseline` is the ratio of our per-chip rate to that 168 imgs/s/GPU
(null on the JAX_PLATFORMS=cpu smoke, where the ratio would be meaningless);
the north star is ≥2.0.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REFERENCE_IMGS_PER_SEC_PER_GPU = 168.0

# Peak bf16 matmul TFLOPS per chip, for the MFU denominator.
PEAK_TFLOPS = {
    "tpu v5 lite": 197.0,  # v5e
    "tpu v5": 459.0,  # v5p
    "tpu v4": 275.0,
    "tpu v6 lite": 918.0,  # v6e
}


def _peak_tflops(device) -> float | None:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_TFLOPS.items():
        if key in kind:
            return val
    return None


def _step_flops(jitted_step, state, batch_dict, rng) -> float | None:
    """Per-step FLOPs from XLA cost analysis; None if unsupported."""
    try:
        cost = jitted_step.lower(state, batch_dict, rng).compile().cost_analysis()
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def _analytic_step_flops(batch: int, img: int) -> float:
    """Fallback estimate for the GLOBAL batch: R50 fwd ≈ 4.1 GFLOPs @224²
    (scales ~quadratically with side); step = q fwd+bwd (3×) + k fwd (1×)
    ⇒ ~16.4 GFLOPs/img. Divided by n_dev at use to get per-device FLOPs
    (XLA's cost_analysis already reports the per-device SPMD module)."""
    r50_fwd = 4.1e9 * (img / 224.0) ** 2
    return 4.0 * r50_fwd * batch


def _ensure_jpeg_folder(root: str, n: int, size: int, classes: int = 8) -> str:
    """Synthetic JPEG ImageFolder for the with-data bench (no datasets on
    disk in this environment). Deterministic, built once, reused."""
    from PIL import Image

    stamp = os.path.join(root, f".complete_{n}_{size}")
    if os.path.exists(stamp):
        return root
    rng = np.random.default_rng(0)
    for c in range(classes):
        os.makedirs(os.path.join(root, f"class_{c}"), exist_ok=True)
    for i in range(n):
        c = i % classes
        # low-frequency field + noise ≈ natural-image JPEG work profile
        coarse = rng.uniform(0, 255, (8, 8, 3))
        img = np.asarray(
            Image.fromarray(coarse.astype(np.uint8)).resize((size, size), Image.BILINEAR),
            np.float32,
        )
        img += rng.normal(0, 12, img.shape)
        Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
            os.path.join(root, f"class_{c}", f"img_{i:05d}.jpg"), quality=90
        )
    open(stamp, "w").close()
    return root


def main() -> None:
    from moco_tpu.utils.platform import (
        cpu_pinned,
        enable_persistent_compilation_cache,
        log_devices,
        pin_platform_from_env,
    )

    # Per-leg skip ledger (BENCH r02–r05 lesson: the bench silently
    # degraded to the CPU smoke for four rounds and nobody could say
    # why from the JSON alone). Every leg records ran/skip_reason; the
    # ledger ships inside the one-line JSON as `legs`.
    legs: dict[str, dict] = {
        name: {"ran": False, "skip_reason": None}
        for name in (
            "accelerator",
            "numerics_crosscheck",
            "obs_overhead",
            "with_data",
            "zero_ab",
            "serving",
            "ann_ab",
        )
    }

    def _skip(leg: str, reason: str) -> None:
        legs[leg]["skip_reason"] = reason
        print(f"leg {leg} skipped: {reason}", file=sys.stderr)

    pin_platform_from_env()  # honor an explicit JAX_PLATFORMS request
    enable_persistent_compilation_cache()
    platform = log_devices("bench", file=sys.stderr)["platform"]  # stdout is the JSON line
    on_tpu = platform == "tpu"
    if on_tpu:
        legs["accelerator"]["ran"] = True
    elif cpu_pinned():
        # CI's smoke (ci.yml): the toy legs below, under CPU metric names
        _skip("accelerator", "JAX_PLATFORMS=cpu pinned by the environment")
    else:
        # no fallback: an unpinned bench is a chip measurement, and a
        # CPU number must never be printed in its place
        raise SystemExit(
            f"bench.py: no TPU — jax resolved platform {platform!r}. Nothing "
            "measured. (JAX_PLATFORMS=cpu runs the CPU smoke explicitly.)"
        )

    from moco_tpu.core import (
        build_encoder,
        build_predictor,
        create_state,
        make_train_step,
        place_state,
    )
    from moco_tpu.parallel import create_mesh, shard_batch
    from moco_tpu.utils.config import DataConfig, MocoConfig, OptimConfig, TrainConfig
    from moco_tpu.utils.schedules import build_optimizer

    if on_tpu:
        arch, img, batch, k, steps, dtype = "resnet50", 224, 256, 65536, 20, "bfloat16"
    else:  # the explicit JAX_PLATFORMS=cpu smoke
        arch, img, batch, k, steps, dtype = "resnet18", 32, 64, 4096, 3, "float32"
    # BENCH_ARCH=vit_b16 benches the v3 ViT step instead (queue-free
    # symmetric loss, AdamW; BENCH_FLASH=1 adds the Pallas flash kernel)
    arch = os.environ.get("BENCH_ARCH", arch)
    is_vit = arch.startswith("vit")
    batch = int(os.environ.get("BENCH_BATCH", batch))
    steps = int(os.environ.get("BENCH_STEPS", steps))

    n_dev = len(jax.devices())
    mesh = create_mesh(num_data=n_dev, num_model=1)
    if is_vit:
        moco = MocoConfig(
            arch=arch,
            dim=256,
            num_negatives=0,
            momentum=0.99,
            momentum_cos=True,
            temperature=0.2,
            v3=True,
            shuffle="none",
            compute_dtype=dtype,
            vit_flash_attention=os.environ.get("BENCH_FLASH", "0") == "1",
        )
        optim = OptimConfig(optimizer="adamw", lr=2.4e-3, weight_decay=0.1,
                            epochs=300, cos=True, warmup_epochs=40)
    else:
        moco = MocoConfig(
            arch=arch,
            dim=128,
            num_negatives=k,
            temperature=0.2,
            mlp=True,
            # virtual groups need the in-batch key permutation, so the
            # single-device bench switches to gather_perm when the
            # BENCH_BN_VIRTUAL_GROUPS A/B leg is active; the EMAN leg
            # (BENCH_KEY_BN_EVAL=1) instead REQUIRES shuffle='none'
            # (running-stats keys have nothing to decorrelate)
            shuffle="none"
            if os.environ.get("BENCH_KEY_BN_EVAL") == "1"
            else "gather_perm"
            if n_dev > 1 or int(os.environ.get("BENCH_BN_VIRTUAL_GROUPS", 0)) > 1
            else "none",
            # BENCH_KEY_BN_EVAL=1 A/Bs the EMAN-style key forward
            # (eval-mode BN from EMA'd running stats — drops the key-side
            # statistics pass, one third of the BN-bytes cost center)
            key_bn_running_stats=os.environ.get("BENCH_KEY_BN_EVAL") == "1",
            cifar_stem=not on_tpu,
            compute_dtype=dtype,
            # BENCH_BN_STATS_ROWS=32 A/Bs the subset-statistics BN (the
            # PROFILE.md byte-reduction lever); BENCH_BN_VIRTUAL_GROUPS=8
            # the virtual Shuffle-BN mode — both without code changes
            bn_stats_rows=int(os.environ.get("BENCH_BN_STATS_ROWS", 0)),
            # BENCH_BN_STATS_BARRIER=1 adds the fusion barrier around the
            # subset slice (the bn_compile_repro candidate workaround)
            bn_stats_barrier=os.environ.get("BENCH_BN_STATS_BARRIER") == "1",
            bn_virtual_groups=int(os.environ.get("BENCH_BN_VIRTUAL_GROUPS", 0)),
            # BENCH_FUSED=0/1 pins the streaming Pallas InfoNCE off/on
            # (unset = the config's auto default) for the fused-vs-dense A/B
            fused_infonce=(
                None
                if os.environ.get("BENCH_FUSED") is None
                else os.environ["BENCH_FUSED"] == "1"
            ),
        )
        optim = OptimConfig(lr=0.03, epochs=200, cos=True)
    config = TrainConfig(
        moco=moco,
        optim=optim,
        data=DataConfig(dataset="synthetic", image_size=img, global_batch=batch),
    )
    encoder = build_encoder(config.moco, num_data=n_dev)
    predictor = build_predictor(config.moco, num_data=n_dev)
    tx = build_optimizer(config.optim, steps_per_epoch=5004)
    rng = jax.random.PRNGKey(0)
    state = create_state(
        rng, config, encoder, tx, jnp.zeros((1, img, img, 3), jnp.float32),
        predictor=predictor,
    )
    state = place_state(state, mesh)
    # donate=False: not re-measured on the v5e (PROFILE.md's one
    # earlier reading was -2 % with donation); flipping it is a perf PR.
    step = make_train_step(
        config, encoder, tx, mesh, donate=False, predictor=predictor,
        total_steps=5004 * config.optim.epochs,
    )

    ims = jax.random.normal(jax.random.PRNGKey(1), (2, batch, img, img, 3), jnp.float32)
    batch_dict = shard_batch(mesh, {"im_q": ims[0], "im_k": ims[1]})
    root_rng = jax.device_put(
        jax.random.PRNGKey(2), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )

    # ---- fused-vs-dense numerics cross-check (BENCH_NUMERICS=1) -------
    # One compiled step per path from the IDENTICAL initial state and
    # batch. The streaming Pallas InfoNCE is default-ON for TPU
    # (core/moco.py fused auto-resolution); a Mosaic lowering bug there
    # would corrupt training silently while benching fast — this prints
    # on-chip correctness evidence without needing the pytest session.
    # Opt-in (two extra full-step compiles, ~2×3.5 min on the chip).
    crosscheck_ok = True
    if os.environ.get("BENCH_NUMERICS") != "1":
        _skip("numerics_crosscheck", "opt-in leg (set BENCH_NUMERICS=1; two extra full-step compiles)")
    elif is_vit or moco.num_negatives == 0:
        _skip("numerics_crosscheck", "fused-vs-dense InfoNCE A/B needs the queue-based (non-ViT) step")
    if (
        os.environ.get("BENCH_NUMERICS") == "1"
        and not is_vit
        and moco.num_negatives > 0
    ):
        legs["numerics_crosscheck"]["ran"] = True
        import dataclasses

        outs = {}
        for name, fused in (("fused", True), ("dense", False)):
            cfg_n = dataclasses.replace(
                config, moco=dataclasses.replace(moco, fused_infonce=fused)
            )
            step_n = make_train_step(
                cfg_n, encoder, tx, mesh, donate=False,
                total_steps=5004 * config.optim.epochs,
            )
            _, m = step_n(state, batch_dict, root_rng)
            outs[name] = (float(m["loss"]), float(m["acc1"]))
        d_loss = abs(outs["fused"][0] - outs["dense"][0])
        d_acc = abs(outs["fused"][1] - outs["dense"][1])
        # Both paths share the (bf16) encoder forwards bit-for-bit; they
        # differ only in the logits/log-sum-exp arithmetic (f32 in both),
        # so tolerance is tight relative to the ~ln(1+K)≈11 loss scale.
        crosscheck_ok = d_loss <= 5e-2 and d_acc <= 1.0
        print(
            "numerics crosscheck: "
            f"fused loss={outs['fused'][0]:.6f} acc1={outs['fused'][1]:.3f} "
            f"dense loss={outs['dense'][0]:.6f} acc1={outs['dense'][1]:.3f} "
            f"dloss={d_loss:.2e} dacc1={d_acc:.3f} "
            f"{'PASS' if crosscheck_ok else 'FAIL'}",
            file=sys.stderr,
        )
        # a FAIL must still let the bench finish (a chip window is
        # precious; the headline JSON and the FAIL line are both
        # evidence) — the nonzero exit happens after the JSON prints

    # Warmup (compile) + steady state, synced by a host transfer of the
    # last loss (whether block_until_ready alone is enough on this
    # runtime is not re-measured — ROADMAP S0).
    for _ in range(3):
        state, metrics = step(state, batch_dict, root_rng)
    float(metrics["loss"])

    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    trace = None
    if trace_dir:
        # a requested trace that cannot start fails the run
        trace = jax.profiler.trace(trace_dir)
        trace.__enter__()

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_dict, root_rng)
    float(metrics["loss"])  # chained state deps force all `steps` steps
    dt = time.perf_counter() - t0
    if trace is not None:
        trace.__exit__(None, None, None)

    imgs_per_sec = batch * steps / dt
    per_chip = imgs_per_sec / n_dev

    # ---- obs overhead (the cost of the telemetry layer itself) --------
    # Same step count twice: FULL obs (in-step health gauges + installed
    # span tracer + a JSONL sink write at logging cadence) vs BARE
    # (--no-health-metrics equivalent, sinks disabled, no tracer). The
    # headline `value` above stays the untouched steady-state loop,
    # comparable with prior BENCH_r*.json rounds; this field tracks what
    # observability costs so a regression in the telemetry layer is a
    # visible number, not a silent throughput tax.
    obs_overhead_pct = None
    if os.environ.get("BENCH_SKIP_OBS_OVERHEAD"):
        _skip("obs_overhead", "BENCH_SKIP_OBS_OVERHEAD set")
    else:
        try:
            import dataclasses as _dc
            import tempfile as _tf

            from moco_tpu import obs as _obs
            from moco_tpu.obs.sinks import JsonlSink

            def _timed_leg(step_fn, sink=None, tracer=None):
                st = state
                prev = _obs.set_tracer(tracer)
                try:
                    for _ in range(2):  # warm this variant's compile
                        st, m = step_fn(st, batch_dict, root_rng)
                    float(m["loss"])
                    t0 = time.perf_counter()
                    for i in range(steps):
                        with _obs.span("step", step=i):
                            st, m = step_fn(st, batch_dict, root_rng)
                        if sink is not None and i % 10 == 0:
                            sink.write(i, m)
                    float(m["loss"])
                    return time.perf_counter() - t0
                finally:
                    _obs.set_tracer(prev)

            sink = JsonlSink(_tf.mkdtemp(prefix="bench_obs_"))
            dt_full = _timed_leg(step, sink=sink, tracer=_obs.Tracer())
            sink.close()
            step_bare = make_train_step(
                _dc.replace(config, health_metrics=False),
                encoder, tx, mesh, donate=False, predictor=predictor,
                total_steps=5004 * config.optim.epochs,
            )
            dt_bare = _timed_leg(step_bare)
            if dt_bare > 0:
                obs_overhead_pct = round((dt_full - dt_bare) / dt_bare * 100.0, 2)
            legs["obs_overhead"]["ran"] = True
            print(
                f"obs overhead: full={dt_full:.2f}s bare={dt_bare:.2f}s "
                f"-> {obs_overhead_pct}%",
                file=sys.stderr,
            )
        except Exception as e:
            _skip("obs_overhead", f"leg crashed: {e!r:.200}")

    # ---- ZeRO weight-update sharding A/B (zero1 vs zero23) ------------
    # Same model/batch, two extra compiled steps: stage 1 (sharded opt
    # state, params re-gathered in-step) vs stage 2/3 (persistently
    # sharded params, bucketed collectives). Recorded per leg: rate,
    # device hbm peak where the backend reports it (NB: peak is a
    # process-lifetime high-water mark, tainted by the main loop above —
    # the analytic at-rest state bytes are the clean A/B signal), and
    # the analytic comms bytes/step from the per-bucket ledger.
    zero_ab = None
    zero_legs = (("zero1", 1, False), ("zero23", 3, False), ("zero_layer", 3, True))
    if os.environ.get("BENCH_SKIP_ZERO"):
        _skip("zero_ab", "BENCH_SKIP_ZERO set")
    elif n_dev < 2:
        reason = (
            f"single-device mesh ({n_dev} chip): ZeRO shards over the data axis "
            "(scripts/fleet_smoke.py covers the fake-8-device A/B)"
        )
        _skip("zero_ab", reason)
        # Sub-leg-granular skip record: CPU-smoke rounds previously wrote
        # a bare null here, so the perf trajectory could not say WHICH
        # zero legs a round was missing once the leg set grew.
        zero_ab = {
            name: {"ran": False, "skip_reason": reason} for name, _, _ in zero_legs
        }
    else:
        try:
            import dataclasses as _dcz

            from moco_tpu.obs import comms as _comms
            from moco_tpu.obs.stepstats import device_memory_stats, tree_shard_bytes

            zero_ab = {}
            zsteps = max(steps // 2, 2)
            for name, stage, layer in zero_legs:
                cfg_z = _dcz.replace(
                    config,
                    parallel=_dcz.replace(
                        config.parallel,
                        shard_weight_update=True,
                        zero_stage=stage,
                        zero_layer_granular=layer,
                    ),
                )
                state_z = create_state(  # mocolint: disable=JX003  (A/B legs share the main run's init seed on purpose: identical weights across zero1/zero23)
                    rng, cfg_z, encoder, tx,
                    jnp.zeros((1, img, img, 3), jnp.float32),
                    predictor=predictor, zero_num_data=n_dev,
                )
                step_z = make_train_step(
                    cfg_z, encoder, tx, mesh, donate=False, predictor=predictor,
                    total_steps=5004 * config.optim.epochs, state_template=state_z,
                )
                state_z = place_state(
                    state_z, mesh, zero=True, zero_params=stage >= 2
                )
                _comms.reset()  # per-leg ledger; tags re-fire on the fresh trace
                st = state_z
                for _ in range(2):
                    st, m = step_z(st, batch_dict, root_rng)
                float(m["loss"])
                t0z = time.perf_counter()
                for _ in range(zsteps):
                    st, m = step_z(st, batch_dict, root_rng)
                float(m["loss"])
                dtz = time.perf_counter() - t0z
                mem = device_memory_stats() or {}
                ledger = _comms.payload()
                zero_ab[name] = {
                    "ran": True,
                    "imgs_per_sec_per_chip": round(batch * zsteps / dtz / n_dev, 2),
                    "hbm_peak_bytes": mem.get("hbm_peak_bytes"),
                    "hbm_state_bytes_per_chip": tree_shard_bytes(st),
                    # analytic shards + live-gather transient: the PEAK
                    # model bytes (not just at-rest) — the number the
                    # layer-granular stage actually moves, trackable on
                    # CPU-smoke rounds where device memory_stats is null
                    "hbm_model_peak_bytes_analytic": getattr(
                        step_z, "hbm_model_peak_bytes", None
                    ),
                    "comms_bytes_per_step": ledger.get("comms/total", 0),
                }
                # Max-feasible-batch probe (analytic, not an OOM search):
                # capacity left after the leg's peak model bytes + state,
                # divided by the measured per-image activation footprint.
                # Null on hosts without memory_stats; the device peak is
                # a process-lifetime watermark, so treat it as a floor
                # estimate, not a guarantee.
                probe = None
                live = mem.get("hbm_live_bytes")
                headroom = mem.get("hbm_headroom_bytes")
                peak_dev = mem.get("hbm_peak_bytes")
                model_peak = zero_ab[name]["hbm_model_peak_bytes_analytic"]
                state_b = zero_ab[name]["hbm_state_bytes_per_chip"]
                if None not in (live, headroom, peak_dev, model_peak):
                    limit = headroom + live
                    act_per_img = max(peak_dev - model_peak - state_b, 1) / batch
                    probe = int(max(limit - model_peak - state_b, 0) // act_per_img)
                zero_ab[name]["max_feasible_batch_probe"] = probe
            legs["zero_ab"]["ran"] = True
            saved = (
                zero_ab["zero1"]["hbm_state_bytes_per_chip"]
                - zero_ab["zero23"]["hbm_state_bytes_per_chip"]
            )
            peak23 = zero_ab["zero23"]["hbm_model_peak_bytes_analytic"]
            peakl = zero_ab["zero_layer"]["hbm_model_peak_bytes_analytic"]
            ratio = round(peak23 / peakl, 2) if peak23 and peakl else None
            print(
                f"zero A/B: zero1={zero_ab['zero1']} zero23={zero_ab['zero23']} "
                f"zero_layer={zero_ab['zero_layer']} "
                f"(at-rest state saved/chip: {saved / 1e6:.1f} MB, "
                f"layer-granular peak-model ratio: {ratio})",
                file=sys.stderr,
            )
        except Exception as e:
            _skip("zero_ab", f"leg crashed: {e!r:.200}")

    # ---- serving leg (queries/s/chip at a fixed SLO) ------------------
    # The platform-independent second headline (ISSUE 8): the key (EMA)
    # encoder behind the continuous batcher, closed-loop clients firing
    # mixed-size requests, measured queries/s at a fixed latency SLO
    # plus padded-bucket occupancy. Runs in the CPU smoke too (counts
    # and recall there; the rates are not device numbers).
    serving = None
    if os.environ.get("BENCH_SKIP_SERVE"):
        _skip("serving", "BENCH_SKIP_SERVE set")
    else:
        try:
            import threading

            from moco_tpu.serve.batcher import ContinuousBatcher
            from moco_tpu.serve.engine import InferenceEngine
            from moco_tpu.serve.index import EmbeddingIndex

            # CPU smoke: shrink the bucket ladder and widen the SLO —
            # the point off-TPU is a nonzero tracked series, not an
            # achievable latency target (same degradation philosophy as
            # the headline's resnet18/32px fallback)
            slo_ms = float(
                os.environ.get("BENCH_SERVE_SLO_MS", 25.0 if on_tpu else 2000.0)
            )
            # the FULL key encoder (backbone + head): serving embeds in
            # the dictionary's space, so the step's own queue rows are
            # the /neighbors corpus
            eng = InferenceEngine(
                encoder,
                jax.device_get(state.params_k),
                jax.device_get(state.batch_stats_k),
                image_size=img,
                buckets=(1, 8, 32, 128) if on_tpu else (1, 8, 32),
            )
            eng.warmup()
            index = None
            if moco.num_negatives > 0:
                index = EmbeddingIndex.from_train_queue(jax.device_get(state.queue))
                index.prepare(eng.buckets, k=5)
                index.freeze()

            def run_batch(images, want_neighbors, *, stages=None):
                if want_neighbors and index is not None:
                    emb, scores, nidx, executed = eng.embed_and_query(
                        images, index, 5, stages=stages
                    )
                    return {"embedding": emb, "scores": scores, "indices": nidx}, executed
                emb, executed = eng.embed(images, stages=stages)
                return {"embedding": emb}, executed

            sizes = tuple(
                s for s in (1, 2, 4, 8, 16, 32) if s <= eng.buckets[-1]
            )
            canned = {
                n: np.random.default_rng(n).integers(0, 255, (n, img, img, 3), np.uint8)
                for n in sizes
            }
            warm_s = float(os.environ.get("BENCH_SERVE_WARM_S", 1.0 if on_tpu else 3.0))
            measure_s = float(
                os.environ.get("BENCH_SERVE_MEASURE_S", 3.0 if on_tpu else 8.0)
            )

            def measure(reqtrace: bool, run_batch_fn=None, warm=None, meas=None):
                """One closed-loop pass: fresh batcher + clients over a
                warm engine's run_batch; returns (qps/chip, payload)."""
                run_batch_fn = run_batch_fn or run_batch
                warm = warm_s if warm is None else warm
                meas = measure_s if meas is None else meas
                batcher = ContinuousBatcher(
                    run_batch_fn, max_batch=eng.buckets[-1], slo_ms=slo_ms,
                    reqtrace=reqtrace,
                )
                measuring = threading.Event()
                stop_clients = threading.Event()
                counts = [0] * 8

                def client(ci: int) -> None:
                    crng = np.random.default_rng(100 + ci)
                    while not stop_clients.is_set():
                        n = int(crng.choice(sizes))
                        try:
                            fut = batcher.submit(
                                canned[n], want_neighbors=index is not None
                            )
                            fut.result(timeout=30.0)
                        except Exception:
                            return
                        if measuring.is_set():
                            counts[ci] += 1

                clients = [
                    threading.Thread(target=client, args=(i,), daemon=True)
                    for i in range(len(counts))
                ]
                for c in clients:
                    c.start()
                time.sleep(warm)
                measuring.set()
                t0s = time.perf_counter()
                time.sleep(meas)
                measuring.clear()
                dts = time.perf_counter() - t0s
                stop_clients.set()
                batcher.close()
                for c in clients:
                    c.join(timeout=5.0)
                payload = batcher.metrics.payload()
                completed = sum(counts)
                if completed == 0:
                    raise RuntimeError(
                        f"no request completed inside the {meas}s measure "
                        "window — raise BENCH_SERVE_MEASURE_S on very slow hosts"
                    )
                return completed / dts / n_dev, payload

            # A/B: the tracked headline stays the tracing-OFF pass (the
            # r06+ series must remain comparable); the tracing-ON pass
            # measures the request-trace overhead the ISSUE-10 acceptance
            # caps (perf_ledger gates trace_overhead_pct)
            qps_chip, payload = measure(reqtrace=False)
            qps_traced, payload_traced = measure(reqtrace=True)
            trace_overhead_pct = (qps_chip - qps_traced) / qps_chip * 100.0

            # ---- router tracing A/B (ISSUE 18) --------------------------
            # The distributed-tracing cost at the fleet front door: the
            # same warm engine behind ONE HTTP replica, a FleetRouter in
            # front, closed-loop clients through real sockets; tracing
            # OFF vs ON (context injection, per-attempt spans, the
            # stitcher, the flight ring). perf_ledger gates the delta
            # under the same trace-overhead caps as the replica-side A/B.
            router_qps = router_qps_traced = None
            if not os.environ.get("BENCH_SKIP_ROUTER"):
                import urllib.request as _urlreq

                from moco_tpu.serve.router import FleetRouter
                from moco_tpu.serve.server import ServeServer

                replica = ServeServer(
                    eng, index=index, port=0, slo_ms=slo_ms,
                    neighbors_k=5, warmup=False,
                )
                router_meas = float(os.environ.get(
                    "BENCH_ROUTER_MEASURE_S", max(measure_s / 2, 2.0)
                ))

                def router_pass(rt: bool) -> float:
                    router = FleetRouter(
                        replica_urls=[f"http://127.0.0.1:{replica.port}"],
                        port=0, slo_ms=slo_ms, hedge=False, reqtrace=rt,
                    )
                    rbase = f"http://127.0.0.1:{router.port}"
                    measuring = threading.Event()
                    stop_r = threading.Event()
                    rcounts = [0] * 4

                    def rclient(ci: int) -> None:
                        crng = np.random.default_rng(200 + ci)
                        while not stop_r.is_set():
                            n = int(crng.choice(sizes))
                            req = _urlreq.Request(
                                rbase + "/embed",
                                data=canned[n].tobytes(),
                                headers={"X-Image-Shape": ",".join(
                                    map(str, canned[n].shape)
                                )},
                            )
                            try:
                                with _urlreq.urlopen(req, timeout=30) as r:
                                    r.read()
                            except Exception:
                                if measuring.is_set():
                                    return
                                # pre-measure 503s while the health loop
                                # admits the replica are expected
                                time.sleep(0.05)
                                continue
                            if measuring.is_set():
                                rcounts[ci] += 1

                    try:
                        rclients = [
                            threading.Thread(
                                target=rclient, args=(i,), daemon=True
                            )
                            for i in range(len(rcounts))
                        ]
                        for c in rclients:
                            c.start()
                        time.sleep(max(warm_s, 1.0))
                        measuring.set()
                        t0r = time.perf_counter()
                        time.sleep(router_meas)
                        measuring.clear()
                        dtr = time.perf_counter() - t0r
                        stop_r.set()
                        for c in rclients:
                            c.join(timeout=10.0)
                    finally:
                        router.close()
                    completed = sum(rcounts)
                    if completed == 0:
                        raise RuntimeError(
                            f"no request completed inside the router "
                            f"{router_meas}s measure window (reqtrace={rt})"
                        )
                    return completed / dtr / n_dev

                try:
                    router_qps = router_pass(False)
                    router_qps_traced = router_pass(True)
                finally:
                    replica.close()
            router_trace_overhead_pct = (
                (router_qps - router_qps_traced) / router_qps * 100.0
                if router_qps
                else None
            )

            # ---- promotion-swap overhead (ISSUE 19) ---------------------
            # What one staged-rollout step costs the fleet front door:
            # two in-process replicas behind a FleetRouter, closed-loop
            # clients running throughout, and replica 0 promoted
            # (drain -> swap -> re-admit with a new model identity).
            # promote_pause_ms = wall time from promote_replica() until
            # /admin/replicas shows the replica back (healthy, not
            # draining, new digest); promote_swap_p99_ms = client p99 of
            # requests overlapping that window; promote_swap_failures
            # must be 0 (the drain path's whole point). The swap rebinds
            # the same port around the already-warm engine, so the pause
            # measures the router-side drain/readmit machinery and
            # EXCLUDES checkpoint restore + AOT re-warm (the fleet smoke
            # exercises the full cold swap). perf_ledger.py check gates
            # all three fields.
            promote_pause_ms = promote_swap_p99 = promote_failures = None
            if not os.environ.get("BENCH_SKIP_PROMOTE"):
                import urllib.request as _urlreq2

                from moco_tpu.serve.router import FleetRouter
                from moco_tpu.serve.server import ServeServer

                class _SwapSupervisor:
                    """Duck-typed ReplicaSupervisor stand-in: the
                    router's promotion path only ever calls
                    set_ckpt_dir() and restart_replica(). A restart
                    rebuilds the in-process replica on the SAME port
                    around the warm engine, bumping the model identity
                    so the digest landing is observable."""

                    def __init__(self, servers):
                        self.servers = servers
                        self.ckpt_dir = None

                    def urls(self):
                        return [
                            f"http://127.0.0.1:{s.port}" for s in self.servers
                        ]

                    def set_ckpt_dir(self, path):
                        self.ckpt_dir = str(path)

                    def restart_replica(self, i):
                        old = self.servers[i]
                        port, step = old.port, (old.model_step or 0) + 1
                        old.close()
                        self.servers[i] = ServeServer(
                            eng, index=index, port=port, slo_ms=slo_ms,
                            neighbors_k=5, warmup=False, model_step=step,
                            model_digest=f"benchswap{step:03d}",
                        )

                duck = _SwapSupervisor([
                    ServeServer(
                        eng, index=index, port=0, slo_ms=slo_ms,
                        neighbors_k=5, warmup=False, model_step=0,
                        model_digest=f"benchlive{i:03d}",
                    )
                    for i in range(2)
                ])
                prouter = FleetRouter(
                    replica_urls=duck.urls(), supervisor=duck, port=0,
                    slo_ms=slo_ms, hedge=False, health_interval_s=0.1,
                )
                pbase = f"http://127.0.0.1:{prouter.port}"
                admitted = threading.Event()
                stop_p = threading.Event()
                p_lock = threading.Lock()
                p_samples = []  # (t_start, t_end, ms) post-admission
                p_failures = []

                def pclient(ci: int) -> None:
                    crng = np.random.default_rng(300 + ci)
                    while not stop_p.is_set():
                        n = int(crng.choice(sizes))
                        req = _urlreq2.Request(
                            pbase + "/embed",
                            data=canned[n].tobytes(),
                            headers={"X-Image-Shape": ",".join(
                                map(str, canned[n].shape)
                            )},
                        )
                        t0 = time.perf_counter()
                        try:
                            with _urlreq2.urlopen(req, timeout=30) as r:
                                r.read()
                        except Exception as e:
                            if admitted.is_set():
                                with p_lock:
                                    p_failures.append(repr(e))
                            else:
                                # pre-admission 503s while the health
                                # loop admits the replicas are expected
                                time.sleep(0.05)
                            continue
                        t1 = time.perf_counter()
                        if admitted.is_set():
                            with p_lock:
                                p_samples.append((t0, t1, (t1 - t0) * 1e3))

                def _fleet_snap():
                    with _urlreq2.urlopen(
                        pbase + "/admin/replicas", timeout=5
                    ) as r:
                        return json.loads(r.read())["replicas"]

                try:
                    deadline = time.monotonic() + 30.0
                    while time.monotonic() < deadline:
                        snaps = _fleet_snap()
                        if all(s["healthy"] and s["warm"] for s in snaps):
                            break
                        time.sleep(0.1)
                    pclients = [
                        threading.Thread(target=pclient, args=(i,), daemon=True)
                        for i in range(4)
                    ]
                    for c in pclients:
                        c.start()
                    admitted.set()
                    time.sleep(max(warm_s, 1.0))
                    t_sw0 = time.perf_counter()
                    if not prouter.promote_replica(0, "bench-candidate"):
                        raise RuntimeError("promotion step refused: replica busy")
                    deadline = time.monotonic() + 60.0
                    landed = False
                    while time.monotonic() < deadline:
                        s0 = _fleet_snap()[0]
                        if (
                            s0["healthy"]
                            and not s0["draining"]
                            and s0["model_digest"] == "benchswap001"
                        ):
                            landed = True
                            break
                        time.sleep(0.05)
                    t_sw1 = time.perf_counter()
                    if not landed:
                        raise RuntimeError(
                            f"promotion swap never landed: {_fleet_snap()[0]}"
                        )
                    time.sleep(0.5)  # tail traffic past the swap window
                    stop_p.set()
                    for c in pclients:
                        c.join(timeout=10.0)
                finally:
                    stop_p.set()
                    prouter.close()
                    for s in duck.servers:
                        s.close()
                promote_pause_ms = (t_sw1 - t_sw0) * 1e3
                with p_lock:
                    window = sorted(
                        ms for (a, b, ms) in p_samples
                        if b >= t_sw0 and a <= t_sw1
                    )
                    promote_failures = len(p_failures)
                promote_swap_p99 = (
                    window[min(len(window) - 1, int(len(window) * 0.99))]
                    if window
                    else None
                )

            # ---- quantized-engine A/B (ISSUE 11): w8 vs w8a8 ----------
            # Same params, same buckets, same index; qps measured in
            # short INTERLEAVED slices (the tiers alternate inside one
            # wall window, so host drift hits both equally) plus each
            # tier's embedding cosine vs the f32 engine on a fixed probe
            # batch. `int8_kernels` records whether true int8×int8→int32
            # actually ran (tpu/gpu) or the bit-faithful CPU emulation
            # did (quant.py docstring: XLA:CPU has no int8 conv kernels,
            # measured ~45x slower — so on the CPU smoke the w8a8-vs-w8
            # speed signal is conv-bound ~parity and the arithmetic
            # factor is an accelerator claim; the cosine floor gates
            # everywhere).
            quant_ab = None
            if not os.environ.get("BENCH_SKIP_QUANT"):
                probe = np.concatenate([canned[n] for n in sizes])
                emb_f32, _ = eng.embed(probe)

                def _mean_cos(a, b):  # rows are L2-normalized
                    return float(np.mean(np.sum(
                        np.asarray(a, np.float64) * np.asarray(b, np.float64),
                        axis=-1,
                    )))

                calib_sample = np.concatenate([
                    np.random.default_rng(50 + n).integers(
                        0, 255, (n, img, img, 3), np.uint8
                    )
                    for n in sizes
                ])
                qengines = {}
                for tier in ("w8", "w8a8"):
                    kw = {"calib_sample": calib_sample} if tier == "w8a8" else {}
                    qe = InferenceEngine(
                        encoder,
                        jax.device_get(state.params_k),
                        jax.device_get(state.batch_stats_k),
                        image_size=img,
                        buckets=eng.buckets,
                        engine_quant=tier,
                        **kw,
                    )
                    qe.warmup()
                    qengines[tier] = qe

                def _quant_run_batch(qe):
                    def rb(images, want_neighbors, *, stages=None):
                        if want_neighbors and index is not None:
                            emb, scores, nidx, executed = qe.embed_and_query(
                                images, index, 5, stages=stages
                            )
                            return {
                                "embedding": emb, "scores": scores, "indices": nidx,
                            }, executed
                        emb, executed = qe.embed(images, stages=stages)
                        return {"embedding": emb}, executed
                    return rb

                slices = int(os.environ.get("BENCH_QUANT_SLICES", 3))
                slice_s = float(
                    os.environ.get("BENCH_QUANT_SLICE_S", max(measure_s / 3, 1.0))
                )
                acc = {t: [] for t in qengines}
                for _ in range(slices):
                    for tier, qe in qengines.items():
                        q_t, _ = measure(
                            reqtrace=False, run_batch_fn=_quant_run_batch(qe),
                            warm=min(warm_s, 1.0), meas=slice_s,
                        )
                        acc[tier].append(q_t)
                quant_ab = {}
                for tier, qe in qengines.items():
                    if qe.recompiles_after_warmup:
                        raise RuntimeError(
                            f"{tier} engine recompiled after warmup"
                        )
                    emb_q, _ = qe.embed(probe)
                    audit = qe.donation_audit()
                    quant_ab[tier] = {
                        "qps": round(sum(acc[tier]) / len(acc[tier]), 2),
                        "cosine_vs_f32": round(_mean_cos(emb_q, emb_f32), 5),
                        "donation_audit_ok": not any(
                            v is False for v in audit.values()
                        ),
                    }
                quant_ab["w8a8"]["int8_kernels"] = bool(
                    qengines["w8a8"].int8_compute
                )
                quant_ab["speedup_w8a8_vs_w8"] = round(
                    quant_ab["w8a8"]["qps"] / quant_ab["w8"]["qps"], 3
                )
                print(
                    f"serving quant A/B: w8={quant_ab['w8']['qps']:.1f} q/s "
                    f"(cos={quant_ab['w8']['cosine_vs_f32']:.5f}) "
                    f"w8a8={quant_ab['w8a8']['qps']:.1f} q/s "
                    f"(cos={quant_ab['w8a8']['cosine_vs_f32']:.5f}, "
                    f"int8_kernels={quant_ab['w8a8']['int8_kernels']}) "
                    f"-> {quant_ab['speedup_w8a8_vs_w8']}x",
                    file=sys.stderr,
                )

            recompiles = eng.recompiles_after_warmup + (
                index.recompiles_after_warmup if index is not None else 0
            )
            if recompiles:
                raise RuntimeError(
                    f"serving leg recompiled {recompiles}x after warmup"
                )
            serving = {
                "metric": (
                    f"moco_serve_{arch}_queries_per_sec_per_chip"
                    if on_tpu
                    else f"moco_serve_{arch}_cpu_smoke_queries_per_sec"
                ),
                "value": round(qps_chip, 2),
                "unit": "queries/sec/chip",
                "slo_ms": slo_ms,
                "p50_ms": round(payload["serve/p50_ms"], 2),
                "p99_ms": round(payload["serve/p99_ms"], 2),
                "occupancy": round(payload["serve/occupancy"], 4),
                "slo_violation_rate": (
                    round(payload["serve/slo_violations"] / payload["serve/requests"], 4)
                    if payload["serve/requests"]
                    else None
                ),
                "bucket_histogram": {
                    k.split("_", 1)[1]: v
                    for k, v in payload.items()
                    if k.startswith("serve/bucket_")
                },
                "neighbors": index is not None,
                # request-tracing A/B (ISSUE 10): qps with per-request
                # waterfalls ON, the measured overhead (gated by
                # perf_ledger.py check), and the traced pass's mean
                # stage split
                "qps_traced": round(qps_traced, 2),
                "trace_overhead_pct": round(trace_overhead_pct, 2),
                # distributed-tracing A/B at the fleet front door
                # (ISSUE 18): qps through a FleetRouter + one HTTP
                # replica with router tracing OFF vs ON; the overhead is
                # gated by perf_ledger.py check under the same caps
                "router_qps": (
                    round(router_qps, 2) if router_qps is not None else None
                ),
                "router_qps_traced": (
                    round(router_qps_traced, 2)
                    if router_qps_traced is not None
                    else None
                ),
                "router_trace_overhead_pct": (
                    round(router_trace_overhead_pct, 2)
                    if router_trace_overhead_pct is not None
                    else None
                ),
                "trace_stage_ms": {
                    k[len("serve/trace_"):-len("_ms")]: v
                    for k, v in payload_traced.items()
                    if k.startswith("serve/trace_") and k.endswith("_ms")
                },
                # promotion-swap overhead (ISSUE 19): one staged-rollout
                # step through the router under live closed-loop load —
                # the pause until the swapped replica re-admits with its
                # new digest, the client p99 across the swap window, and
                # the failure count (gated at 0 by perf_ledger.py check)
                "promote_pause_ms": (
                    round(promote_pause_ms, 2)
                    if promote_pause_ms is not None
                    else None
                ),
                "promote_swap_p99_ms": (
                    round(promote_swap_p99, 2)
                    if promote_swap_p99 is not None
                    else None
                ),
                "promote_swap_failures": promote_failures,
                # quantized-engine tiers (ISSUE 11): w8/w8a8 qps from the
                # interleaved slices + embedding cosine vs f32 (gated at
                # QUANT_COSINE_FLOOR by perf_ledger.py check), and
                # whether true int8 kernels ran
                "quant": quant_ab,
            }
            legs["serving"]["ran"] = True
            print(
                f"serving: {qps_chip:.1f} queries/s/chip @ SLO {slo_ms}ms "
                f"(p50={payload['serve/p50_ms']}ms p99={payload['serve/p99_ms']}ms "
                f"occupancy={payload['serve/occupancy']} "
                f"violations={serving['slo_violation_rate']} "
                f"traced={qps_traced:.1f} q/s "
                f"overhead={trace_overhead_pct:+.1f}%)",
                file=sys.stderr,
            )
            if router_trace_overhead_pct is not None:
                print(
                    f"router tracing A/B: {router_qps:.1f} q/s untraced, "
                    f"{router_qps_traced:.1f} q/s traced "
                    f"(overhead={router_trace_overhead_pct:+.1f}%)",
                    file=sys.stderr,
                )
            if promote_pause_ms is not None:
                print(
                    f"promotion swap: pause={promote_pause_ms:.0f}ms "
                    f"p99-during-swap="
                    + (
                        f"{promote_swap_p99:.0f}ms"
                        if promote_swap_p99 is not None
                        else "n/a"
                    )
                    + f" failures={promote_failures}",
                    file=sys.stderr,
                )
        except Exception as e:
            serving = None  # never ship a half-built serving record
            legs["serving"]["ran"] = False
            _skip("serving", f"leg crashed: {e!r:.200}")

    # ---- ANN A/B: exact scan vs IVF behind EmbeddingIndex (ISSUE 9) ---
    # The sub-linear serving claim, measured: a K-row dictionary (2^20
    # by default — past the point where the exact scan's O(K) matmul
    # dominates a query), exact vs IVF (nprobe cells of ~K/nlist rows)
    # vs int8-IVF queries/s at the same top-k, plus recall@k of each
    # approximate tier against the exact oracle on the same queries.
    # Runs in the CPU smoke too, like the serving leg.
    ann_ab = None
    if os.environ.get("BENCH_SKIP_ANN"):
        _skip("ann_ab", "BENCH_SKIP_ANN set")
    else:
        try:
            from moco_tpu.serve.index import EmbeddingIndex

            ann_rows = int(os.environ.get("BENCH_ANN_ROWS", 1 << 20))
            ann_dim = int(os.environ.get("BENCH_ANN_DIM", 64))
            ann_nlist = int(os.environ.get("BENCH_ANN_NLIST", 1024))
            ann_nprobe = int(os.environ.get("BENCH_ANN_NPROBE", 8))
            ann_m = int(os.environ.get("BENCH_ANN_BATCH", 8))
            ann_batches = int(os.environ.get("BENCH_ANN_QUERY_BATCHES", 8))
            ks = (1, 10)
            # clustered synthetic corpus (mixture of Gaussians on the
            # sphere) — the geometry trained embedding dictionaries
            # actually have; uniform random rows have no neighbor
            # structure for ANY index to exploit
            arng = np.random.default_rng(7)
            n_centers = max(4 * ann_nlist, 64)
            centers = arng.normal(size=(n_centers, ann_dim)).astype(np.float32)
            corpus = centers[arng.integers(0, n_centers, ann_rows)]
            corpus += 0.25 * arng.normal(size=corpus.shape).astype(np.float32)
            corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
            picks = arng.integers(0, ann_rows, ann_batches * ann_m)
            queries = corpus[picks] + 0.05 * arng.normal(
                size=(len(picks), ann_dim)
            ).astype(np.float32)
            queries /= np.linalg.norm(queries, axis=1, keepdims=True)
            qbatches = queries.reshape(ann_batches, ann_m, ann_dim)

            aidx = EmbeddingIndex(ann_rows, ann_dim)
            aidx.snapshot(corpus)
            t0a = time.perf_counter()
            aidx.train_ivf(
                nlist=ann_nlist,
                iters=int(os.environ.get("BENCH_ANN_KMEANS_ITERS", 8)),
                nprobe=ann_nprobe,
            )
            aidx.enable_int8()
            build_s = time.perf_counter() - t0a
            aidx.prepare([ann_m], k=max(ks), nprobe=ann_nprobe,
                         modes=("exact", "ivf", "ivf_i8",
                                "ivf_fused", "ivf_fused_i8"))
            aidx.freeze()

            def _ann_leg(mode):
                outs = []
                t0 = time.perf_counter()
                for qb in qbatches:
                    outs.append(aidx.query(qb, max(ks), mode=mode)[1])
                dt = time.perf_counter() - t0
                return ann_batches * ann_m / dt, np.concatenate(outs)

            exact_qps, exact_idx = _ann_leg("exact")
            ivf_qps, ivf_idx = _ann_leg("ivf")
            i8_qps, i8_idx = _ann_leg("ivf_i8")
            # the fused gather-scan tiers (ISSUE 11): same probe/top-k
            # semantics as the composed scan, one kernel, no
            # (m, nprobe*cell_cap, d) candidate materialization
            fused_qps, fused_idx = _ann_leg("ivf_fused")
            fused_i8_qps, fused_i8_idx = _ann_leg("ivf_fused_i8")
            if aidx.recompiles_after_warmup:
                raise RuntimeError(
                    f"ann leg recompiled {aidx.recompiles_after_warmup}x after freeze"
                )

            def _recall(approx, oracle, k):
                return float(np.mean([
                    len(set(approx[i, :k]) & set(oracle[i, :k])) / k
                    for i in range(oracle.shape[0])
                ]))

            stats = aidx.ivf_stats()
            ann_ab = {
                "metric": (
                    "moco_ann_ivf_queries_per_sec"
                    if on_tpu
                    else "moco_ann_ivf_cpu_smoke_queries_per_sec"
                ),
                "value": round(ivf_qps, 2),
                "unit": "queries/sec",
                "rows": ann_rows,
                "dim": ann_dim,
                "nlist": stats["nlist"],
                "nprobe": ann_nprobe,
                "cell_cap": stats["cell_cap"],
                "spilled": stats["spilled"],
                "batch": ann_m,
                "build_s": round(build_s, 2),
                "exact_qps": round(exact_qps, 2),
                "speedup": round(ivf_qps / exact_qps, 2),
                "recall_at_1": _recall(ivf_idx, exact_idx, 1),
                "recall_at_10": _recall(ivf_idx, exact_idx, 10),
                "int8": {
                    "qps": round(i8_qps, 2),
                    "speedup_vs_exact": round(i8_qps / exact_qps, 2),
                    # honest recall vs the f32 oracle AND vs the int8
                    # exact oracle (isolates IVF loss from quantization
                    # reordering of near-ties)
                    "recall_at_10": _recall(i8_idx, exact_idx, 10),
                },
                # fused gather-scan tier (ISSUE 11): the composed scan's
                # three hops as one kernel — recall-gated like every
                # tier (perf_ledger check: recall floor + fused must
                # beat the composed tier it replaces)
                "fused": {
                    "qps": round(fused_qps, 2),
                    "speedup_vs_ivf": round(fused_qps / ivf_qps, 2),
                    "recall_at_10": _recall(fused_idx, exact_idx, 10),
                    # same candidate set by construction — ids match the
                    # composed scan exactly on ties-free data
                    "ids_match_composed": bool((fused_idx == ivf_idx).all()),
                    "int8": {
                        "qps": round(fused_i8_qps, 2),
                        "speedup_vs_ivf_i8": round(fused_i8_qps / i8_qps, 2),
                        "recall_at_10": _recall(fused_i8_idx, exact_idx, 10),
                    },
                },
            }
            legs["ann_ab"]["ran"] = True
            print(
                f"ann A/B: K={ann_rows} exact={exact_qps:.1f} q/s "
                f"ivf={ivf_qps:.1f} q/s ({ann_ab['speedup']}x, "
                f"recall@10={ann_ab['recall_at_10']:.3f}) "
                f"ivf_i8={i8_qps:.1f} q/s | fused={fused_qps:.1f} q/s "
                f"({ann_ab['fused']['speedup_vs_ivf']}x vs composed, "
                f"recall@10={ann_ab['fused']['recall_at_10']:.3f}, "
                f"ids_match={ann_ab['fused']['ids_match_composed']}) "
                f"fused_i8={fused_i8_qps:.1f} q/s (build {build_s:.1f}s, "
                f"spilled={stats['spilled']})",
                file=sys.stderr,
            )
        except Exception as e:
            ann_ab = None
            legs["ann_ab"]["ran"] = False
            _skip("ann_ab", f"leg crashed: {e!r:.200}")

    # ---- MFU (per-device FLOPs over per-device peak) ------------------
    flops_per_dev = _step_flops(step, state, batch_dict, root_rng) or (
        None if is_vit else _analytic_step_flops(batch, img) / n_dev
    )
    peak = _peak_tflops(jax.devices()[0])
    mfu = (
        (flops_per_dev * steps / dt) / (peak * 1e12)
        if peak and flops_per_dev
        else None
    )

    # ---- with-data rate (real pipeline in the loop) -------------------
    # Two legs since the device prefetch ring landed (ISSUE 5): the
    # synchronous path (decode → transfer → dispatch take turns on one
    # producer thread) vs the overlapped path (epoch(device=True):
    # decode thread + transfer ring + pipelined steps). Both run in the
    # CPU smoke too.
    with_data = with_data_sync = overlap_efficiency = None
    if os.environ.get("BENCH_SKIP_DATA"):
        _skip("with_data", "BENCH_SKIP_DATA set")
    else:
        try:
            from moco_tpu.data.pipeline import TwoCropPipeline

            # drop-last pipeline: an epoch smaller than one batch yields
            # ZERO batches and the epoch roller below would spin forever
            if on_tpu:
                n_imgs, src_size = max(1024, batch), 256
            else:  # CPU smoke: small synthetic folder, small geometry
                n_imgs, src_size = max(256, batch), 64
            folder = _ensure_jpeg_folder("/tmp/moco_bench_imgfolder", n_imgs, src_size)
            dconf = DataConfig(
                dataset="imagefolder",
                data_dir=folder,
                image_size=img,
                global_batch=batch,
                aug_plus=True,
                num_workers=8,
                # decode-once packed RGB cache on by default (best-practice
                # config; BENCH_CACHE_DIR="" disables, see PROFILE.md for
                # the uncached/canvas-mode ladder); BENCH_HOST_RRC=0 moves
                # the crop on-device (canvas mode — a pure mmap row read)
                cache_dir=os.environ.get("BENCH_CACHE_DIR", "/tmp/moco_bench_cache")
                or None,
                host_rrc=os.environ.get("BENCH_HOST_RRC", "1") != "0",
            )
            pipe = TwoCropPipeline(dconf, mesh, seed=0)

            def _with_data_leg(device: bool, warm_steps: int):
                """Sustained imgs/s (global) of `steps` real-pipeline
                steps, plus the ring's TransferStats on the overlapped
                leg. Rolls over epochs; closes abandoned iterators so
                ring/producer threads never leak between legs."""
                st, done, epoch = state, 0, 0
                it = iter(pipe.epoch(epoch, device=device))

                def _next():
                    nonlocal it, epoch
                    while True:
                        b = next(it, None)
                        if b is not None:
                            return b
                        getattr(it, "close", lambda: None)()
                        epoch += 1
                        it = iter(pipe.epoch(epoch, device=device))

                for _ in range(warm_steps):
                    b = _next()
                st, m = step(st, b, root_rng)
                float(m["loss"])
                t0 = time.perf_counter()
                for _ in range(steps):
                    st, m = step(st, _next(), root_rng)
                float(m["loss"])  # chained state deps force all steps
                dt = time.perf_counter() - t0
                stats = getattr(it, "stats", None)
                getattr(it, "close", lambda: None)()
                return batch * steps / dt, stats

            # warm a FULL first epoch before timing: the first pass over
            # a cold cache dir decodes every JPEG and writes the packed
            # cache — a one-time cost that otherwise lands inside the
            # timed loop and misreports the steady-state rate (the
            # ladder in PROFILE.md is steady-state)
            warm_steps = max(n_imgs // batch, 1)
            sync_rate, _ = _with_data_leg(device=False, warm_steps=warm_steps)
            over_rate, ring_stats = _with_data_leg(device=True, warm_steps=1)
            with_data_sync = sync_rate / n_dev
            with_data = over_rate / n_dev

            # overlap_efficiency = achieved / min(host, device, wire):
            # 1.0 means the overlapped loop runs at the binding stage's
            # rate — nothing left to hide. Host rate drains the decode
            # generator alone; device rate is the headline steady-state;
            # wire rate converts the ring's measured MB/s to imgs/s.
            t0 = time.perf_counter()
            host_n = 0
            for _ in pipe._host_gen(97):
                host_n += 1
                if host_n >= steps:
                    break
            host_rate = batch * host_n / (time.perf_counter() - t0)
            bounds = [host_rate, imgs_per_sec]
            if ring_stats is not None and ring_stats.batches:
                wire_bps = ring_stats.wire_rate_bytes_per_sec()
                bytes_per_img = ring_stats.total_bytes / ring_stats.batches / batch
                if wire_bps and bytes_per_img:
                    bounds.append(wire_bps / bytes_per_img)
            overlap_efficiency = over_rate / min(bounds)
            legs["with_data"]["ran"] = True
            print(
                f"with-data: sync={sync_rate:.1f} overlapped={over_rate:.1f} imgs/s "
                f"(bounds host={host_rate:.1f} device={imgs_per_sec:.1f}"
                + (f" wire={bounds[2]:.1f}" if len(bounds) > 2 else "")
                + f") overlap_efficiency={overlap_efficiency:.3f}",
                file=sys.stderr,
            )
        except Exception as e:
            _skip("with_data", f"leg crashed: {e!r:.200}")

    print(
        f"platform={platform} chips={n_dev} arch={arch} batch={batch} "
        f"steps={steps} wall={dt:.2f}s total={imgs_per_sec:.1f} imgs/s "
        f"mfu={mfu if mfu is None else round(mfu, 4)} with_data={with_data}",
        file=sys.stderr,
    )
    if is_vit:
        flash = "_flash" if config.moco.vit_flash_attention else ""
        metric = (
            f"moco_v3_{arch}{flash}_pretrain_imgs_per_sec_per_chip"
            if on_tpu
            else f"moco_v3_{arch}{flash}_cpu_smoke_imgs_per_sec"
        )
    elif on_tpu:
        metric = "moco_v2_r50_pretrain_imgs_per_sec_per_chip"
    else:
        metric = "moco_v1_r18_cpu_smoke_imgs_per_sec"
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(per_chip, 2),
                "unit": "imgs/sec/chip",
                # apples-to-apples only on the real R50/224 TPU metric
                # (the 168 imgs/s/GPU baseline is the reference's R50 run)
                "vs_baseline": round(per_chip / REFERENCE_IMGS_PER_SEC_PER_GPU, 3)
                if on_tpu and not is_vit
                else None,
                "mfu": None if mfu is None else round(mfu, 4),
                # overlapped (device prefetch ring) with-data rate; the
                # sync leg and the efficiency ratio ride along so every
                # BENCH record carries the overlap A/B (CPU smoke too)
                "with_data_imgs_per_sec_per_chip": None
                if with_data is None
                else round(with_data, 2),
                "with_data_sync_imgs_per_sec_per_chip": None
                if with_data_sync is None
                else round(with_data_sync, 2),
                "overlap_efficiency": None
                if overlap_efficiency is None
                else round(overlap_efficiency, 3),
                # telemetry-layer cost: full obs (health gauges + tracer
                # + sink writes) vs bare, same compiled shapes
                "obs_overhead_pct": obs_overhead_pct,
                # ZeRO-1 vs ZeRO-2/3 A/B (multi-chip legs only): per-leg
                # rate, device hbm peak, analytic at-rest state bytes,
                # and bucketed-collective bytes/step
                "zero_ab": zero_ab,
                # serving leg (ISSUE 8): the second headline series —
                # queries/s/chip through the continuous batcher at a
                # fixed SLO, with its own metric name so the perf
                # ledger gates it independently of the training rate
                "serving": serving,
                # ANN A/B (ISSUE 9): exact-vs-IVF-vs-int8 queries/s +
                # recall@k on a 2^20-row dictionary — the third gated
                # series (sub-linear retrieval must stay sub-linear)
                "ann_ab": ann_ab,
                # per-leg skip ledger: WHY a leg didn't run, in-band —
                # a BENCH_*.json degraded to the CPU smoke now says so
                # itself (accelerator.skip_reason) instead of relying on
                # someone reading four rounds of stderr
                "legs": legs,
            }
        )
    )
    if not crosscheck_ok:
        raise SystemExit("fused-vs-dense numerics crosscheck FAILED")


if __name__ == "__main__":
    main()
