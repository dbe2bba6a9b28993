"""Pretraining driver — the TPU-native `main_moco.py`.

Reference call stack (SURVEY.md §3.1): argparse → `mp.spawn` one process
per GPU → NCCL init → build MoCo → DDP wrap → SGD → per-epoch
`adjust_learning_rate` + `train()` + rank-0 checkpoint. Here the whole
process topology collapses into one SPMD program over a
`jax.sharding.Mesh`: no spawn, no rendezvous, no rank bookkeeping — the
mesh and the jitted `train_step` are the distribution model, the LR
schedule lives inside the optimizer, and Orbax handles multi-host
checkpointing.

Library entry: `train(config) -> final metrics`. CLI: repo-root
`train.py` (argparse mapping the reference's flags onto `TrainConfig`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import signal
import threading
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from moco_tpu import obs
from moco_tpu.core import (
    build_encoder,
    build_predictor,
    create_state,
    make_train_step,
    place_state,
    reshard_state,
    sample_input,
    zero_stage23,
)
from moco_tpu.data.pipeline import TwoCropPipeline
from moco_tpu.obs import comms
from moco_tpu.obs.alerts import AlertEngine, FatalAlertError, parse_rules
from moco_tpu.obs.fleet import FleetAggregator, Heartbeat
from moco_tpu.obs.sinks import build_sinks, per_process_filename
from moco_tpu.obs.stepstats import (
    StepTimeProbe,
    memory_payload,
    phase_account,
    setup_account,
    tree_shard_bytes,
)
from moco_tpu.parallel.elastic import (
    RESCALE_EXIT_CODE,
    ElasticCoordinator,
    ElasticRescale,
    plan_rescale,
    surviving_devices,
)
from moco_tpu.parallel.zero import AsyncParamGather, unshard_tree_host
from moco_tpu.parallel import create_mesh, create_multislice_mesh, maybe_initialize_multihost
from moco_tpu.utils import faults, retry
from moco_tpu.utils.checkpoint import CheckpointManager
from moco_tpu.utils.config import (
    ResumeCompatError,
    TrainConfig,
    apply_auto_scale,
    config_to_dict,
    resume_compat_diff,
)
from moco_tpu.utils.metrics import (
    AverageMeter,
    ProfilerWindow,
    ProgressMeter,
    print0,
    profiler_trace,
)
from moco_tpu.utils.platform import log_devices
from moco_tpu.utils.schedules import build_optimizer, make_lr_schedule
from moco_tpu.utils.watchdog import StepWatchdog


def train(
    config: TrainConfig,
    dataset=None,
    profile_dir: Optional[str] = None,
    knn_datasets=None,
    profile_steps: Optional[tuple] = None,
) -> dict:
    """Run the full pretraining loop; returns the last epoch's mean metrics.

    `dataset` overrides the config-built dataset (tests inject synthetic
    data of a chosen size this way). `knn_datasets` is an optional
    (bank_dataset, test_dataset) pair for the periodic kNN monitor
    (config.knn_every_epochs); when None it is built from config.data.
    `profile_steps=(a, b)` captures a jax.profiler trace of exactly
    global steps [a, b) into `profile_dir` (or `workdir/profile`)
    instead of the whole-run trace a bare `profile_dir` records.
    """
    # Partitionable threefry, matching tests/conftest.py. With the
    # default threefry, GSPMD materializes replicated random bits via
    # cross-device collectives; those ride in data-INDEPENDENT programs
    # (the device-side augment) that are in flight concurrently with
    # the step chain — and XLA:CPU launches programs on input-readiness,
    # so two independent collective programs can interleave in different
    # per-device orders and deadlock the rendezvous (observed as a
    # first-step wedge on the 8-virtual-device mesh once ZeRO-2/3's
    # gather program joined the flight). Partitionable threefry shards
    # the bit generation instead: no collectives, no race — and it is
    # the setting the entire test suite already runs under.
    jax.config.update("jax_threefry_partitionable", True)
    # Deterministic fault injection (chaos harness): MOCO_FAULTS installs
    # a fresh plan per run; unset leaves any programmatic plan (tests)
    # alone. Zero-cost when no plan is installed.
    faults.install_from_env()
    # Multi-host rendezvous BEFORE the first backend query (the
    # reference's dist.init_process_group; auto-detected from the
    # coordinator env, or forced with MOCO_MULTIHOST=1) — the tracer
    # below needs the process index, and reading it any earlier would
    # initialize a single-process backend.
    maybe_initialize_multihost()
    log_devices("train")
    pidx = jax.process_index()
    # Telemetry (moco_tpu/obs): the span tracer is installed process-wide
    # for the run's duration, so the data pipeline's decode spans, the
    # checkpoint I/O spans, and the kNN-eval spans all land in one trace.
    # Spans stream to trace_events.jsonl (crash-safe tail; per-process
    # filenames when processes share a workdir — scripts/trace_merge.py
    # stitches them into one Perfetto file with a track per host) and
    # export as a Chrome trace on exit. Every span is also entered as
    # `moco/<name>` on jax's profiler, so a device trace taken over this
    # run (--profile-steps, or anyone's start_trace) carries the host's
    # phases on the device's clock.
    tracer = obs.Tracer(
        os.path.join(
            config.workdir, per_process_filename("trace_events.jsonl", pidx)
        ),
        process_index=pidx,
    )
    prev_tracer = obs.set_tracer(tracer)
    prev_annotator = obs.set_annotator(jax.profiler.TraceAnnotation)
    try:
        # Elastic outer loop (parallel/elastic.py): each _train_impl
        # attempt runs on one mesh shape; an ElasticRescale (heartbeat
        # loss -> consensus -> emergency checkpoint, raised from the
        # log-step elastic check) shrinks the world and re-enters the
        # setup IN-PROCESS — the resume machinery restores the emergency
        # checkpoint and reshards it onto the surviving mesh
        # (reshard_state), so nothing restarts from scratch.
        ref_config = config
        if ref_config.elastic and not ref_config.auto_scale:
            # anchor the scaling rules at the pre-loss batch, so a
            # rescale derives kappa against the original recipe rather
            # than drifting hyperparameters silently
            ref_config = dataclasses.replace(
                ref_config, auto_scale=f"ref_batch={ref_config.data.global_batch}"
            )
        dead_hosts: set = set()
        while True:
            try:
                return _train_impl(
                    ref_config, dataset, profile_dir, knn_datasets, profile_steps,
                    dead_hosts=frozenset(dead_hosts),
                )
            except ElasticRescale as r:
                if jax.process_count() > 1:
                    # a real multi-process fleet cannot shrink the JAX
                    # distributed runtime in-process: the emergency
                    # checkpoint is durable and the plan is agreed —
                    # exit with the rescale code so the launcher
                    # relaunches the survivors with the derived shape
                    # (the resume then reshards onto it).
                    print0(
                        f"elastic rescale (multi-process): {r}; exiting "
                        f"{RESCALE_EXIT_CODE} for the launcher to relaunch "
                        f"with --num-data {r.plan.new_num_data} "
                        f"--batch-size {r.plan.new_global_batch}"
                    )
                    raise SystemExit(RESCALE_EXIT_CODE) from r
                dead_hosts |= set(r.plan.dead_hosts)
                ref_config = r.new_config
                print0(f"{r} — resuming in-process on the surviving mesh")
    finally:
        try:
            tracer.export_chrome(
                os.path.join(config.workdir, per_process_filename("trace.json", pidx))
            )
        except Exception as e:  # telemetry must never mask the real error
            print(f"WARNING: chrome trace export failed: {e!r}", flush=True)
        obs.set_annotator(prev_annotator)
        obs.set_tracer(prev_tracer)
        tracer.close()


def state_needs_single_copy(state_bytes: int) -> bool:
    """Whether the first device can hold the train state only once. Kept
    more than once it is there three times while a step runs (the rollback
    copy of the last logged step, the step's input and its output), beside
    the step's temporaries: the gradient is a quarter of a copy, and a long
    row's activations and expert buffers came to most of another (4.5 GB
    beside a state of 5.3 at one row of 16 384 tokens). So: four copies
    pass what the device reports as its limit. False where the backend
    reports no limit (the CPU)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return bool(limit) and 4 * state_bytes > limit


def _train_impl(
    config: TrainConfig,
    dataset,
    profile_dir: Optional[str],
    knn_datasets,
    profile_steps: Optional[tuple],
    dead_hosts: frozenset = frozenset(),
) -> dict:
    # Set-up is timed in five spans (`setup/*`); what they had cost when
    # the first step's outputs are ready goes out once as the `setup`
    # event line. The baseline makes an elastic re-entry count its own.
    tracer = obs.get_tracer()  # train() installed it
    setup_base = tracer.totals()
    setup_open = True

    def setup_span(name: str):
        """The process's first step is still set-up: its data wait and
        its dispatch are timed under a set-up name as well."""
        return obs.span(name) if setup_open else contextlib.nullcontext()

    # (the multi-host rendezvous already ran in train(), before the
    # tracer needed the process index; this is a no-op then)
    maybe_initialize_multihost()
    # Auto-scale (utils/config.py): `config` arrives carrying REFERENCE
    # hyperparameters; the live lr / EMA momentum are derived here from
    # the actual global batch (kappa = batch/ref_batch: lr linear,
    # momentum m^kappa). The reference config is kept for the elastic
    # rescale, which must re-derive against the same anchor.
    ref_config = config
    config, auto_info = apply_auto_scale(config)
    if auto_info is not None:
        print0(
            f"auto-scale: global batch {config.data.global_batch} vs ref "
            f"{auto_info['ref_batch']} (kappa={auto_info['kappa']:g}) -> "
            f"lr {auto_info['lr']:g}, EMA momentum {auto_info['momentum']:g}"
        )
    if config.elastic and config.parallel.num_model > 1:
        raise ValueError("elastic=True supports num_model=1 meshes only")
    with obs.span("setup/backend"):
        if dead_hosts:
            # post-rescale attempt: the mesh covers the SURVIVING devices
            # only (the agreed width; feasibility was decided by the plan)
            mesh = create_mesh(
                num_data=config.parallel.num_data,
                num_model=config.parallel.num_model,
                devices=surviving_devices(dead_hosts),
            )
        elif config.parallel.num_data is None:
            # slice-aware layout: on multi-slice deployments the data axis
            # orders ICI-adjacent chips together so grad psum rides ICI first
            mesh = create_multislice_mesh(num_model=config.parallel.num_model)
        else:
            mesh = create_mesh(
                num_data=config.parallel.num_data, num_model=config.parallel.num_model
            )
    num_data = mesh.shape["data"]

    with obs.span("setup/pipeline_start"):
        pipeline = TwoCropPipeline(config.data, mesh, seed=config.seed, dataset=dataset)
    steps_per_epoch = config.steps_per_epoch or pipeline.steps_per_epoch
    if steps_per_epoch <= 0:
        raise ValueError("empty pipeline: fewer examples than one global batch")
    if steps_per_epoch > pipeline.steps_per_epoch:
        # the epoch loop stops when the pipeline runs dry, so a longer
        # override would silently train fewer steps than the LR and
        # momentum schedules were built for
        raise ValueError(
            f"steps_per_epoch={steps_per_epoch} exceeds the "
            f"{pipeline.steps_per_epoch} full batches of "
            f"{config.data.global_batch} the dataset yields per epoch"
        )

    encoder = build_encoder(config.moco, num_data=num_data)
    predictor = build_predictor(config.moco, num_data=num_data)
    tx = build_optimizer(config.optim, steps_per_epoch=steps_per_epoch)
    # the optimizer's schedule evaluated on the host, for the log line
    lr_schedule = make_lr_schedule(config.optim, steps_per_epoch, xp=np)

    rng = jax.random.PRNGKey(config.seed)
    init_rng, shuffle_rng = jax.random.split(rng)
    sample = sample_input(config)
    zero = config.parallel.shard_weight_update
    zero23 = zero_stage23(config)
    with obs.span("setup/state_init"):
        state = create_state(
            init_rng, config, encoder, tx, sample, predictor=predictor,
            zero_num_data=num_data if zero else None,
        )

    # Checkpoint ids are the GLOBAL STEP (unique and monotonic even for
    # mid-epoch preemption saves); the epoch lives in extras. Save
    # frequency is gated here in the driver, not by Orbax's policy.
    with obs.span("setup/checkpoint"):
        ckpt = CheckpointManager(
            config.workdir, keep=config.checkpoint_keep, save_interval=1,
            async_save=config.checkpoint_async,
        )
        resuming = ckpt.latest_step() is not None

    def emergency_save(s, completed_epoch: int, reason: str, extra_fields=None) -> None:
        """The shared save-first-die-second path: the watchdog stall,
        the fatal-alert abort, the graceful-preemption (SIGTERM) exit,
        and the elastic rescale all funnel through here — one durable
        mid-epoch checkpoint with the standard resume extras plus the
        exit reason. Skips (not re-saves) a step that is already
        durable; always blocks until the write lands."""
        if int(s.step) in ckpt.all_steps():
            print(
                f"{reason}: step {int(s.step)} already durable, "
                "skipping emergency save", flush=True,
            )
            return
        extra = {
            "epoch": completed_epoch,
            "config": config_to_dict(config),
            "num_data": num_data,
            "emergency": True,
            "reason": reason,
        }
        if extra_fields:
            extra.update(extra_fields)
        ckpt.save(int(s.step), s, extra=extra, force=True)
        ckpt.wait()

    start_epoch = 0
    if resuming:  # --resume semantics, automatic

        def _check_compat(extra: dict) -> None:
            # fail fast with a readable diff BEFORE the state restore: a
            # shape-mismatched restore would otherwise read as corruption
            # (and quarantine a perfectly good checkpoint)
            diffs = resume_compat_diff(extra, config, num_data)
            if diffs:
                raise ResumeCompatError(
                    f"checkpoint under {config.workdir} is incompatible with the "
                    "live config:\n  " + "\n  ".join(diffs)
                )

        # Layout-aware restore: the ZeRO layout fields
        # (shard_weight_update / zero_stage / the ZeRO mesh width) are
        # "compatible but resharded", not incompatibilities — a
        # checkpoint in a different layout restores into a template of
        # ITS OWN layout, then converts host-side (reshard_state).
        def _layout(z, stage, n):
            return (bool(z), bool(z) and int(stage) >= 2, int(n) if z else 0)

        saved_extra = ckpt.read_extra()
        saved_par = (saved_extra.get("config") or {}).get("parallel") or {}
        saved_zero = bool(saved_par.get("shard_weight_update", zero))
        # pre-zero_stage checkpoints with a recorded config were stage-1
        # by definition; a checkpoint with NO recorded config at all is
        # assumed to match the live layout (the old behavior)
        saved_stage = int(
            saved_par.get(
                "zero_stage",
                1 if "shard_weight_update" in saved_par else config.parallel.zero_stage,
            )
        )
        saved_n = int(saved_extra.get("num_data") or num_data)
        live_layout = _layout(zero, config.parallel.zero_stage, num_data)
        saved_layout = _layout(saved_zero, saved_stage, saved_n)
        if saved_layout != live_layout:
            saved_cfg = dataclasses.replace(
                config,
                parallel=dataclasses.replace(
                    config.parallel,
                    shard_weight_update=saved_zero,
                    zero_stage=saved_stage,
                ),
            )
            with obs.span("setup/state_init"):
                saved_template = create_state(  # mocolint: disable=JX003  (restore TEMPLATE: values are overwritten by the checkpoint read, only shapes matter — key reuse is deliberate)
                    init_rng, saved_cfg, encoder, tx, sample, predictor=predictor,
                    zero_num_data=saved_n if saved_zero else None,
                )
            with obs.span("setup/checkpoint"):
                restored, extra = ckpt.restore(saved_template, validate_extra=_check_compat)
            full_cfg = dataclasses.replace(
                config,
                parallel=dataclasses.replace(
                    config.parallel, shard_weight_update=False
                ),
            )
            with obs.span("setup/state_init"):
                full_template = create_state(  # mocolint: disable=JX003  (shape-only template for reshard_state — deliberate key reuse, values never train)
                    init_rng, full_cfg, encoder, tx, sample, predictor=predictor
                )
            state = reshard_state(restored, state, full_template)
            print0(
                "resume reshard: checkpoint ZeRO layout "
                f"{saved_layout} -> live {live_layout}"
            )
        else:
            # a corrupt newest checkpoint is quarantined and the next-older
            # step restores instead (fault-tolerance layer)
            with obs.span("setup/checkpoint"):
                state, extra = ckpt.restore(state, validate_extra=_check_compat)
        start_epoch = int(extra.get("epoch", 0)) + 1
        print0(f"resumed from epoch {start_epoch - 1} (step {int(state.step)})")

    shard_q = config.parallel.num_model > 1 and config.moco.num_negatives > 0
    state = place_state(
        state, mesh, shard_queue_over_model=shard_q, zero=zero, zero_params=zero23
    )
    root_rng = jax.device_put(
        shuffle_rng, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )
    # Analytic at-rest state footprint per device (constant for the run:
    # layout is static) — the ZeRO stages' memory A/B gauge, available
    # on every backend including CPU meshes where memory_stats is not.
    hbm_state_bytes = tree_shard_bytes(state)
    # A state the device cannot hold twice beside a step's temporaries is
    # donated to the step, and the driver keeps no second reference to it
    # (no rollback copy for the NaN guard: a non-finite loss then aborts,
    # and the last checkpoint is the way back).
    single_copy = state_needs_single_copy(hbm_state_bytes)
    step_fn = make_train_step(
        config,
        encoder,
        tx,
        mesh,
        shard_queue_over_model=shard_q,
        donate=single_copy,
        predictor=predictor,
        total_steps=config.optim.epochs * steps_per_epoch,
        state_template=state if zero else None,
    )
    # Analytic PEAK model-param footprint per device (shards + the
    # transient gathered full params): whole-tree for plain zero23, the
    # largest adjacent group pair under layer-granular gathering — the
    # gauge that proves the per-layer schedule's memory claim on hosts
    # without memory_stats. None outside zero23.
    hbm_model_peak_bytes = getattr(step_fn, "hbm_model_peak_bytes", None)
    zero_layer = zero23 and config.parallel.zero_layer_granular

    # Strict tracing (mocolint runtime arm): tracer-leak checking plus a
    # compile-cache-miss counter over the jitted step, read only on log
    # steps. The guard turns a silent recompile loop (minutes per compile
    # on TPU) into a fast, diagnosable abort.
    compile_monitor = None
    recompile_guard = None
    if config.strict_tracing:
        from moco_tpu.analysis.runtime import (
            CompileMonitor,
            RecompileError,
            RecompileGuard,
            enable_strict_tracing,
        )

        enable_strict_tracing()
        compile_monitor = CompileMonitor(step_fn)
        recompile_guard = RecompileGuard(config.recompile_warmup_steps)

    # Collective-schedule sanitizer (mocolint runtime arm, analysis/
    # sanitizer.py): installed BEFORE the first step traces so every
    # comms.tag site lands in the recorder; the cross-process check
    # piggybacks on log steps and aborts with a per-site diff before a
    # schedule mismatch can deadlock the pod.
    schedule_sanitizer = None
    _prev_recorder = None
    if config.sanitize_collectives:
        from moco_tpu.analysis.sanitizer import ScheduleSanitizer, install_recorder

        schedule_sanitizer = ScheduleSanitizer(
            config.workdir,
            process_index=jax.process_index(),
            num_processes=jax.process_count(),
        )
        _prev_recorder = install_recorder(schedule_sanitizer.recorder)

    # Lock-order sanitizer (mocolint v3 runtime arm, analysis/tsan.py):
    # every tsan-factory lock reports acquisition order; a cycle aborts
    # with both stacks (strict — the ScheduleDivergenceError posture)
    # before a lock inversion can wedge the process, and the run report
    # (lock_order.json) lands next to the schedule files on close.
    thread_sanitizer = None
    if config.sanitize_threads:
        from moco_tpu.analysis.tsan import ThreadSanitizer

        thread_sanitizer = ThreadSanitizer(
            workdir=config.workdir, strict=True, profile=True
        )

    # Graceful preemption (TPU VMs are frequently preemptible, typically
    # with a ~30 s SIGTERM grace window): the flag is checked inside the
    # STEP loop, so the save happens within seconds, not at the end of a
    # multi-minute epoch. A second SIGINT raises KeyboardInterrupt so
    # Ctrl-C can always actually stop the process. The reference's
    # failure story is "NCCL hangs, restart by hand with --resume"
    # (SURVEY.md §5.3).
    preempted = {"count": 0}

    def _handle(signum, frame):
        preempted["count"] += 1
        if signum == signal.SIGINT and preempted["count"] > 1:
            raise KeyboardInterrupt
        print0(f"signal {signum}: checkpointing at the next step, then exiting")

    prev_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev_handlers[sig] = signal.signal(sig, _handle)
        except ValueError:  # not the main thread (tests)
            pass

    # kNN monitor setup (config.knn_every_epochs > 0): frozen-backbone
    # weighted kNN as the cheap probe proxy (moco_tpu/knn.py docstring).
    knn_pair = knn_datasets
    if config.knn_every_epochs and knn_pair is None:
        from moco_tpu.data.datasets import build_dataset

        # same cache as the train pipeline: without it every monitor
        # epoch would re-decode the full dataset through the JPEG path
        knn_pair = (
            build_dataset(
                config.data.dataset, config.data.data_dir, config.data.image_size,
                train=True, num_workers=config.data.num_workers,
                cache_dir=config.data.cache_dir,
            ),
            build_dataset(
                config.data.dataset, config.data.data_dir, config.data.image_size,
                train=False, num_workers=config.data.num_workers,
                cache_dir=config.data.cache_dir,
            ),
        )

    # num_classes once at setup: every in-repo dataset exposes it; for a
    # foreign injected dataset prefer a decode-free label source and only
    # as a last resort scan ALL labels via load() (a first-N scan would
    # under-count on class-sorted layouts like ImageFolder and silently
    # zero out the one_hot votes for the missed classes).
    knn_num_classes = None
    if config.knn_every_epochs and knn_pair is not None:
        bank = knn_pair[0]
        knn_num_classes = getattr(bank, "num_classes", None)
        if knn_num_classes is None:
            labels = getattr(bank, "labels", None)
            if labels is None and getattr(bank, "samples", None) is not None:
                labels = [l for _, l in bank.samples]
            if labels is None:
                labels = [bank.load(i)[1] for i in range(len(bank))]
            knn_num_classes = int(np.max(np.asarray(labels)) + 1)

    def run_knn(epoch: int) -> Optional[float]:
        if not (config.knn_every_epochs and knn_pair):
            return None
        last = epoch == config.optim.epochs - 1
        if epoch % config.knn_every_epochs and not last:
            return None
        from moco_tpu.knn import knn_eval

        bank, test = knn_pair
        num_classes = knn_num_classes
        # ZeRO-2/3: params persist as (n, m) shards — one-shot host
        # gather back to full shapes for the eval-side forward
        params_q = state.params_q
        if zero23:
            params_q = unshard_tree_host(params_q, step_fn.param_shapes["enc"])
        top1 = knn_eval(
            encoder.backbone,
            params_q["backbone"],
            state.batch_stats_q.get("backbone", {}),
            bank,
            test,
            num_classes=num_classes,
            k=min(config.knn_k, len(bank)),
            temperature=config.knn_temperature,
            image_size=config.data.image_size,
            mesh=mesh,  # extraction data-parallel over the mesh
        )
        print0(f"Epoch [{epoch}] kNN top-1: {top1:.2f}%")
        return top1

    # Sink fan-out (obs/sinks.py): metrics.jsonl always (primary; file
    # sinks get per-process names when processes share a workdir), plus
    # whatever config.sinks names; metrics_port>0 additionally serves
    # Prometheus text format on /metrics for scraping long runs (port
    # shifted by the process index so co-hosted processes don't collide).
    pidx = jax.process_index()
    writer = build_sinks(
        config.sinks,
        config.workdir,
        metrics_port=config.metrics_port,
        metrics_host=config.metrics_host,
        process_index=pidx,
    )
    if writer.prometheus is not None:
        # the ACTUAL bound address (derived port, configured host), not
        # the requested one — what a scraper must be pointed at
        print(
            f"[p{pidx}] metrics endpoint: "
            f"http://{writer.prometheus.host}:{writer.prometheus.port}/metrics",
            flush=True,
        )
    # Fleet observability (obs/fleet.py): per-host stats vector gathered
    # across processes on log steps (jitted all_gather over a one-device-
    # per-host mesh); process 0's lines carry the fleet reduction. The
    # heartbeat file is the out-of-band liveness signal obs_report and
    # trace_merge fall back to when a host dies mid-run. The comms
    # ledger is reset here so this run's metrics reflect this run's
    # traced collectives only.
    comms.reset()
    # ZeRO-2/3: hoist the bucketed params all_gather for step k+1 under
    # step k — the driver enqueues it right after step k's dispatch
    # (async; dispatch must stay on THIS thread, see AsyncParamGather's
    # concurrent-Execute deadlock note) and the worker absorbs
    # gather-side stalls; the overlap/zero gauge on every metrics line
    # is the proof. zero_overlap_gather=False keeps the inline schedule.
    # This initial submit TRACES the gather, so it must come AFTER the
    # comms.reset() above or the per-bucket ledger sites would be wiped
    # (tags fire at trace time only).
    gatherer: Optional[AsyncParamGather] = None
    if zero23 and config.parallel.zero_overlap_gather:
        gatherer = AsyncParamGather(step_fn.gather)
        gatherer.submit(state, int(state.step))
    fleet = FleetAggregator() if config.fleet_metrics else None
    heartbeat = Heartbeat(
        config.workdir, process_index=pidx,
        trace_wall_t0=getattr(obs.get_tracer(), "wall_t0", None),
    )
    heartbeat.beat(step=int(state.step), epoch=start_epoch)
    # Alerting engine (obs/alerts.py): declarative rules evaluated
    # against every logged payload; fired alerts land in alerts.jsonl +
    # an in-band event line (Prometheus per-rule gauge rides it).
    engine = (
        AlertEngine(
            parse_rules(config.alert_rules, heartbeat_timeout=config.heartbeat_timeout),
            workdir=config.workdir,
            process_index=pidx,
        )
        if config.alert_rules and config.alert_rules != "none"
        else None
    )
    # Elastic loop trigger (parallel/elastic.py): heartbeat-staleness
    # detection + the rescale-consensus barrier, checked on log steps.
    # Already-rescaled-away hosts are known_dead — their stale files
    # stay in the workdir (obs_report's merged heartbeat table names
    # them) and must not re-trigger.
    elastic_coord: Optional[ElasticCoordinator] = None
    if config.elastic:
        elastic_coord = ElasticCoordinator(
            config.workdir,
            process_index=pidx,
            num_processes=jax.process_count(),
            timeout=config.heartbeat_timeout,
            known_dead=dead_hosts,
        )

    def handle_alerts(gstep: int, epoch: int, fired: list) -> None:
        """Write in-band alert event lines; under --alerts-fatal, make
        an emergency checkpoint durable and abort."""
        if not fired:
            return
        for a in fired:
            print0(
                f"ALERT [{a['severity']}] {a['rule']} @ step {gstep}: {a['message']}",
                flush=True,
            )
            writer.write(
                gstep,
                {"epoch": epoch, "event": "alert", "alert": a["rule"],
                 "severity": a["severity"], f"alert/{a['rule']}": 1},
            )
        writer.fsync()
        if config.alerts_fatal:
            # with elastic on, heartbeat loss is HANDLED (checkpoint +
            # rescale), not fatal: the abort would preempt the rescale
            # the same observation is about to trigger
            fatal = [
                a for a in fired
                if not (config.elastic and a.get("kind") == "heartbeat")
            ]
            if not fatal:
                return
            # emergency checkpoint of the last known-finite state (the
            # fault-tolerance layer's save-first-die-second path)
            emergency_save(
                # (the live state where no rollback copy is kept)
                state if single_copy else guard["good_state"],
                epoch - 1,  # mid-epoch semantics (see watchdog)
                "alert", {"alert": fatal[0]["rule"]},
            )
            raise FatalAlertError(
                f"aborting on fired alert(s) {[a['rule'] for a in fatal]} at step "
                f"{gstep} (--alerts-fatal); emergency checkpoint saved — see "
                f"{engine.path} and {writer.path}"
            )

    def elastic_rescale(gstep: int, epoch: int, dead_now: list) -> None:
        """The elastic loop's commit point: agree on the event with the
        surviving peers, make the emergency checkpoint durable, emit the
        schema'd rescale event line, then raise ElasticRescale for the
        outer loop to rebuild the world on the surviving mesh."""
        all_dead = sorted(set(dead_hosts) | set(dead_now))
        plan, new_ref, info = plan_rescale(
            ref_config, num_data, config.parallel.num_model, all_dead, gstep
        )
        print0(
            f"elastic: hosts {dead_now} lost heartbeat (> "
            f"{config.heartbeat_timeout:g}s stale) at step {gstep}; proposing "
            f"mesh {plan.old_num_data} -> {plan.new_num_data}"
        )
        plan = elastic_coord.agree(plan)
        rescale_extra = {**plan.consensus_key(), "step": plan.step}
        for k in ("kappa", "lr", "momentum"):
            if k in info:
                rescale_extra[k] = float(info[k])
        emergency_save(
            state if single_copy else guard["good_state"],
            epoch - 1,  # mid-epoch: redo this epoch
            "rescale", {"rescale": rescale_extra},
        )
        line = {
            "epoch": epoch,
            "event": "rescale",
            "rescale/dead_hosts": list(plan.dead_hosts),
            "rescale/old_num_data": plan.old_num_data,
            "rescale/new_num_data": plan.new_num_data,
            "rescale/old_global_batch": plan.old_global_batch,
            "rescale/new_global_batch": plan.new_global_batch,
        }
        for k in ("kappa", "lr", "momentum"):
            if k in info:
                line[f"rescale/{k}"] = float(info[k])
        writer.write(gstep, line)
        writer.fsync()  # the rescale must leave its event on disk
        raise ElasticRescale(plan, new_ref, info)
    # Step-time breakdown probe + windowed profiler (obs/stepstats.py,
    # utils/metrics.py): both keyed on the host-side global step counter.
    probe = StepTimeProbe(config.obs_probe_every)
    profile_window: Optional[ProfilerWindow] = None
    if profile_steps is not None:
        profile_window = ProfilerWindow(
            profile_dir or os.path.join(config.workdir, "profile"), *profile_steps
        )
        profile_dir = None  # windowed capture replaces the whole-run trace
    last_avg: dict = {}

    # -- runtime guards (fault-tolerance layer) --------------------------
    # `good_state` is the last state whose loss was observed finite (one
    # extra on-device state reference; refreshed on log steps only). The
    # NaN guard rolls back to it, and the watchdog's emergency save uses
    # it — a wedged device can't be asked for the in-flight state.
    guard = {
        "nan_steps": 0, "good_state": None if single_copy else state, "epoch": start_epoch,
    }
    wd: Optional[StepWatchdog] = None
    if config.watchdog_timeout > 0:

        def _emergency():
            # best-effort, bounded: the main thread is stuck in a device
            # call, and the save itself may hang on a wedged runtime — run
            # it in a sidecar thread and exit regardless after the budget.
            try:
                writer.write(
                    0, {"event": "stall", "epoch": guard["epoch"],
                        "watchdog_timeout": config.watchdog_timeout},
                )
                writer.fsync()
            except Exception:
                pass

            def _save():
                if single_copy:
                    # the only copy is the step's own input or output, on a
                    # device that no longer answers
                    print("watchdog: no rollback copy of the state; nothing saved", flush=True)
                    return
                try:
                    # mid-epoch semantics, like the preemption path: the
                    # current epoch is NOT complete, resume redoes it
                    # from the start
                    emergency_save(guard["good_state"], guard["epoch"] - 1, "stall")
                    print("watchdog: emergency checkpoint saved", flush=True)
                except Exception as e:
                    print(f"watchdog: emergency checkpoint failed: {e!r}", flush=True)

            t = threading.Thread(target=_save, daemon=True)
            t.start()
            t.join(timeout=max(30.0, config.watchdog_timeout))

        wd = StepWatchdog(
            config.watchdog_timeout,
            on_stall=_emergency,
            dump_path=os.path.join(config.workdir, "stall_stacks.txt"),
        ).start()

    # Host-side mirror of the global step (one sync here, none per
    # step): drives the profiler window, the probe's sampling schedule,
    # and the log lines — step_fn advances state.step once per dispatch
    # (even on NaN rollback), so the mirror never drifts.
    gstep_host = int(state.step)
    # Software-pipelined step loop (ISSUE 5 tentpole): step k is
    # dispatched against an already device-resident batch while the
    # prefetch ring transfers k+1 and the host decodes k+2. Two loop
    # mechanics make the overlap real:
    # - bounded in-flight window: after each dispatch the loop blocks on
    #   the metrics of the step `prefetch_depth` dispatches BACK (ready
    #   or nearly so in steady state) — backpressure without ever
    #   draining the device queue;
    # - deferred log fetch: a log step's device_get runs one iteration
    #   LATER, after the next step is already queued behind it, so a log
    #   boundary no longer idles the device. Consequence: a non-finite
    #   loss is detected one step late and the rollback also discards
    #   the single in-flight update computed from the poisoned state —
    #   same counters, one extra discarded step.
    pipeline_depth = max(int(config.prefetch_depth), 1)
    try:
        with profiler_trace(profile_dir):
            for epoch in range(start_epoch, config.optim.epochs):
              with obs.span("epoch", epoch=epoch):
                batch_time = AverageMeter("Time", ":6.3f")
                data_time = AverageMeter("Data", ":6.3f")
                losses = AverageMeter("Loss", ":.4e")
                top1 = AverageMeter("Acc@1", ":6.2f")
                top5 = AverageMeter("Acc@5", ":6.2f")
                progress = ProgressMeter(
                    steps_per_epoch,
                    [batch_time, data_time, losses, top1, top5],
                    prefix=f"Epoch: [{epoch}]",
                )
                guard["epoch"] = epoch
                it = iter(pipeline.epoch(
                    epoch,
                    device=config.device_prefetch,
                    depth=config.prefetch_depth,
                    donate=config.prefetch_donate,
                ))
                ring_stats = getattr(it, "stats_payload", None)
                # wall anchor for the smoothed per-step time: t_step on a
                # logged line is (wall since the previous logged flush) /
                # (steps since it) — the sustained rate, which under the
                # pipelined loop is the meaningful number (per-iteration
                # host wall is just dispatch, ~ms)
                flush_anchor = {"wall": time.perf_counter(), "gstep": gstep_host}
                phase_anchor = {"totals": tracer.totals(), "gstep": gstep_host}
                stop_now = False
                pending: Optional[dict] = None
                inflight: deque = deque()

                def flush_log(p: dict) -> None:
                    """Deferred log-step processing: ONE batched
                    device_get for the whole metrics tree (the old
                    per-field float() forced a blocking transfer per
                    metric), then every runtime guard piggybacks on the
                    fetch — NaN guard, chaos hooks, alert engine,
                    recompile guard, fleet gather, heartbeat.

                    The rule: in here the host waits only for device
                    work dispatched BEFORE the newest step — that fetch,
                    with the next step already queued behind it. Nothing
                    here dispatches a device program and then reads it:
                    the read would wait for the steps in flight and hand
                    the next dispatch an empty queue (the learning rate
                    and the one-process fleet reduce are host numpy)."""
                    nonlocal state
                    i, gstep = p["i"], p["gstep"]
                    # taken before this flush does anything: the account
                    # on this line then holds whole spans only, the last
                    # flush (with its own fetch) and every step since it
                    phase_now = tracer.totals()
                    with obs.span("metrics_fetch", step=gstep):
                        fetched = jax.device_get(p["metrics"])
                    m = {
                        k: (float(v) if getattr(v, "ndim", 1) == 0 else v)
                        for k, v in fetched.items()
                    }
                    if faults.enabled():  # chaos harness hooks
                        m["loss"] = faults.corrupt_loss(m["loss"], gstep)
                        faults.maybe_stall(gstep)
                        faults.maybe_preempt(gstep)
                        # kill@host: sudden host death (exit in a real
                        # fleet; a stale simulated heartbeat on the
                        # fake-fleet mesh — the elastic chaos harness)
                        faults.maybe_kill_host(
                            gstep, config.workdir, pidx, jax.process_count()
                        )
                    if not math.isfinite(m["loss"]):
                        # non-finite-loss guard: skip the poisoned
                        # update (params/opt/queue roll back to the
                        # last finite log step; the step counter keeps
                        # advancing so checkpoint ids stay monotonic
                        # and fault-free/faulted runs agree on step
                        # counts), count it, abort past the threshold.
                        guard["nan_steps"] += 1
                        writer.write(
                            gstep,
                            {"epoch": epoch, "event": "nonfinite_loss",
                             "nan_steps": guard["nan_steps"]},
                        )
                        writer.fsync()
                        if engine is not None:
                            handle_alerts(
                                gstep, epoch,
                                engine.observe(
                                    gstep,
                                    {"event": "nonfinite_loss",
                                     "nan_steps": guard["nan_steps"]},
                                ),
                            )
                        print0(
                            f"WARNING: non-finite loss at step {gstep} "
                            f"({guard['nan_steps']}/{config.nan_guard_threshold})"
                            " — update skipped",
                            flush=True,
                        )
                        if single_copy or guard["nan_steps"] >= config.nan_guard_threshold:
                            raise FloatingPointError(
                                f"aborting: {guard['nan_steps']} non-finite "
                                f"loss steps (threshold "
                                f"{config.nan_guard_threshold}); last at step "
                                f"{gstep}, epoch {epoch}, lr "
                                f"{float(lr_schedule(gstep - 1)):.3e} — see "
                                f"{writer.path}"
                            )
                        state = guard["good_state"].replace(step=state.step)
                        inflight.clear()  # poisoned-lineage refs: drop them
                        if gatherer is not None:
                            # the in-flight gather belongs to the poisoned
                            # lineage — drop it and gather the rolled-back
                            # shards instead
                            gatherer.resubmit(state, gstep)
                        return
                    # p["state"] is the state AS OF this logged step —
                    # `state` itself may already be one dispatch ahead
                    # (None where no rollback copy is kept)
                    guard["good_state"] = p["state"]
                    bs = config.data.global_batch
                    losses.update(m["loss"], bs)
                    top1.update(m["acc1"], bs)
                    top5.update(m["acc5"], bs)
                    now = time.perf_counter()
                    steps_since = max(gstep - flush_anchor["gstep"], 1)
                    t_step = (now - flush_anchor["wall"]) / steps_since
                    flush_anchor["wall"], flush_anchor["gstep"] = now, gstep
                    batch_time.update(t_step)
                    # re-pin the probe to THIS step's data wait: the next
                    # iteration's fetch already overwrote it before this
                    # deferred flush ran
                    probe.data_wait(p["t_data"])
                    probe.step_done(t_step)
                    progress.display(i)
                    wire = ring_stats() if ring_stats is not None else {}
                    payload = {
                        "epoch": epoch,
                        "lr": float(lr_schedule(gstep - 1)),
                        **m,
                        # step-time breakdown + device memory
                        # (obs): t_data/t_step always; dispatch/
                        # device split from the latest sampled
                        # step; hbm gauges null where the backend
                        # lacks memory_stats (CPU hosts)
                        **probe.payload(),
                        # the host's phase account: per-step mean seconds
                        # of every driver and ring span since the last
                        # line, every step counted
                        **phase_account(
                            phase_now, phase_anchor["totals"],
                            gstep - phase_anchor["gstep"],
                        ),
                        **memory_payload(),
                        # at-rest state footprint (analytic, per device)
                        "hbm_state_bytes": hbm_state_bytes,
                        # input wire (device prefetch ring): last
                        # batch's transfer time/bytes + live staged
                        # depth — absent on the sync path
                        **wire,
                        # ZeRO-2/3 hoisted-gather overlap efficiency —
                        # absent without the gather worker
                        **(gatherer.payload() if gatherer is not None else {}),
                        # layer-granular stage: mirror the gauge under its
                        # own key so dashboards can tell the per-group
                        # schedule apart from whole-tree gathering, and
                        # publish the analytic peak model footprint
                        **(
                            {"overlap/zero_layer": gatherer.last_overlap}
                            if zero_layer and gatherer is not None
                            else {}
                        ),
                        **(
                            {"hbm_model_peak_bytes": hbm_model_peak_bytes}
                            if hbm_model_peak_bytes is not None
                            else {}
                        ),
                    }
                    phase_anchor["totals"], phase_anchor["gstep"] = phase_now, gstep
                    # fault-tolerance observability: only present
                    # when nonzero, so clean runs keep clean lines
                    if guard["nan_steps"]:
                        payload["nan_steps"] = guard["nan_steps"]
                    decode_failures = getattr(pipeline, "decode_failures", 0)
                    if decode_failures:
                        payload["decode_failures"] = decode_failures
                    io_retries = retry.snapshot()
                    if io_retries:
                        payload["io_retries"] = io_retries
                    if compile_monitor is not None:
                        # always present under --strict-tracing
                        # (not only-when-nonzero like the fault
                        # counters): dashboards watch it for
                        # FLATNESS, and absence would read as 0
                        misses = compile_monitor.misses()
                        payload["compile_cache_misses"] = misses
                    # comms ledger: analytic per-step wire bytes
                    # for every collective the step traced
                    # (obs/comms.py) — static values, no syncs
                    payload.update(comms.payload())
                    if schedule_sanitizer is not None:
                        # schedule hash on every line: dashboards watch
                        # it for FLATNESS (like compile_cache_misses)
                        payload.update(schedule_sanitizer.recorder.payload())
                    if fleet is not None:
                        # cross-host aggregation: EVERY process
                        # contributes its vector (this is a
                        # collective, keyed on the replicated
                        # log schedule so all hosts agree);
                        # process 0's line carries the fleet view
                        stats = fleet.gather(
                            fleet.host_vector(
                                t_data=payload.get("t_data"),
                                t_step=payload.get("t_step"),
                                t_transfer=wire.get("t_transfer"),
                                dispatch_lag=probe.last_dispatch,
                                io_retries=float(
                                    sum(io_retries.values())
                                ) if io_retries else 0.0,
                                decode_failures=float(decode_failures),
                                hbm_live=payload.get("hbm_live_bytes"),
                            )
                        )
                        if fleet.process_index == 0:
                            payload.update(fleet.payload(stats))
                    heartbeat.beat(step=gstep, epoch=epoch)
                    writer.write(gstep, payload)
                    if engine is not None:
                        handle_alerts(
                            gstep, epoch, engine.observe(gstep, payload)
                        )
                    if elastic_coord is not None:
                        # heartbeat-staleness check (off the hot path:
                        # log steps only, file reads). A newly lost host
                        # commits the rescale: consensus -> emergency
                        # checkpoint -> event line -> ElasticRescale.
                        dead_now = elastic_coord.stale_hosts()
                        if dead_now:
                            elastic_rescale(gstep, epoch, dead_now)
                    if schedule_sanitizer is not None:
                        # publish + cross-check AFTER the line is
                        # durable: a divergence abort must leave the
                        # metrics tail (and the hash) on disk
                        writer.fsync()
                        schedule_sanitizer.check(gstep)
                    if recompile_guard is not None:
                        diagnosis = recompile_guard.update(gstep, misses)
                        if diagnosis is not None:
                            writer.write(
                                gstep,
                                {"epoch": epoch,
                                 "event": "recompile_after_warmup",
                                 "compile_cache_misses": misses},
                            )
                            writer.fsync()
                            raise RecompileError(diagnosis)

                try:
                    for i in range(steps_per_epoch):
                        if profile_window is not None:
                            profile_window.on_step(gstep_host)
                        # one number for the host spans and the device
                        # programs of this step in a profiler trace
                        step_scope = jax.profiler.StepTraceAnnotation(
                            "moco/train_step", step_num=gstep_host
                        )
                        with step_scope:
                            with setup_span("setup/pipeline_start"):
                                with obs.span("data_wait", step=gstep_host) as waited:
                                    batch = next(it, None)
                            if batch is None:
                                break
                            t_data = waited.seconds
                            data_time.update(t_data)
                            probe.data_wait(t_data)
                            with setup_span("setup/first_step"):
                                with obs.span("step", step=gstep_host) as dispatched:
                                    if gatherer is not None:
                                        # the gather for THIS step was issued one
                                        # iteration ago and ran under the previous
                                        # step; take() blocks only for what didn't
                                        # fit under it (the overlap/zero gauge)
                                        gathered = gatherer.take()
                                        state, metrics = step_fn.step(
                                            state, gathered, batch, root_rng
                                        )
                                        gatherer.submit(state, gstep_host + 1)
                                    else:
                                        state, metrics = step_fn(state, batch, root_rng)
                                probe.dispatched(dispatched.seconds)
                                if setup_open or probe.should_sample(gstep_host):
                                    # drain the device queue ON SAMPLED STEPS ONLY,
                                    # splitting host dispatch from device compute —
                                    # every other step stays sync-free. The process's
                                    # first step compiles or loads the program: set-up
                                    # ends when its outputs are ready, and the probe
                                    # is not fed a compile as a step time.
                                    with obs.span("device_wait", step=gstep_host) as drained:
                                        jax.block_until_ready((state, metrics))
                                    if not setup_open:
                                        probe.device_block(drained.seconds, gstep_host + 1)
                            gstep_host += 1
                            if setup_open:
                                setup_open = False
                                writer.write(gstep_host, {
                                    "epoch": epoch,
                                    "event": "setup",
                                    **setup_account(tracer.totals(), setup_base),
                                })
                            # bounded in-flight window: wait on the OLDEST
                            # dispatched step only — `pipeline_depth` newer
                            # steps stay queued on the device
                            inflight.append(metrics)
                            if len(inflight) > pipeline_depth:
                                with obs.span("throttle_wait", step=gstep_host):
                                    jax.block_until_ready(inflight.popleft())
                            if wd is not None:
                                wd.beat()  # a timestamp assignment — no device sync
                            if pending is not None:
                                # the previous log step's metrics, fetched
                                # with this step already queued behind them
                                with obs.span("log_flush", step=pending["gstep"]):
                                    flush_log(pending)
                                pending = None
                            if preempted["count"]:
                                stop_now = True
                                break
                            if i % config.log_every == 0 or i == steps_per_epoch - 1:
                                pending = {
                                    "i": i, "gstep": gstep_host,
                                    "metrics": metrics,
                                    "state": None if single_copy else state,
                                    "t_data": t_data,
                                }
                    if pending is not None and not stop_now:
                        # the epoch's final log step has no successor
                        # iteration — flush it here
                        with obs.span("log_flush", step=pending["gstep"]):
                            flush_log(pending)
                        pending = None
                finally:
                    # epoch teardown / preemption exit: release the
                    # prefetch producer + transfer ring (the PR-5
                    # producer-leak fix — an abandoned iterator used to
                    # block its daemon thread on q.put forever, pinning
                    # the decode pool)
                    closer = getattr(it, "close", None)
                    if closer is not None:
                        closer()
                last_avg = {
                    "epoch": epoch,
                    "loss": losses.avg,
                    "acc1": top1.avg,
                    "acc5": top5.avg,
                }
                if not stop_now:
                    knn_top1 = run_knn(epoch)
                    if knn_top1 is not None:
                        last_avg["knn_top1"] = knn_top1
                        writer.write(int(state.step), {"epoch": epoch, "knn_top1": knn_top1})
                # A mid-epoch preemption save records the PREVIOUS epoch
                # as completed, so resume redoes this partial epoch from
                # its start (same granularity the reference's per-epoch
                # checkpoints give a crash, but without losing the work
                # to a SIGKILL: the save happens within one step of the
                # signal, inside a preemption grace window).
                completed_epoch = epoch - 1 if stop_now else epoch
                if stop_now:
                    # Graceful preemption (SIGTERM — how preemptible VMs
                    # announce reclamation — or Ctrl-C): the same
                    # emergency-checkpoint path as the watchdog/alert/
                    # rescale exits (save first, durable before exit),
                    # plus an in-band event line naming the exit.
                    writer.write(gstep_host, {"epoch": epoch, "event": "preempt"})
                    emergency_save(state, completed_epoch, "preempt")
                    writer.fsync()  # the metrics tail must be durable too
                    print0(
                        f"preempted mid-epoch {epoch}: state saved at step "
                        f"{int(state.step)}; resume will redo epoch {epoch}"
                    )
                    break
                if (
                    epoch == config.optim.epochs - 1
                    or epoch % config.checkpoint_every_epochs == 0
                ):
                    ckpt.save(
                        int(state.step),
                        state,
                        extra={
                            "epoch": completed_epoch,
                            "config": config_to_dict(config),
                            # ZeRO opt-state leaves are (num_data, m):
                            # downstream template builders (lincls,
                            # convert_pretrain) need the TRAIN-time mesh
                            # width, which config alone may not pin
                            # (parallel.num_data=None = "all devices")
                            "num_data": num_data,
                        },
                    )
    finally:
        if gatherer is not None:
            gatherer.close()  # join the gather worker; drop a parked result
        if schedule_sanitizer is not None:
            from moco_tpu.analysis.sanitizer import install_recorder

            install_recorder(_prev_recorder)
        if thread_sanitizer is not None:
            thread_sanitizer.close()  # restores hooks, writes lock_order.json
        if profile_window is not None:
            profile_window.close()  # stop a still-open capture window
        if wd is not None:
            wd.stop()
        if engine is not None:
            engine.close()
        writer.close()
        ckpt.close()
        for sig, h in prev_handlers.items():
            signal.signal(sig, h)
    return last_avg
