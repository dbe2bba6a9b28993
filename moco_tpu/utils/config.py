"""Dataclass config system.

Replaces the reference's argparse blocks duplicated across
`main_moco.py:~L30-100` and `main_lincls.py:~L30-95`. Field names and
defaults mirror the reference flags (`--moco-dim 128 --moco-k 65536
--moco-m 0.999 --moco-t 0.07`, `--lr 0.03`, `--schedule 120 160`, v2
switches `--mlp --aug-plus --cos --moco-t 0.2`). Presets correspond to
BASELINE.json's config list.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class MocoConfig:
    arch: str = "resnet50"
    dim: int = 128  # --moco-dim
    num_negatives: int = 65536  # --moco-k
    momentum: float = 0.999  # --moco-m
    # Cosine-anneal the EMA momentum from `momentum` to 1.0 over training
    # (moco-v3's --moco-m-cos; the EMA-scaling literature's recipe).
    momentum_cos: bool = False
    temperature: float = 0.07  # --moco-t (0.2 for v2 recipe)
    mlp: bool = False  # --mlp (v2)
    # BN decorrelation strategy: 'gather_perm' (reference-exact Shuffle-BN),
    # 'a2a' (balanced all_to_all permutation), 'syncbn' (subgroup cross-replica BN, no shuffle),
    # 'none' (single-device / ablation).
    shuffle: str = "gather_perm"
    syncbn_group_size: int = 0  # 0 = whole data axis, else subgroups of this size
    # Training BN statistics from the first N rows of each device's
    # batch (0 = full batch). Byte-reduction lever for the BN-bound step
    # (PROFILE.md: stats reductions are 55% of step time) that matches
    # the reference's statistics granularity — upstream's per-GPU BN
    # estimates from 32 rows (batch 256 / 8 GPUs, main_moco.py:~L172).
    # Interacts with the shuffle gate: a fixed first-N-rows sample makes
    # the BN-statistics leak STRONGER than whole-batch per-device BN, so
    # build_encoder rejects it with shuffle='none' on a multi-device
    # data axis (fine single-device, where it is a pure perf lever).
    bn_stats_rows: int = 0
    # With bn_stats_rows: fusion barrier around the subset slice
    # (BatchNorm.stats_barrier) — numerically identical; candidate
    # workaround for the r50/224 TPU compile pathology (PROFILE.md r4,
    # scripts/bn_compile_repro.py).
    bn_stats_barrier: bool = False
    # Virtual Shuffle-BN on few devices: per-group BN statistics over G
    # contiguous row-groups of each device's batch (the reference's
    # per-GPU BN semantics inside one chip), and the key batch is
    # permuted in-batch even on a single device so group composition
    # decorrelates — a G-GPU recipe on one TPU. 0 = off.
    bn_virtual_groups: int = 0
    # EXPLICIT opt-in for leak-demonstration configs: lets shuffle='none'
    # compose with bn_virtual_groups / bn_stats_rows, which build_encoder
    # otherwise rejects loudly (per-group statistics with UNPERMUTED keys
    # are the exact intra-batch leak Shuffle-BN exists to prevent,
    # `moco/builder.py:~L79-126`). Exists so the BN-cheat positive
    # control (scripts/ablate_shuffle.py arm 'none' with virtual groups
    # on one chip) can reproduce the phenomenon deliberately; never set
    # it in a training recipe.
    allow_leaky_bn: bool = False
    # Momentum-statistics BN ("Momentum² Teacher", arXiv:2101.07525):
    # every training BN normalizes with — and stores — the
    # momentum-updated running statistics m*ra + (1-m)*batch instead of
    # the raw batch statistics, decoupling normalization precision from
    # the per-batch sample. The huge-batch alternative to cross-replica
    # BN statistics (statistics quality comes from history, so nothing
    # needs syncing as the batch grows). ResNet only; mutually
    # exclusive with bn_stats_rows / bn_virtual_groups.
    bn_momentum_stats: bool = False
    # Key-encoder BatchNorm from RUNNING statistics (the EMAN recipe,
    # arXiv:2101.08482, re-derived TPU-first): the key forward runs
    # eval-mode BN against batch_stats_k, which is EMA-updated each
    # step toward the query encoder's RUNNING statistics — the
    # BN-momentum-smoothed buffers, exactly as EMAN tracks buffers,
    # NOT the step's raw batch mean/var — on the params' momentum
    # schedule. Three effects on the HBM-bound step
    # (PROFILE.md: BN statistics reads are 55% of step time, one third
    # of that on the key forward): the key-side statistics pass
    # disappears entirely; the BN-composition leak Shuffle-BN exists to
    # prevent disappears BY CONSTRUCTION (no batch statistics on keys),
    # so the shuffle collectives go too; and multi-chip key forwards
    # need zero communication. Changes training semantics vs the
    # reference recipe — and the measured accuracy arm (REPORT.md
    # "EMAN key forward": 35.6 ± 4.5 vs 53.7 ± 0.6 kNN at the CI
    # budget, likely a stats-EMA warmup artifact at 160 steps but
    # unproven beyond it) keeps this EXPERIMENTAL and default-off.
    # Requires shuffle='none' (or 'syncbn' for the query side); the
    # v2-step lever only (the v3 step has its own momentum encoder).
    key_bn_running_stats: bool = False
    # Fast-tracking warmup for the key-stats EMA (EMAN lever only):
    # stats momentum min(m_params(step), (1+step)/(10+step)) — the
    # classic num_updates moving-average schedule. Addresses the r4
    # accuracy-arm mechanism (at m=0.99 over 160 steps the key BN
    # normalized with ~60-step-stale statistics); at ImageNet scale the
    # schedule converges to the params momentum within one epoch.
    key_bn_stats_warmup: bool = True
    cifar_stem: bool = False
    compute_dtype: str = "bfloat16"
    # MoCo v3 (queue-free symmetric contrastive): set num_negatives=0,
    # v3=True adds the prediction head.
    v3: bool = False
    # v3 stability trick (arXiv:2104.02057 §5): keep the ViT patch-embed
    # projection frozen at its random init.
    freeze_patch_embed: bool = True
    # Override the ViT patch size (None = the arch's default, 16);
    # small-image tests/smoke configs use 4.
    vit_patch_size: Optional[int] = None
    # ViT attention via the Pallas flash kernel (moco_tpu/ops); the
    # parameter tree is identical to the dense path, so checkpoints are
    # interchangeable. Pays off at long sequences (high-res/video).
    vit_flash_attention: bool = False
    # ViT feature pooling: "cls" (v3 default) or "gap" (global average
    # pool — required by sequence parallelism).
    vit_pool: str = "cls"
    # Sequence parallelism for the ViT: shard the token axis over the
    # mesh's MODEL axis and run ring attention across it (long-sequence
    # regime: high-res images / video token counts). Requires v3, gap
    # pooling, and tokens divisible by num_model.
    vit_sequence_parallel: bool = False
    # Streaming pallas InfoNCE (no (B, 1+K) logits materialization):
    # None = auto (on for TPU + replicated tile-divisible queue).
    fused_infonce: Optional[bool] = None
    # Queue tile size streamed through VMEM per grid step; 0 = the
    # kernel's DEFAULT_BLOCK_K. Small values let tests drive the kernel
    # at toy K (the block must divide K).
    fused_block_k: int = 0
    # Rematerialize the query-encoder forward in the backward pass
    # (jax.checkpoint): trades ~30% more FLOPs for O(depth) less
    # activation HBM — for big models / big per-chip batches. A decoder
    # stack recomputes block by block and spares the attention product
    # (models/decoder.py: the kernels' output and log-sum-exp are kept).
    remat: bool = False
    # A decoder stack's cut of a deployment (moco_tpu/models/decoder.py):
    # the layers of this pipeline stage (None = as published), the rows of
    # the vocabulary held here (None = all), and this chip's share of each
    # expert layer as (first_expert, experts_held) (() = every expert).
    # Every width stays the arch's own. `lm_first_layer`: the published
    # index of the stage's first layer (a family whose layers differ by
    # depth builds the stage's own).
    lm_layers: Optional[int] = None
    lm_vocab_rows: Optional[int] = None
    expert_share: Tuple[int, ...] = ()
    lm_first_layer: int = 0


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    optimizer: str = "sgd"  # sgd | lars | adamw
    lr: float = 0.03
    momentum: float = 0.9
    weight_decay: float = 1e-4
    cos: bool = False  # cosine schedule (--cos)
    schedule: Tuple[int, ...] = (120, 160)  # step-decay epochs (--schedule)
    warmup_epochs: int = 0
    epochs: int = 200
    # LARS extras for the pod-scale large-batch config
    trust_coefficient: float = 0.001


@dataclasses.dataclass(frozen=True)
class DataConfig:
    dataset: str = "synthetic"  # synthetic | cifar10 | imagefolder
    data_dir: Optional[str] = None
    image_size: int = 224
    # What a row is: "images" (uint8 pixels, augmented on the device) or
    # "tokens" (`seq_len` int32 ids and a length; the two views are two
    # independent windows of one document, cut on the host, and no
    # augmentation program runs).
    input: str = "images"
    seq_len: int = 0
    global_batch: int = 256
    aug_plus: bool = False  # v2 aug recipe (jitter+blur), main_moco.py:~L225-255
    # Geometric-only two-crop recipe (RRC + flip + normalize): the
    # BN-leak positive control's setting — overrides aug_plus.
    crops_only: bool = False
    num_workers: int = 4
    on_device_augment: bool = True
    # Sample RandomResizedCrop boxes on the HOST against the ORIGINAL
    # image geometry and decode-once/crop-N in the loader (torchvision-
    # exact crop distribution + 224² instead of 256² over PCIe). Applies
    # to datasets exposing the host-crop protocol (imagefolder); others
    # keep the on-device crop from the decode canvas.
    host_rrc: bool = True
    # Decode-once packed RGB cache (moco_tpu/data/cache.py): build on
    # first use under this dir, then epochs read raw full-geometry
    # pixels from an mmap instead of re-decoding JPEGs — the answer to
    # few-core TPU hosts where codec work bounds the input pipeline.
    cache_dir: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    num_data: Optional[int] = None  # None = all devices
    num_model: int = 1  # shards the queue/logits for very large K
    # Sharded weight update (ZeRO over the data axis, arXiv:2004.13336
    # — moco_tpu/parallel/zero.py): optimizer state and update sharded
    # 1/n per replica via psum_scatter + all_gather. Element-wise
    # optimizers only (sgd/adamw).
    shard_weight_update: bool = False
    # ZeRO stage (meaningful with shard_weight_update): 1 = sharded
    # optimizer state only, params re-gathered inside every step (the
    # original). 2/3 (both spellings select the same implementation) =
    # params_q/params_k/predictor ALSO persist between steps as
    # P(data)-sharded flat shards: ~3/n at-rest model memory, the EMA
    # key update runs shard-local (no collective), and the per-bucket
    # params all_gather for step k+1 is hoisted under step k's compute
    # by the pipelined driver (parallel/zero.py module docstring).
    zero_stage: int = 1
    # Fusion-bucket size for the stage-2/3 bucketed collectives: leaves
    # pack into ~this many MB of SHARD payload per all_gather /
    # psum_scatter launch (one collective per bucket, not per leaf).
    zero_bucket_mb: float = 4.0
    # Hoist the stage-2/3 params gather onto the AsyncParamGather worker
    # so it overlaps the previous step (default); False runs gather +
    # step inline (A/B lever; the overlap/zero gauge is then absent).
    zero_overlap_gather: bool = True
    # Layer-granular stage 2/3 (true ZeRO-3): the step gathers each
    # layer group's full params just-in-time (per-group fusion buckets,
    # `comms/zero.gather.<group>` sites) and the rematerialized group
    # segments free them after their forward/backward contribution, so
    # transient model memory drops from full-tree to ~two adjacent
    # groups — the per-chip-batch capacity unlock. Same loss trajectory
    # as the whole-tree stages up to f32 rounding across the separately
    # compiled programs (tests/test_zero.py states the bound). Requires
    # zero_stage >= 2, num_model == 1, and an elementwise optimizer;
    # checkpoint layout is unchanged (the same (n, m) shards), so
    # resume round-trips freely across zero1/zero23/layer-granular.
    zero_layer_granular: bool = False


@dataclasses.dataclass(frozen=True)
class ProbeConfig:
    """Linear-probe hyperparameters (`main_lincls.py:~L30-95, ~L200-210`):
    SGD(lr=30.0, momentum=0.9, wd=0), step schedule [60, 80], 100 epochs,
    frozen backbone with BN in eval mode."""

    lr: float = 30.0
    momentum: float = 0.9
    weight_decay: float = 0.0
    schedule: Tuple[int, ...] = (60, 80)
    epochs: int = 100
    num_classes: int = 1000


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    moco: MocoConfig = dataclasses.field(default_factory=MocoConfig)
    optim: OptimConfig = dataclasses.field(default_factory=OptimConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    seed: int = 0
    workdir: str = "/tmp/moco_tpu"
    log_every: int = 10  # --print-freq
    checkpoint_every_epochs: int = 1
    # Retention: keep the last N checkpoints; 0 keeps EVERY one (the
    # reference's behavior — per-epoch checkpoint_{epoch:04d}.pth.tar,
    # main_moco.py:~L275-280).
    checkpoint_keep: int = 3
    # Overlap checkpoint serialization with training (Orbax async): the
    # save returns after the host snapshot; the write happens on a
    # background thread. The preemption path always waits for durability.
    checkpoint_async: bool = False
    steps_per_epoch: Optional[int] = None  # None = derive from dataset size
    # Periodic weighted-kNN monitor on frozen backbone features (the
    # cheap probe proxy the reference lacks — moco_tpu/knn.py): run every
    # N epochs; 0 disables. Requires a labeled dataset (train=False split
    # buildable from config.data, or knn_datasets passed to train()).
    knn_every_epochs: int = 0
    knn_k: int = 200
    knn_temperature: float = 0.07
    # Non-finite-loss guard (fault-tolerance layer): checked on log steps
    # only (piggybacks on the existing `i % log_every` device fetch — no
    # extra host sync in the step loop). A NaN/Inf loss skips that step's
    # update (params/opt/queue roll back to the last finite log step's
    # state; the step counter keeps advancing so checkpoint ids stay
    # monotonic) and is counted + written to metrics.jsonl; after
    # `nan_guard_threshold` such events the run aborts with diagnostics
    # instead of burning the fleet on a diverged model.
    nan_guard_threshold: int = 10
    # Stall watchdog: seconds without a completed step-loop iteration
    # before the process dumps all-thread stacks, attempts an emergency
    # checkpoint, and exits nonzero (a hung collective blocks the main
    # thread in a device call forever — only a sidecar thread can see
    # it). 0 disables. Must exceed the worst-case log interval; the
    # first step additionally gets a compilation grace period.
    watchdog_timeout: float = 0.0
    # Strict tracing mode (mocolint runtime arm, --strict-tracing):
    # enables jax.check_tracer_leaks, surfaces a `compile_cache_misses`
    # counter on every metrics.jsonl log line, and aborts when the step
    # function recompiles after `recompile_warmup_steps` (each silent
    # recompile of the r50/224 step costs minutes — PROFILE.md). Checked
    # on log steps only, so the step loop stays sync-free.
    strict_tracing: bool = False
    # Steps during which compiles are free (first trace + donation
    # variants); a compile-cache miss after this aborts under
    # --strict-tracing.
    recompile_warmup_steps: int = 8
    # Runtime collective-schedule sanitizer (mocolint runtime arm,
    # analysis/sanitizer.py, --sanitize-collectives): every comms-tagged
    # collective site records its (site, kind, operand-shape) into a
    # per-process schedule; on log steps the schedule hash is published
    # out-of-band (schedule.p<i>.json, heartbeat-style) and cross-checked
    # against every peer. A mismatch aborts with a per-site diff BEFORE
    # the pod deadlocks in the mismatched collective. Off the hot path
    # (recording happens at trace time; the check piggybacks on the log
    # step's host sync).
    sanitize_collectives: bool = False
    # Runtime lock-order sanitizer (mocolint v3 runtime arm,
    # analysis/tsan.py, --sanitize-threads): every tsan-factory lock
    # (serve.index, serve.metrics, obs.*, data.*) reports its
    # acquisition order to a per-process recorder; an order cycle —
    # two code paths nesting the same locks opposite ways — aborts
    # with both acquisition stacks (lock_order_diff.json) BEFORE the
    # deadlock wedges the process, and blocking ops issued under a
    # held lock are recorded for the run report (lock_order.json).
    # Smoke-run tooling: the profile hook costs real CPU.
    sanitize_threads: bool = False
    # -- telemetry (moco_tpu/obs) ---------------------------------------
    # Metric sinks, comma list from the obs sink registry ("jsonl",
    # "csv", "tensorboard"); the JSONL sink is always included — the
    # fault counters, chaos harness, and obs_report key on it.
    sinks: str = "jsonl"
    # Serve Prometheus text format on http://<metrics_host>:<port +
    # process_index>/metrics (in-process daemon thread; scraping long
    # runs). 0 = off. The per-process port shift keeps co-hosted
    # processes from colliding on one bind.
    metrics_port: int = 0
    # Bind address for the Prometheus endpoint; "0.0.0.0" exposes it to
    # off-box scrapers (the old hardcoded loopback made pod-wide
    # scraping impossible).
    metrics_host: str = "127.0.0.1"
    # MoCo health gauges computed INSIDE the jitted step (EMA drift,
    # InfoNCE logit stats, collapse detection, queue staleness —
    # obs/health.py) and returned through the metrics dict. Cheap
    # reductions (one extra pass over params for the drift norm), but a
    # lever exists for steps where every byte counts.
    health_metrics: bool = True
    # Step-time breakdown probe: every N steps, block_until_ready the
    # step's outputs to split host dispatch from device compute
    # (t_dispatch/t_device on the next log line). Off the hot path
    # otherwise; 0 disables sampling (t_data/t_step still logged from
    # host timers, which cost nothing).
    obs_probe_every: int = 50
    # -- fleet observability (obs/fleet.py, obs/alerts.py) --------------
    # Cross-host aggregation: on log steps every process contributes a
    # fixed-width stats vector (t_data/t_step/dispatch lag/io retries/
    # decode failures/live HBM) to a jitted all_gather; process 0's
    # metrics lines then carry fleet min/mean/max/argmax per field and
    # the straggler_skew gauge, and every process writes an out-of-band
    # heartbeat file (merged by obs_report when a host dies mid-run).
    fleet_metrics: bool = True
    # Declarative alert rules evaluated in-stream against every logged
    # payload (obs/alerts.py grammar): "default" = the built-in set
    # (step-time spike, data starvation, straggler skew, EMA runaway,
    # queue staleness, non-finite loss, stall, heartbeat loss);
    # "default,<spec>" extends it; "none" disables. Fired alerts land in
    # workdir/alerts.jsonl + an `event: "alert"` metrics line (which the
    # Prometheus sink exposes as a per-rule gauge).
    alert_rules: str = "default"
    # Abort on any fired alert, after an emergency checkpoint (reuses
    # the fault-tolerance layer's save-first-die-second path).
    alerts_fatal: bool = False
    # -- input wire (data/device_prefetch.py) ---------------------------
    # Device prefetch ring: a dedicated transfer thread stages the next
    # `prefetch_depth` batches on device (sharded uint8 device_put)
    # while the current step runs, so decode, the wire, and compute
    # overlap instead of taking turns (the reference hides this cost
    # behind 32 DataLoader workers + pinned-memory async H2D). Off =
    # the synchronous in-line path (one producer thread does decode →
    # transfer → dispatch serially).
    device_prefetch: bool = True
    prefetch_depth: int = 2
    # Donate the consumed staging slot's uint8 buffer to the augment
    # step so XLA reuses its HBM for the normalized output instead of
    # allocating a fresh batch-sized buffer. Ignored (harmless) on
    # backends without donation support (CPU).
    prefetch_donate: bool = False
    # -- elastic training (parallel/elastic.py) -------------------------
    # Heartbeat-triggered checkpoint-and-rescale: on heartbeat loss the
    # survivors agree on the event (rescale-consensus barrier), take an
    # emergency checkpoint, rebuild a smaller mesh over the surviving
    # devices, reshard params/optimizer/queue onto it (reshard_state),
    # re-derive momentum/LR from the shrunk global batch via the
    # auto-scale rule, and resume in-process — no restart from scratch.
    # Requires num_model == 1.
    elastic: bool = False
    # Heartbeat-staleness threshold in seconds: a host whose out-of-band
    # heartbeat file is older than this is declared lost — by the alert
    # engine's default heartbeat_loss rule AND (with elastic=True) the
    # rescale trigger. Replaces the previously hard-coded 120 s in the
    # alert default spec.
    heartbeat_timeout: float = 120.0
    # Principled batch scaling ("How to Scale Your EMA", arXiv:2307.13813;
    # Momentum² Teacher, arXiv:2101.07525), spec "ref_batch=N": treat
    # optim.lr and moco.momentum as REFERENCE values at global batch N
    # and derive the live values from the actual global batch with
    # κ = global_batch / N — LR linearly (lr·κ), the EMA momentum as
    # m^κ. Warmup needs no re-derivation: warmup_epochs is
    # epoch-denominated, and steps-per-epoch already shifts with the
    # batch. "" disables; elastic runs default it to the original batch
    # so a rescale re-derives against the pre-loss anchor.
    auto_scale: str = ""


def config_to_dict(cfg: TrainConfig) -> dict:
    """JSON-serializable dict (tuples become lists) — stored in every
    checkpoint so downstream tools (linear probe, converters) can rebuild
    the exact model/optimizer without the user re-specifying flags."""
    return dataclasses.asdict(cfg)


def dataclass_from_dict(cls, sub: dict):
    """Rebuild a config dataclass from checkpointed JSON: unknown keys
    are dropped (forward/backward compatibility across field changes)
    and lists become tuples."""
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in sub:
            continue
        v = sub[f.name]
        if isinstance(v, list):
            v = tuple(v)
        kwargs[f.name] = v
    return cls(**kwargs)


def config_from_dict(d: dict) -> TrainConfig:
    build = dataclass_from_dict

    return TrainConfig(
        moco=build(MocoConfig, d.get("moco", {})),
        optim=build(OptimConfig, d.get("optim", {})),
        data=build(DataConfig, d.get("data", {})),
        parallel=build(ParallelConfig, d.get("parallel", {})),
        **{
            k: d[k]
            for k in (
                "seed", "workdir", "log_every", "checkpoint_every_epochs",
                "checkpoint_async", "checkpoint_keep", "steps_per_epoch",
                "nan_guard_threshold", "watchdog_timeout",
                "strict_tracing", "recompile_warmup_steps", "sanitize_collectives",
                "sanitize_threads",
                "sinks", "metrics_port", "metrics_host", "health_metrics",
                "obs_probe_every", "fleet_metrics", "alert_rules", "alerts_fatal",
                "device_prefetch", "prefetch_depth", "prefetch_donate",
                "elastic", "heartbeat_timeout", "auto_scale",
            )
            if k in d
        },
    )


def parse_auto_scale(spec: str) -> Optional[int]:
    """Parse the `--auto-scale` spec ("ref_batch=N"); None when unset.
    Same colon-separated key=val shape as the fault/alert grammars so a
    future key (e.g. a BN-statistics-momentum rule) extends in place."""
    if not spec:
        return None
    ref_batch: Optional[int] = None
    for tok in spec.split(":"):
        tok = tok.strip()
        if not tok:
            continue
        k, _, v = tok.partition("=")
        if k == "ref_batch":
            ref_batch = int(v)
        else:
            raise ValueError(f"unknown auto-scale param {k!r} in {spec!r}")
    if ref_batch is None or ref_batch <= 0:
        raise ValueError(f"auto-scale spec {spec!r} needs ref_batch=<positive int>")
    return ref_batch


def apply_auto_scale(config: TrainConfig) -> Tuple[TrainConfig, Optional[dict]]:
    """Derive the LIVE hyperparameters from the reference ones under the
    batch-scaling rules: κ = global_batch / ref_batch, lr' = lr·κ
    (linear), EMA momentum m' = m^κ (the EMA scaling rule — shrinking
    the batch by κ<1 must SLOW the key encoder's drift per step or it
    decouples from the query encoder; arXiv:2307.13813 §3). Identity
    (config, None) when no auto_scale spec is set.

    Always derives from the values IN `config` — callers that rescale
    repeatedly (the elastic loop) must pass the reference config each
    time, never an already-derived one."""
    ref_batch = parse_auto_scale(config.auto_scale)
    if ref_batch is None:
        return config, None
    kappa = config.data.global_batch / ref_batch
    lr = config.optim.lr * kappa
    momentum = config.moco.momentum**kappa
    derived = dataclasses.replace(
        config,
        optim=dataclasses.replace(config.optim, lr=lr),
        moco=dataclasses.replace(config.moco, momentum=momentum),
    )
    info = {
        "ref_batch": ref_batch,
        "kappa": kappa,
        "lr": lr,
        "momentum": momentum,
        "ref_lr": config.optim.lr,
        "ref_momentum": config.moco.momentum,
    }
    return derived, info


class ResumeCompatError(ValueError):
    """The checkpoint being resumed was trained under a structurally
    different config — restoring it into the live model would either
    fail with an opaque shape error or, worse, silently succeed into the
    wrong semantics. Carries a human-readable field-by-field diff."""


# Structural fields a resume must agree on: they determine parameter /
# optimizer-state / queue SHAPES (a mismatch makes the restore template
# wrong). Tunables (lr, epochs, temperature, aug recipe, ...) may change
# across a resume on purpose and are deliberately not listed.
RESUME_COMPAT_FIELDS = {
    "moco": (
        "arch", "dim", "num_negatives", "mlp", "v3", "cifar_stem",
        "vit_pool", "vit_patch_size", "vit_sequence_parallel",
    ),
    "data": ("image_size",),
    # NOTE: parallel.shard_weight_update / zero_stage / num_data are
    # deliberately NOT hard-compat fields anymore: a layout mismatch is
    # "compatible but resharded" — the driver restores into a template
    # of the checkpoint's own layout and converts host-side
    # (core/moco.py:reshard_state), so zero1 -> zero23, sharded ->
    # replicated, and mesh-width changes all resume.
    "parallel": ("num_model",),
}


def resume_compat_diff(saved_extra: dict, config: TrainConfig, num_data: int) -> list[str]:
    """Field-by-field incompatibility diff between a checkpoint's saved
    `extra` (as written by the train driver: `config` + `num_data`) and
    the live run. Empty list = compatible. Unknown/missing saved keys are
    skipped (older checkpoints stay resumable)."""
    diffs = []
    saved_cfg = saved_extra.get("config") or {}
    live = config_to_dict(config)
    for section, fields in RESUME_COMPAT_FIELDS.items():
        saved_sec = saved_cfg.get(section) or {}
        for f in fields:
            if f not in saved_sec:
                continue
            sv, lv = saved_sec[f], live[section][f]
            if isinstance(lv, tuple):
                lv = list(lv)
            if sv != lv:
                diffs.append(f"{section}.{f}: checkpoint={sv!r} != config={lv!r}")
    # num_data under ZeRO used to be a hard incompatibility (the mesh
    # width is baked into the (n, m) shard shapes); since reshard_state
    # it is a resharding case, handled by the driver's layout-aware
    # restore — no diff entry.
    return diffs


def _v2(moco: MocoConfig, **kw) -> MocoConfig:
    return dataclasses.replace(moco, mlp=True, temperature=0.2, **kw)


PRESETS = {
    # BASELINE.json configs[0]: single-process CPU/1-chip smoke
    "cifar_smoke": TrainConfig(
        moco=MocoConfig(arch="resnet18", num_negatives=4096, cifar_stem=True, shuffle="none"),
        optim=OptimConfig(lr=0.03, epochs=10, cos=True),
        data=DataConfig(dataset="cifar10", image_size=32, global_batch=256),
    ),
    # configs[1]: ImageNet-100 v2
    "imagenet100_v2": TrainConfig(
        moco=_v2(MocoConfig()),
        optim=OptimConfig(lr=0.03, epochs=200, cos=True),
        data=DataConfig(dataset="imagefolder", aug_plus=True),
    ),
    # configs[2]: ImageNet-1k v2 200ep, 8-chip DP
    "imagenet_v2": TrainConfig(
        moco=_v2(MocoConfig()),
        optim=OptimConfig(lr=0.03, epochs=200, cos=True),
        data=DataConfig(dataset="imagefolder", aug_plus=True),
    ),
    # configs[3]: pod-scale large-batch + LARS (v4-128-class). Raised to
    # 8192 once layer-granular ZeRO-3 freed the per-chip headroom; the
    # hyperparameters stay declared at the 4096 reference and the
    # scaling-law rules derive the live ones (κ=2: lr×2, momentum^2 —
    # the README "scaling up batch size correctly" runbook), with
    # momentum-statistics BN standing in for cross-replica statistics.
    # NB: LARS needs whole-tensor trust ratios, so THIS preset cannot
    # also turn on the sharded weight update — the ZeRO-3 huge-batch
    # recipe is the vit preset below.
    "imagenet_v2_large_batch": TrainConfig(
        moco=_v2(MocoConfig(), bn_momentum_stats=True),
        optim=OptimConfig(
            optimizer="lars", lr=4.8, weight_decay=1e-6, epochs=200, cos=True, warmup_epochs=10
        ),
        data=DataConfig(dataset="imagefolder", aug_plus=True, global_batch=8192),
        auto_scale="ref_batch=4096",
    ),
    # NOTE (r5): the former `imagenet_v2_eman` preset was DEMOTED to a
    # documented experiment. The EMAN-style key forward
    # (--key-bn-eval / key_bn_running_stats, arXiv:2101.08482 pattern —
    # no key-side BN statistics pass, no Shuffle-BN collectives,
    # zero-comm multi-chip key forwards) remains fully supported as
    # flags, but its measured accuracy arms argue against recommending
    # it as a recipe: the CI-budget deficit (35.6 vs 53.7 kNN) was only
    # HALF-closed by the stats-EMA warmup fix (44.1), and at 4× budget
    # the deficit persists and mildly widens (46.5 vs 59.8 —
    # REPORT.md "EMAN key forward"). Reproduce with:
    #   train.py --preset imagenet_v2 --shuffle none --key-bn-eval
    # BASELINE.json configs[4]: MoCo v3 ViT-B/16, queue-free symmetric
    # loss, AdamW + warmup (arXiv:2104.02057 recipe: lr=1.5e-4·batch/256,
    # wd=0.1, 40-epoch warmup, batch 4096).
    "vit_b16_v3": TrainConfig(
        moco=MocoConfig(
            arch="vit_b16", dim=256, num_negatives=0, momentum=0.99,
            momentum_cos=True, temperature=0.2, v3=True, shuffle="none",
        ),
        optim=OptimConfig(
            optimizer="adamw", lr=2.4e-3, weight_decay=0.1, epochs=300,
            cos=True, warmup_epochs=40,
        ),
        data=DataConfig(dataset="imagefolder", aug_plus=True, global_batch=4096),
    ),
    # Huge-batch v3 on the layer-granular ZeRO-3 memory budget: the
    # vit_b16_v3 recipe declared at its 4096 reference batch, run at
    # 8192 with the scaling-law rules deriving lr/momentum (κ=2) and
    # params + optimizer state persistently sharded, gathered one layer
    # group at a time (transient model memory ≈ two encoder blocks
    # instead of the full tree — the headroom the doubled batch spends).
    # AdamW is elementwise, so the sharded update is eligible (unlike
    # the LARS preset above).
    "vit_b16_v3_huge_batch_zero3": TrainConfig(
        moco=MocoConfig(
            arch="vit_b16", dim=256, num_negatives=0, momentum=0.99,
            momentum_cos=True, temperature=0.2, v3=True, shuffle="none",
        ),
        optim=OptimConfig(
            optimizer="adamw", lr=2.4e-3, weight_decay=0.1, epochs=300,
            cos=True, warmup_epochs=40,
        ),
        data=DataConfig(dataset="imagefolder", aug_plus=True, global_batch=8192),
        parallel=ParallelConfig(
            shard_weight_update=True, zero_stage=3, zero_layer_granular=True
        ),
        auto_scale="ref_batch=4096",
    ),
    # Long-sequence showcase (beyond the reference): 448px inputs give a
    # 784-token ViT-B/16; tokens shard over an 8-way model axis with ring
    # attention (gap pooling, --num-model 8). Sequence parallelism keeps
    # per-chip attention memory at 1/8 of the full sequence.
    "vit_b16_v3_highres_sp": TrainConfig(
        moco=MocoConfig(
            arch="vit_b16", dim=256, num_negatives=0, momentum=0.99,
            momentum_cos=True, temperature=0.2, v3=True, shuffle="none",
            vit_pool="gap", vit_sequence_parallel=True,
        ),
        # lr follows the v3 rule 1.5e-4 * batch/256 at THIS preset's
        # batch of 1024 (not the 4096 of vit_b16_v3 above)
        optim=OptimConfig(
            optimizer="adamw", lr=6e-4, weight_decay=0.1, epochs=300,
            cos=True, warmup_epochs=40,
        ),
        data=DataConfig(
            dataset="imagefolder", aug_plus=True, global_batch=1024, image_size=448
        ),
        parallel=ParallelConfig(num_model=8),
    ),
    # A language model's decoder stack as a momentum-contrast TEXT encoder
    # (moco_tpu/models/joyai.py: JoyAI-LLM-Flash, latent attention + 256
    # routed experts): the v2 path with the queue, the EMA key encoder and
    # the fused InfoNCE, two independent 8192-token windows of one document
    # as the views (Contriever's recipe, arXiv:2112.09118: T 0.05, m 0.9995,
    # AdamW). As published it is 48 B parameters: a run states its cut of
    # a deployment with moco.lm_layers / lm_vocab_rows / expert_share
    # (benchmarks/configs/joyai_flash_ep16.json is one chip of 16).
    # remat: each decoder block is recomputed in the backward pass, all but
    # its attention product: the kernels' output and log-sum-exp are kept
    # (models/decoder.py remat_block), 136 MB a layer at 2 x 8192 tokens.
    "joyai_llm_flash": TrainConfig(
        moco=MocoConfig(
            arch="joyai_llm_flash", mlp=True, temperature=0.05, momentum=0.9995,
            shuffle="none", remat=True,
        ),
        # one warm-up epoch of 25: over a corpus of 40 000 documents at 2 rows
        # a step that is Contriever's 20 000 warm-up steps of 500 000. Without
        # a warm-up AdamW moves every router logit by ~2 in its first 20 steps
        optim=OptimConfig(
            optimizer="adamw", lr=5e-5, weight_decay=0.01, epochs=25, cos=True, warmup_epochs=1
        ),
        data=DataConfig(dataset="synthetic", input="tokens", seq_len=8192, global_batch=2),
    ),
    # the same stack and path at a test's size, for the CPU
    "joyai_tiny": TrainConfig(
        moco=MocoConfig(
            arch="joyai_tiny", mlp=True, temperature=0.05, momentum=0.9995,
            num_negatives=256, shuffle="none", compute_dtype="float32",
        ),
        optim=OptimConfig(optimizer="adamw", lr=5e-5, weight_decay=0.01, epochs=1, cos=True),
        data=DataConfig(dataset="synthetic", input="tokens", seq_len=64, global_batch=4),
    ),
    # A second decoder stack on the same path (moco_tpu/models/smallthinker.py:
    # SmallThinker-21BA3B, window and full attention over 4 grouped key heads,
    # a softmax-of-the-top-6 router that reads before attention, 64 ReGLU
    # experts): the same recipe over two independent windows of the model's
    # whole 16 384-token context, one row a chip. As published it is 21 B
    # parameters: a run states its cut as above
    # (benchmarks/configs/smallthinker_21b_ep8.json is one chip of 8).
    "smallthinker_21b": TrainConfig(
        moco=MocoConfig(
            arch="smallthinker_21b", mlp=True, temperature=0.05, momentum=0.9995,
            shuffle="none", remat=True,
        ),
        # one warm-up epoch of 25 over the 40 000 documents, as joyai_llm_flash
        optim=OptimConfig(
            optimizer="adamw", lr=5e-5, weight_decay=0.01, epochs=25, cos=True, warmup_epochs=1
        ),
        data=DataConfig(dataset="synthetic", input="tokens", seq_len=16384, global_batch=1),
    ),
    # the same stack and path at a test's size, for the CPU
    "smallthinker_tiny": TrainConfig(
        moco=MocoConfig(
            arch="smallthinker_tiny", mlp=True, temperature=0.05, momentum=0.9995,
            num_negatives=256, shuffle="none", compute_dtype="float32",
        ),
        optim=OptimConfig(optimizer="adamw", lr=5e-5, weight_decay=0.01, epochs=1, cos=True),
        data=DataConfig(dataset="synthetic", input="tokens", seq_len=64, global_batch=4),
    ),
    # A third decoder stack on the same path (moco_tpu/models/phi4flash.py:
    # Phi-4-mini-flash-reasoning, SambaY: Mamba layers on the selective-scan
    # kernel, differential attention over window, full and cross-decoder
    # layers, gated memory units): the same recipe over two independent
    # 16 384-token windows, one row a chip. As published it is 3.8 B
    # parameters: a run states its cut as above, and where its stage starts
    # (moco.lm_first_layer: benchmarks/configs/phi4_mini_flash_stage5.json
    # holds published layers 15-19).
    "phi4_mini_flash": TrainConfig(
        moco=MocoConfig(
            arch="phi4_mini_flash", mlp=True, temperature=0.05, momentum=0.9995,
            shuffle="none", remat=True,
        ),
        optim=OptimConfig(
            optimizer="adamw", lr=5e-5, weight_decay=0.01, epochs=25, cos=True, warmup_epochs=1
        ),
        data=DataConfig(dataset="synthetic", input="tokens", seq_len=16384, global_batch=1),
    ),
    # the same stack and path at a test's size, for the CPU
    "phi4_flash_tiny": TrainConfig(
        moco=MocoConfig(
            arch="phi4_flash_tiny", mlp=True, temperature=0.05, momentum=0.9995,
            num_negatives=256, shuffle="none", compute_dtype="float32",
        ),
        optim=OptimConfig(optimizer="adamw", lr=5e-5, weight_decay=0.01, epochs=1, cos=True),
        data=DataConfig(dataset="synthetic", input="tokens", seq_len=64, global_batch=4),
    ),
}
