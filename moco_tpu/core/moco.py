"""The MoCo algorithm as a pure SPMD train step.

This is the TPU-first re-design of `moco/builder.py` + the hot loop of
`main_moco.py:~L262-310`. Instead of a stateful `nn.Module` with
registered buffers mutated per rank under DDP, the whole algorithm is one
pure function

    train_step(state, batch, root_rng) -> (state, metrics)

jitted once over a `jax.sharding.Mesh` via `shard_map`. The reference's
trickiest invariant — queue + EMA replicas staying bit-identical across
ranks with no dedicated sync traffic (SURVEY.md §2.3) — is structural
here: replicated state in, deterministic math, replicated state out.

Per-step collectives (vs the reference's 3× all_gather + 1× broadcast +
DDP all-reduce, `SURVEY.md §3.1`):
- shuffle='gather_perm': 2× all_gather (images, embeddings; the
  broadcast is replaced by same-seed randomness, and the queue reuses
  the unshuffle gather — one collective fewer than upstream)
- shuffle='a2a': 2× all_to_all + 1× small all_gather (balanced random
  permutation — moves (n-1)/n of the batch over ICI vs the full
  n× batch an all_gather moves)
- 1× psum for gradients (the DDP bucketed all-reduce equivalent)
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from moco_tpu.core.ema import ema_update
from moco_tpu.core.queue import check_queue_divisibility, enqueue, init_queue
from moco_tpu.obs import comms
from moco_tpu.obs import health as obs_health
from moco_tpu.models import ProjectionHead, V3MLPHead, create_resnet
from moco_tpu.models.decoder import routing_metrics
from moco_tpu.models.token_encoders import create_token_encoder, is_token_arch
from moco_tpu.ops.losses import cross_entropy, infonce_logits, l2_normalize, topk_accuracy
from moco_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from moco_tpu.parallel.shuffle import (
    balanced_shuffle,
    balanced_unshuffle,
    make_permutation,
    shuffle_gather,
    unshuffle_gather,
)
from moco_tpu.parallel.zero import (
    BucketPlan,
    GroupPlan,
    expand_opt_state,
    padded_cols,
    shard_template,
    shard_tree,
    sharded_update,
    squeeze_opt_state,
)
from moco_tpu.utils.config import MocoConfig, TrainConfig
from moco_tpu.utils.platform import pallas_interpret


class MoCoEncoder(nn.Module):
    """backbone + projection head = the reference's `base_encoder(num_classes=dim)`
    with optional MLP surgery (`moco/builder.py:~L20-30`), composed explicitly.

    `group`: layer-granular apply (the ZeRO-3 per-group schedule) — run
    only the named backbone group ("stem"/"blockN"/"embed"/...) or the
    "head" group on `x`, which is then the PREVIOUS group's activation,
    not an image. `group=None` is the classic whole-encoder forward;
    both paths register identical parameter trees."""

    backbone: nn.Module
    head: nn.Module

    def __call__(self, x, train: bool = True, group: Optional[str] = None):
        if group is None:
            return self.head(self.backbone(x, train=train), train=train)
        if group == "head":
            return self.head(x, train=train)
        return self.backbone(x, train=train, group=group)


def create_backbone(cfg: MocoConfig, num_data: Optional[int] = None) -> nn.Module:
    """Backbone factory shared by pretraining and the linear probe:
    ResNet family, ViT family or a decoder stack (token rows) from
    `cfg.arch`."""
    dtype = jnp.dtype(cfg.compute_dtype)
    if is_token_arch(cfg.arch):
        if cfg.v3 or cfg.vit_sequence_parallel or cfg.shuffle != "none":
            # a decoder stack has no BatchNorm to shuffle for, and only the
            # queue path (v1/v2) has been taught to read token rows
            raise ValueError(
                f"{cfg.arch!r} is a token encoder: it trains on the v1/v2 step "
                "with shuffle='none'"
            )
        return create_token_encoder(
            cfg.arch, dtype=dtype, layers=cfg.lm_layers, vocab_rows=cfg.lm_vocab_rows,
            expert_share=tuple(cfg.expert_share) or None, remat=cfg.remat,
            first_layer=cfg.lm_first_layer,
        )
    if cfg.vit_sequence_parallel and not cfg.arch.startswith("vit"):
        # must fail HERE, not just in the vit branch: v3_step keys its
        # backbone-grad psum on this flag, and a silently-ignored flag on
        # a ResNet would double backbone grads over the model axis
        raise ValueError(f"vit_sequence_parallel requires a ViT arch, got {cfg.arch!r}")
    if cfg.arch.startswith("vit"):
        if cfg.bn_stats_rows or cfg.bn_virtual_groups > 1 or cfg.bn_momentum_stats:
            # must fail loudly: a ViT has no BatchNorm, the lever would be
            # inert while the checkpoint config records it as active
            raise ValueError(
                "bn_stats_rows / bn_virtual_groups / bn_momentum_stats apply "
                "to ResNet BatchNorm, not ViT archs"
            )
        from moco_tpu.models.vit import create_vit

        vit_kw = {"patch_size": cfg.vit_patch_size} if cfg.vit_patch_size else {}
        if cfg.vit_sequence_parallel:
            if not cfg.v3:
                raise ValueError("vit_sequence_parallel requires the v3 (queue-free) step")
            if cfg.vit_pool != "gap":
                raise ValueError("vit_sequence_parallel requires vit_pool='gap'")
            vit_kw["sequence_axis"] = MODEL_AXIS
        return create_vit(
            cfg.arch,
            dtype=dtype,
            use_flash_attention=cfg.vit_flash_attention,
            pool=cfg.vit_pool,
            **vit_kw,
        )
    syncbn_axis = DATA_AXIS if cfg.shuffle == "syncbn" else None
    groups = None
    if syncbn_axis and cfg.syncbn_group_size and num_data is None:
        raise ValueError(
            "syncbn_group_size is set but build_encoder was called without "
            "num_data — subgrouped SyncBN needs the data-axis size to form groups"
        )
    if syncbn_axis and cfg.syncbn_group_size and num_data:
        # Subgrouped SyncBN — the detection configs' "per-8-GPU" statistics
        # pattern (Base-RCNN-C4-BN.yaml) via axis_index_groups.
        g = cfg.syncbn_group_size
        if num_data % g:
            raise ValueError(f"data axis {num_data} not divisible by syncbn group {g}")
        groups = [list(range(i, i + g)) for i in range(0, num_data, g)]
    if cfg.bn_virtual_groups > 1 and cfg.shuffle == "syncbn":
        raise ValueError("bn_virtual_groups does not compose with syncbn")
    if cfg.bn_stats_barrier and not cfg.bn_stats_rows:
        # must fail loudly: without subset rows the custom BatchNorm is
        # never even selected, and a compile-pathology A/B would silently
        # measure baseline-vs-baseline while reporting the barrier leg
        raise ValueError("bn_stats_barrier requires bn_stats_rows > 0")
    if (
        cfg.bn_stats_rows
        and (cfg.shuffle == "none" or cfg.v3)
        and (num_data or 1) > 1
        and not cfg.allow_leaky_bn
        # with an EMAN key forward the key path reads NO batch
        # statistics, so query-side subset stats cannot leak key
        # composition — stacking the two BN levers is safe. The
        # exemption must not extend to v3: key_bn_running_stats is
        # invalid there (make_train_step rejects the combo), so a
        # v3 config carrying it must still hit this gate rather
        # than silently building a leaky encoder.
        and not (cfg.key_bn_running_stats and not cfg.v3)
    ):
        # same leak logic as the virtual-groups gate below, sharpened:
        # statistics over a FIXED first-r-rows subset leak more than
        # whole-batch per-device BN (fewer rows correlate query/key
        # composition more tightly), so the perf lever must not be
        # combinable with unpermuted multi-device keys — and the v3
        # step never shuffles at all, so it is equally exposed.
        # Single-device training keeps it available (no cross-device
        # composition to leak beyond the known single-GPU MoCo caveat).
        raise ValueError(
            "bn_stats_rows needs a key permutation on a multi-device data "
            "axis (fixed first-N-rows statistics concentrate the BN leak "
            "Shuffle-BN prevents): use shuffle='gather_perm' or 'a2a', and "
            "leave it unset for the v3 step, which never shuffles"
        )
    if (
        cfg.bn_virtual_groups > 1
        and (cfg.shuffle == "none" or cfg.v3)
        and not cfg.allow_leaky_bn
        # EMAN key forward: the key path reads NO batch statistics, so
        # query-side per-group stats cannot leak key composition (same
        # exemption — and same v3 scoping — as the bn_stats_rows gate)
        and not (cfg.key_bn_running_stats and not cfg.v3)
    ):
        # must fail loudly: per-group BN with UNPERMUTED keys is the exact
        # intra-batch statistics leak Shuffle-BN exists to prevent — worse
        # than whole-batch BN, while the config would record virtual
        # Shuffle-BN as active (the v3 step never shuffles at all)
        raise ValueError(
            "bn_virtual_groups needs a key permutation: use shuffle='gather_perm' "
            "or 'a2a' (shuffle='none' and the v3 step would leak per-group stats)"
        )
    return create_resnet(
        cfg.arch,
        cifar_stem=cfg.cifar_stem,
        dtype=dtype,
        bn_cross_replica_axis=syncbn_axis,
        bn_axis_index_groups=groups,
        bn_stats_rows=cfg.bn_stats_rows,
        bn_stats_barrier=cfg.bn_stats_barrier,
        bn_virtual_groups=cfg.bn_virtual_groups,
        bn_momentum_stats=cfg.bn_momentum_stats,
    )


def build_encoder(cfg: MocoConfig, num_data: Optional[int] = None) -> MoCoEncoder:
    """Backbone + projection head. v3 head shape branches on backbone
    family, matching upstream `moco-v3`'s per-family builders
    (`_build_projector_and_predictor_mlps`): ViT gets the 3-layer
    projector, ResNet the 2-layer one (both end in affine-free BN);
    v1/v2 get the reference's Linear / 2-layer MLP
    (`moco/builder.py:~L20-30`)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    backbone = create_backbone(cfg, num_data=num_data)
    if cfg.v3:
        axis = DATA_AXIS if (num_data or 1) > 1 else None
        num_layers = 3 if cfg.arch.startswith("vit") else 2
        head = V3MLPHead(
            num_layers=num_layers, dim=cfg.dim, cross_replica_axis=axis, dtype=dtype
        )
    else:
        head = ProjectionHead(dim=cfg.dim, mlp=cfg.mlp, dtype=dtype)
    return MoCoEncoder(backbone=backbone, head=head)


def build_predictor(cfg: MocoConfig, num_data: Optional[int] = None) -> Optional[nn.Module]:
    """v3's prediction MLP on the query side only (2-layer BN-MLP); None
    for v1/v2, whose query and key encoders are architecturally identical.
    The ViT predictor keeps the final affine-free BN; the ResNet one drops
    it (upstream `MoCo_ResNet` passes last_bn=False)."""
    if not cfg.v3:
        return None
    axis = DATA_AXIS if (num_data or 1) > 1 else None
    return V3MLPHead(
        num_layers=2,
        dim=cfg.dim,
        cross_replica_axis=axis,
        last_bn=cfg.arch.startswith("vit"),
        dtype=jnp.dtype(cfg.compute_dtype),
    )


class MocoState(struct.PyTreeNode):
    """Everything `main_moco.py`'s checkpoint carries (SURVEY.md §3.5):
    both encoders, queue + pointer, optimizer state, step — plus, for the
    v3 variant, the query-side prediction head (empty dicts otherwise)."""

    step: jax.Array
    params_q: Any
    params_k: Any
    batch_stats_q: Any
    batch_stats_k: Any
    queue: jax.Array  # (K, dim) rows; L2-normalized
    queue_ptr: jax.Array  # int32 scalar
    opt_state: Any
    params_pred: Any = struct.field(default_factory=dict)
    batch_stats_pred: Any = struct.field(default_factory=dict)


class ZeroGathered(struct.PyTreeNode):
    """Output of the ZeRO-2/3 per-step params gather (parallel/zero.py
    stage 2/3): the FULL trainable params + key-encoder params step k
    consumes (replicated, donated to the step so XLA frees them after
    the backward), plus the already-EMA'd key-encoder SHARDS that
    become step k's `params_k` — the EMA itself ran shard-local inside
    the gather, with no collective."""

    trainable: Any  # {"enc": ..., "pred": ...}, full shapes, replicated
    params_k: Any  # full enc-shaped tree, replicated
    shards_k: Any  # (n, m) persistent layout, P(data)-sharded


def zero_stage23(config: TrainConfig) -> bool:
    """Whether the config selects the persistently-sharded-params ZeRO
    stage (2 and 3 both map to the one implementation)."""
    return config.parallel.shard_weight_update and config.parallel.zero_stage >= 2


def zero_layer_granular(config: TrainConfig) -> bool:
    """Whether the config selects the LAYER-GRANULAR stage-2/3 schedule:
    per-group just-in-time gather/free instead of the whole-tree gather."""
    return zero_stage23(config) and config.parallel.zero_layer_granular


def _overlay(orig, upd):
    """Merge a PARTIAL mutated batch_stats tree (from a layer-group
    apply, which only touches the called group's entries) back over the
    full tree, preserving `orig`'s nesting — entries the group never
    visited pass through unchanged."""
    if not hasattr(orig, "items"):
        return upd
    return {k: (_overlay(v, upd[k]) if k in upd else v) for k, v in orig.items()}


def _tree_full_bytes(tree) -> int:
    """Bytes of a shape/dtype-carrying abstract tree's FULL leaves."""
    return sum(
        (int(np.prod(tuple(l.shape))) if l.shape else 1) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)
    )


def _tree_shard_bytes_analytic(tree, n: int) -> int:
    """Per-chip bytes of the same tree in the persistent (n, m) layout
    (each replica's row, padding included)."""
    return sum(
        padded_cols(int(np.prod(tuple(l.shape))) if l.shape else 1, n)
        * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)
    )


def sample_input(config: TrainConfig):
    """One row as the encoder takes it, for `init` and `eval_shape`: only
    its structure and types matter (no parameter's shape depends on how
    long a token row is, so a token sample is a short one)."""
    if config.data.input == "tokens":
        return {"ids": jnp.zeros((1, 8), jnp.int32), "lengths": jnp.full((1,), 8, jnp.int32)}
    return jnp.zeros((1, config.data.image_size, config.data.image_size, 3), jnp.float32)


def full_param_shapes(config: TrainConfig, encoder: MoCoEncoder, predictor=None) -> dict:
    """Abstract (ShapeDtypeStruct) trees of the FULL trainable params —
    the shape source the ZeRO-2/3 bucket plans, eval-side gathers, and
    reshard templates all derive from (the persistent (n, m) layout
    does not carry the original leaf shapes)."""
    sample = sample_input(config)
    enc = jax.eval_shape(
        lambda r: encoder.init(r, sample, train=False), jax.random.PRNGKey(0)
    )["params"]
    pred = {}
    if predictor is not None:
        pred = jax.eval_shape(
            lambda r: predictor.init(
                r, jnp.zeros((1, config.moco.dim), jnp.float32), train=False
            ),
            jax.random.PRNGKey(0),
        )["params"]
    return {"enc": enc, "pred": pred}


def create_state(
    rng: jax.Array,
    config: TrainConfig,
    encoder: MoCoEncoder,
    tx,
    sample_input: jax.Array,
    predictor: Optional[nn.Module] = None,
    zero_num_data: Optional[int] = None,
) -> MocoState:
    """`zero_num_data`: when config.parallel.shard_weight_update is on,
    the data-axis size — the optimizer state is then initialized in the
    (n, m) sharded-flat layout (moco_tpu/parallel/zero.py) instead of the
    param tree's shapes."""
    if config.parallel.shard_weight_update and not zero_num_data:
        # fail here, not downstream: a replicated opt state silently built
        # for a ZeRO config would later be mis-sharded by the ndim==2
        # spec heuristic or squeezed into garbage shapes
        raise ValueError(
            "config.parallel.shard_weight_update=True requires zero_num_data "
            "(the data-axis size) so the opt state gets the (n, m) layout"
        )
    p_rng, q_rng, pred_rng = jax.random.split(rng, 3)
    variables = encoder.init(p_rng, sample_input, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    cfg = config.moco
    queue = (
        init_queue(q_rng, cfg.num_negatives, cfg.dim)
        if cfg.num_negatives > 0
        # queue-free (v3): a 1-row placeholder, never read by the step —
        # a (0, dim) array would be rejected by Orbax at checkpoint save
        else jnp.zeros((1, cfg.dim), jnp.float32)
    )
    params_pred, stats_pred = {}, {}
    if predictor is not None:
        pv = predictor.init(pred_rng, jnp.zeros((1, cfg.dim), jnp.float32), train=False)
        params_pred = pv["params"]
        stats_pred = pv.get("batch_stats", {})
    # opt state always initializes from the FULL trainable shapes (the
    # (n, m) template is derived from them); the param trees themselves
    # additionally move to the persistent sharded layout at stage 2/3
    zero = config.parallel.shard_weight_update and zero_num_data
    stage23 = bool(zero) and config.parallel.zero_stage >= 2
    params_k = jax.tree.map(jnp.copy, params)  # moco/builder.py:~L32-36
    opt_state = tx.init(
        {"enc": params, "pred": params_pred}
        if not zero
        else shard_template({"enc": params, "pred": params_pred}, zero_num_data)
    )
    if stage23:
        params = shard_tree(params, zero_num_data)
        params_k = shard_tree(params_k, zero_num_data)
        params_pred = shard_tree(params_pred, zero_num_data)
    return MocoState(
        step=jnp.zeros((), jnp.int32),
        params_q=params,
        params_k=params_k,
        batch_stats_q=batch_stats,
        batch_stats_k=jax.tree.map(jnp.copy, batch_stats),
        queue=queue,
        queue_ptr=jnp.zeros((), jnp.int32),
        opt_state=opt_state,
        params_pred=params_pred,
        batch_stats_pred=stats_pred,
    )


def state_specs(
    shard_queue_over_model: bool,
    zero_opt_state: Optional[Any] = None,
    zero_params: bool = False,
) -> MocoState:
    """PartitionSpec pytree for MocoState: everything replicated except,
    optionally, the queue rows sharded over the model axis (tensor
    parallelism for very large dictionaries), — with sharded weight
    update — the optimizer state's (n, m) leaves sharded over `data`
    (`zero_opt_state` is a concrete opt-state tree to derive per-leaf
    specs from; its 2-D leaves are the sharded ones, scalars replicate),
    and — at ZeRO stage 2/3 (`zero_params`) — the param trees
    themselves, whose leaves all live in the (n, m) persistent layout.
    """
    qspec = P(MODEL_AXIS, None) if shard_queue_over_model else P()
    opt_spec: Any = P()
    if zero_opt_state is not None:
        opt_spec = jax.tree.map(
            lambda x: P(DATA_AXIS, None) if getattr(x, "ndim", 0) == 2 else P(),
            zero_opt_state,
        )
    pspec = P(DATA_AXIS, None) if zero_params else P()
    return MocoState(
        step=P(),
        params_q=pspec,
        params_k=pspec,
        batch_stats_q=P(),
        batch_stats_k=P(),
        queue=qspec,
        queue_ptr=P(),
        opt_state=opt_spec,
        params_pred=pspec,
        batch_stats_pred=P(),
    )


def make_train_step(
    config: TrainConfig,
    encoder: MoCoEncoder,
    tx,
    mesh: Mesh,
    shard_queue_over_model: Optional[bool] = None,
    donate: bool = False,
    predictor: Optional[nn.Module] = None,
    total_steps: Optional[int] = None,
    state_template: Optional[MocoState] = None,
) -> Callable:
    """Builds the jitted SPMD train step over `mesh`.

    `state_template`: required when config.parallel.shard_weight_update
    is on — a concrete (un-placed is fine) MocoState whose opt_state tree
    provides the per-leaf sharding specs of the ZeRO layout.

    batch: {'im_q': (B_global,H,W,C), 'im_k': ...} fp32, already augmented
    (host- or device-side); sharded over the `data` axis.
    """
    cfg = config.moco
    # Training-health gauges (obs/health.py) computed inside the jitted
    # step and returned through the metrics dict — the host only ever
    # sees them on log steps, riding the existing fetch.
    health_on = config.health_metrics
    if cfg.key_bn_running_stats:
        # before the v3/predictor checks: the flag conflict is the more
        # fundamental config error and must be the one reported
        if cfg.v3:
            raise ValueError(
                "key_bn_running_stats is a v2-step lever; the v3 step "
                "manages its own momentum encoder"
            )
        if cfg.shuffle in ("gather_perm", "a2a"):
            raise ValueError(
                "key_bn_running_stats removes batch statistics from the key "
                "forward, so Shuffle-BN would be pure wasted communication: "
                "set shuffle='none' (or 'syncbn' for query-side statistics)"
            )
    if cfg.v3 and predictor is None:
        raise ValueError("v3=True requires a predictor module (build_predictor)")
    if cfg.v3 and cfg.num_negatives:
        raise ValueError("v3 is queue-free: set num_negatives=0")
    if cfg.momentum_cos and total_steps is None:
        raise ValueError("momentum_cos=True needs total_steps for the cosine ramp")
    def ema_momentum(step):
        """Constant m, or moco-v3's cosine ramp m -> 1.0 over training."""
        if not cfg.momentum_cos:
            return cfg.momentum
        # Clamp: a mid-epoch preemption resume can replay steps past
        # total_steps; without the clip cos(pi*frac) passes -1 and the
        # EMA momentum would ramp back DOWN from 1.0.
        frac = jnp.clip(step.astype(jnp.float32) / total_steps, 0.0, 1.0)
        return 1.0 - (1.0 - cfg.momentum) * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    n_data = mesh.shape[DATA_AXIS]
    n_model = mesh.shape.get(MODEL_AXIS, 1)
    global_batch = config.data.global_batch
    if global_batch % n_data:
        raise ValueError(f"global batch {global_batch} not divisible by data axis {n_data}")
    if cfg.num_negatives:
        check_queue_divisibility(cfg.num_negatives, global_batch)
    if shard_queue_over_model is None:
        shard_queue_over_model = n_model > 1 and cfg.num_negatives > 0
    if shard_queue_over_model and cfg.num_negatives % (n_model * max(global_batch, 1)):
        raise ValueError("sharded queue requires K % (num_model*global_batch) == 0")
    zero = config.parallel.shard_weight_update
    zero23 = zero_stage23(config)
    if zero:
        if config.parallel.zero_stage not in (1, 2, 3):
            raise ValueError(
                f"zero_stage must be 1, 2 or 3, got {config.parallel.zero_stage}"
            )
        if config.optim.optimizer == "lars":
            # LARS trust ratios need whole-tensor norms; a flat shard
            # cannot compute them (moco_tpu/parallel/zero.py docstring)
            raise ValueError("shard_weight_update supports element-wise optimizers only (sgd/adamw), not lars")
        if state_template is None:
            raise ValueError("shard_weight_update needs state_template for the opt-state sharding specs")
    # ZeRO-2/3 static machinery: the persistent (n, m) layout loses the
    # original leaf shapes, so the bucket plans (and the in-step
    # reconstruction of full leaves) derive from an abstract init
    plan_trainable = plan_enc = None
    trainable_shapes = None
    zero_layer = zero_layer_granular(config)
    if config.parallel.zero_layer_granular and not zero23:
        raise ValueError(
            "zero_layer_granular requires shard_weight_update=True with "
            "zero_stage >= 2 (the per-group schedule runs on the persistent "
            "shard layout)"
        )
    if zero23:
        trainable_shapes = full_param_shapes(config, encoder, predictor)
        bucket_bytes = int(config.parallel.zero_bucket_mb * 1024 * 1024)
        plan_trainable = BucketPlan(
            jax.tree.leaves(trainable_shapes), n_data, bucket_bytes
        )
        plan_enc = BucketPlan(
            jax.tree.leaves(trainable_shapes["enc"]), n_data, bucket_bytes
        )
        _trainable_def = jax.tree.structure(trainable_shapes)
        _enc_def = jax.tree.structure(trainable_shapes["enc"])
    # ---- layer-granular stage 2/3 static machinery ---------------------
    # GroupPlan over the encoder leaves (backbone groups in schedule
    # order, then the projection head) + a separate single-group plan
    # for the predictor; the analytic HBM peak for BOTH schedules so
    # tests can compare them without device memory_stats.
    enc_group_plan = None
    pred_bucket_plan = None
    g_names: tuple = ()
    hbm_model_peak_bytes = None
    if zero23:
        _shard_resident = _tree_shard_bytes_analytic(
            trainable_shapes, n_data
        ) + _tree_shard_bytes_analytic(trainable_shapes["enc"], n_data)
        hbm_model_peak_bytes = (
            _shard_resident
            + _tree_full_bytes(trainable_shapes)
            + _tree_full_bytes(trainable_shapes["enc"])
        )
    if zero_layer:
        if n_model > 1:
            raise ValueError(
                "zero_layer_granular requires num_model == 1 (the per-group "
                "schedule is a data-axis pipeline; model-axis sharding of the "
                "same params would double-gather)"
            )
        if cfg.vit_sequence_parallel:
            raise ValueError(
                "zero_layer_granular does not compose with vit_sequence_parallel "
                "(the token shard would cross layer-group boundaries)"
            )
        _enc_leaves = jax.tree.leaves(trainable_shapes["enc"])
        _index_tree = jax.tree.unflatten(_enc_def, list(range(len(_enc_leaves))))
        _bb_childmap = encoder.backbone.group_param_names()
        _group_specs = []
        for _g in encoder.backbone.group_names:
            _idx: list = []
            for _child in _bb_childmap[_g]:
                _idx.extend(jax.tree.leaves(_index_tree["backbone"][_child]))
            _group_specs.append((_g, tuple(_idx)))
        _group_specs.append(("head", tuple(jax.tree.leaves(_index_tree["head"]))))
        # GroupPlan raises if the backbone's group map misses any leaf —
        # a silently-ungathered param would train as garbage
        enc_group_plan = GroupPlan(_enc_leaves, _group_specs, n_data, bucket_bytes)
        g_names = tuple(g.name for g in enc_group_plan.groups)
        _pred_def = jax.tree.structure(trainable_shapes["pred"])
        _pred_bytes = _tree_full_bytes(trainable_shapes["pred"])
        if jax.tree.leaves(trainable_shapes["pred"]):
            pred_bucket_plan = BucketPlan(
                jax.tree.leaves(trainable_shapes["pred"]), n_data, bucket_bytes
            )
        # transient high-water mark of the one-group-ahead schedule: the
        # largest adjacent pair along (enc groups..., predictor)
        _sizes = [g.full_bytes for g in enc_group_plan.groups]
        if _pred_bytes:
            _sizes.append(_pred_bytes)
        _transient = (
            _sizes[0]
            if len(_sizes) == 1
            else max(a + b for a, b in zip(_sizes, _sizes[1:]))
        )
        hbm_model_peak_bytes = _shard_resident + _transient

        def _partial_enc(gname: str, full_leaves):
            """Rebuild the PARTIAL {"backbone"/"head": ...} params tree
            holding only group `gname`'s full leaves (group leaf order
            == the order `_group_specs` enumerated them). Flax never
            reads an uncalled module's params, so the grouped apply
            accepts the partial tree as-is."""
            it = iter(full_leaves)
            if gname == "head":
                d = jax.tree.structure(trainable_shapes["enc"]["head"])
                return {
                    "head": jax.tree.unflatten(
                        d, [next(it) for _ in range(d.num_leaves)]
                    )
                }
            out = {}
            for _child in _bb_childmap[gname]:
                d = jax.tree.structure(trainable_shapes["enc"]["backbone"][_child])
                out[_child] = jax.tree.unflatten(
                    d, [next(it) for _ in range(d.num_leaves)]
                )
            return {"backbone": out}

        from moco_tpu.parallel.compat import optimization_barrier

        def _tie(leaves_list, anchor):
            """One-group-ahead liveness bound: barrier-tie the NEXT
            group's gather inputs to the CURRENT group's input
            activation, so XLA may overlap that gather with the current
            group's compute but cannot hoist it any earlier — at most
            two adjacent groups' full params are ever live."""
            tied = optimization_barrier((tuple(leaves_list), anchor))
            return list(tied[0])

        def layer_key_forward(params_k0, shards_k, stats, x, train=True):
            """Grouped key forward (no grad): group 0's full params
            arrive pre-gathered from the prefetch program; each next
            group's gather is issued under the current group's compute
            (`_tie`). Returns (features, merged batch_stats)."""
            k_leaves = jax.tree.leaves(shards_k)
            cur_params = params_k0
            for gi, gname in enumerate(g_names):
                if gi + 1 < len(g_names):
                    nxt = enc_group_plan.group_shards(k_leaves, gi + 1)
                    nxt = _tie(nxt, x)
                    nxt_full = enc_group_plan.gather_group(
                        nxt, gi + 1, site_prefix="zero.gather.k"
                    )
                x, mut = encoder.apply(
                    {"params": cur_params, "batch_stats": stats},
                    x,
                    train=train,
                    mutable=["batch_stats"],
                    group=gname,
                )
                stats = _overlay(stats, mut.get("batch_stats", {}))
                if gi + 1 < len(g_names):
                    cur_params = _partial_enc(g_names[gi + 1], nxt_full)
            return x, stats

        def _make_q_segment(gi: int, gname: str):
            """One rematerialized query segment: gather the group's full
            params + run the group. `jax.checkpoint` drops the full
            params (and activations) after the forward and re-gathers in
            the backward — true ZeRO-3: backward too only ever holds one
            group's full params, at one extra gather of comms.

            Numerics: the LOSS trajectory is bitwise identical to the
            whole-tree stage (remat recomputes the same forward values),
            and on ResNet the gradients are too. On ViT, `jax.checkpoint`
            alone — no sharding, single device — shifts backward
            gradients by ~1e-9 ULPs on CPU (XLA fuses the rematerialized
            backward differently around layernorm/attention reductions),
            so ViT params track the baseline to ~1e-5 rather than
            bitwise; tests assert accordingly."""

            def seg(group_shards, x, stats):
                full = enc_group_plan.gather_group(
                    list(group_shards), gi, site_prefix="zero.gather.q"
                )
                out, mut = encoder.apply(
                    {"params": _partial_enc(gname, full), "batch_stats": stats},
                    x,
                    train=True,
                    mutable=["batch_stats"],
                    group=gname,
                )
                return out, mut.get("batch_stats", {})

            return jax.checkpoint(seg)

        _q_segments = [_make_q_segment(gi, g) for gi, g in enumerate(g_names)]

        def layer_query_forward(enc_sh, stats_q, x):
            """Grouped query forward over the SHARD tree. Each group's
            gather is tied one group ahead (to the previous segment's
            input), same liveness bound as the key side. Gradients flow
            through the in-segment gathers: their AD transpose is the
            bucketed psum_scatter, landing SUMMED cotangents directly on
            the (m,) shards. Stats thread SEQUENTIALLY through the
            segments (like the key side): flax returns the FULL mutated
            collection from a grouped apply, so feeding each segment the
            original stats would let later groups' returns clobber
            earlier groups' fresh running-stat updates in the overlay —
            and momentum-statistics BN reads the running values
            in-forward, so sequential threading is also the semantics
            that matches the whole-tree apply."""
            leaves = jax.tree.leaves(enc_sh)
            stats = stats_q
            prev_in = None
            for gi, seg in enumerate(_q_segments):
                gs = enc_group_plan.group_shards(leaves, gi)
                if prev_in is not None:
                    gs = _tie(gs, prev_in)
                cur_in = x
                x, mut = seg(tuple(gs), x, stats)
                stats = _overlay(stats, mut)
                prev_in = cur_in
            return x, stats

        def layer_pred_forward(pred_sh, stats_pred, feats):
            """Predictor segment (v3): one more group on the query
            schedule, same gather-inside-remat structure."""
            leaves = tuple(jax.tree.leaves(pred_sh))

            def seg(lvs, feats, stats):
                full = pred_bucket_plan.gather(list(lvs), site="zero.gather.q.pred")
                params = jax.tree.unflatten(_pred_def, full)
                out, mut = predictor.apply(
                    {"params": params, "batch_stats": stats},
                    feats,
                    train=True,
                    mutable=["batch_stats"],
                )
                return out, mut.get("batch_stats", {})

            return jax.checkpoint(seg)(leaves, feats, stats_pred)
    # Fused streaming InfoNCE (pallas): auto-on for a TPU backend with a
    # replicated, tile-divisible queue; explicit True forces it (interpret
    # mode off-TPU), False forces the dense logits path.
    from moco_tpu.ops.fused_infonce import DEFAULT_BLOCK_K, check_tiling

    fused_block_k = cfg.fused_block_k or DEFAULT_BLOCK_K
    use_fused = cfg.fused_infonce
    if use_fused:
        # an explicit request fails at build time, not at first trace
        check_tiling(cfg.num_negatives, fused_block_k)
    if use_fused is None:
        use_fused = (
            jax.default_backend() == "tpu"
            and not (shard_queue_over_model or n_model > 1)
            and cfg.num_negatives > 0
            and cfg.num_negatives % fused_block_k == 0
        )
    if use_fused and shard_queue_over_model:
        raise ValueError("fused_infonce does not support a model-sharded queue")

    def apply_encoder(params, batch_stats, x, train=True):
        out, mut = encoder.apply(
            {"params": params, "batch_stats": batch_stats},
            x,
            train=train,
            mutable=["batch_stats"],
        )
        return out, mut["batch_stats"]

    # Rematerialization: recompute the query forward during backward
    # instead of keeping every activation live (SURVEY.md hard-part 6 /
    # the HBM-vs-FLOPs trade). Key-side forwards carry no gradient, so
    # only the grad-bearing query apply is wrapped.
    # A backbone that recomputes block by block itself (`remat` of its own)
    # is not wrapped a second time: that would run its forward three times.
    whole_remat = cfg.remat and not getattr(encoder.backbone, "remat", False)
    grad_apply_encoder = (
        jax.checkpoint(lambda p, s, x: apply_encoder(p, s, x)) if whole_remat else apply_encoder
    )

    def apply_predictor(params, batch_stats, x, train=True):
        out, mut = predictor.apply(
            {"params": params, "batch_stats": batch_stats},
            x,
            train=train,
            mutable=["batch_stats"],
        )
        return out, mut["batch_stats"]

    def zero23_update(state: MocoState, grads):
        """ZeRO-2/3 weight update on the persistent shards: bucketed
        psum_scatter of the full local grads (one collective per fusion
        bucket, issued as backward produces each bucket's leaves), then
        the elementwise optimizer on this replica's (m,) rows only. NO
        trailing all_gather — the params stay sharded; the next step's
        gather re-materializes them. Returns (old shard trees, new
        shard trees, expanded opt state)."""
        grad_leaves, grad_def = jax.tree.flatten(grads)
        grad_sh = jax.tree.unflatten(
            grad_def, plan_trainable.scatter_mean(grad_leaves, site="zero.scatter")
        )
        trainable_sh = {
            "enc": squeeze_opt_state(state.params_q),
            "pred": squeeze_opt_state(state.params_pred),
        }
        updates, new_opt = tx.update(
            grad_sh, squeeze_opt_state(state.opt_state), trainable_sh
        )
        new_tr_sh = jax.tree.map(lambda p, u: p + u, trainable_sh, updates)
        return trainable_sh, new_tr_sh, expand_opt_state(new_opt)

    def zero_layer_update(state: MocoState, grad_sh):
        """Layer-granular weight update: the in-segment gathers' AD
        transposes already psum_scatter'd the grads onto the (m,)
        shards as cross-replica SUMS — divide by n for the mean
        (element→row assignment and ring order match `scatter_mean`,
        so the result is bit-identical to `zero23_update`'s), then the
        elementwise optimizer on this replica's rows. Same return
        contract as `zero23_update`."""
        grad_sh = jax.tree.map(lambda g: g / n_data, grad_sh)
        trainable_sh = {
            "enc": squeeze_opt_state(state.params_q),
            "pred": squeeze_opt_state(state.params_pred),
        }
        updates, new_opt = tx.update(
            grad_sh, squeeze_opt_state(state.opt_state), trainable_sh
        )
        new_tr_sh = jax.tree.map(lambda p, u: p + u, trainable_sh, updates)
        return trainable_sh, new_tr_sh, expand_opt_state(new_opt)

    def gather_core(state: MocoState) -> ZeroGathered:
        """ZeRO-2/3 step-start stage, hoisted into the pipelined driver
        so it hides under the previous step's compute: the EMA key
        update runs SHARD-LOCAL (elementwise on this replica's rows —
        no collective at all), then one bucketed all_gather per param
        family re-materializes the full trees the step consumes."""
        m = ema_momentum(state.step)
        trainable_sh = {
            "enc": squeeze_opt_state(state.params_q),
            "pred": squeeze_opt_state(state.params_pred),
        }
        with jax.named_scope("moco.ema"):
            k_sh = ema_update(
                squeeze_opt_state(state.params_k), trainable_sh["enc"], m
            )
        t_leaves, t_def = jax.tree.flatten(trainable_sh)
        trainable_full = jax.tree.unflatten(
            t_def, plan_trainable.gather(t_leaves, site="zero.gather_q")
        )
        k_leaves, k_def = jax.tree.flatten(k_sh)
        params_k_full = jax.tree.unflatten(
            k_def, plan_enc.gather(k_leaves, site="zero.gather_k")
        )
        return ZeroGathered(
            trainable=trainable_full,
            params_k=params_k_full,
            shards_k=expand_opt_state(k_sh),
        )

    def gather_core_layer(state: MocoState) -> ZeroGathered:
        """Layer-granular prefetch program: same shard-local EMA as
        `gather_core`, but gather ONLY key group 0 — the step's in-loop
        pipeline gathers each next key group under the previous group's
        compute, and the query side re-gathers inside its rematerialized
        segments, so nothing else pre-materializes. `trainable` is empty:
        the layer step differentiates over the shards directly."""
        m = ema_momentum(state.step)
        enc_sh = squeeze_opt_state(state.params_q)
        with jax.named_scope("moco.ema"):
            k_sh = ema_update(squeeze_opt_state(state.params_k), enc_sh, m)
        k_leaves = jax.tree.leaves(k_sh)
        g0_full = enc_group_plan.gather_group(
            enc_group_plan.group_shards(k_leaves, 0), 0, site_prefix="zero.gather.k"
        )
        return ZeroGathered(
            trainable={},
            params_k=_partial_enc(g_names[0], g0_full),
            shards_k=expand_opt_state(k_sh),
        )

    def v3_step(state: MocoState, batch, gathered: Optional[ZeroGathered] = None):
        """MoCo v3 (arXiv:2104.02057 alg. 1): symmetric queue-free
        contrastive loss, both views through both encoders, the global
        batch as negatives, 2τ loss scaling. `gathered` (ZeRO-2/3): the
        full params arrive pre-gathered (EMA already applied shard-local
        in the gather stage) and the update writes back to shards."""
        im_q, im_k = batch["im_q"], batch["im_k"]
        local_b = im_q.shape[0]
        x_cat = jnp.concatenate([im_q, im_k], axis=0)

        if zero_layer:
            params_k = None
        elif gathered is None:
            with jax.named_scope("moco.ema"):
                params_k = ema_update(
                    state.params_k, state.params_q, ema_momentum(state.step)
                )
        else:
            params_k = gathered.params_k
        with jax.named_scope("moco.key_encoder"):
            if zero_layer:
                # grouped key forward over the freshly-EMA'd shards; group 0
                # arrives pre-gathered from the prefetch program
                k_cat, stats_k = layer_key_forward(
                    gathered.params_k,
                    squeeze_opt_state(gathered.shards_k),
                    state.batch_stats_k,
                    x_cat,
                )
            else:
                k_cat, stats_k = apply_encoder(params_k, state.batch_stats_k, x_cat)
            k1, k2 = jnp.split(lax.stop_gradient(l2_normalize(k_cat)), 2, axis=0)
            if n_data > 1:
                with comms.tag("v3.key_gather", "all_gather", (k1, k2), n_data):
                    k1_g = lax.all_gather(k1, DATA_AXIS).reshape(-1, cfg.dim)
                    k2_g = lax.all_gather(k2, DATA_AXIS).reshape(-1, cfg.dim)
                rank = lax.axis_index(DATA_AXIS)
            else:
                k1_g, k2_g, rank = k1, k2, 0
        labels = rank * local_b + jnp.arange(local_b, dtype=jnp.int32)

        def ctr(q, k_g):
            logits = q @ k_g.T / cfg.temperature
            return 2.0 * cfg.temperature * cross_entropy(logits, labels), logits

        def loss_fn(trainable):
            with jax.named_scope("moco.query_encoder"):
                if zero_layer:
                    # layer-granular: `trainable` is the SHARD tree; each
                    # segment gathers its group's full params just-in-time
                    feats, stats_q = layer_query_forward(
                        trainable["enc"], state.batch_stats_q, x_cat
                    )
                    preds, stats_pred = layer_pred_forward(
                        trainable["pred"], state.batch_stats_pred, feats
                    )
                else:
                    feats, stats_q = grad_apply_encoder(
                        trainable["enc"], state.batch_stats_q, x_cat
                    )
                    preds, stats_pred = apply_predictor(
                        trainable["pred"], state.batch_stats_pred, feats
                    )
            with jax.named_scope("moco.contrastive_loss"):
                q1, q2 = jnp.split(l2_normalize(preds), 2, axis=0)
                loss1, logits = ctr(q1, k2_g)
                loss2, _ = ctr(q2, k1_g)
            return loss1 + loss2, (stats_q, stats_pred, logits, q1)

        if zero_layer:
            trainable = {
                "enc": squeeze_opt_state(state.params_q),
                "pred": squeeze_opt_state(state.params_pred),
            }
        else:
            trainable = (
                {"enc": state.params_q, "pred": state.params_pred}
                if gathered is None
                else gathered.trainable
            )
        (loss, (stats_q, stats_pred, logits, q1)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(trainable)
        with jax.named_scope("moco.optimizer"):
            if cfg.freeze_patch_embed and "patch_embed" in grads["enc"].get("backbone", {}):
                grads["enc"]["backbone"]["patch_embed"] = jax.tree.map(
                    jnp.zeros_like, grads["enc"]["backbone"]["patch_embed"]
                )
            if cfg.vit_sequence_parallel:
                # Sequence parallelism: each model-axis member backprops only
                # through ITS token shard, so backbone grads are PARTIAL sums
                # — psum over the sequence (model) axis restores the full
                # gradient. Head/predictor grads are replicated-identical
                # (they consume the psum-pooled feature) and stay untouched.
                with comms.tag(
                    "grad.seq_psum", "psum", grads["enc"]["backbone"], n_model
                ):
                    grads["enc"]["backbone"] = lax.psum(
                        grads["enc"]["backbone"], MODEL_AXIS
                    )
        metrics = {"loss": loss, **topk_accuracy(logits, labels)}
        metrics = lax.pmean(metrics, DATA_AXIS)
        stats_q = lax.pmean(stats_q, DATA_AXIS)
        stats_k = lax.pmean(stats_k, DATA_AXIS)
        stats_pred = lax.pmean(stats_pred, DATA_AXIS)

        with jax.named_scope("moco.optimizer"):
            if gathered is not None:
                # ZeRO-2/3: bucketed psum_scatter + shard-local update; the
                # params never re-materialize — the next step's gather does.
                # In layer mode the scatter already ran inside the segments'
                # backward, so `grads` arrived as summed (m,) shards.
                if zero_layer:
                    trainable_sh, new_tr_sh, opt_state = zero_layer_update(state, grads)
                else:
                    trainable_sh, new_tr_sh, opt_state = zero23_update(state, grads)
                if cfg.freeze_patch_embed and "patch_embed" in new_tr_sh["enc"].get(
                    "backbone", {}
                ):
                    # zeroed grads stop the gradient; restoring the OLD
                    # shards also blocks AdamW's decoupled decay — the
                    # shard-level mirror of the stage-1 full-params freeze
                    new_tr_sh["enc"]["backbone"]["patch_embed"] = trainable_sh["enc"][
                        "backbone"
                    ]["patch_embed"]
                drift = lambda: obs_health.ema_drift_sharded(
                    new_tr_sh["enc"], squeeze_opt_state(gathered.shards_k), DATA_AXIS
                )
                out_params = dict(
                    params_q=expand_opt_state(new_tr_sh["enc"]),
                    params_pred=expand_opt_state(new_tr_sh["pred"]),
                    params_k=gathered.shards_k,
                )
            elif zero:
                # Sharded weight update (parallel/zero.py stage 1):
                # psum_scatter fuses the grad mean-reduction with the 1/n
                # sharding. The patch-embed freeze is applied to the
                # gathered FULL params below, so AdamW's decoupled decay
                # cannot move them either.
                frozen_pe = (
                    trainable["enc"]["backbone"]["patch_embed"]
                    if cfg.freeze_patch_embed
                    and "patch_embed" in trainable["enc"].get("backbone", {})
                    else None
                )
                new_trainable, opt_state = sharded_update(
                    tx, grads, state.opt_state, trainable
                )
                if frozen_pe is not None:
                    new_trainable["enc"]["backbone"]["patch_embed"] = frozen_pe
                drift = lambda: obs_health.ema_drift(new_trainable["enc"], params_k)
                out_params = dict(
                    params_q=new_trainable["enc"],
                    params_pred=new_trainable["pred"],
                    params_k=params_k,
                )
            else:
                with comms.tag("grad.psum", "psum", grads, n_data):
                    grads = lax.pmean(grads, DATA_AXIS)
                updates, opt_state = tx.update(grads, state.opt_state, trainable)
                if cfg.freeze_patch_embed and "patch_embed" in updates["enc"].get("backbone", {}):
                    # zeroed grads are not enough: AdamW's decoupled weight decay
                    # still moves zero-grad params, so zero the *update* as well
                    updates["enc"]["backbone"]["patch_embed"] = jax.tree.map(
                        jnp.zeros_like, updates["enc"]["backbone"]["patch_embed"]
                    )
                new_trainable = optax.apply_updates(trainable, updates)
                drift = lambda: obs_health.ema_drift(new_trainable["enc"], params_k)
                out_params = dict(
                    params_q=new_trainable["enc"],
                    params_pred=new_trainable["pred"],
                    params_k=params_k,
                )
        with jax.named_scope("moco.health"):
            if health_on:
                # batch-local stats pmean over data; drift is a function of
                # replicated params — or, at ZeRO stage 2/3, of the shards
                # with a psum'd norm (v3 has no queue, so no staleness gauges)
                hlocal = {
                    **obs_health.logit_stats_from_dense(logits, labels),
                    **obs_health.feature_stats(q1),
                }
                metrics.update(lax.pmean(hlocal, DATA_AXIS))
                metrics.update(drift())
        new_state = state.replace(
            step=state.step + 1,
            batch_stats_q=stats_q,
            batch_stats_k=stats_k,
            batch_stats_pred=stats_pred,
            opt_state=opt_state,
            **out_params,
        )
        return new_state, metrics

    def step_fn(state: MocoState, batch, root_rng, gathered: Optional[ZeroGathered] = None):
        if cfg.v3:
            return v3_step(state, batch, gathered=gathered)
        im_q, im_k = batch["im_q"], batch["im_k"]
        local_b = jax.tree.leaves(im_q)[0].shape[0]
        # Deterministic per-step randomness, identical on every device:
        # replaces the reference's `broadcast(idx_shuffle, src=0)`
        # (moco/builder.py:~L89).
        step_rng = jax.random.fold_in(root_rng, state.step)

        # (1) EMA momentum update of the key encoder, *before* the key
        # forward, as upstream orders it (moco/builder.py:~L139-141).
        # At ZeRO stage 2/3 both encoders live as shards and the EMA
        # already ran shard-local inside the gather stage.
        if zero_layer:
            # grouped key forward (one-group-ahead pipeline); group 0
            # arrives pre-gathered from the prefetch program
            params_k = None
            _k_shards = squeeze_opt_state(gathered.shards_k)
            key_apply = lambda stats, x, train=True: layer_key_forward(
                gathered.params_k, _k_shards, stats, x, train=train
            )
        else:
            if gathered is None:
                with jax.named_scope("moco.ema"):
                    params_k = ema_update(
                        state.params_k, state.params_q, ema_momentum(state.step)
                    )
            else:
                params_k = gathered.params_k
            key_apply = lambda stats, x, train=True: apply_encoder(
                params_k, stats, x, train=train
            )

        # (2) Shuffle-BN: compute keys on a batch that contains none of
        # this device's own positives. With bn_virtual_groups the same
        # permutation machinery runs even on ONE device (all_gather over
        # a size-1 axis is the identity, so gather_perm degrades to a
        # pure in-batch permutation): per-group BN statistics + permuted
        # group composition = the reference's G-GPU Shuffle-BN inside a
        # single chip's batch.
        with jax.named_scope("moco.key_encoder"):
            shuffle_active = n_data > 1 or cfg.bn_virtual_groups > 1
            if cfg.shuffle == "gather_perm" and shuffle_active:
                perm, inv_perm = make_permutation(step_rng, global_batch)
                im_k_sh = shuffle_gather(im_k, perm, DATA_AXIS)
                k_sh, stats_k = key_apply(state.batch_stats_k, im_k_sh)
                k_sh = l2_normalize(k_sh)
                k_local, k_global = unshuffle_gather(k_sh, inv_perm, DATA_AXIS)
            elif cfg.shuffle == "a2a" and shuffle_active:
                im_k_sh = balanced_shuffle(step_rng, im_k, DATA_AXIS)
                k_sh, stats_k = key_apply(state.batch_stats_k, im_k_sh)
                k_sh = l2_normalize(k_sh)
                # the unshuffle must regenerate the SAME permutation as the
                # shuffle above, so reusing step_rng is the contract, not a bug
                k_local = balanced_unshuffle(step_rng, k_sh, DATA_AXIS)  # mocolint: disable=JX003
                with comms.tag("queue.enqueue_gather", "all_gather", k_local, n_data):
                    k_global = lax.all_gather(k_local, DATA_AXIS).reshape(-1, cfg.dim)
            else:  # 'syncbn' (cross-replica BN handles decorrelation) or 'none'
                # key_bn_running_stats (EMAN, config.py rationale): the key
                # forward runs EVAL-mode BN against the EMA'd running stats —
                # no statistics pass, no composition leak, no shuffle
                # collectives; the returned stats tree is unchanged and is
                # replaced by the EMA advance in (4) below.
                k_local, stats_k = key_apply(
                    state.batch_stats_k, im_k, train=not cfg.key_bn_running_stats
                )
                k_local = l2_normalize(k_local)
                if n_data > 1:
                    with comms.tag("queue.enqueue_gather", "all_gather", k_local, n_data):
                        k_global = lax.all_gather(k_local, DATA_AXIS).reshape(-1, cfg.dim)
                else:
                    k_global = k_local
            k_local = lax.stop_gradient(k_local)
            k_global = lax.stop_gradient(k_global)

        # (3) Query forward + InfoNCE loss (moco/builder.py:~L128-161).
        def loss_fn(trainable):
            with jax.named_scope("moco.query_encoder"):
                if zero_layer:
                    q, stats_q = layer_query_forward(
                        trainable["enc"], state.batch_stats_q, im_q
                    )
                else:
                    q, stats_q = grad_apply_encoder(
                        trainable["enc"], state.batch_stats_q, im_q
                    )
            with jax.named_scope("moco.contrastive_loss"):
                q = l2_normalize(q)
                if cfg.num_negatives and use_fused:
                    # streaming pallas kernel: never materializes (B, 1+K)
                    from moco_tpu.ops.fused_infonce import fused_infonce_loss

                    loss, acc = fused_infonce_loss(
                        q,
                        k_local,
                        state.queue,
                        cfg.temperature,
                        block_k=fused_block_k,
                        interpret=pallas_interpret(),
                    )
                elif cfg.num_negatives:
                    logits, labels = infonce_logits(q, k_local, state.queue, cfg.temperature)
                    if shard_queue_over_model:
                        # queue rows are sharded over `model`: logits currently
                        # hold [pos | my negative shard]; assemble full rows.
                        l_pos, l_neg = logits[:, :1], logits[:, 1:]
                        with comms.tag("queue.logits_gather", "all_gather", l_neg, n_model):
                            l_neg = lax.all_gather(l_neg, MODEL_AXIS, axis=1, tiled=True)
                        logits = jnp.concatenate([l_pos, l_neg], axis=1)
                    loss = cross_entropy(logits, labels)
                    acc = topk_accuracy(logits, labels)
                else:
                    # v3-style queue-free: global batch keys are the negatives.
                    logits = q @ k_global.T / cfg.temperature
                    rank = lax.axis_index(DATA_AXIS)
                    labels = rank * local_b + jnp.arange(local_b, dtype=jnp.int32)
                    loss = cross_entropy(logits, labels)
                    acc = topk_accuracy(logits, labels)
            return loss, (stats_q, acc, q)

        if zero_layer:
            trainable = {
                "enc": squeeze_opt_state(state.params_q),
                "pred": squeeze_opt_state(state.params_pred),
            }
        else:
            trainable = (
                {"enc": state.params_q, "pred": state.params_pred}
                if gathered is None
                else gathered.trainable
            )
        (loss, (stats_q, acc, q_feats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            trainable
        )

        # (4) Gradient + metric reduction over data (DDP all-reduce equiv).
        # With a model-sharded queue the backward of the MODEL-axis
        # all_gather is a reduce-scatter: shard m's grads carry only (M x)
        # its own negative shard's contribution, so they must also be
        # pmean'd over MODEL — the factor M cancels exactly, restoring the
        # replicated-params invariant.
        metrics = {"loss": loss, **acc}
        metrics = lax.pmean(metrics, DATA_AXIS)
        # Running BN stats: average across devices (strictly better than
        # the reference, which checkpoints rank 0's local stats).
        stats_q = lax.pmean(stats_q, DATA_AXIS)
        # what the query encoder's expert layers left there this step
        # ({} for an encoder without any): rides the log line's one fetch
        metrics.update(routing_metrics(stats_q))
        if isinstance(im_q, dict):  # token rows: the valid tokens of both views
            metrics["tokens_per_step"] = lax.psum(
                jnp.sum(im_q["lengths"]) + jnp.sum(im_k["lengths"]), DATA_AXIS
            )
        if cfg.key_bn_running_stats:
            # the key's running statistics trail the query's on the
            # params' momentum schedule (EMAN); stats_q is already
            # pmean'd, so the EMA stays replicated in lockstep
            m_stats = ema_momentum(state.step)
            if cfg.key_bn_stats_warmup:
                # fast-track early statistics (tf.train.EMA num_updates
                # schedule): at m=0.999 a cold-start EMA would normalize
                # keys with badly stale statistics for hundreds of steps
                # — the r4 accuracy arm's suspected failure mechanism
                step_f = state.step.astype(jnp.float32)
                m_stats = jnp.minimum(m_stats, (1.0 + step_f) / (10.0 + step_f))
            with jax.named_scope("moco.ema"):
                stats_k = ema_update(state.batch_stats_k, stats_q, m_stats)
        else:
            stats_k = lax.pmean(stats_k, DATA_AXIS)

        # (5) Optimizer update: replicated full update, or — with
        # shard_weight_update — ZeRO-style (parallel/zero.py): the grad
        # psum_scatter replaces the pmean at identical comm volume, the
        # optimizer touches only this replica's 1/n shard, and an
        # all_gather rebuilds the full params (stage 1) — or never does,
        # because the params persist as shards (stage 2/3).
        with jax.named_scope("moco.optimizer"):
            if gathered is not None:
                if shard_queue_over_model:
                    grads = lax.pmean(grads, MODEL_AXIS)
                if zero_layer:
                    _, new_tr_sh, opt_state = zero_layer_update(state, grads)
                else:
                    _, new_tr_sh, opt_state = zero23_update(state, grads)
                drift = lambda: obs_health.ema_drift_sharded(
                    new_tr_sh["enc"], squeeze_opt_state(gathered.shards_k), DATA_AXIS
                )
                out_params = dict(
                    params_q=expand_opt_state(new_tr_sh["enc"]),
                    params_pred=expand_opt_state(new_tr_sh["pred"]),
                    params_k=gathered.shards_k,
                )
            elif zero:
                if shard_queue_over_model:
                    grads = lax.pmean(grads, MODEL_AXIS)
                new_trainable, opt_state = sharded_update(
                    tx, grads, state.opt_state, trainable
                )
                params_q = new_trainable["enc"]
                drift = lambda: obs_health.ema_drift(params_q, params_k)
                out_params = dict(params_q=params_q, params_k=params_k)
            else:
                grad_axes = (DATA_AXIS, MODEL_AXIS) if shard_queue_over_model else DATA_AXIS
                grad_world = n_data * (n_model if shard_queue_over_model else 1)
                with comms.tag("grad.psum", "psum", grads, grad_world):
                    grads = lax.pmean(grads, grad_axes)
                updates, opt_state = tx.update(grads, state.opt_state, trainable)
                params_q = optax.apply_updates(trainable, updates)["enc"]
                drift = lambda: obs_health.ema_drift(params_q, params_k)
                out_params = dict(params_q=params_q, params_k=params_k)

        # (6) FIFO enqueue of the global key batch
        # (moco/builder.py:~L62-77); with a model-sharded queue each shard
        # writes only the rows that fall inside it.
        with jax.named_scope("moco.enqueue"):
            if cfg.num_negatives:
                if shard_queue_over_model:
                    shard_rows = cfg.num_negatives // n_model
                    m_rank = lax.axis_index(MODEL_AXIS)
                    offset = m_rank * shard_rows
                    local_ptr = state.queue_ptr - offset
                    in_range = (local_ptr >= 0) & (local_ptr + global_batch <= shard_rows)
                    safe_ptr = jnp.clip(local_ptr, 0, shard_rows - global_batch)
                    written, _ = enqueue(state.queue, safe_ptr, k_global)
                    queue = jnp.where(in_range, written, state.queue)
                    queue_ptr = (state.queue_ptr + global_batch) % cfg.num_negatives
                else:
                    queue, queue_ptr = enqueue(state.queue, state.queue_ptr, k_global)
            else:
                queue, queue_ptr = state.queue, state.queue_ptr

        # (7) Training-health gauges (obs/health.py), identical math on
        # the fused and dense paths: positives recomputed from the
        # (q, k) diagonal; negatives from a bounded queue sample (the
        # full K-row pass is exactly what the fused kernel avoids
        # materializing), in post-temperature units.
        with jax.named_scope("moco.health"):
            if health_on:
                q_h = lax.stop_gradient(q_feats)
                pos_l = jnp.sum(q_h * k_local, axis=-1) / cfg.temperature
                if cfg.num_negatives:
                    rows = min(1024, state.queue.shape[0])
                    neg_ref = lax.stop_gradient(state.queue[:rows])
                else:
                    # queue-free: the gathered key batch is the negative set
                    # (contains each row's own positive — 1/B_global of the
                    # sample, negligible contamination for a gauge)
                    neg_ref = k_global
                neg_l = (q_h @ neg_ref.T) / cfg.temperature
                hlocal = {
                    **obs_health.logit_stats(pos_l, neg_l),
                    **obs_health.feature_stats(q_h),
                }
                metrics.update(lax.pmean(hlocal, DATA_AXIS))
                metrics.update(drift())
                if cfg.num_negatives:
                    metrics.update(
                        obs_health.queue_age(state.step, cfg.num_negatives, global_batch)
                    )

        new_state = state.replace(
            step=state.step + 1,
            batch_stats_q=stats_q,
            batch_stats_k=stats_k,
            queue=queue,
            queue_ptr=queue_ptr,
            opt_state=opt_state,
            **out_params,
        )
        return new_state, metrics

    specs = state_specs(
        shard_queue_over_model,
        zero_opt_state=state_template.opt_state if zero else None,
        zero_params=zero23,
    )
    batch_spec = {"im_q": P(DATA_AXIS), "im_k": P(DATA_AXIS)}
    # Explicit in/out shardings matter: letting jit infer them from a
    # SingleDeviceSharding initial state makes every later call re-lay-out
    # the whole state (cost on the v5e not re-measured). Callers should
    # `place_state` the initial state onto the mesh.
    to_sharding = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )
    state_shardings = to_sharding(specs)
    if not zero23:
        sharded = shard_map(
            step_fn,
            mesh=mesh,
            in_specs=(specs, batch_spec, P()),
            out_specs=(specs, P()),
            check_vma=False,
        )
        jit_kwargs = dict(
            in_shardings=(state_shardings, to_sharding(batch_spec), NamedSharding(mesh, P())),
            out_shardings=(state_shardings, NamedSharding(mesh, P())),
        )
        # Donation halves peak state memory; its step-time cost on the v5e
        # is not re-measured (one earlier reading: -2 %, PROFILE.md), and
        # state buffers are small relative to HBM, so it stays opt-in.
        if donate:
            jit_kwargs["donate_argnums"] = 0
        return jax.jit(sharded, **jit_kwargs)

    # -- ZeRO-2/3: two jitted programs, (gather, step) -------------------
    gathered_specs = ZeroGathered(
        trainable=P(), params_k=P(), shards_k=P(DATA_AXIS, None)
    )
    gather_sharded = shard_map(
        gather_core_layer if zero_layer else gather_core,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=gathered_specs,
        check_vma=False,
    )
    gather_jit = jax.jit(
        gather_sharded,
        in_shardings=(state_shardings,),
        out_shardings=to_sharding(gathered_specs),
    )
    step_sharded = shard_map(
        lambda state, gathered, batch, rng: step_fn(state, batch, rng, gathered=gathered),
        mesh=mesh,
        in_specs=(specs, gathered_specs, batch_spec, P()),
        out_specs=(specs, P()),
        check_vma=False,
    )
    step_kwargs = dict(
        in_shardings=(
            state_shardings,
            to_sharding(gathered_specs),
            to_sharding(batch_spec),
            NamedSharding(mesh, P()),
        ),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
    )
    # The gathered full params are one-shot by construction: donating
    # them lets XLA reuse their HBM during the backward, so peak ~
    # shards + one live gathered copy, never two. CPU lacks donation
    # support (it would only warn), so gate on the backend.
    donate_nums = tuple(
        ([0] if donate else []) + ([1] if jax.default_backend() in ("tpu", "gpu") else [])
    )
    if donate_nums:
        step_kwargs["donate_argnums"] = donate_nums
    return Zero23TrainStep(
        gather=gather_jit,
        step=jax.jit(step_sharded, **step_kwargs),
        param_shapes=trainable_shapes,
        bucket_plans={"trainable": plan_trainable, "enc": plan_enc},
        group_plan=enc_group_plan,
        layer_granular=zero_layer,
        hbm_model_peak_bytes=hbm_model_peak_bytes,
    )


def place_state(
    state: MocoState,
    mesh: Mesh,
    shard_queue_over_model: bool = False,
    zero: bool = False,
    zero_params: bool = False,
) -> MocoState:
    """device_put the state into the mesh shardings the train step expects.
    `zero=True` shards the (n, m) opt-state leaves over `data` (sharded
    weight update, parallel/zero.py); `zero_params=True` additionally
    shards the persistent param trees (ZeRO stage 2/3 layout)."""
    specs = state_specs(
        shard_queue_over_model,
        zero_opt_state=state.opt_state if zero else None,
        zero_params=zero_params,
    )
    placed = {}
    for name in state.__dataclass_fields__:
        spec = getattr(specs, name)
        value = getattr(state, name)
        if isinstance(spec, P):  # one spec for the whole subtree
            sharding = NamedSharding(mesh, spec)
            placed[name] = jax.tree.map(lambda x: jax.device_put(x, sharding), value)
        else:  # per-leaf spec tree (ZeRO opt state)
            placed[name] = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), value, spec
            )
    return MocoState(**placed)


class Zero23TrainStep:
    """The ZeRO-2/3 train step as a (gather, step) pair of jitted
    programs (make_train_step return value when zero_stage >= 2).

    - `gather(state) -> ZeroGathered`: shard-local EMA + the bucketed
      params all_gather. The pipelined driver runs this on the
      AsyncParamGather worker so step k+1's gather hides under step k.
    - `step(state, gathered, batch, rng) -> (state, metrics)`: the SPMD
      step consuming the pre-gathered full params (donated on backends
      with donation support).

    Calling the object runs both inline — the un-hoisted schedule —
    so non-pipelined callers (tests, smokes) keep the single-callable
    contract of the classic step.

    `layer_granular` marks the per-group schedule
    (`parallel.zero_layer_granular`): `gather` is then the group-0
    prefetch program and `group_plan` the encoder's `GroupPlan`.
    `hbm_model_peak_bytes` is the ANALYTIC per-chip model-memory
    high-water mark (persistent shards + the schedule's transient full
    params: whole trainable + key tree for the classic gather, the
    largest adjacent group pair for the layer schedule) — the gauge
    tests/test_zero.py compares where `device_memory_stats` is None.
    """

    def __init__(
        self,
        gather,
        step,
        param_shapes,
        bucket_plans,
        group_plan=None,
        layer_granular: bool = False,
        hbm_model_peak_bytes: Optional[int] = None,
    ):
        self.gather = gather
        self.step = step
        self.param_shapes = param_shapes  # {"enc": ..., "pred": ...} abstract
        self.bucket_plans = bucket_plans
        self.group_plan = group_plan
        self.layer_granular = layer_granular
        self.hbm_model_peak_bytes = hbm_model_peak_bytes

    def __call__(self, state, batch, root_rng):
        return self.step(state, self.gather(state), batch, root_rng)


def reshard_state(
    state_saved: MocoState,
    live_template: MocoState,
    full_template: MocoState,
) -> MocoState:
    """Host-side layout conversion between ZeRO checkpoint layouts —
    the "compatible but resharded" resume: zero1 <-> zero23, sharded <->
    replicated, and data-axis-width changes all route through the flat
    vector. `live_template` has the target layout's leaf shapes,
    `full_template` the replicated (true) shapes — needed because the
    (n, m) layout does not record them. Only the param trees and the
    optimizer state reshard; every other field passes through."""

    def _conv(saved, live, full):
        saved_np = np.asarray(saved)
        live_shape = tuple(live.shape)
        full_shape = tuple(full.shape)
        dtype = live.dtype
        if saved_np.shape == live_shape:
            return saved_np.astype(dtype)
        size = int(np.prod(full_shape)) if full_shape else 1
        flat = saved_np.reshape(-1)[:size]  # strip source padding
        if live_shape == full_shape:
            return flat.reshape(full_shape).astype(dtype)
        n, m = live_shape  # target (n, m) sharded-flat
        return np.pad(flat, (0, n * m - size)).reshape(n, m).astype(dtype)

    placed = {}
    for name in state_saved.__dataclass_fields__:
        value = getattr(state_saved, name)
        if name in ("params_q", "params_k", "params_pred", "opt_state"):
            placed[name] = jax.tree.map(
                _conv, value, getattr(live_template, name), getattr(full_template, name)
            )
        else:
            placed[name] = value
    return MocoState(**placed)
