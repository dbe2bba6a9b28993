"""Linear probe ("lincls") — the TPU-native `main_lincls.py`.

Reference semantics reproduced exactly (SURVEY.md §3.2, §2.2 row 10):
- checkpoint surgery: keep only the pretrained query encoder's *backbone*
  (`main_lincls.py:~L170-195` keeps `module.encoder_q.*`, drops the
  projection head / fc). Here backbone and head are separate modules, so
  surgery is a key lookup, not string munging — and the
  `assert missing_keys == {fc.weight, fc.bias}` check becomes structural.
- fresh classifier: weight ~ N(0, 0.01), bias = 0 (`~L160-165`).
- ONLY the classifier trains: SGD(lr=30.0, momentum=0.9, wd=0), step
  schedule [60, 80] over 100 epochs (`~L200-210`).
- the backbone runs in EVAL mode during probe training — frozen BN
  running statistics, the quirk called out in SURVEY.md §7 hard-part 4
  (`train()` calls `model.eval()`, `~L300`).
- `sanity_check()`: after training, every backbone weight is bit-identical
  to the pretrained checkpoint (`~L380-400`).
- `model_best` snapshot by validation top-1 (`~L250-260`).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from moco_tpu.core.moco import MocoState, build_encoder, create_state
from moco_tpu.data.pipeline import EvalPipeline, LabeledPipeline
from moco_tpu.models import LinearClassifier
from moco_tpu.ops.losses import cross_entropy, topk_accuracy
from moco_tpu.parallel import create_mesh
from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.utils.checkpoint import (
    CheckpointManager,
    best_exists,
    restore_best,
    save_best,
)
from moco_tpu.utils.config import (
    DataConfig,
    OptimConfig,
    ProbeConfig,
    TrainConfig,
    config_from_dict,
    config_to_dict,
    dataclass_from_dict,
)
from moco_tpu.utils.metrics import AverageMeter, MetricWriter, ProgressMeter
from moco_tpu.utils.schedules import build_optimizer


class ProbeState(struct.PyTreeNode):
    step: jax.Array
    fc_params: Any  # the only trainable leaves
    backbone_params: Any  # frozen
    backbone_stats: Any  # frozen BN running statistics
    opt_state: Any


def restore_pretrain_state(
    workdir: str,
    config: Optional[TrainConfig] = None,
    unshard: tuple = ("q",),
) -> tuple[MocoState, TrainConfig]:
    """Restore the full pretraining MocoState + its resolved config —
    the shared eval-side entry the probe surgery, the converters, and
    the serve engine all build on.

    With `config=None` the training config stored in the checkpoint's
    extras is used, so the exact model/optimizer template (arch, v3
    predictor, sgd/lars/adamw opt_state tree) is rebuilt without the
    caller re-specifying flags.

    `unshard`: which encoder sides ("q"/"k") to gather back to true
    shapes when the checkpoint persists ZeRO-2/3 (n, m) flat shards
    (full_param_shapes supplies the shapes; the sharded layout doesn't
    record them). Only the requested sides pay the one-shot host gather
    — this is the eval-side unshard every downstream tool
    (convert_pretrain, eval_lincls, export, serve) inherits."""
    from moco_tpu.core.moco import build_predictor
    from moco_tpu.utils.config import config_from_dict
    from moco_tpu.utils.schedules import build_optimizer

    mgr = CheckpointManager(workdir)
    # extras are needed to discover the config and/or the ZeRO mesh width;
    # skip the metadata round-trip entirely on the explicit-config,
    # replicated-opt-state fast path
    extra: dict = {}
    if config is None or config.parallel.shard_weight_update:
        extra = mgr.read_extra()
    if config is None:
        if "config" not in extra:
            raise KeyError(
                f"checkpoint under {workdir} carries no config — pass one explicitly"
            )
        config = config_from_dict(extra["config"])
    encoder = build_encoder(config.moco)
    predictor = build_predictor(config.moco)
    # the template's opt_state tree must match the saved one exactly, so
    # build the same optimizer family the pretrain driver used — including
    # the ZeRO layout: shard_weight_update saves (num_data, m) opt-state
    # leaves, with num_data = the TRAIN-time mesh width from extras (the
    # config alone may say "all devices")
    tx = build_optimizer(config.optim, steps_per_epoch=1)
    zero_num_data = None
    if config.parallel.shard_weight_update:
        zero_num_data = extra.get("num_data") or config.parallel.num_data
        if zero_num_data is None:
            raise ValueError(
                "ZeRO checkpoint carries no train-time num_data and "
                "config.parallel.num_data is unset — cannot size the "
                "opt-state restore template"
            )
    sample = jnp.zeros((1, config.data.image_size, config.data.image_size, 3), jnp.float32)
    template = create_state(
        jax.random.PRNGKey(0), config, encoder, tx, sample, predictor=predictor,
        zero_num_data=zero_num_data,
    )
    state, _ = mgr.restore(template)
    mgr.close()
    if config.parallel.shard_weight_update and config.parallel.zero_stage >= 2:
        # ZeRO-2/3: one-shot host gather of the requested sides back to
        # the true shapes (both encoders persist in the same (n, m)
        # layout, so one path covers both)
        from moco_tpu.core.moco import full_param_shapes
        from moco_tpu.parallel.zero import unshard_tree_host

        shapes = full_param_shapes(config, encoder, predictor)
        replaced = {}
        if "q" in unshard:
            replaced["params_q"] = unshard_tree_host(state.params_q, shapes["enc"])
        if "k" in unshard:
            replaced["params_k"] = unshard_tree_host(state.params_k, shapes["enc"])
        state = state.replace(**replaced)
    return state, config


def load_pretrained_backbone(
    workdir: str, config: Optional[TrainConfig] = None, side: str = "q"
) -> tuple[Any, Any, TrainConfig]:
    """Checkpoint surgery: restore the pretraining state and keep
    `params_<side>.backbone` + `batch_stats_<side>.backbone` — the
    functional equivalent of keeping `module.encoder_q.*` minus the head.

    `side` selects the encoder: "q" (query — the probe/export default,
    matching the reference's `module.encoder_q.*` surgery) or "k" (the
    EMA key encoder — the serving default: the slow-moving stable
    representation, per "How to Scale Your EMA" arXiv:2307.13813).
    Returns (backbone_params, backbone_stats, config)."""
    if side not in ("q", "k"):
        raise ValueError(f"side must be 'q' or 'k', got {side!r}")
    state, config = restore_pretrain_state(workdir, config, unshard=(side,))
    params = state.params_q if side == "q" else state.params_k
    stats = state.batch_stats_q if side == "q" else state.batch_stats_k
    missing = {k for k in ("backbone", "head") if k not in params}
    if missing:
        raise KeyError(f"pretrained params_{side} missing {missing}")
    return params["backbone"], stats.get("backbone", {}), config


def _build_probe_model(config: TrainConfig, num_classes: int):
    from moco_tpu.core.moco import create_backbone

    backbone = create_backbone(config.moco)  # resnet or vit, per the config
    classifier = LinearClassifier(num_classes=num_classes)
    return backbone, classifier


def make_probe_step(backbone, classifier, tx, mesh):
    """Jitted probe train step: frozen-backbone eval-mode forward,
    classifier-only grads, psum over the data axis."""

    def step_fn(state: ProbeState, images, labels):
        def loss_fn(fc_params):
            feats = backbone.apply(
                {"params": state.backbone_params, "batch_stats": state.backbone_stats},
                images,
                train=False,  # eval-mode BN — the reference's model.eval() quirk
            )
            feats = lax.stop_gradient(feats)
            logits = classifier.apply({"params": fc_params}, feats)
            return cross_entropy(logits, labels), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.fc_params)
        grads = lax.pmean(grads, DATA_AXIS)
        metrics = {"loss": loss, **topk_accuracy(logits, labels)}
        metrics = lax.pmean(metrics, DATA_AXIS)
        updates, opt_state = tx.update(grads, state.opt_state, state.fc_params)
        fc_params = optax.apply_updates(state.fc_params, updates)
        return state.replace(step=state.step + 1, fc_params=fc_params, opt_state=opt_state), metrics

    specs = ProbeState(step=P(), fc_params=P(), backbone_params=P(), backbone_stats=P(), opt_state=P())
    sharded = shard_map(
        step_fn,
        mesh=mesh,
        in_specs=(specs, P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(sharded)


def make_eval_step(backbone, classifier, mesh):
    """Jitted eval step returning masked *sums* (not means), so padded
    tail batches score exactly the valid examples (`main_lincls.py`
    evaluates the full split)."""

    def eval_fn(state: ProbeState, images, labels, mask):
        feats = backbone.apply(
            {"params": state.backbone_params, "batch_stats": state.backbone_stats},
            images,
            train=False,
        )
        logits = classifier.apply({"params": state.fc_params}, feats)
        logz = jax.nn.logsumexp(logits, axis=-1)
        per_ex_loss = logz - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        _, top5 = lax.top_k(logits, 5)
        correct = top5 == labels[:, None]
        sums = {
            "loss": jnp.sum(per_ex_loss * mask),
            "correct1": jnp.sum(correct[:, 0] * mask),
            "correct5": jnp.sum(jnp.any(correct, axis=1) * mask),
            "count": jnp.sum(mask),
        }
        return lax.psum(sums, DATA_AXIS)

    specs = ProbeState(step=P(), fc_params=P(), backbone_params=P(), backbone_stats=P(), opt_state=P())
    sharded = shard_map(
        eval_fn,
        mesh=mesh,
        in_specs=(specs, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)


def sanity_check(state: ProbeState, pretrained_backbone: Any) -> None:
    """`main_lincls.py:~L380-400`: every backbone weight must be
    bit-identical to the pretrained checkpoint after probe training."""
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(state.backbone_params),
        jax.tree_util.tree_leaves_with_path(pretrained_backbone),
    ):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(f"backbone weight changed during probe training: {path}")


def _probe_tx(probe: ProbeConfig, steps_per_epoch: int):
    """The probe optimizer (`main_lincls.py:~L200-210` semantics) —
    shared by training and the evaluate-only restore template, which
    must rebuild the exact opt-state pytree."""
    optim_cfg = OptimConfig(
        optimizer="sgd",
        lr=probe.lr,
        momentum=probe.momentum,
        weight_decay=probe.weight_decay,
        cos=False,
        schedule=probe.schedule,
        epochs=probe.epochs,
    )
    return build_optimizer(optim_cfg, steps_per_epoch)


def _probe_template(
    tx,
    backbone,
    classifier,
    backbone_params,
    backbone_stats,
) -> ProbeState:
    """ProbeState with the exact trees train_lincls checkpoints — built
    from the SAME tx instance the caller steps/restores with, so the
    opt-state tree cannot drift. `backbone_params/stats` may be concrete
    arrays (training) or ShapeDtypeStructs (evaluate-only restore
    template)."""
    fc_vars = classifier.init(
        jax.random.PRNGKey(2), jnp.zeros((1, backbone.num_features), jnp.float32)
    )
    return ProbeState(
        step=jnp.zeros((), jnp.int32),
        fc_params=fc_vars["params"],
        backbone_params=backbone_params,
        backbone_stats=backbone_stats,
        opt_state=tx.init(fc_vars["params"]),
    )


def train_lincls(
    pretrain_workdir: str,
    probe: ProbeConfig,
    pretrain_config: Optional[TrainConfig] = None,
    data: Optional[DataConfig] = None,
    workdir: Optional[str] = None,
    train_dataset=None,
    val_dataset=None,
    log_every: int = 10,
) -> dict:
    """Full linear-probe run; returns {'best_acc1', 'acc1', 'acc5', ...}.

    `pretrain_config=None` reads the config stored in the checkpoint."""
    workdir = workdir or (pretrain_workdir.rstrip("/") + "_lincls")
    mesh = create_mesh(num_model=1)

    backbone_params, backbone_stats, pretrain_config = load_pretrained_backbone(
        pretrain_workdir, pretrain_config
    )
    data = data or pretrain_config.data
    backbone, classifier = _build_probe_model(pretrain_config, probe.num_classes)

    train_pipe = LabeledPipeline(data, mesh, seed=1, dataset=train_dataset)
    val_pipe = EvalPipeline(data, mesh, train=False, dataset=val_dataset)
    steps_per_epoch = train_pipe.steps_per_epoch

    tx = _probe_tx(probe, steps_per_epoch)
    state = _probe_template(tx, backbone, classifier, backbone_params, backbone_stats)
    rep = NamedSharding(mesh, P())
    state = jax.tree.map(lambda x: jax.device_put(x, rep), state)

    step_fn = make_probe_step(backbone, classifier, tx, mesh)
    eval_fn = make_eval_step(backbone, classifier, mesh)
    writer = MetricWriter(workdir)
    ckpt = CheckpointManager(workdir, keep=1)

    best_acc1, last_val = 0.0, {}
    for epoch in range(probe.epochs):
        losses = AverageMeter("Loss", ":.4e")
        top1 = AverageMeter("Acc@1", ":6.2f")
        top5 = AverageMeter("Acc@5", ":6.2f")
        progress = ProgressMeter(steps_per_epoch, [losses, top1, top5], prefix=f"Epoch: [{epoch}]")
        for i, (images, labels) in enumerate(train_pipe.epoch(epoch)):
            state, metrics = step_fn(state, images, labels)
            if i % log_every == 0 or i == steps_per_epoch - 1:
                m = {k: float(v) for k, v in metrics.items()}
                losses.update(m["loss"], data.global_batch)
                top1.update(m["acc1"], data.global_batch)
                top5.update(m["acc5"], data.global_batch)
                progress.display(i)
                writer.write(int(state.step), {"epoch": epoch, "split": "train", **m})

        last_val = validate(eval_fn, state, val_pipe)
        writer.write(int(state.step), {"epoch": epoch, "split": "val", **last_val})
        print(f" * Acc@1 {last_val['acc1']:.3f} Acc@5 {last_val['acc5']:.3f}")
        # config-carrying like the pretrain checkpoints: evaluate-only
        # rebuilds the exact template (opt-state tree shape depends on
        # wd/momentum; fc shape on num_classes) without the caller
        # re-typing the training flags
        ckpt.save(
            epoch,
            state,
            extra={
                "epoch": epoch,
                "acc1": last_val["acc1"],
                "probe": dataclasses.asdict(probe),
                "pretrain_config": config_to_dict(pretrain_config),
                # the RESOLVED data config this probe actually used —
                # evaluate-only must score the same dataset, not the
                # pretrain default the caller may have overridden
                "data": dataclasses.asdict(data),
            },
        )
        if last_val["acc1"] > best_acc1:
            best_acc1 = last_val["acc1"]
            save_best(workdir, state, metric=best_acc1)

    sanity_check(state, backbone_params)
    writer.close()
    ckpt.close()
    return {"best_acc1": best_acc1, **last_val}


def evaluate_lincls(
    pretrain_workdir: str,
    probe: ProbeConfig,
    pretrain_config: Optional[TrainConfig] = None,
    data: Optional[DataConfig] = None,
    workdir: Optional[str] = None,
    val_dataset=None,
    data_overrides: Optional[dict] = None,
) -> dict:
    """Validation-only mode (`main_lincls.py`'s `--evaluate` flag): load
    a finished probe run's best snapshot (falling back to the latest
    epoch checkpoint) and score the full val split — no training.
    `data_overrides`: field overrides applied on top of the data config
    resolved from the checkpoint (the CLI's flag passthrough).

    `workdir` is the PROBE workdir (default: the train_lincls naming,
    `<pretrain_workdir>_lincls`). Probe checkpoints carry their own
    probe + pretrain configs, so the restore template is rebuilt from
    the checkpoint — the caller's flags are NOT trusted for
    template-shaping fields (wd/momentum change the opt-state tree,
    num_classes the fc shape) — and the probe checkpoint alone is
    sufficient: nothing is read from the pretrain workdir unless the
    probe checkpoint predates config-carrying extras."""
    workdir = workdir or (pretrain_workdir.rstrip("/") + "_lincls")
    mesh = create_mesh(num_model=1)

    mgr = CheckpointManager(workdir, keep=1)
    extra = mgr.read_extra()
    if "probe" in extra:
        probe = dataclass_from_dict(ProbeConfig, extra["probe"])
        pretrain_config = config_from_dict(extra["pretrain_config"])
    elif pretrain_config is None:
        # pre-config-carrying probe checkpoint: the pretrain workdir's
        # extras supply the config (a JSON read — no state restore)
        pre_mgr = CheckpointManager(pretrain_workdir)
        pretrain_config = config_from_dict(pre_mgr.read_extra()["config"])
        pre_mgr.close()
    if data is None:
        # prefer the data config the probe ACTUALLY trained with (saved
        # in its extras); the pretrain default is the legacy fallback
        data = (
            dataclass_from_dict(DataConfig, extra["data"])
            if "data" in extra
            else pretrain_config.data
        )
    if data_overrides:
        data = dataclasses.replace(data, **data_overrides)
    backbone, classifier = _build_probe_model(pretrain_config, probe.num_classes)
    val_pipe = EvalPipeline(data, mesh, train=False, dataset=val_dataset)

    # abstract backbone trees: eval needs no pretrain-state read — the
    # probe checkpoint holds every weight; eval_shape gives the template
    sample = jnp.zeros((1, data.image_size, data.image_size, 3), jnp.float32)
    var_shapes = jax.eval_shape(
        lambda: backbone.init(jax.random.PRNGKey(0), sample, train=False)
    )
    template = _probe_template(
        _probe_tx(probe, max(val_pipe.steps_per_epoch, 1)),
        backbone,
        classifier,
        var_shapes["params"],
        var_shapes.get("batch_stats", {}),
    )
    legacy_probe_flags = "probe" not in extra
    try:
        if best_exists(workdir):
            state, best_metric = restore_best(workdir, template)
            print(f"evaluating model_best (saved Acc@1 {best_metric:.3f})")
        else:
            state, extra = mgr.restore(template)
            print(f"no model_best; evaluating latest epoch {extra.get('epoch')}")
    except Exception as e:
        if legacy_probe_flags:
            # pre-config-carrying probe checkpoint: the template was shaped
            # from the CLI probe flags, so a wd/momentum/num-classes
            # mismatch with the original probe run surfaces as an Orbax
            # tree-structure error here — say so instead of the raw trace
            raise RuntimeError(
                "probe checkpoint restore failed and this checkpoint predates "
                "config-carrying extras, so the restore template was built from "
                "the probe flags you passed — if they differ from the ORIGINAL "
                "probe training flags (--lr/--wd/--momentum affect the optimizer "
                "state tree, num_classes the fc shape), pass the original values"
            ) from e
        raise
    mgr.close()
    rep = NamedSharding(mesh, P())
    state = jax.tree.map(lambda x: jax.device_put(x, rep), state)

    eval_fn = make_eval_step(backbone, classifier, mesh)
    out = validate(eval_fn, state, val_pipe)
    print(f" * Acc@1 {out['acc1']:.3f} Acc@5 {out['acc5']:.3f}")
    return out


def validate(eval_fn, state: ProbeState, val_pipe: EvalPipeline) -> dict:
    """Top-1/top-5 over the FULL val split (`main_lincls.py:~L330-370`)."""
    loss = c1 = c5 = n = 0.0
    for images, labels, mask in val_pipe:
        s = eval_fn(state, images, labels, mask)
        loss += float(s["loss"])
        c1 += float(s["correct1"])
        c5 += float(s["correct5"])
        n += float(s["count"])
    n = max(n, 1.0)
    return {"loss": loss / n, "acc1": 100.0 * c1 / n, "acc5": 100.0 * c5 / n, "count": n}
