"""LR schedules and optimizer builders.

Reference semantics:
- `adjust_learning_rate` (`main_moco.py:~L362-375`): per-EPOCH granularity;
  cosine `lr *= 0.5*(1+cos(pi*epoch/epochs))` when `--cos`, else step decay
  `lr *= 0.1` at each milestone in `--schedule` (default 120,160).
- Pretrain optimizer (`main_moco.py:~L188`): SGD(lr=0.03, momentum=0.9,
  weight_decay=1e-4) — torch applies wd additively to the grad before the
  momentum buffer, reproduced here with `add_decayed_weights` *before*
  `sgd`.
- Linear probe (`main_lincls.py:~L200-210`): SGD(lr=30.0, wd=0).
- LARS/AdamW have no reference recipe (its max batch is 256); they serve
  the pod-scale and v3 presets, with warmup + BN/bias exclusion per the
  large-batch literature.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from moco_tpu.utils.config import OptimConfig


def make_lr_schedule(cfg: OptimConfig, steps_per_epoch: int, xp=jnp) -> Callable:
    """Per-epoch-granular schedule over the global step, matching
    `adjust_learning_rate` exactly (with optional linear warmup).

    `xp` is the array namespace the one formula is evaluated in: `jnp`
    inside the optimizer (traced into the step program), `numpy` for the
    driver's log line, where a `jnp` evaluation would be a handful of
    device programs queued behind the step in flight. The epoch and the
    cosine stay float32 in both, so the two agree to float32 rounding."""
    total_epochs = cfg.epochs

    def schedule(step):
        epoch = xp.floor_divide(step, steps_per_epoch).astype(xp.float32)
        if cfg.cos:
            factor = 0.5 * (1.0 + xp.cos(math.pi * epoch / total_epochs))
        else:
            milestones = xp.asarray(cfg.schedule, xp.float32)
            factor = 0.1 ** xp.sum(epoch[None] >= milestones)
        lr = cfg.lr * factor
        if cfg.warmup_epochs > 0:
            warm_steps = cfg.warmup_epochs * steps_per_epoch
            warm = cfg.lr * (step + 1) / warm_steps
            lr = xp.where(step < warm_steps, warm, lr)
        return lr

    return schedule


def _bn_and_bias_mask(params):
    """True for weight-decayable leaves: excludes biases and BN/LN
    scale/bias (standard for LARS; torch SGD in the reference decays
    everything).

    Decayability is decided by the leaf's NAME only — every
    non-decayable leaf in this codebase is literally named 'bias' or
    'scale' — not by ndim: under sharded weight update
    (parallel/zero.py) leaves arrive as 1-D flat shards with the same
    tree paths, and an ndim test would silently disable decay there
    (caught by tests/test_zero.py's adamw parity test)."""

    def decayable(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return name not in ("bias", "scale")

    return jax.tree_util.tree_map_with_path(decayable, params)


def build_optimizer(cfg: OptimConfig, steps_per_epoch: int) -> optax.GradientTransformation:
    lr = make_lr_schedule(cfg, steps_per_epoch)
    if cfg.optimizer == "sgd":
        chain = []
        if cfg.weight_decay:
            chain.append(optax.add_decayed_weights(cfg.weight_decay))
        chain.append(optax.sgd(lr, momentum=cfg.momentum or None))
        return optax.chain(*chain)
    if cfg.optimizer == "lars":
        return optax.lars(
            lr,
            weight_decay=cfg.weight_decay,
            weight_decay_mask=_bn_and_bias_mask,
            trust_coefficient=cfg.trust_coefficient,
            trust_ratio_mask=_bn_and_bias_mask,
            momentum=cfg.momentum,
        )
    if cfg.optimizer == "adamw":
        return optax.adamw(lr, weight_decay=cfg.weight_decay, mask=_bn_and_bias_mask)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
