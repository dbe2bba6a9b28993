"""Shared pieces of the plain references: float32 everywhere, matmuls and
convolutions at `Precision.HIGHEST` (on a TPU a float32 matmul otherwise
runs in reduced precision), no kernels, no batching tricks."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


CIFAR_MEAN = (0.4914, 0.4822, 0.4465)
CIFAR_STD = (0.2470, 0.2435, 0.2616)


def preprocess(images_uint8) -> jax.Array:
    """uint8 NHWC -> float32, /255, per-channel normalisation: the
    evaluation recipe a served image gets. ImageNet's statistics, except
    at the CPU rehearsal's toy sizes (<= 64 px), where the program's
    recipes switch to CIFAR-10's."""
    x = jnp.asarray(images_uint8, jnp.float32) / 255.0
    small = x.shape[1] <= 64
    mean, std = (CIFAR_MEAN, CIFAR_STD) if small else (IMAGENET_MEAN, IMAGENET_STD)
    return (x - jnp.asarray(mean, jnp.float32)) / jnp.asarray(std, jnp.float32)


def dense(x, p):
    y = jnp.matmul(x, jnp.asarray(p["kernel"], jnp.float32), precision=HI)
    return y + p["bias"] if "bias" in p else y


def batch_norm(x, p, stats, train: bool, eps: float = 1e-5):
    """Batch normalisation over every axis but the last. Training mode
    uses the batch's own biased statistics; evaluation the running ones.
    `p` may lack scale/bias (the v3 heads' affine-free output BN)."""
    if train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axis=axes)
        var = jnp.mean(jnp.square(x - mean), axis=axes)
    else:
        mean, var = stats["mean"], stats["var"]
    y = (x - mean) / jnp.sqrt(var + eps)
    if p and "scale" in p:
        y = y * p["scale"]
    if p and "bias" in p:
        y = y + p["bias"]
    return y


def l2_normalize(x, eps: float = 1e-12):
    return x / jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)), eps)


def cross_entropy(logits, labels):
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])
