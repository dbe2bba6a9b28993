"""Shared pieces of the plain references: float32 everywhere, matmuls and
convolutions at `Precision.HIGHEST` (on a TPU a float32 matmul otherwise
runs in reduced precision), no kernels, no batching tricks."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST

# The control of `correct` (harness/correct.py::check_train, `control=`):
# the reference put in the program's place, with both operands of every
# matmul and convolution rounded to a lower precision (per-tensor scaled,
# as a deployment would scale them) and the arithmetic left in float32.
# None everywhere else: the reference proper rounds nothing.
_OPERAND_DTYPE = None


@contextlib.contextmanager
def operands_rounded_to(dtype):
    """Trace (and so call a fresh `jax.jit` of) the reference inside this
    to get the control; the setting is read while tracing."""
    global _OPERAND_DTYPE
    before, _OPERAND_DTYPE = _OPERAND_DTYPE, dtype
    try:
        yield
    finally:
        _OPERAND_DTYPE = before


def operand(x):
    """A matmul's or convolution's operand as float32: as it is, or, in
    the control, rounded to the lower precision. An 8-bit type gets a
    per-tensor scale that puts the largest magnitude at the type's top;
    a 16-bit float is wide enough to take the values as they are."""
    x = jnp.asarray(x, jnp.float32)
    dtype = _OPERAND_DTYPE
    if dtype is None:
        return x
    if jnp.dtype(dtype).itemsize > 1:
        return x.astype(dtype).astype(jnp.float32)
    integer = jnp.issubdtype(dtype, jnp.integer)
    top = float(jnp.iinfo(dtype).max if integer else jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    y = jnp.round(x / scale) if integer else x / scale
    return y.astype(dtype).astype(jnp.float32) * scale


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
    y = jnp.matmul(operand(x), operand(p["kernel"]), precision=HI)
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
