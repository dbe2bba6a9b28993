"""Activation-quantized int8 inference: calibration + the w8a8 forward.

PR 9's engine PTQ is *weight-only* (w8): the int8 kernels dequantize to
f32 in-graph and every matmul/conv still runs f32×f32 — the at-rest
memory saving is real, the arithmetic saving is not. Going after the
full factor needs the activations on the int8 grid too (w8a8), and that
needs *calibration*: activation ranges are data-dependent, so a held-out
sample runs through the f32 encoder once, an observer records the
per-tensor |x|max at every quantized-op input, and symmetric per-tensor
scales are fitted from those ranges (`s = amax / 127` — the standard
symmetric PTQ recipe; per-tensor on activations, per-output-channel on
weights, as in `engine.quantize_params_int8`).

The seam is flax's method interceptor (`nn.intercept_methods`), the
same place for both passes:

- **observe** (:class:`ActivationObserver`): the f32 forward runs
  eagerly with an interceptor that records `amax[path] = max|input|`
  for every `nn.Conv` / `nn.Dense` call, keyed by the module's scope
  path. Deterministic: same sample → bitwise-identical ranges (the
  calibration-determinism test pins this).
- **quantize** (:func:`quantized_apply`): the serving forward replaces
  each Conv/Dense with its int8 twin — input quantized to the int8 grid
  with the calibrated per-tensor scale, the (already int8) kernel
  consumed directly, accumulation in int32, one f32 rescale
  (`a_scale · w_scale`) at the layer boundary. Everything between
  layers (BN, ReLU, residual adds, pooling, L2-normalize) stays f32,
  so error cannot compound through normalization statistics.

Backend reality (the bf16 precedent, measured the same way): XLA:CPU
has no int8 conv/GEMM kernels — an int8×int8→int32 conv falls to a
generic path ~45x slower than f32, exactly like its ~50x bf16
emulation that already forces the CPU engine to serve f32. So
`int8_compute` is capability-gated: tpu/gpu run true int8×int8→int32
(`preferred_element_type=jnp.int32`); CPU runs *scaled-integer
emulation* — the operands are the exact same int8-grid values held in
f32, so products and sums are exact integers (f32 is exact through
2^24) and the NUMERICS of w8a8 (embedding cosine, downstream recall)
are faithfully testable on the CPU smoke even though the arithmetic
speedup only exists on a chip. The w8a8-vs-w8 queries/s claim is
therefore an accelerator claim; the CPU smoke gates the cosine floor
(`scripts/serve_smoke.py` QUANT_COSINE_FLOOR), and `default_int8_compute`
says which arithmetic a backend runs.

Calibration persists as a small JSON artifact next to the checkpoint
(`quant_calib.json`: version, image size, sample size, per-path amax)
so a serving replica can boot w8a8 without re-running the sample —
`save_calibration` / `load_calibration` roundtrip bitwise (floats via
repr) and the engine validates the artifact against the module (every
quantized layer must have a range).
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Any, Iterable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict

CALIBRATION_VERSION = 1
CALIBRATION_FILENAME = "quant_calib.json"
# module types the quantized forward replaces; anything else runs f32
QUANT_LAYER_TYPES = (nn.Conv, nn.Dense)
# engine quantization tiers (serve/engine.py's engine_quant knob)
QUANT_MODES = ("off", "w8", "w8a8")


def _layer_path(module) -> str:
    """Stable string key for a bound module's scope path — matches the
    params-tree nesting (flax auto-names: ``backbone/ConvBN_0/Conv_0``)."""
    return "/".join(module.path)


def _is_plain(module) -> bool:
    """Only plain convs/dense quantize; anything exotic (input dilation,
    grouped features) passes through f32 rather than risking a silent
    semantics mismatch in the re-implemented int8 op."""
    if isinstance(module, nn.Dense):
        return True
    if getattr(module, "feature_group_count", 1) != 1:
        return False
    in_dil = getattr(module, "input_dilation", None)
    if in_dil not in (None, 1) and set(np.atleast_1d(in_dil).tolist()) != {1}:
        return False
    return True


class ActivationObserver:
    """Records per-tensor activation ranges (`amax[path] = max|input|`)
    for every plain Conv/Dense call while :meth:`intercept` is active.
    Ranges accumulate across calls (running max over calibration
    batches), so one observer can digest a whole held-out sample."""

    def __init__(self):
        self.amax: dict[str, float] = {}

    def _interceptor(self, next_fun, args, kwargs, context):
        mod = context.module
        if (
            context.method_name == "__call__"
            and isinstance(mod, QUANT_LAYER_TYPES)
            and _is_plain(mod)
            and args
        ):
            path = _layer_path(mod)
            v = float(jnp.max(jnp.abs(args[0])))
            self.amax[path] = max(self.amax.get(path, 0.0), v)
        return next_fun(*args, **kwargs)

    @contextlib.contextmanager
    def intercept(self):
        with nn.intercept_methods(self._interceptor):
            yield self


def fit_scales(amax: dict[str, float]) -> dict[str, float]:
    """Symmetric per-tensor activation scales from observed ranges:
    `s = amax / 127`, with a scale of 1 for a never-activated tensor
    (avoids a 0-divide; its quantized values are all zero anyway)."""
    return {
        path: (v / 127.0 if v > 0.0 else 1.0) for path, v in sorted(amax.items())
    }


def calibrate_encoder(
    module,
    params,
    batch_stats,
    images: np.ndarray,
    image_size: int,
    batch_size: int = 32,
) -> dict:
    """One calibration pass at the engine's preprocessing seam: the
    held-out uint8 `images` run through /255 → per-channel normalize →
    the f32 encoder (eagerly — calibration is offline, determinism
    beats speed) under the observer. Returns the JSON-ready artifact."""
    from moco_tpu.data.augment import get_recipe, normalize

    images = np.asarray(images, np.uint8)
    if images.ndim != 4 or images.shape[1:] != (image_size, image_size, 3):
        raise ValueError(
            f"calibration sample must be (n, {image_size}, {image_size}, 3) "
            f"uint8, got {images.shape}"
        )
    recipe = get_recipe(False, int(image_size))
    variables = {"params": params, "batch_stats": batch_stats}
    obs = ActivationObserver()
    with obs.intercept():
        for lo in range(0, images.shape[0], int(batch_size)):
            x = jnp.asarray(images[lo : lo + int(batch_size)], jnp.float32) / 255.0
            x = normalize(x, recipe.mean, recipe.std)
            module.apply(variables, x, train=False)
    if not obs.amax:
        raise ValueError("calibration saw no quantizable Conv/Dense layer")
    return {
        "version": CALIBRATION_VERSION,
        "image_size": int(image_size),
        "sample_n": int(images.shape[0]),
        "num_layers": len(obs.amax),
        "amax": {k: obs.amax[k] for k in sorted(obs.amax)},
    }


def calibration_path(ckpt_dir: str) -> str:
    """Where the artifact lives relative to a checkpoint directory."""
    return os.path.join(ckpt_dir, CALIBRATION_FILENAME)


def save_calibration(path: str, calib: dict) -> str:
    """Atomic JSON write (floats via repr-roundtripping json, so
    load(save(x)) == x bitwise). Accepts a checkpoint DIR or a file."""
    if os.path.isdir(path):
        path = calibration_path(path)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(calib, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_calibration(path: str) -> dict:
    if os.path.isdir(path):
        path = calibration_path(path)
    with open(path) as f:
        calib = json.load(f)
    if calib.get("version") != CALIBRATION_VERSION or "amax" not in calib:
        raise ValueError(f"{path} is not a v{CALIBRATION_VERSION} calibration artifact")
    return calib


def default_int8_compute() -> bool:
    """True int8×int8→int32 kernels only where the backend has them —
    the same tpu/gpu gate as engine donation and the bf16 serve dtype
    (XLA:CPU measured ~45x slower on an int8 conv; module docstring)."""
    return jax.default_backend() in ("tpu", "gpu")


def _conv_geometry(mod, ndim: int):
    """nn.Conv attribute normalization → lax.conv_general_dilated args
    (spatial rank = ndim - 2; flax accepts ints where lax wants tuples)."""

    def _tup(v, default=1):
        if v is None:
            v = default
        if isinstance(v, int):
            return (v,) * (ndim - 2)
        return tuple(v)

    return _tup(mod.strides), _tup(mod.kernel_dilation), mod.padding


def quantized_apply(
    module,
    qparams,
    qscales,
    batch_stats,
    act_scales: dict[str, jax.Array],
    x: jax.Array,
    int8_compute: bool,
    train: bool = False,
):
    """The w8a8 forward: `module.apply` with every calibrated plain
    Conv/Dense replaced by its int8 twin (module docstring). All of
    `qparams`/`qscales`/`act_scales` are expected to be call ARGUMENTS
    of the enclosing jit — a closure constant would let XLA fold
    `int8 · scale` back into f32 constants and silently undo the 4x
    at-rest saving (the PR-9 lesson, engine.quantize_params_int8)."""
    # per-path per-output-channel weight scales from the scale tree —
    # structure is static under trace, so this flatten costs nothing
    flat_q = flatten_dict(qparams)
    flat_s = flatten_dict(qscales)
    w_scales = {
        "/".join(kpath[:-1]): flat_s[kpath].reshape(-1)
        for kpath, leaf in flat_q.items()
        if kpath[-1] == "kernel" and getattr(leaf, "dtype", None) == jnp.int8
    }

    def interceptor(next_fun, args, kwargs, context):
        mod = context.module
        if (
            context.method_name != "__call__"
            or not isinstance(mod, QUANT_LAYER_TYPES)
            or not _is_plain(mod)
        ):
            return next_fun(*args, **kwargs)
        path = _layer_path(mod)
        a_s = act_scales.get(path)
        w_s = w_scales.get(path)
        if a_s is None or w_s is None:
            # uncalibrated or unquantized layer: f32 pass-through (the
            # engine validates coverage up front, so this is the
            # deliberate escape hatch, not a silent hole)
            return next_fun(*args, **kwargs)
        xin = args[0]
        qx = jnp.clip(jnp.round(xin.astype(jnp.float32) / a_s), -127.0, 127.0)
        kern = mod.variables["params"]["kernel"]  # int8: applied tree is quantized
        if int8_compute:
            qx = qx.astype(jnp.int8)
            pet = {"preferred_element_type": jnp.int32}
        else:
            # scaled-integer emulation: identical int values in f32
            # (exact through 2^24), XLA:CPU keeps its fast f32 kernels
            kern = kern.astype(jnp.float32)
            pet = {}
        if isinstance(mod, nn.Dense):
            acc = jax.lax.dot_general(
                qx, kern, (((qx.ndim - 1,), (0,)), ((), ())), **pet
            )
        else:
            strides, kernel_dilation, padding = _conv_geometry(mod, qx.ndim)
            dn = jax.lax.conv_dimension_numbers(
                qx.shape, kern.shape, ("NHWC", "HWIO", "NHWC")
            )
            acc = jax.lax.conv_general_dilated(
                qx,
                kern,
                strides,
                padding,
                rhs_dilation=kernel_dilation,
                dimension_numbers=dn,
                **pet,
            )
        scale = a_s * w_s
        out = acc.astype(jnp.float32) * scale.reshape((1,) * (acc.ndim - 1) + (-1,))
        if mod.use_bias:
            out = out + mod.variables["params"]["bias"].astype(jnp.float32)
        return out

    with nn.intercept_methods(interceptor):
        return module.apply(
            {"params": qparams, "batch_stats": batch_stats}, x, train=train
        )


def quantized_layer_paths(params) -> set[str]:
    """Paths `quantize_params_int8` will quantize (ndim >= 2 floating
    kernels) — what a calibration artifact must cover for w8a8."""
    out = set()
    for kpath, leaf in flatten_dict(params).items():
        if (
            kpath[-1] == "kernel"
            and getattr(leaf, "ndim", 0) >= 2
            and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
        ):
            out.add("/".join(kpath[:-1]))
    return out


def validate_calibration(calib: dict, params, image_size: int) -> None:
    """Fail loudly at engine build, not silently at serve time: the
    artifact must match the serving geometry and cover every quantized
    layer (an uncovered layer would fall back to f32 — a silent tier
    downgrade)."""
    if int(calib.get("image_size", -1)) != int(image_size):
        raise ValueError(
            f"calibration was captured at image_size="
            f"{calib.get('image_size')}, engine serves {image_size}"
        )
    missing = quantized_layer_paths(params) - set(calib["amax"])
    if missing:
        raise ValueError(
            f"calibration covers {len(calib['amax'])} layers but the encoder "
            f"has {len(missing)} uncovered quantized layers: {sorted(missing)[:4]}"
        )


def activation_scales(calib: dict) -> dict[str, jax.Array]:
    """The calibration artifact as the traced-scale pytree the w8a8
    executable takes as an argument (sorted keys → stable treedef)."""
    return {
        path: jnp.float32(s) for path, s in fit_scales(calib["amax"]).items()
    }


__all__ = [
    "ActivationObserver",
    "CALIBRATION_FILENAME",
    "CALIBRATION_VERSION",
    "QUANT_LAYER_TYPES",
    "QUANT_MODES",
    "activation_scales",
    "calibrate_encoder",
    "calibration_path",
    "default_int8_compute",
    "fit_scales",
    "load_calibration",
    "quantized_apply",
    "quantized_layer_paths",
    "save_calibration",
    "validate_calibration",
]
