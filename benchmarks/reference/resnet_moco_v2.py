"""Plain reference: ResNet (v1.5 bottlenecks or basic blocks) + the MoCo
v2 MLP head + InfoNCE over (q, k, queue).

Written from arXiv:1512.03385 (ResNet; the stride sits on the 3x3 of a
bottleneck, "v1.5", as torchvision and the upstream MoCo code have it),
arXiv:1911.05722 (MoCo: dictionary as a queue, InfoNCE with the positive
in column 0) and arXiv:2003.04297 (v2: 2-layer MLP head, T = 0.2). It
reads the program's parameter tree by its flax names and shares no code
with it.

The family's file: beside the forward (`loss_and_embeddings`, `embed`) it
states what the harness needs to know of the family and finds here by
name: `INPUT`, `TOLERANCES`, `forward_flops`.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from benchmarks.harness.flops import conv_flops, dense_flops, shape
from benchmarks.reference.common import (
    HI, batch_norm, cross_entropy, dense, l2_normalize, operand,
)

# what the encoder reads: `benchmarks/inputs/images.py`
INPUT = "images"

# `correct`'s limits that are this family's own (the others are
# `harness/correct.py`'s defaults, where each measure is explained).
# emb_centred_rel: ||sys - ref||_F over ||ref - mean row of ref||_F of the
# normalised query embeddings, the bfloat16 system against this float32
# reference, ResNet-50 in training mode (53 convolutions deep, BN over 32
# rows, at a random init where the sample's embeddings differ little from
# one another). 0.45 since PR 24. It lies between two readings on the chip
# (PERF.md section 2): sound runs read 0.24-0.352 over 20 seeds (my chip
# runs, PR 28; 0.25-0.31 over 14 in PR 24), the control (this reference
# with fp8 or int8 operands, `benchmarks/control.py`) 0.582-0.742 over
# 6 seeds x 2 types.
TOLERANCES = {"emb_centred_rel": 0.45}


def _conv(x, kernel, stride: int):
    k = kernel.shape[0]
    return lax.conv_general_dilated(
        operand(x), operand(kernel), (stride, stride),
        [(k // 2, k // 2)] * 2, dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
    )


def _conv_bn(x, p, s, stride: int, train: bool):
    return batch_norm(_conv(x, p["Conv_0"]["kernel"], stride), p["BatchNorm_0"],
                      s["BatchNorm_0"] if s else None, train)


def _max_pool_3x3_s2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)]
    )


def backbone(params: dict, stats: dict, x, train: bool):
    """Pooled features (N, C) of float32 NHWC images."""
    if "Conv_0" in params:  # ImageNet stem
        x = _conv(x, params["Conv_0"]["kernel"], 2)
        x = batch_norm(x, params["BatchNorm_0"], stats.get("BatchNorm_0"), train)
        x = _max_pool_3x3_s2(jnp.maximum(x, 0.0))
    else:  # 3x3 stride-1 stem for 32px inputs, no pool
        x = jnp.maximum(_conv_bn(x, params["ConvBN_0"], stats.get("ConvBN_0"), 1, train), 0.0)
    names = sorted(
        (k for k in params if k.startswith(("Bottleneck_", "BasicBlock_"))),
        key=lambda k: int(k.rsplit("_", 1)[1]),
    )
    width = None
    for name in names:
        p, s = params[name], stats.get(name, {})
        first = p["ConvBN_0"]["Conv_0"]["kernel"].shape[3]
        stride = 2 if (width is not None and first != width) else 1
        width = first
        get = lambda i: (p[f"ConvBN_{i}"], s.get(f"ConvBN_{i}"))
        if name.startswith("Bottleneck_"):
            y = jnp.maximum(_conv_bn(x, *get(0), 1, train), 0.0)
            y = jnp.maximum(_conv_bn(y, *get(1), stride, train), 0.0)
            y = _conv_bn(y, *get(2), 1, train)
            proj = 3
        else:
            y = jnp.maximum(_conv_bn(x, *get(0), stride, train), 0.0)
            y = _conv_bn(y, *get(1), 1, train)
            proj = 2
        if f"ConvBN_{proj}" in p:
            x = _conv_bn(x, *get(proj), stride, train)
        x = jnp.maximum(y + x, 0.0)
    return jnp.mean(x, axis=(1, 2))


def encode(params: dict, stats: dict, x, train: bool):
    """L2-normalised embeddings: backbone, then Linear-ReLU-Linear (v2)
    or a single Linear (v1) by what the head holds."""
    f = backbone(params["backbone"], stats.get("backbone", {}), x, train)
    head = params["head"]
    if "Dense_1" in head:
        f = dense(jnp.maximum(dense(f, head["Dense_0"]), 0.0), head["Dense_1"])
    else:
        f = dense(f, head["Dense_0"])
    return l2_normalize(f)


def infonce(q, k, queue, temperature: float):
    """-log softmax of the positive among (1 + K) logits, mean over the batch."""
    k = lax.stop_gradient(k)
    l_pos = jnp.sum(q * k, axis=-1, keepdims=True)
    l_neg = jnp.matmul(operand(q), operand(queue).T, precision=HI)
    logits = jnp.concatenate([l_pos, l_neg], axis=1) / temperature
    return cross_entropy(logits, jnp.zeros((q.shape[0],), jnp.int32))


def loss_and_embeddings(params_q, stats_q, params_k, stats_k, queue, x_q, x_k, temperature):
    """One MoCo v2 training forward on a batch, single device (so the
    key batch needs no shuffle): training-mode BN on both sides."""
    q = encode(params_q, stats_q, x_q, train=True)
    k = encode(params_k, stats_k, x_k, train=True)
    return infonce(q, k, queue, temperature), q


def embed(params: dict, stats: dict, x):
    """What a served image gets: evaluation-mode BN, L2-normalised."""
    return encode(params, stats, x, train=False)


# -- operations, from shapes alone ------------------------------------------
# The topology is the paper's (ResNet v1.5: arXiv:1512.03385 with the
# stride on the 3x3); the tree is the program's own (flax names).


def resnet_forward_flops(backbone: dict, image_size: int) -> float:
    """Forward operations of one image through the program's ResNet
    parameter tree (`Conv_0`/`ConvBN_0` stem, then `Bottleneck_k` or
    `BasicBlock_k` in order). Convolutions only: BN, ReLU, pooling and
    the residual adds are bandwidth, not operations worth counting."""
    total = 0.0
    if "Conv_0" in backbone:  # 7x7 stride 2, then 3x3 stride-2 max pool
        hw = math.ceil(image_size / 2)
        total += conv_flops(shape(backbone["Conv_0"]["kernel"]), hw)
        hw = math.ceil(hw / 2)
    else:  # CIFAR stem: 3x3 stride 1, no pool
        hw = image_size
        total += conv_flops(shape(backbone["ConvBN_0"]["Conv_0"]["kernel"]), hw)
    blocks = sorted(
        (k for k in backbone if k.startswith(("Bottleneck_", "BasicBlock_"))),
        key=lambda k: int(k.rsplit("_", 1)[1]),
    )
    width = None
    for name in blocks:
        blk = backbone[name]
        convs = [shape(blk[f"ConvBN_{i}"]["Conv_0"]["kernel"]) for i in range(len(blk))]
        bottleneck = name.startswith("Bottleneck_")
        main = convs[:3] if bottleneck else convs[:2]
        # a stage's first block (after the first stage) halves the map:
        # the channel width of the block's first conv doubles there
        strided = width is not None and main[0][3] != width
        width = main[0][3]
        in_hw, out_hw = hw, (math.ceil(hw / 2) if strided else hw)
        if bottleneck:  # 1x1 at the input size, 3x3 carries the stride, 1x1 after
            total += conv_flops(main[0], in_hw)
            total += conv_flops(main[1], out_hw) + conv_flops(main[2], out_hw)
        else:  # first 3x3 carries the stride
            total += conv_flops(main[0], out_hw) + conv_flops(main[1], out_hw)
        for extra in convs[len(main):]:  # 1x1 projection on the residual branch
            total += conv_flops(extra, out_hw)
        hw = out_hw
    return total


def forward_flops(param_shapes: dict, config) -> float:
    """One image forward through backbone + projection head, from the
    encoder's parameter shapes and the configuration's input size."""
    fwd = resnet_forward_flops(param_shapes["backbone"], config.data.image_size)
    return fwd + dense_flops(param_shapes.get("head", {}))
