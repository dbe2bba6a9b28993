"""Operations and bytes the algorithm needs, computed from shapes.

The yardstick's own arithmetic: a later PR to the program cannot change
what `step_mfu` or `infonce_roofline` divide by. Counts are the
operations the mathematics requires (a multiply-add is two operations);
rematerialised or recomputed work is not counted, so a kernel that
recomputes its logits in the backward pass gets no credit for that.

Parameter trees are the program's own (flax names): the walkers below
read shapes only and know the two families' topologies from the papers
(ResNet v1.5: arXiv:1512.03385 with the stride on the 3x3; ViT:
arXiv:2010.11929).
"""

from __future__ import annotations

import math


def _shape(leaf) -> tuple:
    return tuple(getattr(leaf, "shape", leaf))


def _conv_flops(kernel_shape: tuple, out_hw: int) -> float:
    kh, kw, cin, cout = kernel_shape
    return 2.0 * kh * kw * cin * cout * out_hw * out_hw


def _dense_flops(tree: dict, rows: float = 1.0) -> float:
    """Every 2-D `kernel` under `tree` applied to `rows` rows."""
    total = 0.0
    for name, sub in tree.items():
        if isinstance(sub, dict):
            total += _dense_flops(sub, rows)
        elif name == "kernel" and len(_shape(sub)) == 2:
            a, b = _shape(sub)
            total += 2.0 * a * b * rows
    return total


def resnet_forward_flops(backbone: dict, image_size: int) -> float:
    """Forward operations of one image through the program's ResNet
    parameter tree (`Conv_0`/`ConvBN_0` stem, then `Bottleneck_k` or
    `BasicBlock_k` in order). Convolutions only: BN, ReLU, pooling and
    the residual adds are bandwidth, not operations worth counting."""
    total = 0.0
    if "Conv_0" in backbone:  # 7x7 stride 2, then 3x3 stride-2 max pool
        hw = math.ceil(image_size / 2)
        total += _conv_flops(_shape(backbone["Conv_0"]["kernel"]), hw)
        hw = math.ceil(hw / 2)
    else:  # CIFAR stem: 3x3 stride 1, no pool
        hw = image_size
        total += _conv_flops(_shape(backbone["ConvBN_0"]["Conv_0"]["kernel"]), hw)
    blocks = sorted(
        (k for k in backbone if k.startswith(("Bottleneck_", "BasicBlock_"))),
        key=lambda k: int(k.rsplit("_", 1)[1]),
    )
    width = None
    for name in blocks:
        blk = backbone[name]
        convs = [_shape(blk[f"ConvBN_{i}"]["Conv_0"]["kernel"]) for i in range(len(blk))]
        bottleneck = name.startswith("Bottleneck_")
        main = convs[:3] if bottleneck else convs[:2]
        # a stage's first block (after the first stage) halves the map:
        # the channel width of the block's first conv doubles there
        strided = width is not None and main[0][3] != width
        width = main[0][3]
        in_hw, out_hw = hw, (math.ceil(hw / 2) if strided else hw)
        if bottleneck:  # 1x1 at the input size, 3x3 carries the stride, 1x1 after
            total += _conv_flops(main[0], in_hw)
            total += _conv_flops(main[1], out_hw) + _conv_flops(main[2], out_hw)
        else:  # first 3x3 carries the stride
            total += _conv_flops(main[0], out_hw) + _conv_flops(main[1], out_hw)
        for extra in convs[len(main):]:  # 1x1 projection on the residual branch
            total += _conv_flops(extra, out_hw)
        hw = out_hw
    return total


def vit_forward_flops(backbone: dict, image_size: int) -> float:
    """Forward operations of one image through the program's ViT tree:
    patch projection, then per block QKV + scores + weighted sum +
    output projection + the two MLP matmuls, over S = patches (+1 with a
    class token) tokens."""
    ph, pw, cin, dim = _shape(backbone["patch_embed"]["kernel"])
    patches = (image_size // ph) * (image_size // pw)
    seq = patches + (1 if "cls_token" in backbone else 0)
    total = 2.0 * ph * pw * cin * dim * patches
    for name, blk in backbone.items():
        if not name.startswith("block_"):
            continue
        total += _dense_flops(blk["MlpBlock_0"], seq)
        attn = blk["MultiHeadDotProductAttention_0"]
        for proj in ("query", "key", "value", "out"):
            k = _shape(attn[proj]["kernel"])
            total += 2.0 * math.prod(k) * seq
        total += 2.0 * 2.0 * seq * seq * dim  # QK^T and softmax(.)V over all heads
    return total


def encoder_forward_flops(params: dict, image_size: int) -> float:
    """One image, backbone + projection head."""
    backbone = params["backbone"]
    fwd = (
        vit_forward_flops(backbone, image_size)
        if "patch_embed" in backbone
        else resnet_forward_flops(backbone, image_size)
    )
    return fwd + _dense_flops(params.get("head", {}))


def train_step_flops(
    params_q: dict, params_pred: dict, image_size: int, global_batch: int,
    v3: bool, dim: int, num_negatives: int,
) -> float:
    """Operations one optimisation step needs over the global batch.

    A trained pass costs 3x its forward (forward, gradient w.r.t.
    activations, gradient w.r.t. weights); the momentum encoder only runs
    forward. MoCo v1/v2: the query encoder trains on one view, the key
    encoder embeds the other (3 + 1 forwards an image pair), and the
    InfoNCE logits against the queue cost 2*B*dim*(1+K) forward and as
    much again for dq. MoCo v3: both views go through both encoders
    (2 x (3 + 1) forwards), the predictor trains on both, and the
    B x B logits are negligible but counted."""
    fwd = encoder_forward_flops(params_q, image_size)
    if v3:
        pred = _dense_flops(params_pred)
        logits = 2 * 2.0 * global_batch * global_batch * dim
        return global_batch * 2 * (4.0 * fwd + 3.0 * pred) + 3.0 * logits
    return global_batch * 4.0 * fwd + infonce_required(global_batch, dim, num_negatives)["flops"]


def infonce_required(batch: int, dim: int, num_keys: int) -> dict:
    """What the streaming InfoNCE over (q, k, queue) needs, forward and
    backward together, with f32 operands as the kernel takes them:
    logits B x (1+K) once forward (2*B*dim*(1+K)) and dq = p @ queue once
    backward (as much again); the logits recomputed in the backward pass
    are recomputation and not counted. Bytes: the queue streams through
    once in each direction; q, k, dq and the per-row statistics are small
    but counted."""
    flops = 2 * 2.0 * batch * dim * (1 + num_keys)
    queue_bytes = 4.0 * num_keys * dim
    small = 4.0 * (3 * batch * dim + 6 * batch)
    return {"flops": flops, "bytes": 2 * queue_bytes + small}


def roofline_seconds(required: dict, peaks: dict) -> tuple[float, str]:
    """(least seconds the chip could take, which bound applies)."""
    t_flops = required["flops"] / peaks["flops_per_s"]
    t_bytes = required["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
