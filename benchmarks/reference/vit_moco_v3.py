"""Plain reference: ViT (pre-norm blocks, class token, fixed 2-D sin-cos
positions) + the MoCo v3 projector and predictor + the symmetric loss.

Written from arXiv:2010.11929 (ViT) and arXiv:2104.02057 (MoCo v3, alg. 1
and section 4: 3-layer projector and 2-layer predictor MLPs with BN, an
affine-free BN on their outputs, loss 2*T*CE summed over both
directions). Departures from the papers, each the program's own choice
that the reference has to share to be comparable:

- GELU in its tanh form (flax's default), where the papers use erf;
- both views go through an encoder as ONE concatenated batch, so the
  heads' BN statistics are over 2B rows, where alg. 1 forwards each view
  on its own;
- LayerNorm epsilon 1e-6 (flax's default, and the ViT code's).

It reads the program's parameter tree by its flax names and shares no
code with it.

The family's file: beside the forward (`loss_and_embeddings`, `embed`) it
states what the harness needs to know of the family and finds here by
name: `INPUT`, `TOLERANCES`, `forward_flops`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.flops import dense_flops, shape
from benchmarks.reference.common import (
    HI, batch_norm, cross_entropy, dense, l2_normalize, operand,
)

# what the encoder reads: `benchmarks/inputs/images.py`
INPUT = "images"

# `correct`'s limits that are this family's own (the others are
# `harness/correct.py`'s defaults, where each measure is explained).
# emb_centred_rel: ||sys - ref||_F over ||ref - mean row of ref||_F of the
# first view's normalised predictions, the bfloat16 system against this
# float32 reference, ViT-B/16. 0.12 since PR 24. It lies between two
# readings on the chip (PERF.md section 2): sound runs read 0.065-0.087
# over 20 seeds (my chip runs, PR 28; 0.068-0.082 over 11 in PR 24), the
# control (this reference with fp8 or int8 operands,
# `benchmarks/control.py`) 0.212-0.409 over 6 seeds x 2 types.
TOLERANCES = {"emb_centred_rel": 0.12}



def trained_gradient(grads, moco_cfg):
    """A gradient of the loss as the step trains on it: with
    `freeze_patch_embed` (the v3 stability trick, arXiv:2104.02057
    section 4.2) the step zeroes the patch projection's, so `correct`
    compares the rest."""
    if not moco_cfg.freeze_patch_embed:
        return grads
    backbone = grads["enc"]["backbone"]
    frozen = jax.tree.map(jnp.zeros_like, backbone["patch_embed"])
    return {**grads, "enc": {**grads["enc"], "backbone": {**backbone, "patch_embed": frozen}}}

def _layer_norm(x, p, eps: float = 1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3)))


def _sincos_2d(dim: int, grid: int, cls_token: bool) -> np.ndarray:
    omega = 1.0 / (10000.0 ** (np.arange(dim // 4, dtype=np.float64) / (dim // 4)))
    pos = np.arange(grid, dtype=np.float64)[:, None] * omega[None, :]
    one = np.concatenate([np.sin(pos), np.cos(pos)], axis=1)  # (grid, dim/2)
    emb = np.concatenate(
        [np.repeat(one[:, None, :], grid, axis=1), np.repeat(one[None, :, :], grid, axis=0)],
        axis=-1,
    ).reshape(grid * grid, dim)
    if cls_token:
        emb = np.concatenate([np.zeros((1, dim)), emb], axis=0)
    return emb.astype(np.float32)


def _attention(x, p):
    """Multi-head self-attention; kernels are (D, H, Dh) in, (H, Dh, D) out."""
    x = operand(x)
    proj = lambda n: jnp.einsum("bsd,dhe->bshe", x, operand(p[n]["kernel"]),
                                precision=HI) + p[n]["bias"]
    q, k, v = proj("query"), proj("key"), proj("value")
    scores = jnp.einsum("bshe,bthe->bhst", operand(q), operand(k), precision=HI) / np.sqrt(q.shape[-1])
    w = jax.nn.softmax(scores, axis=-1)
    y = jnp.einsum("bhst,bthe->bshe", operand(w), operand(v), precision=HI)
    return jnp.einsum("bshe,hed->bsd", operand(y), operand(p["out"]["kernel"]),
                      precision=HI) + p["out"]["bias"]


def backbone(params: dict, x):
    """Final-norm class-token feature (N, D) of float32 NHWC images."""
    w = operand(params["patch_embed"]["kernel"])  # (P, P, 3, D)
    patch, dim = w.shape[0], w.shape[3]
    n, h, _, c = x.shape
    grid = h // patch
    # non-overlapping patches: a strided convolution is a matmul on them
    patches = x.reshape(n, grid, patch, grid, patch, c).transpose(0, 1, 3, 2, 4, 5)
    tokens = jnp.matmul(
        operand(patches.reshape(n, grid * grid, patch * patch * c)), w.reshape(-1, dim), precision=HI
    ) + params["patch_embed"]["bias"]
    cls = "cls_token" in params
    if cls:
        tokens = jnp.concatenate(
            [jnp.broadcast_to(params["cls_token"], (n, 1, dim)), tokens], axis=1
        )
    tokens = tokens + _sincos_2d(dim, grid, cls)
    depth = sum(1 for k in params if k.startswith("block_"))
    for i in range(depth):
        blk = params[f"block_{i}"]
        tokens = tokens + _attention(
            _layer_norm(tokens, blk["LayerNorm_0"]), blk["MultiHeadDotProductAttention_0"]
        )
        y = _layer_norm(tokens, blk["LayerNorm_1"])
        y = dense(_gelu_tanh(dense(y, blk["MlpBlock_0"]["Dense_0"])), blk["MlpBlock_0"]["Dense_1"])
        tokens = tokens + y
    tokens = _layer_norm(tokens, params["final_norm"])
    return tokens[:, 0] if cls else jnp.mean(tokens, axis=1)


def mlp_head(params: dict, stats: dict, x, train: bool):
    """Dense (no bias) -> BN -> ReLU per hidden layer; the last Dense is
    followed by an affine-free BN when the tree holds statistics for it."""
    layers = sum(1 for k in params if k.startswith("Dense_"))
    for i in range(layers):
        x = dense(x, params[f"Dense_{i}"])
        name = f"BatchNorm_{i}"
        last = i == layers - 1
        if name in stats or name in params:
            x = batch_norm(x, params.get(name, {}), stats.get(name), train)
        if not last:
            x = jnp.maximum(x, 0.0)
    return x


def encode(params: dict, stats: dict, x, train: bool):
    """Projector output, not normalised (the predictor consumes it raw)."""
    return mlp_head(params["head"], stats.get("head", {}), backbone(params["backbone"], x), train)


def embed(params: dict, stats: dict, x):
    """What a served image gets: evaluation mode, L2-normalised."""
    return l2_normalize(encode(params, stats, x, train=False))


def loss_and_embeddings(
    params_q, stats_q, params_pred, stats_pred, params_k, stats_k, x1, x2, temperature
):
    """One MoCo v3 training forward, single device. Returns the loss and
    the first view's normalised predictions."""
    x = jnp.concatenate([x1, x2], axis=0)
    preds = mlp_head(params_pred, stats_pred, encode(params_q, stats_q, x, True), True)
    q1, q2 = jnp.split(l2_normalize(preds), 2, axis=0)
    keys = jax.lax.stop_gradient(l2_normalize(encode(params_k, stats_k, x, True)))
    k1, k2 = jnp.split(keys, 2, axis=0)
    labels = jnp.arange(q1.shape[0], dtype=jnp.int32)
    ctr = lambda q, k: 2.0 * temperature * cross_entropy(
        jnp.matmul(operand(q), operand(k).T, precision=HI) / temperature, labels
    )
    return ctr(q1, k2) + ctr(q2, k1), q1


# -- operations, from shapes alone ------------------------------------------
# The topology is the paper's (ViT: arXiv:2010.11929); the tree is the
# program's own (flax names).


def vit_forward_flops(backbone: dict, image_size: int) -> float:
    """Forward operations of one image through the program's ViT tree:
    patch projection, then per block QKV + scores + weighted sum +
    output projection + the two MLP matmuls, over S = patches (+1 with a
    class token) tokens."""
    ph, pw, cin, dim = shape(backbone["patch_embed"]["kernel"])
    patches = (image_size // ph) * (image_size // pw)
    seq = patches + (1 if "cls_token" in backbone else 0)
    total = 2.0 * ph * pw * cin * dim * patches
    for name, blk in backbone.items():
        if not name.startswith("block_"):
            continue
        total += dense_flops(blk["MlpBlock_0"], seq)
        attn = blk["MultiHeadDotProductAttention_0"]
        for proj in ("query", "key", "value", "out"):
            k = shape(attn[proj]["kernel"])
            total += 2.0 * math.prod(k) * seq
        total += 2.0 * 2.0 * seq * seq * dim  # QK^T and softmax(.)V over all heads
    return total


def forward_flops(param_shapes: dict, config) -> float:
    """One image forward through backbone + projection head, from the
    encoder's parameter shapes and the configuration's input size."""
    fwd = vit_forward_flops(param_shapes["backbone"], config.data.image_size)
    return fwd + dense_flops(param_shapes.get("head", {}))
