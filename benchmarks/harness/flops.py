"""Operations and bytes the algorithm needs, computed from shapes.

The yardstick's own arithmetic: a later PR to the program cannot change
what `step_mfu` or `infonce_roofline` divide by. Counts are the
operations the mathematics requires (a multiply-add is two operations);
rematerialised or recomputed work is not counted, so a kernel that
recomputes its logits in the backward pass gets no credit for that.

What is here belongs to momentum contrast and to shape arithmetic, and
names no encoder family: how many forwards a step is, the contrastive
logits, and what a matmul or a convolution of given shapes costs. What
one row costs forward through an encoder is its family's to say
(`forward_flops` of `benchmarks/reference/<name>.py`); what one kernel
needs is in `benchmarks/required/<name>.py`. Parameter trees are the
program's own (flax names); only shapes are read.
"""

from __future__ import annotations

def shape(leaf) -> tuple:
    return tuple(getattr(leaf, "shape", leaf))


def conv_flops(kernel_shape: tuple, out_hw: int) -> float:
    kh, kw, cin, cout = kernel_shape
    return 2.0 * kh * kw * cin * cout * out_hw * out_hw


def dense_flops(tree: dict, rows: float = 1.0) -> float:
    """Every 2-D `kernel` under `tree` applied to `rows` rows."""
    total = 0.0
    for name, sub in tree.items():
        if isinstance(sub, dict):
            total += dense_flops(sub, rows)
        elif name == "kernel" and len(shape(sub)) == 2:
            a, b = shape(sub)
            total += 2.0 * a * b * rows
    return total


def train_step_flops(
    forward: float, params_pred: dict, global_batch: int, v3: bool, dim: int, num_negatives: int,
) -> float:
    """Operations one optimisation step needs over the global batch, given
    `forward`, what one row costs forward through the encoder with its
    projection head (the family's `forward_flops`).

    A trained pass costs 3x its forward (forward, gradient w.r.t.
    activations, gradient w.r.t. weights); the momentum encoder only runs
    forward. MoCo v1/v2: the query encoder trains on one view, the key
    encoder embeds the other (3 + 1 forwards a pair of views), and the
    InfoNCE logits against the queue cost 2*B*dim*(1+K) forward and as
    much again for dq. MoCo v3: both views go through both encoders
    (2 x (3 + 1) forwards), the predictor trains on both, and the
    B x B logits are negligible but counted."""
    if v3:
        pred = dense_flops(params_pred)
        logits = 2 * 2.0 * global_batch * global_batch * dim
        return global_batch * 2 * (4.0 * forward + 3.0 * pred) + 3.0 * logits
    return global_batch * 4.0 * forward + infonce_flops(global_batch, dim, num_negatives)


def infonce_flops(batch: int, dim: int, num_keys: int) -> float:
    """The contrastive loss over (q, k, queue), forward and backward
    together: logits B x (1+K) once forward (2*B*dim*(1+K)) and
    dq = p @ queue once backward (as much again); logits recomputed in a
    backward pass are recomputation and not counted."""
    return 2 * 2.0 * batch * dim * (1 + num_keys)


def roofline_seconds(required: dict, peaks: dict) -> tuple[float, str]:
    """(least seconds the chip could take, which bound applies)."""
    t_flops = required["flops"] / peaks["flops_per_s"]
    t_bytes = required["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
