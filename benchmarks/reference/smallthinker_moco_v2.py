"""Plain reference: SmallThinker-21BA3B's decoder stack as a text encoder +
the MoCo v2 MLP head + InfoNCE over (q, k, queue).

Written from the published configuration
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json,
`model_name` `smallthinker_21b_instruct`), arXiv:2104.09864 (RoPE, here in
the half-split `rotate_half` layout), arXiv:2305.13245 (grouped key heads),
arXiv:2002.05202 (gated linear units, here gated by ReLU) and
arXiv:1911.05722 / 2003.04297 (MoCo's queue, InfoNCE, the 2-layer head).

    x = embed(ids)
    per layer l (full where l mod 4 == 0, window otherwise):
      h = RMSNorm_in(x)
      g = h W_r                      the router reads BEFORE attention
      chosen = top 6 of g;  w = softmax over the six chosen logits
      q = h W_q -> 28 heads x 128;  k = h W_k, v = h W_v -> 4 heads x 128
      window layer: RoPE on q and k, dims i and i + 64 turned by
                    pos * theta^(-2i/128);  full layer: no position encoding
      query head j reads key head j // 7; scores q.k / sqrt(128); key p is
      visible to query t iff p <= t, p < the row's length and, on a window
      layer, t - p < 4096
      x += (softmax . v) W_o
      u = RMSNorm_post(x)
      x += sum over chosen e HELD HERE of w_e W_down,e (relu(W_gate,e u) * W_up,e u)
    output: mean over valid positions of RMSNorm(x) -> Linear-ReLU-Linear -> L2

No kernel, no sort: k and v are indexed per query head, attention is a
masked (S, S) softmax with the band written as a mask, every held expert
is applied densely to every token and masked by the selection. All of it
is computed in blocks (rows of the batch one at a time, query rows in
blocks, experts one at a time) so that 16 384 positions fit a chip. It
reads the program's parameter tree by its flax names and shares no code
with it. The share (`first_expert`, how many experts are held) is read
from the state: the expert weights' leading axis, and the `first_expert`
the program keeps beside the held experts' load; what absent experts
would add is left out here as there.

Departures from the published model, the program's own, shared to be
comparable: no LM head; pooling and projection head are momentum
contrast's; the router reads the input-normalised h (the catalog says
only that it is placed before attention); no auxiliary balance loss; the
secondary experts the model's card mentions have no key in config.json
and are not built.

The family's file: beside the forward it states `INPUT`, `TOLERANCES`,
`forward_flops`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.harness.flops import dense_flops, shape
from benchmarks.reference.common import HI, cross_entropy, dense, l2_normalize, operand
from benchmarks.required.window_attention import pairs

# what the encoder reads: `benchmarks/inputs/tokens.py`
INPUT = "tokens"

# `correct`'s limits that are this family's own (the others are
# `harness/correct.py`'s defaults), set from `benchmarks/control.py` on the
# chip at the cell's own size, 2 x 4 rows of 16 384 positions (PERF.md
# section 2; my chip runs, PR 33):
# emb_centred_rel: ||sys - ref||_F over ||ref - mean row of ref||_F of the
# normalised query embeddings, the bfloat16 program against this float32
# reference. Sound runs read 0.0097-0.0121 over 13 seeds, the control (this
# reference with fp8 / int8 operands) 0.127-0.136 / 0.120-0.135 over 6: the
# mean over 16 384 positions averages the rounding of single tokens away, a
# flipped top-6 choice included. 0.035 lies between, 2.9 times over the one
# and 3.4 under the other. (With the embedding at N(0, 0.02), before the
# family's own init: 0.0102-0.0117 against 0.138-0.155 / 0.102-0.122.)
# loss_abs: |loss_sys - loss_ref| at T = 0.05 as a mean over only 4 rows (a
# row's positive logit moves by its embedding error x 20). Sound runs read
# 0.0004-0.022, which the default 0.02 does not hold; the control reads
# 0.027-0.174 (fp8) and 0.0005-0.133 (int8), so it has NO upper reading, as
# for every other family: the limit of the first token family, 0.1, is kept
# as a guard against a gross fault (a wrong temperature, a missing positive),
# at four times the largest sound reading.
TOLERANCES = {"emb_centred_rel": 0.035, "loss_abs": 0.1}

# What the parameter shapes do not say, from the published config.json
# (keyed by hidden size; the second row is the CPU tests' `smallthinker_tiny`).
SIZES = {
    2560: dict(heads=28, kv_heads=4, top_k=6, window=4096, layout=(0, 1, 1, 1), rope_theta=1.5e6),
    64: dict(heads=4, kv_heads=2, top_k=2, window=16, layout=(0, 1, 1, 1), rope_theta=1.5e6),
}
RMS_EPS = 1e-6
ROW_BLOCK = 256  # query rows of one attention block: (28, 256, 16384) float32 scores are 470 MB


def _sizes(backbone: dict) -> dict:
    return SIZES[shape(backbone["embed"]["embedding"])[1]]


def _mm(x, w):
    return jnp.matmul(operand(x), operand(w), precision=HI)


def _rms_norm(x, p):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + RMS_EPS) * p["scale"]


def _rope(x, theta: float):
    """(S, H, D): the pair (x[i], x[i + D/2]) turned by pos * theta^(-2i/D)."""
    s, d = x.shape[0], x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(s, dtype=np.float64)[:, None] * freq[None, :]).astype(np.float32)[:, None, :]
    lo, hi = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate(
        [lo * np.cos(ang) - hi * np.sin(ang), hi * np.cos(ang) + lo * np.sin(ang)], axis=-1
    )


def _is_window(layer_index: int, sz: dict) -> bool:
    return bool(sz["layout"][layer_index % len(sz["layout"])])


def _attention(h, p, length, sz, window):
    """One row's attention over grouped key heads: h (S, d) -> (S, d);
    `window` None on a full layer."""
    s = h.shape[0]
    n_q, n_kv = sz["heads"], sz["kv_heads"]
    width = shape(p["q"]["kernel"])[1] // n_q
    q = _mm(h, p["q"]["kernel"]).reshape(s, n_q, width)
    k = _mm(h, p["k"]["kernel"]).reshape(s, n_kv, width)
    v = _mm(h, p["v"]["kernel"]).reshape(s, n_kv, width)
    if window is not None:
        q, k = _rope(q, sz["rope_theta"]), _rope(k, sz["rope_theta"])
    key_head = np.arange(n_q) // (n_q // n_kv)  # the key head each query head reads
    k, v = k[:, key_head], v[:, key_head]  # (S, n_q, width): the reference may copy
    cols = jnp.arange(s)

    def block(args):
        qb, rows = args  # (rb, n_q, width), (rb,)
        scores = jnp.einsum("qhd,khd->hqk", operand(qb), operand(k), precision=HI) / np.sqrt(width)
        mask = (cols[None, :] <= rows[:, None]) & (cols[None, :] < length)
        if window is not None:
            mask = mask & (rows[:, None] - cols[None, :] < window)
        w = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", operand(w), operand(v), precision=HI)

    rb = ROW_BLOCK if s % ROW_BLOCK == 0 else s
    out = lax.map(block, (q.reshape(s // rb, rb, n_q, width), cols.reshape(s // rb, rb)))
    return _mm(out.reshape(s, n_q * width), p["o"]["kernel"])


def _experts(u, logits, p, stats, length, sz):
    """One row's expert layer: u (S, d), router logits (S, E) -> (S, d)."""
    s = u.shape[0]
    e = logits.shape[-1]
    held, _, ff2 = shape(p["experts_in"])
    ff = ff2 // 2
    picked, chosen = lax.top_k(logits, sz["top_k"])
    weights = jax.nn.softmax(picked, axis=-1)
    valid = jnp.arange(s) < length
    first = stats["first_expert"].astype(jnp.int32)

    def one(y, args):
        j, w_in, w_out = args
        share = jnp.sum(jnp.where(chosen == (first + j) % e, weights, 0.0), axis=-1) * valid
        hidden = jnp.maximum(_mm(u, w_in[:, :ff]), 0.0) * _mm(u, w_in[:, ff:])
        return y + share[:, None] * _mm(hidden, w_out), None

    y, _ = lax.scan(one, jnp.zeros_like(u), (jnp.arange(held), p["experts_in"], p["experts_out"]))
    return y


def _layer(x, p, stats, length, sz, window):
    h = _rms_norm(x, p["attn_norm"])
    logits = _mm(h, p["router"])  # before attention
    x = x + _attention(h, p["attn"], length, sz, window)
    return x + _experts(_rms_norm(x, p["mlp_norm"]), logits, p["moe"], stats["moe"], length, sz)


def backbone(params: dict, stats: dict, inputs: dict):
    """Pooled features (N, d) of `{"ids": (N, S), "lengths": (N,)}`."""
    sz = _sizes(params)
    layers = sorted((k for k in params if k.startswith("layer_")), key=lambda k: int(k[6:]))

    def row(args):
        ids, length = args
        x = params["embed"]["embedding"][ids]
        for name in layers:
            window = sz["window"] if _is_window(int(name[6:]), sz) else None
            x = _layer(x, params[name], stats[name], length, sz, window)
        x = _rms_norm(x, params["final_norm"])
        valid = (jnp.arange(x.shape[0]) < length)[:, None]
        return jnp.sum(jnp.where(valid, x, 0.0), axis=0) / jnp.maximum(length, 1)

    return lax.map(row, (inputs["ids"], inputs["lengths"].astype(jnp.int32)))


def encode(params: dict, stats: dict, inputs: dict):
    """L2-normalised embeddings."""
    feats = backbone(params["backbone"], stats.get("backbone", {}), inputs)
    head = params["head"]
    return l2_normalize(dense(jnp.maximum(dense(feats, head["Dense_0"]), 0.0), head["Dense_1"]))


def infonce(q, k, queue, temperature: float):
    """-log softmax of the positive among (1 + K) logits, mean over the batch."""
    k = lax.stop_gradient(k)
    l_pos = jnp.sum(q * k, axis=-1, keepdims=True)
    l_neg = jnp.matmul(operand(q), operand(queue).T, precision=HI)
    logits = jnp.concatenate([l_pos, l_neg], axis=1) / temperature
    return cross_entropy(logits, jnp.zeros((q.shape[0],), jnp.int32))


def loss_and_embeddings(params_q, stats_q, params_k, stats_k, queue, x_q, x_k, temperature):
    """One MoCo v2 training forward on a batch, single device."""
    q = encode(params_q, stats_q, x_q)
    k = encode(params_k, stats_k, x_k)
    return infonce(q, k, queue, temperature), q


# what a served sequence gets: the same forward (no layer of the stack
# behaves differently in evaluation)
embed = encode


# -- operations, from shapes alone ------------------------------------------


def forward_flops(param_shapes: dict, config) -> float:
    """One row (one sequence of `config.data.seq_len` tokens, every
    position valid) forward through stack + head. Per token: every 2-D
    `kernel` of the stack (the four attention projections), the router,
    and the routed products this chip is EXPECTED to run: top-k times
    held / all experts of them (uniform routing; what lands on absent
    experts is no work of this chip). Per row: the attention product, the
    layer's pairs a query head (`required/window_attention.py::pairs`:
    S^2/2, or W*S - W^2/2 under a window), 2 operations a pair
    for each of the q.k and p.v widths. The head runs once a row."""
    bb = param_shapes["backbone"]
    sz = _sizes(bb)
    s = config.data.seq_len
    per_token = dense_flops({k: v for k, v in bb.items() if k != "embed"})
    attention = 0.0
    for name, layer in bb.items():
        if not name.startswith("layer_"):
            continue
        d, e = shape(layer["router"])
        held, _, ff2 = shape(layer["moe"]["experts_in"])
        per_token += 2.0 * d * e
        per_token += sz["top_k"] * held / e * 2.0 * d * (ff2 + ff2 // 2)
        width = shape(layer["attn"]["q"]["kernel"])[1] // sz["heads"]
        window = sz["window"] if _is_window(int(name[6:]), sz) else None
        attention += pairs(s, window) * sz["heads"] * 2.0 * (width + width)
    return s * per_token + attention + dense_flops(param_shapes.get("head", {}))
