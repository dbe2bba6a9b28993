"""Plain reference: Phi-4-mini-flash-reasoning's decoder stack (SambaY) as a
text encoder + the MoCo v2 MLP head + InfoNCE over (q, k, queue).

Written from the published configuration
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json,
`model_type` `phi4flash`), arXiv:2507.06607 (SambaY: Mamba and window
attention in the self-decoder, one full-attention layer whose keys and
values the cross-decoder reads, gated memory units), arXiv:2312.00752
(Mamba's selective scan), arXiv:2410.05258 (differential attention) and
arXiv:1911.05722 / 2003.04297 (MoCo's queue, InfoNCE, the 2-layer head).

    x = embed(ids)
    per published layer l (L = 32 layers, so L/2 = 16):
      h = LayerNorm_1(x)
      Mamba (l even, l <= 16):
        [u, z] = h W_in;  x' = silu(sum_i w_i u_{t-3+i} + b)   causal, width 4
        [delta, B, C] = x' W_x;  dt = softplus(delta W_dt + b_dt);  A = -exp(A_log)
        s_t = exp(dt_t A) s_{t-1} + dt_t x'_t B_t;  y_t = s_t C_t + D x'_t
        m = y * silu(z);  out = m W_out;  layer 16's m is the memory
      differential attention (l odd < 16: keys t-511..t; l = 17: every key):
        q = h W_q: 40 heads of 64, q1 = heads 0, 2, ..., q2 = heads 1, 3, ...
        k = h W_k: 20 heads of 64, k1, k2 likewise;  v = h W_v: 10 heads of 128
        pair i reads k1, k2, v of pair i // 2;  lambda_init = 0.8 - 0.6 exp(-0.3 (l - 1))
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        a = softmax(q1 k1^T / 8) v - lambda softmax(q2 k2^T / 8) v
        out = (RMSNorm_128(a) (1 - lambda_init)) W_o;  layer 17 keeps k1, k2, v
      gated memory unit (l even >= 18): out = (memory * silu(h W_1)) W_2
      cross-attention (l odd >= 19): q = h W_q, the same differential
        attention over layer 17's k1, k2, v (causal)
      x += out;  x += (silu(u W_g) * u W_u) W_down,  u = LayerNorm_2(x)
    output: mean over valid positions of LayerNorm(x) -> Linear-ReLU-Linear -> L2

No kernel: the scan is a sequential recurrence one position after
another; attention is a masked softmax, computed in blocks of query rows
(and rows of the batch one at a time) so that 16 384 positions fit a chip.
It reads the program's parameter tree by its flax names, the published
index of each layer from its name (`layer_<l>`), and shares no code with
the program.

Departures from the published model, the program's own, shared to be
comparable: no LM head; pooling and projection head are momentum
contrast's; the layer map, the head pairing, the Mamba sizes and the
window's edge are inferred (the configuration file's `assumed`).

The family's file: beside the forward it states `INPUT`, `TOLERANCES`,
`forward_flops`.
"""

from __future__ import annotations

import math

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
# `harness/correct.py`'s defaults), set on the chip at the cell's own size,
# 2 x 4 rows of 16 384 positions (PERF.md section 2; my chip runs, PR 37):
# emb_centred_rel: the bfloat16 program against this float32 reference read
# 0.0204-0.0325 in nine timed runs and 0.0253-0.0395 on three more seeds
# (`benchmarks/control.py`'s own reading), three times SmallThinker's: each
# differential head subtracts two softmaxes of the same values, and what
# bfloat16 rounds in each survives the subtraction, which the head's RMSNorm
# then scales up. The control (this reference with fp8 / int8 operands)
# read 0.361-0.497 / 0.576-1.009 on those three seeds. 0.12 lies between,
# 3.0 times over the largest sound reading and 3.0 under the smallest
# control. loss_abs keeps the default 0.02: sound runs read 0-0.000035, the
# control 0.000006-0.0013, so it has no upper reading here either and guards
# against a gross fault only.
TOLERANCES = {"emb_centred_rel": 0.12}

# What the parameter shapes do not say, from the published config.json
# (keyed by hidden size; the second row is the CPU tests' `phi4_flash_tiny`).
SIZES = {
    2560: dict(layers=32, heads=40, kv_heads=20, window=512, mb_per_layer=2, eps=1e-5),
    64: dict(layers=12, heads=4, kv_heads=2, window=16, mb_per_layer=2, eps=1e-5),
}
SUBLN_EPS = 1e-5
ROW_BLOCK = 256  # query rows of one attention block: 2 x (20, 256, 16384) float32 scores, 671 MB


def _sizes(backbone: dict) -> dict:
    return SIZES[shape(backbone["embed"]["embedding"])[1]]


def _kind(layer: int, sz: dict) -> str:
    half = sz["layers"] // 2
    if layer % sz["mb_per_layer"] == 0:
        return "mamba" if layer <= half else "gmu"
    if layer < half:
        return "window"
    return "full" if layer == half + 1 else "cross"


def _mm(x, w):
    return jnp.matmul(operand(x), operand(w), precision=HI)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(h, p, length):
    """One row's Mamba mixer: h (S, d) -> (out (S, d), m (S, E))."""
    s = h.shape[0]
    e, n = shape(p["A_log"])
    rank = shape(p["dt_kernel"])[0]
    xz = _mm(h, p["in_proj"]["kernel"])
    u, z = xz[:, :e], xz[:, e:]
    w = p["conv_kernel"]  # (K, E): output t reads inputs t-K+1 .. t
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, e), u.dtype), u], axis=0)
    x = _silu(sum(w[i] * padded[i : i + s] for i in range(taps)) + p["conv_bias"])
    proj = _mm(x, p["x_proj"]["kernel"])
    delta, b, c = proj[:, :rank], proj[:, rank : rank + n], proj[:, rank + n :]
    dt = jax.nn.softplus(_mm(delta, p["dt_kernel"]) + p["dt_bias"])
    dt = jnp.where((jnp.arange(s) < length)[:, None], dt, 0.0)
    a = -jnp.exp(p["A_log"])  # (E, N)

    def step(state, t):
        dt_t, x_t, b_t, c_t = t
        state = jnp.exp(dt_t[:, None] * a) * state + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=1)

    _, y = lax.scan(step, jnp.zeros((e, n), jnp.float32), (dt, x, b, c))
    m = (y + p["D"] * x) * _silu(z)
    return _mm(m, p["out_proj"]["kernel"]), m


def _diff_attention(h, p, length, layer, sz, window, kv=None):
    """One row's differential attention: h (S, d) -> (out (S, d), (k1, k2, v)).
    `kv` given: a cross layer (queries only)."""
    s = h.shape[0]
    n_q, n_kv = sz["heads"], sz["kv_heads"]
    width = shape(p["q"]["kernel"])[1] // n_q
    q = _mm(h, p["q"]["kernel"]).reshape(s, n_q, width)
    q1, q2 = q[:, 0::2], q[:, 1::2]  # (S, n_q / 2, width)
    if kv is None:
        k = _mm(h, p["k"]["kernel"]).reshape(s, n_kv, width)
        v = _mm(h, p["v"]["kernel"]).reshape(s, n_kv // 2, 2 * width)
        kv = (k[:, 0::2], k[:, 1::2], v)
    k1, k2, v = kv
    pair_kv = np.arange(n_q // 2) // (n_q // n_kv)  # the key/value pair each query pair reads
    init = 0.8 - 0.6 * math.exp(-0.3 * (layer - 1))
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)
    kk1, kk2, vv = k1[:, pair_kv], k2[:, pair_kv], v[:, pair_kv]  # the reference may copy
    cols = jnp.arange(s)

    def block(args):
        qb1, qb2, rows = args
        mask = (cols[None, :] <= rows[:, None]) & (cols[None, :] < length)
        if window is not None:
            mask = mask & (rows[:, None] - cols[None, :] < window)

        def softmax_v(qb, kk):
            scores = jnp.einsum("qhd,khd->hqk", operand(qb), operand(kk), precision=HI) / np.sqrt(width)
            w = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
            return jnp.einsum("hqk,khd->qhd", operand(w), operand(vv), precision=HI)

        return softmax_v(qb1, kk1) - lam * softmax_v(qb2, kk2)

    rb = ROW_BLOCK if s % ROW_BLOCK == 0 else s
    split = lambda t: t.reshape(s // rb, rb, *t.shape[1:])
    a = lax.map(block, (split(q1), split(q2), split(cols))).reshape(s, n_q // 2, 2 * width)
    a = a * lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + SUBLN_EPS) * p["subln"]
    return _mm((a * (1.0 - init)).reshape(s, -1), p["o"]["kernel"]), kv


def backbone(params: dict, stats: dict, inputs: dict):
    """Pooled features (N, d) of `{"ids": (N, S), "lengths": (N,)}`."""
    sz = _sizes(params)
    layers = sorted(int(k[6:]) for k in params if k.startswith("layer_"))

    def row(args):
        ids, length = args
        x = params["embed"]["embedding"][ids]
        memory = kv = None
        for layer in layers:
            p = params[f"layer_{layer}"]
            kind = _kind(layer, sz)
            h = _layer_norm(x, p["norm_1"], sz["eps"])
            if kind == "mamba":
                out, m = _mamba(h, p["mamba"], length)
                memory = m if layer == sz["layers"] // 2 else memory
            elif kind == "gmu":
                out = _mm(memory * _silu(_mm(h, p["in_proj"]["kernel"])), p["out_proj"]["kernel"])
            elif kind == "cross":
                out, _ = _diff_attention(h, p["attn"], length, layer, sz, None, kv)
            else:
                window = sz["window"] if kind == "window" else None
                out, kv_l = _diff_attention(h, p["attn"], length, layer, sz, window)
                kv = kv_l if kind == "full" else kv
            x = x + out
            u = _layer_norm(x, p["norm_2"], sz["eps"])
            gate_up = _mm(u, p["gate_up"]["kernel"])
            ff = gate_up.shape[-1] // 2
            x = x + _mm(_silu(gate_up[:, :ff]) * gate_up[:, ff:], p["down"]["kernel"])
        x = _layer_norm(x, params["final_norm"], sz["eps"])
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

# operations of the scan a token, channel and state: the decay's multiply
# and exp, the state's two multiplies and add, C's multiply-add
SCAN_OPS_PER_STATE = 7


# -- operations, from shapes alone ------------------------------------------


def forward_flops(param_shapes: dict, config) -> float:
    """One row (one sequence of `config.data.seq_len` tokens, every
    position valid) forward through stack + head. Per token: every 2-D
    `kernel` of the stack (projections, memory units, MLPs) and the Mamba
    layers' dt projection; the causal convolution (2 a tap and channel);
    the scan (`SCAN_OPS_PER_STATE` a channel and state, and the skip's
    multiply-add and the input's dt multiply a channel). Per row: each
    attention layer's pairs a differential head
    (`required/window_attention.py::pairs`: S^2/2, or W*S - W^2/2 under
    the window) x 2 (its two softmaxes) x (q.k width + v width) x 2. The
    head runs once a row."""
    bb = param_shapes["backbone"]
    sz = _sizes(bb)
    s = config.data.seq_len
    per_token = dense_flops({k: v for k, v in bb.items() if k != "embed"})
    attention = 0.0
    for name, layer in bb.items():
        if not name.startswith("layer_"):
            continue
        kind = _kind(int(name[6:]), sz)
        if kind == "mamba":
            mb = layer["mamba"]
            rank, e = shape(mb["dt_kernel"])
            taps, _ = shape(mb["conv_kernel"])
            n = shape(mb["A_log"])[1]
            per_token += 2.0 * rank * e + 2.0 * taps * e + e * (SCAN_OPS_PER_STATE * n + 3)
        elif kind != "gmu":
            width = shape(layer["attn"]["q"]["kernel"])[1] // sz["heads"]
            window = sz["window"] if kind == "window" else None
            attention += pairs(s, window) * (sz["heads"] // 2) * 2 * 2.0 * (width + 2 * width)
    return s * per_token + attention + dense_flops(param_shapes.get("head", {}))
