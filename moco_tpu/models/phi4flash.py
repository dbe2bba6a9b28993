"""Phi-4-mini-flash-reasoning's decoder stack as an encoder backbone.

The published language model (`model_type` `phi4flash`, 3.8 B:
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json),
the SambaY decoder-hybrid-decoder of arXiv:2507.06607, read as a text
encoder for momentum contrast: token ids in, one pooled feature row out.
Pre-norm blocks, LayerNorm with bias, residual adds, no position encoding
anywhere:

    x <- x + mixer_l(LN_1(x));  x <- x + W_down(silu(W_g h) * W_u h), h = LN_2(x)

The mixer of published layer l (0-based; `layer_kind`), with L/2 = 16:

- **Mamba** (l even, l <= L/2): [u, z] = W_in h; x = silu(causal
  depthwise conv_4(u) + b); [delta, B, C] = W_x x; dt = softplus(W_dt delta
  + b_dt); the selective scan s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t,
  y_t = s_t C_t + D x_t with A = -exp(A_log), (5120 channels, 16 states:
  `ops/selective_scan.py`); m = y * silu(z); out = W_out m. Layer L/2's m
  is the **memory** the gated memory units read.
- **window attention** (l odd, l < L/2) and **full attention** (l = L/2 + 1):
  differential attention (arXiv:2410.05258 section 2.1). Query heads 2i and
  2i+1 of 64 are pair i (20 pairs), key heads likewise (10 pairs), values
  10 heads of 128, pair i reads key/value pair i // 2;
  out = (softmax(q1 k1^T / 8) - lambda softmax(q2 k2^T / 8)) V as two calls
  of the causal kernels over the same V (`name="diff_attention"`), a
  per-head RMSNorm over the 128 values times (1 - lambda_init), then W_o.
  lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init =
  0.8 - 0.6 exp(-0.3 (l - 1)) at the PUBLISHED l. A window layer sees keys
  t-511..t. The full layer's projected k and v are kept.
- **gated memory unit** (l even, l >= L/2 + 2): out = W_2(m * silu(W_1 h)).
- **cross-attention** (l odd, l >= L/2 + 3): queries only (W_q, W_o), the
  same differential attention over the full layer's kept k and v; their
  gradient reaches that layer from every cross layer.

Not built: the LM head (tied to the embedding; a contrastive encoder has
no next-token objective). The state one block leaves for later ones is
`DecoderBackbone.run_block`'s carry, handed to a block as its argument:
under remat it is kept, never recomputed. What any decoder stack here
needs is `models/decoder.py`'s.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.models.decoder import DecoderBackbone, create_stack, dense, remat_block
from moco_tpu.ops.flash_attention import causal_flash_attention
from moco_tpu.ops.selective_scan import selective_scan
from moco_tpu.utils.platform import pallas_interpret

SUBLN_EPS = 1e-5  # the differential heads' RMSNorm (DIFF Transformer's)


@dataclasses.dataclass(frozen=True)
class StackSizes:
    """The sizes of one published stack (hashable: a flax attribute)."""

    vocab_size: int
    hidden: int
    layers: int
    heads: int  # query heads of head_dim; two make a differential pair
    kv_heads: int
    mlp: int
    window: int
    mb_per_layer: int
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int
    norm_eps: float

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def inner(self) -> int:
        return self.expand * self.hidden


_PHI4FLASH_CONFIGS = {
    # config.json's numbers; the Mamba sizes are the family's (config.json
    # has no key for them: `assumed` in benchmarks/configs/phi4_mini_flash_stage5.json)
    "phi4_mini_flash": StackSizes(
        vocab_size=200064, hidden=2560, layers=32, heads=40, kv_heads=20, mlp=10240, window=512,
        mb_per_layer=2, d_state=16, d_conv=4, expand=2, dt_rank=160, norm_eps=1e-5,
    ),
    # the same stack at a test's size (CPU): 12 layers, so the map's
    # boundaries are 6 | 7 | 8+ and two cross layers read layer 7
    "phi4_flash_tiny": StackSizes(
        vocab_size=512, hidden=64, layers=12, heads=4, kv_heads=2, mlp=128, window=16,
        mb_per_layer=2, d_state=4, d_conv=4, expand=2, dt_rank=8, norm_eps=1e-5,
    ),
}


def layer_kind(cfg: StackSizes, layer: int) -> str:
    """The mixer of published layer `layer`: "mamba", "window", "full",
    "gmu" or "cross" (SambaY: the self-decoder's Mamba and window layers up
    to L/2, then the full layer whose k and v the cross-decoder reads)."""
    half, mamba = cfg.layers // 2, layer % cfg.mb_per_layer == 0
    if layer >= half + 2:
        return "gmu" if mamba else "cross"
    if mamba:
        return "mamba"
    return "window" if layer < half else "full"


def memory_layer(cfg: StackSizes) -> int:
    """The Mamba layer whose gated output the memory units read."""
    return cfg.layers // 2


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * (layer - 1))


def _dt_bias_init(key, shape, dtype=jnp.float32, dt_min=1e-3, dt_max=0.1):
    """softplus^-1 of a step drawn log-uniform in [dt_min, dt_max] (Mamba's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (math.log(dt_max) - math.log(dt_min))
                 + math.log(dt_min))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    """A = -(1, 2, ..., N) in every channel (S4D-real, Mamba's)."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


class Mamba(nn.Module):
    cfg: StackSizes
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, lengths):
        """h (B, S, d) -> (out (B, S, d), m (B, S, inner): the tensor W_out reads)."""
        c, dt_ = self.cfg, self.dtype
        e, n, k = c.inner, c.d_state, c.d_conv
        u, z = jnp.split(dense(2 * e, dt_, "in_proj")(h), 2, axis=-1)
        w = self.param("conv_kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=1), (k, e), jnp.float32)
        bias = self.param("conv_bias", nn.initializers.zeros, (e,), jnp.float32)
        padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        s = u.shape[1]
        conv = sum(w[i] * padded[:, i : i + s] for i in range(k)) + bias  # out_t reads u_{t-k+1..t}
        x = nn.silu(conv).astype(dt_)
        delta, b, cc = jnp.split(dense(c.dt_rank + 2 * n, dt_, "x_proj")(x), [c.dt_rank, c.dt_rank + n], axis=-1)
        limit = c.dt_rank ** -0.5
        w_dt = self.param("dt_kernel", lambda key, sh: jax.random.uniform(key, sh, jnp.float32, -limit, limit),
                          (c.dt_rank, e))
        b_dt = self.param("dt_bias", _dt_bias_init, (e,))
        step = jax.nn.softplus(
            jnp.matmul(delta, w_dt.astype(dt_), preferred_element_type=jnp.float32) + b_dt
        )
        a_log = self.param("A_log", _a_log_init, (e, n))
        skip = self.param("D", nn.initializers.ones, (e,), jnp.float32)
        y = selective_scan(x, step, a_log, b, cc, skip, lengths, interpret=pallas_interpret())
        m = y * nn.silu(z)
        return dense(c.hidden, dt_, "out_proj")(m), m


class DiffAttention(nn.Module):
    """Differential attention: "self" layers project q, k and v (`window`
    None: every causal key); a "cross" layer projects q alone and reads the
    k and v it is handed."""

    cfg: StackSizes
    layer: int  # published: lambda_init depends on depth
    window: Optional[int]
    cross: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, lengths, kv=None):
        """-> (out (B, S, d), (k1, k2, v) as the kernels read them)."""
        c, dt_ = self.cfg, self.dtype
        b, s, d = h.shape
        hd, pairs, kv_pairs = c.head_dim, c.heads // 2, c.kv_heads // 2
        heads = lambda t, n, w: t.reshape(b, s, n, w).transpose(0, 2, 1, 3)  # (B, n, S, w)
        q = dense(c.heads * hd, dt_, "q")(h).reshape(b, s, pairs, 2 * hd)
        q1, q2 = heads(q[..., :hd], pairs, hd), heads(q[..., hd:], pairs, hd)
        if self.cross:
            k1, k2, v = kv
        else:
            k = dense(c.kv_heads * hd, dt_, "k")(h).reshape(b, s, kv_pairs, 2 * hd)
            k1, k2 = heads(k[..., :hd], kv_pairs, hd), heads(k[..., hd:], kv_pairs, hd)
            v = heads(dense(c.kv_heads * hd, dt_, "v")(h), kv_pairs, 2 * hd)
        attend = lambda qi, ki: causal_flash_attention(
            qi, ki, v, lengths, scale=hd**-0.5, interpret=pallas_interpret(), window=self.window,
            name="diff_attention",
        ).astype(jnp.float32)
        vec = lambda name: self.param(name, nn.initializers.normal(0.1), (hd,), jnp.float32)
        init = lambda_init(self.layer)
        lam = (jnp.exp(jnp.dot(vec("lambda_q1"), vec("lambda_k1")))
               - jnp.exp(jnp.dot(vec("lambda_q2"), vec("lambda_k2"))) + init)
        out = attend(q1, k1) - lam * attend(q2, k2)  # (B, pairs, S, 2 hd)
        scale = self.param("subln", nn.initializers.ones, (2 * hd,), jnp.float32)
        out = out * lax.rsqrt(jnp.mean(jnp.square(out), axis=-1, keepdims=True) + SUBLN_EPS)
        out = (out * scale * (1.0 - init)).astype(dt_).transpose(0, 2, 1, 3).reshape(b, s, d)
        return dense(d, dt_, "o")(out), (k1, k2, v)


class Block(nn.Module):
    cfg: StackSizes
    layer: int  # published index
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, lengths, shared=None):
        """-> (x, what this block leaves for later ones or None). `shared`:
        the memory (a gated memory unit) or the full layer's (k1, k2, v)
        (a cross layer)."""
        c, dt_ = self.cfg, self.dtype
        kind = layer_kind(c, self.layer)
        norm = lambda name: nn.LayerNorm(epsilon=c.norm_eps, dtype=dt_, name=name)
        h = norm("norm_1")(x)
        left = None
        if kind == "mamba":
            with jax.named_scope("mamba"):
                out, m = Mamba(c, dt_, name="mamba")(h, lengths)
            left = m if self.layer == memory_layer(c) else None
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                gate = nn.silu(dense(c.inner, dt_, "in_proj")(h))
                out = dense(c.hidden, dt_, "out_proj")(shared * gate)
        elif kind == "cross":
            with jax.named_scope("cross_attention"):
                out, _ = DiffAttention(c, self.layer, None, cross=True, dtype=dt_, name="attn")(
                    h, lengths, shared
                )
        else:
            window = c.window if kind == "window" else None
            out, kv = DiffAttention(c, self.layer, window, dtype=dt_, name="attn")(h, lengths)
            left = kv if kind == "full" else None
        x = x + out
        gate, up = jnp.split(dense(2 * c.mlp, dt_, "gate_up")(norm("norm_2")(x)), 2, axis=-1)
        return x + dense(c.hidden, dt_, "down")(nn.silu(gate) * up), left


RematBlock = remat_block(Block)

# what a block of each kind reads from the carry (the mamba and full layers leave it)
_READS = {"gmu": "memory", "cross": "kv"}


class Phi4FlashBackbone(DecoderBackbone):
    """`models/decoder.py::DecoderBackbone` over this family's blocks: the
    published index `first_layer + i` says what block i is, and names it
    (`layer_<published index>`); the memory and the full layer's k and v
    travel in the carry; the final norm is a LayerNorm."""

    def block(self, i: int, train: bool) -> nn.Module:
        layer = self.first_layer + i
        return (RematBlock if self.remat else Block)(
            cfg=self.cfg, layer=layer, dtype=self.dtype, name=f"layer_{layer}"
        )

    def run_block(self, i: int, train: bool, x, lengths, carry: dict):
        kind = layer_kind(self.cfg, self.first_layer + i)
        reads = _READS.get(kind)
        if reads is not None and reads not in carry:
            raise ValueError(
                f"layer {self.first_layer + i} ({kind}) reads the {reads} of an earlier layer this cut does not hold"
            )
        x, left = self.block(i, train)(x, lengths, carry.get(reads))
        if left is not None:
            carry = {**carry, "memory" if kind == "mamba" else "kv": left}
        return x, carry

    def norm(self, name: str) -> nn.Module:
        return nn.LayerNorm(epsilon=self.cfg.norm_eps, dtype=jnp.float32, name=name)


def create_phi4flash(arch: str, **cut) -> Phi4FlashBackbone:
    """`cut`: `models/decoder.py::create_stack`'s (dtype, layers,
    vocab_rows, remat, first_layer); no expert share."""
    return create_stack(Phi4FlashBackbone, _PHI4FLASH_CONFIGS, arch, **cut)
