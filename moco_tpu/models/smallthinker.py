"""SmallThinker-21BA3B's decoder stack as an encoder backbone.

The published language model (`model_name` `smallthinker_21b_instruct`,
21B-A3B: https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json)
read as a text encoder for momentum contrast: token ids in, one pooled
feature row out. Pre-norm blocks, RMSNorm, residual adds; layer l is
**full** where `layout[l mod 4]` is 0 and **window** where it is 1
(`sliding_window_layout` and `rope_layout`, both full, window, window,
window):

- **the router reads before attention**: h = RMSNorm_in(x); the logits
  g = h W_r over all routed experts, float32 at highest precision. The 6
  chosen are the top 6 of g, their weights the softmax over those six
  logits (`moe_primary_router_apply_softmax`; softmax over all 64 and
  renormalising the six, `norm_topk_prob`, is the same number). No bias,
  no balance term, no shared expert, no scaling factor.
- **attention over grouped key heads**: 28 query heads and 4 key/value
  heads of 128, query head j reads key head j // 7, no biases. A window
  layer turns q and k by RoPE in the half-split layout (dims i and i + 64
  by position * theta^(-2i/128)) and sees key p from query t iff
  t - p < 4096; a full layer has no position encoding and sees every key
  at or before the query. The product runs through
  `ops/flash_attention.py::causal_flash_attention`, which is handed the
  4 key heads as they are and the layer's window, and takes the Pallas
  kernels from the sequence length on, never from a flag.
- **ReGLU experts**: u = RMSNorm_post(x); y = sum over the chosen e of
  w_e W_down,e (relu(W_gate,e u) * W_up,e u), width 768, through the
  share this chip holds (`models/decoder.py::ExpertDispatch`: selects
  over ALL experts, computes what its own give, adds nothing for the
  absent ones, drops no token).

Not built: the LM head (a contrastive encoder has no next-token
objective) and the secondary experts the model's card mentions (the
published config.json has no key for them). Assumed: that the router
reads the input-normalised h. What any decoder stack here needs is
`models/decoder.py`'s, shared with `models/joyai.py`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.models.decoder import (
    DecoderBackbone, ExpertDispatch, RMSNorm, create_stack, dense, remat_block,
    valid_positions,
)
from moco_tpu.ops.flash_attention import causal_flash_attention
from moco_tpu.utils.platform import pallas_interpret


@dataclasses.dataclass(frozen=True)
class StackSizes:
    """The sizes of one published stack (hashable: a flax attribute)."""

    vocab_size: int
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_mlp: int
    experts: int
    top_k: int
    window: int
    layout: tuple  # one period of sliding_window_layout / rope_layout: 1 = window + RoPE
    rope_theta: float


_SMALLTHINKER_CONFIGS = {
    # every number is the published config.json's
    "smallthinker_21b": StackSizes(
        vocab_size=151936, hidden=2560, layers=52, heads=28, kv_heads=4, head_dim=128,
        expert_mlp=768, experts=64, top_k=6, window=4096, layout=(0, 1, 1, 1),
        rope_theta=1.5e6,
    ),
    # the same stack at a test's size (CPU)
    "smallthinker_tiny": StackSizes(
        vocab_size=512, hidden=64, layers=4, heads=4, kv_heads=2, head_dim=16,
        expert_mlp=32, experts=8, top_k=2, window=16, layout=(0, 1, 1, 1),
        rope_theta=1.5e6,
    ),
}


def rope_half_split(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on (B, S, H, D) in the `rotate_half` layout: the
    pair (x[i], x[i + D/2]) turns by position * theta^(-2i/D). float32 inside."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos[:, None] * inv_freq[None, :])[None, :, None, :]  # (1, S, 1, D/2)
    xf = x.astype(jnp.float32)
    a, b = xf[..., : d // 2], xf[..., d // 2 :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


class GroupedAttention(nn.Module):
    """`window` None: a full layer, no position encoding."""

    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    rope_theta: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, lengths):
        b, s, d = x.shape
        h, hk, hd, dt = self.heads, self.kv_heads, self.head_dim, self.dtype
        q = dense(h * hd, dt, "q")(x).reshape(b, s, h, hd)
        k = dense(hk * hd, dt, "k")(x).reshape(b, s, hk, hd)
        v = dense(hk * hd, dt, "v")(x).reshape(b, s, hk, hd)
        if self.window is not None:
            q, k = rope_half_split(q, self.rope_theta), rope_half_split(k, self.rope_theta)
        out = causal_flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            lengths, scale=hd**-0.5, interpret=pallas_interpret(), window=self.window,
        )
        return dense(d, dt, "o")(out.transpose(0, 2, 1, 3).reshape(b, s, h * hd))


def route(logits: jax.Array, top_k: int):
    """The top k of the raw logits choose; the softmax over the chosen
    logits weighs. (T, E) float32 -> chosen (T, k) int32, weights (T, k)."""
    picked, chosen = lax.top_k(logits, top_k)
    return chosen, jax.nn.softmax(picked, axis=-1)


class ExpertLayer(ExpertDispatch):
    """Routed ReGLU experts (this chip's share of them), the selection
    from logits the block computed before its attention."""

    @nn.compact
    def __call__(self, x, valid, logits):
        """x (T, d) tokens; valid (T,) bool, False on padding; logits (T, E)."""
        with jax.named_scope("moco.moe_dispatch"):
            chosen, weights = route(logits, self.top_k)
        return self.routed(x, valid, chosen, weights, nn.relu)


class Block(nn.Module):
    cfg: StackSizes
    window: Optional[int]  # None: a full layer
    first_expert: int
    experts_held: int
    train: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, lengths):
        c, dt = self.cfg, self.dtype
        b, s, d = x.shape
        h = RMSNorm(dt, name="attn_norm")(x)
        router = self.param("router", nn.initializers.lecun_normal(), (d, c.experts), jnp.float32)
        with jax.named_scope("moco.moe_dispatch"):
            logits = jnp.matmul(
                h.astype(jnp.float32).reshape(b * s, d), router, precision=lax.Precision.HIGHEST
            )
        attn = GroupedAttention(
            heads=c.heads, kv_heads=c.kv_heads, head_dim=c.head_dim, window=self.window,
            rope_theta=c.rope_theta, dtype=dt, name="attn",
        )
        x = x + attn(h, lengths)
        u = RMSNorm(dt, name="mlp_norm")(x)
        layer = ExpertLayer(
            experts=c.experts, top_k=c.top_k, expert_mlp=c.expert_mlp,
            first_expert=self.first_expert, experts_held=self.experts_held,
            train=self.train, dtype=dt, name="moe",
        )
        valid = valid_positions(lengths, s).reshape(-1)
        return x + layer(u.reshape(b * s, d), valid, logits).reshape(b, s, d)


RematBlock = remat_block(Block)


def layer_window(cfg: StackSizes, i: int) -> Optional[int]:
    """Layer i's window from its place in the period; None: a full layer."""
    return cfg.window if cfg.layout[i % len(cfg.layout)] else None


class SmallThinkerBackbone(DecoderBackbone):
    """`models/decoder.py::DecoderBackbone` over this family's blocks: the
    layer's place in the period says whether it has a window.

    The embedding starts at unit variance (torch's `nn.Embedding` default;
    config.json states no range). At 0.02 a row's embedding has norm 1
    beside block outputs of norm 7-16, most of them the first full layer's
    near-uniform mean of values, which is the same vector at every
    position: the routers of layers 1-3, which read ahead of attention,
    then send nearly every token to the same six experts, and whether
    those are among the 8 held is a coin toss by seed (a held expert saw
    1 030-2 480 tokens and the step took 0.774-0.792 s by seed on the
    chip; PERF.md section 6, PR 33). A trained router is not collapsed; at
    unit variance routing stays a token's own, as uneven as the ids."""

    embed_std: float = 1.0

    def block(self, i: int, train: bool) -> nn.Module:
        return (RematBlock if self.remat else Block)(
            cfg=self.cfg, window=layer_window(self.cfg, i),
            first_expert=self.first_expert, experts_held=self.experts_held, train=train,
            dtype=self.dtype, name=f"layer_{i}",
        )


def create_smallthinker(arch: str, **cut) -> SmallThinkerBackbone:
    """`cut`: `models/decoder.py::create_stack`'s (dtype, layers,
    vocab_rows, expert_share, remat)."""
    return create_stack(SmallThinkerBackbone, _SMALLTHINKER_CONFIGS, arch, **cut)
