"""JoyAI-LLM-Flash's decoder stack as an encoder backbone.

The published language model (`model_type` `joyai_llm_flash`, 48B-A2.7B:
https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json)
read as a text encoder for momentum contrast: token ids in, one pooled
feature row out. Pre-norm blocks, RMSNorm, residual adds:

- **latent attention (MLA)**: queries through a 1536-wide latent, keys and
  values through a shared 512-wide latent plus one 64-wide rotary key that
  all heads share; per head 128 no-position + 64 rotary dims for q and k,
  128 for v; interleaved RoPE; causal. The attention product runs through
  `ops/flash_attention.py::causal_flash_attention`, which takes the Pallas
  kernels from the sequence length on, never from a flag.
- **layer 0** a dense SwiGLU MLP; **every later layer** a mixture of
  experts: sigmoid scores over all routed experts in float32, the top k of
  score + bias (`noaux_tc`, one group), weights = the chosen scores
  normalised to sum 1, times the routed scaling factor; plus a shared
  expert that every token takes.
- **the share**: an expert layer is told which experts it holds
  (`first_expert`, `experts_held`): it scores and selects over ALL of
  them and `models/decoder.py::ExpertDispatch` computes the part its own
  give (buffers sized by what is live, no token dropped at any load,
  nothing for the absent ones).
- **the bias** (`e_score_correction_bias`) is not trained by the gradient.
  Every training forward moves it by `BIAS_UPDATE_RATE` towards balance,
  from the selection counts over all experts (the DeepSeek-V3 report's
  rule, which `noaux_tc` names). It lives in `batch_stats`, the collection
  the train step already carries for BatchNorm statistics, so the key
  encoder routes with its own EMA router and its own bias.

Not built: the multi-token-prediction layer and the LM head (a
contrastive encoder has no next-token objective). The output is the mean
over a row's valid positions of the final RMSNorm'd states.

What any decoder stack here needs (RMSNorm, the expert dispatch, the
backbone skeleton with its pooling, the remat policy, `routing_metrics`)
is `models/decoder.py`'s, shared with `models/smallthinker.py`; this file
keeps the family's attention, routing function, block and sizes.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.models.decoder import (
    DecoderBackbone, ExpertDispatch, RMSNorm, create_stack, dense as _dense, remat_block,
    routing_metrics, valid_positions,
)
from moco_tpu.ops.flash_attention import causal_flash_attention
from moco_tpu.utils.platform import pallas_interpret

# gamma of the auxiliary-loss-free balancing rule (DeepSeek-V3 report)
BIAS_UPDATE_RATE = 1e-3


@dataclasses.dataclass(frozen=True)
class StackSizes:
    """The sizes of one published stack (hashable: a flax attribute)."""

    vocab_size: int
    hidden: int
    layers: int
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    dense_mlp: int
    expert_mlp: int
    experts: int
    top_k: int
    shared_experts: int
    routed_scale: float
    rope_theta: float


_JOYAI_CONFIGS = {
    # every number is the published config.json's
    "joyai_llm_flash": StackSizes(
        vocab_size=129280, hidden=2048, layers=40, heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope=128, qk_rope=64, v_head=128, dense_mlp=7168,
        expert_mlp=768, experts=256, top_k=8, shared_experts=1, routed_scale=2.5,
        rope_theta=32e6,
    ),
    # the same stack at a test's size (CPU)
    "joyai_tiny": StackSizes(
        vocab_size=512, hidden=64, layers=3, heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope=16, qk_rope=8, v_head=16, dense_mlp=128,
        expert_mlp=32, experts=8, top_k=2, shared_experts=1, routed_scale=2.5,
        rope_theta=32e6,
    ),
}


def rope_interleaved(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on (B, S, ..., D): the pair (x[2i], x[2i+1]) turns
    by position * theta^(-2i/D). float32 inside."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * inv_freq[None, :]  # (S, D/2)
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    rope_theta: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, lengths):
        b, s, d = x.shape
        h, dt = self.heads, self.dtype
        c_q = RMSNorm(dt, name="q_a_norm")(_dense(self.q_lora_rank, dt, "q_a")(x))
        q = _dense(h * (self.qk_nope + self.qk_rope), dt, "q_b")(c_q)
        q = q.reshape(b, s, h, self.qk_nope + self.qk_rope)
        kv = _dense(self.kv_lora_rank + self.qk_rope, dt, "kv_a")(x)
        c_kv = RMSNorm(dt, name="kv_a_norm")(kv[..., : self.kv_lora_rank])
        k_rope = rope_interleaved(kv[..., self.kv_lora_rank :], self.rope_theta)  # (B, S, rope)
        kv_b = _dense(h * (self.qk_nope + self.v_head), dt, "kv_b")(c_kv)
        kv_b = kv_b.reshape(b, s, h, self.qk_nope + self.v_head)
        k_nope, v = kv_b[..., : self.qk_nope], kv_b[..., self.qk_nope :]
        q = jnp.concatenate(
            [q[..., : self.qk_nope], rope_interleaved(q[..., self.qk_nope :], self.rope_theta)],
            axis=-1,
        )
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, self.qk_rope))], axis=-1
        )
        out = causal_flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            lengths, scale=(self.qk_nope + self.qk_rope) ** -0.5, interpret=pallas_interpret(),
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * self.v_head)
        return _dense(d, dt, "o")(out)


class SwiGLU(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.width, self.dtype, "gate")(x)
        up = _dense(self.width, self.dtype, "up")(x)
        return _dense(x.shape[-1], self.dtype, "down")(nn.silu(gate) * up)


def route(scores: jax.Array, bias: jax.Array, top_k: int, routed_scale: float):
    """`noaux_tc` with one group: the top k of score + bias choose, the
    scores alone weigh. (T, E) float32 -> chosen (T, k) int32, weights (T, k)."""
    _, chosen = lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * routed_scale
    return chosen, weights


class ExpertLayer(ExpertDispatch):
    """Routed experts (this chip's share of them) + the shared expert."""

    shared_experts: int = 0
    routed_scale: float = 1.0

    @nn.compact
    def __call__(self, x, valid):
        """x (T, d) tokens; valid (T,) bool, False on padding."""
        e, k = self.experts, self.top_k
        router = self.param(
            "router", nn.initializers.lecun_normal(), (x.shape[-1], e), jnp.float32
        )
        bias = self.variable("batch_stats", "bias", jnp.zeros, (e,), jnp.float32)
        with jax.named_scope("moco.moe_dispatch"):
            scores = nn.sigmoid(
                jnp.matmul(x.astype(jnp.float32), router, precision=lax.Precision.HIGHEST)
            )
            chosen, weights = route(scores, bias.value, k, self.routed_scale)
        y = self.routed(x, valid, chosen, weights, nn.silu)
        for i in range(self.shared_experts):
            y = y + SwiGLU(self.expert_mlp, self.dtype, name=f"shared_{i}")(x)

        if self.train and not self.is_initializing():
            with jax.named_scope("moco.moe_dispatch"):
                counts = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(
                    jnp.repeat(valid, k).astype(jnp.float32)
                )
                bias.value = bias.value + BIAS_UPDATE_RATE * jnp.sign(jnp.mean(counts) - counts)
        return y


class Block(nn.Module):
    cfg: StackSizes
    moe: bool
    first_expert: int
    experts_held: int
    train: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, lengths):
        c, dt = self.cfg, self.dtype
        b, s, d = x.shape
        attn = LatentAttention(
            heads=c.heads, q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
            qk_nope=c.qk_nope, qk_rope=c.qk_rope, v_head=c.v_head,
            rope_theta=c.rope_theta, dtype=dt, name="attn",
        )
        x = x + attn(RMSNorm(dt, name="attn_norm")(x), lengths)
        y = RMSNorm(dt, name="mlp_norm")(x)
        if not self.moe:
            return x + SwiGLU(c.dense_mlp, dt, name="mlp")(y)
        valid = valid_positions(lengths, s).reshape(-1)
        layer = ExpertLayer(
            experts=c.experts, top_k=c.top_k, expert_mlp=c.expert_mlp,
            shared_experts=c.shared_experts, routed_scale=c.routed_scale,
            first_expert=self.first_expert, experts_held=self.experts_held,
            train=self.train, dtype=dt, name="moe",
        )
        return x + layer(y.reshape(b * s, d), valid).reshape(b, s, d)


RematBlock = remat_block(Block)


class JoyAIBackbone(DecoderBackbone):
    """`models/decoder.py::DecoderBackbone` over this family's blocks:
    layer 0 dense, every later layer a mixture of experts."""

    def block(self, i: int, train: bool) -> nn.Module:
        return (RematBlock if self.remat else Block)(
            cfg=self.cfg, moe=i >= 1, first_expert=self.first_expert,
            experts_held=self.experts_held, train=train, dtype=self.dtype,
            name=f"layer_{i}",
        )


def create_joyai(arch: str, **cut) -> JoyAIBackbone:
    """`cut`: `models/decoder.py::create_stack`'s (dtype, layers,
    vocab_rows, expert_share, remat)."""
    return create_stack(JoyAIBackbone, _JOYAI_CONFIGS, arch, **cut)
