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
  (`first_expert`, `experts_held`). It scores and selects over ALL of
  them, sorts the (token, expert) assignments that land on its own, runs
  one grouped product over them (`ops/grouped_matmul.py`) and adds nothing
  for the absent ones. No capacity, no dropped token: the buffers are the
  worst case's. On one chip there is no exchange and nothing stands in
  for the absent chips.
- **the bias** (`e_score_correction_bias`) is not trained by the gradient.
  Every training forward moves it by `BIAS_UPDATE_RATE` towards balance,
  from the selection counts over all experts (the DeepSeek-V3 report's
  rule, which `noaux_tc` names). It lives in `batch_stats`, the collection
  the train step already carries for BatchNorm statistics, so the key
  encoder routes with its own EMA router and its own bias.

Not built: the multi-token-prediction layer and the LM head (a
contrastive encoder has no next-token objective). The output is the mean
over a row's valid positions of the final RMSNorm'd states.

An input is `{"ids": (B, S) int32, "lengths": (B,) int32}`: positions at
or beyond a row's length are padding: masked as keys, routed nowhere,
counted nowhere and left out of the pool.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.ops.flash_attention import CAUSAL_SAVED_NAMES, causal_flash_attention
from moco_tpu.ops.grouped_matmul import grouped_matmul
from moco_tpu.utils.platform import pallas_interpret

# gamma of the auxiliary-loss-free balancing rule (DeepSeek-V3 report)
BIAS_UPDATE_RATE = 1e-3
RMS_EPS = 1e-6


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


def is_token_arch(arch: str) -> bool:
    return arch in _JOYAI_CONFIGS


class RMSNorm(nn.Module):
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + RMS_EPS)
        return (y * scale).astype(self.dtype)


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


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


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


@jax.custom_vjp
def _permute(x, perm, inv):
    """x[perm] for a permutation `perm` with inverse `inv`: its transpose
    is the gather by `inv`, not the scatter XLA would derive."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inv):
    return jnp.take(x, perm, axis=0), (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return jnp.take(g, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def route(scores: jax.Array, bias: jax.Array, top_k: int, routed_scale: float):
    """`noaux_tc` with one group: the top k of score + bias choose, the
    scores alone weigh. (T, E) float32 -> chosen (T, k) int32, weights (T, k)."""
    _, chosen = lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * routed_scale
    return chosen, weights


class ExpertLayer(nn.Module):
    """Routed experts (this chip's share of them) + the shared expert."""

    experts: int
    top_k: int
    expert_mlp: int
    shared_experts: int
    routed_scale: float
    first_expert: int
    experts_held: int
    train: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, valid):
        """x (T, d) tokens; valid (T,) bool, False on padding."""
        t, d = x.shape
        e, k, held, ff = self.experts, self.top_k, self.experts_held, self.expert_mlp
        dt = self.dtype
        router = self.param("router", nn.initializers.lecun_normal(), (d, e), jnp.float32)
        fan_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
        )
        w_in = self.param("experts_in", fan_in, (held, d, 2 * ff), jnp.float32)  # gate | up
        w_out = self.param("experts_out", fan_in, (held, ff, d), jnp.float32)
        bias = self.variable("batch_stats", "bias", jnp.zeros, (e,), jnp.float32)
        load = self.variable("batch_stats", "load", jnp.zeros, (held,), jnp.float32)
        # which share this is travels with the state (float: the step
        # averages the collection over devices), so a checkpoint knows it
        self.variable(
            "batch_stats", "first_expert", lambda: jnp.asarray(self.first_expert, jnp.float32)
        )

        scores = nn.sigmoid(
            jnp.matmul(x.astype(jnp.float32), router, precision=lax.Precision.HIGHEST)
        )
        chosen, weights = route(scores, bias.value, k, self.routed_scale)

        # assignments on held experts first, in expert order; the rest
        # (absent experts, padding) share one key that sorts behind them
        local = (chosen - self.first_expert) % e
        mine = valid[:, None] & (local < held)
        key = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        sizes = jnp.bincount(key, length=held + 1)[:held]
        xs = _permute(jnp.repeat(x.astype(dt), k, axis=0), order, inv)
        gate_up = grouped_matmul(xs, w_in.astype(dt), sizes)
        act = nn.silu(gate_up[:, :ff]) * gate_up[:, ff:]
        ys = grouped_matmul(act, w_out.astype(dt), sizes)
        y = _permute(ys, inv, order).reshape(t, k, d)
        y = jnp.sum(jnp.where(mine[..., None], y * weights[..., None].astype(dt), 0), axis=1)
        for i in range(self.shared_experts):
            y = y + SwiGLU(ff, dt, name=f"shared_{i}")(x)

        if self.train and not self.is_initializing():
            counts = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(
                jnp.repeat(valid, k).astype(jnp.float32)
            )
            bias.value = bias.value + BIAS_UPDATE_RATE * jnp.sign(jnp.mean(counts) - counts)
            load.value = sizes.astype(jnp.float32)
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
        valid = (jnp.arange(s)[None, :] < lengths[:, None]).reshape(-1)
        layer = ExpertLayer(
            experts=c.experts, top_k=c.top_k, expert_mlp=c.expert_mlp,
            shared_experts=c.shared_experts, routed_scale=c.routed_scale,
            first_expert=self.first_expert, experts_held=self.experts_held,
            train=self.train, dtype=dt, name="moe",
        )
        return x + layer(y.reshape(b * s, d), valid).reshape(b, s, d)


# A block recomputed in the backward pass, which keeps nothing but the
# causal kernel's two outputs. A short sequence takes the dense product,
# names nothing, and is recomputed whole.
RematBlock = nn.remat(
    Block, policy=jax.checkpoint_policies.save_only_these_names(*CAUSAL_SAVED_NAMES)
)


class JoyAIBackbone(nn.Module):
    """Token ids -> pooled features (B, hidden) float32. `layers`,
    `vocab_rows` and the expert share are this chip's cut of a deployment
    (a pipeline stage's layers, a vocabulary slice, one chip's experts);
    every width is `cfg`'s. `remat`: recompute each block in the backward
    pass instead of keeping its activations, with one exception: where the
    attention product ran on the Pallas kernels its output and log-sum-exp
    are kept (`RematBlock`), since they are all the backward kernels need
    of the forward kernel and cost far less to hold (136 MB a layer at 2 x
    8192 tokens) than to compute again (a third forward kernel a layer)."""

    cfg: StackSizes
    layers: int
    vocab_rows: int
    first_expert: int
    experts_held: int
    remat: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs, train: bool = True, group: Optional[str] = None):
        if group is not None:
            raise ValueError("the decoder stack has no layer-group schedule")
        ids, lengths = inputs["ids"], inputs["lengths"].astype(jnp.int32)
        x = nn.Embed(
            self.vocab_rows, self.cfg.hidden, dtype=self.dtype,
            embedding_init=nn.initializers.normal(0.02), name="embed",
        )(ids)
        block_cls = RematBlock if self.remat else Block
        for i in range(self.layers):
            x = block_cls(
                cfg=self.cfg, moe=i >= 1, first_expert=self.first_expert,
                experts_held=self.experts_held, train=train, dtype=self.dtype,
                name=f"layer_{i}",
            )(x, lengths)
        x = RMSNorm(jnp.float32, name="final_norm")(x)
        valid = (jnp.arange(x.shape[1])[None, :] < lengths[:, None])[..., None]
        total = jnp.sum(jnp.where(valid, x, 0.0), axis=1)
        return total / jnp.maximum(lengths, 1)[:, None].astype(jnp.float32)


def create_joyai(
    arch: str,
    dtype=jnp.float32,
    layers: Optional[int] = None,
    vocab_rows: Optional[int] = None,
    expert_share: Optional[tuple] = None,
    remat: bool = False,
) -> JoyAIBackbone:
    if arch not in _JOYAI_CONFIGS:
        raise ValueError(f"unknown arch {arch!r}; choose from {sorted(_JOYAI_CONFIGS)}")
    cfg = _JOYAI_CONFIGS[arch]
    first, held = expert_share or (0, cfg.experts)
    if not (0 <= first < cfg.experts and 0 < held <= cfg.experts):
        raise ValueError(f"expert share {(first, held)} outside the {cfg.experts} routed experts")
    return JoyAIBackbone(
        cfg=cfg, layers=layers or cfg.layers, vocab_rows=vocab_rows or cfg.vocab_size,
        first_expert=int(first), experts_held=int(held), remat=remat, dtype=dtype,
    )


def routing_metrics(batch_stats) -> dict:
    """What a log line says of the routing, from the counts the expert
    layers left in `batch_stats` (each layer's `load`: tokens on each held
    expert this step): the largest held expert's tokens over the mean,
    worst layer; and the mean tokens a held expert saw. {} for an encoder
    with no expert layer."""
    loads = [
        leaf for path, leaf in jax.tree_util.tree_leaves_with_path(batch_stats)
        if getattr(path[-1], "key", None) == "load"
    ]
    if not loads:
        return {}
    loads = jnp.stack(loads)  # (layers, held)
    mean = jnp.mean(loads, axis=1)
    return {
        "moe/load_max_over_mean": jnp.max(jnp.max(loads, axis=1) / jnp.maximum(mean, 1.0)),
        "moe/tokens_per_expert": jnp.mean(mean),
    }
