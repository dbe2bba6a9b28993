"""Vision Transformer backbone for MoCo v3.

The reference repo itself is CNN-only (SURVEY.md §5.7); MoCo v3
("An Empirical Study of Training Self-Supervised Vision Transformers",
arXiv:2104.02057, from the same authors' follow-up `facebookresearch/
moco-v3`) is the queue-free ViT variant named by BASELINE.json's config
list. TPU-first choices:
- fixed 2-D sin-cos position embedding (the v3 paper's choice — no
  learned posembed to shard or interpolate);
- optionally frozen random patch projection (v3's key stability trick:
  the patch-embed conv stays at init; handled by the train step masking
  its grads, `freeze_patch_embed` in the config);
- pre-LN blocks, GELU MLP, bf16 compute / fp32 params, static 197-token
  sequence — everything XLA wants: one fused attention matmul chain on
  the MXU, no dynamic shapes.

Attention defaults to plain `jnp.einsum` — at 197 tokens the whole
sequence fits in VMEM and XLA's fusion is already optimal. Setting
`use_flash_attention=True` swaps in the Pallas flash kernel
(`moco_tpu/ops/flash_attention`, which pads + masks ViT's prime 197 to
the block size) via flax's `attention_fn` hook — the parameter tree is
identical either way, so checkpoints are interchangeable between the
two paths. Worth it for the long-sequence regime (high-res/video
tokens); at 197 it is a correctness-exercised alternative, not a win.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from moco_tpu.utils.platform import pallas_interpret


def flash_attention_fn(query, key, value, **kwargs):
    """`nn.MultiHeadDotProductAttention`-compatible attention_fn backed
    by the Pallas flash kernel. Inputs arrive (B, S, H, Dh); the kernel
    wants (B, H, S, Dh). Ignores bias/mask/dropout (ViT uses none)."""
    from moco_tpu.ops.flash_attention import flash_attention

    q = query.transpose(0, 2, 1, 3)
    k = key.transpose(0, 2, 1, 3)
    v = value.transpose(0, 2, 1, 3)
    out = flash_attention(q, k, v, interpret=pallas_interpret())
    return out.transpose(0, 2, 1, 3)


def ring_attention_fn(axis_name: str):
    """attention_fn computing EXACT attention over a sequence sharded on
    `axis_name`: per-device flash attention against the visiting K/V
    shard, rotated around the ring with ppermute
    (`moco_tpu/parallel/ring_attention.py`). Must run inside `shard_map`
    with the token axis sharded on `axis_name`."""

    def fn(query, key, value, **kwargs):
        from moco_tpu.parallel.ring_attention import ring_attention

        q = query.transpose(0, 2, 1, 3)
        k = key.transpose(0, 2, 1, 3)
        v = value.transpose(0, 2, 1, 3)
        out = ring_attention(q, k, v, axis_name, interpret=pallas_interpret())
        return out.transpose(0, 2, 1, 3)

    return fn


def sincos_2d_posembed(dim: int, grid: int, cls_token: bool = True) -> np.ndarray:
    """Fixed 2-D sin-cos position embedding, (1, grid²[+1], dim) fp32."""
    assert dim % 4 == 0, "sincos 2d posembed needs dim % 4 == 0"
    coords = np.arange(grid, dtype=np.float32)
    omega = 1.0 / (10000 ** (np.arange(dim // 4, dtype=np.float32) / (dim // 4)))
    out_h = np.einsum("i,j->ij", coords, omega)  # (grid, dim/4)
    emb_h = np.concatenate([np.sin(out_h), np.cos(out_h)], axis=1)  # (grid, dim/2)
    emb = np.concatenate(
        [
            np.repeat(emb_h[:, None, :], grid, axis=1),  # y
            np.repeat(emb_h[None, :, :], grid, axis=0),  # x
        ],
        axis=-1,
    ).reshape(grid * grid, dim)
    if cls_token:
        emb = np.concatenate([np.zeros((1, dim), np.float32), emb], axis=0)
    return emb[None]


class MlpBlock(nn.Module):
    mlp_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        x = nn.Dense(self.mlp_dim, dtype=self.dtype)(x)
        x = nn.gelu(x)
        return nn.Dense(d, dtype=self.dtype)(x)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.float32
    use_flash_attention: bool = False
    # explicit attention_fn override (e.g. ring_attention_fn for the
    # sequence-parallel path); takes precedence over use_flash_attention.
    # The parameter tree is identical for every attention implementation.
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm(dtype=self.dtype)(x)
        fn = self.attention_fn or (flash_attention_fn if self.use_flash_attention else None)
        attn_kwargs = {"attention_fn": fn} if fn is not None else {}
        y = nn.MultiHeadDotProductAttention(
            num_heads=self.num_heads, dtype=self.dtype, deterministic=True, **attn_kwargs
        )(y, y)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype)(x)
        y = MlpBlock(mlp_dim=self.mlp_dim, dtype=self.dtype)(y)
        return x + y


class VisionTransformer(nn.Module):
    """ViT returning the final-LN pooled feature (pre-head), the
    interface shape `ResNet.__call__` has, so `MoCoEncoder` composes
    either backbone unchanged.

    `pool`: "cls" (v3 default) or "gap" (global average pool, the v3
    paper's ablated alternative — and the mode sequence parallelism
    requires, since a cls token cannot be sharded).

    `sequence_axis`: name of a mesh axis to shard the TOKEN dimension
    over. When the module is applied inside `shard_map` with that axis
    bound, each device patchifies the (replicated) image, keeps only its
    token shard, runs the blocks with ring attention (exact attention
    over the full sequence via ppermute rotation), and gap-pools with a
    psum. Applied OUTSIDE shard_map (init, kNN, export) the same module
    falls back to the dense single-device path — the parameter tree is
    identical, so one set of weights serves both."""

    patch_size: int = 16
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    image_size: int = 224
    dtype: jnp.dtype = jnp.float32
    use_flash_attention: bool = False
    pool: str = "cls"
    sequence_axis: Optional[str] = None

    @property
    def num_features(self) -> int:
        return self.hidden_dim

    @property
    def group_names(self) -> tuple:
        """Schedule-ordered layer groups for the layer-granular ZeRO-3
        apply: patch embedding (+cls token), one group per encoder
        block, and the final norm + pool."""
        return ("embed",) + tuple(f"block_{i}" for i in range(self.depth)) + ("final",)

    def group_param_names(self) -> dict:
        """group -> its top-level param-tree child names (all EXPLICIT
        flax names here, so the map is construction-order independent)."""
        names = {
            "embed": ("patch_embed", "cls_token") if self.pool == "cls" else ("patch_embed",),
            "final": ("final_norm",),
        }
        for i in range(self.depth):
            names[f"block_{i}"] = (f"block_{i}",)
        return names

    @nn.compact
    def __call__(self, x, train: bool = True, group: Optional[str] = None):
        if self.pool not in ("cls", "gap"):
            raise ValueError(f"pool={self.pool!r}: choose 'cls' or 'gap'")
        if group is not None and self.sequence_axis is not None:
            raise ValueError(
                "layer-group apply does not compose with sequence_axis "
                "(the token shard would cross group boundaries)"
            )

        def run_embed(x):
            b, h, w, _ = x.shape
            assert h % self.patch_size == 0 and w % self.patch_size == 0, (
                f"image {h}x{w} not divisible by patch {self.patch_size}"
            )
            grid = h // self.patch_size
            x = x.astype(self.dtype)
            # Patch embedding: conv stride=patch (the "random patch
            # projection" v3 freezes — freezing is the train step's job,
            # not the module's).
            x = nn.Conv(
                self.hidden_dim,
                (self.patch_size, self.patch_size),
                strides=self.patch_size,
                padding="VALID",
                name="patch_embed",
                dtype=self.dtype,
            )(x)
            x = x.reshape(b, grid * grid, self.hidden_dim)
            if self.pool == "cls":
                cls = self.param(
                    "cls_token", nn.initializers.normal(stddev=0.02), (1, 1, self.hidden_dim)
                )
                x = jnp.concatenate(
                    [jnp.broadcast_to(cls.astype(self.dtype), (b, 1, self.hidden_dim)), x],
                    axis=1,
                )
            pos = sincos_2d_posembed(self.hidden_dim, grid, cls_token=self.pool == "cls")
            return x + jnp.asarray(pos, self.dtype)

        def make_block(i, attn_fn):
            return EncoderBlock(
                num_heads=self.num_heads,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                use_flash_attention=self.use_flash_attention,
                attention_fn=attn_fn,
                name=f"block_{i}",
            )

        def run_final(x, seq_total, sp_rank):
            x = nn.LayerNorm(dtype=self.dtype, name="final_norm")(x)
            if self.pool == "cls":
                return x[:, 0].astype(jnp.float32)
            # gap: mean over ALL tokens (psum across the shard ring when SP)
            s = jnp.sum(x.astype(jnp.float32), axis=1)
            if sp_rank is not None:
                s = lax.psum(s, self.sequence_axis)
            return s / seq_total

        if group is not None:
            if group == "embed":
                return run_embed(x)
            if group == "final":
                return run_final(x, x.shape[1], None)
            if group.startswith("block_") and group[6:].isdigit():
                i = int(group[6:])
                if i < self.depth:
                    return make_block(i, None)(x)
            raise ValueError(f"unknown layer group {group!r}")

        x = run_embed(x)
        # Sequence parallelism: bind to the axis if we are inside a
        # shard_map that names it; otherwise (init / single-device eval)
        # run dense. axis_index raises NameError at TRACE time when the
        # axis is unbound, so the fallback costs nothing at runtime.
        seq_total = x.shape[1]
        sp_rank = None
        if self.sequence_axis is not None:
            try:
                sp_rank = lax.axis_index(self.sequence_axis)
                sp_n = lax.axis_size(self.sequence_axis)
            except NameError:
                sp_rank = None
        if sp_rank is not None:
            if self.pool != "gap":
                raise ValueError("sequence_axis requires pool='gap' (cls token cannot be sharded)")
            if seq_total % sp_n:
                raise ValueError(
                    f"{seq_total} tokens not divisible by sequence axis size {sp_n}"
                )
            local = seq_total // sp_n
            x = lax.dynamic_slice_in_dim(x, sp_rank * local, local, axis=1)
            attn_fn = ring_attention_fn(self.sequence_axis)
        else:
            attn_fn = None

        for i in range(self.depth):
            x = make_block(i, attn_fn)(x)
        return run_final(x, seq_total, sp_rank)


_VIT_CONFIGS = {
    "vit_tiny": dict(hidden_dim=192, depth=4, num_heads=3, mlp_dim=768),  # tests
    "vit_s16": dict(hidden_dim=384, depth=12, num_heads=6, mlp_dim=1536),
    "vit_b16": dict(hidden_dim=768, depth=12, num_heads=12, mlp_dim=3072),
    "vit_l16": dict(hidden_dim=1024, depth=24, num_heads=16, mlp_dim=4096),
}


def create_vit(arch: str, image_size: int = 224, **kwargs) -> VisionTransformer:
    if arch not in _VIT_CONFIGS:
        raise ValueError(f"unknown ViT arch {arch!r}; choose from {sorted(_VIT_CONFIGS)}")
    return VisionTransformer(image_size=image_size, **_VIT_CONFIGS[arch], **kwargs)


VIT_ARCHS = tuple(sorted(_VIT_CONFIGS))
