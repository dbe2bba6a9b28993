"""What every decoder stack read as a text encoder needs, whatever the
family: the half `models/joyai.py`, `models/smallthinker.py` and
`models/phi4flash.py` share.

- `RMSNorm` and the bias-free `dense`;
- **the expert dispatch** (`ExpertDispatch.routed`): an expert layer is
  told which experts it holds (`first_expert`, `experts_held`). The
  family's router scores and selects over ALL experts; the dispatch sorts
  the (token, expert) assignments that land on its own, gathers their rows
  straight from the tokens, runs one grouped product over them
  (`ops/grouped_matmul.py`), adds each weighted row straight into its
  token and adds nothing for the absent experts. No token is ever
  dropped, and the buffers are not the worst case's either: a share of
  `experts_held` in `experts` has a ladder of buffer sizes (`rung_ladder`:
  twice its even share of the tokens x top_k assignments, and all of
  them), and each call takes, on the device and from its own
  count, the smallest that holds what is live. The worst case is the last
  rung's program; an uncut layer has that one and no branch. On one chip
  there is no exchange and nothing stands in for the absent chips. The
  routing function and the gate's activation are the family's; the held
  experts' load, the rung taken and the share's first expert live in
  `batch_stats`, the collection the train step already carries;
- **the backbone skeleton** (`DecoderBackbone`): embed the ids, run the
  family's blocks, the family's final norm (RMSNorm unless it says
  otherwise), the mean over a row's valid positions. A block may leave
  state for later blocks (`run_block`'s `carry`), and a cut may start at a
  published layer other than the first (`first_layer`);
- **the remat policy** (`remat_block`): a block recomputed in the backward
  pass, which keeps nothing but the causal kernels' and the selective
  scan's outputs;
- `routing_metrics`: what a log line says of the routing.

A family's file keeps its attention, its routing function, its block and
its sizes. An input is `{"ids": (B, S) int32, "lengths": (B,) int32}`:
positions at or beyond a row's length are padding: masked as keys, routed
nowhere, counted nowhere and left out of the pool.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.ops.flash_attention import CAUSAL_SAVED_NAMES
from moco_tpu.ops.selective_scan import SCAN_SAVED_NAMES
from moco_tpu.ops.grouped_matmul import TILE_ROWS, grouped_matmul

RMS_EPS = 1e-6


class RMSNorm(nn.Module):
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + RMS_EPS)
        return (y * scale).astype(self.dtype)


def dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


def rung_ladder(rows: int, experts: int, held: int) -> tuple:
    """The buffer sizes a share of `held` of `experts` experts runs its
    rows = tokens x top_k assignments on: twice its even share, in whole
    row tiles, and `rows` itself, the worst case. An uncut layer has the
    one. No rung between them: each is a program of its own in every
    expert layer's forward and backward pass, one at four times the even
    share was taken by no measured step and cost a warm start seconds of
    loading (PERF.md section 6, PR 34)."""
    even = -(-rows * held // experts)
    bounded = -(-2 * even // TILE_ROWS) * TILE_ROWS
    return (bounded, rows) if bounded < rows else (rows,)


def _rung(rows: int, k: int, activation: Callable, x, w_in, w_out, weights, order, sizes):
    """The held experts' outputs from a buffer of `rows` rows, which holds
    every live assignment (`sum(sizes) <= rows`). x (T, d); weights (T, k)
    float32; `order` the assignments (token * k + choice) sorted by held
    expert, the live ones first; `sizes` (held,) their count an expert.
    Nothing here has more than `rows` rows of width d or ff: a row is
    gathered straight from its token and added straight into it."""
    ff = w_out.shape[1]
    slot = order[:rows]
    token = slot // k
    live = jnp.arange(rows) < jnp.sum(sizes)
    # masked for the backward pass's sake: on a TPU the grouped product's
    # gradient leaves the rows of no group unwritten
    xs = jnp.where(live[:, None], jnp.take(x, token, axis=0), 0)
    gate_up = grouped_matmul(xs, w_in, sizes)
    act = activation(gate_up[:, :ff]) * gate_up[:, ff:]
    ys = grouped_matmul(act, w_out, sizes)
    w = jnp.where(live, jnp.take(weights.reshape(-1), slot), 0.0)
    y = jnp.zeros(x.shape, jnp.float32).at[token].add(ys.astype(jnp.float32) * w[:, None])
    return y.astype(x.dtype)


def rung_taken(ladder: tuple, sizes) -> jax.Array:
    """Index of the smallest rung that holds every live assignment."""
    return jnp.sum(jnp.sum(sizes) > jnp.asarray(ladder[:-1], sizes.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _laddered(ladder: tuple, k: int, activation: Callable, x, w_in, w_out, weights, order, sizes):
    """`_rung` at the smallest size of `ladder` that holds this call's live
    assignments, chosen on the device. The backward pass chooses by the
    same count and computes its rung again from the inputs: differentiated
    as it stands, the conditional would hand out every rung's residuals
    from every rung, the worst case's filled with zeros by the others."""
    rungs = [functools.partial(_rung, rows, k, activation) for rows in ladder]
    return lax.switch(rung_taken(ladder, sizes), rungs, x, w_in, w_out, weights, order, sizes)


def _laddered_fwd(ladder, k, activation, *operands):
    return _laddered(ladder, k, activation, *operands), operands


def _laddered_bwd(ladder, k, activation, operands, g):
    *floats, order, sizes = operands

    def pull(rows, floats, order, sizes, g):
        return jax.vjp(lambda *f: _rung(rows, k, activation, *f, order, sizes), *floats)[1](g)

    rungs = [functools.partial(pull, rows) for rows in ladder]
    return (*lax.switch(rung_taken(ladder, sizes), rungs, floats, order, sizes, g), None, None)


_laddered.defvjp(_laddered_fwd, _laddered_bwd)


class ExpertDispatch(nn.Module):
    """This chip's share of a layer's routed experts. A family's expert
    layer derives from it, routes as the family routes and hands `routed`
    the choice; the stacked weights, `load` and `first_expert` are the
    deriving module's own variables."""

    experts: int
    top_k: int
    expert_mlp: int
    first_expert: int
    experts_held: int
    train: bool
    dtype: jnp.dtype = jnp.float32

    def routed(self, x, valid, chosen, weights, activation: Callable):
        """x (T, d) tokens; valid (T,) bool, False on padding; chosen,
        weights (T, k) over ALL experts. What the held experts give:
        sum over a token's chosen e held here of w_e * W_out,e
        (activation(W_gate,e x) * W_up,e x), (T, d)."""
        t, d = x.shape
        e, k, held, ff = self.experts, self.top_k, self.experts_held, self.expert_mlp
        dt = self.dtype
        fan_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
        )
        w_in = self.param("experts_in", fan_in, (held, d, 2 * ff), jnp.float32)  # gate | up
        w_out = self.param("experts_out", fan_in, (held, ff, d), jnp.float32)
        load = self.variable("batch_stats", "load", jnp.zeros, (held,), jnp.float32)
        # the rung this call took: its rows, and 1.0 where that is not the worst case
        buffer_rows = self.variable("batch_stats", "buffer_rows", jnp.zeros, (), jnp.float32)
        bounded = self.variable("batch_stats", "bounded", jnp.zeros, (), jnp.float32)
        # which share this is travels with the state (float: the step
        # averages the collection over devices), so a checkpoint knows it
        self.variable(
            "batch_stats", "first_expert", lambda: jnp.asarray(self.first_expert, jnp.float32)
        )

        # assignments on held experts first, in expert order; the rest
        # (absent experts, padding) share one key that sorts behind them
        with jax.named_scope("moco.moe_dispatch"):
            local = (chosen - self.first_expert) % e
            mine = valid[:, None] & (local < held)
            key = jnp.where(mine, local, held).reshape(-1)
            order = jnp.argsort(key, stable=True)
            sizes = jnp.bincount(key, length=held + 1)[:held]
            ladder = rung_ladder(t * k, e, held)
            operands = (x.astype(dt), w_in.astype(dt), w_out.astype(dt), weights, order, sizes)
            if len(ladder) == 1:  # the uncut layer: one program, nothing to choose
                y = _rung(t * k, k, activation, *operands)
            else:
                y = _laddered(ladder, k, activation, *operands)
            if self.train and not self.is_initializing():
                load.value = sizes.astype(jnp.float32)
                taken = rung_taken(ladder, sizes)
                buffer_rows.value = jnp.asarray(ladder, jnp.float32)[taken]
                bounded.value = (taken < len(ladder) - 1).astype(jnp.float32)
        return y


def valid_positions(lengths: jax.Array, seq_len: int) -> jax.Array:
    """(B, S) bool: the positions inside each row's length."""
    return jnp.arange(seq_len)[None, :] < lengths[:, None]


def remat_block(block_cls):
    """A block recomputed in the backward pass, which keeps nothing but
    the causal kernel's two outputs and the selective scan's (its output
    and the state entering each chunk): with them the forward kernels are
    dead code in the recomputation. A short sequence takes the dense
    attention product, names nothing, and is recomputed whole. What a
    block receives from an earlier one (`DecoderBackbone.run_block`'s
    carry) is its argument, so it is kept and never recomputed here."""
    return nn.remat(
        block_cls,
        policy=jax.checkpoint_policies.save_only_these_names(*CAUSAL_SAVED_NAMES, *SCAN_SAVED_NAMES),
    )


class DecoderBackbone(nn.Module):
    """Token ids -> pooled features (B, hidden) float32. `layers`,
    `vocab_rows` and the expert share are this chip's cut of a deployment
    (a pipeline stage's layers, a vocabulary slice, one chip's experts);
    every width is `cfg`'s. `remat`: recompute each block in the backward
    pass instead of keeping its activations, with one exception: where the
    attention product ran on the Pallas kernels its output and log-sum-exp
    are kept (`remat_block`), since they are all the backward kernels need
    of the forward kernel and cost far less to hold (136 MB a layer at 2 x
    8192 tokens and 32 heads of 128) than to compute again (a third
    forward kernel a layer). A family derives from it and says what its
    i-th block is (`block`); `first_layer` is the published index of the
    first block held (a pipeline stage past the first), for a family whose
    layers differ by depth."""

    cfg: Any  # the family's sizes: `hidden` is read here
    layers: int
    vocab_rows: int
    first_expert: int
    experts_held: int
    remat: bool = False
    dtype: jnp.dtype = jnp.float32
    # the embedding's initial standard deviation: the family's (a derived
    # backbone may state another; no run reads a published weight)
    embed_std: float = 0.02
    first_layer: int = 0

    def block(self, i: int, train: bool) -> nn.Module:
        raise NotImplementedError

    def run_block(self, i: int, train: bool, x, lengths, carry: dict):
        """Block i on the residual stream -> (x, carry). `carry` holds what
        earlier blocks left for later ones; a family whose blocks share
        nothing leaves it empty."""
        return self.block(i, train)(x, lengths), carry

    def norm(self, name: str) -> nn.Module:
        """The family's final norm, float32."""
        return RMSNorm(jnp.float32, name=name)

    @nn.compact
    def __call__(self, inputs, train: bool = True, group: Optional[str] = None):
        if group is not None:
            raise ValueError("the decoder stack has no layer-group schedule")
        ids, lengths = inputs["ids"], inputs["lengths"].astype(jnp.int32)
        x = nn.Embed(
            self.vocab_rows, self.cfg.hidden, dtype=self.dtype,
            embedding_init=nn.initializers.normal(self.embed_std), name="embed",
        )(ids)
        carry = {}
        for i in range(self.layers):
            x, carry = self.run_block(i, train, x, lengths, carry)
        x = self.norm("final_norm")(x)
        valid = valid_positions(lengths, x.shape[1])[..., None]
        total = jnp.sum(jnp.where(valid, x, 0.0), axis=1)
        return total / jnp.maximum(lengths, 1)[:, None].astype(jnp.float32)


def create_stack(
    backbone_cls,
    configs: dict,
    arch: str,
    dtype=jnp.float32,
    layers: Optional[int] = None,
    vocab_rows: Optional[int] = None,
    expert_share: Optional[tuple] = None,
    remat: bool = False,
    first_layer: int = 0,
):
    """A family's backbone at its cut of a deployment: `None` means as
    published (every layer from `first_layer` on, every vocabulary row,
    every expert). A family with no routed experts (`cfg` has no
    `experts`) takes no share."""
    if arch not in configs:
        raise ValueError(f"unknown arch {arch!r}; choose from {sorted(configs)}")
    cfg = configs[arch]
    experts = getattr(cfg, "experts", 0)
    if experts:
        first, held = expert_share or (0, experts)
        if not (0 <= first < experts and 0 < held <= experts):
            raise ValueError(f"expert share {(first, held)} outside the {experts} routed experts")
    elif expert_share:
        raise ValueError(f"{arch!r} has no routed experts to share, got {expert_share}")
    else:
        first, held = 0, 0
    layers = layers or cfg.layers - first_layer
    if not (0 <= first_layer and first_layer + layers <= cfg.layers):
        raise ValueError(f"layers {first_layer}..{first_layer + layers - 1} outside the {cfg.layers} published")
    return backbone_cls(
        cfg=cfg, layers=layers, vocab_rows=vocab_rows or cfg.vocab_size,
        first_expert=int(first), experts_held=int(held), remat=remat, dtype=dtype,
        first_layer=int(first_layer),
    )


def routing_metrics(batch_stats) -> dict:
    """What a log line says of the routing, from what the expert layers
    left in `batch_stats` this step (each layer's `load`: tokens on each
    held expert; `buffer_rows`, `bounded`: the rung its dispatch took): the
    largest held expert's tokens over the mean, worst layer; the mean
    tokens a held expert saw; the share of the layers that ran below the
    worst case; and the mean rows of the buffers they ran on. {} for an
    encoder with no expert layer."""
    named = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(batch_stats):
        named.setdefault(getattr(path[-1], "key", None), []).append(leaf)
    if "load" not in named:
        return {}
    loads = jnp.stack(named["load"])  # (layers, held)
    mean = jnp.mean(loads, axis=1)
    return {
        "moe/load_max_over_mean": jnp.max(jnp.max(loads, axis=1) / jnp.maximum(mean, 1.0)),
        "moe/tokens_per_expert": jnp.mean(mean),
        "moe/bounded_share": jnp.mean(jnp.stack(named["bounded"])),
        "moe/buffer_rows": jnp.mean(jnp.stack(named["buffer_rows"])),
    }
