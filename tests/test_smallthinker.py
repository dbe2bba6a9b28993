"""The second decoder-stack text encoder (`models/smallthinker.py`) on the
normal train path, at `smallthinker_tiny`: the program's modules against
the plain reference (`benchmarks/reference/smallthinker_moco_v2.py`), the
expert share, the causal kernels' grouped key heads and window in
interpret mode, one `make_train_step` step, and what the move of the
shared half into `models/decoder.py` left of `joyai_llm_flash`."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import smallthinker_moco_v2 as ref
from moco_tpu.core import build_encoder, create_state, make_train_step, place_state, sample_input
from moco_tpu.models import decoder, joyai, smallthinker
from moco_tpu.models.smallthinker import _SMALLTHINKER_CONFIGS, ExpertLayer, create_smallthinker
from moco_tpu.models.token_encoders import create_token_encoder, is_token_arch
from moco_tpu.ops.flash_attention import (
    CAUSAL_BLOCK, CAUSAL_MIN_SEQ, _band_steps, _causal_attn_reference, causal_flash_attention,
)
from moco_tpu.ops.losses import cross_entropy, infonce_logits, l2_normalize
from moco_tpu.parallel.mesh import create_mesh
from moco_tpu.utils.config import PRESETS
from moco_tpu.utils.schedules import build_optimizer

TINY = _SMALLTHINKER_CONFIGS["smallthinker_tiny"]


def _config(layers=4, share=(0, 8), batch=4, seq_len=32, preset="smallthinker_tiny"):
    cfg = PRESETS[preset]
    return dataclasses.replace(
        cfg,
        moco=dataclasses.replace(cfg.moco, lm_layers=layers, expert_share=share, num_negatives=64),
        data=dataclasses.replace(cfg.data, global_batch=batch, seq_len=seq_len),
        parallel=dataclasses.replace(cfg.parallel, num_data=1),
    )


def _rows(seed, n, seq_len, lengths):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (n, seq_len), 0, TINY.vocab_size)
    return {"ids": ids, "lengths": jnp.asarray(lengths, jnp.int32)}


@functools.partial(jax.jit, static_argnums=0)
def _apply(encoder, params, stats, x):
    out, mut = encoder.apply({"params": params, "batch_stats": stats}, x, train=True,
                             mutable=["batch_stats"])
    return out, mut["batch_stats"]


def _seeded_state(config, seed=3):
    encoder = build_encoder(config.moco)
    tx = build_optimizer(config.optim, steps_per_epoch=1)
    state = jax.jit(
        lambda r: create_state(r, config, encoder, tx, sample_input(config))
    )(jax.random.PRNGKey(seed))
    return encoder, tx, state


def _assert_loss_and_gradients_match(config, state, encoder, x_q, x_k, atol=2e-5):
    t = config.moco.temperature

    def sys_loss(params):
        q, _ = _apply(encoder, params, state.batch_stats_q, x_q)
        k, _ = _apply(encoder, state.params_k, state.batch_stats_k, x_k)
        logits, labels = infonce_logits(l2_normalize(q), l2_normalize(k), state.queue, t)
        return cross_entropy(logits, labels), l2_normalize(q)

    def ref_loss(params):
        return ref.loss_and_embeddings(
            params, state.batch_stats_q, state.params_k, state.batch_stats_k, state.queue,
            x_q, x_k, t,
        )

    grad = lambda f: jax.jit(jax.value_and_grad(f, has_aux=True))
    (loss_s, q_s), g_s = grad(sys_loss)(state.params_q)
    (loss_r, q_r), g_r = grad(ref_loss)(state.params_q)
    np.testing.assert_allclose(q_s, q_r, atol=atol)
    np.testing.assert_allclose(loss_s, loss_r, atol=atol)
    flat_s, flat_r = jax.tree_util.tree_leaves_with_path(g_s), jax.tree.leaves(g_r)
    assert len(flat_s) == len(flat_r)
    for (path, a), b in zip(flat_s, flat_r):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(a / scale, b / scale, atol=10 * atol, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("share", [(0, 8), (6, 4)], ids=["every_expert", "share_of_4_wrapping"])
@pytest.mark.parametrize("layers", [1, 2, 4], ids=["full_layer", "full_and_window_layer", "whole_period"])
def test_model_matches_the_plain_reference(layers, share):
    """Embedding, loss and every gradient leaf after one training forward,
    ragged lengths included; 32 positions are two windows of 16."""
    config = _config(layers, share)
    encoder, _, state = _seeded_state(config)
    x_q, x_k = _rows(1, 4, 32, [32, 20, 7, 32]), _rows(2, 4, 32, [32, 32, 11, 1])
    _assert_loss_and_gradients_match(config, state, encoder, x_q, x_k)


def test_the_kinds_of_layer_follow_the_published_layout_and_differ():
    """Layer 0 of a period has no window and no position encoding, the
    other three have both: a window layer's output moves when a key beyond
    its window does not... and a full layer's does."""
    windows = [smallthinker.layer_window(TINY, i) for i in range(8)]
    assert windows == [None, 16, 16, 16, None, 16, 16, 16]
    attn = lambda window: smallthinker.GroupedAttention(
        heads=4, kv_heads=2, head_dim=16, window=window, rope_theta=1.5e6
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 48, 64))
    lengths = jnp.asarray([48])
    params = attn(None).init(jax.random.PRNGKey(1), x, lengths)
    moved = x.at[0, 3].add(1.0)  # position 3 is beyond the window of position 40
    for window, sees in ((None, True), (16, False)):
        a, b = attn(window).apply(params, x, lengths), attn(window).apply(params, moved, lengths)
        assert bool(jnp.any(jnp.abs(a[0, 40] - b[0, 40]) > 1e-6)) is sees
        assert bool(jnp.any(jnp.abs(a[0, 18] - b[0, 18]) > 1e-6))  # t - p = 15 < 16: visible
        np.testing.assert_array_equal(a[0, :3], b[0, :3])  # causal
    # a full layer has no position encoding: permuting the keys before the last query
    # changes nothing of its output there; a window layer's RoPE sees the order
    perm = jnp.concatenate([jnp.arange(8)[::-1], jnp.arange(8, 48)])
    for window, same in ((None, True), (16, False)):
        a = attn(window).apply(params, x[:, :9], jnp.asarray([9]))[0, 8]
        b = attn(window).apply(params, x[:, perm][:, :9], jnp.asarray([9]))[0, 8]
        assert bool(jnp.allclose(a, b, atol=1e-5)) is same


def test_the_router_reads_before_attention():
    """The selection is a function of RMSNorm_in(x) alone: zeroing the
    attention's output projection leaves every router logit, and with it
    the held experts' load, where it was."""
    config = _config(layers=2)
    encoder, _, state = _seeded_state(config)
    x = _rows(1, 4, 32, [32, 32, 32, 32])
    _, stats = _apply(encoder, state.params_q, state.batch_stats_q, x)
    muted = jax.tree_util.tree_map_with_path(
        lambda path, p: jnp.zeros_like(p) if "layer_0']['attn']['o'" in jax.tree_util.keystr(path) else p,
        state.params_q,
    )
    _, stats_muted = _apply(encoder, muted, state.batch_stats_q, x)
    load = lambda s, i: np.asarray(s["backbone"][f"layer_{i}"]["moe"]["load"])
    np.testing.assert_array_equal(load(stats, 0), load(stats_muted, 0))
    assert load(stats, 0).sum() == 4 * 32 * TINY.top_k
    assert not np.array_equal(load(stats, 1), load(stats_muted, 1))  # layer 1 reads what attention 0 wrote


def _layer(share, train=True):
    return ExpertLayer(
        experts=TINY.experts, top_k=TINY.top_k, expert_mlp=TINY.expert_mlp,
        first_expert=share[0], experts_held=share[1], train=train,
    )


def _cut(variables, first, held):
    p, s = dict(variables["params"]), dict(variables["batch_stats"])
    index = (first + jnp.arange(held)) % TINY.experts
    p["experts_in"], p["experts_out"] = p["experts_in"][index], p["experts_out"][index]
    s["load"], s["first_expert"] = jnp.zeros((held,)), jnp.asarray(float(first))
    return {"params": p, "batch_stats": s}


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 experts: what all of them give is the uncut reference
    layer's output (there is no shared expert to count once), a share that
    wraps past the last expert included, and the loads add up to every
    valid token's 2 choices."""
    tokens = 48
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, TINY.hidden))
    logits = jax.random.normal(jax.random.PRNGKey(2), (tokens, TINY.experts))
    valid = jnp.arange(tokens) < tokens - 5
    whole = _layer((0, 8)).init(jax.random.PRNGKey(1), x, valid, logits)
    expected = ref._experts(
        x, logits, whole["params"], whole["batch_stats"], tokens - 5, ref.SIZES[64]
    )
    assert float(jnp.max(jnp.abs(expected))) > 0.1
    for firsts in ((0, 2, 4, 6), (7, 1, 3, 5)):
        total, load = 0.0, 0.0
        for first in firsts:
            y, mut = _layer((first, 2)).apply(
                _cut(whole, first, 2), x, valid, logits, mutable=["batch_stats"]
            )
            total, load = total + y, load + float(jnp.sum(mut["batch_stats"]["load"]))
        np.testing.assert_allclose(total, expected, atol=2e-5)
        assert load == (tokens - 5) * TINY.top_k
    np.testing.assert_array_equal(total[tokens - 5 :], 0.0)  # padding is routed nowhere


# ---- the causal kernels: grouped key heads and a window ------------------

KERNEL_S, BLOCK_Q, BLOCK_K = 256, 64, 32


def _qkv(group, s=KERNEL_S, b=2, h=4, d=32):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h // group, s, d))
    v = jax.random.normal(ks[2], (b, h // group, s, d))
    return q, k, v, jax.random.normal(ks[3], (b, h, s, d))


@pytest.mark.parametrize("lengths", [(256, 256), (37, 129)], ids=str)
@pytest.mark.parametrize("window", [None, 20, 150], ids=["no_window", "window_under_a_block", "window_of_several_blocks"])
@pytest.mark.parametrize("group", [1, 2])
def test_grouped_and_window_kernels_match_dense(group, window, lengths):
    """Forward and all three gradients of the Pallas kernels (interpret
    mode) with k and v at fewer heads than q and a window, ragged key
    lengths, on the positions inside each length; dk and dv come back at
    the KEY heads' shape, summed over the group."""
    q, k, v, w = _qkv(group)
    lens = jnp.asarray(lengths, jnp.int32)
    inside = (jnp.arange(KERNEL_S)[None, None, :, None] < lens[:, None, None, None]).astype(jnp.float32)
    w = w * inside
    kernel = lambda q, k, v: causal_flash_attention(
        q, k, v, lens, block_q=BLOCK_Q, block_k=BLOCK_K, interpret=True, window=window
    )
    dense = lambda q, k, v: _causal_attn_reference(q, k, v, lens, 32**-0.5, window)
    np.testing.assert_allclose(kernel(q, k, v) * inside, dense(q, k, v) * inside, atol=1e-5)
    g_kernel = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, d, x in zip(g_kernel, g_dense, (q, k, v)):
        assert a.shape == x.shape
        np.testing.assert_allclose(a, d, atol=2e-5)


def test_the_dense_product_under_a_window_is_the_band_written_out():
    """The oracle of the test above against an explicit loop: query t sees
    keys max(0, t - window + 1) .. t of key head j // group."""
    q, k, v, _ = _qkv(group=2, s=24, b=1)
    out = _causal_attn_reference(q, k, v, jnp.asarray([24]), 32**-0.5, window=5)
    for head in range(4):
        for t in (0, 3, 11, 23):
            lo = max(0, t - 4)
            scores = k[0, head // 2, lo : t + 1] @ q[0, head, t] * 32**-0.5
            expected = jax.nn.softmax(scores) @ v[0, head // 2, lo : t + 1]
            np.testing.assert_allclose(out[0, head, t], expected, atol=1e-5)


@pytest.mark.parametrize("group", [1, 2])
def test_a_window_kernel_never_reads_a_key_block_older_than_the_window(group):
    """Window 64 over 8 key blocks of 32: the last two query blocks
    (positions 128-255) read keys 65-255 only, so with keys 0-63 (two whole
    blocks, NaN) never fetched into a step that runs, forward and every
    gradient of those query rows stay finite, and the grid's last axis is
    as long as the band, not as the sequence."""
    window = 64
    q, k, v, w = _qkv(group)
    poison = jnp.arange(KERNEL_S)[None, None, :, None] < 64
    k, v = jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v)
    late = (jnp.arange(KERNEL_S) >= 128)[None, None, :, None]
    lens = jnp.asarray([KERNEL_S, KERNEL_S])
    kernel = lambda q, k, v: causal_flash_attention(
        q, k, v, lens, block_q=BLOCK_Q, block_k=BLOCK_K, interpret=True, window=window
    )
    out = kernel(q, k, v)
    assert bool(jnp.all(jnp.isfinite(jnp.where(late, out, 0.0))))
    assert bool(jnp.all(jnp.isnan(out[:, :, :32])))  # the poison is real: early rows read it
    dq, dk, dv = jax.grad(lambda *a: jnp.sum(jnp.where(late, kernel(*a) * w, 0.0)), (0, 1, 2))(q, k, v)
    assert bool(jnp.all(jnp.isfinite(jnp.where(late, dq, 0.0))))
    for g in (dk, dv):  # keys 128.. are read by the late query rows only, whose log-sum-exp is finite
        assert bool(jnp.all(jnp.isfinite(g[:, :, 128:])))
    # 64 query rows and 63 older keys span 4 key blocks of 32; a key block and
    # the 63 newer queries span 2 query blocks of 64
    assert _band_steps(KERNEL_S, BLOCK_Q, BLOCK_K, window) == (4, 2)
    assert _band_steps(KERNEL_S, BLOCK_Q, BLOCK_K, None) == (8, 4)


def test_the_band_of_the_16k_cell_is_252_key_blocks_a_head_where_a_full_layer_has_528():
    """Blocks of 512 at 16 384 positions under a window of 4096: 9 steps a
    query block, of which the first 8 query blocks run i + 1."""
    steps, _ = _band_steps(16384, 512, 512, 4096)
    assert steps == 9
    assert sum(min(i + 1, steps) for i in range(32)) == 252
    assert sum(i + 1 for i in range(32)) == 528


def _pallas_calls(jaxpr) -> list:
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)))
        for value in eqn.params.values():
            for v in value if isinstance(value, (list, tuple)) else (value,):
                v = getattr(v, "jaxpr", v)
                if hasattr(v, "eqns"):
                    calls += _pallas_calls(v)
    return calls


def test_the_stack_takes_the_kernels_by_length_with_the_layer_s_window_and_four_key_heads():
    """At CAUSAL_MIN_SEQ positions a full and a window layer, rematerialised
    under the shared policy: the full layer's three kernels keep their
    names, the window layer's carry `window_attention_*`, the dk/dv grids
    have one cell a KEY head (2, not 4), the forward kernel is not run
    again in the backward pass, and loss and gradients are the reference's."""
    s = CAUSAL_MIN_SEQ
    config = _config(layers=2, batch=1, seq_len=s)
    config = dataclasses.replace(config, moco=dataclasses.replace(config.moco, remat=True))
    encoder, _, state = _seeded_state(config)
    # (a seed at which no ReLU gate lies within float32 rounding of its kink: there the two
    # sides, 1e-6 apart, take different derivatives, and one token moves a column of a gradient)
    x_q, x_k = _rows(3, 1, s, [s]), _rows(4, 1, s, [s - 100])

    def loss(params):
        out, _ = encoder.apply({"params": params, "batch_stats": state.batch_stats_q}, x_q,
                               train=True, mutable=["batch_stats"])
        return jnp.sum(jnp.square(out))

    calls = _pallas_calls(jax.make_jaxpr(jax.grad(loss))(state.params_q).jaxpr)
    blocks = s // CAUSAL_BLOCK
    assert sorted(calls) == sorted([
        ("causal_attention_fwd", (TINY.heads, blocks, blocks)),
        ("causal_attention_dq", (TINY.heads, blocks, blocks)),
        ("causal_attention_dkv", (TINY.kv_heads, blocks, 2 * blocks)),
        # a window of 16 under blocks of 512: the diagonal block and the one before it
        ("window_attention_fwd", (TINY.heads, blocks, 2)),
        ("window_attention_dq", (TINY.heads, blocks, 2)),
        ("window_attention_dkv", (TINY.kv_heads, blocks, 2 * 2)),
    ])
    _assert_loss_and_gradients_match(config, state, encoder, x_q, x_k, atol=5e-5)


def test_one_train_step_on_the_new_arch_enqueues_as_the_image_path_does():
    """The v2 step on token rows of the second family, one row a step as
    its cell runs: the queue takes the key batch at the pointer, keys come
    from the EMA'd key encoder, the routing metrics ride the metrics dict,
    and the gauges that take a deviation across the batch read 0, finite."""
    config = _config(layers=2, batch=1, seq_len=32)
    mesh = create_mesh(num_data=1, num_model=1)
    encoder = build_encoder(config.moco, num_data=1)
    tx = build_optimizer(config.optim, steps_per_epoch=10)
    state = jax.jit(
        lambda r: create_state(r, config, encoder, tx, sample_input(config))
    )(jax.random.PRNGKey(0))
    state = place_state(state, mesh)
    batch = {"im_q": _rows(1, 1, 32, [32]), "im_k": _rows(2, 1, 32, [27])}
    step = make_train_step(config, encoder, tx, mesh)
    new, metrics = step(state, batch, jax.random.PRNGKey(1))

    m = config.moco.momentum
    ema_k = jax.tree.map(lambda k, q: m * k + (1 - m) * q, state.params_k, state.params_q)
    keys, _ = _apply(encoder, ema_k, state.batch_stats_k, batch["im_k"])
    np.testing.assert_allclose(new.queue[:1], l2_normalize(keys), atol=1e-5)
    np.testing.assert_array_equal(new.queue[1:], state.queue[1:])
    assert int(new.queue_ptr) == 1 and int(new.step) == 1
    assert float(metrics["tokens_per_step"]) == 32 + 27
    assert float(metrics["moe/tokens_per_expert"]) == pytest.approx(32 * 2 / 8)
    assert float(metrics["moe/load_max_over_mean"]) >= 1.0
    assert float(metrics["moe/buffer_rows"]) == 32 * 2 and float(metrics["moe/bounded_share"]) == 0.0
    for name, value in metrics.items():
        assert np.all(np.isfinite(np.asarray(value))), name
    assert float(metrics["feature_std"]) == 0.0 and float(metrics["logit_pos_std"]) == 0.0
    assert "bias" not in new.batch_stats_q["backbone"]["layer_0"]["moe"]  # no bias to move


def test_the_cut_of_a_deployment_changes_counts_and_no_width():
    assert is_token_arch("smallthinker_21b") and is_token_arch("joyai_tiny")
    assert not is_token_arch("resnet50")
    whole = create_token_encoder("smallthinker_tiny")
    cut = create_token_encoder("smallthinker_tiny", layers=2, vocab_rows=64, expert_share=(4, 2))
    shapes = lambda m: jax.eval_shape(
        lambda r: m.init(r, sample_input(_config()), train=False), jax.random.PRNGKey(0)
    )["params"]
    a, b = shapes(whole), shapes(cut)
    assert set(b) == {"embed", "final_norm", "layer_0", "layer_1"} and "layer_3" in a
    assert b["embed"]["embedding"].shape == (64, 64)
    assert b["layer_1"]["router"].shape == a["layer_1"]["router"].shape == (64, 8)
    assert b["layer_1"]["moe"]["experts_in"].shape == (2, 64, 64)
    assert a["layer_1"]["moe"]["experts_in"].shape == (8, 64, 64)
    assert set(b["layer_1"]) == {"attn", "attn_norm", "mlp_norm", "moe", "router"}
    assert b["layer_1"]["attn"]["k"]["kernel"].shape == (64, 2 * 16)  # 2 key heads, never 4
    assert jax.tree.map(lambda x: x.shape, a["layer_0"]["attn"]) == jax.tree.map(
        lambda x: x.shape, b["layer_0"]["attn"]
    )
    with pytest.raises(ValueError, match="share"):
        create_smallthinker("smallthinker_tiny", expert_share=(0, 9))
    with pytest.raises(ValueError, match="token encoder"):
        build_encoder(dataclasses.replace(PRESETS["smallthinker_tiny"].moco, shuffle="gather_perm"))


def test_the_published_sizes_give_the_configuration_s_parameter_count():
    """One chip of 8: four layers, 8 of 64 experts, 18 992 vocabulary rows."""
    config = PRESETS["smallthinker_21b"]
    config = dataclasses.replace(config, moco=dataclasses.replace(
        config.moco, lm_layers=4, lm_vocab_rows=18992, expert_share=(0, 8)))
    shapes = jax.eval_shape(
        lambda r: build_encoder(config.moco).init(r, sample_input(config), train=False),
        jax.random.PRNGKey(0),
    )["params"]
    layer = 2560 * 3584 * 2 + 2560 * 512 * 2 + 2560 * 64 + 8 * 3 * 2560 * 768 + 2 * 2560
    head = 2560 * 2560 + 2560 + 2560 * 128 + 128
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == 4 * layer + 18992 * 2560 + 2560 + head == 328_811_648


# ---- what the move into models/decoder.py left of the first family --------

# sha256 over (path, bytes) of joyai_tiny's seeded state, taken on PR 32 with
# the script this test repeats (the two counters the dispatch has kept beside
# `load` since PR 34 left out), and over one training forward's loss, output,
# mutated statistics and gradients, taken on PR 34: the dispatch now adds a
# token's expert outputs in float32 in another order, so the numbers moved in
# the last bits (output 3e-7, gradients 6e-7 of their largest, against PR 33's)
JOYAI_TREE = "f20de1333dc3c983500d3854dd2f5aa70b0825290880e2a04c5d728c77fe3e16"
JOYAI_FORWARD = "29d93ec1e68f304bf5a32a6055d7064be7fcb33766fec27e991a354543606f67"


def test_joyai_builds_the_parent_s_tree_and_numbers_after_the_move():
    """Every parameter and statistic of `joyai_tiny` from a seed, bit for
    bit what it was before both decoder families derived from
    `models/decoder.py` (init order and names are part of a checkpoint),
    and one training forward with its gradients, bit for bit what the
    bounded dispatch gave when it came."""
    config = _config(layers=3, share=(2, 4), preset="joyai_tiny")
    encoder, _, state = _seeded_state(config)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path({"p": state.params_q, "s": state.batch_stats_q}):
        if path[-1].key in ("buffer_rows", "bounded"):
            continue
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == JOYAI_TREE
    x = {"ids": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 512),
         "lengths": jnp.asarray([32, 20, 7, 32], jnp.int32)}

    def loss(p):
        out, mut = encoder.apply({"params": p, "batch_stats": state.batch_stats_q}, x, train=True,
                                 mutable=["batch_stats"])
        return jnp.sum(jnp.square(out)), (out, mut)

    (l, (out, mut)), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(state.params_q)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves((l, out, mut, g)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == JOYAI_FORWARD


def test_both_families_share_one_dispatch_one_skeleton_and_one_policy():
    for family in (joyai, smallthinker):
        assert issubclass(family.ExpertLayer, decoder.ExpertDispatch)
        assert family.RMSNorm is decoder.RMSNorm
    assert issubclass(joyai.JoyAIBackbone, decoder.DecoderBackbone)
    assert issubclass(smallthinker.SmallThinkerBackbone, decoder.DecoderBackbone)
    assert joyai.routing_metrics is decoder.routing_metrics


@pytest.mark.parametrize(
    "state_gb,once", [(0.2, False), (1.7, False), (5.29, True), (8.61, True)],
    ids=["r50", "vit_b16", "smallthinker_ep8", "joyai_ep16"],
)
def test_a_state_is_held_once_where_three_copies_and_a_step_would_not_fit(monkeypatch, state_gb, once):
    """The driver's rule on a chip that reports 15.75 GiB: the two token
    configurations' states are donated to the step, the image cells' keep
    their rollback copy (held twice a state is there three times while a
    step runs; 2.5 x 5.29 GB had passed the old rule and 20.4 GB would
    not have run)."""
    import moco_tpu.train as driver

    class Chip:
        def memory_stats(self):
            return {"bytes_limit": int(15.75 * 2**30)}

    monkeypatch.setattr(driver.jax, "local_devices", lambda: [Chip()])
    assert driver.state_needs_single_copy(int(state_gb * 1e9)) is once


def test_each_family_states_its_embedding_s_initial_deviation():
    """0.02 for the first family (its checkpoints' and the parent's bits),
    1 for the second, whose routers would otherwise collapse onto six
    experts by seed (PERF.md section 6, PR 33); every other leaf's init is shared."""
    std = lambda arch: float(jnp.std(
        create_token_encoder(arch, layers=1).init(
            jax.random.PRNGKey(0), sample_input(_config()), train=False
        )["params"]["embed"]["embedding"]
    ))
    assert std("joyai_tiny") == pytest.approx(0.02, rel=0.05)
    assert std("smallthinker_tiny") == pytest.approx(1.0, rel=0.05)
