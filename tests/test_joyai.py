"""The decoder-stack text encoder (`models/joyai.py`) on the normal train
path, at `joyai_tiny`: the program's modules against the plain reference
(`benchmarks/reference/joyai_moco_v2.py`), the expert share, the causal
attention kernels in interpret mode, token input through `TwoCropPipeline`
and `make_train_step`."""

import dataclasses
import functools
from unittest import mock

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_moco_v2 as ref
from moco_tpu.core import build_encoder, create_state, make_train_step, place_state, sample_input
from moco_tpu.data.pipeline import TwoCropPipeline
from moco_tpu.models import joyai
from moco_tpu.models.joyai import (
    Block, ExpertLayer, _JOYAI_CONFIGS, create_joyai, routing_metrics,
)
from moco_tpu.ops.flash_attention import (
    CAUSAL_MIN_SEQ, _causal_attn_reference, causal_flash_attention,
)
from moco_tpu.ops.losses import cross_entropy, infonce_logits, l2_normalize
from moco_tpu.parallel.mesh import create_mesh
from moco_tpu.utils.config import PRESETS
from moco_tpu.utils.schedules import build_optimizer

TINY = _JOYAI_CONFIGS["joyai_tiny"]


def _config(layers=3, share=(0, 8), batch=4, seq_len=32):
    cfg = PRESETS["joyai_tiny"]
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


@pytest.mark.parametrize(
    "layers,share", [(1, (0, 8)), (2, (0, 8)), (3, (2, 4))],
    ids=["dense_layer", "expert_layer", "whole_stack_share_of_4"],
)
def test_model_matches_the_plain_reference(layers, share):
    """Embedding, loss, every gradient leaf and the routing bias after one
    training forward, ragged lengths included."""
    config = _config(layers, share)
    encoder = build_encoder(config.moco)
    tx = build_optimizer(config.optim, steps_per_epoch=1)
    state = jax.jit(
        lambda r: create_state(r, config, encoder, tx, sample_input(config))
    )(jax.random.PRNGKey(3))
    x_q, x_k = _rows(1, 4, 32, [32, 20, 7, 32]), _rows(2, 4, 32, [32, 32, 11, 1])
    t = config.moco.temperature

    def sys_loss(params):
        q, stats = _apply(encoder, params, state.batch_stats_q, x_q)
        k, _ = _apply(encoder, state.params_k, state.batch_stats_k, x_k)
        logits, labels = infonce_logits(l2_normalize(q), l2_normalize(k), state.queue, t)
        return cross_entropy(logits, labels), (l2_normalize(q), stats)

    def ref_loss(params):
        return ref.loss_and_embeddings(
            params, state.batch_stats_q, state.params_k, state.batch_stats_k, state.queue,
            x_q, x_k, t,
        )

    grad = lambda f: jax.jit(jax.value_and_grad(f, has_aux=True))
    (loss_s, (q_s, stats_s)), g_s = grad(sys_loss)(state.params_q)
    (loss_r, q_r), g_r = grad(ref_loss)(state.params_q)
    np.testing.assert_allclose(q_s, q_r, atol=2e-5)
    np.testing.assert_allclose(loss_s, loss_r, atol=2e-5)
    flat_s, flat_r = jax.tree_util.tree_leaves_with_path(g_s), jax.tree.leaves(g_r)
    assert len(flat_s) == len(flat_r)
    for (path, a), b in zip(flat_s, flat_r):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        np.testing.assert_allclose(a / scale, b / scale, atol=2e-4, err_msg=jax.tree_util.keystr(path))
    _, bias_r = jax.jit(ref.forward)(state.params_q, state.batch_stats_q, x_q)
    assert len(bias_r) == layers - 1
    for name, b in bias_r.items():
        got = stats_s["backbone"][name]["moe"]["bias"]
        np.testing.assert_array_equal(got, b)
        assert float(jnp.max(jnp.abs(got))) == pytest.approx(1e-3)  # it moved, by gamma


def _layer(share, train=True):
    return ExpertLayer(
        experts=TINY.experts, top_k=TINY.top_k, expert_mlp=TINY.expert_mlp,
        shared_experts=1, routed_scale=TINY.routed_scale, first_expert=share[0],
        experts_held=share[1], train=train,
    )


def _whole_layer(seed=0, tokens=48):
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, TINY.hidden))
    valid = jnp.arange(tokens) < tokens - 5
    variables = _layer((0, 8)).init(jax.random.PRNGKey(seed + 1), x, valid)
    return x, valid, variables


def _cut(variables, first, held):
    """The variables of the share (first, held) of a whole layer's."""
    p, s = dict(variables["params"]), dict(variables["batch_stats"])
    p["experts_in"] = p["experts_in"][first : first + held]
    p["experts_out"] = p["experts_out"][first : first + held]
    s["load"], s["first_expert"] = jnp.zeros((held,)), jnp.asarray(float(first))
    return {"params": p, "batch_stats": s}


def _reference_layer(x, valid, variables):
    length = int(jnp.sum(valid))
    y, _ = ref._experts(x, variables["params"], variables["batch_stats"], length, ref.SIZES[64])
    return y


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 experts: what all of them give, the shared expert
    counted once, is the uncut reference layer's output."""
    x, valid, whole = _whole_layer()
    shared = ref._swiglu(x, whole["params"]["shared_0"])
    total = shared
    for first in range(0, 8, 2):
        y, _ = _layer((first, 2)).apply(_cut(whole, first, 2), x, valid, mutable=["batch_stats"])
        total = total + (y - shared)
    np.testing.assert_allclose(total, _reference_layer(x, valid, whole), atol=2e-5)
    # and a share that wraps past the last expert holds experts 7 and 0
    y, _ = _layer((7, 2)).apply(
        {"params": {**whole["params"],
                    "experts_in": whole["params"]["experts_in"][jnp.array([7, 0])],
                    "experts_out": whole["params"]["experts_out"][jnp.array([7, 0])]},
         "batch_stats": {**whole["batch_stats"], "load": jnp.zeros((2,)),
                         "first_expert": jnp.asarray(7.0)}},
        x, valid, mutable=["batch_stats"],
    )
    assert bool(jnp.all(jnp.isfinite(y)))


def test_every_token_on_one_expert_still_matches_and_the_load_reads_it():
    """No capacity and no dropped token: a bias that sends every token to
    experts 0 and 1 fills the worst-case buffer, the output is still the
    reference's, and `moe/load_max_over_mean` reads the skew."""
    x, valid, whole = _whole_layer(seed=5)
    bias = jnp.zeros((8,)).at[0].set(10.0).at[1].set(5.0)
    forced = {"params": whole["params"], "batch_stats": {**whole["batch_stats"], "bias": bias}}
    share = _cut(forced, 0, 4)
    y, mut = _layer((0, 4)).apply(share, x, valid, mutable=["batch_stats"])
    np.testing.assert_allclose(y, _reference_layer(x, valid, share), atol=2e-5)
    n_valid = float(jnp.sum(valid))
    np.testing.assert_array_equal(mut["batch_stats"]["load"], [n_valid, n_valid, 0.0, 0.0])
    metrics = routing_metrics({"layer_1": {"moe": mut["batch_stats"]}})
    assert float(metrics["moe/load_max_over_mean"]) == pytest.approx(2.0)
    assert float(metrics["moe/tokens_per_expert"]) == pytest.approx(n_valid / 2)
    assert routing_metrics({"BatchNorm_0": {"mean": jnp.zeros(3)}}) == {}


@pytest.mark.parametrize("lengths", [(256, 256), (256, 100), (37, 129)], ids=str)
def test_causal_kernels_match_dense(lengths):
    """Forward and all three gradients of the Pallas kernels (interpret
    mode) at latent attention's widths, 192 for q and k and 128 for v,
    causal, with ragged key lengths, on the positions inside each length."""
    b, h, s = 2, 2, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = jax.random.normal(ks[0], (b, h, s, 192)), jax.random.normal(ks[1], (b, h, s, 192))
    v = jax.random.normal(ks[2], (b, h, s, 128))
    lens = jnp.asarray(lengths, jnp.int32)
    inside = (jnp.arange(s)[None, None, :, None] < lens[:, None, None, None]).astype(jnp.float32)
    w = jax.random.normal(ks[3], (b, h, s, 128)) * inside
    kernel = lambda q, k, v: causal_flash_attention(
        q, k, v, lens, block_q=64, block_k=128, interpret=True
    )
    dense = lambda q, k, v: _causal_attn_reference(q, k, v, lens, 192**-0.5)
    np.testing.assert_allclose(kernel(q, k, v) * inside, dense(q, k, v) * inside, atol=1e-5)
    g_kernel = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, d in zip(g_kernel, g_dense):
        np.testing.assert_allclose(a, d, atol=2e-5)


def test_a_short_sequence_takes_the_dense_product_and_a_ragged_block_is_refused():
    q = jnp.ones((1, 1, 64, 24))
    out = causal_flash_attention(q, q, q[..., :16], jnp.asarray([64]))
    assert out.shape == (1, 1, 64, 16)
    with pytest.raises(ValueError, match="multiple"):
        causal_flash_attention(q, q, q, jnp.asarray([64]), block_q=48, block_k=48, interpret=True)


# ---- what a rematerialised block keeps of its attention (PR 30) ----------
#
# At CAUSAL_MIN_SEQ positions the tiny stack takes the kernels by length, as
# the 8k cell does, and they run in interpret mode.

KERNEL_LAYERS = 2  # one dense block, one expert block
# how the stack treats a block in the backward pass -> (remat, the class it wraps a block in)
REMAT_FORMS = {
    "policy": (True, joyai.RematBlock),
    "unpoliced": (True, nn.remat(Block)),
    "no_remat": (False, None),
}


@functools.cache
def _kernel_row_and_variables():
    x = _rows(5, 1, CAUSAL_MIN_SEQ, [CAUSAL_MIN_SEQ])
    encoder = create_joyai("joyai_tiny", layers=KERNEL_LAYERS)
    return x, jax.jit(lambda r: encoder.init(r, x, train=False))(jax.random.PRNGKey(1))


def _kernel_stack(form: str):
    """(loss, params) of the tiny stack's first blocks on one full row of
    CAUSAL_MIN_SEQ tokens; the parameters do not depend on the form."""
    remat, remat_block = REMAT_FORMS[form]
    x, variables = _kernel_row_and_variables()
    encoder = create_joyai("joyai_tiny", layers=KERNEL_LAYERS, remat=remat)

    def loss(params):
        with mock.patch.object(joyai, "RematBlock", remat_block):
            out, _ = encoder.apply(
                {"params": params, "batch_stats": variables["batch_stats"]}, x, train=True,
                mutable=["batch_stats"],
            )
        return jnp.sum(jnp.square(out))

    return loss, variables["params"]


def _jaxprs_in(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (list, tuple)) else (value,):
            v = getattr(v, "jaxpr", v)  # a ClosedJaxpr's
            if hasattr(v, "eqns"):
                yield v


def _kernel_calls(jaxpr, in_remat=False) -> list:
    """(name of the `pallas_call`, whether it lies inside a `remat2`
    equation) for every kernel call of a jaxpr, at any depth."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.params["name"], in_remat))
            continue
        for sub in _jaxprs_in(eqn):
            calls += _kernel_calls(sub, in_remat or eqn.primitive.name == "remat2")
    return calls


@pytest.mark.parametrize("form,recomputed", [("policy", 0), ("unpoliced", 1)])
def test_the_policy_takes_the_forward_kernel_out_of_the_backward_pass(form, recomputed):
    """Inside the gradient's `remat2` equations: both backward kernels once
    a layer, and the forward kernel once a layer only without the policy
    (fails if a later change drops a name or the policy)."""
    loss, params = _kernel_stack(form)
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert sorted(calls) == sorted(
        [("causal_attention_fwd", False)] * KERNEL_LAYERS
        + [("causal_attention_fwd", True)] * (KERNEL_LAYERS * recomputed)
        + [("causal_attention_dq", True), ("causal_attention_dkv", True)] * KERNEL_LAYERS
    )


@functools.cache
def _loss_and_grads(form: str):
    loss, params = _kernel_stack(form)
    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("form", ["unpoliced", "no_remat"])
def test_the_policy_changes_no_bit_of_the_loss_or_a_gradient(form):
    loss_p, grads_p = _loss_and_grads("policy")
    loss_o, grads_o = _loss_and_grads(form)
    assert np.asarray(loss_p) == np.asarray(loss_o)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads_p), jax.tree.leaves(grads_o)):
        assert np.any(np.asarray(a) != 0), path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))


@pytest.mark.parametrize("moe", [False, True], ids=["dense_block", "expert_block"])
def test_a_block_keeps_the_attention_output_and_a_lane_dense_log_sum_exp(moe):
    """What one rematerialised block saves beside its arguments: `out` as
    (B, H, S, Dv) and the log-sum-exp as (B*H, S), with no unit axis for
    HBM to pad to 128 lanes, and nothing else of the attention's size."""
    from jax._src.ad_checkpoint import saved_residuals

    b, s = 2, CAUSAL_MIN_SEQ
    block = joyai.RematBlock(
        cfg=TINY, moe=moe, first_expert=0, experts_held=TINY.experts, train=True
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, TINY.hidden))
    lengths = jnp.full((b,), s, jnp.int32)
    variables = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(1), x, lengths))

    def loss(variables, x):
        y, _ = block.apply(variables, x, lengths, mutable=["batch_stats"])
        return jnp.sum(y)

    kept = [
        (aval.shape, why) for aval, why in saved_residuals(loss, variables, x)
        if "from the argument" not in why and aval.size >= b * TINY.heads * s
    ]
    assert sorted(shape for shape, _ in kept) == [
        (b, TINY.heads, s, TINY.v_head), (b * TINY.heads, s)
    ], kept


class _DocIdDataset:
    """Document i is its own index, repeated: a window says whose it is."""

    def __len__(self):
        return 64

    def load_tokens(self, index):
        return np.full(40 + 7 * (index % 9), index, np.int32)


def test_token_pipeline_is_seeded_and_both_views_come_from_one_document():
    config = _config(batch=4, seq_len=48)
    mesh = create_mesh(num_data=1, num_model=1)
    batches = lambda seed: list(
        TwoCropPipeline(config.data, mesh, seed=seed, dataset=_DocIdDataset()).epoch(0)
    )[:3]
    first, again, other = batches(5), batches(5), batches(6)
    for a, b in zip(first, again):
        jax.tree.map(np.testing.assert_array_equal, a, b)
    assert any(
        not np.array_equal(a["im_q"]["ids"], b["im_q"]["ids"]) for a, b in zip(first, other)
    )
    for batch in first:
        q, k = batch["im_q"], batch["im_k"]
        assert q["ids"].shape == (4, 48) and q["ids"].dtype == jnp.int32
        assert q["lengths"].dtype == jnp.int32
        np.testing.assert_array_equal(q["ids"][:, 0], k["ids"][:, 0])  # the same documents
        for view in (q, k):
            for row, length in zip(np.asarray(view["ids"]), np.asarray(view["lengths"])):
                assert 40 <= length <= 48 and (row[:length] == row[0]).all() and (row[length:] == 0).all()


def test_one_train_step_on_tokens_enqueues_as_the_image_path_does():
    """The v2 step on token rows: the queue takes the global key batch at
    the pointer, keys come from the EMA'd key encoder with ITS router and
    ITS bias, and the log line's routing metrics ride the metrics dict."""
    config = dataclasses.replace(_config(layers=2, batch=4, seq_len=32), health_metrics=False)
    mesh = create_mesh(num_data=1, num_model=1)
    encoder = build_encoder(config.moco, num_data=1)
    tx = build_optimizer(config.optim, steps_per_epoch=10)
    state = jax.jit(
        lambda r: create_state(r, config, encoder, tx, sample_input(config))
    )(jax.random.PRNGKey(0))
    # a key encoder whose router differs from the query's
    noise = lambda p: p + 0.5 * jax.random.normal(jax.random.PRNGKey(9), p.shape)
    params_k = jax.tree_util.tree_map_with_path(
        lambda path, p: noise(p) if "router" in jax.tree_util.keystr(path) else p, state.params_k
    )
    state = place_state(state.replace(params_k=params_k), mesh)
    batch = {"im_q": _rows(1, 4, 32, [32, 32, 20, 9]), "im_k": _rows(2, 4, 32, [32, 15, 32, 32])}
    step = make_train_step(config, encoder, tx, mesh)
    new, metrics = step(state, batch, jax.random.PRNGKey(1))

    m = config.moco.momentum
    ema_k = jax.tree.map(lambda k, q: m * k + (1 - m) * q, state.params_k, state.params_q)
    keys, stats_k = _apply(encoder, ema_k, state.batch_stats_k, batch["im_k"])
    np.testing.assert_allclose(new.queue[:4], l2_normalize(keys), atol=1e-5)
    np.testing.assert_array_equal(new.queue[4:], state.queue[4:])
    assert int(new.queue_ptr) == 4 and int(new.step) == 1
    wrong, _ = _apply(encoder, state.params_q, state.batch_stats_q, batch["im_k"])
    assert float(jnp.max(jnp.abs(l2_normalize(wrong) - new.queue[:4]))) > 1e-3
    bias = lambda s: s["backbone"]["layer_1"]["moe"]["bias"]
    np.testing.assert_array_equal(bias(new.batch_stats_k), bias(stats_k))
    assert not np.array_equal(bias(new.batch_stats_k), bias(new.batch_stats_q))
    assert float(metrics["tokens_per_step"]) == 32 + 32 + 20 + 9 + 32 + 15 + 32 + 32
    assert float(metrics["moe/tokens_per_expert"]) == pytest.approx((32 + 32 + 20 + 9) * 2 / 8)
    assert float(metrics["moe/load_max_over_mean"]) >= 1.0 and np.isfinite(float(metrics["loss"]))
    # the step's log fields say which rung the dispatch took: at 4 x 32 tokens x 2 choices twice
    # the even share is under one row tile, so the ladder is the worst case alone
    assert float(metrics["moe/buffer_rows"]) == 4 * 32 * 2 and float(metrics["moe/bounded_share"]) == 0.0


def test_the_cut_of_a_deployment_changes_counts_and_no_width():
    whole = create_joyai("joyai_tiny")
    cut = create_joyai("joyai_tiny", layers=2, vocab_rows=64, expert_share=(4, 2))
    shapes = lambda m: jax.eval_shape(
        lambda r: m.init(r, sample_input(_config()), train=False), jax.random.PRNGKey(0)
    )["params"]
    a, b = shapes(whole), shapes(cut)
    assert set(b) == {"embed", "final_norm", "layer_0", "layer_1"} and "layer_2" in a
    assert b["embed"]["embedding"].shape == (64, 64) and a["embed"]["embedding"].shape == (512, 64)
    assert b["layer_1"]["moe"]["router"].shape == a["layer_1"]["moe"]["router"].shape == (64, 8)
    assert b["layer_1"]["moe"]["experts_in"].shape == (2, 64, 64)
    assert a["layer_1"]["moe"]["experts_in"].shape == (8, 64, 64)
    assert jax.tree.map(lambda x: x.shape, a["layer_0"]) == jax.tree.map(lambda x: x.shape, b["layer_0"])
    with pytest.raises(ValueError, match="share"):
        create_joyai("joyai_tiny", expert_share=(0, 9))
    with pytest.raises(ValueError, match="token encoder"):
        build_encoder(dataclasses.replace(PRESETS["joyai_tiny"].moco, shuffle="gather_perm"))


def test_a_state_held_once_is_donated_saved_on_sigterm_and_aborts_on_a_nan(tmp_path, monkeypatch):
    """The driver's branch for a state the device cannot hold twice (the
    536 M-parameter cell: 8.6 of 16 GB): the step takes the state by
    donation and no rollback copy is kept, so the preemption save writes
    the live state, and the first non-finite loss ends the run instead of
    rolling back. Forced here, where the backend reports no memory limit."""
    import moco_tpu.train as driver
    from moco_tpu.utils import faults
    from moco_tpu.utils.checkpoint import CheckpointManager

    assert driver.state_needs_single_copy(10**12) is False  # the CPU reports no limit
    monkeypatch.setattr(driver, "state_needs_single_copy", lambda state_bytes: True)
    base = dataclasses.replace(
        _config(layers=1, batch=4, seq_len=32), log_every=1, health_metrics=False,
        optim=dataclasses.replace(PRESETS["joyai_tiny"].optim, epochs=50),
    )
    config = dataclasses.replace(base, workdir=str(tmp_path / "preempt"), steps_per_epoch=40)
    faults.install("preempt@step=3")  # SIGTERM to itself, from inside the loop
    try:
        driver.train(config)
    finally:
        faults.clear()
    mgr = CheckpointManager(config.workdir)
    assert mgr.latest_step() and mgr.read_extra()["reason"] == "preempt"
    mgr.close()

    faults.install("nan@step=2:times=99")
    try:
        with pytest.raises(FloatingPointError, match="non-finite"):
            driver.train(dataclasses.replace(base, workdir=str(tmp_path / "nan"), steps_per_epoch=8))
    finally:
        faults.clear()
