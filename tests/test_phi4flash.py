"""The third decoder-stack text encoder (`models/phi4flash.py`, SambaY) on
the normal train path, at `phi4_flash_tiny`: the program against the plain
reference (`benchmarks/reference/phi4flash_moco_v2.py`), differential
attention on the causal kernels in interpret mode, the state one layer
leaves for later ones, the layer map and depth, and what the shared
skeleton's changes left of the other two families."""

import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash_moco_v2 as ref
from moco_tpu.core import build_encoder, create_state, sample_input
from moco_tpu.models import phi4flash
from moco_tpu.models.phi4flash import _PHI4FLASH_CONFIGS, Phi4FlashBackbone, layer_kind, lambda_init
from moco_tpu.models.token_encoders import create_token_encoder
from moco_tpu.ops.losses import cross_entropy, infonce_logits, l2_normalize
from moco_tpu.ops.selective_scan import selective_scan_reference
from moco_tpu.utils.config import PRESETS
from moco_tpu.utils.schedules import build_optimizer

TINY = _PHI4FLASH_CONFIGS["phi4_flash_tiny"]
PUBLISHED = _PHI4FLASH_CONFIGS["phi4_mini_flash"]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(first=5, layers=5, batch=4, seq_len=32, preset="phi4_flash_tiny", **moco):
    cfg = PRESETS[preset]
    return dataclasses.replace(
        cfg,
        moco=dataclasses.replace(cfg.moco, lm_layers=layers, lm_first_layer=first, num_negatives=64, **moco),
        data=dataclasses.replace(cfg.data, global_batch=batch, seq_len=seq_len),
        parallel=dataclasses.replace(cfg.parallel, num_data=1),
    )


def _rows(seed, n, seq_len, lengths, vocab=TINY.vocab_size):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (n, seq_len), 0, vocab)
    return {"ids": ids, "lengths": jnp.asarray(lengths, jnp.int32)}


def _seeded_state(config, seed=3):
    encoder = build_encoder(config.moco)
    tx = build_optimizer(config.optim, steps_per_epoch=1)
    return encoder, jax.jit(lambda r: create_state(r, config, encoder, tx, sample_input(config)))(
        jax.random.PRNGKey(seed)
    )


def test_model_matches_the_plain_reference():
    """Embedding, loss and every gradient leaf after one training forward,
    ragged lengths included, through published layers 5-9 of the tiny
    stack, the cell's cut: window (16 keys of 32 positions), Mamba with the
    memory, full, memory unit and cross layer."""
    config = _config(5, 5, batch=2)
    encoder, state = _seeded_state(config)
    x_q, x_k = _rows(1, 2, 32, [32, 20]), _rows(2, 2, 32, [11, 32])
    t = config.moco.temperature

    def sys_loss(params):
        q = encoder.apply({"params": params}, x_q, train=True)
        k = encoder.apply({"params": state.params_k}, x_k, train=True)
        logits, labels = infonce_logits(l2_normalize(q), l2_normalize(k), state.queue, t)
        return cross_entropy(logits, labels), l2_normalize(q)

    def ref_loss(params):
        return ref.loss_and_embeddings(params, {}, state.params_k, {}, state.queue, x_q, x_k, t)

    grad = lambda f: jax.jit(jax.value_and_grad(f, has_aux=True))
    (loss_s, q_s), g_s = grad(sys_loss)(state.params_q)
    (loss_r, q_r), g_r = grad(ref_loss)(state.params_q)
    np.testing.assert_allclose(q_s, q_r, atol=2e-5)
    np.testing.assert_allclose(loss_s, loss_r, atol=2e-5)
    flat_s, flat_r = jax.tree_util.tree_leaves_with_path(g_s), jax.tree.leaves(g_r)
    assert len(flat_s) == len(flat_r)
    for (path, a), b in zip(flat_s, flat_r):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(a / scale, b / scale, atol=2e-4, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_differential_attention_on_the_kernels_matches_its_plain_form(kind, monkeypatch):
    """One differential layer with the causal kernels forced (blocks of 16
    over 64 positions, interpret mode; a window of 16 keys), against the
    reference's plain masked softmaxes: output and gradients."""
    monkeypatch.setattr(
        phi4flash, "causal_flash_attention",
        functools.partial(phi4flash.causal_flash_attention, block_q=16, block_k=16),
    )
    window = TINY.window if kind == "window" else None
    layer = {"window": 5, "full": 7, "cross": 9}[kind]
    attn = phi4flash.DiffAttention(TINY, layer, window, cross=kind == "cross")
    s, hd = 64, TINY.head_dim
    h = jax.random.normal(jax.random.PRNGKey(0), (2, s, TINY.hidden))
    lens = jnp.asarray([64, 45], jnp.int32)
    kv = None
    if kind == "cross":
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        kv = (jax.random.normal(keys[0], (2, 1, s, hd)), jax.random.normal(keys[1], (2, 1, s, hd)),
              jax.random.normal(keys[2], (2, 1, s, 2 * hd)))
    params = attn.init(jax.random.PRNGKey(2), h, lens, kv)["params"]
    valid = (jnp.arange(s)[None] < lens[:, None])[..., None]

    def program(p, h, kv):
        return jnp.sum(jnp.where(valid, attn.apply({"params": p}, h, lens, kv)[0], 0.0) ** 2)

    def plain(p, h, kv):
        row_kv = lambda r: None if kv is None else tuple(t[r].transpose(1, 0, 2) for t in kv)
        out = jnp.stack([
            ref._diff_attention(h[r], p, lens[r], layer, ref.SIZES[TINY.hidden], window, row_kv(r))[0]
            for r in range(2)
        ])
        return jnp.sum(jnp.where(valid, out, 0.0) ** 2)

    got = jax.jit(jax.value_and_grad(program, (0, 1, 2) if kv else (0, 1)))(params, h, kv)
    want = jax.jit(jax.value_and_grad(plain, (0, 1, 2) if kv else (0, 1)))(params, h, kv)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))))


def _plain_scan(*args, **kw):
    return selective_scan_reference(*args)


class _Probed(Phi4FlashBackbone):
    """The stack with each cross layer reading the full layer's k and v
    plus a zero of its own (collection `probe`), whose gradient is what
    that layer sends back into them; `stop`: read them behind a
    stop-gradient instead (values unchanged either way)."""

    stop: bool = False

    def run_block(self, i, train, x, lengths, carry):
        layer = self.first_layer + i
        if "kv" not in carry or layer_kind(self.cfg, layer) != "cross":
            return super().run_block(i, train, x, lengths, carry)
        kv = carry["kv"]
        zero = self.variable("probe", f"kv_{layer}", lambda: jax.tree.map(jnp.zeros_like, kv)).value
        read = jax.lax.stop_gradient(kv) if self.stop else jax.tree.map(jnp.add, kv, zero)
        x, _ = super().run_block(i, train, x, lengths, {**carry, "kv": read})
        return x, carry


def test_the_full_layer_s_keys_and_values_take_gradient_from_every_cross_layer(monkeypatch):
    """Layers 6-11: cross layers 9 and 11 read layer 7's k and v. Each
    sends a gradient of its own back into them, and layer 7's k and v
    projections take more than their own layer's share (the scan as its
    plain recurrence: the kernel is not what this is about)."""
    monkeypatch.setattr(phi4flash, "selective_scan", _plain_scan)
    x = _rows(4, 1, 16, [13])
    model = lambda stop: _Probed(
        cfg=TINY, layers=6, vocab_rows=TINY.vocab_size, first_expert=0, experts_held=0,
        first_layer=6, stop=stop,
    )
    variables = model(False).init(jax.random.PRNGKey(5), x, train=False)
    assert sorted(variables["probe"]) == ["kv_11", "kv_9"]

    def grads(stop):
        loss = lambda v: jnp.sum(model(stop).apply(v, x) ** 2)
        return jax.jit(jax.grad(loss))(variables)

    whole, own = grads(False), grads(True)
    for layer in ("kv_9", "kv_11"):
        for sent in whole["probe"][layer]:  # k1, k2, v
            assert float(jnp.max(jnp.abs(sent))) > 0, layer
    kernels = lambda g: np.concatenate([np.ravel(g["params"]["layer_7"]["attn"][n]["kernel"]) for n in ("k", "v")])
    assert np.linalg.norm(kernels(whole) - kernels(own)) > 1e-2 * np.linalg.norm(kernels(whole))


def test_the_layer_map_gives_the_published_kinds():
    """The configuration file's 32-entry map is the program's, the
    reference's and the required-work module's; the cut holds published
    layers 15-19, one of each kind, and the memory and k/v it reads."""
    from benchmarks.required import diff_attention

    cfg = json.load(open(os.path.join(REPO, "benchmarks", "configs", "phi4_mini_flash_stage5.json")))
    published = cfg["published"]["layer_map"]
    assert published == [layer_kind(PUBLISHED, l) for l in range(32)]
    assert published == [ref._kind(l, ref.SIZES[2560]) for l in range(32)]
    assert published == [diff_attention.layer_kind(l) for l in range(32)]
    assert published[:16] == ["mamba", "window"] * 8
    assert published[16:18] == ["mamba", "full"] and published[18:] == ["gmu", "cross"] * 7
    first, layers = cfg["overrides"]["moco.lm_first_layer"], cfg["overrides"]["moco.lm_layers"]
    assert published[first : first + layers] == ["window", "mamba", "full", "gmu", "cross"]
    assert phi4flash.memory_layer(PUBLISHED) == 16
    # a cut that starts past the memory and the full layer has nothing to read
    late = create_token_encoder("phi4_flash_tiny", first_layer=8, layers=2)
    with pytest.raises(ValueError, match="memory"):
        late.init(jax.random.PRNGKey(0), _rows(0, 1, 8, [8]), train=False)


def test_a_cut_computes_the_published_depth_s_lambda_init(monkeypatch):
    """The stage of layers 5-9 and the whole 12-layer stack's layers 5-9
    (the others passed over) give the same embedding from the same
    weights: each block takes lambda_init from its published index, which
    at layer l is not layer l - 5's."""
    monkeypatch.setattr(phi4flash, "selective_scan", _plain_scan)

    class Skip(Phi4FlashBackbone):
        def run_block(self, i, train, x, lengths, carry):
            return super().run_block(i, train, x, lengths, carry) if 5 <= i <= 9 else (x, carry)

    x = _rows(6, 2, 32, [32, 17])
    cut = create_token_encoder("phi4_flash_tiny", first_layer=5, layers=5)
    params = cut.init(jax.random.PRNGKey(7), x, train=False)["params"]
    assert sorted(k for k in params if k.startswith("layer_")) == [f"layer_{l}" for l in range(5, 10)]
    whole = Skip(cfg=TINY, layers=12, vocab_rows=TINY.vocab_size, first_expert=0, experts_held=0)
    np.testing.assert_array_equal(cut.apply({"params": params}, x), whole.apply({"params": params}, x))
    assert [round(lambda_init(l), 4) for l in range(15, 20)] == [0.791, 0.7933, 0.7951, 0.7963, 0.7973]
    assert lambda_init(5) - lambda_init(0) > 0.5


def test_remat_keeps_the_scan_s_output_and_states_and_never_recomputes_the_full_layer():
    """Under the shared policy a Mamba block keeps the scan's output and
    chunk-entry states beside its arguments; a cross block keeps the k and
    v it was handed (its arguments) and runs no projection of them."""
    from jax._src.ad_checkpoint import saved_residuals

    s = 32
    x = jax.random.normal(jax.random.PRNGKey(0), (1, s, TINY.hidden))
    lens = jnp.asarray([s], jnp.int32)
    block = phi4flash.RematBlock(cfg=TINY, layer=6)
    params = block.init(jax.random.PRNGKey(1), x, lens)["params"]
    g = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda p, x: jnp.sum(block.apply({"params": p}, x, lens)[0] * g)  # linear: the block's own
    kept = sorted(a.str_short() for a, why in saved_residuals(loss, params, x)
                  if "argument" not in why and "constant" not in why)
    assert kept == [f"float32[1,1,{TINY.d_state},{TINY.inner}]", f"float32[1,{s},{TINY.inner}]"]
    cross = phi4flash.RematBlock(cfg=TINY, layer=9)
    kv = tuple(jnp.ones((1, 1, s, w)) for w in (TINY.head_dim, TINY.head_dim, 2 * TINY.head_dim))
    cparams = cross.init(jax.random.PRNGKey(2), x, lens, kv)["params"]
    assert set(cparams["attn"]) == {"q", "o", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln"}


def test_the_published_sizes_give_the_configuration_s_parameter_count():
    """Published layers 15-19 at every width and 25 008 vocabulary rows,
    by hand from the shapes: the numbers the configuration states."""
    from benchmarks.harness import common
    from benchmarks.harness.manifest import Manifest

    m = Manifest()
    cfg_file = m.config_file("phi4_mini_flash_stage5")
    config = common.build_train_config(cfg_file, m.traffic_file("job_loop_tokens_16k"), 1, "/x", False)
    shapes = jax.eval_shape(
        lambda r: build_encoder(config.moco).init(r, sample_input(config), train=False), jax.random.PRNGKey(0)
    )["params"]
    count = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    bb = shapes["backbone"]
    d, e, ff = 2560, 5120, 10240
    mlp, norms = 3 * d * ff, 4 * d
    attention = 2 * d * d + 2 * d * 1280 + 4 * 64 + 128
    mamba = 2 * d * e + 4 * e + e + e * 192 + 160 * e + e + e * 16 + e + e * d
    assert count(bb["layer_15"]) == count(bb["layer_17"]) == attention + mlp + norms
    assert count(bb["layer_16"]) == mamba + mlp + norms
    assert count(bb["layer_18"]) == 2 * d * e + mlp + norms
    assert count(bb["layer_19"]) == 2 * d * d + 4 * 64 + 128 + mlp + norms
    assert count(bb["embed"]) == 25008 * d
    assert count(shapes) == cfg_file["model"]["parameters"]
    assert round(count(shapes) / 1e6) == 584


JOYAI_SMALLTHINKER = {
    # the parent's (commit 29d9dd7) trees, outputs and gradients, before the
    # skeleton carried state, took a family's norm, a first layer and no experts
    "joyai_tiny": "3bad6a57bd01759aa442a4471215977b2cf6cdb2d877bfe0205ed94da7f1dcae",
    "smallthinker_tiny": "6b17eb186dbbe45913befff222c72c0c436954002bf97060260a43062913936f",
}


@pytest.mark.parametrize("preset,layers,share", [("joyai_tiny", 3, (2, 4)), ("smallthinker_tiny", 4, (6, 4))])
def test_the_other_families_give_the_parent_s_bits(preset, layers, share):
    """Every parameter and statistic from a seed, one training forward, its
    batch statistics and every gradient, bit for bit the parent's."""
    cfg = PRESETS[preset]
    config = dataclasses.replace(
        cfg,
        moco=dataclasses.replace(cfg.moco, lm_layers=layers, expert_share=share, num_negatives=64),
        data=dataclasses.replace(cfg.data, global_batch=4, seq_len=32),
        parallel=dataclasses.replace(cfg.parallel, num_data=1),
    )
    encoder, state = _seeded_state(config)
    x = {"ids": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 512),
         "lengths": jnp.asarray([32, 20, 7, 32], jnp.int32)}

    def loss(p):
        out, mut = encoder.apply({"params": p, "batch_stats": state.batch_stats_q}, x, train=True,
                                 mutable=["batch_stats"])
        return jnp.sum(jnp.square(out)), (out, mut)

    (l, (out, mut)), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(state.params_q)
    h = hashlib.sha256()
    tree = {"p": state.params_q, "s": state.batch_stats_q, "out": (l, out, mut, g)}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == JOYAI_SMALLTHINKER[preset]
