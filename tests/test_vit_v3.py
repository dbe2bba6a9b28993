"""MoCo v3 ViT: backbone shape/determinism, symmetric train step,
patch-embed freeze, multi-device run.

The v3 variant is queue-free (batch negatives, symmetric 2τ-scaled loss,
prediction head) per arXiv:2104.02057; the reference repo itself is
CNN-only (SURVEY.md §5.7)."""

import jax
import jax.numpy as jnp
import numpy as np
import dataclasses
import pytest

from moco_tpu.core import (
    build_encoder,
    build_predictor,
    create_state,
    make_train_step,
    place_state,
)
from moco_tpu.models import create_vit, sincos_2d_posembed
from moco_tpu.parallel import create_mesh, shard_batch
from moco_tpu.utils.config import DataConfig, MocoConfig, OptimConfig, TrainConfig
from moco_tpu.utils.schedules import build_optimizer
from jax import shard_map

IMG = 16  # 4x4 grid of 4px patches


def _v3_config(n_data: int) -> TrainConfig:
    return TrainConfig(
        moco=MocoConfig(
            arch="vit_tiny",
            dim=32,
            num_negatives=0,
            momentum=0.99,
            temperature=0.2,
            v3=True,
            shuffle="none",
            compute_dtype="float32",
            vit_patch_size=4,
        ),
        optim=OptimConfig(optimizer="adamw", lr=1e-3, weight_decay=0.1, epochs=2, cos=True),
        data=DataConfig(dataset="synthetic", image_size=IMG, global_batch=4 * n_data),
    )


def test_vit_forward_shape_and_determinism():
    vit = create_vit("vit_tiny", image_size=IMG, patch_size=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, IMG, IMG, 3))
    params = vit.init(jax.random.PRNGKey(1), x)
    out1 = vit.apply(params, x)
    out2 = vit.apply(params, x)
    assert out1.shape == (2, vit.num_features)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_sincos_posembed_properties():
    emb = sincos_2d_posembed(64, 4)
    assert emb.shape == (1, 17, 64)
    np.testing.assert_array_equal(emb[0, 0], np.zeros(64))  # cls slot
    # distinct positions get distinct embeddings
    assert not np.allclose(emb[0, 1], emb[0, 2])


@pytest.fixture(scope="module")
def v3_setup():
    n_data = 2
    config = _v3_config(n_data)
    mesh = create_mesh(num_data=n_data, num_model=1, devices=jax.devices()[:n_data])
    encoder = build_encoder(config.moco, num_data=n_data)
    predictor = build_predictor(config.moco, num_data=n_data)
    assert predictor is not None
    tx = build_optimizer(config.optim, steps_per_epoch=4)
    sample = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
    state = create_state(jax.random.PRNGKey(0), config, encoder, tx, sample, predictor=predictor)
    state = place_state(state, mesh)
    step = make_train_step(config, encoder, tx, mesh, predictor=predictor)
    batch = {
        "im_q": jax.random.normal(jax.random.PRNGKey(1), (8, IMG, IMG, 3)),
        "im_k": jax.random.normal(jax.random.PRNGKey(2), (8, IMG, IMG, 3)),
    }
    batch = shard_batch(mesh, batch)
    rng = jax.device_put(
        jax.random.PRNGKey(3), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )
    return config, state, step, batch, rng


def test_v3_step_runs_and_is_finite(v3_setup):
    config, state, step, batch, rng = v3_setup
    new_state, metrics = step(state, batch, rng)
    assert int(new_state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert 0 <= float(metrics["acc1"]) <= 100


def test_v3_patch_embed_frozen(v3_setup):
    config, state, step, batch, rng = v3_setup
    new_state, _ = step(state, batch, rng)
    before = jax.tree.leaves(state.params_q["backbone"]["patch_embed"])
    after = jax.tree.leaves(new_state.params_q["backbone"]["patch_embed"])
    for a, b in zip(before, after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # but the transformer blocks DID train
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(state.params_q["backbone"]["block_0"]),
            jax.tree.leaves(new_state.params_q["backbone"]["block_0"]),
        )
    )
    assert changed


def test_v3_key_encoder_is_ema(v3_setup):
    config, state, step, batch, rng = v3_setup
    new_state, _ = step(state, batch, rng)
    m = config.moco.momentum
    q0 = jax.tree.leaves(state.params_q)[0]
    k0 = jax.tree.leaves(state.params_k)[0]
    k1 = jax.tree.leaves(new_state.params_k)[0]
    np.testing.assert_allclose(
        np.asarray(k1), np.asarray(k0) * m + np.asarray(q0) * (1 - m), rtol=1e-5
    )


def test_momentum_cos_requires_total_steps():
    import dataclasses as dc

    config = _v3_config(1)
    config = dc.replace(config, moco=dc.replace(config.moco, momentum_cos=True))
    mesh = create_mesh(num_data=1, num_model=1, devices=jax.devices()[:1])
    encoder = build_encoder(config.moco, num_data=1)
    predictor = build_predictor(config.moco, num_data=1)
    tx = build_optimizer(config.optim, steps_per_epoch=4)
    with pytest.raises(ValueError, match="total_steps"):
        make_train_step(config, encoder, tx, mesh, predictor=predictor)
    # with total_steps it builds fine
    make_train_step(config, encoder, tx, mesh, predictor=predictor, total_steps=8)


def test_v3_head_shapes_per_backbone_family():
    """Upstream moco-v3 `_build_projector_and_predictor_mlps`: ResNet
    gets a 2-layer projector + predictor WITHOUT the final BN; ViT gets
    the 3-layer projector + predictor ending in affine-free BN."""
    from moco_tpu.models import V3MLPHead

    r_cfg = MocoConfig(
        arch="resnet18", dim=32, num_negatives=0, v3=True,
        shuffle="none", cifar_stem=True, compute_dtype="float32",
    )
    r_enc = build_encoder(r_cfg, num_data=1)
    r_pred = build_predictor(r_cfg, num_data=1)
    assert isinstance(r_enc.head, V3MLPHead)
    assert r_enc.head.num_layers == 2 and r_enc.head.last_bn
    assert r_pred.num_layers == 2 and not r_pred.last_bn
    # predictor without last_bn really has no BN after the output Dense
    pv = r_pred.init(jax.random.PRNGKey(0), jnp.zeros((2, 32)), train=False)
    n_bn = sum(1 for k in pv["params"] if k.startswith("BatchNorm"))
    assert n_bn == 1  # only the hidden layer's BN

    v_cfg = MocoConfig(
        arch="vit_tiny", dim=32, num_negatives=0, v3=True,
        shuffle="none", compute_dtype="float32", vit_patch_size=4,
    )
    v_enc = build_encoder(v_cfg, num_data=1)
    v_pred = build_predictor(v_cfg, num_data=1)
    assert v_enc.head.num_layers == 3 and v_enc.head.last_bn
    assert v_pred.num_layers == 2 and v_pred.last_bn


def test_v3_predictor_trains(v3_setup):
    config, state, step, batch, rng = v3_setup
    new_state, _ = step(state, batch, rng)
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state.params_pred), jax.tree.leaves(new_state.params_pred))
    )
    assert changed


def test_vit_flash_attention_matches_dense():
    """use_flash_attention swaps the compute but not the param tree:
    identical params, near-identical output (fp32, interpret kernel).
    Uses a 32px/4px-patch grid -> 65 tokens (odd, exercises padding+mask
    via the dense short-seq path) and a 4-block seq via block override is
    covered in tests/test_flash_attention.py; here the wiring is under test."""
    vit_dense = create_vit("vit_tiny", image_size=32, patch_size=4)
    vit_flash = create_vit(
        "vit_tiny", image_size=32, patch_size=4, use_flash_attention=True
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    params = vit_dense.init(jax.random.PRNGKey(1), x)
    # same param tree: flash params init to identical structure
    params_flash = vit_flash.init(jax.random.PRNGKey(1), x)
    assert jax.tree.structure(params) == jax.tree.structure(params_flash)
    out_dense = vit_dense.apply(params, x)
    out_flash = vit_flash.apply(params, x)  # dense-trained params, flash compute
    np.testing.assert_allclose(
        np.asarray(out_dense), np.asarray(out_flash), rtol=2e-4, atol=2e-4
    )


class TestSequenceParallelViT:
    """Sequence parallelism: tokens sharded over the mesh's model axis,
    ring attention across shards (the long-context path, SURVEY.md §5.7
    'beyond reference'). Parity against the dense single-device ViT."""

    def _vit(self, **kw):
        return create_vit("vit_tiny", image_size=32, patch_size=4, pool="gap", **kw)

    def test_forward_matches_dense(self):
        from jax.sharding import PartitionSpec as P

        mesh = create_mesh(num_data=1, num_model=8)
        vit_sp = self._vit(sequence_axis="model")
        vit_dense = self._vit()
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
        params = vit_dense.init(jax.random.PRNGKey(1), x)
        # identical param trees: SP is a compute-path choice, not a model
        assert jax.tree.structure(params) == jax.tree.structure(
            vit_sp.init(jax.random.PRNGKey(1), x)
        )
        want = vit_dense.apply(params, x)

        def fwd(params, x):
            return vit_sp.apply(params, x)

        got = jax.jit(
            shard_map(
                fwd, mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False
            )
        )(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_outside_shard_map_falls_back_dense(self):
        vit_sp = self._vit(sequence_axis="model")
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
        params = vit_sp.init(jax.random.PRNGKey(1), x)
        out = vit_sp.apply(params, x)  # no axis bound -> dense path
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._vit().apply(params, x)), rtol=1e-5, atol=1e-5
        )

    def _sp_config(self, num_model: int) -> TrainConfig:
        cfg = _v3_config(4)
        return dataclasses.replace(
            cfg,
            moco=dataclasses.replace(
                cfg.moco, vit_pool="gap", vit_sequence_parallel=num_model > 0
            ),
        )

    @pytest.mark.slow  # full v3 SP step over the 8-dev mesh: heaviest compile in the suite
    def test_v3_train_step_with_sp_matches_dense(self):
        """One v3 step on a (4, 2) mesh with token-sharded ViT == the same
        step on (4, 1) dense — loss and updated params agree."""
        results = {}
        for num_model in (1, 2):
            config = self._sp_config(num_model if num_model > 1 else 0)
            mesh = create_mesh(num_data=4, num_model=num_model)
            encoder = build_encoder(config.moco, num_data=4)
            predictor = build_predictor(config.moco, num_data=4)
            from moco_tpu.utils.schedules import build_optimizer

            tx = build_optimizer(config.optim, steps_per_epoch=2)
            from moco_tpu.core import create_state, make_train_step, place_state

            sample = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
            state = create_state(
                jax.random.PRNGKey(0), config, encoder, tx, sample, predictor=predictor
            )
            state = place_state(state, mesh)
            step = make_train_step(
                config, encoder, tx, mesh, predictor=predictor, total_steps=4
            )
            ims = jax.random.normal(jax.random.PRNGKey(5), (2, 16, IMG, IMG, 3))
            batch = shard_batch(mesh, {"im_q": ims[0], "im_k": ims[1]})
            rng = jax.device_put(
                jax.random.PRNGKey(7),
                jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            )
            new_state, metrics = step(state, batch, rng)
            results[num_model] = (
                float(metrics["loss"]),
                np.asarray(
                    jax.tree.leaves(new_state.params_q)[0], dtype=np.float64
                ),
            )
        loss_dense, leaf_dense = results[1]
        loss_sp, leaf_sp = results[2]
        assert np.isfinite(loss_sp)
        np.testing.assert_allclose(loss_sp, loss_dense, rtol=1e-4)
        np.testing.assert_allclose(leaf_sp, leaf_dense, rtol=1e-3, atol=1e-5)


def test_vit_grouped_apply_matches_whole_bitwise():
    """Layer-granular ZeRO-3 seam (ISSUE 20): embed -> block_i... ->
    final, each applied with only its own param children, reproduces the
    whole-model forward BIT-identically, and the group->child map tiles
    the param tree exactly."""
    vit = create_vit("vit_tiny", image_size=IMG, patch_size=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, IMG, IMG, 3))
    variables = vit.init(jax.random.PRNGKey(1), x)
    whole = vit.apply(variables, x, train=True)

    names = vit.group_param_names()
    claimed = [c for g in vit.group_names for c in names[g]]
    assert sorted(claimed) == sorted(variables["params"].keys())

    out = x
    for g in vit.group_names:
        params_g = {k: variables["params"][k] for k in names[g]}
        out = vit.apply({"params": params_g}, out, train=True, group=g)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(out))

    with pytest.raises(ValueError, match="unknown layer group"):
        vit.apply(variables, x, train=True, group="block_99")
    # grouped apply + sequence parallelism would shard tokens across
    # group boundaries: rejected at the module gate
    sp = create_vit(
        "vit_tiny", image_size=IMG, patch_size=4, sequence_axis="model"
    )
    vsp = sp.init(jax.random.PRNGKey(1), x)
    with pytest.raises(ValueError, match="sequence_axis"):
        sp.apply(vsp, x, train=True, group="embed")
