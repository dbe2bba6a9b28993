"""The fused-InfoNCE train step produces the same trajectory as the
dense-logits train step (CPU interpret mode, multi-device mesh)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from moco_tpu.core import build_encoder, create_state, make_train_step, place_state
from moco_tpu.parallel import create_mesh, shard_batch
from moco_tpu.utils.config import DataConfig, MocoConfig, OptimConfig, TrainConfig
from moco_tpu.utils.schedules import build_optimizer


def _run_steps(fused: bool, n_steps: int = 2):
    n_data = 2
    config = TrainConfig(
        moco=MocoConfig(
            arch="resnet18",
            dim=16,
            num_negatives=64,
            temperature=0.2,
            mlp=True,
            shuffle="gather_perm",
            cifar_stem=True,
            compute_dtype="float32",
            fused_infonce=fused,
            # block_k=32 with K=64 → the pallas kernel (interpret mode,
            # 2-tile grid) inside the train step; the default 2048-row
            # block does not tile K=64 and would be refused.
            fused_block_k=32,
        ),
        optim=OptimConfig(lr=0.05, epochs=2, cos=True),
        data=DataConfig(dataset="synthetic", image_size=16, global_batch=8),
    )
    mesh = create_mesh(num_data=n_data, num_model=1, devices=jax.devices()[:n_data])
    encoder = build_encoder(config.moco, num_data=n_data)
    tx = build_optimizer(config.optim, steps_per_epoch=4)
    state = create_state(
        jax.random.PRNGKey(0), config, encoder, tx, jnp.zeros((1, 16, 16, 3))
    )
    state = place_state(state, mesh)
    step = make_train_step(config, encoder, tx, mesh)
    rng = jax.device_put(
        jax.random.PRNGKey(3), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )
    metrics_hist = []
    for i in range(n_steps):
        ims = jax.random.normal(jax.random.PRNGKey(10 + i), (2, 8, 16, 16, 3))
        batch = shard_batch(mesh, {"im_q": ims[0], "im_k": ims[1]})
        state, metrics = step(state, batch, rng)
        # metrics now carry non-scalar health gauges too (queue_age_hist)
        metrics_hist.append({k: np.asarray(v) for k, v in metrics.items()})
    return state, metrics_hist


def test_fused_step_matches_dense_step():
    # fused_infonce=True on CPU runs the pallas kernel in interpret mode
    # over a 2-tile grid (fused_block_k=32, K=64)
    state_f, hist_f = _run_steps(fused=True)
    state_d, hist_d = _run_steps(fused=False)
    for mf, md in zip(hist_f, hist_d):
        np.testing.assert_allclose(mf["loss"], md["loss"], rtol=1e-5)
        np.testing.assert_allclose(mf["acc1"], md["acc1"], atol=1e-6)
        np.testing.assert_allclose(mf["acc5"], md["acc5"], atol=1e-6)
        # the health gauges are path-independent by construction (same
        # q/k/queue inputs on both sides) — they must agree too
        np.testing.assert_allclose(mf["logit_pos_mean"], md["logit_pos_mean"], rtol=1e-5)
        np.testing.assert_allclose(mf["queue_age_hist"], md["queue_age_hist"], atol=0)
    for a, b in zip(jax.tree.leaves(state_f.params_q), jax.tree.leaves(state_d.params_q)):
        # Tolerances calibrated to fp32 reassociation, not kernel bugs:
        # the fused kernel and the dense path reduce the queue axis in
        # different orders, and XLA:CPU's own reduction order varies
        # across jax releases. Two momentum-SGD steps at lr=0.05 amplify
        # that to a few 1e-4 absolute on a handful of elements (weight
        # scale ~5e-2), while the losses above still agree to rtol 1e-5.
        # A genuinely wrong gradient moves params at the 1e-2 scale.
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=5e-4)
