"""Shuffle-BN collective patterns on the 8-virtual-device mesh:
inverse property, cross-device movement, and determinism (the properties
the reference gets from NCCL broadcast + all_gather, moco/builder.py:~L79-126)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from moco_tpu.parallel import (
    DATA_AXIS,
    balanced_shuffle,
    balanced_unshuffle,
    create_mesh,
    make_permutation,
    shuffle_gather,
    unshuffle_gather,
)
from jax import shard_map


def _mesh():
    return create_mesh(num_data=8, num_model=1)


def test_shuffle_unshuffle_is_identity():
    mesh = _mesh()
    x = jnp.arange(16 * 3, dtype=jnp.float32).reshape(16, 3)

    def f(x, rng):
        perm, inv = make_permutation(rng, 16)
        x_sh = shuffle_gather(x, perm, DATA_AXIS)
        # pretend-encode: identity, so unshuffle must reconstruct x
        local, global_ = unshuffle_gather(x_sh, inv, DATA_AXIS)
        return local, global_

    local, global_ = jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=(P(DATA_AXIS), P()), out_specs=(P(DATA_AXIS), P()), check_vma=False
        )
    )(x, jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(local), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(global_), np.asarray(x))


def test_shuffle_actually_permutes():
    mesh = _mesh()
    x = jnp.arange(16, dtype=jnp.float32).reshape(16, 1)

    def f(x, rng):
        perm, _ = make_permutation(rng, 16)
        return shuffle_gather(x, perm, DATA_AXIS)

    shuffled = jax.jit(
        shard_map(f, mesh=mesh, in_specs=(P(DATA_AXIS), P()), out_specs=P(DATA_AXIS), check_vma=False)
    )(x, jax.random.key(0))
    assert not np.array_equal(np.asarray(shuffled), np.asarray(x))
    assert sorted(np.asarray(shuffled).ravel().tolist()) == list(range(16))


def test_balanced_shuffle_mixes_and_inverts():
    """The property the removed `ring` mode LACKED (it moved batches
    intact, leaving BN batch composition — and therefore the BN leak —
    identical to no shuffle): every device's shuffled batch must mix
    sources, and unshuffle must be an exact inverse."""
    mesh = _mesh()
    # row value encodes source device: device d holds rows valued d
    x = jnp.repeat(jnp.arange(8, dtype=jnp.float32), 8).reshape(64, 1)
    rng = jax.random.key(5)

    def f(x):
        y = balanced_shuffle(rng, x, DATA_AXIS)
        # balanced: exactly local_b/n rows from each source device
        counts = jnp.stack([jnp.sum(y == d) for d in range(8)])
        back = balanced_unshuffle(rng, y, DATA_AXIS)
        return y, back, counts[None]

    y, back, counts = jax.jit(
        shard_map(
            f,
            mesh=mesh,
            in_specs=P(DATA_AXIS),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            check_vma=False,
        )
    )(x)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    # each device got exactly one row from every source device
    np.testing.assert_array_equal(np.asarray(counts), np.ones((8, 8)))
    assert not np.array_equal(np.asarray(y), np.asarray(x))


def test_balanced_shuffle_changes_per_device_statistics():
    """Regression for the ring-mode bug: per-device batch *statistics*
    (what BN sees) must change under the shuffle."""
    mesh = _mesh()
    x = jax.random.normal(jax.random.key(0), (64, 4))

    def f(x):
        y = balanced_shuffle(jax.random.key(1), x, DATA_AXIS)
        return jnp.mean(x, 0, keepdims=True), jnp.mean(y, 0, keepdims=True)

    mx, my = jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=(P(DATA_AXIS), P(DATA_AXIS)), check_vma=False
        )
    )(x)
    # per-device means of the shuffled batch differ from the unshuffled ones
    assert not np.allclose(np.asarray(mx), np.asarray(my), atol=1e-6)


def test_permutation_is_deterministic_per_seed():
    p1, i1 = make_permutation(jax.random.key(7), 32)
    p2, _ = make_permutation(jax.random.key(7), 32)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(p1)[np.asarray(i1)], np.arange(32))


@pytest.mark.slow
def test_leak_control_cheat_arm_trains_and_probes(tmp_path):
    """The BN-cheat positive-control pipeline end-to-end at smoke scale:
    the cheat config (shuffle='none' + virtual per-group BN, opted in
    via allow_leaky_bn) must train on `synthetic_leak_control`, and the
    leak probe must resolve the virtual grouping from the checkpoint by
    default and produce finite aligned/shuffled accuracies. Guards the
    single-chip path scripts/ablate_shuffle.py + scripts/leak_probe.py
    run at full budget."""
    import numpy as np

    from moco_tpu.data.datasets import build_dataset
    from moco_tpu.train import train
    from moco_tpu.utils.config import (
        DataConfig,
        MocoConfig,
        OptimConfig,
        ParallelConfig,
        TrainConfig,
    )

    workdir = str(tmp_path / "none")
    config = TrainConfig(
        moco=MocoConfig(
            arch="resnet18", dim=32, num_negatives=64, momentum=0.9,
            temperature=0.2, mlp=True, shuffle="none", cifar_stem=True,
            compute_dtype="float32", bn_virtual_groups=4,
            allow_leaky_bn=True,
        ),
        optim=OptimConfig(lr=0.06, epochs=1, cos=True),
        data=DataConfig(
            dataset="synthetic_leak_control", image_size=32,
            global_batch=16, aug_plus=True, crops_only=True,
        ),
        parallel=ParallelConfig(num_data=1),
        workdir=workdir,
        knn_every_epochs=0,
        seed=0,
    )
    dataset = build_dataset("synthetic_leak_control", None, 32, train=True)
    dataset.num_examples = 64
    final = train(config, dataset=dataset)
    assert np.isfinite(final["loss"])

    from tests.conftest import load_script

    mod = load_script("leak_probe.py")
    # groups=None: must resolve to num_data (1) x bn_virtual_groups (4)
    result = mod.probe_arm("none", workdir, None, batches=2, batch=None)
    assert result["groups"] == 4
    assert np.isfinite(result["contrast_acc_aligned"])
    assert np.isfinite(result["acc_drop_when_decorrelated"])
