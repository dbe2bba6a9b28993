"""Input module: uint8 RGB images, square, `config.data.image_size` a side.

What the harness needs of a modality, and nothing else. A reference module
names its input module as `INPUT`; the harness finds it here by that name
and calls these four. The traffic file's keys that belong to this input
(`pool_images`) are read here and nowhere in the harness: another
modality's traffic file brings its own keys, read by its own module.
"""

from __future__ import annotations


def dataset(seed: int, traffic: dict, config):
    """What `moco_tpu.train.train(config, dataset=...)` is fed from: the
    seeded in-memory pool of `traffic["pool_images"]` images
    (`benchmarks/data/pool.py` says why a pool)."""
    from benchmarks.data.pool import PoolDataset

    return PoolDataset(
        seed, pool_size=traffic["pool_images"], image_size=config.data.image_size
    )


def sample_input(config):
    """One row as the encoder takes it, for `create_state` and
    `jax.eval_shape`: only its shape and type matter."""
    import jax.numpy as jnp

    size = config.data.image_size
    return jnp.zeros((1, size, size, 3), jnp.float32)


def correct_rows(seed: int, n: int, config):
    """The correctness sample as the encoder takes it: `n` structured
    seeded images (`loadgen/schedule.py::structured_images` says why
    structured) through the evaluation recipe's preprocessing. A serve
    cell's client sends the same `n` images as uint8 bodies."""
    from benchmarks.loadgen.schedule import structured_images
    from benchmarks.reference.common import preprocess

    return preprocess(structured_images(seed, n, config.data.image_size))


def correct_views(seed: int, n: int, config):
    """Two views of `n` rows each for a training forward: the two halves
    of one sample of 2n different images."""
    rows = correct_rows(seed, 2 * n, config)
    return rows[:n], rows[n:]
