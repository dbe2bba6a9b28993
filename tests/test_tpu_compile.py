"""The Pallas kernels of the token path compile for the chip at the
published widths: the TPU's compiler is installed here and compiles for a
described `v5e:2x2` chip that is not attached (what interpret mode cannot
show: tiling, fast-memory use, partitioning). Nothing runs, so nothing
here is a result or a time. One file and a fixture, so that only the
worker that is handed this file loads the TPU's library."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_causal_attention_compiles_at_8192_positions(one_chip):
    """Forward, dq and dk/dv at 2 rows x 32 heads x 8192 positions, 192-wide
    q and k, 128-wide v, bfloat16: the cell's own call."""
    from moco_tpu.ops.flash_attention import causal_flash_attention

    qk = _shape(one_chip, (2, 32, 8192, 192), jnp.bfloat16)
    v = _shape(one_chip, (2, 32, 8192, 128), jnp.bfloat16)
    lens = _shape(one_chip, (2,), jnp.int32)

    def f(q, k, v, lens):
        loss = lambda q, k, v: jnp.sum(causal_flash_attention(q, k, v, lens).astype(jnp.float32))
        return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

    text = jax.jit(f).lower(qk, qk, v, lens).compile().as_text()
    for name in ("causal_attention_fwd", "causal_attention_dq", "causal_attention_dkv"):
        assert name in text


@pytest.mark.parametrize("k,n", [(2048, 1536), (768, 2048)], ids=["experts_in", "experts_out"])
def test_grouped_matmul_compiles_at_the_worst_case_buffer(one_chip, monkeypatch, k, n):
    """16 held experts over the 2 x 8192 x 8 rows of the worst case, both
    products of an expert and their gradients."""
    from moco_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _shape(one_chip, (131072, k), jnp.bfloat16)
    w = _shape(one_chip, (16, k, n), jnp.bfloat16)
    sizes = _shape(one_chip, (16,), jnp.int32)

    def f(x, w, sizes):
        loss = lambda x, w: jnp.sum(gm.grouped_matmul(x, w, sizes).astype(jnp.float32))
        return jax.value_and_grad(loss, (0, 1))(x, w)

    assert "tpu_custom_call" in jax.jit(f).lower(x, w, sizes).compile().as_text()
