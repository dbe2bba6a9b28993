"""The Mamba selective scan (`ops/selective_scan.py`) in interpret mode
against the plain sequential recurrence: the forward and all six
gradients across chunk and channel-block boundaries, rows shorter than the
sequence, and what a padded position may and may not change."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.ops.selective_scan import SCAN_SAVED_NAMES, selective_scan, selective_scan_reference

BT, LENGTH, WIDTH, STATES = 2, 48, 256, 4
NAMES = ("x", "dt", "a_log", "b", "c", "d")


def _operands(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (BT, LENGTH, WIDTH))
    dt = jax.nn.softplus(jax.random.normal(k[1], (BT, LENGTH, WIDTH)) - 1.0)
    a_log = jnp.log(jnp.arange(1, STATES + 1, dtype=jnp.float32))[None] + 0.1 * jax.random.normal(k[2], (WIDTH, STATES))
    b = jax.random.normal(k[3], (BT, LENGTH, STATES))
    c = jax.random.normal(k[4], (BT, LENGTH, STATES))
    d = jax.random.normal(k[5], (WIDTH,))
    return (x, dt, a_log, b, c, d), jax.random.normal(k[6], (BT, LENGTH, WIDTH))


def _kernel(chunk, block):
    return lambda *a: selective_scan(*a, chunk=chunk, block=block, interpret=True)


@pytest.mark.parametrize(
    "chunk,block,lengths", [(16, 128, (48, 29)), (48, 256, (48, 48))],
    ids=["3_chunks_2_blocks_a_short_row", "one_tile_full_rows"],
)
def test_forward_and_six_gradients_match_the_recurrence(chunk, block, lengths):
    """y at every valid position, and the gradients of x, dt, A_log, B, C
    and D, with the state carried over chunk boundaries in the forward
    pass and the adjoint carried back over them in the backward pass."""
    args, gy = _operands()
    lens = jnp.asarray(lengths, jnp.int32)
    valid = (jnp.arange(LENGTH)[None] < lens[:, None])[..., None]

    def loss(fn):
        return lambda *a: jnp.sum(jnp.where(valid, fn(*a, lens), 0.0) * gy)

    got = jax.jit(jax.value_and_grad(loss(_kernel(chunk, block)), tuple(range(6))))(*args)
    want = jax.jit(jax.value_and_grad(loss(selective_scan_reference), tuple(range(6))))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, g, r in zip(NAMES, got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=2e-5 * float(jnp.max(jnp.abs(r))), err_msg=name)


def test_a_padded_position_changes_nothing_valid_and_takes_no_step_gradient():
    """Garbage past a row's length leaves its valid outputs as they were,
    and dt, x and B there take no gradient."""
    args, gy = _operands(1)
    lens = jnp.asarray([48, 20], jnp.int32)
    pad = (jnp.arange(LENGTH)[None] >= lens[:, None])[..., None]
    noisy = list(args)
    for i in (0, 1, 3, 4):
        noisy[i] = jnp.where(pad, 1e3 * jnp.ones_like(args[i]), args[i])
    kernel = jax.jit(_kernel(16, 128))
    np.testing.assert_array_equal(
        jnp.where(pad, 0.0, kernel(*args, lens)), jnp.where(pad, 0.0, kernel(*noisy, lens))
    )
    loss = lambda x, dt, b: jnp.sum(_kernel(16, 128)(x, dt, args[2], b, args[4], args[5], lens) * gy)
    gx, gdt, gb = jax.jit(jax.grad(loss, (0, 1, 2)))(args[0], args[1], args[3])
    # x still feeds the skip D x at a padded position; the scan takes nothing from it
    np.testing.assert_array_equal(jnp.where(pad, gx - args[5] * gy, 0.0), 0.0)
    np.testing.assert_array_equal(jnp.where(pad, gdt, 0.0), 0.0)
    assert float(jnp.max(jnp.abs(gb[1, 20:]))) == 0.0


def test_the_forward_rule_names_what_a_remat_policy_keeps():
    """Under `save_only_these_names(*SCAN_SAVED_NAMES)` the rematerialised
    scan keeps its output and the (chunks, states, channels) states that
    entered each chunk, and nothing wider."""
    from jax._src.ad_checkpoint import saved_residuals

    args, _ = _operands()
    lens = jnp.asarray([48, 48], jnp.int32)
    policy = jax.checkpoint_policies.save_only_these_names(*SCAN_SAVED_NAMES)
    f = jax.checkpoint(lambda *a: jnp.sum(jnp.square(_kernel(16, 128)(*a, lens))), policy=policy)
    kept = sorted(
        aval.str_short() for aval, why in saved_residuals(f, *args)
        if "argument" not in why and aval.dtype == jnp.float32
    )
    assert kept == ["float32[2,3,4,256]", "float32[2,48,256]"]
