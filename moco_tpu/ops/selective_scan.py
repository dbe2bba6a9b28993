"""The selective scan of a Mamba layer (arXiv:2312.00752, Algorithm 2) as
Pallas TPU kernels, forward AND backward.

For every channel d of the layer's inner width and every state n, with a
diagonal A (one negative number a channel and state):

    s_t = exp(dt_t A) * s_{t-1} + dt_t x_t B_t      s: (D, N)
    y_t = s_t C_t + D_skip * x_t

dt, x: (batch, L, D); B, C: (batch, L, N), shared by every channel. A
`lax.scan` over the positions is a sequential program of L steps, and an
associative scan over (L, D, N) materialises L x D x N float32 states
(5.4 GB at 16 384 x 5120 x 16): neither is acceptable. Here the grid is
(batch, channel block, chunk of positions), the state of a block of
channels stays in VMEM scratch from one chunk to the next, and only the
state entering each chunk goes to HBM (the backward pass starts from it).
Inside a chunk the recurrence of each state n is a scan over the
chunk's positions, which lie on the sublanes: log2(chunk) steps of a roll
and a multiply-add over the whole (chunk, channels) tile, so that every
operation fills the vector unit.

The backward kernel walks the chunks in reverse. It recomputes the
chunk's states from the saved state that entered it, and runs the adjoint
recurrence g_t = C_t gy_t + exp(dt_{t+1} A) g_{t+1} the same way, with
the adjoint leaving the chunk carried in scratch to the one before. B's
and C's gradients are sums over every channel: each channel block writes
its own partial sums, added outside.

`lengths`: the dt of a position at or beyond its row's length is taken as
0, so such a position leaves the state as it found it and takes no
gradient; a position only reads earlier ones, so no valid output reads a
padded one. The causal depthwise convolution and the gates around the scan
stay XLA fusions (the model's file).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a grid step scans (sublanes: a multiple of 8) and channels it
# holds (lanes: a multiple of 128); a (128, 512) float32 tile is 256 KB
CHUNK = 128
BLOCK = 512
# what the forward rule names for a remat policy to keep: the scan's output
# and the state entering each chunk, all its backward kernel needs beyond
# its inputs
SCAN_SAVED_NAMES = ("selective_scan_out", "selective_scan_states")

_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def selective_scan_reference(x, dt, a_log, b, c, d, lengths):
    """The plain recurrence, one position after another (float32): the
    kernels' oracle. Same arguments as `selective_scan`."""
    a = -jnp.exp(a_log.astype(jnp.float32))  # (D, N)
    valid = (jnp.arange(x.shape[1])[None, :] < lengths[:, None])[..., None]
    dt = jnp.where(valid, dt.astype(jnp.float32), 0.0)
    xf = x.astype(jnp.float32)

    def step(s, t):
        dt_t, x_t, b_t, c_t = t  # (Bt, D), (Bt, D), (Bt, N), (Bt, N)
        s = jnp.exp(dt_t[..., None] * a) * s + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, c_t, precision=lax.Precision.HIGHEST)

    s0 = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), jnp.float32)
    per_t = lambda v: jnp.moveaxis(v.astype(jnp.float32), 1, 0)
    _, y = lax.scan(step, s0, (per_t(dt), per_t(xf), per_t(b), per_t(c)))
    return jnp.moveaxis(y, 0, 1) + d.astype(jnp.float32) * xf


def _scan_down(a, b, rows):
    """Inclusive scan of h_t = a_t h_{t-1} + b_t over axis 0 from h = 0:
    (prod of a up to t, h_t). Hillis-Steele: step k joins each row to the
    row k above it."""
    k = 1
    while k < a.shape[0]:
        above = rows >= k
        b = b + a * jnp.where(above, pltpu.roll(b, k, 0), 0.0)
        a = a * jnp.where(above, pltpu.roll(a, k, 0), 1.0)
        k *= 2
    return a, b


def _scan_up(a, c, rows):
    """Inclusive scan of g_t = c_t + a_t g_{t+1} over axis 0 from the
    bottom, g = 0 past the last row."""
    n, k = a.shape[0], 1
    while k < n:
        below = rows < n - k
        c = c + a * jnp.where(below, pltpu.roll(c, n - k, 0), 0.0)  # roll by n - k: row t reads t + k
        a = a * jnp.where(below, pltpu.roll(a, n - k, 0), 1.0)
        k *= 2
    return c


def _chunk_inputs(lens_ref, x_ref, dt_ref, chunk: int, block: int, j):
    """The chunk's dt (0 past the row's length), x and dt * x, float32."""
    rows = lax.broadcasted_iota(jnp.int32, (chunk, block), 0)
    valid = j * chunk + rows < lens_ref[pl.program_id(0)]
    dt = jnp.where(valid, dt_ref[...].astype(jnp.float32), 0.0)
    x = x_ref[...].astype(jnp.float32)
    return rows, valid, dt, x, dt * x


def _fwd_kernel(lens_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, s_sc,
                *, chunk: int, block: int, states: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        s_sc[...] = jnp.zeros_like(s_sc)

    st_ref[...] = s_sc[...]  # the state entering this chunk: the backward pass starts here
    rows, _, dt, _, u = _chunk_inputs(lens_ref, x_ref, dt_ref, chunk, block, j)
    bm, cm = b_ref[...], c_ref[...]
    y = jnp.zeros((chunk, block), jnp.float32)
    for n in range(states):
        decay, h = _scan_down(jnp.exp(dt * a_ref[n : n + 1, :]), u * bm[:, n : n + 1], rows)
        h = h + decay * s_sc[n : n + 1, :]
        y = y + h * cm[:, n : n + 1]
        s_sc[n : n + 1, :] = h[chunk - 1 :, :]
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(lens_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, gy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, r_sc,
                *, chunk: int, block: int, states: int, n_chunks: int):
    step = pl.program_id(2)  # chunks in reverse: this is chunk n_chunks - 1 - step
    j = n_chunks - 1 - step

    @pl.when(step == 0)
    def _():
        r_sc[...] = jnp.zeros_like(r_sc)  # nothing flows back from past the last position
        da_ref[...] = jnp.zeros_like(da_ref)

    rows, valid, dt, x, u = _chunk_inputs(lens_ref, x_ref, dt_ref, chunk, block, j)
    bm, cm = b_ref[...], c_ref[...]
    gy = gy_ref[...].astype(jnp.float32)
    first, last = rows == 0, rows == chunk - 1
    lane = lax.broadcasted_iota(jnp.int32, (1, states), 1)
    g_b = jnp.zeros((chunk, block), jnp.float32)  # sum over n of g B_n
    g_dt = jnp.zeros((chunk, block), jnp.float32)  # sum over n of A_n g a h_prev
    db = jnp.zeros((chunk, states), jnp.float32)
    dc = jnp.zeros((chunk, states), jnp.float32)
    for n in range(states):
        a_n = a_ref[n : n + 1, :]
        s0 = st_ref[n : n + 1, :]
        decay = jnp.exp(dt * a_n)
        cum, h = _scan_down(decay, u * bm[:, n : n + 1], rows)
        h = h + cum * s0
        h_prev = jnp.where(first, s0, pltpu.roll(h, 1, 0))
        dc = dc + jnp.sum(gy * h, axis=1, keepdims=True) * (lane == n)
        # g_t = C_t gy_t + a_{t+1} g_{t+1}; the chunk's last row adds what
        # the chunk after it left (r = a g at that chunk's first row)
        carry_in = jnp.where(last, r_sc[n : n + 1, :], 0.0)
        next_decay = jnp.where(last, 0.0, pltpu.roll(decay, chunk - 1, 0))
        g = _scan_up(next_decay, gy * cm[:, n : n + 1] + carry_in, rows)
        r_sc[n : n + 1, :] = (decay * g)[:1, :]
        w = g * decay * h_prev  # d loss / d (dt A) at (t, n)
        g_b = g_b + g * bm[:, n : n + 1]
        g_dt = g_dt + w * a_n
        da_ref[n : n + 1, :] += jnp.sum(w * dt, axis=0, keepdims=True)
        db = db + jnp.sum(g * u, axis=1, keepdims=True) * (lane == n)
    dx_ref[...] = (g_b * dt).astype(dx_ref.dtype)
    ddt_ref[...] = jnp.where(valid, g_b * x + g_dt, 0.0)
    db_ref[...] = db
    dc_ref[...] = dc


def _tiles(x, chunk: int, block: int):
    bt, length, width = x.shape
    chunk, block = min(chunk, length), min(block, width)
    if length % chunk or width % block:
        raise ValueError(f"({length}, {width}) is not tiled by chunks of {chunk} and blocks of {block}")
    return bt, length, width, chunk, block


def _forward(x, dt, a, b, c, lengths, chunk, block, interpret):
    bt, length, width, chunk, block = _tiles(x, chunk, block)
    states, n_chunks = a.shape[0], length // chunk
    tile = pl.BlockSpec((None, chunk, block), lambda i, k, j, lens: (i, j, k))
    narrow = pl.BlockSpec((None, chunk, states), lambda i, k, j, lens: (i, j, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, block=block, states=states),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bt, width // block, n_chunks),
            in_specs=[tile, tile, pl.BlockSpec((states, block), lambda i, k, j, lens: (0, k)),
                      narrow, narrow],
            out_specs=[tile, pl.BlockSpec((None, None, states, block), lambda i, k, j, lens: (i, j, 0, k))],
            scratch_shapes=[pltpu.VMEM((states, block), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bt, length, width), x.dtype),
            jax.ShapeDtypeStruct((bt, n_chunks, states, width), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="selective_scan_fwd",
    )(lengths, x, dt, a, b, c)


def _backward(x, dt, a, b, c, lengths, saved, gy, chunk, block, interpret):
    bt, length, width, chunk, block = _tiles(x, chunk, block)
    states, n_chunks, n_blocks = a.shape[0], length // chunk, width // block
    back = lambda j: n_chunks - 1 - j
    tile = pl.BlockSpec((None, chunk, block), lambda i, k, j, lens: (i, back(j), k))
    narrow = pl.BlockSpec((None, chunk, states), lambda i, k, j, lens: (i, back(j), 0))
    partial = pl.BlockSpec((None, None, chunk, states), lambda i, k, j, lens: (i, k, back(j), 0))
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, block=block, states=states, n_chunks=n_chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bt, n_blocks, n_chunks),
            in_specs=[
                tile, tile, pl.BlockSpec((states, block), lambda i, k, j, lens: (0, k)), narrow, narrow,
                pl.BlockSpec((None, None, states, block), lambda i, k, j, lens: (i, back(j), 0, k)),
                tile,
            ],
            out_specs=[
                tile, tile, pl.BlockSpec((None, states, block), lambda i, k, j, lens: (i, 0, k)),
                partial, partial,
            ],
            scratch_shapes=[pltpu.VMEM((states, block), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bt, length, width), x.dtype),
            jax.ShapeDtypeStruct((bt, length, width), jnp.float32),
            jax.ShapeDtypeStruct((bt, states, width), jnp.float32),
            jax.ShapeDtypeStruct((bt, n_blocks, length, states), jnp.float32),
            jax.ShapeDtypeStruct((bt, n_blocks, length, states), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="selective_scan_bwd",
    )(lengths, x, dt, a, b, c, saved, gy)
    return dx, ddt, jnp.sum(da, axis=0), jnp.sum(db, axis=1), jnp.sum(dc, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, a, b, c, lengths, chunk, block, interpret):
    return _forward(x, dt, a, b, c, lengths, chunk, block, interpret)[0]


def _scan_fwd(x, dt, a, b, c, lengths, chunk, block, interpret):
    """The forward rule. Its two outputs carry `SCAN_SAVED_NAMES`, so a
    `jax.checkpoint` whose policy saves those names keeps them and the
    forward kernel is dead code in its recomputation."""
    y, saved = _forward(x, dt, a, b, c, lengths, chunk, block, interpret)
    y = checkpoint_name(y, SCAN_SAVED_NAMES[0])
    saved = checkpoint_name(saved, SCAN_SAVED_NAMES[1])
    return y, (x, dt, a, b, c, lengths, saved)


def _scan_bwd(chunk, block, interpret, res, gy):
    x, dt, a, b, c, lengths, saved = res
    return (*_backward(x, dt, a, b, c, lengths, saved, gy, chunk, block, interpret), None)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(
    x: jax.Array,  # (Bt, L, D): the scan's input, after the convolution and its gate
    dt: jax.Array,  # (Bt, L, D): the step, after its softplus
    a_log: jax.Array,  # (D, N): A = -exp(a_log)
    b: jax.Array,  # (Bt, L, N)
    c: jax.Array,  # (Bt, L, N)
    d: jax.Array,  # (D,): the skip
    lengths: jax.Array,  # (Bt,) int32: dt is 0 at and beyond a row's length
    chunk: int = CHUNK,
    block: int = BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """y (Bt, L, D) in x's dtype; differentiable in x, dt, a_log, b, c and
    d. The scan runs on the kernels at any size their tiles divide (a
    chunk or block longer than the axis is the whole axis); the states,
    dt, B and C are float32 inside."""
    a = -jnp.exp(a_log.astype(jnp.float32)).T  # (N, D): a state's row spans the channels
    f32 = lambda v: v.astype(jnp.float32)
    y = _scan(x, f32(dt), a, f32(b), f32(c), lengths.astype(jnp.int32), chunk, block, interpret)
    return (f32(y) + f32(d) * f32(x)).astype(x.dtype)
