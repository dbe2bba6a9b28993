"""The embedding index — MoCo's dictionary, factored out of the queue.

MoCo's framing is "contrastive learning as dictionary look-up"
(arXiv:1911.05722): training scores queries against a FIFO dictionary
of key embeddings, and serving scores user queries against the same
kind of store. Until this module those two look-ups were separate
implementations — `core/queue.py` owned the FIFO write, `knn.py` owned
its own cosine top-k scan, and nothing served either. Both now rehost
on the two kernels here:

- :func:`fifo_write` — the FIFO block write (`dynamic_update_slice` at
  `ptr`, no wrap because callers keep K % block == 0). `core/queue.py`'s
  `enqueue` delegates here bit-for-bit, so the train-time queue IS the
  train-time instance of the index (the equivalence test in
  tests/test_serve.py pins this).
- :func:`topk_cosine` — the top-k cosine scan (one matmul + lax.top_k,
  optional valid-row mask). `knn.py`'s classifier and the serving
  `/neighbors` endpoint both call it.

:class:`EmbeddingIndex` wraps the kernels into the serving-side store:
rows live on device — optionally P(data)-sharded over a mesh, so the
scan's (m, K) matmul shards its contraction over the data axis exactly
like the model-sharded queue shards InfoNCE logits — with FIFO and
snapshot ingest, and an AOT-compiled query per padded query bucket so
serving traffic can never trigger a recompile (mocolint JX004 /
RecompileGuard discipline; serve/engine.py's bucket set is reused).

Two query tiers, one freeze() contract:

- **exact** (`topk_cosine`): brute-force top-k over every valid row —
  one (m, K) matmul. O(K) per query; below ~10^7 rows it is one small
  matmul next to the encoder forward, and it stays the correctness
  ORACLE for the approximate tier (the online recall estimator and the
  recall property tests both score IVF against it).
- **IVF** (`train_ivf` + `mode="ivf"`): an inverted-file structure.
  A jitted spherical k-means (:func:`kmeans_fit`, Lloyd iterations on
  device) partitions rows into `nlist` cells around L2-normalized
  centroids; a query scores the `nprobe` nearest centroids (one
  (m, nlist) matmul) and scans ONLY those cells. TPU-natively the cells
  are *dense padded* id lists — a static (nlist, cell_cap) int32 table,
  padded slots holding the sentinel id `capacity` — so the probe scan
  is a static-shape gather of (m, nprobe·cell_cap) candidate rows plus
  one batched matmul, and the executable is AOT-bucketed per
  (m, k, nprobe) exactly like the exact scan. Cost per query drops from
  O(K) to O(nprobe·K/nlist): the sub-linear unlock for the 10^7-row
  dictionaries the north star implies. Cell membership follows FIFO
  ingest incrementally (evicted rows swap-removed, fresh rows assigned
  to their nearest — or second-nearest, when full — cell), so a
  streaming replica never rebuilds.

An **int8 scoring path** (`enable_int8`) layers on both tiers:
symmetric per-row quantization (`q = round(127·x / max|x|)`, one f32
scale per row) of the stored rows, queries quantized the same way
in-graph, scores accumulated in int8→int32 and rescaled to f32 — ~4×
less score-stage memory traffic, bounded error (the recall tests pin
int8 recall and rescale error against the f32 oracle).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from moco_tpu.ops.losses import l2_normalize
from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.utils import faults
from moco_tpu.utils.platform import pallas_interpret

DEFAULT_KMEANS_ITERS = 10
# modes query()/prepare() understand; "*_i8" score in int8 (enable_int8),
# "ivf_fused*" run the fused gather-scan (no materialized candidate
# gather — _ivf_topk_fused) instead of the composed three-hop scan
QUERY_MODES = ("exact", "ivf", "exact_i8", "ivf_i8", "ivf_fused", "ivf_fused_i8")


def fifo_write(
    rows: jax.Array, ptr: jax.Array, values: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """FIFO block write of `values` (N, dim) at `ptr`; returns
    (rows, new_ptr). The write never wraps — callers maintain
    K % N == 0 (the reference queue invariant, `moco/builder.py:~L70`),
    so one `dynamic_update_slice` suffices. Bit-identical to the
    pre-refactor `core/queue.enqueue` body, which now delegates here."""
    num_rows = rows.shape[0]
    values = jax.lax.stop_gradient(values).astype(rows.dtype)
    rows = jax.lax.dynamic_update_slice(rows, values, (ptr, jnp.zeros_like(ptr)))
    new_ptr = (ptr + values.shape[0]) % num_rows
    return rows, new_ptr


def topk_cosine(
    queries: jax.Array,  # (m, dim) L2-normalized
    rows: jax.Array,  # (K, dim) L2-normalized
    k: int,
    valid_count: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k cosine scores + row indices of `queries` against `rows`.

    One (m, K) matmul + `lax.top_k` — the shared scan `knn.knn_classify`
    and the serving `/neighbors` path both rehost on. `valid_count`
    (dynamic scalar) masks rows at index >= count to -inf so a
    partially-filled index never surfaces uninitialized rows; passing it
    as a traced value means fill level changes never recompile."""
    sims = queries @ rows.T  # cosine: inputs are L2-normalized
    if valid_count is not None:
        invalid = jnp.arange(rows.shape[0]) >= valid_count
        sims = jnp.where(invalid[None, :], -jnp.inf, sims)
    return jax.lax.top_k(sims, k)


# -- IVF kernels ----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("nlist", "iters"))
def kmeans_fit(rows: jax.Array, nlist: int, iters: int = DEFAULT_KMEANS_ITERS):
    """Spherical k-means on L2-normalized `rows` (n, d): `iters` Lloyd
    iterations entirely on device, returning (nlist, d) L2-normalized
    centroids. Deterministic strided init (every n//nlist-th row), so
    the coarse quantizer is reproducible without threading a PRNG key.
    Empty cells keep their previous centroid (the standard Lloyd
    degenerate-cell fix). All shapes static: one executable per
    (n, d, nlist, iters)."""
    n = rows.shape[0]
    if nlist > n:
        raise ValueError(f"nlist={nlist} exceeds the {n} training rows")
    stride = max(n // nlist, 1)
    init = l2_normalize(jax.lax.slice(rows, (0, 0), (stride * nlist, rows.shape[1]), (stride, 1)))

    def body(_, cent):
        sims = rows @ cent.T  # (n, nlist)
        onehot = jax.nn.one_hot(jnp.argmax(sims, axis=1), nlist, dtype=rows.dtype)
        sums = onehot.T @ rows  # (nlist, d) — the segment-sum as one matmul
        counts = jnp.sum(onehot, axis=0)[:, None]
        cent = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), cent)
        return l2_normalize(cent)

    return jax.lax.fori_loop(0, iters, body, init)


@jax.jit
def _assign_top2(rows: jax.Array, centroids: jax.Array):
    """(first, second) nearest-centroid ids per row — the second choice
    is the overflow fallback when a dense padded cell is already full.
    Two argmax passes, NOT `lax.top_k(sims, 2)`: top_k sorts the whole
    (n, nlist) score matrix, which measured ~6x slower than the matmul
    itself on XLA:CPU and dominated the 2^20-row build."""
    sims = rows @ centroids.T
    first = jnp.argmax(sims, axis=1).astype(jnp.int32)
    masked = jnp.where(
        jnp.arange(sims.shape[1])[None, :] == first[:, None], -jnp.inf, sims
    )
    return first, jnp.argmax(masked, axis=1).astype(jnp.int32)


@jax.jit
def _quantize_rows_int8(x: jax.Array):
    """Symmetric per-row int8: q = round(127·x / max|x|), one f32 scale
    per row (zero rows get scale 1 so padding stays exactly zero)."""
    s = jnp.max(jnp.abs(x), axis=-1).astype(jnp.float32) / 127.0
    s = jnp.where(s <= 0, jnp.float32(1.0), s)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def _ivf_topk(
    queries,  # (m, d) f32 L2-normalized
    rows,  # (K, d) f32 — or (K, d) int8 when row_scale is given
    centroids,  # (nlist, d) f32
    cell_ids,  # (nlist, cell_cap) int32, sentinel id == K on padded slots
    valid_count,  # traced scalar: rows at id >= valid are masked
    k: int,
    nprobe: int,
    row_scale=None,  # (K,) f32 per-row dequant scales (int8 path)
):
    """The IVF probe scan, all shapes static per (m, k, nprobe):
    coarse (m, nlist) matmul → top-nprobe cells per query → ONE dense
    gather of the probed cells' candidate ids (m, nprobe·cell_cap) →
    candidate row gather + one batched matmul → top-k over candidates,
    mapped back to global row ids. Padded slots carry the sentinel id
    (== capacity), which the valid mask sends to -inf, so partial cells
    and partial fills never surface junk rows and never recompile."""
    m = queries.shape[0]
    num_rows = rows.shape[0]
    coarse = queries @ centroids.T  # (m, nlist)
    _, probes = jax.lax.top_k(coarse, nprobe)  # (m, nprobe)
    cand_ids = cell_ids[probes].reshape(m, -1)  # (m, nprobe*cell_cap)
    safe = jnp.minimum(cand_ids, num_rows - 1)
    cand = rows[safe]  # (m, L, d) dense padded-cell gather
    if row_scale is None:
        sims = jax.lax.dot_general(
            queries, cand, (((1,), (2,)), ((0,), (0,)))
        )  # (m, L): one small matmul per probe batch
    else:
        q8, qs = _quantize_rows_int8(queries)
        acc = jax.lax.dot_general(
            q8, cand, (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )
        sims = acc.astype(jnp.float32) * qs[:, None] * row_scale[safe]
    sims = jnp.where(cand_ids >= valid_count, -jnp.inf, sims)
    scores, local = jax.lax.top_k(sims, k)
    return scores, jnp.take_along_axis(cand_ids, local, axis=1)


def _ivf_topk_fused(
    queries,  # (m, d) f32 L2-normalized
    rows,  # (K, d) f32 — or (K, d) int8 when row_scale is given
    centroids,  # (nlist, d) f32
    cell_ids,  # (nlist, cell_cap) int32, sentinel id == K on padded slots
    valid_count,  # traced scalar: rows at id >= valid are masked
    k: int,
    nprobe: int,
    row_scale=None,  # (K,) f32 per-row dequant scales (int8 path)
):
    """The fused IVF gather-scan: one kernel instead of the composed
    centroid-score → cell-gather → score → top-k hops. A hand-tiled
    `lax.fori_loop` over the nprobe probed cells scores ONE dense padded
    cell per query in place each step and folds it into a running top-k
    (concat the k carried best with the cell's cell_cap scores, re-top-k)
    — the composed path's (m, nprobe·cell_cap, d) candidate gather never
    materializes; peak live candidate memory drops nprobe-fold to
    (m, cell_cap, d). On the 1-core CPU smoke that cache residency is
    worth ~3.7x queries/s at identical results; on TPU the same shape
    maps onto the Pallas variant (`_fused_cell_scores_pallas`, one
    scalar-prefetched cell DMA per grid step). Results: the exact same
    candidate set as `_ivf_topk` (top_k probes are distinct, each row
    lives in one cell — no duplicates), so on ties-free data the top-k
    ids are identical and the scores allclose (the oracle test pins
    both). -inf-scored tail slots (k exceeding the valid candidates)
    carry the sentinel id `K` where the composed scan surfaces an
    arbitrary masked row — neither is a valid neighbor."""
    m = queries.shape[0]
    num_rows = rows.shape[0]
    coarse = queries @ centroids.T  # (m, nlist): the only dense hop kept
    _, probes = jax.lax.top_k(coarse, nprobe)  # (m, nprobe)
    if row_scale is not None:
        q8, qs = _quantize_rows_int8(queries)

    def body(j, carry):
        best_s, best_i = carry
        cell_j = jax.lax.dynamic_slice_in_dim(probes, j, 1, axis=1)[:, 0]  # (m,)
        ids = cell_ids[cell_j]  # (m, cell_cap): this step's cells only
        safe = jnp.minimum(ids, num_rows - 1)
        cand = rows[safe]  # (m, cell_cap, d) — the whole live gather
        if row_scale is None:
            sims = jax.lax.dot_general(
                queries, cand, (((1,), (2,)), ((0,), (0,)))
            )  # (m, cell_cap) scored in place
        else:
            acc = jax.lax.dot_general(
                q8, cand, (((1,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.int32,
            )
            sims = acc.astype(jnp.float32) * qs[:, None] * row_scale[safe]
        sims = jnp.where(ids >= valid_count, -jnp.inf, sims)
        merged_s = jnp.concatenate([best_s, sims], axis=1)
        merged_i = jnp.concatenate([best_i, ids], axis=1)
        s, loc = jax.lax.top_k(merged_s, k)  # running top-k, O(k + cell_cap)
        return s, jnp.take_along_axis(merged_i, loc, axis=1)

    init = (
        jnp.full((m, k), -jnp.inf, jnp.float32),
        jnp.full((m, k), num_rows, jnp.int32),
    )
    return jax.lax.fori_loop(0, nprobe, body, init)


def _fused_cell_scores_kernel(probes_ref, q_ref, cell_rows_ref, out_ref):
    """Pallas body for one (query, probe) grid step: the BlockSpec index
    map already DMA'd this query's j-th probed cell (scalar-prefetched
    `probes` pick the block), so the kernel is a single (1, d) ×
    (cell_cap, d)^T dot — the cell is scored straight out of its DMA
    tile, and the (m, nprobe·cell_cap, d) gather never exists in HBM."""
    out_ref[...] = jax.lax.dot_general(
        q_ref[...],
        cell_rows_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _fused_cell_scores_pallas(queries, cell_rows, probes, interpret=False):
    """(m, nprobe, cell_cap) candidate scores via a Pallas grid over
    (query, probe): `cell_rows` is the cell-major (nlist, cell_cap, d)
    row layout (built lazily per IVF epoch, like the device cell table)
    and `probes` rides the scalar-prefetch channel so each grid step's
    BlockSpec selects the right cell tile to DMA. Real chips only
    (capability probe `_pallas_fused_default`); `interpret=True` runs
    the same kernel on CPU for the equivalence tests."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = queries.shape
    nlist, cell_cap, _ = cell_rows.shape
    nprobe = probes.shape[1]
    # Mosaic wants a block's last two extents (8, 128)-aligned or equal
    # to the array's, and a one-query (1, d) block of an (m, d) array
    # is neither ("The Pallas TPU lowering currently requires that the
    # last two dimensions of your block shape are divisible by 8 and
    # 128 respectively, or be equal to the respective dimensions of the
    # overall array", v5e, jax 0.9.0). So queries ride as (m, 1, d) and
    # scores as (m, nprobe, 1, cell_cap): the per-step (1, d) and
    # (1, cell_cap) tiles are then full trailing extents, and the
    # leading axes are squeezed (None) out of the kernel's view.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, nprobe),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda i, j, p: (i, 0, 0)),
            pl.BlockSpec((None, cell_cap, d), lambda i, j, p: (p[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, 1, cell_cap), lambda i, j, p: (i, j, 0, 0)
        ),
    )
    scores = pl.pallas_call(
        _fused_cell_scores_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nprobe, 1, cell_cap), jnp.float32),
        interpret=interpret,
    )(probes, queries.astype(jnp.float32).reshape(m, 1, d), cell_rows)
    return scores.reshape(m, nprobe, cell_cap)


def _ivf_topk_fused_pallas(
    queries,
    rows,
    centroids,
    cell_ids,
    cell_rows,  # (nlist, cell_cap, d) cell-major row copy (f32)
    valid_count,
    k: int,
    nprobe: int,
    interpret: bool = False,
):
    """Fused scan with the cell scoring in Pallas: coarse matmul →
    top-nprobe probes → `_fused_cell_scores_pallas` (per-cell DMA +
    dot, no candidate-row gather) → mask + one top-k over the scores.
    Same candidate set and mask as `_ivf_topk`, so ids/scores match the
    composed oracle on ties-free data. `rows` is unused (the cell-major
    copy carries the vectors) but kept in the signature so query()'s
    argument plumbing stays uniform across fused variants."""
    del rows
    m = queries.shape[0]
    coarse = queries @ centroids.T
    _, probes = jax.lax.top_k(coarse, nprobe)
    sims = _fused_cell_scores_pallas(queries, cell_rows, probes, interpret=interpret)
    sims = sims.reshape(m, -1)  # (m, nprobe*cell_cap) — scores, not rows
    cand_ids = cell_ids[probes].reshape(m, -1)
    sims = jnp.where(cand_ids >= valid_count, -jnp.inf, sims)
    scores, local = jax.lax.top_k(sims, k)
    return scores, jnp.take_along_axis(cand_ids, local, axis=1)


def _exact_topk_int8(queries, rows_i8, row_scale, valid_count, k: int):
    """The exact scan's int8 twin: per-row quantized queries against the
    per-row quantized store, int32 accumulation, f32 rescale — same
    mask/top-k contract as `topk_cosine`."""
    q8, qs = _quantize_rows_int8(queries)
    acc = jax.lax.dot_general(
        q8, rows_i8, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )
    sims = acc.astype(jnp.float32) * qs[:, None] * row_scale[None, :]
    invalid = jnp.arange(rows_i8.shape[0]) >= valid_count
    sims = jnp.where(invalid[None, :], -jnp.inf, sims)
    return jax.lax.top_k(sims, k)


def _pallas_fused_default() -> tuple[bool, bool]:
    """(use_pallas, interpret) for the fused scan: the Pallas cell-DMA
    kernel runs on a TPU backend by default (compiled there and at the
    serving shapes by tests/test_tpu_kernels.py); `MOCO_IVF_PALLAS`
    overrides: `0` forces the portable lax fori_loop variant on a chip,
    `1` forces Pallas, `interpret` runs the kernel in interpret mode on
    any backend (the CPU equivalence tests)."""
    env = os.environ.get("MOCO_IVF_PALLAS", "").strip().lower()
    if env in ("0", "off", "false"):
        return False, False
    if env == "interpret":
        return True, True
    if env in ("1", "on", "true"):
        return True, False
    return not pallas_interpret(), False


class IndexRecompileError(RuntimeError):
    """A query shape arrived that was not AOT-compiled at prepare()
    time — serving must pad to a prepared bucket, never trace anew."""


class EmbeddingIndex:
    """Device-resident embedding store with FIFO/snapshot ingest and
    AOT-bucketed top-k cosine queries — exact, IVF approximate, and
    int8 variants of both (module docstring).

    `mesh` shards the rows P(data, None) — capacity is padded up to a
    multiple of the data-axis width so the shard is rectangular; padded
    rows sit above `count` and are masked out of every query. Without a
    mesh the rows live replicated on the default device.
    """

    def __init__(
        self,
        capacity: int,
        dim: int,
        mesh=None,
        dtype=jnp.float32,
    ):
        if capacity < 1:
            raise ValueError(f"index capacity must be >= 1, got {capacity}")
        self.dim = int(dim)
        self.mesh = mesh
        self._n_data = mesh.shape[DATA_AXIS] if mesh is not None else 1
        # rectangular shard: pad capacity up to a multiple of the axis
        self.capacity = -(-int(capacity) // self._n_data) * self._n_data
        self.requested_capacity = int(capacity)
        self.count = 0  # valid rows (host-side; queries read a device copy)
        self._ptr = 0  # FIFO write head (host-side mirror)
        # wall-clock ingest stamps (freshness SLO): one host-side float
        # per row slot, NaN = never written. The training queue_age
        # gauge is STEP-denominated; serving staleness must be wall
        # seconds — `row_age_stats()` reads these, the serve flusher
        # feeds them to the FreshnessBurnTracker.
        self._row_time = np.full(self.capacity, np.nan, np.float64)
        self._row_sharding = None
        self._rep_sharding = None
        self._scale_sharding = None
        rows = jnp.zeros((self.capacity, self.dim), dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._row_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
            self._rep_sharding = NamedSharding(mesh, P())
            self._scale_sharding = NamedSharding(mesh, P(DATA_AXIS))
            rows = jax.device_put(rows, self._row_sharding)
        self.rows = rows
        self._compiled: dict[tuple, object] = {}
        self._ingest_jits: dict[tuple, object] = {}
        self._frozen = False
        self.aot_compiles = 0
        self._warm_compiles: Optional[int] = None
        # int8 scoring state (enable_int8): per-row quantized rows + scales
        self._rows_i8: Optional[jax.Array] = None
        self._row_scale: Optional[jax.Array] = None
        # IVF state (train_ivf): device arrays + host mirrors for
        # incremental FIFO maintenance
        self._ivf: Optional[dict] = None
        # fused-scan lowering: Pallas cell-DMA kernel on real chips,
        # hand-tiled lax fori_loop everywhere else (_pallas_fused_default)
        self._fused_pallas, self._fused_interpret = _pallas_fused_default()

    # -- ingest ----------------------------------------------------------

    def snapshot(
        self, embeddings: np.ndarray, normalized: bool = True,
        now: Optional[float] = None,
    ) -> None:
        """Bulk (re)load: replace the store's contents with `embeddings`
        (n <= capacity rows) — the "load the trained dictionary" path
        (e.g. a checkpoint's queue). Resets the FIFO head. Invalidates a
        trained IVF structure (cell membership is content-derived —
        retrain with `train_ivf` after a bulk reload); the int8 mirror
        is requantized in place. Every loaded row is ingest-stamped at
        `now` (wall clock by default; injectable for tests)."""
        embs = np.asarray(embeddings)
        n = embs.shape[0]
        if n > self.capacity or embs.shape[1] != self.dim:
            raise ValueError(
                f"snapshot shape {embs.shape} exceeds index ({self.capacity}, {self.dim})"
            )
        if not normalized:
            embs = np.asarray(l2_normalize(jnp.asarray(embs)))
        full = np.zeros((self.capacity, self.dim), self.rows.dtype)
        full[:n] = embs
        rows = jnp.asarray(full)
        if self._row_sharding is not None:
            rows = jax.device_put(rows, self._row_sharding)
        self.rows = rows
        self.count = n
        self._ptr = n % self.capacity
        self._row_time[:] = np.nan
        self._row_time[:n] = time.time() if now is None else now
        self._ivf = None  # content replaced wholesale: cells are stale
        if self._rows_i8 is not None:
            self._requantize_all()

    def _fifo_jit(self, n: int):
        """Donated jitted FIFO write for an n-row block: the update runs
        in place on device, the P(data) sharding (when meshed) is pinned
        by in/out shardings, and NO host round-trip or re-shard happens
        — the pre-IVF `add()` rebuilt rows via host `device_put` every
        block. `ptr` is traced, so the write head never recompiles."""
        key = ("fifo", n)
        fn = self._ingest_jits.get(key)
        if fn is None:
            donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
            kwargs = {}
            if self._row_sharding is not None:
                kwargs = dict(
                    in_shardings=(self._row_sharding, self._rep_sharding, self._rep_sharding),
                    out_shardings=(self._row_sharding, self._rep_sharding),
                )
            fn = jax.jit(fifo_write, donate_argnums=donate, **kwargs)
            self._ingest_jits[key] = fn
        return fn

    def _int8_write_jit(self, n: int):
        key = ("int8", n)
        fn = self._ingest_jits.get(key)
        if fn is None:

            def write(rows_i8, scale, values, ptr):
                q, s = _quantize_rows_int8(values)
                rows_i8 = jax.lax.dynamic_update_slice(
                    rows_i8, q, (ptr, jnp.zeros_like(ptr))
                )
                scale = jax.lax.dynamic_update_slice(scale, s, (ptr,))
                return rows_i8, scale

            donate = (0, 1) if jax.default_backend() in ("tpu", "gpu") else ()
            kwargs = {}
            if self._row_sharding is not None:
                kwargs = dict(
                    in_shardings=(
                        self._row_sharding, self._scale_sharding,
                        self._rep_sharding, self._rep_sharding,
                    ),
                    out_shardings=(self._row_sharding, self._scale_sharding),
                )
            fn = jax.jit(write, donate_argnums=donate, **kwargs)
            self._ingest_jits[key] = fn
        return fn

    def _write_block(self, values: jax.Array, ptr: int) -> None:
        """One no-wrap block write at `ptr` through the donated jitted
        updates (rows, then the int8 mirror when enabled)."""
        p = jnp.int32(ptr)
        self.rows, _ = self._fifo_jit(values.shape[0])(self.rows, p, values)
        if self._rows_i8 is not None:
            self._rows_i8, self._row_scale = self._int8_write_jit(values.shape[0])(
                self._rows_i8, self._row_scale, values.astype(jnp.float32), p
            )

    def add(self, embeddings: np.ndarray, now: Optional[float] = None) -> None:
        """FIFO ingest of an (N, dim) block at the write head — the
        serving-side mirror of the training enqueue. A block crossing
        the capacity boundary splits into two no-wrap writes (training
        keeps its K % N == 0 invariant and never takes the split). The
        write is a donated jitted device update that keeps the P(data)
        sharding in place; the int8 mirror and IVF cell membership (when
        enabled/trained) follow incrementally. Overwritten slots get a
        fresh ingest stamp at `now` — FIFO eviction is what keeps the
        freshness SLO honest (the oldest stamp leaves with its row)."""
        embs = jnp.asarray(embeddings, self.rows.dtype)
        n = embs.shape[0]
        if n == 0:
            return
        if n > self.capacity:
            raise ValueError(
                f"FIFO block of {n} rows exceeds capacity {self.capacity}; "
                "use snapshot() for bulk loads"
            )
        start = self._ptr
        head = min(n, self.capacity - start)
        written = [(start, embs[:head])]
        if head < n:
            written.append((0, embs[head:]))
        overwritten = np.concatenate(
            [np.arange(p, p + b.shape[0]) for p, b in written]
        )
        for p, block in written:
            self._write_block(block, p)
        if self._ivf is not None:
            self._ivf_reassign(overwritten, np.asarray(embs, np.float32))
        self._row_time[overwritten] = time.time() if now is None else now
        self._ptr = (self._ptr + n) % self.capacity
        self.count = min(self.count + n, self.capacity)

    @classmethod
    def from_train_queue(
        cls, queue: jax.Array, queue_ptr=0, count: Optional[int] = None, mesh=None
    ) -> "EmbeddingIndex":
        """The train-time queue as an index: wrap a checkpoint's
        (K, dim) queue rows (already L2-normalized by `init_queue`/
        `enqueue`). `count=None` treats every row as valid — after
        warmup the training queue is always full."""
        rows = np.asarray(queue)
        idx = cls(rows.shape[0], rows.shape[1], mesh=mesh, dtype=rows.dtype)
        idx.snapshot(rows)
        idx.count = rows.shape[0] if count is None else int(count)
        idx._ptr = int(queue_ptr)
        return idx

    def row_age_stats(self, now: Optional[float] = None) -> dict:
        """Wall-clock staleness of the valid rows: max/mean seconds
        since each row's ingest stamp. `{"row_age_max_s": None, ...}`
        while no stamped rows exist (empty index). The serve flusher
        exports these as `serve/row_age_max_s`/`serve/row_age_mean_s`
        and feeds the max to the freshness burn tracker; `now` is
        injectable so the burn math is unit-testable."""
        now = time.time() if now is None else now
        stamps = self._row_time[: self.count]
        valid = stamps[np.isfinite(stamps)]
        if valid.size == 0:
            return {"row_age_max_s": None, "row_age_mean_s": None}
        ages = np.maximum(now - valid, 0.0)
        return {
            "row_age_max_s": float(ages.max()),
            "row_age_mean_s": float(ages.mean()),
        }

    # -- int8 scoring path ----------------------------------------------

    def enable_int8(self) -> None:
        """Build the symmetric per-row int8 mirror of the store. From
        here on `exact_i8`/`ivf_i8` modes are available and every FIFO
        write keeps the mirror fresh (quantized on device, in the same
        donated update)."""
        if self._rows_i8 is None:
            self._requantize_all()

    @property
    def int8_enabled(self) -> bool:
        return self._rows_i8 is not None

    def _requantize_all(self) -> None:
        q, s = _quantize_rows_int8(self.rows.astype(jnp.float32))
        if self._row_sharding is not None:
            q = jax.device_put(q, self._row_sharding)
            s = jax.device_put(s, self._scale_sharding)
        self._rows_i8, self._row_scale = q, s

    # -- IVF build + maintenance -----------------------------------------

    def train_ivf(
        self,
        nlist: Optional[int] = None,
        iters: int = DEFAULT_KMEANS_ITERS,
        cell_cap: Optional[int] = None,
        sample_rows: int = 65536,
        nprobe: Optional[int] = None,
        assign_chunk: int = 65536,
    ) -> dict:
        """Fit the coarse quantizer and build the inverted file over the
        current contents. k-means runs on device (`kmeans_fit`) over a
        strided sample of ≤ `sample_rows` valid rows (the standard IVF
        train/add split: Lloyd cost is O(sample·nlist·d), not O(K)),
        then every valid row is assigned to its nearest centroid in
        `assign_chunk` blocks. Cells are DENSE PADDED id lists of width
        `cell_cap` (default 2× the balanced fill, so mild imbalance
        never spills): a row whose first-choice cell is full falls to
        its second choice; only a doubly-full row is left out of the IVF
        (still served by the exact tier — `ivf_stats()['spilled']`
        counts them and the recall gate catches pathological skew).
        `nprobe` sets the default probe width for `mode="ivf"` queries.
        Returns `ivf_stats()`."""
        if self.count < 2:
            raise ValueError("train_ivf needs at least 2 valid rows")
        if nlist is None:
            nlist = max(2, int(np.sqrt(self.count)))
        valid = np.asarray(self.rows[: self.count].astype(jnp.float32))
        stride = max(self.count // int(sample_rows), 1)
        sample = jnp.asarray(valid[::stride][: int(sample_rows)])
        # top-2 fallback assignment needs >= 2 cells; the sample bounds
        # the fit, so nlist can never exceed it
        nlist = int(max(2, min(nlist, sample.shape[0])))
        centroids = kmeans_fit(sample, nlist=nlist, iters=int(iters))
        if cell_cap is None:
            cell_cap = max(2 * -(-self.count // nlist), 8)
        cell_cap = int(min(cell_cap, self.capacity))
        # chunked top-2 assignment of every valid row (one executable:
        # the tail chunk is zero-padded up to assign_chunk)
        first = np.empty(self.count, np.int32)
        second = np.empty(self.count, np.int32)
        chunk = int(min(assign_chunk, self.count))
        for lo in range(0, self.count, chunk):
            block = valid[lo : lo + chunk]
            pad = chunk - block.shape[0]
            if pad:
                block = np.concatenate([block, np.zeros((pad, self.dim), np.float32)])
            a1, a2 = _assign_top2(jnp.asarray(block), centroids)
            first[lo : lo + chunk - pad] = np.asarray(a1)[: chunk - pad]
            second[lo : lo + chunk - pad] = np.asarray(a2)[: chunk - pad]
        # host build of the dense padded cells (vectorized first choice,
        # loop only over the overflow tail)
        cells = np.full((nlist, cell_cap), self.capacity, np.int32)
        counts = np.zeros(nlist, np.int32)
        row_cell = np.full(self.capacity, -1, np.int32)
        row_slot = np.full(self.capacity, -1, np.int32)
        order = np.argsort(first, kind="stable")
        sorted_cells = first[order]
        starts = np.searchsorted(sorted_cells, np.arange(nlist), side="left")
        pos = np.arange(self.count) - starts[sorted_cells]
        ok = pos < cell_cap
        cells[sorted_cells[ok], pos[ok]] = order[ok]
        row_cell[order[ok]] = sorted_cells[ok]
        row_slot[order[ok]] = pos[ok]
        np.add.at(counts, sorted_cells[ok], 1)
        spilled = 0
        for rid in order[~ok]:  # overflow: second-choice fallback
            c2 = second[rid]
            if counts[c2] < cell_cap:
                cells[c2, counts[c2]] = rid
                row_cell[rid], row_slot[rid] = c2, counts[c2]
                counts[c2] += 1
            else:
                spilled += 1
        self._ivf = {
            "nlist": nlist,
            "cell_cap": cell_cap,
            "nprobe": int(nprobe) if nprobe else max(1, nlist // 16),
            "centroids": centroids,
            "cells_dev": None,  # lazily pushed (dirty)
            "cell_rows_dev": None,  # cell-major copy (Pallas fused scan)
            "cells": cells,
            "counts": counts,
            "row_cell": row_cell,
            "row_slot": row_slot,
            "spilled": int(spilled),
            "dirty": True,
        }
        return self.ivf_stats()

    def ivf_stats(self) -> dict:
        """Coarse-quantizer health: cell-occupancy spread and spill
        count (rows absent from the IVF, still served exactly)."""
        if self._ivf is None:
            return {"trained": False}
        c = self._ivf["counts"]
        return {
            "trained": True,
            "nlist": self._ivf["nlist"],
            "cell_cap": self._ivf["cell_cap"],
            "nprobe": self._ivf["nprobe"],
            "spilled": self._ivf["spilled"],
            "cell_count_min": int(c.min()),
            "cell_count_mean": float(c.mean()),
            "cell_count_max": int(c.max()),
            # mean cell fill over capacity — with `spilled`, the re-fit
            # trigger the fleet roadmap names (exported as
            # serve/ivf_occupancy + serve/ivf_spill by the server)
            "occupancy": float(c.mean()) / self._ivf["cell_cap"],
        }

    def _ivf_reassign(self, overwritten: np.ndarray, fresh: np.ndarray) -> None:
        """Incremental inverted-file maintenance for one FIFO block:
        swap-remove every overwritten row from its cell, then insert the
        fresh rows at their (first-, else second-) nearest centroid.
        Host-side on the small mirrors; the device table re-uploads
        lazily before the next IVF query."""
        ivf = self._ivf
        cells, counts = ivf["cells"], ivf["counts"]
        row_cell, row_slot = ivf["row_cell"], ivf["row_slot"]
        for rid in overwritten:
            c = row_cell[rid]
            if c < 0:
                continue
            slot, last = row_slot[rid], counts[c] - 1
            mover = cells[c, last]
            cells[c, slot] = mover
            row_slot[mover] = slot
            cells[c, last] = self.capacity
            counts[c] = last
            row_cell[rid] = row_slot[rid] = -1
        a1, a2 = _assign_top2(jnp.asarray(fresh), ivf["centroids"])
        a1, a2 = np.asarray(a1), np.asarray(a2)
        for i, rid in enumerate(overwritten):
            for c in (a1[i], a2[i]):
                if counts[c] < ivf["cell_cap"]:
                    cells[c, counts[c]] = rid
                    row_cell[rid], row_slot[rid] = c, counts[c]
                    counts[c] += 1
                    break
            else:
                ivf["spilled"] += 1
        ivf["dirty"] = True

    def _ivf_device_cells(self) -> jax.Array:
        ivf = self._ivf
        if ivf["dirty"] or ivf["cells_dev"] is None:
            cells = jnp.asarray(ivf["cells"])
            if self._rep_sharding is not None:
                cells = jax.device_put(cells, self._rep_sharding)
            ivf["cells_dev"] = cells
            ivf["cell_rows_dev"] = None  # cell-major copy went stale too
            ivf["dirty"] = False
        return ivf["cells_dev"]

    def _ivf_device_cell_rows(self) -> jax.Array:
        """Cell-major (nlist, cell_cap, d) f32 row copy for the Pallas
        fused scan: each grid step DMAs one cell tile straight from this
        layout instead of gathering candidate rows per query. Built
        lazily per IVF epoch (one gather) like the id table; ~2x the
        row memory at the default 2x cell_cap padding — the canonical
        IVF-on-TPU trade."""
        ivf = self._ivf
        cells = self._ivf_device_cells()
        if ivf.get("cell_rows_dev") is None:
            safe = jnp.minimum(cells, self.capacity - 1)
            cell_rows = self.rows.astype(jnp.float32)[safe]
            if self._rep_sharding is not None:
                cell_rows = jax.device_put(cell_rows, self._rep_sharding)
            ivf["cell_rows_dev"] = cell_rows
        return ivf["cell_rows_dev"]

    # -- query -----------------------------------------------------------

    def _require(self, mode: str, nprobe: Optional[int]) -> int:
        if mode not in QUERY_MODES:
            raise ValueError(f"unknown query mode {mode!r}; one of {QUERY_MODES}")
        if mode.endswith("_i8") and self._rows_i8 is None:
            raise ValueError(f"mode {mode!r} needs enable_int8() first")
        if mode.startswith("ivf"):
            if self._ivf is None:
                raise ValueError(f"mode {mode!r} needs train_ivf() first")
            return int(nprobe or self._ivf["nprobe"])
        return 0

    def _compile(self, m: int, k: int, mode: str = "exact", nprobe: int = 0):
        if self._frozen:
            raise IndexRecompileError(
                f"query shape (mode={mode}, m={m}, k={k}, nprobe={nprobe}) was "
                "not prepared before freeze() — serving must pad to a prepared "
                "bucket (engine bucket set); compiling now would be the "
                "recompile-after-warmup class RecompileGuard aborts on"
            )
        rep = self._rep_sharding
        shard_kw: dict = {}
        q_s = jax.ShapeDtypeStruct((m, self.dim), jnp.float32)
        valid_s = jax.ShapeDtypeStruct((), jnp.int32)
        if mode == "exact":
            fn = lambda q, rows, valid: topk_cosine(q, rows, k, valid_count=valid)
            args = (q_s, jax.ShapeDtypeStruct(self.rows.shape, self.rows.dtype), valid_s)
            if rep is not None:
                shard_kw = dict(
                    in_shardings=(rep, self._row_sharding, rep), out_shardings=rep
                )
        elif mode == "exact_i8":
            fn = lambda q, r8, sc, valid: _exact_topk_int8(q, r8, sc, valid, k)
            args = (
                q_s,
                jax.ShapeDtypeStruct(self._rows_i8.shape, jnp.int8),
                jax.ShapeDtypeStruct(self._row_scale.shape, jnp.float32),
                valid_s,
            )
            if rep is not None:
                shard_kw = dict(
                    in_shardings=(rep, self._row_sharding, self._scale_sharding, rep),
                    out_shardings=rep,
                )
        else:  # ivf / ivf_i8 / ivf_fused / ivf_fused_i8
            ivf = self._ivf
            if k > nprobe * ivf["cell_cap"]:
                raise ValueError(
                    f"k={k} exceeds the candidate pool nprobe*cell_cap="
                    f"{nprobe * ivf['cell_cap']}; raise nprobe"
                )
            cent_s = jax.ShapeDtypeStruct(ivf["centroids"].shape, jnp.float32)
            cells_s = jax.ShapeDtypeStruct((ivf["nlist"], ivf["cell_cap"]), jnp.int32)
            if mode == "ivf_fused" and self._fused_pallas:
                # Pallas lowering: scores come from per-cell DMA tiles
                # out of the cell-major row copy (an extra argument)
                interp = self._fused_interpret
                fn = lambda q, rows, cent, cells, cell_rows, valid: (
                    _ivf_topk_fused_pallas(
                        q, rows, cent, cells, cell_rows, valid,
                        k=k, nprobe=nprobe, interpret=interp,
                    )
                )
                args = (
                    q_s,
                    jax.ShapeDtypeStruct(self.rows.shape, self.rows.dtype),
                    cent_s, cells_s,
                    jax.ShapeDtypeStruct(
                        (ivf["nlist"], ivf["cell_cap"], self.dim), jnp.float32
                    ),
                    valid_s,
                )
                if rep is not None:
                    shard_kw = dict(
                        in_shardings=(rep, self._row_sharding, rep, rep, rep, rep),
                        out_shardings=rep,
                    )
            elif mode in ("ivf", "ivf_fused"):
                kernel = _ivf_topk_fused if mode == "ivf_fused" else _ivf_topk
                fn = lambda q, rows, cent, cells, valid: kernel(
                    q, rows, cent, cells, valid, k=k, nprobe=nprobe
                )
                args = (
                    q_s,
                    jax.ShapeDtypeStruct(self.rows.shape, self.rows.dtype),
                    cent_s, cells_s, valid_s,
                )
                if rep is not None:
                    shard_kw = dict(
                        in_shardings=(rep, self._row_sharding, rep, rep, rep),
                        out_shardings=rep,
                    )
            else:
                kernel = _ivf_topk_fused if mode == "ivf_fused_i8" else _ivf_topk
                fn = lambda q, r8, sc, cent, cells, valid: kernel(
                    q, r8, cent, cells, valid, k=k, nprobe=nprobe, row_scale=sc
                )
                args = (
                    q_s,
                    jax.ShapeDtypeStruct(self._rows_i8.shape, jnp.int8),
                    jax.ShapeDtypeStruct(self._row_scale.shape, jnp.float32),
                    cent_s, cells_s, valid_s,
                )
                if rep is not None:
                    shard_kw = dict(
                        in_shardings=(
                            rep, self._row_sharding, self._scale_sharding, rep, rep, rep,
                        ),
                        out_shardings=rep,
                    )
        compiled = jax.jit(fn, **shard_kw).lower(*args).compile()
        self.aot_compiles += 1
        self._compiled[(mode, m, k, nprobe)] = compiled
        return compiled

    def prepare(
        self,
        buckets: Sequence[int],
        k: int,
        nprobe: Optional[int] = None,
        modes: Sequence[str] = ("exact",),
    ) -> None:
        """AOT-compile the query for every padded bucket shape — one
        executable per (mode, m, k, nprobe); serve traffic then never
        traces. IVF modes need `train_ivf` first (nprobe defaults to the
        trained one), int8 modes `enable_int8`."""
        for mode in modes:
            np_eff = self._require(mode, nprobe)
            for m in buckets:
                if (mode, int(m), int(k), np_eff) not in self._compiled:
                    self._compile(int(m), int(k), mode, np_eff)

    def freeze(self) -> None:
        """End of warmup: any later unprepared shape raises
        IndexRecompileError instead of silently compiling."""
        self._frozen = True
        self._warm_compiles = self.aot_compiles

    @property
    def recompiles_after_warmup(self) -> int:
        if self._warm_compiles is None:
            return 0
        return self.aot_compiles - self._warm_compiles

    def query(
        self,
        queries,
        k: int,
        mode: str = "exact",
        nprobe: Optional[int] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(scores, indices), each (m, k), of the top-k valid rows per
        query. `m` must be a prepared bucket once frozen; `k` is capped
        by the caller to `count` if exact-rank semantics matter (indices
        past the fill level never appear — their scores are -inf-masked
        and top_k orders them last only when k > count). `mode` selects
        the tier: "exact" (the oracle), "ivf" (sub-linear probe scan,
        `nprobe` cells — defaults to the trained width), "ivf_fused"
        (the same scan as ONE kernel — running top-k over per-cell
        scores, no materialized candidate gather; Pallas cell-DMA
        lowering on real chips), and their int8 twins
        "exact_i8"/"ivf_i8"/"ivf_fused_i8"."""
        # deterministic tail injection for the request-trace waterfall's
        # index_query stage (slow@site=serve.index_query)
        faults.maybe_slow("serve.index_query")
        q = jnp.asarray(queries, jnp.float32)
        m, k = q.shape[0], int(k)
        np_eff = self._require(mode, nprobe)
        compiled = self._compiled.get((mode, m, k, np_eff))
        if compiled is None:
            compiled = self._compile(m, k, mode, np_eff)
        valid = jnp.int32(self.count)
        if mode == "exact":
            scores, idx = compiled(q, self.rows, valid)
        elif mode == "exact_i8":
            scores, idx = compiled(q, self._rows_i8, self._row_scale, valid)
        elif mode == "ivf_fused" and self._fused_pallas:
            scores, idx = compiled(
                q, self.rows, self._ivf["centroids"], self._ivf_device_cells(),
                self._ivf_device_cell_rows(), valid,
            )
        elif mode in ("ivf", "ivf_fused"):
            scores, idx = compiled(
                q, self.rows, self._ivf["centroids"], self._ivf_device_cells(), valid
            )
        else:  # ivf_i8 / ivf_fused_i8
            scores, idx = compiled(
                q, self._rows_i8, self._row_scale,
                self._ivf["centroids"], self._ivf_device_cells(), valid,
            )
        return np.asarray(scores), np.asarray(idx)


__all__ = [
    "DEFAULT_KMEANS_ITERS",
    "EmbeddingIndex",
    "IndexRecompileError",
    "QUERY_MODES",
    "fifo_write",
    "kmeans_fit",
    "topk_cosine",
]

