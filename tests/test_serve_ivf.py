"""IVF approximate-NN tier + int8 scoring (ISSUE 9): k-means coarse
quantizer, recall properties vs the exact oracle across fill levels /
shard widths / nprobe settings, freeze discipline per (m, k, nprobe),
incremental FIFO maintenance, engine int8 PTQ, batcher mode routing,
server wiring (mode knob, recall gauge, /ingest), schema validators."""

import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.ops.losses import l2_normalize
from moco_tpu.serve.index import (
    EmbeddingIndex,
    IndexRecompileError,
    kmeans_fit,
)

from tests.conftest import load_script


def _clustered(nc=16, per=32, dim=16, noise=0.2, seed=0):
    """Mixture-of-Gaussians rows on the sphere — the geometry trained
    dictionaries have; uniform rows give any ANN nothing to exploit."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(nc, dim)).astype(np.float32)
    rows = np.repeat(centers, per, axis=0) + noise * rng.normal(
        size=(nc * per, dim)
    ).astype(np.float32)
    rows = np.asarray(l2_normalize(jnp.asarray(rows)))
    order = rng.permutation(rows.shape[0])  # cells must be learned, not given
    return rows[order], centers


def _recall(approx_idx, oracle_idx, k):
    return float(np.mean([
        len(set(approx_idx[i, :k]) & set(oracle_idx[i, :k])) / k
        for i in range(oracle_idx.shape[0])
    ]))


def _queries(rows, m, seed=1, noise=0.05):
    rng = np.random.default_rng(seed)
    q = rows[rng.integers(0, rows.shape[0], m)] + noise * rng.normal(
        size=(m, rows.shape[1])
    ).astype(np.float32)
    return np.asarray(l2_normalize(jnp.asarray(q)))


# -- k-means coarse quantizer --------------------------------------------


def test_kmeans_quantizes_clustered_rows_tightly():
    """Lloyd converges to SOME good partition (local optima may split a
    true cluster and merge two others — that's fine for an IVF coarse
    quantizer): assert the quantization objective, not center recovery.
    Every row must sit in a tight cosine ball of its nearest centroid."""
    rows, _ = _clustered(nc=8, per=64, noise=0.05)
    init = np.asarray(kmeans_fit(jnp.asarray(rows), nlist=8, iters=0))
    cents = np.asarray(kmeans_fit(jnp.asarray(rows), nlist=8, iters=10))
    best = (rows @ cents.T).max(axis=1)
    assert best.mean() > (rows @ init.T).max(axis=1).mean(), "Lloyd didn't improve"
    assert best.mean() > 0.85, best.mean()
    assert best.min() > 0.6, best.min()
    np.testing.assert_allclose(np.linalg.norm(cents, axis=1), 1.0, rtol=1e-5)


def test_kmeans_rejects_nlist_above_rows():
    with pytest.raises(ValueError, match="training rows"):
        kmeans_fit(jnp.zeros((4, 8)), nlist=8)


def test_kmeans_deterministic():
    rows, _ = _clustered(nc=4, per=16)
    a = np.asarray(kmeans_fit(jnp.asarray(rows), nlist=4, iters=5))
    b = np.asarray(kmeans_fit(jnp.asarray(rows), nlist=4, iters=5))
    np.testing.assert_array_equal(a, b)


# -- recall properties vs the exact oracle -------------------------------


@pytest.mark.parametrize("fill", [0.25, 0.6, 1.0])
@pytest.mark.parametrize("nprobe", [4, 8])
def test_ivf_recall_floor_across_fills_and_nprobe(fill, nprobe):
    """The acceptance property: recall@k >= 0.95 vs the exact oracle,
    across fill levels and probe widths (clustered dictionary)."""
    rows, _ = _clustered(nc=16, per=32)
    idx = EmbeddingIndex(rows.shape[0], rows.shape[1])
    n = int(rows.shape[0] * fill)
    idx.snapshot(rows[:n])
    idx.train_ivf(nlist=16, nprobe=nprobe)
    q = _queries(rows[:n], 12)
    _, exact = idx.query(q, 10)
    _, ivf = idx.query(q, 10, mode="ivf")
    assert _recall(ivf, exact, 10) >= 0.95
    assert (ivf < max(n, 10)).all() or n >= 10  # never a junk row


def test_ivf_full_probe_matches_exact():
    """nprobe == nlist with no spill scans every cell: the IVF top-k SET
    equals the exact top-k (scores allclose; order ties aside)."""
    rows, _ = _clustered(nc=8, per=16, dim=8)
    idx = EmbeddingIndex(rows.shape[0], rows.shape[1])
    idx.snapshot(rows)
    stats = idx.train_ivf(nlist=8, nprobe=8)
    assert stats["spilled"] == 0
    q = _queries(rows, 6)
    se, ie = idx.query(q, 5)
    si, ii = idx.query(q, 5, mode="ivf")
    for r in range(q.shape[0]):
        assert set(ie[r]) == set(ii[r])
    np.testing.assert_allclose(np.sort(se, 1), np.sort(si, 1), rtol=1e-5, atol=1e-6)


def test_ivf_sharded_matches_single_device():
    from moco_tpu.parallel import create_mesh

    rows, _ = _clustered(nc=8, per=32, dim=16)
    q = _queries(rows, 8)
    plain = EmbeddingIndex(rows.shape[0], 16)
    plain.snapshot(rows)
    plain.train_ivf(nlist=8, nprobe=4)
    mesh = create_mesh()
    sharded = EmbeddingIndex(rows.shape[0], 16, mesh=mesh)
    sharded.snapshot(rows)
    sharded.train_ivf(nlist=8, nprobe=4)
    s1, i1 = plain.query(q, 5, mode="ivf")
    s2, i2 = sharded.query(q, 5, mode="ivf")
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-6)


# -- int8 scoring path ---------------------------------------------------


def test_int8_exact_scores_within_rescale_bounds():
    """Symmetric per-row int8 + f32 rescale: scores within the analytic
    quantization bound of the f32 oracle (|err| <~ 2*sqrt(d)/127 for
    unit rows; empirically far tighter), and int8-IVF recall vs the
    int8-exact oracle stays at the floor (the IVF mechanism itself
    loses nothing extra in int8)."""
    rows, _ = _clustered(nc=16, per=32)
    idx = EmbeddingIndex(rows.shape[0], rows.shape[1])
    idx.snapshot(rows)
    idx.train_ivf(nlist=16, nprobe=8)
    idx.enable_int8()
    q = _queries(rows, 12)
    se, _ = idx.query(q, 10)
    s8, i8e = idx.query(q, 10, mode="exact_i8")
    assert np.abs(s8 - se).max() < 0.02, "int8 rescale error out of bounds"
    _, i8v = idx.query(q, 10, mode="ivf_i8")
    assert _recall(i8v, i8e, 10) >= 0.95


def test_int8_mirror_follows_fifo_ingest():
    rows, _ = _clustered(nc=4, per=16, dim=8)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    idx.enable_int8()
    fresh = _queries(rows, 8, seed=9, noise=0.3)
    idx.add(fresh)
    s, i = idx.query(fresh[:4], 1, mode="exact_i8")
    # the freshly written (requantized-on-device) rows are their own
    # nearest neighbors at the head
    np.testing.assert_array_equal(i[:, 0], np.arange(4))
    assert (s[:, 0] > 0.99).all()


# -- freeze discipline per (m, k, nprobe) --------------------------------


def test_frozen_rejects_unprepared_m_k_nprobe_and_mode():
    rows, _ = _clustered(nc=4, per=16, dim=8)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    idx.train_ivf(nlist=4, nprobe=2)
    idx.enable_int8()
    idx.prepare([4], k=3, nprobe=2, modes=("exact", "ivf"))
    idx.freeze()
    q = _queries(rows, 4)
    idx.query(q, 3)  # prepared
    idx.query(q, 3, mode="ivf", nprobe=2)  # prepared
    for bad in (
        lambda: idx.query(q[:3], 3, mode="ivf", nprobe=2),  # unprepared m
        lambda: idx.query(q, 2, mode="ivf", nprobe=2),  # unprepared k
        lambda: idx.query(q, 3, mode="ivf", nprobe=3),  # unprepared nprobe
        lambda: idx.query(q, 3, mode="ivf_i8", nprobe=2),  # unprepared mode
    ):
        with pytest.raises(IndexRecompileError):
            bad()
    assert idx.recompiles_after_warmup == 0


def test_ivf_modes_require_training_and_int8():
    idx = EmbeddingIndex(16, 8)
    idx.snapshot(np.eye(8, dtype=np.float32))
    with pytest.raises(ValueError, match="train_ivf"):
        idx.query(np.eye(8, dtype=np.float32)[:2], 2, mode="ivf")
    with pytest.raises(ValueError, match="enable_int8"):
        idx.query(np.eye(8, dtype=np.float32)[:2], 2, mode="exact_i8")
    with pytest.raises(ValueError, match="unknown query mode"):
        idx.query(np.eye(8, dtype=np.float32)[:2], 2, mode="cosine")


def test_k_exceeding_candidate_pool_rejected():
    rows, _ = _clustered(nc=4, per=4, dim=8, noise=0.05)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    idx.train_ivf(nlist=4, cell_cap=8, nprobe=1)
    with pytest.raises(ValueError, match="candidate pool"):
        idx.query(_queries(rows, 2), 9, mode="ivf", nprobe=1)


# -- incremental FIFO maintenance ----------------------------------------


def test_ivf_cells_follow_fifo_eviction_and_ingest():
    """After FIFO blocks overwrite old rows, IVF queries find the fresh
    rows and never surface evicted content; cell bookkeeping stays
    consistent (every valid row in exactly one cell or spilled)."""
    rows, centers = _clustered(nc=8, per=16, dim=16, noise=0.1)
    idx = EmbeddingIndex(rows.shape[0], 16)
    idx.snapshot(rows)
    idx.train_ivf(nlist=8, nprobe=8)  # full probe: IVF == exact reachability
    for seed in (3, 4, 5):
        fresh = _queries(rows, 32, seed=seed, noise=0.4)
        idx.add(fresh)
        s, i = idx.query(fresh[:8], 1, mode="ivf")
        start = (idx._ptr - 32) % idx.capacity
        np.testing.assert_array_equal(
            i[:, 0], (start + np.arange(8)) % idx.capacity
        )
        assert (s[:, 0] > 0.999).all()
    ivf = idx._ivf
    in_cells = sorted(x for x in ivf["cells"].flatten() if x < idx.capacity)
    assert len(in_cells) == len(set(in_cells)), "row in two cells"
    assert len(in_cells) + ivf["spilled"] == idx.count
    counts_from_table = (ivf["cells"] < idx.capacity).sum(axis=1)
    np.testing.assert_array_equal(counts_from_table, ivf["counts"])


def test_ivf_add_with_wrap_keeps_recall():
    rows, _ = _clustered(nc=4, per=16, dim=8)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    idx.train_ivf(nlist=4, nprobe=4)
    idx._ptr = idx.capacity - 3  # force the wrap split on the next add
    fresh = _queries(rows, 8, seed=7, noise=0.3)
    idx.add(fresh)
    _, exact = idx.query(fresh, 5)
    _, ivf = idx.query(fresh, 5, mode="ivf")
    assert _recall(ivf, exact, 5) >= 0.95


def test_snapshot_invalidates_trained_ivf():
    rows, _ = _clustered(nc=4, per=8, dim=8)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    idx.train_ivf(nlist=4)
    idx.snapshot(rows[::-1])  # bulk reload: cells are content-derived
    assert idx._ivf is None
    with pytest.raises(ValueError, match="train_ivf"):
        idx.query(rows[:2], 2, mode="ivf")


def test_sharded_add_keeps_sharding_without_host_copy():
    """Satellite 1: the donated jitted fifo_write keeps the P(data)
    sharding in place across add() — no re-shard, same results as the
    single-device index."""
    from moco_tpu.parallel import create_mesh

    mesh = create_mesh()
    rows, _ = _clustered(nc=4, per=16, dim=8)
    sharded = EmbeddingIndex(rows.shape[0], 8, mesh=mesh)
    plain = EmbeddingIndex(rows.shape[0], 8)
    for idx in (sharded, plain):
        idx.snapshot(rows[:32])
    want = sharded.rows.sharding
    fresh = _queries(rows, 16, seed=11)
    for idx in (sharded, plain):
        idx.add(fresh)
    assert sharded.rows.sharding.is_equivalent_to(want, sharded.rows.ndim)
    np.testing.assert_array_equal(np.asarray(sharded.rows), np.asarray(plain.rows))
    s1, i1 = sharded.query(fresh[:4], 3)
    s2, i2 = plain.query(fresh[:4], 3)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(s1, s2, rtol=1e-6, atol=1e-6)


# -- engine int8 PTQ ------------------------------------------------------


def test_quantize_params_roundtrip_bounds():
    from moco_tpu.serve.engine import dequantize_params, quantize_params_int8

    rng = np.random.default_rng(0)
    params = {
        "conv": {"kernel": jnp.asarray(rng.normal(size=(3, 3, 8, 16)), jnp.float32)},
        "dense": {
            "kernel": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32),
            "bias": jnp.asarray(rng.normal(size=(4,)), jnp.float32),
        },
    }
    q, s = quantize_params_int8(params)
    assert q["conv"]["kernel"].dtype == jnp.int8
    assert q["dense"]["kernel"].dtype == jnp.int8
    assert q["dense"]["bias"].dtype == jnp.float32  # 1-D: passes through
    deq = dequantize_params(q, s)
    for path in (("conv", "kernel"), ("dense", "kernel")):
        a = params[path[0]][path[1]]
        b = deq[path[0]][path[1]]
        # symmetric per-output-channel: |err| <= scale/2 = max|w|/254
        bound = np.abs(np.asarray(a)).max(axis=tuple(range(a.ndim - 1))) / 254.0
        assert (np.abs(np.asarray(a - b)) <= bound[None] + 1e-7).all()
    np.testing.assert_array_equal(deq["dense"]["bias"], params["dense"]["bias"])


@pytest.mark.slow
def test_engine_int8_ptq_embeddings_close_and_no_recompiles():
    from moco_tpu.core import build_encoder
    from moco_tpu.serve.engine import InferenceEngine
    from moco_tpu.utils.config import MocoConfig

    cfg = MocoConfig(
        arch="resnet18", dim=16, mlp=True, cifar_stem=True,
        shuffle="none", compute_dtype="float32",
    )
    enc = build_encoder(cfg)
    v = enc.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False)
    kwargs = dict(image_size=32, buckets=(1, 4))
    f32 = InferenceEngine(enc, v["params"], v.get("batch_stats", {}), **kwargs)
    i8 = InferenceEngine(
        enc, v["params"], v.get("batch_stats", {}), int8=True, **kwargs
    )
    assert i8.int8
    for e in (f32, i8):
        e.warmup()
    imgs = np.random.default_rng(0).integers(0, 255, (4, 32, 32, 3), np.uint8)
    a, _ = f32.embed(imgs)
    b, executed = i8.embed(imgs)
    assert executed == [(4, 4)]
    # weight-only PTQ keeps the representation: near-unit cosine per row
    cos = np.sum(a * b, axis=1)
    assert (cos > 0.99).all(), cos
    np.testing.assert_allclose(np.linalg.norm(b, axis=1), 1.0, rtol=1e-5)
    assert i8.recompiles_after_warmup == 0
    # the at-rest quantized tree really is int8 (the seam's memory win)
    leaves = jax.tree.leaves(i8._qparams)
    i8_bytes = sum(x.nbytes for x in leaves if x.dtype == jnp.int8)
    f32_bytes = sum(x.nbytes for x in jax.tree.leaves(v["params"]))
    assert i8_bytes > 0 and i8_bytes < f32_bytes / 3


# -- batcher mode routing -------------------------------------------------


def test_batcher_passes_modes_to_three_arg_run_batch():
    from moco_tpu.serve.batcher import ContinuousBatcher

    seen = []

    def run_batch(images, want_neighbors, modes):
        seen.append((want_neighbors, modes))
        n = images.shape[0]
        return {"embedding": np.zeros((n, 2), np.float32)}, [(n, n)]

    b = ContinuousBatcher(run_batch, max_batch=4, slo_ms=200)
    try:
        futs = [
            b.submit(np.zeros((1, 4, 4, 3), np.uint8), want_neighbors=True, mode="ivf"),
            b.submit(np.zeros((1, 4, 4, 3), np.uint8), want_neighbors=True),
            b.submit(np.zeros((2, 4, 4, 3), np.uint8), want_neighbors=True, mode="exact"),
        ]
        for f in futs:
            f.result(10)
    finally:
        b.close()
    assert seen and seen[0][0] is True
    assert seen[0][1] == ("exact", "ivf")  # None-mode rider adds nothing


def test_batcher_two_arg_run_batch_still_supported():
    from moco_tpu.serve.batcher import ContinuousBatcher

    def legacy(images, want_neighbors):
        return {"embedding": np.zeros((images.shape[0], 2), np.float32)}, [(1, 1)]

    b = ContinuousBatcher(legacy, max_batch=2, slo_ms=100)
    try:
        out = b.submit(np.zeros((1, 4, 4, 3), np.uint8), mode="ivf").result(10)
        assert out["embedding"].shape == (1, 2)
    finally:
        b.close()


def test_serve_metrics_recall_gauge():
    from moco_tpu.obs import schema
    from moco_tpu.serve.batcher import ServeMetrics

    m = ServeMetrics(slo_ms=100)
    rec = {"step": 1, "time": time.time(), **m.payload()}
    assert rec["serve/recall_estimate"] is None
    assert schema.validate_line(rec) == []
    m.record_recall(1.0)
    m.record_recall(0.9)
    assert abs(m.payload()["serve/recall_estimate"] - 0.95) < 1e-9


# -- schema validators ----------------------------------------------------


def test_schema_serving_tier_validators():
    from moco_tpu.obs import schema

    base = {"step": 1, "time": 0.0}
    good = dict(base, **{
        "serve/recall_estimate": 0.97, "serve/nprobe": 8,
        "serve/int8": 0, "serve/ingested_rows": 128,
    })
    assert schema.validate_line(good) == []
    assert schema.validate_line(dict(base, **{"serve/recall_estimate": 1.5}))
    assert schema.validate_line(dict(base, **{"serve/recall_estimate": -0.1}))
    assert schema.validate_line(dict(base, **{"serve/nprobe": 0}))
    assert schema.validate_line(dict(base, **{"serve/nprobe": 2.5}))
    assert schema.validate_line(dict(base, **{"serve/int8": 2}))
    assert schema.validate_line(dict(base, **{"serve/ingested_rows": None}))
    # nulls allowed where the gauge is dormant
    assert schema.validate_line(dict(base, **{
        "serve/recall_estimate": None, "serve/nprobe": None, "serve/int8": 1,
    })) == []


# -- serve_ingest ---------------------------------------------------------


def test_serve_ingest_fresh_rows_diff():
    si = load_script("serve_ingest.py")
    q = np.arange(8)[:, None] * np.ones((8, 2), np.float32)
    # first sighting: whole queue, oldest-first from the head
    np.testing.assert_array_equal(
        si.fresh_rows(q, None, 3)[:, 0], [3, 4, 5, 6, 7, 0, 1, 2]
    )
    np.testing.assert_array_equal(si.fresh_rows(q, 2, 5)[:, 0], [2, 3, 4])
    np.testing.assert_array_equal(si.fresh_rows(q, 6, 2)[:, 0], [6, 7, 0, 1])
    assert si.fresh_rows(q, 4, 4).shape[0] == 0


# -- fused gather-scan tier (ISSUE 11) -----------------------------------


@pytest.mark.parametrize("fill", [0.25, 0.6, 1.0])
@pytest.mark.parametrize("nprobe", [4, 8])
def test_fused_matches_composed_across_fills_and_nprobe(fill, nprobe):
    """The fused oracle property: identical top-k ids (same candidate
    set by construction — distinct probes, one cell per row) and
    allclose scores vs the composed scan, across fill levels and probe
    widths on ties-free clustered data."""
    rows, _ = _clustered(nc=16, per=32)
    idx = EmbeddingIndex(rows.shape[0], rows.shape[1])
    n = int(rows.shape[0] * fill)
    idx.snapshot(rows[:n])
    idx.train_ivf(nlist=16, nprobe=nprobe)
    q = _queries(rows[:n], 12)
    sc, ic = idx.query(q, 10, mode="ivf")
    sf, i_f = idx.query(q, 10, mode="ivf_fused")
    np.testing.assert_array_equal(ic, i_f)
    finite = np.isfinite(sc)
    np.testing.assert_allclose(sf[finite], sc[finite], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.isfinite(sf), finite)


def test_fused_int8_matches_composed_int8():
    rows, _ = _clustered(nc=16, per=32)
    idx = EmbeddingIndex(rows.shape[0], rows.shape[1])
    idx.snapshot(rows)
    idx.train_ivf(nlist=16, nprobe=6)
    idx.enable_int8()
    q = _queries(rows, 10)
    sc, ic = idx.query(q, 8, mode="ivf_i8")
    sf, i_f = idx.query(q, 8, mode="ivf_fused_i8")
    np.testing.assert_array_equal(ic, i_f)
    np.testing.assert_allclose(sf, sc, rtol=1e-5, atol=1e-5)


def test_fused_sharded_matches_single_device():
    """Shard-width property: the fused scan over P(data)-sharded rows
    returns exactly the single-device result (same discipline as the
    composed-scan sharding test above)."""
    from moco_tpu.parallel import create_mesh

    rows, _ = _clustered(nc=8, per=32, dim=16)
    q = _queries(rows, 8)
    plain = EmbeddingIndex(rows.shape[0], 16)
    plain.snapshot(rows)
    plain.train_ivf(nlist=8, nprobe=4)
    mesh = create_mesh()
    sharded = EmbeddingIndex(rows.shape[0], 16, mesh=mesh)
    sharded.snapshot(rows)
    sharded.train_ivf(nlist=8, nprobe=4)
    s1, i1 = plain.query(q, 5, mode="ivf_fused")
    s2, i2 = sharded.query(q, 5, mode="ivf_fused")
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-6)
    # and the fused result equals the composed result on the mesh too
    s3, i3 = sharded.query(q, 5, mode="ivf")
    np.testing.assert_array_equal(i2, i3)


def test_fused_pallas_interpret_matches_composed(monkeypatch):
    """The Pallas cell-DMA lowering (scalar-prefetched cell tiles from
    the cell-major row copy) in interpret mode returns the composed
    scan's exact ids — the equivalence CI can check without a chip."""
    monkeypatch.setenv("MOCO_IVF_PALLAS", "interpret")
    rows, _ = _clustered(nc=8, per=32, dim=16)
    q = _queries(rows, 6)
    idx = EmbeddingIndex(rows.shape[0], 16)
    assert idx._fused_pallas and idx._fused_interpret
    idx.snapshot(rows)
    idx.train_ivf(nlist=8, nprobe=4)
    sc, ic = idx.query(q, 5, mode="ivf")
    sf, i_f = idx.query(q, 5, mode="ivf_fused")
    np.testing.assert_array_equal(ic, i_f)
    np.testing.assert_allclose(sf, sc, rtol=1e-5, atol=1e-6)


def test_fused_follows_fifo_ingest():
    """Incremental maintenance parity: after FIFO writes re-home cells,
    the fused scan still mirrors the composed scan (the cell-major
    Pallas copy is also invalidated — covered via the dirty flag)."""
    rows, _ = _clustered(nc=8, per=16, dim=8)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    idx.train_ivf(nlist=8, nprobe=4)
    fresh = _queries(rows, 16, seed=9, noise=0.3)
    idx.add(fresh)
    q = _queries(rows, 8, seed=10)
    sc, ic = idx.query(q, 5, mode="ivf")
    sf, i_f = idx.query(q, 5, mode="ivf_fused")
    np.testing.assert_array_equal(ic, i_f)
    np.testing.assert_allclose(sf, sc, rtol=1e-5, atol=1e-6)


def test_frozen_rejects_unprepared_fused_modes():
    rows, _ = _clustered(nc=4, per=16, dim=8)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    idx.train_ivf(nlist=4, nprobe=2)
    idx.enable_int8()
    idx.prepare([4], k=3, modes=("ivf_fused",))
    idx.freeze()
    q = _queries(rows, 4)
    idx.query(q, 3, mode="ivf_fused")  # prepared: fine
    assert idx.recompiles_after_warmup == 0
    with pytest.raises(IndexRecompileError):
        idx.query(q[:2], 3, mode="ivf_fused")  # unprepared m
    with pytest.raises(IndexRecompileError):
        idx.query(q, 3, mode="ivf_fused_i8")  # unprepared quantized twin


def test_ivf_stats_occupancy_gauge():
    rows, _ = _clustered(nc=8, per=16, dim=8)
    idx = EmbeddingIndex(rows.shape[0], 8)
    idx.snapshot(rows)
    stats = idx.train_ivf(nlist=8, nprobe=4)
    assert 0.0 < stats["occupancy"] <= 1.0
    assert stats["occupancy"] == pytest.approx(
        stats["cell_count_mean"] / stats["cell_cap"]
    )


def test_batcher_mode_counts_surface():
    """serve/mode_<tier> counts: explicit riders under their tier,
    default-mode riders under "default"."""
    from moco_tpu.serve.batcher import ContinuousBatcher

    def run_batch(images, want_neighbors, modes=()):
        return {"embedding": np.zeros((images.shape[0], 4), np.float32)}, [
            (images.shape[0], images.shape[0])
        ]

    b = ContinuousBatcher(run_batch, max_batch=8, slo_ms=50.0)
    try:
        imgs = np.zeros((1, 4, 4, 3), np.uint8)
        futs = [b.submit(imgs, want_neighbors=True, mode="ivf_fused")
                for _ in range(3)]
        futs += [b.submit(imgs, want_neighbors=True) for _ in range(2)]
        for f in futs:
            f.result(timeout=10.0)
    finally:
        b.close()
    payload = b.metrics.payload()
    assert payload["serve/mode_ivf_fused"] == 3
    assert payload["serve/mode_default"] == 2
