"""Serving subsystem: index kernels (+ bitwise equivalence with the
pre-refactor queue/kNN paths), AOT engine, continuous batcher, HTTP
server, schema/port satellites, and the perf-ledger serving series."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.ops.losses import l2_normalize
from moco_tpu.serve.batcher import BatcherClosedError, ContinuousBatcher, ServeMetrics
from moco_tpu.serve.index import (
    EmbeddingIndex,
    IndexRecompileError,
    fifo_write,
    topk_cosine,
)

from tests.conftest import load_script


# -- shared kernels: bitwise equivalence with the pre-refactor paths ----


def _old_enqueue(queue, ptr, keys):
    """core/queue.py's enqueue body as it was before the serve refactor
    (PR 7 state) — the oracle the shared kernel must match bitwise."""
    num_neg = queue.shape[0]
    keys = jax.lax.stop_gradient(keys).astype(queue.dtype)
    queue = jax.lax.dynamic_update_slice(queue, keys, (ptr, jnp.zeros_like(ptr)))
    new_ptr = (ptr + keys.shape[0]) % num_neg
    return queue, new_ptr


def _old_knn_scan(q, bank, k):
    """knn.py's inline cosine top-k as it was before the refactor."""
    sims = q @ bank.T
    return jax.lax.top_k(sims, k)


@pytest.mark.parametrize("ptr", [0, 8, 56])
def test_fifo_write_bitwise_matches_pre_refactor(ptr):
    from moco_tpu.core.queue import enqueue, init_queue

    queue = init_queue(jax.random.PRNGKey(0), 64, 16)
    keys = jax.random.normal(jax.random.PRNGKey(1), (8, 16), jnp.float32)
    new, np_new = enqueue(queue, jnp.int32(ptr), keys)
    old, np_old = _old_enqueue(queue, jnp.int32(ptr), keys)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    assert int(np_new) == int(np_old)
    # and under jit (the in-step context), still bitwise
    new_j, _ = jax.jit(fifo_write)(queue, jnp.int32(ptr), keys)
    np.testing.assert_array_equal(np.asarray(new_j), np.asarray(old))


def test_topk_cosine_bitwise_matches_pre_refactor_knn_scan():
    rng = np.random.default_rng(0)
    bank = np.asarray(l2_normalize(jnp.asarray(rng.normal(size=(128, 32)), jnp.float32)))
    q = np.asarray(l2_normalize(jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)))
    s_new, i_new = jax.jit(lambda q, b: topk_cosine(q, b, 10))(q, bank)
    s_old, i_old = jax.jit(lambda q, b: _old_knn_scan(q, b, 10))(q, bank)
    np.testing.assert_array_equal(np.asarray(s_new), np.asarray(s_old))
    np.testing.assert_array_equal(np.asarray(i_new), np.asarray(i_old))


def test_knn_classify_unchanged_by_rehost():
    """knn_classify on the shared kernel == the inline pre-refactor
    classifier, bitwise on the predictions."""
    from moco_tpu.knn import knn_classify

    rng = np.random.default_rng(1)
    bank = np.asarray(l2_normalize(jnp.asarray(rng.normal(size=(200, 16)), jnp.float32)))
    bank_y = rng.integers(0, 4, 200)
    q = np.asarray(l2_normalize(jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)))
    preds = knn_classify(bank, bank_y, q, num_classes=4, k=20)

    bank_j, labels_j = jnp.asarray(bank), jnp.asarray(bank_y)

    @jax.jit
    def old_classify(qb):
        top_sims, top_idx = _old_knn_scan(qb, bank_j, 20)
        weights = jnp.exp(top_sims / 0.07)
        votes = jax.nn.one_hot(labels_j[top_idx], 4)
        return jnp.argmax(jnp.einsum("mk,mkc->mc", weights, votes), axis=-1)

    np.testing.assert_array_equal(preds, np.asarray(old_classify(jnp.asarray(q))))


@pytest.mark.slow
def test_train_step_trajectory_bit_identical_after_rehost():
    """The acceptance bullet, executable: a train run whose queue update
    goes through the rehosted kernel is BIT-identical (queue, ptr,
    params, loss) to the same run with the pre-refactor inline enqueue
    monkeypatched back in."""
    from moco_tpu.core import moco as moco_mod
    from moco_tpu.core.moco import build_encoder, create_state, make_train_step, place_state
    from moco_tpu.parallel import create_mesh, shard_batch
    from moco_tpu.utils.config import DataConfig, MocoConfig, OptimConfig, TrainConfig
    from moco_tpu.utils.schedules import build_optimizer

    config = TrainConfig(
        moco=MocoConfig(
            arch="resnet18", dim=16, num_negatives=64, mlp=True,
            shuffle="gather_perm", cifar_stem=True, compute_dtype="float32",
        ),
        optim=OptimConfig(lr=0.03, epochs=1),
        data=DataConfig(dataset="synthetic", image_size=16, global_batch=16),
    )
    mesh = create_mesh()
    encoder = build_encoder(config.moco, num_data=mesh.shape["data"])
    tx = build_optimizer(config.optim, steps_per_epoch=2)
    rng = jax.random.PRNGKey(0)
    ims = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 16, 3), jnp.float32)
    batch = shard_batch(mesh, {"im_q": ims[0], "im_k": ims[1]})
    root = jax.device_put(
        jax.random.PRNGKey(2),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )

    def run(enqueue_impl):
        orig = moco_mod.enqueue
        moco_mod.enqueue = enqueue_impl
        try:
            state = create_state(
                rng, config, encoder, tx, jnp.zeros((1, 16, 16, 3), jnp.float32)
            )
            state = place_state(state, mesh)
            step = make_train_step(config, encoder, tx, mesh)
            for _ in range(2):
                state, metrics = step(state, batch, root)
            return jax.device_get(state), float(metrics["loss"])
        finally:
            moco_mod.enqueue = orig

    state_new, loss_new = run(moco_mod.enqueue)
    state_old, loss_old = run(_old_enqueue)
    assert loss_new == loss_old
    np.testing.assert_array_equal(np.asarray(state_new.queue), np.asarray(state_old.queue))
    assert int(state_new.queue_ptr) == int(state_old.queue_ptr)
    for a, b in zip(jax.tree.leaves(state_new.params_q), jax.tree.leaves(state_old.params_q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- EmbeddingIndex ------------------------------------------------------


def _clusters(num_clusters=4, per=50, dim=32, noise=0.05, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(num_clusters, dim)).astype(np.float32) * 4
    rows = np.concatenate(
        [centers[i] + rng.normal(0, noise, (per, dim)).astype(np.float32)
         for i in range(num_clusters)]
    )
    labels = np.repeat(np.arange(num_clusters), per)
    rows = np.asarray(l2_normalize(jnp.asarray(rows)))
    return rows, labels, centers


def test_index_recall_at_k_on_clustered_data():
    """Every query's top-k must come from its own cluster (well-separated
    synthetic clusters -> exact scan recall@k should be 1.0)."""
    rows, labels, centers = _clusters()
    idx = EmbeddingIndex(rows.shape[0], rows.shape[1])
    idx.snapshot(rows)
    queries = np.asarray(l2_normalize(jnp.asarray(centers)))
    scores, nbr = idx.query(queries, 10)
    for c in range(len(centers)):
        assert (labels[nbr[c]] == c).all(), f"cluster {c} recall@10 < 1"
        assert (np.diff(scores[c]) <= 1e-6).all(), "scores not sorted"


def test_index_fifo_eviction_order():
    idx = EmbeddingIndex(8, 4)
    blocks = [np.full((4, 4), float(i + 1), np.float32) for i in range(3)]
    for b in blocks:
        idx.add(np.asarray(l2_normalize(jnp.asarray(b))))
    # capacity 8, three blocks of 4: block 0 evicted, 2 and 1 resident
    rows = np.asarray(idx.rows)
    np.testing.assert_allclose(rows[:4], np.asarray(l2_normalize(jnp.asarray(blocks[2]))))
    np.testing.assert_allclose(rows[4:], np.asarray(l2_normalize(jnp.asarray(blocks[1]))))
    assert idx.count == 8


def test_index_valid_count_masks_unfilled_rows():
    rows, _, _ = _clusters(num_clusters=2, per=8)
    idx = EmbeddingIndex(64, rows.shape[1])
    idx.snapshot(rows[:4])
    scores, nbr = idx.query(rows[:2], 4)
    assert (nbr < 4).all(), "query surfaced an unfilled row"
    scores_full, _ = idx.query(rows[:2], 8)
    assert (scores_full[:, 4:] == -np.inf).all(), "unfilled rows not masked"


def _assert_same_neighbours(s1, i1, s2, i2):
    """What two separately compiled scans of the same rows guarantee on
    jax 0.9.0. The sharded and the single-device program sum the
    16-term dot products in different orders, so a score may differ in
    its last bit (measured: at most 1 ULP, 6e-8 at these cosines); two
    neighbours whose scores sit that close may therefore come back in
    either order (measured: ids 90 and 51, one ULP apart in one program
    and exactly tied in the other). So: scores equal within 1 ULP, and
    ids equal as SETS over every run of scores tied within 2 ULP."""
    ulp = float(np.spacing(np.float32(0.5)))  # cosines in (0.5, 1]: 2**-24
    np.testing.assert_allclose(s1, s2, rtol=0, atol=ulp)
    k = i1.shape[1]
    for r in range(i1.shape[0]):
        start = 0
        for j in range(1, k + 1):
            if j == k or s1[r, j - 1] - s1[r, j] > 2 * ulp:
                assert set(i1[r, start:j]) == set(i2[r, start:j]), (r, i1[r], i2[r])
                start = j


def test_index_sharded_matches_single_device():
    from moco_tpu.parallel import create_mesh

    rows, _, centers = _clusters(dim=16)
    queries = np.asarray(l2_normalize(jnp.asarray(centers)))
    plain = EmbeddingIndex(rows.shape[0], 16)
    plain.snapshot(rows)
    mesh = create_mesh()
    sharded = EmbeddingIndex(rows.shape[0], 16, mesh=mesh)
    sharded.snapshot(rows)
    assert sharded.capacity % mesh.shape["data"] == 0
    s1, i1 = plain.query(queries, 5)
    s2, i2 = sharded.query(queries, 5)
    _assert_same_neighbours(s1, i1, s2, i2)


def test_index_frozen_rejects_unprepared_shape():
    idx = EmbeddingIndex(16, 8)
    idx.snapshot(np.eye(8, dtype=np.float32))
    idx.prepare([4], k=2)
    idx.freeze()
    idx.query(np.eye(8, dtype=np.float32)[:4], 2)  # prepared: fine
    with pytest.raises(IndexRecompileError):
        idx.query(np.eye(8, dtype=np.float32)[:3], 2)
    assert idx.recompiles_after_warmup == 0


def test_index_from_train_queue_roundtrip():
    from moco_tpu.core.queue import init_queue

    queue = init_queue(jax.random.PRNGKey(3), 32, 8)
    idx = EmbeddingIndex.from_train_queue(np.asarray(queue), queue_ptr=16)
    assert idx.count == 32 and idx.capacity == 32 and idx._ptr == 16
    q = np.asarray(queue)[:2]
    scores, nbr = idx.query(q, 1)
    np.testing.assert_array_equal(nbr[:, 0], [0, 1])
    np.testing.assert_allclose(scores[:, 0], 1.0, rtol=1e-5)


def test_index_add_wrap_splits_at_capacity_boundary():
    """Serving ingest takes arbitrary block sizes: a block crossing the
    capacity boundary splits into two no-wrap writes (training keeps its
    K % N == 0 invariant and never wraps)."""
    idx = EmbeddingIndex(8, 4)
    blocks = [
        np.asarray(l2_normalize(jnp.full((3, 4), float(i + 1), jnp.float32)))
        for i in range(3)
    ]
    for b in blocks:
        idx.add(b)
    # 9 rows through capacity 8: head wrapped to 0 and row 0 holds the
    # last row of block 2; rows 3..5 hold block 1, 6..7 block 2's head
    rows = np.asarray(idx.rows)
    np.testing.assert_allclose(rows[0], blocks[2][2])
    np.testing.assert_allclose(rows[3:6], blocks[1])
    np.testing.assert_allclose(rows[6:8], blocks[2][:2])
    assert idx.count == 8 and idx._ptr == 1
    with pytest.raises(ValueError, match="exceeds capacity"):
        idx.add(np.zeros((9, 4), np.float32))


# -- engine + server (shared fixture: AOT compiles are the slow part) ---

IMG = 32  # NB not 16: XLA:CPU's tiny-spatial-dim conv path is ~10x slower


@pytest.fixture(scope="module")
def toy_engine():
    from moco_tpu.core import build_encoder
    from moco_tpu.serve.engine import InferenceEngine
    from moco_tpu.utils.config import MocoConfig

    cfg = MocoConfig(
        arch="resnet18", dim=16, mlp=True, cifar_stem=True,
        shuffle="none", compute_dtype="float32",
    )
    enc = build_encoder(cfg)
    v = enc.init(jax.random.PRNGKey(0), jnp.zeros((1, IMG, IMG, 3)), train=False)
    engine = InferenceEngine(
        enc, v["params"], v.get("batch_stats", {}), image_size=IMG, buckets=(1, 4, 8)
    )
    engine.warmup()
    return engine


def test_engine_padding_never_leaks(toy_engine):
    """Padding rows must not contaminate valid rows: within ONE bucket
    program, the same images embed bitwise-identically at any occupancy
    (pad contents differ, results must not). Across buckets the
    programs differ (XLA fuses per batch size), so only allclose."""
    imgs = np.random.default_rng(0).integers(0, 255, (8, IMG, IMG, 3), np.uint8)
    full, _ = toy_engine.embed(imgs)  # bucket 8, occupancy 8/8
    for n in (5, 7):  # bucket 8 at partial occupancy: bitwise
        part, executed = toy_engine.embed(imgs[:n])
        assert executed == [(8, n)]
        np.testing.assert_array_equal(part, full[:n])
    p2, ex2 = toy_engine.embed(imgs[:2])  # bucket 4 vs bucket 4
    p3, ex3 = toy_engine.embed(imgs[:3])
    assert ex2 == [(4, 2)] and ex3 == [(4, 3)]
    np.testing.assert_array_equal(p2, p3[:2])
    # cross-bucket: same math, different program -> tolerance only
    np.testing.assert_allclose(p3, full[:3], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(full, axis=1), 1.0, rtol=1e-5)


def test_engine_zero_recompiles_across_mixed_sizes(toy_engine):
    from moco_tpu.serve.engine import EngineRecompileError

    rng = np.random.default_rng(1)
    for n in (1, 2, 3, 4, 5, 8, 9, 17):
        toy_engine.embed(rng.integers(0, 255, (n, IMG, IMG, 3), np.uint8))
    assert toy_engine.recompiles_after_warmup == 0
    with pytest.raises(EngineRecompileError):
        toy_engine._compile(64)  # post-warmup compile must refuse


def test_engine_bucket_selection(toy_engine):
    assert toy_engine.bucket_for(1) == 1
    assert toy_engine.bucket_for(2) == 4
    assert toy_engine.bucket_for(8) == 8
    with pytest.raises(ValueError):
        toy_engine.bucket_for(9)
    imgs = np.random.default_rng(2).integers(0, 255, (17, IMG, IMG, 3), np.uint8)
    _, executed = toy_engine.embed(imgs)  # chunks of max bucket 8: 8+8+1
    assert executed == [(8, 8), (8, 8), (1, 1)]


def test_engine_donation_audit_disabled_on_cpu(toy_engine):
    audit = toy_engine.donation_audit()
    # CPU backend: donation gated off -> audited as None (not False)
    assert audit and all(v is None for v in audit.values())


def test_embed_and_query_matches_separate_calls(toy_engine):
    rng = np.random.default_rng(3)
    imgs = rng.integers(0, 255, (5, IMG, IMG, 3), np.uint8)
    feats, _ = toy_engine.embed(imgs)
    idx = EmbeddingIndex(16, feats.shape[1])
    idx.snapshot(feats)
    emb, scores, nbr, executed = toy_engine.embed_and_query(imgs, idx, 3)
    np.testing.assert_array_equal(emb, feats)
    np.testing.assert_array_equal(nbr[:, 0], np.arange(5))
    s2, i2 = idx.query(feats, 3)
    np.testing.assert_array_equal(nbr, i2)
    np.testing.assert_allclose(scores, s2, rtol=1e-6)


@pytest.mark.slow
def test_load_serving_encoder_key_side(tmp_path):
    """The serving loader restores the KEY (EMA) encoder + queue: make
    params_k distinguishable from params_q in the checkpoint and assert
    the served embeddings come from the key side."""
    sm = load_script("serve_smoke.py")
    from moco_tpu.serve.engine import InferenceEngine, load_serving_encoder
    from moco_tpu.utils.checkpoint import CheckpointManager

    ckpt = str(tmp_path / "ckpt")
    sm.make_toy_checkpoint(ckpt)
    # perturb params_k so the sides differ (create_state copies q -> k)
    from moco_tpu.lincls import restore_pretrain_state

    state, config = restore_pretrain_state(ckpt)
    state = state.replace(
        params_k=jax.tree.map(lambda x: x * 1.5, state.params_k)
    )
    mgr = CheckpointManager(ckpt)
    from moco_tpu.utils.config import config_to_dict

    mgr.save(1, state, extra={"epoch": 0, "config": config_to_dict(config), "num_data": 1})
    mgr.close()

    module, params, stats, queue, queue_ptr, _ = load_serving_encoder(ckpt)
    assert queue.shape == (64, 16) and queue_ptr == 0
    k_leaf = jax.tree.leaves(params)[0]
    q_leaf = jax.tree.leaves(state.params_q)[0]
    np.testing.assert_allclose(np.asarray(k_leaf), np.asarray(q_leaf) * 1.5, rtol=1e-6)
    module_q, params_q, *_ = load_serving_encoder(ckpt, side="q")
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(params_q)[0]), np.asarray(q_leaf)
    )


# -- batcher -------------------------------------------------------------


def _echo_run_batch(images, want_neighbors):
    return {"embedding": np.arange(images.shape[0], dtype=np.float32)[:, None]}, [
        (8, images.shape[0])
    ]


def test_batcher_size_flush_before_deadline():
    calls = []

    def run_batch(images, wn):
        calls.append(images.shape[0])
        return _echo_run_batch(images, wn)

    b = ContinuousBatcher(run_batch, max_batch=8, slo_ms=10_000)
    try:
        t0 = time.perf_counter()
        futs = [b.submit(np.zeros((2, 4, 4, 3), np.uint8)) for _ in range(4)]
        outs = [f.result(10) for f in futs]
        # flushed by SIZE (8 rows), far before the 5s deadline
        assert time.perf_counter() - t0 < 2.0
        assert calls and calls[0] == 8
        # scatter: each future got ITS rows, in submit order
        got = np.concatenate([o["embedding"][:, 0] for o in outs])
        np.testing.assert_array_equal(got, np.arange(8, dtype=np.float32))
    finally:
        b.close()


def test_batcher_deadline_flush_without_size():
    b = ContinuousBatcher(_echo_run_batch, max_batch=1000, slo_ms=200)
    try:
        t0 = time.perf_counter()
        out = b.submit(np.zeros((3, 4, 4, 3), np.uint8)).result(10)
        dt = time.perf_counter() - t0
        assert out["embedding"].shape == (3, 1)
        # flushed by the slo/2 deadline (~100ms), never by size
        assert 0.05 < dt < 2.0
    finally:
        b.close()


def test_batcher_slo_violation_accounting():
    def slow_run(images, wn):
        time.sleep(0.12)
        return _echo_run_batch(images, wn)

    b = ContinuousBatcher(slow_run, max_batch=4, slo_ms=100)
    try:
        futs = [b.submit(np.zeros((4, 4, 4, 3), np.uint8)) for _ in range(2)]
        for f in futs:
            f.result(10)
        p = b.metrics.payload()
        assert p["serve/requests"] == 2
        assert p["serve/slo_violations"] == 2  # 120ms compute > 100ms SLO
        assert p["serve/p99_ms"] > 100
    finally:
        b.close()


def test_batcher_close_unblocks_put_blocked_producers():
    release = threading.Event()

    def stuck_run(images, wn):
        release.wait(5)
        return _echo_run_batch(images, wn)

    b = ContinuousBatcher(stuck_run, max_batch=1, slo_ms=50, queue_depth=1)
    errors = []

    def producer():
        try:
            for _ in range(100):
                b.submit(np.zeros((1, 4, 4, 3), np.uint8))
        except BatcherClosedError:
            errors.append("closed")

    threads = [threading.Thread(target=producer) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.3)  # producers now blocked on the bounded queue
    release.set()
    b.close()
    for t in threads:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in threads), "producer leaked (JX011)"
    assert len(errors) == 3


def test_batcher_close_fails_pending_futures():
    def slow_run(images, wn):
        time.sleep(0.2)
        return _echo_run_batch(images, wn)

    b = ContinuousBatcher(slow_run, max_batch=1, slo_ms=1000, queue_depth=8)
    futs = [b.submit(np.zeros((1, 4, 4, 3), np.uint8)) for _ in range(4)]
    b.close()
    resolved = failed = 0
    for f in futs:
        try:
            f.result(5)
            resolved += 1
        except BatcherClosedError:
            failed += 1
    assert resolved + failed == 4 and failed >= 1
    with pytest.raises(BatcherClosedError):
        b.submit(np.zeros((1, 4, 4, 3), np.uint8))


def test_batcher_run_batch_error_propagates_to_futures():
    def bad_run(images, wn):
        raise RuntimeError("engine on fire")

    b = ContinuousBatcher(bad_run, max_batch=1, slo_ms=50)
    try:
        with pytest.raises(RuntimeError, match="engine on fire"):
            b.submit(np.zeros((1, 4, 4, 3), np.uint8)).result(10)
    finally:
        b.close()


def test_serve_metrics_payload_schema():
    from moco_tpu.obs import schema

    m = ServeMetrics(slo_ms=100)
    m.record_flush([(8, 5), (32, 30)])
    m.record_request(0.050)
    m.record_request(0.250)  # violation
    rec = {"step": 1, "time": time.time(), **m.payload()}
    assert schema.validate_line(rec) == []
    assert rec["serve/occupancy"] == 35 / 40
    assert rec["serve/slo_violations"] == 1
    assert rec["serve/bucket_8"] == 1 and rec["serve/bucket_32"] == 1
    # a malformed serve/ value must be rejected by the prefix validator
    assert schema.validate_line({"step": 1, "time": 0.0, "serve/qps": "fast"})


# -- server + satellites -------------------------------------------------


def test_resolve_serve_port_offset_rule():
    from moco_tpu.obs.sinks import SERVE_PORT_STRIDE, resolve_serve_port

    # no metrics endpoint: plain per-process family
    assert resolve_serve_port(8000, 0, 0) == 8000
    assert resolve_serve_port(8000, 0, 3) == 8003
    # collision with the Prometheus family -> shift by the stride
    # this test ASSERTS the offset rule, so it hand-computes the
    # expected values on purpose
    assert resolve_serve_port(9090, 9090, 0) == 9090 + SERVE_PORT_STRIDE  # mocolint: disable=JX018
    assert resolve_serve_port(9090, 9090, 2) == 9092 + SERVE_PORT_STRIDE  # mocolint: disable=JX018
    # distinct families never shift
    assert resolve_serve_port(8000, 9090, 1) == 8001
    # 0 = ephemeral stays 0
    assert resolve_serve_port(0, 9090, 1) == 0


@pytest.mark.slow
def test_server_end_to_end(toy_engine, tmp_path):
    from moco_tpu.obs import schema
    from moco_tpu.obs.sinks import JsonlSink
    from moco_tpu.serve.server import ServeServer

    rng = np.random.default_rng(0)
    seed_imgs = rng.integers(0, 255, (8, IMG, IMG, 3), np.uint8)
    feats, _ = toy_engine.embed(seed_imgs)
    index = EmbeddingIndex(16, feats.shape[1])
    index.snapshot(feats)
    sink = JsonlSink(str(tmp_path))
    server = ServeServer(
        toy_engine, index=index, port=0, slo_ms=5000, neighbors_k=3,
        sink=sink, metrics_flush_s=0.2,
        warmup=False,  # module-scoped engine is already warm
    )
    index.prepare(toy_engine.buckets, 3)
    index.freeze()
    try:
        base = f"http://127.0.0.1:{server.port}"

        def post(path, imgs):
            req = urllib.request.Request(
                base + path, data=imgs.tobytes(),
                headers={"X-Image-Shape": ",".join(map(str, imgs.shape))},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        out = post("/embed", seed_imgs[:2])
        np.testing.assert_allclose(np.asarray(out["embedding"]), feats[:2], atol=1e-5)
        out = post("/neighbors?k=2", seed_imgs[:3])
        nbr = np.asarray(out["indices"])
        assert nbr.shape == (3, 2)
        np.testing.assert_array_equal(nbr[:, 0], np.arange(3))
        # malformed request -> 400, not a crash
        req = urllib.request.Request(
            base + "/embed", data=b"xx", headers={"X-Image-Shape": "1,2,3"}
        )
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(req, timeout=10)
        assert exc_info.value.code == 400
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            assert json.loads(r.read())["ok"] is True
        with urllib.request.urlopen(base + "/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["serve/recompiles_after_warmup"] == 0
        assert stats["serve/requests"] >= 2
        time.sleep(0.5)  # let the flusher write at least one line
    finally:
        server.close()
        sink.close()
    errors = schema.validate_file(str(tmp_path / "metrics.jsonl"))
    assert not errors, errors
    lines = schema.read_metrics(str(tmp_path / "metrics.jsonl"))
    assert any("serve/qps" in r for r in lines)
