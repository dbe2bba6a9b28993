"""Device prefetch ring (ISSUE 5 tentpole): correctness vs the sync
path, real overlap, donation safety, clean shutdown.

The overlap assertions use the deterministic `delay@site=...` fault
hooks (utils/faults.py) to slow individual stages — wall-clock math on
injected, known stage times instead of flaky scheduler-dependent
measurements.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.data.device_prefetch import DevicePrefetchRing, H2D_SITE
from moco_tpu.data.pipeline import TwoCropPipeline, _prefetch
from moco_tpu.parallel import create_mesh
from moco_tpu.utils import faults
from moco_tpu.utils.config import DataConfig


@pytest.fixture(autouse=True)
def _no_fault_plan():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def folder(tmp_path_factory):
    """Tiny JPEG ImageFolder — the jpeg/cache pipeline variants decode
    from it; geometry varies per image so host-RRC boxes are exercised
    against original dims."""
    from PIL import Image as PILImage

    root = tmp_path_factory.mktemp("ring_imgs")
    rng = np.random.default_rng(0)
    for cls in ("a", "b"):
        (root / cls).mkdir()
        for i in range(16):
            h, w = rng.integers(40, 90, 2)
            arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
            PILImage.fromarray(arr).save(root / cls / f"i{i}.jpg", quality=92)
    return str(root)


def _variant_config(variant: str, folder: str, tmp_path) -> DataConfig:
    """The three input modes the ring must feed identically: JPEG decode
    + host RRC, packed-RGB cache + host RRC, canvas (device-side crop)."""
    if variant == "jpeg":
        return DataConfig(
            dataset="imagefolder", data_dir=folder, image_size=16,
            global_batch=8, num_workers=2, host_rrc=True,
        )
    if variant == "cache":
        return DataConfig(
            dataset="imagefolder", data_dir=folder, image_size=16,
            global_batch=8, num_workers=2, host_rrc=True,
            cache_dir=str(tmp_path / "rgb_cache"),
        )
    assert variant == "canvas"
    return DataConfig(
        dataset="imagefolder", data_dir=folder, image_size=16,
        global_batch=8, num_workers=2, host_rrc=False,
    )


class TestRingMatchesSyncPath:
    @pytest.mark.parametrize("variant", ["jpeg", "cache", "canvas"])
    def test_bit_identical_batches(self, variant, folder, tmp_path):
        mesh = create_mesh()
        cfg = _variant_config(variant, folder, tmp_path)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        sync = list(pipe.epoch(0))
        ring = list(pipe.epoch(0, device=True))
        assert len(sync) == len(ring) == pipe.steps_per_epoch
        for a, b in zip(sync, ring):
            np.testing.assert_array_equal(np.asarray(a["im_q"]), np.asarray(b["im_q"]))
            np.testing.assert_array_equal(np.asarray(a["im_k"]), np.asarray(b["im_k"]))

    def test_synthetic_variant_and_sharding(self):
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=16, global_batch=16, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        sync_it = pipe.epoch(0)
        a = next(sync_it)
        sync_it.close()
        it = pipe.epoch(0, device=True)
        b = next(iter(it))
        np.testing.assert_array_equal(np.asarray(a["im_q"]), np.asarray(b["im_q"]))
        # ring batches keep the data-axis sharding the step expects
        assert len(b["im_q"].addressable_shards) == jax.device_count()
        it.close()

    def test_labeled_pipeline_ring(self, folder, tmp_path):
        from moco_tpu.data.pipeline import LabeledPipeline

        mesh = create_mesh()
        cfg = _variant_config("jpeg", folder, tmp_path)
        pipe = LabeledPipeline(cfg, mesh, seed=0)
        sync_it, ring_it = pipe.epoch(0), pipe.epoch(0, device=True)
        (xs, ys) = next(sync_it)
        (xr, yr) = next(iter(ring_it))
        sync_it.close()
        ring_it.close()
        np.testing.assert_array_equal(np.asarray(xs), np.asarray(xr))
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(yr))


class TestOverlap:
    def test_wall_clock_overlaps_stages(self):
        """With an injected slow wire (0.05 s/batch) AND slow decode
        (0.05 s/batch), the overlapped wall for N batches must be well
        under the serial sum — the stages run concurrently. The sync
        path by construction pays decode+wire serially on its one
        producer thread."""
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=8, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        n = 8
        delay = 0.05
        faults.install(
            f"delay@site=data.read:seconds={delay},"
            f"delay@site={H2D_SITE}:seconds={delay}"
        )
        it = pipe.epoch(0, device=True, depth=2)
        # consume n batches; time from first to last so thread spin-up
        # is excluded
        next(it)
        t0 = time.perf_counter()
        for _ in range(n):
            next(it)
        wall = time.perf_counter() - t0
        it.close()
        serial = 2 * delay * n  # decode + wire, if they took turns
        assert wall < 0.8 * serial, (
            f"no overlap: wall {wall:.3f}s vs serial bound {serial:.3f}s"
        )
        # ...and the per-batch wire time was actually recorded
        pay = it.stats_payload()
        assert pay["t_transfer"] >= delay
        assert pay["transfer_bytes"] > 0
        assert 0 <= pay["prefetch_depth_live"] <= 2

    def test_sync_path_is_serial_baseline(self):
        """Control for the assertion above: the same injected delays on
        the SYNC path cost the full serial sum per batch."""
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=8, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        n, delay = 4, 0.05
        faults.install(f"delay@site=data.read:seconds={delay}")
        it = pipe.epoch(0)
        next(it)
        t0 = time.perf_counter()
        for _ in range(n):
            next(it)
        wall = time.perf_counter() - t0
        it.close()
        assert wall >= 0.9 * delay * n


class TestDonation:
    def test_donated_slots_match_plain(self):
        """prefetch_donate recycles the consumed staging buffer; outputs
        must be identical and no donated buffer may be touched again
        (jax raises on donated-buffer reuse when it is)."""
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=16, global_batch=16, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        plain_it = pipe.epoch(0)
        plain = [next(plain_it)]
        plain_it.close()
        don_it = pipe.epoch(0, device=True, donate=True)
        donated = []
        for _ in range(3):
            donated.append(next(don_it))
        don_it.close()
        np.testing.assert_array_equal(
            np.asarray(plain[0]["im_q"]), np.asarray(donated[0]["im_q"])
        )
        # every ring batch stays fully readable after later transfers
        # rotated (and donated) other slots
        for b in donated:
            assert bool(jnp.isfinite(b["im_q"]).all())
            assert bool(jnp.isfinite(b["im_k"]).all())


def _pipeline_threads():
    """Live prefetch-producer / transfer-ring threads (the leak
    targets; the pipeline's decode POOL threads are lazy-spawned and
    live for the pipeline's lifetime by design, so absolute
    active_count comparisons are noise)."""
    return [
        t for t in threading.enumerate()
        if t.name.startswith(("prefetch", "device_prefetch")) and t.is_alive()
    ]


def _assert_pipeline_threads_exit(timeout: float = 5.0):
    deadline = time.time() + timeout
    while _pipeline_threads() and time.time() < deadline:
        time.sleep(0.02)
    leaked = _pipeline_threads()
    assert not leaked, f"leaked threads: {[t.name for t in leaked]}"


class TestShutdown:
    def test_close_mid_epoch_leaks_no_threads(self):
        """The PR-1..4 era leak: abandoning the iterator mid-epoch left
        the daemon producer blocked on q.put forever. close() must end
        both the producer and the transfer thread."""
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=8, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        it = pipe.epoch(0, device=True)
        next(it)  # producer + ring threads are live and mid-stream
        assert _pipeline_threads()
        it.close()
        _assert_pipeline_threads_exit()

    def test_close_unblocks_put_blocked_producer(self):
        """Producer blocked on a FULL queue (consumer never drains — the
        exact leak shape: an exception in the step loop) must exit."""
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=8, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        it = pipe.epoch(0, device=True, depth=1)
        # never consume: both queues fill, both threads block on put
        time.sleep(0.3)
        assert _pipeline_threads()
        it.close()
        _assert_pipeline_threads_exit()

    def test_sync_iterator_close_is_also_leakfree(self):
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=8, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        it = pipe.epoch(0)
        next(it)
        it.close()
        _assert_pipeline_threads_exit()

    def test_abandoned_iterator_self_cleans_on_gc(self):
        """A consumer that simply DROPS the iterator (no close()) must
        not leak threads either: the producer/ring threads hold no
        reference to the iterator object, so GC fires __del__, which
        flips the stop flag and lets them unwind."""
        import gc

        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=8, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        next(iter(pipe.epoch(0, device=True)))  # abandoned immediately
        next(iter(pipe.epoch(0)))  # sync path too
        gc.collect()
        _assert_pipeline_threads_exit()

    def test_exhausted_iterator_is_reentrant_safe(self):
        """next() after exhaustion and close() after exhaustion both
        behave (no hang on an empty queue, no double-join error)."""
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=64, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        it = pipe.epoch(0, device=True)
        batches = list(it)
        assert len(batches) == pipe.steps_per_epoch
        assert next(it, None) is None
        it.close()
        it.close()

    def test_producer_error_propagates_then_shuts_down(self, monkeypatch):
        """An injected decode IOError past the retry budget must surface
        at the consumer's next() (not vanish on the ring thread) and
        leave no live threads behind."""
        monkeypatch.setenv("MOCO_IO_RETRIES", "2")
        monkeypatch.setenv("MOCO_IO_RETRY_BASE", "0.01")
        mesh = create_mesh()
        cfg = DataConfig(dataset="synthetic", image_size=8, global_batch=8, num_workers=2)
        pipe = TwoCropPipeline(cfg, mesh, seed=0)
        # every read fails: retries exhaust, the error crosses both queues
        faults.install("io@site=data.read:at=1:times=999")
        it = pipe.epoch(0, device=True)
        with pytest.raises(IOError):
            for _ in range(pipe.steps_per_epoch):
                next(it)
        it.close()


class TestRingUnit:
    """DevicePrefetchRing against a hand-rolled transfer fn — no
    pipeline, exact control of item flow."""

    def test_order_and_stats(self):
        items = list(range(10))
        ring = DevicePrefetchRing(
            iter(items), lambda x: (x * 2, 100), depth=3
        )
        assert list(ring) == [x * 2 for x in items]
        assert ring.stats.batches == 10
        assert ring.stats.total_bytes == 1000
        assert ring.stats.wire_rate_bytes_per_sec() > 0

    def test_transfer_error_reraises(self):
        def boom(x):
            raise RuntimeError("wire down")

        ring = DevicePrefetchRing(iter([1]), boom, depth=2)
        with pytest.raises(RuntimeError, match="wire down"):
            next(ring)

    def test_depth_validation(self):
        with pytest.raises(ValueError, match="depth"):
            DevicePrefetchRing(iter([]), lambda x: (x, 0), depth=0)

    @pytest.mark.parametrize("consumer_delay", [0.0, 0.03])
    def test_ring_blocked_is_the_input_sides_slack(self, consumer_delay):
        """The wait for a free slot is timed as `ring_blocked`: next to
        nothing while the consumer keeps up, and about the consumer's
        extra time a batch once it is slowed (through the `delay@site`
        hook), with the `transfer` spans unchanged."""
        from moco_tpu import obs

        n = 8
        tracer = obs.Tracer()
        prev = obs.set_tracer(tracer)
        try:
            if consumer_delay:
                faults.install(f"delay@site=test.consumer:seconds={consumer_delay}")
            ring = DevicePrefetchRing(iter(range(n)), lambda x: (x, 1), depth=1)
            for _ in ring:
                faults.maybe_delay("test.consumer")
            ring.close()
        finally:
            obs.set_tracer(prev)
        totals = tracer.totals()
        assert totals["transfer"][0] == n and totals["ring_blocked"][0] == n
        blocked = totals["ring_blocked"][1]
        if consumer_delay:
            # depth 1: one batch waits in the slot, so the ring sits out
            # all of the consumer's delays but the first two
            assert blocked >= (n - 2) * consumer_delay * 0.9
        else:
            assert blocked < 0.05
        assert totals["transfer"][1] < 0.05

    def test_empty_payload_before_first_batch(self):
        ring = DevicePrefetchRing(iter([]), lambda x: (x, 0), depth=1)
        assert list(ring) == []
        assert ring.stats_payload() == {}


def test_delay_fault_hook_grammar():
    """The delay@ fault kind: per-site, 1-based at/times window, every
    call by default."""
    plan = faults.install("delay@site=wire:seconds=0.02:at=2:times=2")
    t0 = time.perf_counter()
    plan.maybe_delay("wire")  # call 1: before `at` — no sleep
    fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan.maybe_delay("wire")  # call 2: sleeps
    slow = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan.maybe_delay("wire")  # call 3: sleeps (times=2)
    slow2 = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan.maybe_delay("wire")  # call 4: window over
    fast2 = time.perf_counter() - t0
    assert fast < 0.01 and fast2 < 0.01
    assert slow >= 0.02 and slow2 >= 0.02
    # other sites unaffected
    t0 = time.perf_counter()
    plan.maybe_delay("elsewhere")
    assert time.perf_counter() - t0 < 0.01
