"""Device prefetch ring — overlap the host→device wire with compute.

PROFILE.md's round-5 ledger: the device sustains ~1940 imgs/s/chip and
the host pipeline alone feeds 3474–6816 imgs/s in canvas mode, yet the
with-data rate was 288 imgs/s — because decode, transfer, and compute
ran *serially* on one producer thread. The reference MoCo recipe hides
the wire behind 32 DataLoader workers + pinned-memory async H2D per GPU
(`main_moco.py` DataLoader(pin_memory=True)); this module is the JAX
rebuild of that overlap:

- the host pipeline's `_prefetch` thread decodes batch *k+2*;
- this ring's dedicated transfer thread issues the sharded
  `jax.device_put` (uint8 on the wire — 4x fewer bytes than fp32;
  normalize/cast happen on device inside the jitted augment) for batch
  *k+1* into the next staging slot;
- the train loop dispatches step *k* against an already device-resident
  batch.

The "ring" is the bounded output queue: at most `depth` transferred
batches are alive at once, so the staging slots rotate — a new transfer
only starts once the consumer has taken a slot, and (optionally) the
consumed slot's uint8 buffer is *donated* to the augment step so XLA
reuses its memory for the normalized output instead of allocating a
fresh batch-sized buffer.

Observability contract (wired end-to-end, see ISSUE 5): every transfer
runs under a `transfer` span on the ring thread's trace track and the
wait for a free slot after it under `ring_blocked`, the ring
keeps per-batch `t_transfer`/`transfer_bytes` plus a live-depth gauge
(`stats_payload()` feeds the driver's metrics lines and the fleet
straggler vector), and the wire registers an `input.h2d` entry in the
comms ledger so obs_report's byte table shows H2D next to the ICI
collectives.

Shutdown: `close()` is safe from the consumer side at any point —
mid-epoch abandonment (preemption, a step-loop exception) must not leak
the transfer thread or the upstream producer (see `_prefetch`'s
poison-pill close, which this propagates to).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional

from moco_tpu.obs.trace import counter as obs_counter, span as obs_span
from moco_tpu.utils import faults

from moco_tpu.analysis import tsan

# fault-injection site for the wire (`delay@site=input.h2d:seconds=S`):
# the overlap tests and `scripts/overlap_smoke.py` slow the transfer
# stage deterministically through this hook
H2D_SITE = "input.h2d"

_END = object()
_CLOSED = object()


def _responsive_put(q: queue.Queue, stop: threading.Event, item) -> bool:
    """Bounded put that stays responsive to a stop flag; False = stopped."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


def _ring_loop(
    host_iter: Iterator,
    transfer: Callable,
    q: queue.Queue,
    stop: threading.Event,
) -> None:
    """Transfer-thread body. MODULE-LEVEL on purpose: the thread must
    not reference the ring OBJECT, so an abandoned ring can be GC'd
    (`__del__` flips the stop flag) instead of living forever."""
    seq = 0
    try:
        for item in host_iter:
            if stop.is_set():
                return
            t0 = time.perf_counter()
            with obs_span("transfer", seq=seq):
                faults.maybe_delay(H2D_SITE)
                batch, nbytes = transfer(item)
            seconds = time.perf_counter() - t0
            # the wait for a free slot is the input side's slack: near
            # zero, the ring (or the decode behind it) sets the pace
            with obs_span("ring_blocked", seq=seq):
                placed = _responsive_put(q, stop, (batch, seconds, nbytes))
            if not placed:
                return
            seq += 1
        _responsive_put(q, stop, _END)
    except BaseException as e:  # surface transfer errors to the consumer
        _responsive_put(q, stop, e)


class TransferStats:
    """Thread-safe per-batch + cumulative transfer accounting."""

    def __init__(self):
        # tsan factory (analysis/tsan.py): traced under --sanitize-threads
        self._lock = tsan.make_lock("data.transfer_stats")
        self.t_transfer: Optional[float] = None  # seconds, last batch
        self.transfer_bytes: Optional[int] = None  # wire bytes, last batch
        self.depth_live: int = 0  # staged batches ready right now
        self.batches: int = 0
        self.total_seconds: float = 0.0
        self.total_bytes: int = 0

    def record(self, seconds: float, nbytes: int, depth_live: int) -> None:
        with self._lock:
            self.t_transfer = seconds
            self.transfer_bytes = int(nbytes)
            self.depth_live = int(depth_live)
            self.batches += 1
            self.total_seconds += seconds
            self.total_bytes += int(nbytes)

    def set_depth(self, depth_live: int) -> None:
        with self._lock:
            self.depth_live = int(depth_live)

    def payload(self) -> dict:
        """Metrics-line fields (schema: t_transfer/transfer_bytes/
        prefetch_depth_live) — empty before the first transfer so sync
        runs keep clean lines."""
        with self._lock:
            if self.batches == 0:
                return {}
            return {
                "t_transfer": self.t_transfer,
                "transfer_bytes": self.transfer_bytes,
                "prefetch_depth_live": self.depth_live,
            }

    def wire_rate_bytes_per_sec(self) -> Optional[float]:
        """Cumulative wire bandwidth (the `wire-rate` leg of
        `scripts/profile_input.py`'s overlap_efficiency denominator)."""
        with self._lock:
            if self.total_seconds <= 0:
                return None
            return self.total_bytes / self.total_seconds


class DevicePrefetchRing:
    """Depth-N transfer ring between a host-batch iterator and the step
    loop (module docstring). Iterate it like the sync pipeline iterator;
    `stats_payload()` exposes the per-line wire metrics; `close()` shuts
    the transfer thread and the upstream producer down without leaks.

    `transfer(host_item) -> (device_batch, wire_bytes)` runs on the ring
    thread — it owns the sharded `device_put` + the jitted augment
    dispatch, so the main thread never touches the wire.
    """

    def __init__(
        self,
        host_iter: Iterator,
        transfer: Callable,
        depth: int = 2,
        name: str = "device_prefetch",
    ):
        if depth < 1:
            raise ValueError(f"ring depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self.stats = TransferStats()
        self._host_iter = host_iter
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=_ring_loop, args=(host_iter, transfer, self._q, self._stop),
            daemon=True, name=name,
        )
        self._thread.start()

    # -- consumer side ---------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        item = self._q.get()
        if item is _END or item is _CLOSED:
            # re-arm the sentinel: a second next() after exhaustion must
            # also stop, not block on an empty queue
            self._stop.set()
            raise StopIteration
        if isinstance(item, BaseException):
            self._stop.set()
            raise item
        batch, seconds, nbytes = item
        depth_live = self._q.qsize()
        self.stats.record(seconds, nbytes, depth_live=depth_live)
        obs_counter("prefetch_depth_live", depth=depth_live)
        return batch

    def stats_payload(self) -> dict:
        return self.stats.payload()

    def close(self, timeout: float = 5.0) -> None:
        """Consumer-side shutdown: unblock and join the transfer thread,
        then close the upstream host iterator (poison-pill through the
        decode producer). Idempotent; safe mid-epoch."""
        self._stop.set()
        # drain so a put()-blocked transfer thread unblocks immediately
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        upstream_close = getattr(self._host_iter, "close", None)
        if upstream_close is not None:
            upstream_close()
        self._thread.join(timeout=timeout)

    @property
    def closed(self) -> bool:
        return self._stop.is_set()

    def __del__(self):
        # abandoned-ring safety net (no close() call): the transfer
        # thread holds no reference to this object, so GC reaches here —
        # flip the flags and let both threads exit on their next poll
        self._stop.set()
        upstream_close = getattr(self._host_iter, "close", None)
        if upstream_close is not None:
            try:
                # timeout=0: never block inside GC — the pill is posted
                # and the threads unwind on their own
                upstream_close(timeout=0)
            except Exception:
                pass


__all__ = ["DevicePrefetchRing", "TransferStats", "H2D_SITE"]
