"""Step-time breakdown probe + device-memory gauges.

Where does a step's wall time go? Four places the bare loss line can't
distinguish:

- *host data wait* — the step loop blocked on the prefetch queue
  (input-bound run);
- *wire* — host→device transfer of the batch (reported separately as
  `t_transfer` by the device prefetch ring, data/device_prefetch.py,
  which runs the wire on its own thread so it overlaps both of the
  stages below);
- *dispatch* — host-side time to enqueue the jitted step (tracing,
  argument placement, python overhead);
- *device compute* — the accelerator actually executing.

Because dispatch is async, `t_dispatch` alone says nothing about device
time. The probe separates them by calling `jax.block_until_ready` on
the step's outputs on SAMPLED steps only (`every` steps apart): the
block drains the device queue, so `t_device` ≈ the device-side tail of
this step. Off sampled steps the loop stays sync-free — the probe adds
zero cost to the hot path, same contract as the fault guards.

Device memory comes from `device.memory_stats()` (PjRt): live and peak
bytes in use. The CPU backend reports none (`memory_stats()` is None)
and the metrics line carries `null` — "unknown", never fake zero. A TPU
run always has the numbers; chip_smoke.py fails without them.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import numpy as np


class StepTimeProbe:
    """Per-step timing accumulator for the train loop.

    Usage per iteration:
        probe.data_wait(seconds)        # host blocked on input
        probe.dispatched(seconds)       # step_fn call returned (async)
        if probe.should_sample(step):
            t0 = time.perf_counter()
            jax.block_until_ready(outputs)
            probe.device_block(time.perf_counter() - t0)
        probe.step_done(total_seconds)

    `payload()` returns the fields for the metrics line: always
    `t_data`/`t_step`; `t_dispatch`/`t_device` from the most recent
    sampled step (absent until one happened).

    Under the software-pipelined driver loop (ISSUE 5) the log-step
    fetch is deferred one dispatch, so `step_done` receives the
    SMOOTHED per-step wall — (wall since the previous logged flush) /
    (steps since it) — rather than one bursty iteration's host wall;
    per-iteration wall under pipelining is just dispatch time and would
    read ~0 between throttle waits.
    """

    def __init__(self, every: int = 0):
        self.every = int(every)
        self.t_data = 0.0
        self.t_step = 0.0
        self._last_dispatch: Optional[float] = None
        self._t_dispatch: Optional[float] = None
        self._t_device: Optional[float] = None

    def should_sample(self, step: int) -> bool:
        return self.every > 0 and step % self.every == 0

    def data_wait(self, seconds: float) -> None:
        self.t_data = seconds

    def dispatched(self, seconds: float) -> None:
        self._last_dispatch = seconds

    def device_block(self, seconds: float) -> None:
        # a sampled step: the dispatch measured this iteration becomes
        # the published pair (dispatch, device)
        self._t_dispatch = self._last_dispatch
        self._t_device = seconds

    def step_done(self, seconds: float) -> None:
        self.t_step = seconds

    @property
    def last_dispatch(self) -> Optional[float]:
        """Most recent host-side dispatch time (every step, not just
        probe-sampled ones) — the fleet vector's dispatch-lag field."""
        return self._last_dispatch

    def payload(self) -> dict:
        out = {"t_data": self.t_data, "t_step": self.t_step}
        if self._t_device is not None:
            out["t_dispatch"] = self._t_dispatch
            out["t_device"] = self._t_device
        return out


def device_memory_stats(device=None) -> Optional[dict]:
    """{'hbm_live_bytes', 'hbm_peak_bytes', 'hbm_headroom_bytes'} for
    `device` (default: first local device), or None on a backend whose
    `memory_stats()` is None (the CPU). Keys are jax 0.9.0's on a TPU:
    `bytes_in_use`, `peak_bytes_in_use`, `bytes_limit`."""
    if device is None:
        device = jax.local_devices()[0]
    stats = device.memory_stats()
    if not stats:
        return None
    live = int(stats["bytes_in_use"])
    return {
        "hbm_live_bytes": live,
        "hbm_peak_bytes": int(stats["peak_bytes_in_use"]),
        # how much HBM is LEFT at the live watermark — the gauge the
        # ZeRO-2/3 work exists to raise (more headroom = bigger per-chip
        # batch)
        "hbm_headroom_bytes": int(stats["bytes_limit"]) - live,
    }


def memory_payload() -> dict:
    """Metrics-line fields for device memory: concrete gauges when the
    backend reports them, explicit nulls (schema-locked) otherwise."""
    stats = device_memory_stats()
    if stats is None:
        return {
            "hbm_live_bytes": None,
            "hbm_peak_bytes": None,
            "hbm_headroom_bytes": None,
        }
    return stats


def tree_shard_bytes(tree) -> int:
    """Analytic per-device bytes of a pytree's PERSISTENT arrays: each
    leaf contributes its shard size under its actual sharding (a
    replicated leaf costs its full bytes on every device; a
    P(data)-sharded ZeRO leaf 1/n). Backend-independent — this is the
    at-rest state footprint the CPU-mesh smokes compare across ZeRO
    stages, where `memory_stats` is unavailable."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        itemsize = np.dtype(dtype).itemsize
        if sharding is not None:
            try:
                shard_shape = sharding.shard_shape(tuple(shape))
                total += int(np.prod(shard_shape, dtype=np.int64)) * itemsize
                continue
            except Exception:
                pass  # exotic shardings: fall through to full bytes
        total += int(np.prod(shape, dtype=np.int64)) * itemsize
    return total


__all__ = [
    "StepTimeProbe",
    "device_memory_stats",
    "memory_payload",
    "tree_shard_bytes",
]
