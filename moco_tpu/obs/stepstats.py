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

from typing import Optional

import jax
import numpy as np


class StepTimeProbe:
    """Per-step timing accumulator for the train loop.

    Usage per iteration:
        probe.data_wait(seconds)        # host blocked on input
        probe.dispatched(seconds)       # step_fn call returned (async)
        if probe.should_sample(step):
            with obs.span("device_wait") as wait:
                jax.block_until_ready(outputs)
            probe.device_block(wait.seconds, steps_done)
        probe.step_done(total_seconds)

    The seconds come from the driver's spans (`_SpanCM.seconds`), one
    clock read an edge. `payload()` returns the fields for the metrics
    line: always `t_data`/`t_step`; `t_dispatch`/`t_device` from the
    most recent sampled step with `t_probe_step`, the step they were
    sampled on. The driver never feeds it the process's first step,
    which compiles (or loads) the program and is timed as
    `setup/first_step` instead: the three stay off the line until a
    later step has been sampled.

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
        self._probe_step: Optional[int] = None

    def should_sample(self, step: int) -> bool:
        return self.every > 0 and step % self.every == 0

    def data_wait(self, seconds: float) -> None:
        self.t_data = seconds

    def dispatched(self, seconds: float) -> None:
        self._last_dispatch = seconds

    def device_block(self, seconds: float, step: int) -> None:
        # a sampled step: the dispatch measured this iteration becomes
        # the published pair (dispatch, device). `step` is the global
        # step with this one done, as a log line's `step` counts.
        self._t_dispatch = self._last_dispatch
        self._t_device = seconds
        self._probe_step = int(step)

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
            out["t_probe_step"] = self._probe_step
        return out


# The host phases a log line accounts for, by span name: the driver
# thread's (always on the line) and the prefetch ring thread's (on the
# line once the ring has run). `log_flush` contains its waits for the
# device: `metrics_fetch`, the read of a step dispatched before the newest
# one, and `fleet_gather`, the cross-process collective (zero on one
# process, which reduces on the host). `device_wait` is the probe's
# drain, on one step in `obs_probe_every`.
DRIVER_PHASES = (
    "data_wait", "step", "device_wait", "throttle_wait", "log_flush", "metrics_fetch", "fleet_gather",
)
RING_PHASES = ("transfer", "augment_dispatch", "ring_blocked")
SETUP_PARTS = ("backend", "state_init", "checkpoint", "pipeline_start", "first_step")


def _seconds_between(now: dict, before: dict, name: str) -> float:
    return now.get(name, (0, 0.0))[1] - before.get(name, (0, 0.0))[1]


def phase_account(now: dict, before: dict, steps: int) -> dict:
    """The `phase/*` fields of a log line: per-step mean seconds of each
    host phase between two `Tracer.totals()` snapshots `steps` steps
    apart. Every step counts, where the probe samples one in
    `obs_probe_every`. `phase/log_flush_host` is the flush less the fetch
    of the step's metrics: the log path's host work, and whatever else
    in it waits for the device (`phase/fleet_gather` with more than one
    process; nothing with one)."""
    steps = max(int(steps), 1)
    names = DRIVER_PHASES + tuple(n for n in RING_PHASES if n in now)
    out = {f"phase/{n}": _seconds_between(now, before, n) / steps for n in names}
    out["phase/log_flush_host"] = out["phase/log_flush"] - out["phase/metrics_fetch"]
    out["phase/steps"] = steps
    return out


def setup_account(now: dict, before: dict) -> dict:
    """The `setup/<part>_s` fields of the `setup` event line: seconds
    spent under each set-up span between two `Tracer.totals()` snapshots."""
    return {f"setup/{p}_s": _seconds_between(now, before, f"setup/{p}") for p in SETUP_PARTS}


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
    "phase_account",
    "setup_account",
    "device_memory_stats",
    "memory_payload",
    "tree_shard_bytes",
]
