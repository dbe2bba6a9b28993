"""Cross-host fleet aggregation + out-of-band host heartbeats.

PR 3's telemetry is strictly per-process: every host writes its own
metrics/trace files and nothing measures inter-host skew — on a pod a
single straggling host stretches every synchronous collective and the
only symptom is global wall clock. This module gives the driver a fleet
view at log-step cadence:

- `FleetAggregator`: each process contributes a small fixed-width
  per-host stats vector (`FLEET_FIELDS`: data wait, step wall, wire
  transfer time, dispatch lag, io retries, decode failures, live HBM);
  a jitted `all_gather` +
  reduction over a one-device-per-host mesh (on one process: the same
  reduction on the host, no device program) returns per-field
  min/mean/max/argmax plus a `straggler_skew` gauge — `(max(t_step) -
  mean(t_step)) / mean(t_step)`, the fraction of every step the fleet
  spends waiting for its slowest host. Process 0 merges the result into
  its metrics line, so one file answers "which host is slow, and by how
  much".

  Unknown values travel as NaN and aggregate with NaN-aware reductions,
  so a field no host reports (e.g. HBM on CPU) stays null in the line —
  same "unknown, never fake zero" contract as the memory gauges.

- `Heartbeat`: an out-of-band per-process file
  (`heartbeat.p<i>.json`, atomically replaced each beat) carrying the
  process's last step, wall time, and its tracer's wall-clock origin.
  It exists for the failure case the in-band path can't cover: when a
  host dies mid-run its metrics stop, but its heartbeat remains —
  `scripts/obs_report.py` merges heartbeats to name dead hosts, and
  `scripts/trace_merge.py` uses the wall origins for clock-offset
  correction when stitching per-process traces into one Perfetto file.

The aggregation is a real cross-process collective: every process must
call `gather()` at the same (deterministic) log steps — the driver
keys it on the replicated loss's log schedule, which all processes
agree on by construction.
"""

from __future__ import annotations

import json
import os
import socket
import time
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from moco_tpu.obs.trace import span

FLEET_FIELDS = (
    "t_data",
    "t_step",
    # per-batch host→device transfer seconds (device prefetch ring,
    # data/device_prefetch.py) — lets straggler skew attribute to the
    # WIRE: a host whose t_step is fat but whose t_transfer is fatter
    # is PCIe/DMA-bound, not compute-bound. NaN on sync-path runs.
    "t_transfer",
    "dispatch_lag",
    "io_retries",
    "decode_failures",
    "hbm_live",
)


def reduce_stats(stats, t_step_index: int, xp=jnp) -> dict:
    """Pure per-field reduction over an (n_hosts, n_fields) stats matrix.

    NaN-aware: a host that can't report a field contributes NaN, and a
    field nobody reports reduces to NaN (-> null in the line). Returns
    {'min','mean','max' (F,), 'argmax' (F,) int32, 'straggler_skew' ()}.
    Jit-compatible; shared by the live aggregator and the skew tests.
    `xp` is the array namespace: `jnp` for the jitted collective,
    `numpy` where one process reduces its own row on the host.
    """
    s = stats.astype(xp.float32)
    mins = xp.nanmin(s, axis=0)
    means = xp.nanmean(s, axis=0)
    maxs = xp.nanmax(s, axis=0)
    # argmax over NaN-padded columns: NaN -> -inf so a reporting host
    # always wins; an all-NaN column degrades to host 0 (meaningless
    # alongside a null max, which readers key on).
    argmax = xp.argmax(xp.where(xp.isnan(s), -xp.inf, s), axis=0).astype(xp.int32)
    t = s[:, t_step_index]
    t_mean = xp.nanmean(t)
    skew = (xp.nanmax(t) - t_mean) / xp.maximum(t_mean, 1e-12)
    return {
        "min": mins,
        "mean": means,
        "max": maxs,
        "argmax": argmax,
        "straggler_skew": skew,
    }


class FleetAggregator:
    """Jitted cross-host reduction of per-host stats vectors.

    Builds a 1-D `hosts` mesh with ONE representative device per
    process; each process's vector becomes its row of a (n_hosts, F)
    array sharded over that mesh, and the jitted reduce (replicated
    output) is the per-step all_gather, under a `fleet_gather` span. A
    single process has one row and no one to gather from: `gather`
    reduces it on the host with the same `reduce_stats` and dispatches
    nothing to the device.
    """

    def __init__(self, fields: Sequence[str] = FLEET_FIELDS):
        self.fields = tuple(fields)
        if "t_step" not in self.fields:
            raise ValueError("fleet fields must include 't_step' (skew is defined on it)")
        reps: dict[int, jax.Device] = {}
        for d in jax.devices():
            reps.setdefault(d.process_index, d)
        self.rep_devices = [reps[p] for p in sorted(reps)]
        self.num_hosts = len(self.rep_devices)
        self.process_index = jax.process_index()
        self._t_idx = self.fields.index("t_step")
        mesh = Mesh(np.asarray(self.rep_devices), ("hosts",))
        self._row_sharding = NamedSharding(mesh, P("hosts"))
        self._reduce = jax.jit(
            lambda s: reduce_stats(s, self._t_idx),
            out_shardings=NamedSharding(mesh, P()),
        )

    def host_vector(self, **values) -> np.ndarray:
        """(F,) float32 vector from per-field keyword values; missing or
        None fields become NaN ("unknown")."""
        unknown = set(values) - set(self.fields)
        if unknown:
            raise ValueError(f"unknown fleet fields {sorted(unknown)}; have {self.fields}")
        out = np.full((len(self.fields),), np.nan, np.float32)
        for i, name in enumerate(self.fields):
            v = values.get(name)
            if v is not None:
                out[i] = float(v)
        return out

    def gather(self, host_vector: np.ndarray) -> dict:
        """The per-step reduction: contribute this host's vector, get
        the fleet reduction back (host numpy values, replicated — every
        process sees the same result). ALL processes must call this at
        the same step.

        With one process there is nothing to gather and the reduction
        runs on the host: the caller is the driver's log flush, where a
        device program would queue behind the step in flight and reading
        it back would drain the device's queue."""
        row = np.asarray(host_vector, np.float32).reshape(1, len(self.fields))
        if self.num_hosts == 1:
            with warnings.catch_warnings():
                # a field nobody reports is an all-NaN column: NaN, as jnp gives
                warnings.simplefilter("ignore", RuntimeWarning)
                return reduce_stats(row, self._t_idx, xp=np)
        with span("fleet_gather"):
            return self._collective(row)

    def _collective(self, row: np.ndarray) -> dict:
        """The cross-process path: this host's row onto its representative
        device, the jitted all_gather + reduce, the result back."""
        local = jax.device_put(row, self.rep_devices[self.process_index])
        stats = jax.make_array_from_single_device_arrays(
            (self.num_hosts, len(self.fields)), self._row_sharding, [local]
        )
        return jax.device_get(self._reduce(stats))

    def payload(self, stats: dict) -> dict:
        """Metrics-line fields from a `gather()` result: per-field
        `fleet/<name>_{min,mean,max,argmax}`, `straggler_skew`, and the
        host count. NaNs pass through — the sink scrubs them to null."""
        out = {"fleet_hosts": self.num_hosts}
        for i, name in enumerate(self.fields):
            out[f"fleet/{name}_min"] = float(stats["min"][i])
            out[f"fleet/{name}_mean"] = float(stats["mean"][i])
            out[f"fleet/{name}_max"] = float(stats["max"][i])
            out[f"fleet/{name}_argmax"] = int(stats["argmax"][i])
        out["straggler_skew"] = float(stats["straggler_skew"])
        return out


# -- out-of-band heartbeats ----------------------------------------------


def heartbeat_path(workdir: str, process_index: int) -> str:
    return os.path.join(workdir, f"heartbeat.p{process_index}.json")


class Heartbeat:
    """Atomically-replaced per-process liveness file (see module
    docstring). `beat()` cost is one small JSON write + rename; the
    driver calls it on log steps only."""

    def __init__(self, workdir: str, process_index: int = 0, trace_wall_t0: Optional[float] = None):
        os.makedirs(workdir, exist_ok=True)
        self.process_index = int(process_index)
        self.path = heartbeat_path(workdir, self.process_index)
        self.trace_wall_t0 = trace_wall_t0
        self._host = socket.gethostname()
        self._pid = os.getpid()

    def beat(self, step: int = 0, epoch: int = 0, **extra) -> None:
        rec = {
            "process": self.process_index,
            "host": self._host,
            "pid": self._pid,
            "time": time.time(),
            "step": int(step),
            "epoch": int(epoch),
        }
        if self.trace_wall_t0 is not None:
            rec["trace_wall_t0"] = self.trace_wall_t0
        rec.update(extra)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, self.path)  # readers never see a torn write


def read_heartbeats(workdir: str) -> dict[int, dict]:
    """{process_index: last heartbeat record} for every heartbeat file
    under `workdir`. Unparseable files (a crash mid-rename is made
    impossible by the atomic replace, but a foreign file isn't) are
    skipped rather than fatal — the merge path runs on crashed runs."""
    import glob as _glob

    out: dict[int, dict] = {}
    for path in sorted(_glob.glob(os.path.join(workdir, "heartbeat.p*.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
            out[int(rec["process"])] = rec
        except (ValueError, KeyError, OSError):
            continue
    return out


__all__ = [
    "FLEET_FIELDS",
    "FleetAggregator",
    "Heartbeat",
    "heartbeat_path",
    "read_heartbeats",
    "reduce_stats",
]
