"""Per-process (multi-host) data sharding.

The reference shards input across ranks with `DistributedSampler`
(`main_moco.py:~L258`): each of the 8 GPU processes loads 1/8 of every
batch. The JAX equivalent on a multi-host pod: each host process decodes
ONLY the rows of the global batch that land on its addressable devices,
then the per-host shards are assembled into one global `jax.Array`
(`jax.make_array_from_single_device_arrays`) that the SPMD train step
consumes exactly as if a single controller had `device_put` the whole
batch.

`ProcessDataPartition` computes the row ranges once from the batch
sharding itself (not from process arithmetic), so any mesh layout —
1-D data, (data, model) with replication over the model axis,
multi-slice hybrid meshes — gets a correct, collision-free partition:
the sharding's `devices_indices_map` is the single source of truth.
On a single process it degenerates to "load everything", so the same
code path runs everywhere (and is exercised by every CI test).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding


def device_row_ranges(
    sharding: NamedSharding, global_batch: int
) -> dict[jax.Device, tuple[int, int]]:
    """Map every device in the sharding to its [start, stop) row range of
    the global batch's leading dimension. Devices that hold replicas of
    the same rows (e.g. across a model axis) map to the same range."""
    imap = sharding.devices_indices_map((global_batch,))
    out = {}
    for d, idx in imap.items():
        sl = idx[0]
        start = 0 if sl.start is None else int(sl.start)
        stop = global_batch if sl.stop is None else int(sl.stop)
        out[d] = (start, stop)
    return out


class ProcessDataPartition:
    """This process's slice of every global batch, plus the assembler
    that turns host-decoded local rows into the global sharded array.

    `addressable_devices` overrides the real process boundary — tests
    use it to simulate multi-host partitions on a single process.
    """

    def __init__(
        self,
        sharding: NamedSharding,
        global_batch: int,
        addressable_devices: Optional[Sequence[jax.Device]] = None,
    ):
        self.sharding = sharding
        self.global_batch = global_batch
        ranges = device_row_ranges(sharding, global_batch)
        if addressable_devices is None:
            addressable_devices = sharding.addressable_devices
        mine = {d: ranges[d] for d in ranges if d in set(addressable_devices)}
        if not mine:
            raise ValueError("no addressable devices in sharding")
        # unique row ranges this host must decode (replicas share ranges)
        uniq = sorted(set(mine.values()))
        self.local_positions = (
            np.concatenate([np.arange(a, b) for a, b in uniq])
            if uniq
            else np.zeros((0,), np.int64)
        )
        offsets, off = {}, 0
        for a, b in uniq:
            offsets[(a, b)] = off
            off += b - a
        self.local_rows = off
        # deterministic device order for the assembled shard list
        self._dev_ranges = [
            (d, mine[d], offsets[mine[d]])
            for d in sorted(mine, key=lambda d: d.id)
        ]

    @property
    def is_trivial(self) -> bool:
        """True when this process holds every row (single-host case)."""
        return self.local_rows == self.global_batch

    def local_indices(self, global_indices: np.ndarray) -> np.ndarray:
        """Dataset indices this process must load for one step, given the
        step's global-batch index array (identical on every host — the
        epoch shuffle is seeded)."""
        return np.asarray(global_indices)[self.local_positions]

    def assemble(self, local_data: np.ndarray) -> jax.Array:
        """Global sharded array from this process's decoded rows
        (row i of `local_data` is global row `local_positions[i]`)."""
        if local_data.shape[0] != self.local_rows:
            raise ValueError(
                f"expected {self.local_rows} local rows, got {local_data.shape[0]}"
            )
        shape = (self.global_batch,) + tuple(local_data.shape[1:])
        # the ONE intentional per-step H2D site (mocolint JX002
        # allowlist): the device prefetch ring calls this off-thread so
        # the transfer overlaps compute, and accounts the bytes to the
        # `input.h2d` comms ledger — eager host code, uint8 on the wire
        arrays = [
            jax.device_put(local_data[off : off + (b - a)], d)  # mocolint: disable=JX002
            for d, (a, b), off in self._dev_ranges
        ]
        return jax.make_array_from_single_device_arrays(shape, self.sharding, arrays)


def maybe_initialize_multihost() -> bool:
    """Auto-detect a multi-host launch and run the rendezvous.

    The reference requires the user to pass `--dist-url/--world-size/
    --rank` (`main_moco.py:~L70-85`); on TPU pods the coordinator is
    discoverable, so the driver just calls this. Returns True when
    `jax.distributed.initialize` was invoked. Detection: any of the
    standard coordinator variables, or an explicit MOCO_MULTIHOST=1.
    """
    import os

    if jax.distributed.is_initialized():
        return False
    env = os.environ
    wants = (
        env.get("MOCO_MULTIHOST") == "1"
        or "JAX_COORDINATOR_ADDRESS" in env
        or "COORDINATOR_ADDRESS" in env
        or "MEGASCALE_COORDINATOR_ADDRESS" in env
    )
    if not wants:
        return False
    from moco_tpu.parallel.mesh import initialize_multihost

    initialize_multihost()
    return True
