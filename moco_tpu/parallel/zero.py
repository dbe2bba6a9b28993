"""Weight-update (optimizer-state / parameter) sharding over the data axis.

TPU-native ZeRO, after "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (arXiv:2004.13336, the XLA/TPU paper
retrieved in PAPERS.md): in plain data parallelism every replica holds
the full optimizer state and applies the identical full weight update —
redundant memory AND redundant compute. Two stages live here:

**Stage 1** (`sharded_update`, the original): params stay replicated
between steps; inside the step

    grads --psum_scatter--> per-replica 1/n grad shard  (one collective,
                            same volume as the all-reduce it replaces)
    optimizer update on the shard only   (1/n state, 1/n update FLOPs)
    params <--all_gather-- updated shards

**Stage 2/3** (`BucketPlan` + the Zero23 step in core/moco.py): the
parameters themselves persist BETWEEN steps as `P(data)`-sharded flat
shards — same (n, m) layout as the stage-1 optimizer state — so the
at-rest replica cost of params_q + params_k + opt state is ~3/n of a
model instead of 2 + 1/n. The EMA key-encoder update becomes a
shard-local elementwise op (NO collective at all), the parameter
all_gather moves from the end of step k to the start of step k+1 where
the software-pipelined driver hoists it under step k's compute
(`AsyncParamGather`), and the gathered full params are donated to the
step so XLA frees them after the backward instead of keeping a second
replica alive.

Collectives are **bucketed**: leaves are greedily packed (in pytree
order, per dtype) into fusion buckets of ~`bucket_bytes`, ONE
all_gather / psum_scatter per bucket instead of per leaf — fewer
collective launches, big enough payloads to saturate ICI, and a
per-bucket `comms.tag` site (`zero.gather_q.b<i>`, `zero.scatter.b<i>`,
...) so the PR-4 ledger and the schedule sanitizer see each bucket.
The bucket transforms PRESERVE the per-leaf (n, m) partitioning —
element e of leaf L lands on the same replica row whether the
collective is bucketed or per-leaf — so the bucketed update is
bit-identical to stage 1's and the checkpoint layout stays per-leaf.

Each parameter leaf is flattened, zero-padded to a multiple of the axis
size, and viewed as (n, m): replica r owns row r. Optimizer state leaves
are stored GLOBALLY as (n, m) arrays sharded `P(data)` on the leading
dim, so checkpoints carry exactly each replica's rows and resume is
topology-stable for the same mesh (and host-side reshard helpers below
convert between layouts/mesh widths on resume).

**Layer-granular stage 2/3** (`GroupPlan` + the layer schedule in
core/moco.py, `parallel.zero_layer_granular`): the whole-tree gather
still materializes every full parameter at once inside the step, so
peak — not at-rest — memory caps the per-chip batch. The group plan
partitions the leaves into schedule-ordered layer groups (stem, blocks,
head), each with its own fusion buckets and its own
`comms/zero.gather.<group>` ledger site; the step gathers each group
just-in-time and the rematerialized segment boundaries free it after
its forward/backward contribution, so the transient cost drops from
full-tree to at most two adjacent groups (the one-group-ahead
prefetch).

Element-wise optimizers only (SGD momentum, AdamW): their update is
position-independent, so updating a flat shard equals sharding the full
update. LARS is NOT eligible (per-layer trust ratios need whole-tensor
norms) — callers must reject it.
"""

from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from moco_tpu import obs
from moco_tpu.obs import comms
from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.utils import faults


def padded_cols(numel: int, n: int) -> int:
    """Columns of the (n, m) sharded view of a flat leaf of `numel`."""
    return -(-max(numel, 1) // n)


def shard_template(tree, n: int):
    """(n, m)-shaped zero arrays matching each leaf's sharded flat layout
    — what `tx.init` consumes to build a SHARDED optimizer state."""
    return jax.tree.map(
        lambda x: jnp.zeros((n, padded_cols(x.size, n)), x.dtype), tree
    )


def scatter_mean(x: jax.Array, axis_name: str = DATA_AXIS) -> jax.Array:
    """Mean-reduce a full local grad leaf across the axis AND keep only
    this replica's (m,) shard — one psum_scatter, the fused collective
    that makes sharded weight update cost no extra communication."""
    n = lax.axis_size(axis_name)
    m = padded_cols(x.size, n)
    flat = jnp.pad(x.reshape(-1), (0, n * m - x.size))
    return lax.psum_scatter(flat, axis_name, scatter_dimension=0, tiled=True) / n


def local_shard(x: jax.Array, axis_name: str = DATA_AXIS) -> jax.Array:
    """This replica's (m,) rows of a replicated full leaf."""
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    m = padded_cols(x.size, n)
    flat = jnp.pad(x.reshape(-1), (0, n * m - x.size))
    return lax.dynamic_slice(flat, (r * m,), (m,))

def unshard(shard: jax.Array, like: jax.Array, axis_name: str = DATA_AXIS) -> jax.Array:
    """all_gather the (m,) shards back into a full leaf shaped `like`."""
    full = lax.all_gather(shard, axis_name, tiled=True)
    return full[: like.size].reshape(like.shape).astype(like.dtype)


def squeeze_opt_state(opt_state):
    """Local view inside shard_map: (1, m) sharded leaves -> (m,);
    scalars (e.g. Adam's count) pass through."""
    return jax.tree.map(lambda x: x[0] if x.ndim == 2 else x, opt_state)


def expand_opt_state(opt_state):
    """Inverse of squeeze: (m,) leaves -> (1, m) for the P(data) out-spec."""
    return jax.tree.map(lambda x: x[None] if x.ndim == 1 else x, opt_state)


def sharded_update(tx, grads, opt_state, trainable, axis_name: str = DATA_AXIS):
    """Stage-1 sharded weight update: returns (new_trainable_full,
    new_opt_state_local_expanded). Call inside shard_map; `grads` are the
    LOCAL (pre-reduction) gradients, `trainable` the replicated params,
    `opt_state` the local (1, m)/scalar view of the sharded state."""
    n = lax.axis_size(axis_name)
    with comms.tag("zero.grad_reduce_scatter", "psum_scatter", grads, n):
        grad_sh = jax.tree.map(lambda g: scatter_mean(g, axis_name), grads)
    param_sh = jax.tree.map(lambda p: local_shard(p, axis_name), trainable)
    updates, new_opt = tx.update(grad_sh, squeeze_opt_state(opt_state), param_sh)
    new_param_sh = jax.tree.map(lambda p, u: p + u, param_sh, updates)
    with comms.tag("zero.params_all_gather", "all_gather", new_param_sh, n):
        new_trainable = jax.tree.map(
            lambda s, p: unshard(s, p, axis_name), new_param_sh, trainable
        )
    return new_trainable, expand_opt_state(new_opt)


# ---------------------------------------------------------------------------
# Stage 2/3: persistent shard layout + bucketed collectives
# ---------------------------------------------------------------------------

DEFAULT_BUCKET_MB = 4.0


def shard_tree(tree, n: int):
    """Full-shape param tree -> the persistent (n, m) sharded-flat layout
    (per leaf; row r belongs to replica r). jnp ops, jit-safe."""
    def _one(x):
        m = padded_cols(x.size, n)
        return jnp.pad(x.reshape(-1), (0, n * m - x.size)).reshape(n, m)

    return jax.tree.map(_one, tree)


def shard_leaf_host(x, n: int) -> np.ndarray:
    """Host (numpy) variant of `shard_tree` for one leaf — checkpoint
    resharding runs on restored host arrays, no mesh required."""
    x = np.asarray(x)
    m = padded_cols(x.size, n)
    return np.pad(x.reshape(-1), (0, n * m - x.size)).reshape(n, m)


def unshard_leaf_host(x, shape, dtype=None) -> np.ndarray:
    """Host inverse: (n, m) sharded-flat -> the full leaf of `shape`."""
    x = np.asarray(x)
    size = int(np.prod(shape)) if shape else 1
    out = x.reshape(-1)[:size].reshape(shape)
    return out.astype(dtype) if dtype is not None else out


def unshard_tree_host(tree, template):
    """Gather a whole persistently-sharded param tree back to full
    shapes on the host (numpy) — the eval/export one-shot gather.
    `template` leaves provide shape/dtype (e.g. from `jax.eval_shape`
    of the encoder init). Single-controller: every (n, m) leaf must be
    host-addressable (true for the eval tools, which run one process)."""
    return jax.tree.map(
        lambda x, t: unshard_leaf_host(x, t.shape, t.dtype), tree, template
    )


@dataclasses.dataclass(frozen=True)
class _LeafSlot:
    """One leaf's place inside a fusion bucket."""

    index: int  # position in jax.tree.leaves order
    size: int  # true element count
    m: int  # padded cols = padded_cols(size, n)
    offset: int  # column offset inside the bucket's (n, total_m) view
    shape: tuple
    dtype: Any


@dataclasses.dataclass(frozen=True)
class Bucket:
    slots: tuple
    total_m: int
    dtype: Any


class BucketPlan:
    """Static packing of a param tree's leaves into fusion buckets.

    Greedy in pytree-leaves order, one open bucket per dtype (leaves of
    different dtypes cannot share a concatenated payload); a bucket
    closes once it holds ≥ `bucket_bytes` of shard payload, so the last
    bucket per dtype is the ragged tail (possibly much smaller). A leaf
    larger than `bucket_bytes` gets its own bucket.

    The transforms preserve per-leaf (n, m) partitioning: bucket row r
    is the concatenation of every member leaf's row r, so one
    collective per bucket moves exactly what per-leaf collectives would
    — same bits per replica, fewer launches.
    """

    def __init__(self, leaves: Sequence, n: int, bucket_bytes: Optional[int] = None):
        """`leaves`: shape/dtype-carrying leaf descriptors (e.g. from
        `jax.eval_shape`), in `jax.tree.leaves` order of the tree the
        runtime methods will be fed."""
        self.n = int(n)
        bucket_bytes = int(
            bucket_bytes
            if bucket_bytes is not None
            else DEFAULT_BUCKET_MB * 1024 * 1024
        )
        buckets: list[Bucket] = []
        open_slots: dict = {}  # dtype -> (slots list, cols, bytes)
        for i, leaf in enumerate(leaves):
            shape = tuple(leaf.shape)
            dtype = jnp.dtype(leaf.dtype)
            size = int(np.prod(shape)) if shape else 1
            m = padded_cols(size, self.n)
            slots, cols, nbytes = open_slots.setdefault(dtype, ([], 0, 0))
            slots.append(
                _LeafSlot(index=i, size=size, m=m, offset=cols, shape=shape, dtype=dtype)
            )
            cols += m
            nbytes += m * dtype.itemsize  # shard payload per replica
            if nbytes >= bucket_bytes:
                buckets.append(Bucket(slots=tuple(slots), total_m=cols, dtype=dtype))
                del open_slots[dtype]
            else:
                open_slots[dtype] = (slots, cols, nbytes)
        for dtype, (slots, cols, _) in open_slots.items():  # ragged tails
            buckets.append(Bucket(slots=tuple(slots), total_m=cols, dtype=dtype))
        self.buckets = tuple(buckets)
        self.num_leaves = len(list(leaves))

    # -- persistent-layout construction ---------------------------------
    def shard_leaves(self, full_leaves: Sequence) -> list:
        """Full leaves -> (n, m) persistent layout, leaf-by-leaf."""
        return [
            jnp.pad(x.reshape(-1), (0, self.n * padded_cols(x.size, self.n) - x.size))
            .reshape(self.n, padded_cols(x.size, self.n))
            for x in full_leaves
        ]

    # -- in-step transforms (call inside shard_map) ---------------------
    def gather(self, shard_leaves: Sequence, site: str, axis_name: str = DATA_AXIS) -> list:
        """Local (m,) shards -> FULL leaves, one tiled all_gather per
        bucket, each under its own `comms.tag` site `<site>.b<i>`."""
        out: list = [None] * self.num_leaves
        n = self.n
        for bi, bucket in enumerate(self.buckets):
            concat = jnp.concatenate([shard_leaves[s.index] for s in bucket.slots])
            with comms.tag(f"{site}.b{bi}", "all_gather", concat, n):
                full = lax.all_gather(concat, axis_name, tiled=True)
            rows = full.reshape(n, bucket.total_m)
            for s in bucket.slots:
                flat = rows[:, s.offset : s.offset + s.m].reshape(-1)[: s.size]
                out[s.index] = flat.reshape(s.shape).astype(s.dtype)
        return out

    def scatter_mean(
        self, grad_leaves: Sequence, site: str = "zero.scatter", axis_name: str = DATA_AXIS
    ) -> list:
        """Full local (pre-reduction) grad leaves -> this replica's (m,)
        reduced shards, one tiled psum_scatter per bucket. Bit-identical
        to per-leaf `scatter_mean`: element -> chunk assignment is
        unchanged, so the ring reduction order per element is too."""
        out: list = [None] * self.num_leaves
        n = self.n
        for bi, bucket in enumerate(self.buckets):
            parts = []
            for s in bucket.slots:
                g = grad_leaves[s.index].reshape(-1)
                parts.append(jnp.pad(g, (0, n * s.m - s.size)).reshape(n, s.m))
            block = jnp.concatenate(parts, axis=1).reshape(-1)
            with comms.tag(f"{site}.b{bi}", "psum_scatter", block, n):
                shard = (
                    lax.psum_scatter(block, axis_name, scatter_dimension=0, tiled=True)
                    / n
                )
            for s in bucket.slots:
                out[s.index] = shard[s.offset : s.offset + s.m]
        return out

    def describe(self) -> list[dict]:
        """Static bucket table (bench/report surface)."""
        return [
            {
                "bucket": i,
                "leaves": len(b.slots),
                "dtype": str(b.dtype),
                "shard_bytes": b.total_m * b.dtype.itemsize,
            }
            for i, b in enumerate(self.buckets)
        ]


@dataclasses.dataclass(frozen=True)
class _Group:
    """One layer group of a GroupPlan: a named, contiguous-in-schedule
    slice of the tree's leaves with its own fusion-bucket plan."""

    name: str
    indices: tuple  # leaf positions in jax.tree.leaves order
    plan: BucketPlan
    full_bytes: int  # bytes of the group's FULL (unsharded) leaves


class GroupPlan:
    """Layer-granular extension of `BucketPlan`: an ordered partition of
    a param tree's leaves into named layer groups, each with its own
    bucket plan, so the step can gather ONE group's full params
    just-in-time (site `zero.gather.<prefix>_<group>.b<i>`) instead of
    materializing the whole tree at once.

    The partition must cover every leaf exactly once — a leaf the group
    map misses would silently never be gathered, so that is a
    construction-time error, not a runtime surprise. Group order is the
    schedule order (stem → stages → head); `peak_full_bytes` is the
    analytic transient high-water mark of the one-group-ahead pipeline:
    the largest sum of two ADJACENT groups' full bytes (group g's params
    are still live while group g+1 prefetches).
    """

    def __init__(
        self,
        leaves: Sequence,
        groups: Sequence,
        n: int,
        bucket_bytes: Optional[int] = None,
    ):
        """`leaves`: shape/dtype descriptors in `jax.tree.leaves` order;
        `groups`: ordered `(name, leaf_indices)` pairs partitioning
        `range(len(leaves))`."""
        self.n = int(n)
        leaves = list(leaves)
        seen: set = set()
        built = []
        for name, indices in groups:
            indices = tuple(int(i) for i in indices)
            overlap = seen.intersection(indices)
            if overlap:
                raise ValueError(
                    f"group {name!r} re-claims leaves {sorted(overlap)}"
                )
            seen.update(indices)
            full_bytes = 0
            for i in indices:
                shape = tuple(leaves[i].shape)
                size = int(np.prod(shape)) if shape else 1
                full_bytes += size * jnp.dtype(leaves[i].dtype).itemsize
            built.append(
                _Group(
                    name=str(name),
                    indices=indices,
                    plan=BucketPlan([leaves[i] for i in indices], n, bucket_bytes),
                    full_bytes=full_bytes,
                )
            )
        missing = sorted(set(range(len(leaves))) - seen)
        if missing:
            raise ValueError(f"group map misses leaves {missing}")
        self.groups = tuple(built)
        self.num_leaves = len(leaves)

    def group_shards(self, shard_leaves: Sequence, gi: int) -> list:
        """The (m,)/(n, m) shard leaves belonging to group `gi`, in the
        group's own leaf order (what `gather_group` consumes)."""
        return [shard_leaves[i] for i in self.groups[gi].indices]

    def gather_group(
        self,
        group_shard_leaves: Sequence,
        gi: int,
        site_prefix: str = "zero.gather",
        axis_name: str = DATA_AXIS,
    ) -> list:
        """One group's local shards -> its FULL leaves (group leaf
        order), bucketed all_gathers under the group-named ledger site
        `<site_prefix>.<group>` — the per-group seam the comms ledger
        and the schedule sanitizer observe."""
        g = self.groups[gi]
        return g.plan.gather(
            group_shard_leaves, site=f"{site_prefix}.{g.name}", axis_name=axis_name
        )

    def scatter_leaves(self, full_leaves: Sequence, gi: int) -> list:
        """Full leaves of group `gi` -> (n, m) persistent layout."""
        return self.groups[gi].plan.shard_leaves(full_leaves)

    def peak_full_bytes(self) -> int:
        """Transient full-param high-water mark of the one-group-ahead
        schedule: max over adjacent group pairs (a single group when
        there is only one)."""
        sizes = [g.full_bytes for g in self.groups]
        if not sizes:
            return 0
        if len(sizes) == 1:
            return sizes[0]
        return max(a + b for a, b in zip(sizes, sizes[1:]))

    def total_full_bytes(self) -> int:
        return sum(g.full_bytes for g in self.groups)

    def describe(self) -> list[dict]:
        """Static per-group table (bench/report surface)."""
        return [
            {
                "group": g.name,
                "leaves": len(g.indices),
                "buckets": len(g.plan.buckets),
                "full_bytes": g.full_bytes,
            }
            for g in self.groups
        ]


class AsyncParamGather:
    """Hoists the stage-2/3 per-bucket params all_gather for step k+1
    under step k's compute — the software-pipelined driver's wire for
    the weight-update collectives.

    Two contracts, learned the hard way on the 8-virtual-device mesh:

    1. DISPATCH STAYS ON THE CALLER'S THREAD. `submit()` itself
       enqueues the jitted gather (jax dispatch is async and returns
       immediately): two threads racing `Execute` over the same
       multi-device set can enqueue in different per-device orders and
       deadlock the collective rendezvous — observed as a wedged scalar
       all-reduce with ranks 0-2 never arriving. Every multi-device
       executable in the driver (step, augment, gather) is enqueued
       from one thread, preserving a single per-device order.
    2. `take()` NEVER WAITS FOR DEVICE COMPLETION. The gathered tree is
       an async value; jax's dependency tracking orders step k+1 behind
       the gather on-device, and blocking the host on readiness would
       re-serialize the very pipeline the hoist exists to build. What
       `take()` waits for is only the stall the worker ABSORBS off the
       critical path: the deterministic `delay@site=zero.gather` fault
       — the synthetic slow collective the overlap smoke injects.

    `overlap` reports how much of that absorbed stall hid under the
    driver's iteration (dispatches, input wait, the in-flight
    throttle):

        overlap = 1 - wait / duration    (clamped to [0, 1];
                  None when nothing was absorbed — no stall, nothing
                  to hide; DEVICE-side gather/compute overlap is read
                  from the merged trace, where the worker's
                  `zero_gather` span covers delay + time-to-ready)

    After handing the result over, the worker ripens it
    (block_until_ready) purely so the trace span shows the gather's
    real extent; an async error in the gather then surfaces where jax
    always surfaces it — at the consumer — not on this thread.

    Thread hygiene (mocolint JX011 contract): bounded handoff queues,
    poison-pill `close()` that joins the worker, pre-handoff errors
    propagate to `take()` instead of dying silently on the thread.
    """

    FAULT_SITE = "zero.gather"

    def __init__(self, gather_fn: Callable):
        self._gather_fn = gather_fn
        self._submit: queue_mod.Queue = queue_mod.Queue(maxsize=1)
        self._done: queue_mod.Queue = queue_mod.Queue(maxsize=1)
        self._outstanding = 0  # submits not yet taken (driver thread only)
        self._closed = False
        self.last_overlap: Optional[float] = None
        self.last_duration: Optional[float] = None
        self._thread = threading.Thread(
            target=self._run, name="zero-param-gather", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while True:
            item = self._submit.get()
            if item is None:  # poison pill
                return
            out, step = item
            t0 = time.perf_counter()
            handed = False
            try:
                with obs.span("zero_gather", step=step):
                    faults.maybe_delay(self.FAULT_SITE)
                    self._done.put(("ok", out, time.perf_counter() - t0))
                    handed = True
                    # ripen AFTER the hand-off: take() must not wait for
                    # device completion (contract 2 in the class doc);
                    # the span end then marks when the gather was truly
                    # ready, which is what the merged trace overlays
                    # against the driver's step spans
                    jax.block_until_ready(out)
            except BaseException as e:
                if not handed:  # surface on take(), not the thread
                    self._done.put(("err", e, time.perf_counter() - t0))
                # post-hand-off failures are async-value errors; they
                # surface at the consumer exactly as un-hoisted jax would

    def submit(self, state, step: int = 0) -> None:
        """Enqueue the gather for `state` (the params step k+1 will
        consume) on THIS thread — see the class docstring for why the
        dispatch must not move to the worker — then hand the async
        result to the worker to ripen. Exactly one submit must be
        outstanding per take."""
        if self._closed:
            raise RuntimeError("AsyncParamGather is closed")
        out = self._gather_fn(state)
        self._outstanding += 1
        self._submit.put((out, step))

    def take(self):
        """Block until the worker has absorbed the submitted gather's
        stall; returns the (async) gathered tree. Updates
        `last_overlap`/`last_duration`."""
        t0 = time.perf_counter()
        kind, payload, duration = self._done.get()
        self._outstanding -= 1
        wait = time.perf_counter() - t0
        self.last_duration = duration
        self.last_overlap = (
            max(0.0, min(1.0, 1.0 - wait / duration))
            # sub-ms "absorption" is span/queue overhead, not a stall —
            # reporting a ratio of noise would read as a real gauge
            if duration > 1e-3
            else None
        )
        if kind == "err":
            raise payload
        return payload

    def resubmit(self, state, step: int = 0) -> None:
        """Drop any parked result (poisoned lineage after a NaN
        rollback) and gather `state` instead."""
        while self._outstanding:
            try:
                self.take()
            except Exception:
                pass  # a poisoned gather's error dies with its lineage
        self.submit(state, step)

    def payload(self) -> dict:
        """Metrics-line fields: the hoisted gather's overlap efficiency
        (None until the first take)."""
        return {"overlap/zero": self.last_overlap}

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._submit.put(None)
        self._thread.join(timeout=30.0)
