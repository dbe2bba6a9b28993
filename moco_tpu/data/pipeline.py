"""Input pipeline: host decode → device transfer ring → device augment.

Replaces the reference's `DataLoader(workers=32)` + `TwoCropTransform`
(`main_moco.py:~L255-260`, `moco/loader.py`). Split of labor:

- host: index shuffling (per-epoch, seeded — the
  `DistributedSampler.set_epoch` equivalent); for datasets exposing the
  host-crop protocol (ImageFolder), torchvision-exact RandomResizedCrop
  boxes sampled against each image's ORIGINAL geometry and executed in
  the loader (decode once, crop/resize N times — native C++ pool when
  built, else PIL threads); otherwise decode to a fixed uint8 canvas;
- wire: uint8 crosses the host→device boundary (4x fewer bytes than
  fp32), sharded over the mesh's data axis;
- device: /255 + the remaining stochastic augmentation (jitter/gray/
  blur/flip/normalize — plus the crop itself on the canvas path),
  batched and jitted (`moco_tpu.data.augment`).

Token input (`DataConfig.input == "tokens"`) keeps the threads, the ring
and the epoch modes and changes what a row is: the host cuts two
independent `seq_len`-token windows from each document (span
`host_crop`, where images have `host_decode`), int32 ids and int32
lengths cross the wire, and no augmentation program is dispatched.

Two epoch modes, bit-identical in output (same seeded order, same step
rngs, same jitted augment):

- `epoch(e)` — the synchronous path: one producer thread runs decode →
  transfer → augment dispatch serially, a depth-2 prefetch queue
  overlaps that whole chain with the train step;
- `epoch(e, device=True)` — the overlapped path
  (`data/device_prefetch.py`): the producer thread decodes batch k+2
  while a dedicated transfer thread stages batch k+1 on device and the
  driver dispatches step k. Decode, wire, and compute pipeline instead
  of taking turns — the round-5 with-data ceiling lever (PROFILE.md).

Training pipelines use drop_last=True semantics (reference DataLoader) —
the queue's `K % global_batch == 0` invariant requires full batches. The
eval pipeline instead pads the tail batch and carries a validity mask so
the whole val split is scored (the reference evaluates the full split
too).

Every epoch iterator exposes `close()`: a consumer that abandons it
mid-epoch (preemption, a step-loop exception) MUST call it — before the
poison-pill close existed, the daemon producer stayed blocked on
`q.put` forever, holding the decode pool (the PR-5 leak fix; the train
driver closes on every epoch exit path).
"""

from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from moco_tpu.data.augment import (
    AugRecipe,
    PROBE_RECIPE,
    apply_recipe,
    get_recipe,
    two_crop_augment,
)
from moco_tpu.data.datasets import build_dataset, build_token_dataset
from moco_tpu.data.device_prefetch import DevicePrefetchRing
from moco_tpu.obs import comms
from moco_tpu.obs.trace import span as obs_span
from moco_tpu.parallel.dist import ProcessDataPartition
from moco_tpu.parallel.mesh import batch_sharding
from moco_tpu.utils import faults, retry
from moco_tpu.utils.config import DataConfig

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


def _producer_loop(src: Iterator, q: queue.Queue, stop: threading.Event) -> None:
    """Prefetch producer body. A MODULE-LEVEL function on purpose: the
    thread must not hold a reference to the iterator OBJECT, or the
    abandoned-iterator safety net (`__del__` flips the stop flag) could
    never fire — the thread would keep its owner alive forever."""
    try:
        for item in src:
            if not _responsive_put(q, stop, item):
                return
        _responsive_put(q, stop, _END)
    except BaseException as e:  # surface producer errors to the consumer
        _responsive_put(q, stop, e)
    finally:
        close = getattr(src, "close", None)
        if close is not None:
            try:
                close()
            except Exception:
                pass


class _PrefetchIterator:
    """Producer thread + bounded queue, with a poison-pill `close()`.

    The producer keeps `depth` items in flight; errors it raises are
    re-raised at the consumer's `next()`. `close()` is the leak fix: it
    flips the stop flag, drains the queue (so a `put`-blocked producer
    unblocks within one poll interval), enqueues a CLOSED pill (so a
    `get`-blocked consumer on another thread unblocks too), closes the
    source iterator (releasing the decode pool a suspended generator
    would pin), and joins the thread. Idempotent, safe mid-epoch.

    An iterator abandoned WITHOUT close() (a consumer that just drops
    it) still self-cleans: the producer thread does not reference this
    object, so GC runs `__del__`, which flips the stop flag and lets
    the thread exit on its next put poll.
    """

    def __init__(self, it: Iterator, depth: int = 2, name: str = "prefetch"):
        self._src = it
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=_producer_loop, args=(it, self._q, self._stop),
            daemon=True, name=name,
        )
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        item = self._q.get()
        if item is _END or item is _CLOSED:
            self._stop.set()  # later next() calls must not block
            raise StopIteration
        if isinstance(item, BaseException):
            self._stop.set()
            raise item
        return item

    def close(self, timeout: float = 5.0) -> None:
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        try:
            self._q.put_nowait(_CLOSED)  # unblock a get()-blocked consumer
        except queue.Full:
            pass
        self._thread.join(timeout=timeout)

    def __del__(self):
        self._stop.set()


def _prefetch(it: Iterator, depth: int = 2) -> _PrefetchIterator:
    """Run the producer in a thread, keeping `depth` batches in flight."""
    return _PrefetchIterator(it, depth=depth)


class HostBatch(NamedTuple):
    """One step's host-side product: local uint8 rows, not yet on
    device. `views` is (B_local, n_views, S, S, 3) on the host-crop
    path (n_views precropped images per row) or (B_local, H, W, 3) on
    the canvas path (`precropped=False`)."""

    step: int
    rng: jax.Array
    views: np.ndarray
    labels: Optional[np.ndarray]
    precropped: bool

    @property
    def wire_bytes(self) -> int:
        """uint8 payload this process puts on the wire for this batch."""
        n = int(self.views.nbytes)
        if self.labels is not None:
            n += int(self.labels.nbytes)
        return n


class TokenBatch(NamedTuple):
    """One step's host-side product on token input: this process's rows,
    `ids` (B_local, 2, seq_len) int32 with zeros past a window's end and
    `lengths` (B_local, 2) int32, one window a view."""

    step: int
    ids: np.ndarray
    lengths: np.ndarray

    @property
    def wire_bytes(self) -> int:
        return int(self.ids.nbytes) + int(self.lengths.nbytes)


class _HostPipeline:
    """Shared host-side machinery: dataset build, batch/steps accounting,
    decode pool, mesh sharding, seeded per-epoch shuffling."""

    def __init__(
        self,
        config: DataConfig,
        mesh: Mesh,
        seed: int = 0,
        dataset=None,
        train: bool = True,
        drop_last: bool = True,
    ):
        self.config = config
        self.mesh = mesh
        self.seed = seed
        if dataset is None and config.input == "tokens":
            dataset = build_token_dataset(config.dataset, config.seq_len)
        self.dataset = dataset or build_dataset(
            config.dataset,
            config.data_dir,
            config.image_size,
            train=train,
            num_workers=config.num_workers,
            cache_dir=config.cache_dir,
        )
        self.batch_size = config.global_batch
        if drop_last and len(self.dataset) < self.batch_size:
            raise ValueError(
                f"dataset of {len(self.dataset)} examples < global batch {self.batch_size}"
            )
        n = len(self.dataset)
        self.steps_per_epoch = n // self.batch_size if drop_last else -(-n // self.batch_size)
        self._pool = ThreadPoolExecutor(max_workers=max(config.num_workers, 1))
        # the wire sharding: batch rows over the data axis (mesh.py) —
        # the same layout the prefetch ring stages uint8 into
        self._sharding = batch_sharding(mesh)
        # Multi-host input sharding (DistributedSampler equivalent,
        # main_moco.py:~L258): this process decodes only the global-batch
        # rows owned by its addressable devices; single-host it holds all
        # rows, so one code path serves both.
        self._partition = ProcessDataPartition(self._sharding, self.batch_size)

    # -- host stage (decode; numpy out, nothing on device) ---------------

    def _host_batch(self, indices: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(images uint8 stack, labels int32) via the native C++ batch path
        when the dataset provides it, else the Python thread pool.

        The whole read runs under the retry layer (site `data.read`):
        a transient filesystem error — or an injected `io@site=data.read`
        fault — degrades to a logged retry instead of aborting the epoch
        through the prefetch thread."""

        def _load():
            faults.maybe_io_error("data.read")
            faults.maybe_delay("data.read")
            if hasattr(self.dataset, "load_batch"):  # native/loader.cc decode pool
                imgs, labels = self.dataset.load_batch(indices)
                return imgs, np.asarray(labels, np.int32)
            loads = list(self._pool.map(self.dataset.load, indices))
            return (
                np.stack([img for img, _ in loads]),
                np.asarray([l for _, l in loads], np.int32),
            )

        # span lands on the prefetch producer's thread track: decode
        # time that OVERLAPS the train step is visible as such in the
        # trace, instead of inflating the step's apparent data wait
        with obs_span("host_decode", n=len(indices)):
            return retry.retry_call(_load, site="data.read")

    def _local_crop_batch(
        self, global_indices: np.ndarray, epoch: int, step: int,
        n_crops: int, scale: tuple, out_size: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Host-crop path: sample n_crops RRC boxes per image against its
        original dims, decode once + crop/resize in the loader; returns
        this process's (B_local, n_crops, S, S, 3) uint8 rows + labels.

        The crop uniforms are drawn ONCE per step for the full global
        batch × crops from a (seed, epoch, step)-keyed generator, and
        each process slices its rows by GLOBAL POSITION — process-
        independent, so model-axis replica groups that span processes
        (which hold the SAME global rows) decode identical pixels. (A
        per-(row, crop) seeded Generator here cost ~0.24 ms each of pure
        seeding overhead — ~120 ms of serial host time per 256-image
        batch, scripts/profile_input.py.)"""
        local_idx = self._partition.local_indices(global_indices)

        def _read_dims():
            faults.maybe_io_error("data.read")
            return self.dataset.dims(local_idx)

        dims = retry.retry_call(_read_dims, site="data.read")
        from moco_tpu.data.datasets import draw_rrc_uniforms, rrc_boxes_from_uniforms

        rng = np.random.default_rng((self.seed, epoch, step))
        u = draw_rrc_uniforms(rng, self.batch_size * n_crops)
        pos = np.asarray(self._partition.local_positions, np.int64)
        flat = (pos[:, None] * n_crops + np.arange(n_crops)[None, :]).reshape(-1)
        u_local = {k: v[flat] for k, v in u.items()}
        boxes = rrc_boxes_from_uniforms(
            u_local, np.repeat(dims, n_crops, axis=0), scale=scale
        ).reshape(len(local_idx), n_crops, 4)
        with obs_span("host_decode", n=len(local_idx), crops=n_crops):
            faults.maybe_delay("data.read")
            raw, labels = retry.retry_call(
                self.dataset.load_crop_batch,
                local_idx,
                boxes,
                out_size,
                pool=self._pool,
                site="data.read",
            )
        return raw, np.asarray(labels, np.int32)

    # -- device stage (sharded uint8 device_put + labels) ----------------

    def _assemble_views(self, hb: HostBatch) -> tuple[list[jax.Array], Optional[jax.Array]]:
        """Sharded device_put of one host batch: per-crop uint8 views on
        the host-crop path (slicing the crop axis of an already-assembled
        global array would not be fully-addressable under multi-host),
        the single canvas array otherwise. Registers the `input.h2d`
        comms-ledger entry so the wire shows up in the byte tables next
        to the ICI collectives."""
        part = self._partition
        with comms.tag("input.h2d", "device_put", (hb.views, hb.labels), axis_size=1):
            if hb.precropped:
                views = [
                    part.assemble(np.ascontiguousarray(hb.views[:, c]))
                    for c in range(hb.views.shape[1])
                ]
            else:
                views = [part.assemble(hb.views)]
            labels = (
                part.assemble(hb.labels) if hb.labels is not None else None
            )
        return views, labels

    @property
    def decode_failures(self) -> int:
        """Cumulative undecodable samples seen by the underlying dataset
        (zero-filled slots) — the train driver writes this to
        metrics.jsonl so data corruption is visible, not silent."""
        return int(getattr(self.dataset, "decode_failures", 0))

    def _epoch_order(self, epoch: int) -> np.ndarray:
        """Seeded shuffle per (seed, epoch) — sampler.set_epoch equivalent."""
        return np.random.default_rng((self.seed, epoch)).permutation(len(self.dataset))

    def _epoch_rng(self, epoch: int) -> jax.Array:
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)

    @property
    def host_crops(self) -> bool:
        """Host-side RandomResizedCrop (decode-once/crop-N against the
        ORIGINAL image geometry — torchvision-exact distribution, no
        fixed-canvas clipping) when the dataset and config support it."""
        return self.config.host_rrc and hasattr(self.dataset, "load_crop_batch")

    # -- epoch assembly (shared by the two-crop/labeled pipelines) -------

    def _epoch_iter(self, host_gen, stage, device: bool, depth: Optional[int], donate: bool):
        """Wire one epoch's host generator + device stage into either
        mode (module docstring): sync = both on one producer thread;
        device=True = decode thread → transfer ring → consumer."""
        depth = 2 if depth is None else int(depth)
        if device:
            host_it = _prefetch(host_gen, depth=depth)
            return DevicePrefetchRing(
                host_it, lambda hb: stage(hb, donate), depth=depth
            )

        def gen():
            for hb in host_gen:
                out, _ = stage(hb, donate)
                yield out

        return _prefetch(gen(), depth=depth)


def _jit_pair(fn, donate_argnums: tuple):
    """(plain, donating) jitted variants of one augment fn. The donating
    variant recycles the consumed staging slot's HBM for the normalized
    output (prefetch_donate) — a separate executable, compiled only if
    donation is ever requested."""
    return jax.jit(fn), jax.jit(fn, donate_argnums=donate_argnums)


class TwoCropPipeline(_HostPipeline):
    """Iterable over {'im_q','im_k'} device batches for one epoch at a time."""

    def __init__(self, config: DataConfig, mesh: Mesh, seed: int = 0, dataset=None, train: bool = True):
        super().__init__(config, mesh, seed=seed, dataset=dataset, train=train, drop_last=True)
        self.tokens = config.input == "tokens"
        if self.tokens:
            if config.seq_len <= 0:
                raise ValueError("token input needs data.seq_len > 0")
            return  # no recipe, no augmentation program
        self.recipe: AugRecipe = get_recipe(
            config.aug_plus, config.image_size, crops_only=config.crops_only
        )
        recipe, out_size = self.recipe, config.image_size

        def _augment(rng, raw_uint8):
            with jax.named_scope("moco.augment.crop"):  # the crop reads what this converts
                images = raw_uint8.astype(jnp.float32) / 255.0
            return two_crop_augment(recipe, rng, images, out_size, mesh)

        self._augment, self._augment_donated = _jit_pair(_augment, (1,))

        # host-crop variant: images arrive already cropped to out_size;
        # the device applies everything in the recipe EXCEPT the crop
        nocrop = recipe._replace(crop=False)

        def _augment_precropped(rng, q_uint8, k_uint8):
            k_q, k_k = jax.random.split(rng)
            q = apply_recipe(nocrop, k_q, q_uint8.astype(jnp.float32) / 255.0, out_size, mesh)
            k = apply_recipe(nocrop, k_k, k_uint8.astype(jnp.float32) / 255.0, out_size, mesh)
            return {"im_q": q, "im_k": k}

        self._augment_precropped, self._augment_precropped_donated = _jit_pair(
            _augment_precropped, (1, 2)
        )

    def _host_gen(self, epoch: int):
        order, rng = self._epoch_order(epoch), self._epoch_rng(epoch)
        for step in range(self.steps_per_epoch):
            idx = order[step * self.batch_size : (step + 1) * self.batch_size]
            step_rng = jax.random.fold_in(rng, step)
            if self.host_crops:
                raw, _ = self._local_crop_batch(
                    idx, epoch, step, n_crops=2,
                    scale=self.recipe.crop_scale,
                    out_size=self.config.image_size,
                )
                yield HostBatch(step, step_rng, raw, None, precropped=True)
            else:
                raw, _ = self._host_batch(self._partition.local_indices(idx))
                yield HostBatch(step, step_rng, raw, None, precropped=False)

    def _stage(self, hb: HostBatch, donate: bool):
        views, _ = self._assemble_views(hb)
        # span closed BEFORE the batch is handed on: a generator/queue
        # suspends inside `with`, which would bill consumer time to it
        with obs_span("augment_dispatch", step=hb.step):
            if hb.precropped:
                aug = self._augment_precropped_donated if donate else self._augment_precropped
                out = aug(hb.rng, views[0], views[1])
            else:
                aug = self._augment_donated if donate else self._augment
                out = aug(hb.rng, views[0])
        return out, hb.wire_bytes

    def _token_gen(self, epoch: int):
        """Two independent windows of `seq_len` tokens from each document
        (a document shorter than the window is one whole view, padded).
        The window starts are drawn once a step for the global batch and
        sliced by global position, as the crop boxes are."""
        order, seq = self._epoch_order(epoch), self.config.seq_len
        positions = np.asarray(self._partition.local_positions, np.int64)
        for step in range(self.steps_per_epoch):
            idx = order[step * self.batch_size : (step + 1) * self.batch_size]
            local_idx = self._partition.local_indices(idx)
            u = np.random.default_rng((self.seed, epoch, step)).random((self.batch_size, 2))
            ids = np.zeros((len(local_idx), 2, seq), np.int32)
            lengths = np.zeros((len(local_idx), 2), np.int32)
            with obs_span("host_crop", n=len(local_idx)):
                for row, (index, pos) in enumerate(zip(local_idx, positions)):
                    doc = np.asarray(self.dataset.load_tokens(int(index)), np.int32)
                    span = min(len(doc), seq)
                    for view in range(2):
                        start = int(u[pos, view] * (len(doc) - span + 1))
                        ids[row, view, :span] = doc[start : start + span]
                        lengths[row, view] = span
            yield TokenBatch(step, ids, lengths)

    def _stage_tokens(self, tb: TokenBatch, donate: bool):
        part = self._partition
        with comms.tag("input.h2d", "device_put", (tb.ids, tb.lengths), axis_size=1):
            out = {
                name: {
                    "ids": part.assemble(np.ascontiguousarray(tb.ids[:, view])),
                    "lengths": part.assemble(np.ascontiguousarray(tb.lengths[:, view])),
                }
                for view, name in enumerate(("im_q", "im_k"))
            }
        return out, tb.wire_bytes

    def epoch(
        self,
        epoch: int,
        device: bool = False,
        depth: Optional[int] = None,
        donate: bool = False,
    ) -> Iterator[dict]:
        if self.tokens:
            return self._epoch_iter(
                self._token_gen(epoch), self._stage_tokens, device, depth, donate
            )
        return self._epoch_iter(self._host_gen(epoch), self._stage, device, depth, donate)


class LabeledPipeline(_HostPipeline):
    """Shuffled (images, labels) train batches with the probe transform
    (`main_lincls.py` train pipeline: RandomResizedCrop + flip + normalize)."""

    def __init__(self, config: DataConfig, mesh: Mesh, seed: int = 0, dataset=None):
        super().__init__(config, mesh, seed=seed, dataset=dataset, train=True, drop_last=True)
        base = get_recipe(config.aug_plus, config.image_size)
        self.recipe = PROBE_RECIPE._replace(mean=base.mean, std=base.std)
        recipe, out_size = self.recipe, config.image_size

        def _augment(rng, raw_uint8):
            images = raw_uint8.astype(jnp.float32) / 255.0
            return apply_recipe(recipe, rng, images, out_size)

        self._augment, self._augment_donated = _jit_pair(_augment, (1,))
        nocrop = recipe._replace(crop=False)

        def _augment_precropped(rng, raw_uint8):
            images = raw_uint8.astype(jnp.float32) / 255.0
            return apply_recipe(nocrop, rng, images, out_size)

        self._augment_precropped, self._augment_precropped_donated = _jit_pair(
            _augment_precropped, (1,)
        )

    def _host_gen(self, epoch: int):
        order, rng = self._epoch_order(epoch), self._epoch_rng(epoch)
        for step in range(self.steps_per_epoch):
            idx = order[step * self.batch_size : (step + 1) * self.batch_size]
            step_rng = jax.random.fold_in(rng, step)
            if self.host_crops:
                raw, labels = self._local_crop_batch(
                    idx, epoch, step, n_crops=1,
                    scale=self.recipe.crop_scale,
                    out_size=self.config.image_size,
                )
                yield HostBatch(step, step_rng, raw, labels, precropped=True)
            else:
                raw, labels = self._host_batch(self._partition.local_indices(idx))
                yield HostBatch(step, step_rng, raw, labels, precropped=False)

    def _stage(self, hb: HostBatch, donate: bool):
        views, labels = self._assemble_views(hb)
        with obs_span("augment_dispatch", step=hb.step):
            if hb.precropped:
                aug = self._augment_precropped_donated if donate else self._augment_precropped
            else:
                aug = self._augment_donated if donate else self._augment
            out = aug(hb.rng, views[0])
        return (out, labels), hb.wire_bytes

    def epoch(
        self,
        epoch: int,
        device: bool = False,
        depth: Optional[int] = None,
        donate: bool = False,
    ) -> Iterator[tuple]:
        return self._epoch_iter(self._host_gen(epoch), self._stage, device, depth, donate)


class EvalPipeline(_HostPipeline):
    """Deterministic center-crop (images, labels, valid_mask) batches for
    the linear probe (`main_lincls.py` val transform: Resize(256),
    CenterCrop(224)). The tail batch is padded to full size with repeats
    and masked so the *entire* split is scored — a truncated class-sorted
    val set would bias top-1 (the last classes would never be evaluated).
    """

    def __init__(self, config: DataConfig, mesh: Mesh, train: bool = False, dataset=None):
        super().__init__(config, mesh, dataset=dataset, train=train, drop_last=False)
        self.steps = self.steps_per_epoch

    def __iter__(self):
        recipe = get_recipe(self.config.aug_plus, self.config.image_size)
        n = len(self.dataset)
        out_size = self.config.image_size

        # uint8 crosses the host->device boundary (4x less transfer than
        # fp32); /255, center-crop, normalize run jitted on the sharded
        # array, like the train pipelines do
        @jax.jit
        def _prep(raw_uint8):
            x = raw_uint8.astype(jnp.float32) / 255.0
            # one decode geometry per run: the branch specializes the one
            # trace, it cannot retrigger (tail batches are padded to size)
            if x.shape[1] != out_size:  # mocolint: disable=JX004
                y0 = (x.shape[1] - out_size) // 2
                x = x[:, y0 : y0 + out_size, y0 : y0 + out_size]
            mean = jnp.asarray(recipe.mean, jnp.float32)
            std = jnp.asarray(recipe.std, jnp.float32)
            return (x - mean) / std

        def gen():
            part = self._partition
            for step in range(self.steps):
                start = step * self.batch_size
                idx = np.arange(start, min(start + self.batch_size, n))
                valid = len(idx)
                if valid < self.batch_size:  # pad the tail, mask the pads
                    idx = np.concatenate([idx, np.full(self.batch_size - valid, idx[-1])])
                mask = (np.arange(self.batch_size) < valid).astype(np.float32)
                # per-process decode of only this host's rows
                raw, labels = self._host_batch(part.local_indices(idx))
                yield (
                    _prep(part.assemble(raw)),
                    part.assemble(np.asarray(labels, np.int32)),
                    part.assemble(mask[part.local_positions]),
                )

        return _prefetch(gen(), depth=2)
