"""AOT-compiled embedding inference over the exported encoder.

Training compiles one step shape and amortizes it over an epoch;
serving sees arbitrary request sizes, and a `jax.jit` that traces per
shape would recompile on live traffic — exactly the
recompile-after-warmup class mocolint's JX004 and the runtime
`RecompileGuard` exist to abort. The engine therefore compiles *ahead
of time*: one executable per padded batch bucket
(`jit(...).lower(shapes).compile()`, default buckets {1, 8, 32, 128}),
requests pad up to the next bucket, and after :meth:`mark_warm` any
shape that would need a fresh trace raises :class:`EngineRecompileError`
instead of silently compiling. `recompiles_after_warmup` is the gauge
the serve smoke asserts at zero across mixed request sizes.

Graph: uint8 images → /255 → per-channel normalize (the eval recipe
`knn.extract_features` uses) → module forward in bf16 (the serving
default — inference tolerates bf16 activations; params stay f32) →
f32 cast → L2-normalize. `engine_quant` selects the quantization tier
at this same seam (`int8=True` is the back-compat spelling of "w8"):

- **w8** — weight-only PTQ: the encoder's matmul/conv kernels are
  stored int8 (symmetric per-output-channel,
  :func:`quantize_params_int8`) and dequantized inside each bucket's
  executable; matmuls still run f32. ~4x at-rest param memory.
- **w8a8** — activation-quantized int8 end-to-end (serve/quant.py):
  a calibration artifact (per-tensor activation ranges from a held-out
  sample run through the f32 encoder at this exact preprocessing seam)
  supplies symmetric input scales, and every plain conv/dense runs
  int8×int8→int32 (`preferred_element_type=jnp.int32`) with one f32
  rescale at the layer boundary. True int8 kernels are tpu/gpu-only;
  CPU runs the bit-faithful scaled-integer emulation (quant.py module
  docstring — the bf16 story again), so cosine/recall are testable on
  the CPU smoke while the arithmetic factor is an accelerator claim.

All quantized trees (int8 params, weight scales, activation scales)
are passed as call ARGUMENTS to the per-bucket executables, never
closure constants — XLA would constant-fold `int8 · scale` straight
back into f32 constants and silently undo the at-rest saving. The
module is whatever representation the
deployment serves: the FULL encoder (backbone + projection head, the
`load_serving_encoder` default) embeds into the negative queue's space
so the index can hold the trained dictionary, while a bare backbone
serves kNN-style features. Input buffers are donated on backends with
donation support and the donation is *audited*: :meth:`donation_audit`
verifies post-hoc that each bucket's input buffer was actually consumed
(deleted) by its call, so a silent donation regression (e.g. a wrapper
holding a reference) shows up as a boolean, not a slow leak. On the
quantized tiers the audit extends to the quantized parameter trees:
the donated input must still be consumed per bucket exactly as on the
f32 path, while the int8 param/scale trees — reused by every later
call — must SURVIVE it (`qtree:<bucket>` audit entries; an accidental
donation there would be a use-after-free on the next request, and
serve_smoke fails loudly on any False entry).

Encoder side: the *key* (EMA) encoder by default — serving wants the
slow-moving stable representation ("How to Scale Your EMA",
arXiv:2307.13813), while probes/export keep the query side. The loader
reuses `lincls.load_pretrained_backbone` (side="k"), so ZeRO-2/3
checkpoints unshard through the same one-shot host gather as every
other eval tool.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import time

from moco_tpu.obs.trace import span as obs_span
from moco_tpu.ops.losses import l2_normalize
from moco_tpu.utils import faults

DEFAULT_BUCKETS = (1, 8, 32, 128)


class EngineRecompileError(RuntimeError):
    """A batch shape arrived after warmup that has no AOT executable —
    the serving mirror of analysis/runtime.py's RecompileError."""


def quantize_params_int8(params):
    """Weight-only int8 PTQ of the encoder's matmul/conv kernels:
    symmetric per-output-channel scales (`s = max|w| / 127` over all
    but the last axis) on every floating leaf with ndim >= 2; biases,
    scalars, and BN stats pass through untouched. Returns
    (int8_tree, scale_tree) sharing the params treedef — unquantized
    leaves ride along with a scalar scale of 1 so the two trees always
    zip. Dequantization happens *inside* the jitted forward with the
    quantized tree passed as a call ARGUMENT, not a closure constant:
    XLA constant-folds a baked `int8_const * scale` straight back into
    an f32 constant, which would silently undo the ~4x at-rest saving
    the PTQ exists for."""
    flat, treedef = jax.tree_util.tree_flatten(params)
    q_flat, s_flat = [], []
    for leaf in flat:
        leaf = jnp.asarray(leaf)
        if leaf.ndim >= 2 and jnp.issubdtype(leaf.dtype, jnp.floating):
            axes = tuple(range(leaf.ndim - 1))
            s = jnp.max(jnp.abs(leaf).astype(jnp.float32), axis=axes, keepdims=True) / 127.0
            s = jnp.where(s <= 0, jnp.float32(1.0), s)
            q_flat.append(
                jnp.clip(jnp.round(leaf.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
            )
            s_flat.append(s)
        else:
            q_flat.append(leaf)
            s_flat.append(jnp.ones((), jnp.float32))
    return (
        jax.tree_util.tree_unflatten(treedef, q_flat),
        jax.tree_util.tree_unflatten(treedef, s_flat),
    )


def dequantize_params(qparams, scales):
    """The in-graph inverse of `quantize_params_int8` (int8 leaves
    rescale to f32; pass-through leaves come back untouched)."""
    return jax.tree_util.tree_map(
        lambda w, s: w.astype(jnp.float32) * s if w.dtype == jnp.int8 else w,
        qparams,
        scales,
    )


def load_serving_encoder(
    workdir: str, config=None, side: str = "k"
) -> tuple[Any, Any, Any, np.ndarray, int, Any]:
    """(encoder_module, params, batch_stats, queue, queue_ptr, config)
    for serving from a pretraining checkpoint — the key (EMA) side by
    default, and the FULL encoder (backbone + projection head): serving
    embeds into the same space the negative queue lives in, so the
    checkpoint's dictionary rows load straight into an EmbeddingIndex
    (`EmbeddingIndex.from_train_queue`) and `/neighbors` is literally
    the training look-up as a product. On accelerator backends the
    encoder is rebuilt in bf16 regardless of the training compute dtype
    (the serving default; params stay f32); CPU keeps f32 — XLA:CPU
    *emulates* bf16 at a measured ~50x slowdown, which would poison the
    CPU smokes. ZeRO-2/3 checkpoints unshard
    through `lincls.restore_pretrain_state`, the shared eval-side
    path."""
    from moco_tpu.core.moco import build_encoder
    from moco_tpu.lincls import restore_pretrain_state

    if side not in ("q", "k"):
        raise ValueError(f"side must be 'q' or 'k', got {side!r}")
    state, config = restore_pretrain_state(workdir, config, unshard=(side,))
    serve_dtype = (
        "bfloat16" if jax.default_backend() in ("tpu", "gpu") else "float32"
    )
    encoder = build_encoder(dataclasses.replace(config.moco, compute_dtype=serve_dtype))
    params = state.params_k if side == "k" else state.params_q
    stats = state.batch_stats_k if side == "k" else state.batch_stats_q
    return (
        encoder,
        jax.device_get(params),
        jax.device_get(stats),
        np.asarray(state.queue),
        int(state.queue_ptr),
        config,
    )


class InferenceEngine:
    """Bucketed AOT inference: `embed` (and `embed_and_query` against an
    `EmbeddingIndex`) over uint8 image batches of any size ≤ the largest
    bucket × chunking (module docstring).

    `mesh=None` runs single-device (the serving replica unit — scale-out
    is N processes behind a balancer, not one sharded forward; the
    *index* shards instead, see serve/index.py).
    """

    def __init__(
        self,
        module,
        params: Any,
        batch_stats: Any,
        image_size: int,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        donate: Optional[bool] = None,
        int8: bool = False,
        engine_quant: Optional[str] = None,
        calibration: Optional[dict] = None,
        calib_sample: Optional[np.ndarray] = None,
        int8_compute: Optional[bool] = None,
    ):
        from moco_tpu.serve import quant as quant_mod

        if not buckets or sorted(set(int(b) for b in buckets)) != sorted(
            int(b) for b in buckets
        ):
            raise ValueError(f"buckets must be unique and non-empty, got {buckets}")
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.image_size = int(image_size)
        self.num_features = getattr(module, "num_features", None)
        if donate is None:
            # CPU lacks donation support (jit would only warn and keep the
            # buffer) — same backend gate as make_train_step's donate_nums
            donate = jax.default_backend() in ("tpu", "gpu")
        self.donate = bool(donate)
        # tier resolution: engine_quant wins; int8=True is the PR-9
        # spelling of "w8" (kept so existing callers/tests read the same)
        if engine_quant is None:
            engine_quant = "w8" if int8 else "off"
        if engine_quant not in quant_mod.QUANT_MODES:
            raise ValueError(
                f"engine_quant must be one of {quant_mod.QUANT_MODES}, "
                f"got {engine_quant!r}"
            )
        self.quant = engine_quant
        self.int8 = engine_quant != "off"  # back-compat gauge (serve/int8)
        self._variables = {"params": params, "batch_stats": batch_stats}
        self._qparams = self._qscales = None
        self._act_scales = None
        self.calibration: Optional[dict] = None
        # true int8 kernels only where the backend has them (quant.py
        # docstring: XLA:CPU emulates, measured ~45x — the bf16 story)
        self.int8_compute = (
            quant_mod.default_int8_compute() if int8_compute is None else bool(int8_compute)
        )

        from moco_tpu.data.augment import get_recipe, normalize

        recipe = get_recipe(False, self.image_size)

        if self.quant != "off":
            # PTQ slots into the same per-bucket AOT seam: the forward
            # takes the quantized trees as ARGUMENTS (quantize_params_int8
            # docstring explains why a closure constant would constant-fold
            # the saving away) and dequantizes in-graph before apply
            self._qparams, self._qscales = quantize_params_int8(params)
            self._qparams = jax.device_put(self._qparams)
            self._qscales = jax.device_put(self._qscales)

        if self.quant == "w8a8":
            # calibration: an explicit artifact wins; else fit one from
            # the held-out sample at this exact preprocessing seam
            if calibration is None:
                if calib_sample is None:
                    raise ValueError(
                        "engine_quant='w8a8' needs a calibration artifact "
                        "(calibration=...) or a held-out sample (calib_sample=...)"
                    )
                calibration = quant_mod.calibrate_encoder(
                    module, params, batch_stats, calib_sample, self.image_size
                )
            quant_mod.validate_calibration(calibration, params, self.image_size)
            self.calibration = calibration
            self._act_scales = jax.device_put(
                quant_mod.activation_scales(calibration)
            )
            int8_compute_flag = self.int8_compute

            def forward(raw, qparams, qscales, act_scales):  # (b,H,W,C) uint8
                x = raw.astype(jnp.float32) / 255.0
                x = normalize(x, recipe.mean, recipe.std)
                feats = quant_mod.quantized_apply(
                    module, qparams, qscales, batch_stats, act_scales, x,
                    int8_compute=int8_compute_flag,
                )
                return l2_normalize(feats.astype(jnp.float32))

        elif self.quant == "w8":

            def forward(raw, qparams, qscales):  # (b, H, W, C) uint8
                x = raw.astype(jnp.float32) / 255.0
                x = normalize(x, recipe.mean, recipe.std)
                variables = {
                    "params": dequantize_params(qparams, qscales),
                    "batch_stats": batch_stats,
                }
                feats = module.apply(variables, x, train=False)
                return l2_normalize(feats.astype(jnp.float32))

        else:

            def forward(raw):  # (b, H, W, C) uint8
                x = raw.astype(jnp.float32) / 255.0
                x = normalize(x, recipe.mean, recipe.std)
                feats = module.apply(self._variables, x, train=False)
                return l2_normalize(feats.astype(jnp.float32))

        self._forward = forward
        self._compiled: dict[int, object] = {}
        self._frozen = False
        self.aot_compiles = 0
        self._warm_compiles: Optional[int] = None
        self._donation_audit: dict = {}
        for b in self.buckets:
            self._compile(b)

    def _quant_args(self) -> tuple:
        """The quantized trees each executable takes as arguments —
        () / (qparams, qscales) / (qparams, qscales, act_scales)."""
        if self.quant == "w8a8":
            return (self._qparams, self._qscales, self._act_scales)
        if self.quant == "w8":
            return (self._qparams, self._qscales)
        return ()

    # -- compilation -----------------------------------------------------

    def _compile(self, bucket: int):
        if self._frozen:
            raise EngineRecompileError(
                f"batch bucket {bucket} has no AOT executable and the engine "
                "is warm — pad requests to a compiled bucket "
                f"{self.buckets} instead of tracing on live traffic"
            )
        jitted = jax.jit(
            self._forward, donate_argnums=(0,) if self.donate else ()
        )
        shape = jax.ShapeDtypeStruct(
            (bucket, self.image_size, self.image_size, 3), jnp.uint8
        )
        args = (shape,) + self._quant_args()
        with obs_span("serve_aot_compile", bucket=bucket, quant=self.quant):
            compiled = jitted.lower(*args).compile()
        self.aot_compiles += 1
        self._compiled[bucket] = compiled
        return compiled

    def warmup(self) -> None:
        """Execute every bucket once (primes allocator/layout work the
        compile alone doesn't) and freeze: from here on an uncompiled
        shape raises instead of tracing. Blocks until the warmup work
        actually ran — otherwise the async dispatches queue up and the
        FIRST real request pays for all of them (observed: ~20s of
        deferred bucket executions landing on one request)."""
        for b in self.buckets:
            out = self._run_bucket(
                np.zeros((b, self.image_size, self.image_size, 3), np.uint8)
            )
            out.block_until_ready()
        self.mark_warm()

    def mark_warm(self) -> None:
        self._frozen = True
        self._warm_compiles = self.aot_compiles

    @property
    def recompiles_after_warmup(self) -> int:
        if self._warm_compiles is None:
            return 0
        return self.aot_compiles - self._warm_compiles

    def donation_audit(self) -> dict:
        """Per-bucket: True = the donated input buffer was consumed by
        the call (deleted — donation is real), False = donation was
        requested but the buffer survived (a reference leak would
        double peak memory per request), None = donation disabled
        (backend without support). Populated lazily as buckets run.

        Quantized tiers add `"qtree:<bucket>"` entries auditing the
        quantized parameter trees (int8 params + scales + activation
        scales): True = every tree buffer SURVIVED the call (they are
        reused by every later request; an accidental donation would be
        a use-after-free on the next one), False = some buffer was
        consumed. serve_smoke fails loudly on any False in the map."""
        return dict(self._donation_audit)

    # -- execution -------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest compiled bucket holding n rows (n ≤ max bucket)."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch of {n} exceeds the largest bucket {self.buckets[-1]}")

    def _run_bucket(self, padded: np.ndarray) -> jax.Array:
        """One compiled call on an exactly-bucket-shaped uint8 batch."""
        # deterministic tail injection (slow@site=serve.engine_execute):
        # the sleep lands inside the engine_execute stage's stamped
        # interval, so the flight recorder attributes it correctly
        faults.maybe_slow("serve.engine_execute")
        bucket = padded.shape[0]
        compiled = self._compiled.get(bucket)
        if compiled is None:
            compiled = self._compile(bucket)
        staged = jax.device_put(jnp.asarray(padded, jnp.uint8))
        quant_args = self._quant_args()
        out = compiled(staged, *quant_args)
        if bucket not in self._donation_audit:
            if self.donate:
                out.block_until_ready()
                self._donation_audit[bucket] = bool(staged.is_deleted())
            else:
                self._donation_audit[bucket] = None
            if quant_args:
                # the quantized trees are call arguments on EVERY bucket
                # execution — they must all survive (donation_audit
                # docstring); checked once per bucket like the input
                out.block_until_ready()
                self._donation_audit[f"qtree:{bucket}"] = not any(
                    getattr(leaf, "is_deleted", lambda: False)()
                    for leaf in jax.tree_util.tree_leaves(quant_args)
                )
        return out

    def _padded_chunks(self, images: np.ndarray):
        """Yield (padded_uint8, valid_rows, bucket): chunk at the
        largest bucket, pad each chunk with zero rows to its bucket."""
        images = np.asarray(images, np.uint8)
        if images.ndim != 4 or images.shape[1:] != (self.image_size, self.image_size, 3):
            raise ValueError(
                f"expected (n, {self.image_size}, {self.image_size}, 3) uint8, "
                f"got {images.shape}"
            )
        max_b = self.buckets[-1]
        for start in range(0, images.shape[0], max_b):
            chunk = images[start : start + max_b]
            bucket = self.bucket_for(chunk.shape[0])
            padded = chunk
            if bucket != chunk.shape[0]:
                padded = np.zeros((bucket,) + chunk.shape[1:], np.uint8)
                padded[: chunk.shape[0]] = chunk
            yield padded, chunk.shape[0], bucket

    def embed(
        self, images: np.ndarray, stages: Optional[dict] = None
    ) -> tuple[np.ndarray, list[Tuple[int, int]]]:
        """L2-normalized (n, num_features) f32 embeddings of an
        (n, H, W, C) uint8 batch, plus the executed (bucket, valid_rows)
        pairs for occupancy accounting. Oversized batches chunk at the
        largest bucket; padding rows are zeros and their outputs are
        sliced away before anything downstream sees them. `stages` (the
        request-trace contract) accumulates per-stage seconds; timing a
        stage forces device readiness inside its window, so the split is
        honest under async dispatch — that sync is what tracing costs."""
        outs, executed = [], []
        for padded, n, bucket in self._padded_chunks(images):
            with obs_span("serve_embed", bucket=bucket, valid=n):
                if stages is None:
                    feats = self._run_bucket(padded)
                else:
                    t0 = time.perf_counter()
                    feats = self._run_bucket(padded)
                    feats.block_until_ready()
                    stages["engine_execute"] = (
                        stages.get("engine_execute", 0.0) + time.perf_counter() - t0
                    )
            outs.append(np.asarray(feats)[:n])
            executed.append((bucket, n))
        return np.concatenate(outs), executed

    def embed_and_query(
        self, images: np.ndarray, index, k: int, stages: Optional[dict] = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, list[Tuple[int, int]]]:
        """(embeddings, scores, indices, executed) — the `/neighbors`
        path against the exact tier. The index query runs on the PADDED
        bucket rows (the same shapes `index.prepare(self.buckets, k)`
        AOT-compiled), so mixed request sizes never trace; padding rows'
        neighbors are sliced away with their embeddings."""
        emb, per_mode, executed = self.embed_and_query_modes(
            images, index, k, stages=stages
        )
        scores, idx = per_mode["exact"]
        return emb, scores, idx, executed

    def embed_and_query_modes(
        self,
        images: np.ndarray,
        index,
        k: int,
        modes: Sequence[str] = ("exact",),
        nprobe: Optional[int] = None,
        stages: Optional[dict] = None,
    ) -> tuple[np.ndarray, dict, list[Tuple[int, int]]]:
        """(embeddings, {mode: (scores, indices)}, executed): one encoder
        forward per padded chunk, then one index query PER REQUESTED TIER
        on the same device features — how the server answers a micro-batch
        mixing `?mode=ivf` and `?mode=exact` riders, and how the sampled
        recall estimator gets its IVF/oracle pair from a single forward.
        Every (mode, bucket, k, nprobe) must be prepared once frozen.
        `stages` splits engine_execute/index_query seconds for the
        request-trace waterfall (see `embed` on the forced readiness)."""
        outs, executed = [], []
        per_mode: dict = {mode: ([], []) for mode in modes}
        for padded, n, bucket in self._padded_chunks(images):
            with obs_span("serve_embed", bucket=bucket, valid=n):
                if stages is None:
                    feats = self._run_bucket(padded)  # (bucket, d) on device
                else:
                    t0 = time.perf_counter()
                    feats = self._run_bucket(padded)
                    feats.block_until_ready()
                    stages["engine_execute"] = (
                        stages.get("engine_execute", 0.0) + time.perf_counter() - t0
                    )
            for mode in modes:
                with obs_span("serve_query", bucket=bucket, k=k, mode=mode):
                    if stages is None:
                        scores, idx = index.query(feats, k, mode=mode, nprobe=nprobe)
                    else:
                        t0 = time.perf_counter()
                        scores, idx = index.query(feats, k, mode=mode, nprobe=nprobe)
                        jax.block_until_ready((scores, idx))
                        stages["index_query"] = (
                            stages.get("index_query", 0.0) + time.perf_counter() - t0
                        )
                per_mode[mode][0].append(scores[:n])
                per_mode[mode][1].append(idx[:n])
            outs.append(np.asarray(feats)[:n])
            executed.append((bucket, n))
        return (
            np.concatenate(outs),
            {m: (np.concatenate(s), np.concatenate(i)) for m, (s, i) in per_mode.items()},
            executed,
        )


__all__ = [
    "DEFAULT_BUCKETS",
    "EngineRecompileError",
    "InferenceEngine",
    "dequantize_params",
    "load_serving_encoder",
    "quantize_params_int8",
]
