"""Minimal repro / bisect harness for the bn_stats_rows TPU compile
pathology (VERDICT r4 #2, PROFILE.md round-4 notes).

Observed: the r50/224 MoCo step with `--bn-stats-rows 32` compiles in
>15 min on the TPU backend vs ~3.5 min for the full-batch-BN baseline,
while the SAME program compiles FASTER than baseline on CPU — i.e. a
TPU-backend (Mosaic/layout/fusion) compile-time behavior, not a
graph-size explosion. This script isolates WHICH ingredient triggers it
by timing `jit(f).lower()` and `.compile()` separately over a grid:

  axis 1 — depth: a stack of D ConvBN(+ReLU) cells at r50 stage-1
           geometry (56x56x256-ish activations), D in --depths;
  axis 2 — rows: BN statistics subset size, in --rows (0 = full batch,
           the baseline arm);
  axis 3 — variant:
      slice      x[:r] subset statistics (the shipped implementation,
                 models/resnet.py BatchNorm);
      mask       full-row read with a row mask (same RESULT, no slice /
                 no pad-transpose in the backward — reads all bytes, so
                 it forfeits the lever; DIAGNOSIS control only);
      fwd        `slice` without value_and_grad (no backward pad): did
                 the transpose introduce it?
      align      `slice` with r rounded up to a multiple of 8 before
                 slicing (sublane alignment probe; only differs for
                 r not already 8-aligned);
      barrier    `slice` with an optimization_barrier around the
                 subset — breaks the slice out of XLA's fusion
                 clustering (candidate workaround if the pathology is
                 fusion/layout interaction, at the cost of one small
                 materialization per BN).

Each (depth, rows, variant) cell is compiled in a fresh subprocess, one
at a time (one process per chip; this parent never imports jax), so a
pathological cell can be timed out (--cell-timeout) and KILLED — the
chip is free again for the next cell — and so each cell pays its own
cold compile: children never enable the persistent compilation cache,
and `JAX_COMPILATION_CACHE_DIR` is dropped from their environment
(cache hits would report 0 s and hide the pathology).

Run on CPU (sanity: everything fast) or on the TPU (the diagnosis).
Output: one table row per cell to stdout + a JSON artifact with all
timings.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHILD_ENV_FLAG = "BN_REPRO_CHILD"


def depth_cells(rows, variants):
    """Cell order within a depth: the rows=0 baseline FIRST (its timing
    anchors the bisect), control variants next, the shipped slice-subset
    suspects LAST — so a run cut short by its outer time limit forfeits
    the least information."""
    sub_rows = [r for r in rows if r]
    cells = [("slice", 0)] if 0 in rows and "slice" in variants else []
    cells += [(v, r) for v in variants if v != "slice" for r in sub_rows]
    if "slice" in variants:
        cells += [("slice", r) for r in sub_rows]
    return cells


def child_main() -> None:
    """Time lower+compile of one grid cell; print one JSON line."""
    from moco_tpu.utils.platform import pin_platform_from_env

    pin_platform_from_env()
    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    from moco_tpu.models.resnet import BatchNorm, conv_kernel_init

    spec = json.loads(os.environ["BN_REPRO_SPEC"])
    depth = spec["depth"]
    rows = spec["rows"]
    variant = spec["variant"]
    batch = spec["batch"]
    hw = spec["hw"]
    feats = spec["feats"]
    dtype = jnp.bfloat16 if spec["dtype"] == "bfloat16" else jnp.float32

    def _track_running_stats(mod, mean, var, feats):
        """Every variant must compile the SAME running-average EMA
        writes the real BatchNorm does (mutable batch_stats outputs
        change XLA's program structure) — otherwise a mask-vs-slice
        compile-time gap could be the stats writes, not the slice."""
        ra_mean = mod.variable(
            "batch_stats", "mean", lambda: jnp.zeros((feats,), jnp.float32)
        )
        ra_var = mod.variable(
            "batch_stats", "var", lambda: jnp.ones((feats,), jnp.float32)
        )
        if not mod.is_initializing():
            ra_mean.value = 0.9 * ra_mean.value + 0.1 * mean
            ra_var.value = 0.9 * ra_var.value + 0.1 * var

    class MaskBN(nn.Module):
        """Row-mask subset statistics: identical result to x[:r] stats,
        but the reduction reads every row (no slice, no backward pad)."""

        stats_rows: int
        dtype: jnp.dtype

        @nn.compact
        def __call__(self, x):
            feats = x.shape[-1]
            scale = self.param("scale", nn.initializers.ones, (feats,), jnp.float32)
            bias = self.param("bias", nn.initializers.zeros, (feats,), jnp.float32)
            r = self.stats_rows or x.shape[0]
            mask = (jnp.arange(x.shape[0]) < r).astype(jnp.float32)
            bcast = (x.shape[0],) + (1,) * (x.ndim - 1)
            xf = x.astype(jnp.float32) * mask.reshape(bcast)
            denom = r * x.shape[1] * x.shape[2]
            axes = tuple(range(x.ndim - 1))
            mean = jnp.sum(xf, axis=axes) / denom
            mean2 = jnp.sum(jnp.square(xf), axis=axes) / denom
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            _track_running_stats(self, mean, var, feats)
            mul = scale * jax.lax.rsqrt(var + 1e-5)
            shift = bias - mean * mul
            return x * mul.astype(self.dtype) + shift.astype(self.dtype)

    class BarrierBN(nn.Module):
        """x[:r] subset statistics with an optimization_barrier around
        the sliced subset: same math as `slice`, but the barrier stops
        XLA fusing the slice into the surrounding conv/reduce clusters
        — the candidate workaround if the compile pathology is a
        fusion/layout interaction."""

        stats_rows: int
        dtype: jnp.dtype

        @nn.compact
        def __call__(self, x):
            feats = x.shape[-1]
            scale = self.param("scale", nn.initializers.ones, (feats,), jnp.float32)
            bias = self.param("bias", nn.initializers.zeros, (feats,), jnp.float32)
            r = self.stats_rows or x.shape[0]
            sub = jax.lax.optimization_barrier(x[:r]).astype(jnp.float32)
            axes = tuple(range(sub.ndim - 1))
            mean = jnp.mean(sub, axis=axes)
            mean2 = jnp.mean(jnp.square(sub), axis=axes)
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            _track_running_stats(self, mean, var, feats)
            mul = scale * jax.lax.rsqrt(var + 1e-5)
            shift = bias - mean * mul
            return x * mul.astype(self.dtype) + shift.astype(self.dtype)

    class Stack(nn.Module):
        depth: int
        norm_rows: int
        variant: str
        dtype: jnp.dtype

        @nn.compact
        def __call__(self, x):
            x = x.astype(self.dtype)
            for _ in range(self.depth):
                x = nn.Conv(
                    feats, (3, 3), padding=[(1, 1), (1, 1)], use_bias=False,
                    kernel_init=conv_kernel_init, dtype=x.dtype,
                )(x)
                if self.variant == "mask":
                    x = MaskBN(stats_rows=self.norm_rows, dtype=self.dtype)(x)
                elif self.variant == "barrier":
                    x = BarrierBN(stats_rows=self.norm_rows, dtype=self.dtype)(x)
                else:
                    r = self.norm_rows
                    if self.variant == "align" and r:
                        r = (r + 7) // 8 * 8
                    x = BatchNorm(stats_rows=r, dtype=self.dtype)(x)
                x = nn.relu(x)
            return jnp.mean(x.astype(jnp.float32))

    model = Stack(depth=depth, norm_rows=rows, variant=variant, dtype=dtype)
    x = jnp.zeros((batch, hw, hw, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    params = variables["params"]
    stats = variables.get("batch_stats", {})

    def apply(p, x):
        # mutable batch_stats mirrors the real train step (BatchNorm
        # writes its running-average variables every training call)
        out, _ = model.apply(
            {"params": p, "batch_stats": stats}, x, mutable=["batch_stats"]
        )
        return out

    if variant == "fwd":
        f = apply
    else:
        def f(p, x):
            return jax.value_and_grad(lambda q: apply(q, x))(p)

    t0 = time.perf_counter()
    lowered = jax.jit(f).lower(params, x)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    lowered.compile()
    t_compile = time.perf_counter() - t0
    print(json.dumps({
        "depth": depth, "rows": rows, "variant": variant,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "backend": jax.default_backend(),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--depths", type=int, nargs="*", default=[1, 4, 8, 16])
    ap.add_argument("--rows", type=int, nargs="*", default=[0, 32, 8])
    ap.add_argument("--variants", nargs="*",
                    default=["slice", "mask", "fwd"],
                    choices=("slice", "mask", "fwd", "align", "barrier"))
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--hw", type=int, default=56,
                    help="activation side (56 = r50 stage-1 at 224px input)")
    ap.add_argument("--feats", type=int, default=256)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--cell-timeout", type=int, default=1200)
    ap.add_argument("--out", default="artifacts/bn_compile_repro.json")
    args = ap.parse_args()

    results = []
    cells = depth_cells(args.rows, args.variants)
    print(f"{'depth':>5} {'rows':>5} {'variant':>8} {'lower_s':>8} {'compile_s':>10}")
    for depth in args.depths:
        for variant, rows in cells:
            spec = dict(
                depth=depth, rows=rows, variant=variant, batch=args.batch,
                hw=args.hw, feats=args.feats, dtype=args.dtype,
            )
            env = dict(os.environ)
            env[CHILD_ENV_FLAG] = "1"
            env["BN_REPRO_SPEC"] = json.dumps(spec)
            # a cold compile per cell: cache hits would hide the bug
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
            try:
                out, err = proc.communicate(timeout=args.cell_timeout)
                line = out.strip().splitlines()[-1] if out.strip() else ""
                try:
                    cell = json.loads(line) if proc.returncode == 0 and line else {
                        **spec, "error": f"rc={proc.returncode}",
                        "stderr_tail": err[-400:],
                    }
                except json.JSONDecodeError:
                    # a stray runtime notice on the child's last stdout
                    # line must cost one cell, not the grid
                    cell = {**spec, "error": "unparseable child output",
                            "stdout_tail": out[-400:]}
            except subprocess.TimeoutExpired:
                cell = {**spec, "error": f"timeout>{args.cell_timeout}s"}
                proc.kill()
                proc.communicate()
            results.append(cell)
            print(
                f"{depth:>5} {rows:>5} {variant:>8} "
                f"{cell.get('lower_s', '—'):>8} "
                f"{str(cell.get('compile_s', cell.get('error', '—'))):>10}",
                flush=True,
            )
            # incremental artifact: an outer kill must not discard
            # hours of already-timed chip compiles
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=2)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    if os.environ.get(CHILD_ENV_FLAG):
        child_main()
    else:
        main()
