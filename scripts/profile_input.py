"""Per-stage breakdown of the exact-host-RRC input path (VERDICT r2 #5).

PROFILE.md's with-data ladder showed the host pipeline ~10x below the
device rate but attributed the ceiling by extrapolation. This script
measures where each millisecond goes, per batch, for every input mode:

  stages: dims lookup -> RRC box sampling -> source read (JPEG decode
  or cache mmap) -> crop+resize (PIL or C++ resize_region) -> assemble
  [-> host-to-device transfer, when an accelerator is attached]

Modes (the ladder of PROFILE.md):
  jpeg_pil     — ImageFolderDataset: PIL decode + PIL crop/resize
  jpeg_native  — native/loader.cc decode pool + C++ crops
  cache_pil    — PackedRGBCacheDataset(use_native=False): mmap + PIL
  cache_native — PackedRGBCacheDataset: mmap + C++ resize_region
  cache_canvas — canvas mode: pure mmap row read (host_rrc=False)

The crop stage is additionally swept over thread counts; on a 1-core
host that curve is expected flat (it measures GIL/pool overhead, not
parallel speedup) — the per-thread number is what transfers to
multi-core hosts since both crop backends release the GIL (C++) or run
in PIL's C core.

`--overlap` additionally A/Bs the end-to-end input path — the
synchronous epoch iterator (decode → transfer → augment dispatch taking
turns on one producer thread) vs the device prefetch ring
(`data/device_prefetch.py`: decode thread + dedicated transfer thread +
staged device batches) — and reports the ring's measured wire rate and
`overlap_efficiency` = achieved / min(host-rate, wire-rate).

Writes artifacts/input_profile.json and a marker-delimited section into
PROFILE.md. Run:
    python scripts/profile_input.py            # TPU if healthy, else CPU
    JAX_PLATFORMS=cpu python scripts/profile_input.py --batches 4
    python scripts/profile_input.py --overlap  # + sync-vs-ring A/B
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moco_tpu.utils.platform import pin_platform_from_env

pin_platform_from_env()

import numpy as np

ART_PATH = "artifacts/input_profile.json"


def _ensure_jpeg_folder(root: str, n: int, size: int, classes: int = 8) -> str:
    """Synthetic JPEG ImageFolder for the input profile (no datasets on
    disk in this environment). Deterministic, built once, reused."""
    from PIL import Image

    stamp = os.path.join(root, f".complete_{n}_{size}")
    if os.path.exists(stamp):
        return root
    rng = np.random.default_rng(0)
    for c in range(classes):
        os.makedirs(os.path.join(root, f"class_{c}"), exist_ok=True)
    for i in range(n):
        c = i % classes
        # low-frequency field + noise ≈ natural-image JPEG work profile
        coarse = rng.uniform(0, 255, (8, 8, 3))
        img = np.asarray(
            Image.fromarray(coarse.astype(np.uint8)).resize((size, size), Image.BILINEAR),
            np.float32,
        )
        img += rng.normal(0, 12, img.shape)
        Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
            os.path.join(root, f"class_{c}", f"img_{i:05d}.jpg"), quality=90
        )
    open(stamp, "w").close()
    return root


def _sample_boxes(dims: np.ndarray, n_crops: int, seed: int, epoch: int, step: int,
                  idx: np.ndarray, scale=(0.2, 1.0)) -> np.ndarray:
    """The pipeline's exact box sampling (pipeline.py:_put_crop_batch):
    one (seed, epoch, step)-keyed vectorized uniform draw for the whole
    batch × crops, sliced by global position. (The prior per-(row, crop)
    seeded-Generator scheme measured ~0.24 ms per crop of pure seeding
    overhead here — the reason the pipeline was rewritten; 107x faster.)"""
    from moco_tpu.data.datasets import draw_rrc_uniforms, rrc_boxes_from_uniforms

    rng = np.random.default_rng((seed, epoch, step))
    u = draw_rrc_uniforms(rng, len(idx) * n_crops)
    return rrc_boxes_from_uniforms(
        u, np.repeat(dims, n_crops, axis=0), scale=scale
    ).reshape(len(idx), n_crops, 4)


def _time(fn, reps: int) -> float:
    """Best-of-reps milliseconds (min filters scheduler noise on the
    shared single core)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def profile_mode(name: str, dataset, batch: int, out_size: int, reps: int,
                 pool) -> dict:
    idx = np.arange(batch) % len(dataset)
    res = {"mode": name, "batch": batch, "out_size": out_size}

    res["dims_ms"] = _time(lambda: dataset.dims(idx), reps)
    dims = dataset.dims(idx)
    res["boxes_ms"] = _time(lambda: _sample_boxes(dims, 2, 0, 0, 0, idx), reps)
    boxes = _sample_boxes(dims, 2, 0, 0, 0, idx)

    if name == "cache_canvas":
        # canvas mode has no crop stage: one mmap row read per image
        res["read_ms"] = _time(
            lambda: np.stack([dataset.load(int(i))[0] for i in idx]), reps
        )
        res["crop_ms"] = 0.0
        # boxes_ms included for cross-mode comparability even though
        # canvas mode consumes no boxes host-side (the RRC crop runs on
        # device from the fixed canvas) — every mode's total now sums
        # the same stages
        res["total_ms"] = res["dims_ms"] + res["boxes_ms"] + res["read_ms"]
        return res

    # full crop-batch stage (read + crop + resize + assembly into the
    # output array, exactly what the pipeline calls)
    res["crop_batch_ms"] = _time(
        lambda: dataset.load_crop_batch(idx, boxes, out_size, pool=pool), reps
    )

    # source-read sub-stage: decode (JPEG) or mmap slice (cache)
    if hasattr(dataset, "_image"):  # cache: mmap read + materialize
        res["read_ms"] = _time(
            lambda: [np.ascontiguousarray(dataset._image(int(i))) for i in idx], reps
        )
    elif hasattr(dataset, "samples"):  # JPEG folder: PIL decode only
        from PIL import Image

        def decode_all():
            for i in idx:
                with Image.open(dataset.samples[int(i)][0]) as im:
                    np.asarray(im.convert("RGB"))

        res["read_ms"] = _time(decode_all, reps)
    else:
        res["read_ms"] = None
    if res["read_ms"] is not None:
        # APPROXIMATE: crop_batch_ms and read_ms are independent
        # best-of-reps measurements, so their difference can misattribute
        # assembly cost or go negative under scheduler noise — clamp at 0
        # and treat as indicative only (the render marks it "~")
        res["crop_resize_ms"] = max(0.0, res["crop_batch_ms"] - res["read_ms"])
    res["total_ms"] = res["dims_ms"] + res["boxes_ms"] + res["crop_batch_ms"]
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--out-size", type=int, default=224)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--src-size", type=int, default=256, help="synthetic JPEG geometry")
    ap.add_argument("--n-images", type=int, default=512)
    ap.add_argument("--threads", type=int, nargs="*", default=[1, 2, 4, 8])
    ap.add_argument("--profile-md", default="PROFILE.md")
    ap.add_argument("--artifact", default=ART_PATH)
    ap.add_argument(
        "--overlap", action="store_true",
        help="A/B the sync epoch iterator vs the device prefetch ring "
        "(cache canvas mode, the fastest host path) and report "
        "overlap_efficiency",
    )
    args = ap.parse_args()

    from moco_tpu.data.cache import PackedRGBCacheDataset, build_rgb_cache
    from moco_tpu.data.datasets import ImageFolderDataset
    from moco_tpu.data.native_loader import native_available

    folder = _ensure_jpeg_folder("/tmp/moco_bench_imgfolder", args.n_images, args.src_size)
    cache_dir = "/tmp/moco_input_profile_cache"
    build_rgb_cache(
        lambda: ImageFolderDataset(folder, decode_size=args.src_size),
        cache_dir, num_workers=1, canvas_size=args.src_size, root=folder,
    )

    from concurrent.futures import ThreadPoolExecutor

    results = []
    native = native_available()
    for threads in args.threads:
        pool = ThreadPoolExecutor(max_workers=threads)
        modes = {
            "jpeg_pil": ImageFolderDataset(folder, decode_size=args.src_size),
            "cache_pil": PackedRGBCacheDataset(
                cache_dir, decode_size=args.src_size, use_native=False,
                num_workers=threads,
            ),
        }
        if native:
            from moco_tpu.data.native_loader import NativeImageFolderDataset

            modes["jpeg_native"] = NativeImageFolderDataset(
                folder, decode_size=args.src_size, threads=threads
            )
            modes["cache_native"] = PackedRGBCacheDataset(
                cache_dir, decode_size=args.src_size, use_native=True,
                num_workers=threads,
            )
        modes["cache_canvas"] = PackedRGBCacheDataset(
            cache_dir, decode_size=args.src_size, use_native=False,
            num_workers=threads,
        )
        for name, ds in modes.items():
            r = profile_mode(name, ds, args.batch, args.out_size, args.reps, pool)
            r["threads"] = threads
            r["imgs_per_sec"] = 1e3 * args.batch / r["total_ms"]
            results.append(r)
            print(
                f"[threads={threads}] {name:13s} total {r['total_ms']:8.1f} ms/batch "
                f"({r['imgs_per_sec']:7.1f} imgs/s) "
                + " ".join(
                    f"{k.replace('_ms','')}={v:.1f}"
                    for k, v in r.items()
                    if k.endswith("_ms") and k != "total_ms" and v is not None
                ),
                flush=True,
            )
        pool.shutdown()

    # host->device transfer of one batch's fresh uint8 buffers (2 crops)
    transfer = None
    import jax

    try:
        dev = jax.devices()[0]
        buf = np.random.default_rng(0).integers(
            0, 255, (args.batch, args.out_size, args.out_size, 3), np.uint8
        )
        def put():
            a = jax.device_put(buf.copy(), dev)  # fresh buffer: no cache
            b = jax.device_put(buf.copy(), dev)
            np.asarray(a[0, 0, 0]); np.asarray(b[0, 0, 0])  # sync via fetch
        transfer = {
            "platform": dev.platform,
            "two_crop_put_ms": _time(put, args.reps),
            "bytes": 2 * buf.nbytes,
        }
        transfer["mb_per_sec"] = (
            transfer["bytes"] / 1e6 / (transfer["two_crop_put_ms"] / 1e3)
        )
        print(f"transfer: {transfer}")
    except Exception as e:
        print(f"transfer timing skipped: {e}", file=sys.stderr)

    # sync-vs-ring overlap A/B over the full epoch path (--overlap)
    overlap = None
    if args.overlap:
        try:
            overlap = profile_overlap(folder, cache_dir, args.batch, args.out_size,
                                      src_size=args.src_size)
            print(f"overlap: {overlap}")
        except Exception as e:
            print(f"overlap profiling skipped: {e}", file=sys.stderr)

    os.makedirs(os.path.dirname(args.artifact) or ".", exist_ok=True)
    payload = {
        "batch": args.batch, "out_size": args.out_size,
        "src_size": args.src_size, "native_available": native,
        "results": results, "transfer": transfer, "overlap": overlap,
    }
    with open(args.artifact, "w") as f:
        json.dump(payload, f, indent=2)
    write_section(args.profile_md, payload)


def profile_overlap(folder: str, cache_dir: str, batch: int, out_size: int,
                    src_size: int, n_batches: int = 6) -> dict:
    """End-to-end epoch-path A/B: sync iterator vs the device prefetch
    ring, canvas mode (the fastest host path, so the WIRE + consumer
    side is what the A/B isolates). Consumes each batch to readiness —
    the closest harness to the train loop without paying a train step.

    The geometric-only recipe (crops_only) stands in for the augment:
    on a 1-core CPU host the full jitter/blur recipe costs ~80 s/batch
    of pure compute, which would bury the input path this script
    profiles (on a TPU the augmentation is a device program of its own:
    `benchmarks/run.py --workload train_r50_v2` measures it there)."""
    import jax

    from moco_tpu.data.pipeline import TwoCropPipeline
    from moco_tpu.parallel import create_mesh
    from moco_tpu.utils.config import DataConfig

    mesh = create_mesh(num_data=1, num_model=1, devices=jax.devices()[:1])
    cfg = DataConfig(
        dataset="imagefolder", data_dir=folder, image_size=out_size,
        global_batch=batch, crops_only=True, num_workers=8,
        cache_dir=cache_dir, host_rrc=False,  # canvas: pure mmap row read
    )
    pipe = TwoCropPipeline(cfg, mesh, seed=0)

    def leg(device: bool) -> tuple[float, object]:
        state = {"it": pipe.epoch(0, device=device), "epoch": 0}

        def nxt():
            while True:
                b = next(state["it"], None)
                if b is not None:
                    return b
                getattr(state["it"], "close", lambda: None)()
                state["epoch"] += 1
                state["it"] = pipe.epoch(state["epoch"], device=device)

        jax.block_until_ready(nxt()["im_q"])  # spin-up + compile
        t0 = time.perf_counter()
        for _ in range(n_batches):
            jax.block_until_ready(nxt()["im_q"])
        dt = time.perf_counter() - t0
        stats = getattr(state["it"], "stats", None)
        getattr(state["it"], "close", lambda: None)()
        return batch * n_batches / dt, stats

    sync_rate, _ = leg(device=False)
    ring_rate, stats = leg(device=True)
    out = {
        "mode": "cache_canvas+crops_only",
        "sync_imgs_per_sec": round(sync_rate, 1),
        "ring_imgs_per_sec": round(ring_rate, 1),
        "speedup": round(ring_rate / sync_rate, 3) if sync_rate else None,
    }
    # stage bounds for the efficiency denominator: host decode alone,
    # the measured wire rate, and the CONSUMER (transfer + augment
    # compute on the same staged batch — on a CPU host this is the
    # binding stage and must be in the denominator, else the ratio
    # reads as overlap failure when compute is simply the bottleneck)
    bounds = {}
    t0 = time.perf_counter()
    n = 0
    for _ in pipe._host_gen(97):
        n += 1
        if n >= n_batches:
            break
    bounds["host"] = batch * n / (time.perf_counter() - t0)
    hb = next(pipe._host_gen(98))
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out_b, _ = pipe._stage(hb, False)
        jax.block_until_ready(out_b["im_q"])
    bounds["consume"] = batch * reps / (time.perf_counter() - t0)
    if stats is not None and stats.batches:
        wire_bps = stats.wire_rate_bytes_per_sec()
        bytes_per_img = stats.total_bytes / stats.batches / batch
        if wire_bps and bytes_per_img:
            bounds["wire"] = wire_bps / bytes_per_img
            out["wire_mb_per_sec"] = round(wire_bps / 1e6, 1)
    for name, rate in bounds.items():
        out[f"{name}_imgs_per_sec"] = round(rate, 1)
    out["overlap_efficiency"] = round(ring_rate / min(bounds.values()), 3)
    return out


def write_section(profile_md: str, payload: dict) -> None:
    rows = [r for r in payload["results"] if r["threads"] == 1]
    by_threads: dict = {}
    for r in payload["results"]:
        by_threads.setdefault(r["mode"], {})[r["threads"]] = r["imgs_per_sec"]
    lines = [
        "## Input-path per-stage breakdown",
        "",
        f"`scripts/profile_input.py`: batch {payload['batch']}, two "
        f"{payload['out_size']}px crops/image, {payload['src_size']}px synthetic "
        "JPEGs, best-of-reps ms per batch, single thread (per-stage):",
        "",
        "| mode | dims | box sample | source read | ~crop+resize | total ms | imgs/s |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        cr = r.get("crop_resize_ms")
        lines.append(
            f"| {r['mode']} | {r['dims_ms']:.1f} | {r.get('boxes_ms', 0):.1f} | "
            f"{r['read_ms'] if r['read_ms'] is not None else float('nan'):.1f} | "
            f"{cr if cr is not None else 0:.1f} | "
            f"{r['total_ms']:.1f} | {r['imgs_per_sec']:.0f} |"
        )
    lines += [
        "",
        "(~crop+resize is approximate — derived by subtracting two",
        "independently-timed best-of-reps stages, clamped at 0; canvas",
        "mode's box-sample column is host cost only, its RRC crop runs",
        "on device from the fixed canvas.)",
    ]
    lines += [
        "",
        "Thread scaling (imgs/s; flat on this 1-core host — the pools add",
        "no overhead but there is no parallelism to harvest; both crop",
        "backends run outside the GIL, so the 1-thread rate scales with",
        "cores on real TPU-VM hosts):",
        "",
        "| mode | " + " | ".join(f"{t} thr" for t in sorted({r['threads'] for r in payload['results']})) + " |",
        "|---|" + "---|" * len({r['threads'] for r in payload['results']}),
    ]
    for mode, per in by_threads.items():
        lines.append(
            f"| {mode} | " + " | ".join(f"{per[t]:.0f}" for t in sorted(per)) + " |"
        )
    t = payload.get("transfer")
    if t:
        lines += [
            "",
            f"Host→device transfer ({t['platform']}): {t['two_crop_put_ms']:.1f} ms "
            f"for both crop buffers ({t['bytes'] / 1e6:.0f} MB) = "
            f"{t['mb_per_sec']:.0f} MB/s.",
        ]
    ov = payload.get("overlap")
    if ov:
        lines += [
            "",
            "### Input-wire overlap (device prefetch ring)",
            "",
            f"End-to-end epoch path, {ov['mode']} mode, sync iterator vs "
            "`epoch(device=True)` (`data/device_prefetch.py`):",
            "",
            f"- sync: {ov['sync_imgs_per_sec']:.0f} imgs/s; overlapped: "
            f"{ov['ring_imgs_per_sec']:.0f} imgs/s "
            f"(×{ov['speedup']:.2f})",
            "- stage bounds (imgs/s): "
            + ", ".join(
                f"{k.removesuffix('_imgs_per_sec')} {ov[k]:.0f}"
                for k in ("host_imgs_per_sec", "wire_imgs_per_sec",
                          "consume_imgs_per_sec")
                if k in ov
            )
            + (f" (wire {ov['wire_mb_per_sec']:.0f} MB/s)"
               if "wire_mb_per_sec" in ov else ""),
            f"- overlap_efficiency (achieved / min(stage bounds)): "
            f"{ov['overlap_efficiency']:.3f} — on this 1-core host the "
            "consumer (augment compute shares the single core) is the "
            "binding stage, and >1 means the serially-measured consume "
            "bound (transfer then augment, no overlap) understates the "
            "pipelined bound; the chip's number is the benchmark's "
            "(`benchmarks/run.py`, PERF.md)",
        ]
    from moco_tpu.utils.report import replace_marker_block

    replace_marker_block(profile_md, "input-profile", "\n".join(lines))
    print(f"input-profile section written into {profile_md}")


if __name__ == "__main__":
    main()
