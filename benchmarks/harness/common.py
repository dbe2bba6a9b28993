"""What both kinds of cell share: the device gate, the compile cache, the
compile counter, the program's configuration built from the data files."""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

from benchmarks.harness.manifest import BENCH_DIR, REPO_ROOT, ManifestError

OUT_DIR = os.path.join(BENCH_DIR, "out")
NO_DEVICE_EXIT = 3


def log(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.time()


def set_process_start(t0: float) -> None:
    global _T0
    _T0 = t0


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless the environment already names one. Set through the
    environment so that the program's `enable_persistent_compilation_cache`
    (which sets nothing when the variable is there) and the benchmark's
    own programs share one directory. Must run before jax is imported."""
    path = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO_ROOT, ".jax_cache")
    )
    os.makedirs(path, exist_ok=True)
    return path


def tune_compile_cache() -> None:
    """Cache every program, however quick to compile or small: the
    defaults (1 s, some KB) leave the small init programs out, and they
    then compile again in every run (PERF.md, PR 21)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts backend compilations (cache loads are not compilations) and
    when they happened, through jax's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.times.append(time.time())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def require_devices(chips: int, rehearse: bool) -> dict:
    """The device record of the result line; exits (code 3, no result) when
    jax found no TPU or fewer chips than the cell asks for. A rehearsal
    takes whatever is there."""
    import jax

    devices = jax.devices()
    record = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearse:
        return record
    if record["platform"] != "tpu" or len(devices) < chips:
        print(
            f"benchmark: needs {chips} TPU chip(s), jax resolved "
            f"{record['count']} x {record['platform']} ({record['kind']!r}); "
            "no result. (--rehearse runs a tiny preset on the CPU and prints counts only.)",
            file=sys.stderr, flush=True,
        )
        raise SystemExit(NO_DEVICE_EXIT)
    return record


def memory_peak_bytes() -> int:
    """Peak device memory on the fullest chip. The TPU runtime keeps two
    gauges: `peak_bytes_in_use` counts buffers (state, batches, outputs),
    `peak_bytes_reserved` what running programs reserve for their
    temporaries (for the R50 step 9.16 GB against the 9.27 GB XLA's
    `memory_analysis` gives, while `peak_bytes_in_use` read 4.18 GB:
    PERF.md section 6, PR 24). The two pools are disjoint and their peaks
    need not coincide, so the larger of the two is a floor under the true
    peak and their sum a ceiling; the floor is what is reported."""
    import jax

    peaks = [0]
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(max(stats.get("peak_bytes_in_use", 0), stats.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


def _replace_dotted(cfg, dotted: str, value):
    head, _, rest = dotted.partition(".")
    if not rest:
        if isinstance(getattr(cfg, head), tuple) and isinstance(value, list):
            value = tuple(value)
        return dataclasses.replace(cfg, **{head: value})
    return dataclasses.replace(cfg, **{head: _replace_dotted(getattr(cfg, head), rest, value)})


def weights_seed(cfg_file: dict, seed: int) -> int:
    """The seed the timed run's weights are drawn from: `--seed`, unless
    the configuration file carries `weights_seed`, which makes one draw of
    the weights part of the cell (README, `configs/`: one configuration
    has it, as a stop-gap). The key is an integer, and the file's
    `assumed` says why it is there and what removes it."""
    if "weights_seed" not in cfg_file:
        return int(seed)
    value = cfg_file["weights_seed"]
    if type(value) is not int or value < 0:
        raise ManifestError(f"{cfg_file.get('name')}: weights_seed is {value!r}, not a whole number")
    if "weights_seed" not in cfg_file.get("assumed", {}):
        raise ManifestError(f"{cfg_file.get('name')}: weights_seed has no reason under `assumed`")
    return value


def build_train_config(cfg_file: dict, traffic: dict, seed: int, workdir: str, rehearse: bool):
    """The program's `TrainConfig`: the preset the configuration file
    names, its `overrides`, the traffic file's `overrides` (mesh shape),
    then the seed and the workdir. A rehearsal adds each file's
    `rehearsal.overrides` (a tiny model on the CPU). `cfg.seed` is what
    the program draws its weights from (and the order it reads the pool
    in, and where it cuts a document's two windows): `weights_seed` says
    which. The pool's documents and `correct`'s sample are the caller's,
    from `--seed` always; `correct`'s weights are `cfg.seed`'s, the timed
    run's."""
    from moco_tpu.utils.config import PRESETS

    cfg = PRESETS[cfg_file["preset"]]
    layers = [cfg_file.get("overrides", {}), traffic.get("overrides", {})]
    if rehearse:
        layers += [cfg_file.get("rehearsal", {}).get("overrides", {}),
                   traffic.get("rehearsal", {}).get("overrides", {})]
    for layer in layers:
        for key, value in layer.items():
            cfg = _replace_dotted(cfg, key, value)
    return dataclasses.replace(
        cfg, seed=weights_seed(cfg_file, seed), workdir=workdir, knn_every_epochs=0
    )


def merged(d: dict, rehearse: bool) -> dict:
    """A data file with its `rehearsal` block folded in when rehearsing."""
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    if rehearse:
        out.update({k: v for k, v in d.get("rehearsal", {}).items() if k != "overrides"})
    return out


def read_jsonl(path: str) -> list[dict]:
    out = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass  # a line being written
    return out


def start_device_trace(trace_dir: str) -> None:
    """Start jax's profiler for the device and minimal host events only.
    With the defaults the Python tracer is on, which slowed this host's
    step loop ~11x (PERF.md section 6, PR 24): the traced seconds then
    stand for nothing."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def end_to_end_metrics(manifest, cell: str, values: dict) -> dict:
    """The `--trace 0` metrics: those of `values` the manifest lists for the cell."""
    return {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in manifest.metrics_for(cell, "end_to_end")
        if values.get(m["name"]) is not None
    }


def add_traced(result: dict, detail: dict, manifest, cell: str, ctx: dict, loaded: dict) -> None:
    """The `--trace 1` part of a result line: per-layer metrics through
    their readers, the device's busy and traced seconds, the breakdown."""
    from benchmarks.harness.manifest import read_layer_metrics

    reduced = ctx["trace"]
    result["metrics"] = read_layer_metrics(manifest, cell, ctx)
    result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result["breakdown"] = {
        "device_ops": reduced.get("device_ops", []), "idle_gaps": reduced.get("idle_gaps", []),
    }
    detail["trace"] = {**reduced, "lines": loaded["lines"], "planes": loaded["planes"]}
