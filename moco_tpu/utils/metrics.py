"""Training metrics: meters, progress display, JSONL writer, profiler.

Reference: `AverageMeter` / `ProgressMeter` (`main_moco.py:~L322-360`)
print `Epoch: [e][i/n] Time ... Data ... Loss ... Acc@1 ... Acc@5 ...`
every `--print-freq` steps; non-master ranks are silenced
(`main_moco.py:~L145`). Structured logging lives in `moco_tpu.obs`
(span tracer, sink registry, step-time probe, health gauges) — this
module keeps the reference-shaped console surface plus back-compat
aliases: `MetricWriter` IS the obs JSONL sink (refactored out in the
telemetry PR; same constructor, same crash-safe flush contract).

Multi-host semantics (reference behavior): only process 0 prints
console lines; every process keeps writing its own JSONL/sinks —
per-host metrics matter (a sick host shows up in ITS file), stdout
interleaving from N hosts does not.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax

from moco_tpu.obs.sinks import JsonlSink


def is_primary() -> bool:
    """True on the process that owns console output (process 0; always
    True single-host). Tolerates being called before any backend/
    distributed init."""
    try:
        return jax.process_index() == 0
    except Exception:
        return True


def print0(*args, **kwargs) -> None:
    """`print` on process 0 only — the reference's non-master silencing
    (`main_moco.py:~L145`) for the driver's informational lines."""
    if is_primary():
        print(*args, **kwargs)


class AverageMeter:
    """Running value/average, formatted like the reference's meter."""

    def __init__(self, name: str, fmt: str = ":f"):
        self.name, self.fmt = name, fmt
        self.reset()

    def reset(self) -> None:
        self.val = self.sum = self.count = 0.0

    def update(self, val: float, n: int = 1) -> None:
        self.val = float(val)
        self.sum += float(val) * n
        self.count += n

    @property
    def avg(self) -> float:
        return self.sum / max(self.count, 1)

    def __str__(self) -> str:
        return ("{name} {val" + self.fmt + "} ({avg" + self.fmt + "})").format(
            name=self.name, val=self.val, avg=self.avg
        )


class ProgressMeter:
    """`Epoch: [e][ i/n] <meters>` lines, as `main_moco.py:~L340-360`.

    `display` prints on process 0 only (reference: non-master ranks are
    silenced, `main_moco.py:~L145`) but always returns the formatted
    line, so per-process callers/tests can still observe it."""

    def __init__(self, num_batches: int, meters: list[AverageMeter], prefix: str = ""):
        num_digits = len(str(num_batches))
        self.batch_fmtstr = "[{:" + str(num_digits) + "d}/" + str(num_batches) + "]"
        self.meters = meters
        self.prefix = prefix

    def display(self, batch: int) -> str:
        entries = [self.prefix + self.batch_fmtstr.format(batch)]
        entries += [str(m) for m in self.meters]
        line = "\t".join(entries)
        if is_primary():
            print(line, flush=True)
        return line


class MetricWriter(JsonlSink):
    """Back-compat name for the JSONL sink (see obs/sinks.py): the
    original single-destination writer grew into the sink registry; this
    alias keeps the constructor signature and crash-safe flush contract
    every existing call site (and the chaos harness) relies on."""


# -- jax.profiler management ---------------------------------------------
#
# `jax.profiler.start_trace` is process-global and refuses to start
# while a trace is active. A naive context manager has two failure
# modes: (a) nested/overlapping regions crash the outer one, and (b) a
# region that died between start and stop (exception in user code that
# skipped the finally, or a prior library leaving a trace running)
# poisons every LATER region — start_trace raises forever and the run
# loses profiling. The bookkeeping below makes regions reentrant
# (inner region = no-op) and start-failure self-healing (stop the
# dangler, retry once).

_profiler_state = {"active": False}


def _profile_options():
    """Device events and the host's annotated spans (`moco/<name>`, what
    `obs.span` enters), no Python tracer: with `start_trace`'s defaults
    every Python call is recorded and a traced ResNet-50 step took 1.9 s
    where an untraced one takes 0.177 s (PERF.md section 6, PR 24), so
    the trace stood for nothing."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _start_profiler(logdir: str) -> bool:
    """Start a trace; returns True when THIS call owns the stop. A
    dangling trace from a previous failed region is stopped and the
    start retried once."""
    if _profiler_state["active"]:
        return False  # reentrant region: outer owns the trace
    try:
        jax.profiler.start_trace(logdir, profiler_options=_profile_options())
    except Exception:
        # a trace someone else started and never stopped — clear it and
        # retry once; a second failure is a real error and propagates
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        jax.profiler.start_trace(logdir, profiler_options=_profile_options())
    _profiler_state["active"] = True
    return True


def _stop_profiler() -> None:
    _profiler_state["active"] = False
    jax.profiler.stop_trace()


@contextlib.contextmanager
def profiler_trace(logdir: Optional[str]):
    """`jax.profiler` trace (TensorBoard/Perfetto-viewable) around a
    code region; no-op when logdir is None; reentrancy-safe (an inner
    region under an active one is a no-op rather than a crash)."""
    if not logdir:
        yield
        return
    owns = _start_profiler(logdir)
    try:
        yield
    finally:
        if owns:
            _stop_profiler()


class ProfilerWindow:
    """Windowed `--profile-steps a:b` capture: trace exactly global
    steps [a, b) instead of the whole run. Whole-run traces of long
    jobs are gigabytes of mostly-identical steps; a window placed after
    warmup is what one actually loads into Perfetto. Drive with
    `on_step(gstep)` once per loop iteration; `close()` stops a
    still-open window (early exit, preemption)."""

    def __init__(self, logdir: str, start_step: int, end_step: int):
        if end_step <= start_step:
            raise ValueError(f"empty profile window [{start_step}, {end_step})")
        self.logdir = logdir
        self.start_step = int(start_step)
        self.end_step = int(end_step)
        self._owns = False
        self._done = False

    def on_step(self, gstep: int) -> None:
        """Called with the step about to run; starts/stops the window."""
        if self._done:
            return
        if not self._owns and self.start_step <= gstep < self.end_step:
            self._owns = _start_profiler(self.logdir)
        elif self._owns and gstep >= self.end_step:
            self.close()

    def close(self) -> None:
        if self._owns:
            self._owns = False
            _stop_profiler()
        self._done = True


def parse_profile_steps(spec: str) -> Tuple[int, int]:
    """`"a:b"` -> (a, b) with validation (CLI surface for ProfilerWindow)."""
    try:
        a, b = spec.split(":")
        lo, hi = int(a), int(b)
    except ValueError:
        raise ValueError(f"--profile-steps wants 'a:b' (global steps), got {spec!r}")
    if hi <= lo or lo < 0:
        raise ValueError(f"--profile-steps window [{lo}, {hi}) is empty or negative")
    return lo, hi
