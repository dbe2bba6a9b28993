"""Platform resolution for entry points: which backend, which devices,
where compiled programs are cached, and whether Pallas kernels compile
or interpret.

Two ways to run this repo: on the CPU for tests (`JAX_PLATFORMS=cpu`,
tests/conftest.py's eight virtual devices) and on the TPU. An unpinned
run means "the accelerator": entry points print what they resolved
(:func:`log_devices`) so a run that came up on the wrong platform says
so on its first line instead of being slow and correct.

One process per chip: a process that has initialised the TPU backend
holds every chip on the host until it exits, so nothing here starts a
child to look at the devices.
"""

from __future__ import annotations

import functools
import os
import sys

# The compile cache when the environment names none: one fixed,
# git-ignored directory inside the checkout. A directory that moves
# between runs is never found again, so no /tmp, pid, temp name or
# timestamp.
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def _pinned_platform(env=None) -> str:
    env = os.environ if env is None else env
    return env.get("JAX_PLATFORMS", "").strip().lower()


def cpu_pinned(env=None) -> bool:
    """Whether `env` (default: this process's environment) pins jax to
    the CPU — the one spelling of that question (the compile-cache rule
    below, the replica supervisor's placement check on its children's
    environments)."""
    return _pinned_platform(env).startswith("cpu")


def pin_platform_from_env() -> None:
    """Mirror a ``JAX_PLATFORMS`` env request into jax's config, before
    any operation initializes a backend, so `JAX_PLATFORMS=cpu python
    train.py ...` is CPU-only even with an accelerator plugin installed
    (what tests/conftest.py does for the suite). No-op when unset."""
    want = _pinned_platform()
    if want:
        import jax

        jax.config.update("jax_platforms", want)


def enable_persistent_compilation_cache() -> str | None:
    """Turn on XLA's persistent compilation cache for this process and
    return the directory in use (None = no cache). Called by every
    entry point that compiles, so a second process — the next train
    run, a serving replica booting its buckets — loads what the first
    one compiled.

    - `JAX_COMPILATION_CACHE_DIR` set: jax reads it itself; no other
      directory is set in code, whatever the platform.
    - unset and the run is pinned to the CPU: no cache (compiles are
      not the bottleneck there, and XLA:CPU's AOT cache loader warns
      about machine-feature mismatches between writer and reader).
    - unset otherwise: `DEFAULT_CACHE_DIR`.

    Wherever a cache is used, its key keeps the programs' metadata. A
    device trace names each op by the `moco.` scopes it ran under
    (`obs.trace.STEP_SCOPES`), and those come from the executable. Jax's
    default key leaves them out, so a checkout whose scopes differ from
    another's would load the other's program and its trace would name the
    other's parts. The key then also holds source locations: an edit to a
    traced line, or a second checkout, compiles again.

    Decided from the environment alone — never initialises a backend
    (multi-host runs must rendezvous first, moco_tpu/train.py).
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir and cpu_pinned():
        return None
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def log_devices(who: str, file=None) -> dict:
    """Print the platform, device kind and device count jax resolved —
    the first line of the trainer, a replica and chip_smoke —
    and return them as `{"platform", "device_kind", "count"}`.
    Initialises the backend."""
    import jax

    devices = jax.devices()
    d = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }
    if jax.process_index() == 0:
        print(
            f"{who}: platform={d['platform']} device_kind={d['device_kind']!r} "
            f"devices={d['count']}",
            file=file, flush=True,
        )
    return d


@functools.cache
def pallas_interpret() -> bool:
    """Whether this process runs its Pallas kernels in interpret mode:
    compiled (Mosaic) on a TPU backend, not compiled anywhere else — the
    CPU test suite's mode, where the train-step and attention kernels
    interpret and the fused IVF scan takes its lax variant. Decided once
    per process, from the resolved backend, and logged to stderr (stdout
    belongs to the entry point: `benchmarks/run.py`'s last line is its
    JSON result), so a run
    that lost its chip shows the switch instead of silently interpreting
    every kernel."""
    import jax

    backend = jax.default_backend()
    interpret = backend != "tpu"
    print(
        f"pallas kernels: compiled (Mosaic) on backend {backend!r}"
        if not interpret
        else f"pallas kernels: not compiled on backend {backend!r} "
        "(interpret mode, or the caller's lax variant)",
        file=sys.stderr, flush=True,
    )
    return interpret
