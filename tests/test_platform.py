"""Guards on moco_tpu.utils.platform and the start-up rules around it.

The compile cache must be placeable from outside: with
`JAX_COMPILATION_CACHE_DIR` set the program sets no directory in code;
unset, it is one fixed git-ignored path inside the checkout — except on
a CPU-pinned run, which skips it from the environment alone (XLA:CPU's
AOT cache loader warns about machine-feature mismatches between writer
and reader). Deciding must never initialise a backend: multi-host runs
rendezvous first.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from moco_tpu.utils import platform
from moco_tpu.utils.platform import enable_persistent_compilation_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_means_nothing_is_set_in_code(restore_cache_config, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, on any
    platform — the function reports it and touches no config."""
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    for pinned in ("cpu", "tpu,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", pinned)
        assert enable_persistent_compilation_cache() == str(tmp_path / "outside")
        assert jax.config.jax_compilation_cache_dir is None
    assert not (tmp_path / "outside").exists()


def test_cpu_pinned_run_skips_cache(restore_cache_config, monkeypatch):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert enable_persistent_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_env_uses_the_fixed_in_checkout_dir(restore_cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    for unpinned in ("", "tpu,cpu"):
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_PLATFORMS", unpinned)
        assert enable_persistent_compilation_cache() == platform.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == platform.DEFAULT_CACHE_DIR
    assert platform.DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    # git must never see it
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=ROOT
    ).returncode
    assert ignored == 0


def test_cache_decision_initialises_no_backend():
    """In a fresh interpreter, on the path that does set a directory."""
    code = (  # the module by path: the package import would pull in flax/orbax
        "import importlib.util as u\n"
        "spec = u.spec_from_file_location('platform_', 'moco_tpu/utils/platform.py')\n"
        "mod = u.module_from_spec(spec); spec.loader.exec_module(mod)\n"
        "assert mod.enable_persistent_compilation_cache().endswith('.jax_cache')\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = ""
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, check=True, timeout=120)


def test_diagnostics_stay_off_stdout(capsys, monkeypatch):
    """An entry point owns its stdout (the last line of `chip_smoke.py`
    and of `benchmarks/run.py` is the result): what the library has to say
    about the platform goes to stderr."""
    from moco_tpu.data import native_loader

    platform.pallas_interpret.__wrapped__()

    def no_compiler():
        raise OSError("no compiler here")

    monkeypatch.setattr(native_loader, "_load_lib", no_compiler)
    assert native_loader.native_available.__wrapped__() is False
    platform.log_devices("test", file=sys.stderr)
    out, err = capsys.readouterr()
    assert out == ""
    assert "pallas kernels: not compiled on backend 'cpu'" in err
    assert "native loader unavailable" in err and "no compiler here" in err
    assert "test: platform=cpu" in err


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    """chip_smoke.py has no CPU mode: on a machine with no TPU it exits
    non-zero, names the platform jax resolved, and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_knows_where_the_cache_is(monkeypatch):
    """chip_smoke's parent counts cache entries without importing jax,
    so it carries the placement rule a second time: keep them equal."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert mod.cache_dir() == platform.DEFAULT_CACHE_DIR
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert mod.cache_dir() == "/some/dir"


def test_bn_compile_repro_grid_order():
    """The bisect harness must order each depth's cells baseline-first,
    shipped-slice-suspects last (a run cut short forfeits the least
    information — scripts/bn_compile_repro.py docstring)."""
    from tests.conftest import load_script

    mod = load_script("bn_compile_repro.py")
    cells = mod.depth_cells([0, 32, 8], ["mask", "fwd", "barrier", "slice"])
    assert cells[0] == ("slice", 0)
    assert cells[-2:] == [("slice", 32), ("slice", 8)]
    # controls in between, one per (variant, subset-rows) pair
    assert set(cells[1:-2]) == {
        (v, r) for v in ("mask", "fwd", "barrier") for r in (32, 8)
    }
    # no slice: no baseline cell, nothing crashes
    assert mod.depth_cells([0, 32], ["mask"]) == [("mask", 32)]


def test_profile_input_jpeg_folder_same_bytes_and_built_once(tmp_path):
    """scripts/profile_input.py makes its ImageFolder from a fixed seed:
    the same arguments give the same bytes, and a second call on a
    finished folder writes nothing."""
    from tests.conftest import load_script

    mod = load_script("profile_input.py")

    def snapshot(root):
        found = {}
        for base, _, names in os.walk(root):
            for n in names:
                path = os.path.join(base, n)
                with open(path, "rb") as f:
                    found[os.path.relpath(path, root)] = (f.read(), os.stat(path).st_mtime_ns)
        return found

    a = snapshot(mod._ensure_jpeg_folder(str(tmp_path / "a"), 10, 32))
    b = snapshot(mod._ensure_jpeg_folder(str(tmp_path / "b"), 10, 32))
    assert sorted(a) == sorted(b) and len(a) == 11  # ten images over eight classes + the stamp
    assert {k: v[0] for k, v in a.items()} == {k: v[0] for k, v in b.items()}
    assert a["class_1/img_00009.jpg"][0][:2] == b"\xff\xd8"
    assert mod._ensure_jpeg_folder(str(tmp_path / "a"), 10, 32) == str(tmp_path / "a")
    assert snapshot(str(tmp_path / "a")) == a
