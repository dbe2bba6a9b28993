"""Test harness: force an 8-virtual-device CPU platform.

This is the JAX-native answer to "test multi-node without a cluster"
(SURVEY.md §4): `--xla_force_host_platform_device_count=8` gives 8
CpuDevices, so every cross-replica pattern (shuffle-BN, queue lockstep,
grad psum) runs under a real Mesh in CI.

Must run before jax initializes a backend; the environment may pin
JAX_PLATFORMS to an accelerator (the chip machine sets `tpu,cpu`), so
we override both the env var and the config flag.
"""

import os

# MOCO_TPU_TESTS=1 leaves the real accelerator visible so the TPU-gated
# kernel tests (tests/test_tpu_kernels.py) can drive compiled Mosaic
# kernels: `MOCO_TPU_TESTS=1 pytest tests/test_tpu_kernels.py`. Default
# runs pin the 8-virtual-device CPU platform.
if not os.environ.get("MOCO_TPU_TESTS"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax

if not os.environ.get("MOCO_TPU_TESTS"):
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)


def load_script(name: str):
    """Import a module from scripts/ by filename (they are not a
    package); shared by tests that exercise script-level entry points."""
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), "..", "scripts", name)
    spec = importlib.util.spec_from_file_location(name.removesuffix(".py"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
