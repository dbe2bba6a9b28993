"""End-to-end telemetry: the ≤5-step CPU driver smoke must produce the
full observability surface (ISSUE-3 acceptance bullet).

The assertions live in `scripts/obs_smoke.py` (CI's tier-1 job runs the
same script and uploads its workdir as artifacts); here they run under
pytest against a fresh driver run. Slow-marked like the other
full-driver e2e tests — the obs-smoke CI step covers every PR."""

import json
import os

import pytest

from tests.conftest import load_script


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    mod = load_script("obs_smoke.py")
    workdir = str(tmp_path_factory.mktemp("obs_smoke"))
    out = mod.run_smoke(workdir)
    return mod, workdir, out


@pytest.mark.slow
def test_driver_smoke_produces_obs_surface(smoke):
    """Chrome trace with nested epoch/step/data_wait spans; JSONL lines
    with t_data/t_step, hbm gauges (null on CPU), queue_age_mean,
    ema_drift, logit pos/neg means; schema-clean; CSV sink populated."""
    mod, workdir, _ = smoke
    mod.assert_obs_surface(workdir)


@pytest.mark.slow
def test_obs_report_renders_driver_run(smoke):
    """`scripts/obs_report.py` renders the real run without error and
    covers every section (the satellite's anti-rot check)."""
    _, workdir, _ = smoke
    report_mod = load_script("obs_report.py")
    report = report_mod.render_report(
        os.path.join(workdir, "metrics.jsonl"), os.path.join(workdir, "trace.json")
    )
    for section in (
        "Step-time breakdown", "Device memory", "Training health",
        "Fault ledger", "Trace summary",
    ):
        assert section in report
    assert "ema_drift" in report and "queue_age_mean" in report


@pytest.mark.slow
def test_driver_trace_json_loads_and_nests(smoke):
    """The golden acceptance check, independent of the smoke script's
    own assertions: the exported file is plain JSON, and the epoch span
    contains its step spans by timestamp on the driver thread."""
    _, workdir, _ = smoke
    with open(os.path.join(workdir, "trace.json")) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    epoch = next(e for e in xs if e["name"] == "epoch")
    steps = [e for e in xs if e["name"] == "step" and e["tid"] == epoch["tid"]]
    assert len(steps) == 3
    for s in steps:
        assert epoch["ts"] <= s["ts"]
        assert s["ts"] + s["dur"] <= epoch["ts"] + epoch["dur"] + 1


# -- the host phase account and the profiler's clock (PR 26) ---------------
#
# One 6-step run of the real driver on the tiny preset, small enough for
# tier-1 (one device, a CIFAR-stem ResNet-18 at 16 px), traced with the
# driver's own `--profile-steps` window.

STEPS = 6


@pytest.fixture(scope="module")
def phase_run(tmp_path_factory):
    import faulthandler
    import time

    from moco_tpu.data.datasets import SyntheticDataset
    from moco_tpu.train import train
    from moco_tpu.utils.config import (
        DataConfig, MocoConfig, OptimConfig, ParallelConfig, TrainConfig,
    )

    workdir = str(tmp_path_factory.mktemp("phase_run"))
    config = TrainConfig(
        moco=MocoConfig(arch="resnet18", dim=16, num_negatives=64, mlp=True, shuffle="none",
                        cifar_stem=True, compute_dtype="float32"),
        optim=OptimConfig(lr=0.03, epochs=1, cos=True),
        data=DataConfig(dataset="synthetic", image_size=16, global_batch=16, num_workers=2),
        parallel=ParallelConfig(num_data=1),
        workdir=workdir, log_every=2, obs_probe_every=2, knn_every_epochs=0,
    )
    t_start = time.time()
    # a profiler session opened mid-run beside the ring's dispatching thread:
    # should it ever wedge, lose this worker after four minutes, not the run
    faulthandler.dump_traceback_later(240, exit=True)
    try:
        train(config, dataset=SyntheticDataset(num_examples=16 * STEPS, image_size=16),
              profile_steps=(2, 5))
    finally:
        faulthandler.cancel_dump_traceback_later()
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        lines = [json.loads(line) for line in f]
    return workdir, t_start, lines


def test_phase_account_on_every_log_line(phase_run):
    from moco_tpu.obs import schema
    from moco_tpu.obs.stepstats import DRIVER_PHASES, RING_PHASES

    workdir, _, lines = phase_run
    logged = [ln for ln in lines if "loss" in ln]
    assert [ln["step"] for ln in logged] == [1, 3, 5, 6]
    for ln in logged:
        for name in DRIVER_PHASES + RING_PHASES + ("log_flush_host",):
            assert ln[f"phase/{name}"] >= 0.0, (name, ln["step"])
    # every step is counted once: the divisors add up to the steps run
    assert sum(ln["phase/steps"] for ln in logged) == STEPS
    # the account holds whole spans only: a flush with its own fetch
    for ln in logged:
        assert ln["phase/log_flush"] >= ln["phase/metrics_fetch"]
    assert any(ln["phase/metrics_fetch"] > 0 for ln in logged[1:])
    assert schema.validate_file(os.path.join(workdir, "metrics.jsonl")) == []


def test_one_setup_line_whose_parts_fit_the_wall_time(phase_run):
    from moco_tpu.obs.stepstats import SETUP_PARTS

    _, t_start, lines = phase_run
    (setup,) = [ln for ln in lines if ln.get("event") == "setup"]
    parts = [setup[f"setup/{p}_s"] for p in SETUP_PARTS]
    assert all(p > 0 for p in parts), dict(zip(SETUP_PARTS, parts))
    first_line = next(ln for ln in lines if "loss" in ln)
    assert setup["step"] == 1 and setup["time"] <= first_line["time"]
    assert sum(parts) <= first_line["time"] - t_start


def test_probe_pair_names_its_step_and_skips_the_compile(phase_run):
    _, _, lines = phase_run
    logged = [ln for ln in lines if "loss" in ln]
    with_pair = [ln for ln in logged if "t_dispatch" in ln]
    assert with_pair and "t_dispatch" not in logged[0]  # the first step is set-up, not a sample
    (setup,) = [ln for ln in lines if ln.get("event") == "setup"]
    for ln in with_pair:
        assert 2 <= ln["t_probe_step"] <= ln["step"] + 1
        assert ln["t_dispatch"] + ln["t_device"] < setup["setup/first_step_s"]


def test_profile_holds_driver_and_ring_spans_on_their_own_lines(phase_run):
    """`--profile-steps a:b` on the CPU: the `/host:CPU` plane holds the
    driver's `moco/train_step` with its `step_num` and the ring thread's
    `moco/transfer` on another line, and the Python tracer stayed off."""
    import glob

    from jax.profiler import ProfileData

    workdir, _, _ = phase_run
    (path,) = glob.glob(os.path.join(workdir, "profile", "**", "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU")
    where = {}
    for i, line in enumerate(host.lines):
        for e in line.events:
            assert not e.name.startswith("$"), "a Python-tracer event: the tracer was on"
            if e.name.startswith("moco/"):
                where.setdefault(e.name, []).append((i, dict(e.stats)))
    steps = sorted(stats["step_num"] for _, stats in where["moco/train_step"])
    assert steps and set(steps) <= {2, 3, 4} and 3 in steps
    driver_lines = {i for i, _ in where["moco/train_step"]}
    assert len(driver_lines) == 1
    for name in ("moco/data_wait", "moco/step", "moco/throttle_wait"):
        assert {i for i, _ in where[name]} == driver_lines, name
    ring_lines = {i for i, _ in where["moco/transfer"]}
    assert ring_lines and not (ring_lines & driver_lines)
    assert {i for i, _ in where["moco/ring_blocked"]} == ring_lines
