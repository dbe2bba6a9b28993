"""End-to-end driver smoke: train() on synthetic data, resume, CLI config.

The reference has no tests (SURVEY.md §4); its implicit e2e check is
"loss goes down and checkpoints restore". Reproduced here in miniature.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from moco_tpu.data.datasets import SyntheticDataset
from moco_tpu.utils.config import DataConfig, MocoConfig, OptimConfig, ParallelConfig, TrainConfig


def _tiny_config(workdir, epochs=2, shuffle="gather_perm"):
    return TrainConfig(
        moco=MocoConfig(
            arch="resnet18",
            dim=16,
            num_negatives=64,
            temperature=0.2,
            mlp=True,
            shuffle=shuffle,
            cifar_stem=True,
            compute_dtype="float32",
        ),
        optim=OptimConfig(lr=0.03, epochs=epochs, cos=True),
        data=DataConfig(dataset="synthetic", image_size=16, global_batch=16, num_workers=2),
        parallel=ParallelConfig(),
        workdir=str(workdir),
        log_every=2,
    )


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from moco_tpu.train import train

    workdir = tmp_path_factory.mktemp("train_e2e")
    config = _tiny_config(workdir)
    dataset = SyntheticDataset(num_examples=64, image_size=16)
    result = train(config, dataset=dataset)
    return config, dataset, result


# The full-driver e2e tests compile and run real training loops over the
# 8-virtual-device mesh — minutes each on a CPU host. They carry the
# `slow` marker (tier-1 deselects them); CI's chaos-smoke job exercises
# the same driver paths end-to-end in every PR.
@pytest.mark.slow
def test_train_runs_and_reports(trained):
    _, _, result = trained
    assert result["epoch"] == 1
    assert np.isfinite(result["loss"])
    assert 0.0 <= result["acc1"] <= 100.0


@pytest.mark.slow
def test_train_writes_metrics_and_checkpoints(trained):
    config, _, _ = trained
    lines = [json.loads(l) for l in open(os.path.join(config.workdir, "metrics.jsonl"))]
    assert lines and {"loss", "acc1", "lr", "epoch"} <= set(lines[-1])
    # lr followed the cosine schedule downward across epochs
    lrs = [l["lr"] for l in lines]
    assert lrs[-1] < lrs[0]


@pytest.mark.slow
def test_train_resumes_from_checkpoint(trained):
    from moco_tpu.train import train

    config, dataset, _ = trained
    # extend epochs; train() must resume at epoch 2, not restart
    config3 = dataclasses.replace(config, optim=dataclasses.replace(config.optim, epochs=3))
    result = train(config3, dataset=dataset)
    assert result["epoch"] == 2


@pytest.mark.slow
def test_sigterm_checkpoints_and_exits_cleanly(tmp_path):
    """Preemption: SIGTERM mid-training -> save within a step, clean
    return, resumable state; original handlers restored afterwards."""
    import os
    import signal
    import threading

    from moco_tpu.train import train
    from moco_tpu.utils.checkpoint import CheckpointManager

    config = _tiny_config(tmp_path / "preempt", epochs=50, shuffle="none")
    dataset = SyntheticDataset(num_examples=64, image_size=16)
    before_handler = signal.getsignal(signal.SIGTERM)
    timer = threading.Timer(6.0, lambda: os.kill(os.getpid(), signal.SIGTERM))
    timer.start()
    try:
        train(config, dataset=dataset)  # returns early instead of dying
    finally:
        timer.cancel()
    assert signal.getsignal(signal.SIGTERM) is before_handler
    mgr = CheckpointManager(str(config.workdir))
    assert mgr.latest_step() is not None
    extra = mgr.read_extra()
    assert extra["epoch"] < 49  # exited before finishing all 50 epochs
    mgr.close()


@pytest.mark.slow
def test_preempt_fault_resume_and_nan_guard(tmp_path):
    """Injected-fault end-to-end (fault-tolerance layer):

    1. deterministic SIGTERM mid-epoch (preempt fault at global step 3 of
       a 3-epoch / 2-steps-per-epoch run) -> mid-epoch checkpoint, clean
       early return, at most one step of overrun;
    2. resume redoes the partial epoch at its full step count and — with
       a NaN loss injected at one resumed step — the non-finite guard
       skips that update while keeping the step counter advancing, so the
       run still completes at exactly the fault-free total.
    """
    import json

    from moco_tpu.train import train
    from moco_tpu.utils import faults
    from moco_tpu.utils.checkpoint import CheckpointManager

    spe = 2  # 32 examples / batch 16
    config = dataclasses.replace(
        _tiny_config(tmp_path / "chaos", epochs=3, shuffle="none"), log_every=1
    )
    dataset = SyntheticDataset(num_examples=32, image_size=16)

    faults.install("preempt@step=3")
    try:
        train(config, dataset=dataset)
    finally:
        faults.clear()
    mgr = CheckpointManager(str(config.workdir))
    mid_step = mgr.latest_step()
    mid_extra = mgr.read_extra()
    mgr.close()
    # SIGTERM landed at step 3 (epoch 1's first step); the save happens
    # within one step and records epoch 0 as the last COMPLETED epoch
    assert mid_extra["epoch"] == 0
    assert spe < mid_step <= 2 * spe  # mid-epoch, at most one step late

    faults.install("nan@step=5")  # one resumed step observes NaN loss
    try:
        result = train(config, dataset=dataset)
    finally:
        faults.clear()
    assert result["epoch"] == 2  # ran to completion
    mgr = CheckpointManager(str(config.workdir))
    final_step = mgr.latest_step()
    mgr.close()
    # the redone partial epoch has its full step count: final id is the
    # preemption save plus exactly the 2 redone epochs
    assert final_step == mid_step + 2 * spe
    # ...and the preemption cost at most one checkpoint interval of work
    assert final_step - 3 * spe <= spe
    events = [
        json.loads(l)
        for l in open(os.path.join(config.workdir, "metrics.jsonl"))
    ]
    nan_events = [e for e in events if e.get("event") == "nonfinite_loss"]
    assert len(nan_events) == 1 and nan_events[0]["nan_steps"] == 1


@pytest.mark.slow
def test_nan_guard_aborts_past_threshold(tmp_path):
    """Persistent divergence must kill the run with diagnostics, not
    burn the fleet: every log step NaN + threshold 2 -> abort on the
    second event."""
    from moco_tpu.train import train
    from moco_tpu.utils import faults

    config = dataclasses.replace(
        _tiny_config(tmp_path / "nan_abort", epochs=2, shuffle="none"),
        log_every=1,
        nan_guard_threshold=2,
    )
    dataset = SyntheticDataset(num_examples=32, image_size=16)
    faults.install("nan@step=1:times=99")
    try:
        with pytest.raises(FloatingPointError, match="non-finite"):
            train(config, dataset=dataset)
    finally:
        faults.clear()


@pytest.mark.slow
def test_resume_incompatible_config_fails_fast(trained):
    """Resuming under a structurally different config raises the
    field-by-field diff BEFORE restoring (a shape-mismatch restore would
    read as corruption and quarantine a good checkpoint)."""
    from moco_tpu.train import train
    from moco_tpu.utils.config import ResumeCompatError

    config, dataset, _ = trained
    bad = dataclasses.replace(
        config,
        moco=dataclasses.replace(config.moco, dim=32),
        optim=dataclasses.replace(config.optim, epochs=5),
    )
    with pytest.raises(ResumeCompatError, match="moco.dim"):
        train(bad, dataset=dataset)
    # nothing was quarantined for it
    assert not os.path.isdir(os.path.join(config.workdir, "quarantine"))


def test_cli_maps_reference_flags(tmp_path):
    import train as cli

    args = cli.build_parser().parse_args(
        [
            "--arch", "resnet50", "--mlp", "--aug-plus", "--cos",
            "--moco-t", "0.2", "--lr", "0.03", "--batch-size", "256",
            "--epochs", "200", "--workdir", str(tmp_path),
            "--watchdog-timeout", "300", "--nan-guard-threshold", "5",
        ]
    )
    cfg = cli.config_from_args(args)
    assert cfg.moco.arch == "resnet50" and cfg.moco.mlp
    assert cfg.moco.temperature == 0.2
    assert cfg.optim.cos and cfg.optim.lr == 0.03
    assert cfg.data.global_batch == 256 and cfg.data.aug_plus
    assert cfg.workdir == str(tmp_path)
    assert cfg.watchdog_timeout == 300.0 and cfg.nan_guard_threshold == 5


def test_cli_preset_with_override(tmp_path):
    import train as cli

    args = cli.build_parser().parse_args(
        ["--preset", "cifar_smoke", "--epochs", "1", "--workdir", str(tmp_path)]
    )
    cfg = cli.config_from_args(args)
    assert cfg.moco.arch == "resnet18" and cfg.moco.cifar_stem
    assert cfg.optim.epochs == 1  # override wins over preset


# -- the log flush's rule (PR 27) -----------------------------------------


def test_log_flush_reads_only_the_step_metrics_and_dispatches_nothing(tmp_path, monkeypatch):
    """Inside `flush_log` the host waits only for device work dispatched
    before the newest step: the one `device_get` of the logged step's
    metrics. Anything else the flush dispatched and read would wait for
    the steps in flight and leave the device's queue empty (PR 26 measured
    it: `float(lr_schedule(...))` and, behind it, the one-process fleet
    reduce). Held here by refusing EVERY host-to-device transfer while a
    `log_flush` span is open on the driver thread (a `jnp` op on a Python
    number and a `device_put` both need one; on the CPU backend the
    device-to-host direction cannot be guarded), and by counting the
    explicit reads. One device, a CIFAR-stem ResNet-18 at 16 px, 7 steps."""
    import threading

    import jax

    from moco_tpu.obs.fleet import FLEET_FIELDS
    from moco_tpu.train import train
    from moco_tpu.utils.schedules import make_lr_schedule

    local = threading.local()  # the ring thread transfers batches all the while
    flushes = []  # one entry a flush: the trees it read back

    class FlushGuard:
        """Stands in for `jax.profiler.TraceAnnotation`, which `train()`
        installs as the annotator of every `obs.span`."""

        def __init__(self, name, **kwargs):
            self.flush = name == "moco/log_flush"

        def __enter__(self):
            if self.flush:
                local.reads = []
                self.guard = jax.transfer_guard_host_to_device("disallow_explicit")
                self.guard.__enter__()
            return self

        def __exit__(self, *exc):
            if self.flush:
                self.guard.__exit__(*exc)
                flushes.append(local.reads)
                local.reads = None
            return False

    device_get = jax.device_get

    def counting_device_get(tree):
        if getattr(local, "reads", None) is not None:
            local.reads.append(tree)
        return device_get(tree)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FlushGuard)
    monkeypatch.setattr(jax, "device_get", counting_device_get)

    steps = 7
    config = dataclasses.replace(
        _tiny_config(tmp_path, epochs=1, shuffle="none"),
        parallel=ParallelConfig(num_data=1), knn_every_epochs=0,
    )
    assert config.fleet_metrics and config.log_every == 2
    train(config, dataset=SyntheticDataset(num_examples=16 * steps, image_size=16))

    lines = [json.loads(l) for l in open(os.path.join(config.workdir, "metrics.jsonl"))]
    logged = [l for l in lines if "loss" in l]
    assert [l["step"] for l in logged] == [1, 3, 5, 7]
    # one read a flush, and it is the logged step's metrics tree
    assert len(flushes) == len(logged)
    for reads in flushes:
        assert len(reads) == 1 and "loss" in reads[0], [sorted(r) for r in reads]
    on_device = make_lr_schedule(config.optim, steps)
    for l in logged:
        assert l["lr"] == pytest.approx(float(on_device(l["step"] - 1)), rel=1e-6)
        assert l["fleet_hosts"] == 1 and l["straggler_skew"] == pytest.approx(0.0)
        for name in FLEET_FIELDS:
            assert {f"fleet/{name}_{r}" for r in ("min", "mean", "max", "argmax")} <= set(l)
        assert l["fleet/t_step_mean"] == pytest.approx(l["t_step"], rel=1e-6)
        assert l["phase/log_flush_host"] >= 0.0
        assert l["phase/fleet_gather"] == 0.0  # one process: no collective ran
        assert "phase/lr_fetch" not in l
