"""Elastic training (parallel/elastic.py + the driver's rescale loop):
heartbeat-staleness detection, the rescale-consensus barrier, the
feasible-width policy, the auto-scale hyperparameter derivation
(m^kappa / linear LR), the kill@host chaos fault, the rescale event
schema, the graceful-preemption (SIGTERM) emergency-checkpoint path,
and the retry-wrapped serve_ingest POSTs."""

import dataclasses
import json
import os
import threading
import time

import pytest

from moco_tpu.parallel.elastic import (
    ElasticCoordinator,
    RescalePlan,
    feasible_width,
    plan_rescale,
    rescale_path,
    surviving_devices,
)
from moco_tpu.utils.config import (
    DataConfig,
    MocoConfig,
    TrainConfig,
    apply_auto_scale,
    parse_auto_scale,
    config_from_dict,
    config_to_dict,
)

from tests.conftest import load_script


def _beat(workdir, process, t):
    path = os.path.join(workdir, f"heartbeat.p{process}.json")
    with open(path, "w") as f:
        json.dump({"process": process, "time": t, "step": 1, "epoch": 0}, f)


# -- feasible-width policy ------------------------------------------------


def test_feasible_width_keeps_queue_divisibility():
    # per-device batch 8, K=128: 7/6/5 all break K % global == 0 -> 4
    assert feasible_width(7, 8, 128) == 4
    # a divisible width survives as-is
    assert feasible_width(6, 8, 96) == 6
    # queue-free (v3): any surviving width works
    assert feasible_width(7, 8, 0) == 7


def test_feasible_width_errors():
    with pytest.raises(ValueError, match="no surviving hosts"):
        feasible_width(0, 8, 128)
    with pytest.raises(ValueError, match="divisible"):
        feasible_width(3, 7, 128)  # 128 % 7/14/21 != 0


# -- auto-scale derivation ------------------------------------------------


def test_parse_auto_scale():
    assert parse_auto_scale("") is None
    assert parse_auto_scale("ref_batch=256") == 256
    with pytest.raises(ValueError):
        parse_auto_scale("ref_batch=0")
    with pytest.raises(ValueError):
        parse_auto_scale("batch=256")


def test_apply_auto_scale_identity_and_kappa():
    base = TrainConfig(
        moco=MocoConfig(momentum=0.99),
        data=DataConfig(global_batch=128),
    )
    same, info = apply_auto_scale(base)
    assert same is base and info is None

    cfg = dataclasses.replace(base, auto_scale="ref_batch=256")
    derived, info = apply_auto_scale(cfg)
    assert info["kappa"] == 0.5
    assert derived.optim.lr == pytest.approx(cfg.optim.lr * 0.5)
    assert derived.moco.momentum == pytest.approx(0.99**0.5)
    # always derives from the passed (reference) values: re-applying to
    # the reference gives the same result, not a compounded one
    derived2, _ = apply_auto_scale(cfg)
    assert derived2.optim.lr == derived.optim.lr


def test_config_roundtrips_elastic_fields():
    cfg = TrainConfig(elastic=True, heartbeat_timeout=7.5, auto_scale="ref_batch=64")
    rt = config_from_dict(config_to_dict(cfg))
    assert rt.elastic and rt.heartbeat_timeout == 7.5
    assert rt.auto_scale == "ref_batch=64"


# -- rescale planning -----------------------------------------------------


def test_plan_rescale_derives_mesh_batch_and_hyperparams():
    cfg = TrainConfig(
        moco=MocoConfig(num_negatives=128, momentum=0.99),
        data=DataConfig(global_batch=64),
        auto_scale="ref_batch=64",
    )
    plan, new_ref, info = plan_rescale(cfg, 8, 1, [2], step=3)
    assert plan.old_num_data == 8 and plan.new_num_data == 4
    assert plan.old_global_batch == 64 and plan.new_global_batch == 32
    assert plan.dead_hosts == (2,)
    assert new_ref.parallel.num_data == 4
    assert new_ref.data.global_batch == 32
    # the reference hyperparameters stay the anchor in the new ref config
    assert new_ref.optim.lr == cfg.optim.lr
    assert info["kappa"] == 0.5
    assert info["momentum"] == pytest.approx(0.99**0.5)
    assert info["lr"] == pytest.approx(cfg.optim.lr * 0.5)


def test_plan_rescale_rejects_model_parallel():
    cfg = TrainConfig(data=DataConfig(global_batch=64))
    with pytest.raises(ValueError, match="num_model=1"):
        plan_rescale(cfg, 8, 2, [2], step=3)


def test_surviving_devices_excludes_dead_host_indices():
    import jax

    devs = surviving_devices([2, 5])
    assert len(devs) == len(jax.devices()) - 2
    assert jax.devices()[2] not in devs and jax.devices()[5] not in devs


# -- heartbeat-staleness detection ---------------------------------------


def test_stale_hosts_flags_only_new_dead(tmp_path):
    now = time.time()
    _beat(tmp_path, 0, now)  # self
    _beat(tmp_path, 1, now - 1.0)  # fresh
    _beat(tmp_path, 2, 0.0)  # dead
    _beat(tmp_path, 3, now - 100.0)  # dead
    _beat(tmp_path, 4, 0.0)  # dead but already rescaled away
    coord = ElasticCoordinator(
        str(tmp_path), process_index=0, num_processes=5, timeout=10.0, known_dead=[4]
    )
    assert coord.stale_hosts(now=now) == [2, 3]
    # a revived host drops off the stale list
    _beat(tmp_path, 2, now)
    assert coord.stale_hosts(now=now) == [3]


def test_stale_hosts_ignores_hosts_that_never_beat(tmp_path):
    _beat(tmp_path, 0, time.time())
    coord = ElasticCoordinator(str(tmp_path), 0, num_processes=8, timeout=5.0)
    assert coord.stale_hosts() == []


# -- rescale-consensus barrier -------------------------------------------


def _plan(dead=(2,), new_n=4, new_b=32, step=3):
    return RescalePlan(
        step=step, dead_hosts=tuple(dead), old_num_data=8, new_num_data=new_n,
        old_global_batch=64, new_global_batch=new_b,
    )


def test_consensus_barrier_agrees_across_survivors(tmp_path):
    """Two survivors of a 3-host fleet (host 2 dead) publish matching
    plans from separate threads; both clear the barrier."""
    coords = [
        ElasticCoordinator(str(tmp_path), p, num_processes=3, barrier_timeout=5.0)
        for p in (0, 1)
    ]
    results, errors = {}, []

    def run(i):
        try:
            results[i] = coords[i].agree(_plan(step=3 + i))  # step may differ
        except Exception as e:  # pragma: no cover - surfaced by assert
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not errors and set(results) == {0, 1}
    for p in (0, 1):
        assert os.path.exists(rescale_path(str(tmp_path), p))


def test_consensus_barrier_times_out_without_peer(tmp_path):
    coord = ElasticCoordinator(
        str(tmp_path), 0, num_processes=2, barrier_timeout=0.3, poll_interval=0.02
    )
    with pytest.raises(RuntimeError, match="timed out"):
        coord.agree(_plan())


def test_consensus_barrier_rejects_conflicting_plan(tmp_path):
    # peer 1 freshly proposes a DIFFERENT world -> split brain, abort
    with open(rescale_path(str(tmp_path), 1), "w") as f:
        json.dump(
            {"process": 1, "time": time.time(), "dead_hosts": [3],
             "new_num_data": 2, "new_global_batch": 16},
            f,
        )
    coord = ElasticCoordinator(
        str(tmp_path), 0, num_processes=2, barrier_timeout=1.0, poll_interval=0.02
    )
    with pytest.raises(RuntimeError, match="conflict"):
        coord.agree(_plan())


def test_consensus_barrier_ignores_stale_previous_round(tmp_path):
    """A leftover file from a PREVIOUS rescale (old timestamp, smaller
    dead set) must not read as a conflict — the barrier waits for the
    peer to overwrite it (and times out here, since none does)."""
    with open(rescale_path(str(tmp_path), 1), "w") as f:
        json.dump(
            {"process": 1, "time": time.time() - 3600, "dead_hosts": [],
             "new_num_data": 8, "new_global_batch": 64},
            f,
        )
    coord = ElasticCoordinator(
        str(tmp_path), 0, num_processes=2, barrier_timeout=0.3, poll_interval=0.02
    )
    with pytest.raises(RuntimeError, match="timed out"):
        coord.agree(_plan())


# -- alerts: configurable heartbeat threshold ----------------------------


def test_default_alert_spec_takes_heartbeat_timeout():
    from moco_tpu.obs.alerts import parse_rules

    hb = [r for r in parse_rules("default", heartbeat_timeout=9.0) if r.kind == "heartbeat"]
    assert hb and hb[0].timeout == 9.0
    # explicit heartbeat@ rules keep their own timeout
    spec = "default,heartbeat@name=custom_hb:timeout=33"
    rules = {r.name: r for r in parse_rules(spec, heartbeat_timeout=9.0)}
    assert rules["heartbeat_loss"].timeout == 9.0
    assert rules["custom_hb"].timeout == 33.0


# -- schema: rescale / preempt event lines -------------------------------


def test_rescale_event_line_schema():
    from moco_tpu.obs.schema import validate_line

    line = {
        "step": 3, "time": 1.0, "epoch": 1, "event": "rescale",
        "rescale/dead_hosts": [2], "rescale/old_num_data": 8,
        "rescale/new_num_data": 4, "rescale/old_global_batch": 64,
        "rescale/new_global_batch": 32, "rescale/kappa": 0.5,
        "rescale/lr": 0.015, "rescale/momentum": 0.99498,
    }
    assert validate_line(line) == []
    assert validate_line({**line, "rescale/new_num_data": "four"})
    assert validate_line({**line, "rescale/dead_hosts": "2"})
    assert validate_line({"step": 1, "time": 1.0, "epoch": 0, "event": "preempt"}) == []


# -- serve_ingest: retry-wrapped POSTs -----------------------------------


class _FakeResponse:
    def __init__(self, payload):
        self._payload = payload

    def read(self):
        return self._payload

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_serve_ingest_posts_retry_through_backoff(monkeypatch):
    """A replica restart mid-tail (one connection-refused POST) degrades
    to a logged retry at site ingest.post — the block is re-POSTed, not
    dropped."""
    import urllib.error

    import numpy as np

    from moco_tpu.utils import retry

    ingest = load_script("serve_ingest.py")
    calls = {"n": 0}

    def flaky_urlopen(req, timeout=0):
        calls["n"] += 1
        if calls["n"] == 1:
            raise urllib.error.URLError("connection refused")
        return _FakeResponse(json.dumps({"index_rows": 7}).encode())

    monkeypatch.setattr(ingest, "_urlopen", flaky_urlopen)
    monkeypatch.setattr(retry, "_retries", retry._retries.__class__())
    rows = np.zeros((3, 4), np.float32)
    got = ingest.post_rows("http://127.0.0.1:9", rows, block=8)
    assert got == 7 and calls["n"] == 2
    assert retry.snapshot().get("ingest.post") == 1


def test_serve_ingest_propagates_persistent_failure(monkeypatch):
    import urllib.error

    import numpy as np

    ingest = load_script("serve_ingest.py")

    def down(req, timeout=0):
        raise urllib.error.URLError("still down")

    monkeypatch.setattr(ingest, "_urlopen", down)
    monkeypatch.setenv("MOCO_IO_RETRY_BASE", "0.001")
    monkeypatch.setenv("MOCO_IO_RETRY_MAX", "0.002")
    with pytest.raises(urllib.error.URLError):
        ingest.post_rows("http://127.0.0.1:9", np.zeros((1, 4), np.float32))


# -- driver end-to-end (slow: full chaos run, same path CI's smoke runs) --


@pytest.mark.slow
def test_elastic_driver_rescales_and_finishes(tmp_path):
    """The acceptance chaos run, in-process: kill@host=2 on a fake-8
    ZeRO-2/3 mesh -> heartbeat staleness -> consensus -> emergency
    checkpoint -> 8->4 reshard -> m^kappa / linear-LR rescale -> resume
    to completion, loss within tolerance of the uninterrupted control."""
    smoke = load_script("elastic_smoke.py")
    control = smoke.run_control(str(tmp_path / "control"))
    chaos = smoke.run_chaos(str(tmp_path / "chaos"))
    summary = smoke.assert_surface(str(tmp_path / "chaos"), chaos, control)
    assert summary["rescale_event"]["rescale/new_num_data"] == 4


@pytest.mark.slow
def test_sigterm_to_driver_subprocess_takes_emergency_path(tmp_path):
    """Graceful preemption the way preemptible VMs announce it: SIGTERM
    to a real driver subprocess -> `event: "preempt"` metrics line, a
    durable emergency checkpoint tagged with the reason, exit 0."""
    import signal
    import subprocess
    import sys

    workdir = str(tmp_path / "preempt")
    script = os.path.join(os.path.dirname(__file__), "..", "scripts", "chaos_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, script, "--worker", "--workdir", workdir, "--epochs", "50"],
        env=env,
    )
    try:
        metrics = os.path.join(workdir, "metrics.jsonl")
        deadline = time.time() + 300
        while time.time() < deadline:
            if os.path.exists(metrics) and os.path.getsize(metrics) > 0:
                break
            time.sleep(0.5)
        else:  # pragma: no cover
            pytest.fail("driver subprocess produced no metrics in time")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 0  # graceful: saved, then returned
    lines = [json.loads(l) for l in open(metrics) if l.strip()]
    assert any(l.get("event") == "preempt" for l in lines)

    from moco_tpu.utils.checkpoint import CheckpointManager

    mgr = CheckpointManager(workdir)
    step = mgr.latest_step()
    assert step is not None
    extra = mgr.read_extra(step)
    mgr.close()
    assert extra.get("reason") == "preempt" and extra.get("emergency") is True
    assert extra["epoch"] < 49  # exited long before the configured run
