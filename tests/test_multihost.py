"""True multi-process integration test of the multi-host path.

Spawns TWO real OS processes that rendezvous through
`jax.distributed.initialize` (via `initialize_multihost`) on the CPU
backend — the same code path a multi-host TPU pod takes, minus the ICI.
This is the one test where process boundaries are real rather than
simulated with `addressable_devices` overrides (tests/test_dist.py):
collectives cross processes, each process can only address half the
mesh, and the input pipeline must decode only its own global-batch rows.

Reference equivalents: `dist.init_process_group` (`main_moco.py:~L150`)
and `DistributedSampler` (`~L258`).
"""

import json
import os
import socket
import subprocess
import sys
import threading

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_multihost_worker.py")
NPROC = 2
DEVICES_PER_PROC = 2


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={DEVICES_PER_PROC}")
    env["XLA_FLAGS"] = " ".join(flags)
    # a worker must not inherit a half-configured distributed env
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID"):
        env.pop(k, None)
    return env


def _run_pair(extra_args: list[str] | None = None) -> list[dict]:
    """Spawn a 2-process world, drain both workers concurrently, return
    their JSON evidence lines. Concurrent drain matters: a full stderr
    pipe on one worker mid-collective would block its peer too, and a
    sequential communicate() would read that as a spurious timeout."""
    addr = f"127.0.0.1:{_free_port()}"
    env = _worker_env()
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, addr, str(pid), str(NPROC), *(extra_args or [])],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(NPROC)
    ]
    results: dict[int, tuple] = {}

    def drain(i, p):
        results[i] = p.communicate(timeout=560)

    outs = []
    try:
        threads = [
            threading.Thread(target=drain, args=(i, p), daemon=True)
            for i, p in enumerate(procs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=580)
        for i, p in enumerate(procs):
            assert i in results, f"worker {i} did not complete in time"
            out, err = results[i]
            assert p.returncode == 0, f"worker failed rc={p.returncode}\n{err[-4000:]}"
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        # a hung rendezvous must not leak workers (and the coordinator
        # port) past the test
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return outs


def test_two_process_world_trains_in_lockstep():
    outs = _run_pair()
    by_pid = {o["process"]: o for o in outs}
    assert set(by_pid) == {0, 1}
    for o in outs:
        assert o["process_count"] == NPROC
        assert o["world_devices"] == NPROC * DEVICES_PER_PROC
        assert o["local_devices"] == DEVICES_PER_PROC
        # DistributedSampler equivalent: each process decoded exactly its
        # half of the global batch
        assert o["local_rows"] == o["global_batch"] // NPROC
        assert o["final_step"] == 2
        assert all(l == l and abs(l) < 1e6 for l in o["losses"])  # finite

    # the two halves tile the global batch exactly
    rows0 = set(by_pid[0]["local_positions"])
    rows1 = set(by_pid[1]["local_positions"])
    assert rows0.isdisjoint(rows1)
    assert rows0 | rows1 == set(range(outs[0]["global_batch"]))

    # replicated lockstep: the SPMD program is identical on both
    # processes, so the replicated loss must match bit-for-bit
    assert by_pid[0]["losses"] == by_pid[1]["losses"]


def test_checkpoint_restore_continuity_across_restart(tmp_path):
    """The reference's recovery story is manual `--resume` from the last
    checkpoint (`main_moco.py:~L195-215`). The multi-host equivalent:
    a 2-process world saves mid-run via Orbax, BOTH processes restart
    (a fresh rendezvous), restore, and continue — and the continuation
    must be bit-identical to the run that never stopped (params, opt
    state, queue+ptr, EMA encoder, and the step counter that seeds the
    per-step shuffle RNG all round-tripped exactly), on both processes.
    """
    workdir = str(tmp_path / "ckpt")
    saved = _run_pair(["save", workdir])
    by_pid = {o["process"]: o for o in saved}
    assert by_pid[0]["post_losses"] == by_pid[1]["post_losses"]
    oracle = by_pid[0]["post_losses"]  # uninterrupted continuation
    assert by_pid[0]["final_step"] == 4

    restored = _run_pair(["restore", workdir])
    r_by_pid = {o["process"]: o for o in restored}
    for o in restored:
        assert o["restored_step"] == 2
        assert o["restored_epoch"] == 0
        assert o["final_step"] == 4
    # lockstep across the restarted processes...
    assert r_by_pid[0]["post_losses"] == r_by_pid[1]["post_losses"]
    # ...and bit-identical to the run that never restarted
    assert r_by_pid[0]["post_losses"] == oracle
