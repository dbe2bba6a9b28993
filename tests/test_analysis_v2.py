"""mocolint v2: the interprocedural engine (call graph + dataflow
summaries), the cross-function re-hosts of JX002/JX003/JX005, the
baseline workflow, statement-extent suppressions, and the runtime
collective-schedule sanitizer (unit + fake-8-device end-to-end)."""

import json
import os

import pytest

from moco_tpu.analysis import analyze_paths, analyze_source
from moco_tpu.analysis.__main__ import main as mocolint_main
from moco_tpu.analysis.callgraph import Program, build_program, module_name_for
from moco_tpu.analysis.dataflow import build_summaries
from moco_tpu.analysis.engine import (
    Finding,
    load_baseline,
    parse_module,
    write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "lint")


def _program(files: dict[str, str]) -> Program:
    contexts = {}
    for path, src in files.items():
        ctx = parse_module(src, path)
        assert not isinstance(ctx, Finding), ctx.render()
        contexts[path] = ctx
    return build_program(contexts)


def _findings(files: dict[str, str], rules=None) -> list:
    prog = _program(files)
    out = []
    for path, ctx in prog.contexts.items():
        out.extend(
            analyze_source("\n".join(ctx.source_lines), path, rules=rules, ctx=ctx)
        )
    return out


# ---------------------------------------------------------------------------
# call graph


def test_module_name_for():
    assert module_name_for("moco_tpu/parallel/shuffle.py", [""]) == (
        "moco_tpu.parallel.shuffle"
    )
    assert module_name_for("pkg/__init__.py", [""]) == "pkg"


def test_cross_module_call_resolution():
    prog = _program({
        "lib.py": "def helper(x):\n    return x\n",
        "app.py": "from lib import helper\n\ndef main(y):\n    return helper(y)\n",
    })
    edges = prog.edges()
    assert "lib.helper" in edges["app.main"]


def test_method_resolution_via_self():
    prog = _program({
        "m.py": (
            "class C:\n"
            "    def a(self):\n"
            "        return self.b()\n"
            "    def b(self):\n"
            "        return 1\n"
        ),
    })
    assert "m.C.b" in prog.edges()["m.C.a"]


def test_jitted_closure_crosses_modules():
    prog = _program({
        "lib.py": "def helper(x):\n    return float(x)\n",
        "app.py": (
            "import jax\n"
            "from lib import helper\n\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return helper(x)\n"
        ),
    })
    jitted = prog.jitted()
    assert "app.step" in jitted and "lib.helper" in jitted


# ---------------------------------------------------------------------------
# dataflow summaries


def test_summary_sanitizes_and_propagates():
    prog = _program({
        "m.py": (
            "from jax import lax\n\n"
            "def clean(k):\n"
            "    return lax.stop_gradient(k)\n\n"
            "def passthrough(k):\n"
            "    return k * 2\n"
        ),
    })
    table = build_summaries(prog)
    assert table.get("m.clean").sanitizes
    assert "k" in table.get("m.passthrough").returns_taint_of


def test_summary_host_local_and_collectives():
    prog = _program({
        "m.py": (
            "import jax\n"
            "from jax import lax\n\n"
            "def who_am_i():\n"
            "    return jax.process_index()\n\n"
            "def reduce(x, axis_name):\n"
            "    return lax.psum(x, axis_name)\n"
        ),
    })
    table = build_summaries(prog)
    assert table.get("m.who_am_i").returns_host_local
    uses = table.get("m.reduce").collectives
    assert [u.kind for u in uses] == ["psum"]
    assert uses[0].axis_param == "axis_name"


def test_summary_derive_only_rng():
    prog = _program({
        "m.py": (
            "import jax\n\n"
            "def derive(rng, i):\n"
            "    return jax.random.fold_in(rng, i)\n\n"
            "def sample(rng, shape):\n"
            "    return jax.random.normal(rng, shape)\n"
        ),
    })
    table = build_summaries(prog)
    assert "rng" in table.get("m.derive").derives_only_rng_params
    assert "rng" in table.get("m.sample").consumes_rng_params


# ---------------------------------------------------------------------------
# interprocedural rule behavior


def test_jx002_flags_helper_in_other_module():
    findings = _findings({
        "lib.py": "def fetch(x):\n    return float(x)\n",
        "app.py": (
            "import jax\n"
            "from lib import fetch\n\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return fetch(x)\n"
        ),
    }, rules=["JX002"])
    assert [(f.path, f.rule) for f in findings] == [("lib.py", "JX002")]


def test_jx003_derive_only_helper_is_not_consumption():
    src = (
        "import jax\n\n"
        "def derive(rng, i):\n"
        "    return jax.random.fold_in(rng, i)\n\n"
        "def use(rng):\n"
        "    a = jax.random.normal(derive(rng, 1), (2,))\n"
        "    b = jax.random.normal(derive(rng, 2), (2,))\n"
        "    return a + b\n"
    )
    assert analyze_source(src, "m.py", rules=["JX003"]) == []


def test_jx003_consuming_helper_still_counts():
    src = (
        "import jax\n\n"
        "def sample(rng):\n"
        "    return jax.random.normal(rng, (2,))\n\n"
        "def use(rng):\n"
        "    a = sample(rng)\n"
        "    b = sample(rng)\n"
        "    return a + b\n"
    )
    findings = analyze_source(src, "m.py", rules=["JX003"])
    assert [f.line for f in findings] == [8]


def test_jx005_cross_function_fixture():
    """The ISSUE-6 acceptance bullet: the interprocedural JX005 pass
    flags the seeded cross-function stop_gradient violation, at the
    call site, and stays quiet on the stop_gradient'd twin."""
    path = os.path.join(FIXTURES, "jx005_crossfn_bad.py")
    findings = analyze_paths([path], rules=["JX005"])
    assert [f.line for f in findings] == [21]
    assert "project" in findings[0].message and "einsum" in findings[0].message


# ---------------------------------------------------------------------------
# statement-extent suppression (multi-line statements)


def test_suppression_on_closing_line_of_multiline_call():
    src = (
        "import jax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    t = float(\n"
        "        x\n"
        "    )  # mocolint: disable=JX002  (justified)\n"
        "    return t\n"
    )
    findings = analyze_source(src, "m.py", rules=["JX002"])
    assert len(findings) == 1 and findings[0].suppressed


def test_suppression_does_not_leak_across_statements():
    src = (
        "import jax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = float(x)  # mocolint: disable=JX002  (justified)\n"
        "    b = float(x)\n"
        "    return a + b\n"
    )
    findings = analyze_source(src, "m.py", rules=["JX002"])
    assert [(f.line, f.suppressed) for f in findings] == [(5, True), (6, False)]


# ---------------------------------------------------------------------------
# baseline workflow


def test_baseline_roundtrip_and_gating(tmp_path):
    bad = os.path.join(FIXTURES, "jx001_bad.py")
    findings = analyze_paths([bad], rules=["JX001"])
    assert findings and all(f.active for f in findings)
    baseline_path = tmp_path / "baseline.json"
    n = write_baseline(str(baseline_path), findings)
    assert n == len(findings)
    fingerprints = load_baseline(str(baseline_path))
    regated = analyze_paths([bad], rules=["JX001"], baseline=fingerprints)
    assert regated and all(f.baselined and not f.active for f in regated)


def test_baseline_path_normalization(tmp_path):
    f = Finding(rule="JX001", message="m", path="./tests/fixtures/lint/x.py", line=3)
    g = Finding(rule="JX001", message="m", path="tests/fixtures/lint/x.py", line=3)
    assert f.fingerprint() == g.fingerprint()


def test_cli_update_baseline_then_pass(tmp_path, capsys):
    bad = os.path.join(FIXTURES, "jx002_bad.py")
    baseline = str(tmp_path / "b.json")
    assert mocolint_main([bad, "--update-baseline", "--baseline", baseline]) == 0
    capsys.readouterr()
    # gated run passes; --no-baseline still fails
    assert mocolint_main([bad, "--baseline", baseline]) == 0
    assert mocolint_main([bad, "--no-baseline"]) == 1
    capsys.readouterr()


def test_cli_new_finding_fails_despite_baseline(tmp_path, capsys):
    old = "import time\nimport jax\n\n@jax.jit\ndef f(x):\n    return x + time.time()\n"
    src_path = tmp_path / "mod.py"
    src_path.write_text(old)
    baseline = str(tmp_path / "b.json")
    assert mocolint_main([str(src_path), "--update-baseline", "--baseline", baseline]) == 0
    # a NEW finding (second impure call) is not fingerprinted -> fail
    src_path.write_text(old + "\n@jax.jit\ndef g(x):\n    return x + time.time()\n")
    assert mocolint_main([str(src_path), "--baseline", baseline]) == 1
    capsys.readouterr()


def test_checked_in_baseline_matches_tree():
    """`--update-baseline` regenerates exactly what is checked in — the
    baseline cannot drift from the tree without CI noticing."""
    baseline = load_baseline(os.path.join(REPO, "mocolint-baseline.json"))
    paths = [
        os.path.join(REPO, d)
        for d in ("moco_tpu", "scripts", "tests")
    ] + [
        os.path.join(REPO, f)
        for f in ("train.py", "eval_lincls.py",
                  "convert_pretrain.py", "import_pretrain.py")
    ]
    findings = analyze_paths(paths)
    current = {f.fingerprint() for f in findings if not f.suppressed}
    assert current == baseline, (
        "baseline drift — rerun: python -m moco_tpu.analysis moco_tpu/ "
        "scripts/ tests/ train.py eval_lincls.py "
        "convert_pretrain.py import_pretrain.py --update-baseline"
    )


# ---------------------------------------------------------------------------
# runtime collective-schedule sanitizer


def test_recorder_dedupes_and_hashes_deterministically():
    from moco_tpu.analysis.sanitizer import ScheduleRecorder

    r1 = ScheduleRecorder(0)
    for _ in range(3):  # idempotent across retraces
        r1.record("shuffle.a2a", "all_to_all", "(16, 4):float32")
        r1.record("grad.psum", "psum", "(16, 4):float32")
    r2 = ScheduleRecorder(1)
    r2.record("shuffle.a2a", "all_to_all", "(16, 4):float32")
    r2.record("grad.psum", "psum", "(16, 4):float32")
    assert len(r1.entries()) == 2
    assert r1.schedule_hash() == r2.schedule_hash()
    # order matters: a reordered schedule is a DIFFERENT schedule
    r3 = ScheduleRecorder(2)
    r3.record("grad.psum", "psum", "(16, 4):float32")
    r3.record("shuffle.a2a", "all_to_all", "(16, 4):float32")
    assert r3.schedule_hash() != r1.schedule_hash()


def test_diverge_fault_perturbs_schedule():
    from moco_tpu.analysis.sanitizer import ScheduleRecorder
    from moco_tpu.utils import faults

    clean = ScheduleRecorder(0)
    clean.record("queue.enqueue_gather", "all_gather", "(32, 128):float32")
    faults.install("diverge@site=queue.enqueue_gather")
    try:
        divergent = ScheduleRecorder(1)
        divergent.record("queue.enqueue_gather", "all_gather", "(32, 128):float32")
    finally:
        faults.clear()
    assert clean.schedule_hash() != divergent.schedule_hash()
    assert "#diverged" in divergent.entries()[0][2]


def test_sanitizer_clean_and_divergent(tmp_path):
    from moco_tpu.analysis.sanitizer import (
        ScheduleDivergenceError,
        ScheduleRecorder,
        ScheduleSanitizer,
    )

    def make(pidx, sig):
        r = ScheduleRecorder(pidx)
        r.record("shuffle.a2a", "all_to_all", sig)
        r.record("grad.psum", "psum", "(8,):float32")
        return ScheduleSanitizer(
            str(tmp_path), process_index=pidx, num_processes=2, recorder=r
        )

    a = make(0, "(16, 4):float32")
    b = make(1, "(16, 4):float32")
    b.publish(step=0)
    a.check(step=0)  # clean: no raise
    # peer re-publishes a diverged schedule
    b2 = make(1, "(16, 8):float32")
    b2.publish(step=1)
    with pytest.raises(ScheduleDivergenceError) as e:
        a.check(step=1)
    assert "shuffle.a2a" in str(e.value)
    diff = json.loads((tmp_path / "schedule_diff.json").read_text())
    assert diff["divergent_peers"] == [1]
    assert any("shuffle.a2a" in line for line in diff["diff"])


def test_unpublished_peer_is_skipped(tmp_path):
    from moco_tpu.analysis.sanitizer import ScheduleRecorder, ScheduleSanitizer

    r = ScheduleRecorder(0)
    r.record("grad.psum", "psum", "(8,):float32")
    san = ScheduleSanitizer(str(tmp_path), process_index=0, num_processes=4, recorder=r)
    san.check(step=0)  # peers 1..3 never published: not a divergence


def test_comms_tag_feeds_recorder():
    import jax.numpy as jnp

    from moco_tpu.analysis.sanitizer import ScheduleRecorder, install_recorder
    from moco_tpu.obs import comms

    rec = ScheduleRecorder(0)
    prev = install_recorder(rec)
    try:
        with comms.tag("unit.site", "all_gather", jnp.zeros((4, 2)), 8):
            pass
    finally:
        install_recorder(prev)
    entries = rec.entries()
    assert entries == [("unit.site", "all_gather", "(4, 2):float32")]


@pytest.mark.slow
def test_driver_publishes_schedule_hash(tmp_path):
    """`--sanitize-collectives` end-to-end through the train driver: the
    recorder is installed before the first trace, every log line carries
    `collective_schedule_hash` (flat), and the out-of-band
    schedule.p0.json is published with the traced sites."""
    from moco_tpu.data.datasets import SyntheticDataset
    from moco_tpu.train import train
    from moco_tpu.utils.config import (
        DataConfig,
        MocoConfig,
        OptimConfig,
        TrainConfig,
    )

    config = TrainConfig(
        moco=MocoConfig(
            arch="resnet18", dim=16, num_negatives=64, mlp=True,
            shuffle="gather_perm", cifar_stem=True, compute_dtype="float32",
        ),
        optim=OptimConfig(lr=0.03, epochs=1, cos=True),
        data=DataConfig(dataset="synthetic", image_size=16, global_batch=16),
        workdir=str(tmp_path),
        log_every=1,
        sanitize_collectives=True,
    )
    dataset = SyntheticDataset(num_examples=48, image_size=16)
    result = train(config, dataset=dataset)
    assert result["epoch"] == 0

    lines = [
        json.loads(l) for l in open(os.path.join(str(tmp_path), "metrics.jsonl"))
    ]
    hashes = {
        l["collective_schedule_hash"] for l in lines if "collective_schedule_hash" in l
    }
    assert len(hashes) == 1, f"schedule hash must be flat on a healthy run: {hashes}"
    sched = json.loads(
        open(os.path.join(str(tmp_path), "schedule.p0.json")).read()
    )
    sites = [e[0] for e in sched["schedule"]]
    assert sites, "driver run traced no comms-tagged collectives"
    assert sched["hash"][:12] == next(iter(hashes))


@pytest.mark.slow
def test_sanitizer_catches_divergence_on_fake_8_device_mesh(tmp_path):
    """End-to-end on the 8-virtual-device mesh: the REAL collective
    schedule (a2a shuffle + gathers + psum, traced through comms.tag) is
    recorded by two simulated processes; an injected diverge@ fault on
    one of them must be caught with a per-site diff, and the clean
    control must pass. Reuses scripts/sanitizer_smoke.py so the CI leg
    and the test cannot drift apart."""
    from tests.conftest import load_script

    smoke = load_script("sanitizer_smoke.py")
    report = smoke.run_smoke(str(tmp_path))
    assert report["control"]["ok"]
    assert report["chaos"]["caught"]
    assert any("shuffle.a2a" in line for line in report["chaos"]["diff_lines"])
    assert os.path.exists(os.path.join(str(tmp_path), "schedule_diff.json"))
