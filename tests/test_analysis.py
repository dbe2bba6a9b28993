"""mocolint: every rule proven on paired known-bad/known-good fixtures
(exact rule ids AND line numbers), suppression comments, CLI/JSON
surface, the repo-wide self-check, and the runtime arm (compile-miss
counter + recompile guard + strict-tracing driver smoke).

Fixtures under tests/fixtures/lint/ are parsed by the analyzer, never
imported: each `# expect: JXnnn` trailing comment marks a line that must
produce exactly one finding of that rule.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from moco_tpu.analysis import analyze_paths, analyze_source, iter_rules
from moco_tpu.analysis.__main__ import main as mocolint_main
from moco_tpu.analysis.runtime import CompileMonitor, RecompileGuard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "lint")
ALL_RULES = (
    "JX001", "JX002", "JX003", "JX004", "JX005", "JX006", "JX007",
    "JX008", "JX009", "JX010", "JX011", "JX012", "JX013", "JX014",
    "JX015", "JX016", "JX017", "JX018",
)

_EXPECT_RE = re.compile(r"#\s*expect:\s*([A-Z0-9,\s]+)")


def _expected_lines(path: str, rule: str) -> set[int]:
    out = set()
    with open(path) as fh:
        for lineno, line in enumerate(fh, start=1):
            m = _EXPECT_RE.search(line)
            if m and rule in {t.strip() for t in m.group(1).split(",")}:
                out.add(lineno)
    return out


def _fixture(rule: str, kind: str) -> str:
    return os.path.join(FIXTURES, f"{rule.lower()}_{kind}.py")


# ---------------------------------------------------------------------------
# static rules


def test_all_rules_registered():
    assert [rid for rid, _ in iter_rules()] == list(ALL_RULES)


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_fires_on_bad_fixture(rule):
    """Exact rule ids and line numbers on the known-bad snippet."""
    path = _fixture(rule, "bad")
    expected = _expected_lines(path, rule)
    assert expected, f"fixture {path} carries no expectations"
    findings = analyze_paths([path], rules=[rule])
    assert {f.line for f in findings} == expected
    assert all(f.rule == rule and not f.suppressed for f in findings)


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_quiet_on_good_fixture(rule):
    """The paired known-good snippet is clean under EVERY rule — the
    false-positive guard for the idiomatic patterns."""
    findings = analyze_paths([_fixture(rule, "good")])
    assert findings == []


@pytest.mark.parametrize("rule", ALL_RULES)
def test_suppression_comment_mutes_rule(rule):
    """Appending `# mocolint: disable=<rule>` to each flagged line turns
    every finding into a suppressed one (and flips the exit semantics)."""
    path = _fixture(rule, "bad")
    expected = _expected_lines(path, rule)
    with open(path) as fh:
        lines = fh.read().splitlines()
    for lineno in expected:
        lines[lineno - 1] += f"  # mocolint: disable={rule}"
    findings = analyze_source("\n".join(lines), path, rules=[rule])
    assert {f.line for f in findings} == expected
    assert all(f.suppressed for f in findings)


def test_disable_all_token():
    src = "import time\nimport jax\n\n@jax.jit\ndef f(x):\n    t = time.time()  # mocolint: disable=all\n    return x + t\n"
    findings = analyze_source(src, "inline.py")
    assert findings and all(f.suppressed for f in findings)


def test_unrelated_suppression_does_not_mute():
    src = "import time\nimport jax\n\n@jax.jit\ndef f(x):\n    t = time.time()  # mocolint: disable=JX007\n    return x + t\n"
    findings = analyze_source(src, "inline.py", rules=["JX001"])
    assert findings and not any(f.suppressed for f in findings)


def test_syntax_error_is_reported_not_raised():
    findings = analyze_source("def broken(:\n", "broken.py")
    assert [f.rule for f in findings] == ["PARSE"]


def test_self_check_repo_is_lint_clean():
    """The acceptance bar: mocolint over the shipped tree reports zero
    unsuppressed findings (intentional patterns carry justified
    `# mocolint: disable=` comments)."""
    paths = [
        os.path.join(REPO, "moco_tpu"),
        os.path.join(REPO, "scripts"),
        os.path.join(REPO, "train.py"),
        os.path.join(REPO, "eval_lincls.py"),
    ]
    bad = [f for f in analyze_paths(paths) if not f.suppressed]
    assert bad == [], "\n".join(f.render() for f in bad)


# ---------------------------------------------------------------------------
# CLI surface


def test_cli_exit_codes_and_json(tmp_path, capsys):
    report_path = tmp_path / "report.json"
    rc = mocolint_main(
        [_fixture("JX001", "bad"), "--no-baseline",
         "--format", "json", "-o", str(report_path)]
    )
    assert rc == 1
    report = json.loads(report_path.read_text())
    assert report["counts"]["active"] == len(_expected_lines(_fixture("JX001", "bad"), "JX001"))
    assert all(f["rule"] == "JX001" for f in report["findings"])
    capsys.readouterr()

    assert mocolint_main([_fixture("JX001", "good"), "--no-baseline"]) == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_cli_list_rules(capsys):
    assert mocolint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ALL_RULES:
        assert rule in out


def test_cli_rejects_unknown_rule(capsys):
    assert mocolint_main([_fixture("JX001", "bad"), "--rules", "JX999"]) == 2


def test_self_check_tests_tree_is_baseline_clean():
    """The acceptance command includes tests/ — every fixture finding is
    fingerprinted in the checked-in baseline, so the full run exits 0
    while a NEW finding would still fail. Analyzed at the SAME scope the
    baseline was generated at (interprocedural summaries are
    scope-dependent: a helper resolved in the full program can prove a
    pattern safe that looks risky in isolation)."""
    from moco_tpu.analysis.engine import load_baseline

    baseline = load_baseline(os.path.join(REPO, "mocolint-baseline.json"))
    assert baseline, "checked-in baseline is empty"
    paths = [
        os.path.join(REPO, "moco_tpu"),
        os.path.join(REPO, "scripts"),
        os.path.join(REPO, "tests"),
        os.path.join(REPO, "train.py"),
        os.path.join(REPO, "eval_lincls.py"),
        os.path.join(REPO, "convert_pretrain.py"),
        os.path.join(REPO, "import_pretrain.py"),
    ]
    findings = analyze_paths(paths, baseline=baseline)
    fresh = [f for f in findings if f.active]
    assert fresh == [], "\n".join(f.render() for f in fresh)


# ---------------------------------------------------------------------------
# runtime arm


def test_compile_monitor_counts_retraces():
    @jax.jit
    def f(x):
        return x * 2

    mon = CompileMonitor(f)
    f(jnp.ones((4,)))
    first = mon.misses()
    assert first >= 1
    f(jnp.ones((4,)))  # cache hit: same shape
    assert mon.misses() == first
    f(jnp.ones((8,)))  # new shape: retrace
    assert mon.misses() == first + 1


def test_recompile_guard_aborts_only_after_warmup():
    guard = RecompileGuard(warmup_steps=8)
    assert guard.update(2, 1) is None
    assert guard.update(8, 3) is None  # warm-up compiles are free
    assert guard.update(16, 3) is None  # stable: healthy
    diagnosis = guard.update(24, 4)
    assert diagnosis is not None and "recompiled after warm-up" in diagnosis


def test_config_carries_strict_tracing_fields():
    from moco_tpu.utils.config import TrainConfig, config_from_dict, config_to_dict

    cfg = dataclasses.replace(
        TrainConfig(), strict_tracing=True, recompile_warmup_steps=3
    )
    rt = config_from_dict(config_to_dict(cfg))
    assert rt.strict_tracing is True
    assert rt.recompile_warmup_steps == 3


@pytest.mark.slow
def test_train_strict_tracing_smoke(tmp_path):
    """Driver smoke under --strict-tracing: every log line carries
    compile_cache_misses and the count is stable after warm-up (no
    recompiles) — the acceptance criterion, in miniature."""
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
        optim=OptimConfig(lr=0.03, epochs=2, cos=True),
        data=DataConfig(dataset="synthetic", image_size=16, global_batch=16),
        workdir=str(tmp_path),
        log_every=1,
        strict_tracing=True,
        recompile_warmup_steps=2,
    )
    dataset = SyntheticDataset(num_examples=64, image_size=16)
    result = train(config, dataset=dataset)
    assert result["epoch"] == 1

    lines = [
        json.loads(l) for l in open(os.path.join(str(tmp_path), "metrics.jsonl"))
    ]
    logged = [l for l in lines if "compile_cache_misses" in l]
    assert logged, "strict tracing must surface compile_cache_misses"
    post_warmup = [
        l["compile_cache_misses"] for l in logged if l["step"] > config.recompile_warmup_steps
    ]
    assert post_warmup and len(set(post_warmup)) == 1, (
        f"recompiles after warm-up: {post_warmup}"
    )
