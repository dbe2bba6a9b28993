"""The manifest keeps to the contract, every name resolves to a file, and a
configuration, a cell, a per-layer metric and a reader can each be added
by new files and manifest entries alone."""

import json
import os
import shutil

import pytest

from benchmarks.harness.manifest import (
    BENCH_DIR, NAME_RE, REPO_ROOT, UNIT_RE, Manifest, read_layer_metrics,
)


CANDIDATES = os.path.join(BENCH_DIR, "candidates", "BENCHMARK.candidates.json")


@pytest.fixture(scope="module", params=["admitted", "candidates"])
def manifest(request):
    """BENCHMARK.json, and the manifest of the cells that are built and
    rehearsed but not admitted yet (PERF.md section 7 says why): both keep
    to the same rules."""
    return Manifest(manifest_path=CANDIDATES if request.param == "candidates" else None)


def test_keys_and_limits(manifest):
    raw = manifest.raw
    assert set(raw) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(REPO_ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert raw["command"] == ["python3", "benchmarks/run.py"] and raw["paths"] == ["benchmarks"]
    assert 1 <= raw["run_seconds"] <= 51 and isinstance(raw["run_seconds"], int)
    # a full check with all 24 cells must fit: (2 + 14*24) runs of run_seconds + 60,
    # 2 x 90 s a cell to compile, 1200 s spare, in 43200 s
    assert (2 + 14 * 24) * (raw["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(raw["workloads"]) <= 24 and 1 <= len(raw["configs"]) <= 24
    if raw is Manifest().raw or raw == Manifest().raw:  # candidates are not a benchmark yet
        four = sum(1 for w in raw["workloads"] if w["chips"] == 4)
        assert four <= max(len(raw["workloads"]) // 4, 1)


def test_names_units_and_entries(manifest):
    raw = manifest.raw
    for group, keys in (
        ("configs", {"name", "source", "file", "reduced", "why"}),
        ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ):
        names = [e["name"] for e in raw[group]]
        assert len(names) == len(set(names))
        for e in raw[group]:
            assert set(e) == keys, e
            assert NAME_RE.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
    for c in raw["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in raw["paths"]))
        assert all(NAME_RE.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in raw["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in raw["workloads"]:
        assert w["config"] in manifest.configs and NAME_RE.match(w["traffic"])
        assert w["chips"] in (1, 4)
    metric_names = [m["name"] for m in raw["end_to_end"] + raw["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in raw["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in raw["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in manifest.end_to_end
        assert 1 <= len(m["layer"]) <= 200
    for m in raw["end_to_end"] + raw["per_layer"]:
        assert NAME_RE.match(m["name"]) and UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert all(w in manifest.workloads for w in m.get("workloads", []))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert "setup_s" in manifest.end_to_end and "workloads" not in manifest.end_to_end["setup_s"]


def test_every_cell_reports_enough_and_moves_resolve(manifest):
    for cell in manifest.workloads:
        e2e = {m["name"] for m in manifest.metrics_for(cell, "end_to_end")}
        layer = manifest.metrics_for(cell, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for m in layer:  # the metric it should move is reported in the same cell
            assert m["moves"] in e2e, (cell, m["name"])
    used = {w["config"] for w in manifest.raw["workloads"]}
    assert used == set(manifest.configs)
    for m in manifest.raw["end_to_end"] + manifest.raw["per_layer"]:
        assert m.get("workloads", True), f"{m['name']} is reported by no cell"


def test_files_found_by_name(manifest):
    for cell, w in manifest.workloads.items():
        cfg = manifest.config_file(w["config"])
        assert cfg["name"] == w["config"] and "preset" in cfg and "reference" in cfg
        assert sorted(cfg["reduced"]) == sorted(manifest.configs[w["config"]]["reduced"])
        ref = os.path.join(BENCH_DIR, "reference", cfg["reference"] + ".py")
        assert os.path.exists(ref)
        traffic = manifest.traffic_file(w["traffic"])
        assert traffic["kind"] in ("train", "serve")
        if traffic["kind"] == "serve":
            assert "serve" in cfg
        for m in manifest.metrics_for(cell, "per_layer"):
            spec = manifest.layer_metric_file(m["name"])
            assert hasattr(manifest.reader(spec["reader"]), "read")


def test_additions_need_new_files_only(tmp_path):
    """A throw-away configuration, cell, per-layer metric and reader that
    exist only in a temporary directory: nothing already there is edited."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH_DIR, root / "benchmarks", ignore=shutil.ignore_patterns("out", "__pycache__"))
    raw = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    before = {
        str(p.relative_to(root)): p.read_bytes() for p in (root / "benchmarks").rglob("*") if p.is_file()
    }
    cfg = json.load(open(root / "benchmarks/configs/r50_v2.json"))
    cfg.update(name="r50_v2_b512", overrides={"data.global_batch": 512})
    (root / "benchmarks/configs/r50_v2_b512.json").write_text(json.dumps(cfg))
    (root / "benchmarks/traffic/job_loop_long_warmup.json").write_text(json.dumps(
        {**json.load(open(root / "benchmarks/traffic/job_loop.json")), "warmup_steps": 100}
    ))
    (root / "benchmarks/layer_metrics/loss_last.json").write_text(json.dumps(
        {"reader": "last_field", "field": "loss"}
    ))
    (root / "benchmarks/readers/last_field.py").write_text(
        "def read(spec, ctx):\n"
        "    lines = ctx.get('train_lines') or []\n"
        "    return lines[-1].get(spec['field']) if lines else None\n"
    )
    raw["configs"].append({"name": "r50_v2_b512", "source": "x", "reduced": [], "why": "y",
                           "file": "benchmarks/configs/r50_v2_b512.json"})
    raw["workloads"].append({"name": "train_r50_b512", "config": "r50_v2_b512",
                             "traffic": "job_loop_long_warmup", "chips": 1, "why": "z"})
    raw["end_to_end"][0]["workloads"].append("train_r50_b512")
    raw["per_layer"].append({"name": "loss_last", "unit": "nats", "better": "lower",
                             "source": "program_counter", "layer": "step function",
                             "moves": "train_img_per_s_chip", "workloads": ["train_r50_b512"]})
    (root / "BENCHMARK.json").write_text(json.dumps(raw))

    m = Manifest(repo_root=str(root))
    cell = m.cell("train_r50_b512")
    assert m.config_file(cell["config"])["overrides"] == {"data.global_batch": 512}
    assert m.traffic_file(cell["traffic"])["warmup_steps"] == 100
    got = read_layer_metrics(m, "train_r50_b512", {"train_lines": [{"loss": 9.5}, {"loss": 9.25}]})
    assert got == {"loss_last": {"value": 9.25, "unit": "nats"}}
    # a reader that finds nothing leaves its metric out of the line
    assert read_layer_metrics(m, "train_r50_b512", {}) == {}
    after = {k: (root / k).read_bytes() for k in before}
    assert after == before


def test_program_config_is_built_from_the_files():
    from benchmarks.harness.common import build_train_config

    manifest = Manifest()
    cell = manifest.cell("train_vit_b16_v3")
    cfg = build_train_config(
        manifest.config_file(cell["config"]), manifest.traffic_file(cell["traffic"]),
        seed=2**31 + 11, workdir="/nonexistent", rehearse=False,
    )
    assert cfg.moco.arch == "vit_b16" and cfg.data.global_batch == 64
    assert cfg.parallel.num_data == 1 and cfg.seed == 2**31 + 11
    assert cfg.optim.lr == 2.4e-3 and cfg.knn_every_epochs == 0
    manifest = Manifest(manifest_path=CANDIDATES)
    x4 = manifest.cell("train_r50_v2_x4")
    cfg4 = build_train_config(
        manifest.config_file(x4["config"]), manifest.traffic_file(x4["traffic"]), 0, "/x", False
    )
    assert cfg4.parallel.num_data == 4 and cfg4.data.global_batch == 256
    assert cfg4.moco.num_negatives == 65536 and cfg4.moco.shuffle == "gather_perm"
