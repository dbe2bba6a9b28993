"""The manifest keeps to the contract, every name resolves to a file, and a
configuration, a cell, a per-layer metric and a reader, and a whole encoder
family with its own input, tolerance, operation count and kernel, can each
be added by new files and manifest entries alone."""

import json
import os
import shutil

import pytest

from benchmarks.harness import common
from benchmarks.harness.manifest import (
    BENCH_DIR, NAME_RE, REPO_ROOT, UNIT_RE, Manifest, ManifestError, load_module, read_layer_metrics,
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
        # a draw of the weights made part of the cell: a whole number, its reason under `assumed`
        assert common.weights_seed(cfg, 7) == cfg.get("weights_seed", 7)
        assert ("weights_seed" in cfg) == ("weights_seed" in cfg["assumed"])
        # the family's file states its input, its tolerances and its operation count, and the
        # input module has what the harness asks of a modality
        ref, inputs = manifest.family(cfg)
        assert NAME_RE.match(ref.INPUT) and callable(ref.forward_flops)
        assert 0 < ref.TOLERANCES["emb_centred_rel"] < 1
        assert all(callable(getattr(ref, f)) for f in ("loss_and_embeddings", "embed"))
        assert all(callable(getattr(inputs, f))
                   for f in ("dataset", "sample_input", "correct_views", "correct_rows"))
        traffic = manifest.traffic_file(w["traffic"])
        assert traffic["kind"] in ("train", "serve")
        if traffic["kind"] == "serve":
            assert "serve" in cfg
        for m in manifest.metrics_for(cell, "per_layer"):
            spec = manifest.layer_metric_file(m["name"])
            assert hasattr(manifest.reader(spec["reader"]), "read")
            if "required" in spec:  # a kernel's required work is a module found by name
                assert callable(load_module(manifest.bench_dir, "required", spec["required"]).required)


def _throwaway_copy(tmp_path):
    """(root of a copy of the benchmark in a temporary directory, its manifest as a dict,
    every file of the copy with its bytes)."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH_DIR, root / "benchmarks", ignore=shutil.ignore_patterns("out", "__pycache__"))
    raw = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    before = {
        str(p.relative_to(root)): p.read_bytes() for p in (root / "benchmarks").rglob("*") if p.is_file()
    }
    return root, raw, before


def _a_cell(root, raw):
    """A throw-away configuration, cell, per-layer metric and reader."""
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


TOY_REFERENCE = '''"""A throw-away family: the ResNet reference's forward under another name,
with its own input, tolerances and operation count."""
from benchmarks.harness.flops import dense_flops
from benchmarks.reference.resnet_moco_v2 import embed, loss_and_embeddings  # noqa: F401

INPUT = "toy_rows"
TOLERANCES = {"emb_centred_rel": 2e-3, "loss_abs": 5e-4}


def forward_flops(param_shapes, config):
    return 7.0 * dense_flops(param_shapes) + config.data.image_size
'''

TOY_INPUT = '''"""A throw-away input: the image module's rows from another seed, the views
the other way round; `CALLS` records what the harness asked for."""
from benchmarks.inputs import images

CALLS = []


def dataset(seed, traffic, config):
    CALLS.append("dataset")
    return images.dataset(seed + 1, {"pool_images": traffic["toy_pool"]}, config)


def sample_input(config):
    CALLS.append("sample_input")
    return images.sample_input(config)


def correct_rows(seed, n, config):
    CALLS.append("correct_rows")
    return images.correct_rows(seed + 1, n, config)


def correct_views(seed, n, config):
    CALLS.append("correct_views")
    return images.correct_views(seed + 1, n, config)[::-1]
'''

TOY_REQUIRED = '''"""A throw-away kernel's required work: 8190 bytes a chip and step."""


def required(ctx):
    return {"flops": 2.0 * ctx["chips"], "bytes": 8190.0} if ctx.get("toy") else None
'''


def _a_family(root, raw):
    """A throw-away encoder family: a reference module with its own tolerance, operation count
    and input, that input module, a kernel's required work and the roofline metric that names it;
    `check_train`, `_step_flops` and the `kernel` reader driven through them."""
    from benchmarks.harness import correct, flops
    from benchmarks.harness.common import build_train_config, merged
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.train_cell import _step_flops
    from benchmarks.trace_reduce import ops_inside, reduce_trace

    bench = root / "benchmarks"
    (bench / "reference/toy_family.py").write_text(TOY_REFERENCE)
    (bench / "inputs/toy_rows.py").write_text(TOY_INPUT)
    (bench / "required/toy_copy.py").write_text(TOY_REQUIRED)
    cfg = json.load(open(bench / "configs/r50_v2.json"))
    cfg.update(name="toy", reference="toy_family")
    (bench / "configs/toy.json").write_text(json.dumps(cfg))
    traffic = json.load(open(bench / "traffic/job_loop.json"))
    del traffic["pool_images"], traffic["rehearsal"]["pool_images"]  # the image module's key
    traffic["toy_pool"] = 16  # this input module's own
    (bench / "traffic/toy_loop.json").write_text(json.dumps(traffic))
    for name, required in (("toy_copy_roofline", "toy_copy"), ("toy_lost_roofline", "nowhere")):
        (bench / f"layer_metrics/{name}.json").write_text(json.dumps(
            {"reader": "kernel", "pattern": "^copy", "what": "roofline", "required": required}
        ))
    raw["configs"].append({"name": "toy", "source": "x", "reduced": [], "why": "y",
                           "file": "benchmarks/configs/toy.json"})
    raw["workloads"].append({"name": "train_toy", "config": "toy", "traffic": "toy_loop",
                             "chips": 1, "why": "z"})
    raw["end_to_end"][0]["workloads"].append("train_toy")
    raw["per_layer"].append({"name": "toy_copy_roofline", "unit": "%", "better": "higher",
                             "source": "device_trace", "layer": "kernels",
                             "moves": "train_img_per_s_chip", "workloads": ["train_toy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(raw))

    m = Manifest(repo_root=str(root))
    cell = m.cell("train_toy")
    cfg_file, traffic_file = m.config_file(cell["config"]), m.traffic_file(cell["traffic"])
    ref, inputs = m.family(cfg_file)
    assert ref.__file__.startswith(str(root)) and inputs.__file__.startswith(str(root))
    config = build_train_config(cfg_file, traffic_file, 11, "/nonexistent", True)  # resnet18, 32 px
    pool = inputs.dataset(11, merged(traffic_file, True), config)
    assert pool.load(0)[0].shape == (32, 32, 3) and len(pool._pool) == 16

    # `correct` through the family's own sample and held to its own limits
    out = correct.check_train(config, ref, inputs, seed=11, sample_n=8, gradient=False)
    assert out["ok"], out
    assert inputs.CALLS == ["dataset", "sample_input", "correct_views"]
    beside = correct.compared(out, ref)
    assert beside["emb_centred_rel_error"]["at_most"] == 2e-3 and beside["loss_abs_diff"]["at_most"] == 5e-4
    ref.TOLERANCES = {"loss_abs": 0.02}  # a family that states no embedding limit is refused
    with pytest.raises(ValueError, match="emb_centred_rel"):
        correct.tolerances(ref)

    # the step's operations from the family's count (MoCo v2: 3 + 1 forwards a pair, and InfoNCE)
    step = _step_flops(config, ref, inputs)
    import jax
    from moco_tpu.core import build_encoder
    shapes = jax.eval_shape(
        lambda r: build_encoder(config.moco).init(r, inputs.sample_input(config), train=False),
        jax.random.PRNGKey(0),
    )["params"]
    fwd = 7.0 * flops.dense_flops(shapes) + 32
    assert fwd > 32 and step == 32 * 4.0 * fwd + flops.infonce_flops(32, 128, 4096)

    # the kernel reader finds the copy's required-work module by the name in the metric's file
    ops = [("fusion.1", 0, 100), ("while.2", 100, 300), ("convolution.3", 120, 50),
           ("all-reduce-done.4", 200, 100), ("copy.5", 500, 100)]
    mods = [("jit_step_fn(1)", 0, 400), ("jit_step_fn(1)", 450, 150), ("jit__augment(2)", 405, 40)]
    reduced = reduce_trace(ops, mods, "jit_step_fn")
    ctx = {"trace": reduced, "trace_ops": ops_inside(ops, reduced), "chips": 1, "toy": True,
           "peaks": peaks_for("TPU v5 lite")}
    got = read_layer_metrics(m, "train_toy", ctx)
    # 100 ns of `copy` over 2 steps; 8190 B at 819 GB/s is 10 ns: a fifth of 50 ns
    assert got["toy_copy_roofline"] == {"value": pytest.approx(20.0), "unit": "%"}
    assert "toy_copy_roofline" not in read_layer_metrics(m, "train_toy", {**ctx, "toy": False})
    lost = m.layer_metric_file("toy_lost_roofline")
    with pytest.raises(ManifestError, match="required/nowhere.py"):
        m.reader(lost["reader"]).read(lost, ctx)


@pytest.mark.parametrize("add", [_a_cell, _a_family], ids=["cell", "family"])
def test_additions_need_new_files_only(tmp_path, add):
    """What a later PR adds exists only in a temporary copy, as new files and manifest entries:
    it works there, and nothing that was already there is edited."""
    root, raw, before = _throwaway_copy(tmp_path)
    add(root, raw)
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
