"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name in the
manifest: `configs/<config>.json`, `traffic/<traffic>.json`,
`layer_metrics/<metric>.json`, and a reader `readers/<reader>.py` named
by the layer-metric file. What belongs to one encoder family, one kind
of input or one kernel is a module found by name in the same way:
`reference/<name>.py` (named by the configuration file), the
`inputs/<name>.py` the reference names as its `INPUT`, and the
`required/<name>.py` a kernel metric's file names. The harness's own
code names no family, no modality and no kernel. A later PR adds files
and manifest entries and edits none. `root` is injectable so the tests
can prove that with a throw-away family in a temporary directory.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(ValueError):
    pass


def load_module(bench_dir: str, kind: str, name: str):
    """The module `<bench_dir>/<kind>/<name>.py` (`kind`: readers,
    reference, inputs or required), loaded by path so that one added in a
    temporary directory is found like one in the repo."""
    if not NAME_RE.match(name):
        raise ManifestError(f"bad module name {kind}/{name!r}")
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise ManifestError(f"missing benchmark module {path}")
    spec = importlib.util.spec_from_file_location(f"_bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing benchmark data file {path}") from None


class Manifest:
    """The parsed manifest plus look-ups by name. `repo_root` holds
    BENCHMARK.json (or `manifest_path` names a manifest of candidate
    cells); its first `paths` entry holds configs/, traffic/,
    layer_metrics/, readers/, reference/, inputs/ and required/."""

    def __init__(self, repo_root: str = REPO_ROOT, manifest_path: str | None = None):
        self.repo_root = repo_root
        self.raw = _load_json(manifest_path or os.path.join(repo_root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(repo_root, self.raw["paths"][0])
        self.workloads = {w["name"]: w for w in self.raw["workloads"]}
        self.configs = {c["name"]: c for c in self.raw["configs"]}
        self.end_to_end = {m["name"]: m for m in self.raw["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.raw["per_layer"]}

    def cell(self, name: str) -> dict:
        if name not in self.workloads:
            raise ManifestError(
                f"unknown workload {name!r}; BENCHMARK.json has {sorted(self.workloads)}"
            )
        return self.workloads[name]

    def config_file(self, config: str) -> dict:
        return _load_json(os.path.join(self.repo_root, self.configs[config]["file"]))

    def traffic_file(self, traffic: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "traffic", f"{traffic}.json"))

    def layer_metric_file(self, metric: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "layer_metrics", f"{metric}.json"))

    def metrics_for(self, cell: str, group: str) -> list[dict]:
        """The manifest's `end_to_end` or `per_layer` entries that this
        cell reports: those with no `workloads` key, or that list it."""
        return [
            m for m in self.raw[group]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def reader(self, name: str):
        return load_module(self.bench_dir, "readers", name)

    def family(self, cfg_file: dict) -> tuple:
        """(reference module, input module) of a configuration: the plain
        reference its file names, and the input module that reference
        names as its `INPUT`."""
        ref = load_module(self.bench_dir, "reference", cfg_file["reference"])
        return ref, load_module(self.bench_dir, "inputs", ref.INPUT)


def read_layer_metrics(manifest: Manifest, cell: str, ctx: dict) -> dict:
    """{metric: {"value", "unit"}} for every per-layer metric of `cell`
    whose reader found something to read. `ctx` is what the run left
    behind (window lines, reduced trace, load-generator results, memory,
    configuration); a reader that finds nothing returns None and the
    metric is left out of the line."""
    out = {}
    for m in manifest.metrics_for(cell, "per_layer"):
        spec = manifest.layer_metric_file(m["name"])
        value = manifest.reader(spec["reader"]).read(spec, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
