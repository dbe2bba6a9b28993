"""`weights_seed`: a configuration file may make one draw of the weights part
of its cell. Where the key is there, `--seed` draws the documents and
`correct`'s sample, and no longer the weights, the timed run's or the ones
`correct` is computed on; where it is absent, the configuration built is the
one the harness built before the key existed. One case a cell, so each counts."""

import dataclasses

import numpy as np
import pytest

from benchmarks.harness import common
from benchmarks.harness.manifest import Manifest, ManifestError

SEEDS = (19, 2**31 + 11)
CELLS = ["train_r50_v2", "train_vit_b16_v3", "train_joyai_flash_8k", "train_smallthinker_16k"]
WITH_KEY = {"train_joyai_flash_8k"}


def _built_before_the_key(cfg_file, traffic, seed, workdir, rehearse):
    """`build_train_config` as it stood at PR 35 (commit 07ed593), word for word."""
    from moco_tpu.utils.config import PRESETS

    cfg = PRESETS[cfg_file["preset"]]
    layers = [cfg_file.get("overrides", {}), traffic.get("overrides", {})]
    if rehearse:
        layers += [cfg_file.get("rehearsal", {}).get("overrides", {}),
                   traffic.get("rehearsal", {}).get("overrides", {})]
    for layer in layers:
        for key, value in layer.items():
            cfg = common._replace_dotted(cfg, key, value)
    return dataclasses.replace(cfg, seed=int(seed), workdir=workdir, knn_every_epochs=0)


def _rows(x) -> np.ndarray:
    return np.asarray(x["ids"] if isinstance(x, dict) else x)


def test_every_admitted_cell_is_a_case():
    assert sorted(CELLS) == sorted(Manifest().workloads)


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_seed_draws_the_inputs_and_the_key_alone_fixes_the_weights(cell_name):
    m = Manifest()
    cell = m.cell(cell_name)
    cfg_file, traffic = m.config_file(cell["config"]), m.traffic_file(cell["traffic"])
    assert ("weights_seed" in cfg_file) == (cell_name in WITH_KEY)
    for rehearse in (False, True):
        built = [common.build_train_config(cfg_file, traffic, s, "/nonexistent", rehearse) for s in SEEDS]
        before = [_built_before_the_key(cfg_file, traffic, s, "/nonexistent", rehearse) for s in SEEDS]
        if cell_name in WITH_KEY:
            # the same weights in every run, and nothing else of the configuration moved
            assert built[0] == built[1] and built[0].seed == cfg_file["weights_seed"]
            assert [dataclasses.replace(b, seed=built[0].seed) for b in before] == built
        else:
            assert built == before and [b.seed for b in built] == list(SEEDS)

    # what `--seed` still draws, at the rehearsal's size: the pool and `correct`'s sample
    _, inputs = m.family(cfg_file)
    small, cfg = common.merged(traffic, True), built[0]
    pools = [inputs.dataset(s, small, cfg) for s in (*SEEDS, SEEDS[0])]
    first = [p.load_tokens(0) if hasattr(p, "load_tokens") else p.load(0)[0] for p in pools]
    assert np.array_equal(first[0], first[2])
    assert first[0].shape != first[1].shape or not np.array_equal(first[0], first[1])
    views = [inputs.correct_views(s, 2, cfg) for s in SEEDS]
    assert not np.array_equal(_rows(views[0][0]), _rows(views[1][0]))


def test_correct_is_computed_on_the_timed_runs_weights_and_the_seeds_sample(monkeypatch):
    """`check_train` makes its state from `config.seed` (what the timed run's `create_state`
    draws from) and its sample from the seed it is handed."""
    from benchmarks.harness import correct

    m = Manifest()
    cell = m.cell("train_joyai_flash_8k")
    cfg_file, traffic = m.config_file(cell["config"]), m.traffic_file(cell["traffic"])
    ref, inputs = m.family(cfg_file)
    config = common.build_train_config(cfg_file, traffic, SEEDS[0], "/nonexistent", True)
    seen = {}

    class Stop(Exception):
        pass

    def state(config, seed, inputs):
        seen["weights"] = seed
        raise Stop

    monkeypatch.setattr(correct, "seeded_state", state)
    with pytest.raises(Stop):
        correct.check_train(config, ref, inputs, SEEDS[0], sample_n=2, gradient=False)
    assert seen["weights"] == cfg_file["weights_seed"] != SEEDS[0]


@pytest.mark.parametrize("bad, why", [
    ({"weights_seed": 19.0, "assumed": {"weights_seed": "x"}}, "whole number"),
    ({"weights_seed": "19", "assumed": {"weights_seed": "x"}}, "whole number"),
    ({"weights_seed": True, "assumed": {"weights_seed": "x"}}, "whole number"),
    ({"weights_seed": -1, "assumed": {"weights_seed": "x"}}, "whole number"),
    ({"weights_seed": 19, "assumed": {"init": "x"}}, "assumed"),
    ({"weights_seed": 19}, "assumed"),
], ids=["float", "string", "bool", "negative", "unnamed", "no_assumed"])
def test_the_key_is_an_integer_with_its_reason_under_assumed(bad, why):
    with pytest.raises(ManifestError, match=why):
        common.weights_seed({"name": "x", **bad}, 7)
    assert common.weights_seed({"weights_seed": 19, "assumed": {"weights_seed": "x"}}, 7) == 19
    assert common.weights_seed({"assumed": {}}, 7) == 7
