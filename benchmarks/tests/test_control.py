"""`correct` has to be able to fail: its control (the reference in the
program's place, one precision step under the configuration's) at a size a
test run can hold, and a whole rehearsal run with the program's own
embedding altered where it is produced."""

import json

import jax.numpy as jnp
import pytest

from benchmarks import control
from benchmarks.harness import correct
from benchmarks.harness.common import build_train_config
from benchmarks.harness.manifest import Manifest


def _family(cell_name):
    m = Manifest()
    cell = m.cell(cell_name)
    cfg_file = m.config_file(cell["config"])
    cfg = build_train_config(cfg_file, m.traffic_file(cell["traffic"]), 13, "/nonexistent", True)
    return (cfg, *m.family(cfg_file))


@pytest.mark.parametrize("cell", ["train_r50_v2", "train_vit_b16_v3"])
def test_the_control_reads_far_above_the_programs_precision(cell):
    """`control.readings`, the function the chip tool prints, at resnet18 / vit_tiny, 32 px, 8 rows.
    At this size the program computes in float32, so the reference with operands rounded to the
    configurations' own bfloat16 stands for it; every control reads five times that or more. (Whether
    a control is `ok` is judged by `TOLERANCES`, which are for the cell's own depth and size: PERF.md
    section 2 has those readings from the chip, where every control run comes out not `ok`.)"""
    sound, *controls = control.readings(Manifest(), cell, [13], rehearse=True)
    assert sound["control"] is None and sound["ok"] and sound["emb_centred_rel_error"] < 1e-3
    assert tuple(r["control"] for r in controls) == control.CONTROLS
    cfg, ref, inputs = _family(cell)
    read = lambda dtype: correct.check_train(
        cfg, ref, inputs, seed=13, sample_n=8, gradient=False, control=dtype
    )
    same, own = read(jnp.float32), read(jnp.bfloat16)
    assert same["emb_centred_rel_error"] == 0.0 and same["ok"]  # nothing rounded: the reference itself
    assert own["ok"], own
    for r in controls:
        assert r["emb_centred_rel_error"] >= 5 * own["emb_centred_rel_error"], (r, own)


def test_a_run_with_an_altered_embedding_is_not_correct(monkeypatch, capsys):
    """The whole run at rehearsal size (no look for a chip), with the program's `l2_normalize`
    mixing each row with its neighbour: the step still trains, the losses are finite, and
    `correct` comes out false on the embeddings, each number printed beside its limit."""
    import moco_tpu.ops.losses as losses
    from benchmarks import run

    real = losses.l2_normalize
    monkeypatch.setattr(
        losses, "l2_normalize", lambda x, *a, **k: real(x + 0.5 * jnp.roll(x, 1, axis=0), *a, **k)
    )
    assert run.main(["--workload", "train_r50_v2", "--seed", "13", "--seconds", "3", "--rehearse"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False and list(result)[-1] == "compared"
    assert result["seed"] == result["weights_seed"] == 13
    beside = result["compared"]
    assert beside["emb_centred_rel_error"]["value"] > beside["emb_centred_rel_error"]["at_most"]
    assert beside["nonfinite_losses"]["value"] == 0 and beside["compiled_in_window"]["value"] == 0
