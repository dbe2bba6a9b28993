"""The plain references against the system at resnet18 / vit_tiny sizes,
in float32 on the CPU: loss, embeddings and gradient agree to rounding,
and the comparison notices a wrong input."""

import dataclasses

import numpy as np
import pytest

from benchmarks.harness import correct
from benchmarks.harness.common import build_train_config
from benchmarks.harness.manifest import Manifest


def _tiny(cell_name):
    m = Manifest()
    cell = m.cell(cell_name)
    cfg_file = m.config_file(cell["config"])
    cfg = build_train_config(cfg_file, m.traffic_file(cell["traffic"]), 11, "/nonexistent", True)
    return (cfg, *m.family(cfg_file))


@pytest.mark.parametrize("cell", ["train_r50_v2", "train_vit_b16_v3"])
def test_reference_matches_system(cell):
    cfg, ref, inputs = _tiny(cell)
    out = correct.check_train(cfg, ref, inputs, seed=11, sample_n=8, gradient=True)
    assert out["ok"], out
    beside = correct.compared(out, ref)  # every number compared, beside its limit
    assert set(beside) == {"loss_abs_diff", "emb_centred_rel_error", "grad_cosine", "grad_norm_ratio"}
    assert beside["emb_centred_rel_error"] == {
        "value": out["emb_centred_rel_error"], "at_most": ref.TOLERANCES["emb_centred_rel"],
    }
    assert abs(out["loss_system"] - out["loss_reference"]) < 1e-4
    assert out["emb_centred_rel_error"] < 1e-3
    assert out["grad_cosine"] > 0.9999 and abs(out["grad_norm_ratio"] - 1) < 1e-3


def test_serve_reference_and_neighbours():
    import jax

    from benchmarks.loadgen.schedule import structured_images
    from benchmarks.reference.common import preprocess
    from moco_tpu.ops.losses import l2_normalize

    cfg, ref, inputs = _tiny("train_r50_v2")
    state, encoder, _ = correct.seeded_state(cfg, 11, inputs)
    imgs = structured_images(11, 8, cfg.data.image_size)
    emb = np.asarray(l2_normalize(encoder.apply(
        {"params": state.params_k, "batch_stats": state.batch_stats_k}, preprocess(imgs), train=False
    )))
    scores = emb @ np.asarray(state.queue).T
    ids = np.argsort(-scores, axis=1)[:, :5]
    answer = {"embedding": emb.tolist(), "indices": ids.tolist(),
              "scores": np.take_along_axis(scores, ids, axis=1).tolist()}
    good = correct.check_serve(state, cfg, ref, inputs, 11, {"/neighbors?k=5": answer}, 5)
    assert good["ok"], good
    wrong_ids = dict(answer, indices=(ids[:, ::-1] * 0 + np.arange(5)).tolist())
    assert not correct.check_serve(state, cfg, ref, inputs, 11, {"/neighbors?k=5": wrong_ids}, 5)["ok"]
    other = dict(answer, embedding=np.roll(emb, 1, axis=0).tolist())
    assert not correct.check_serve(state, cfg, ref, inputs, 11, {"/embed": other}, 5)["ok"]
