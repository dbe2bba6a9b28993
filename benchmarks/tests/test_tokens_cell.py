"""The token-input family (`reference/joyai_moco_v2.py`, `inputs/tokens.py`,
`required/mla_attention.py`, `required/expert_ffn.py`) and its cell,
`train_joyai_flash_8k`: a whole rehearsal run reads `correct` true and reads
false with the program's routing altered; the control reads far above the
program's precision; the pool, the operation counts and the kernels'
required work are what the files say."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import control
from benchmarks.harness import common, correct, train_cell
from benchmarks.harness.manifest import Manifest, load_module

CELL = "train_joyai_flash_8k"


def _family(rehearse: bool):
    m = Manifest()
    cell = m.cell(CELL)
    cfg_file, traffic = m.config_file(cell["config"]), m.traffic_file(cell["traffic"])
    cfg = common.build_train_config(cfg_file, traffic, 13, "/nonexistent", rehearse)
    return (cfg, traffic, *m.family(cfg_file))


def _run(capsys) -> dict:
    from benchmarks import run

    assert run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "3", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearses_correct_and_a_wrong_routing_scale_does_not(monkeypatch, capsys):
    result = _run(capsys)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    # the line says what drew the documents and what drew the timed run's weights
    fixed = Manifest().config_file("joyai_flash_ep16")["weights_seed"]
    assert result["seed"] == 2147483659 != fixed == result["weights_seed"]
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) >= {"emb_centred_rel_error", "loss_abs_diff", "grad_cosine"}
    # the program weighs its routed experts by 1.0 where the model says 2.5
    import moco_tpu.models.joyai as joyai

    tiny = joyai._JOYAI_CONFIGS["joyai_tiny"]
    monkeypatch.setitem(joyai._JOYAI_CONFIGS, "joyai_tiny", dataclasses.replace(tiny, routed_scale=1.0))
    sound = result["compared"]["emb_centred_rel_error"]["value"]
    result = _run(capsys)
    assert result["correct"] is False
    failing = {k for k, c in result["compared"].items() if not correct.holds(c)}
    assert failing and failing <= {"emb_centred_rel_error", "loss_abs_diff", "grad_cosine", "grad_norm_ratio"}
    assert result["compared"]["emb_centred_rel_error"]["value"] > 1000 * sound
    assert result["compared"]["nonfinite_losses"]["value"] == 0


def test_the_control_reads_far_above_the_programs_precision():
    """At the rehearsal's size the program computes in float32, so the
    reference with bfloat16 operands stands for it. A flipped top-k choice
    costs a whole expert's output whatever the precision that flipped it,
    so the controls stand closer to it than an image family's do: twice
    and more here (PERF.md section 2 has the chip's readings)."""
    sound, *controls = control.readings(Manifest(), CELL, [13], rehearse=True)
    assert sound["control"] is None and sound["ok"] and sound["emb_centred_rel_error"] < 1e-3
    cfg, _, ref, inputs = _family(True)
    own = correct.check_train(cfg, ref, inputs, seed=13, sample_n=4, gradient=False,
                              control=jnp.bfloat16)
    for r in controls:
        assert r["emb_centred_rel_error"] >= 2 * own["emb_centred_rel_error"], (r, own)


def test_the_pool_is_seeded_clipped_and_skewed():
    cfg, traffic, _, inputs = _family(False)
    small = {**traffic, "pool_documents": 8}
    a, b, c = (inputs.dataset(s, small, cfg) for s in (5, 5, 6))
    docs = [a.load_tokens(i) for i in range(8)]
    assert all(np.array_equal(d, b.load_tokens(i)) for i, d in enumerate(docs))
    assert not np.array_equal(docs[0][:64], c.load_tokens(0)[:64])
    assert np.array_equal(a.load_tokens(8), docs[0]) and len(a) == 40_000
    lengths = [len(d) for d in docs]
    assert min(lengths) >= 8192 and max(lengths) <= 65536 and len(set(lengths)) > 1
    ids = np.concatenate(docs)
    assert ids.dtype == np.int32 and 0 <= ids.min() and ids.max() < cfg.moco.lm_vocab_rows == 16160
    counts = np.bincount(ids, minlength=16160)
    assert counts.max() > 50 * counts.mean()  # Zipf: the commonest id is ~1/ln(V) of all tokens
    x1, x2 = inputs.correct_views(5, 2, dataclasses.replace(cfg, data=dataclasses.replace(cfg.data, seq_len=16)))
    assert x1["ids"].shape == (2, 16) and not np.array_equal(x1["ids"], x2["ids"])
    assert list(x1["lengths"]) == [16, 16]


def test_rank_seed_fixes_the_rows_the_commonest_ids_read_and_nothing_else():
    """The cell's traffic file carries `rank_seed`, a whole number with its reason under the file's
    own `assumed`: the Zipf head reads the same vocabulary rows whatever `--seed`; the documents
    stay the seed's, and a traffic file without the key (the other token cell's) draws what it drew."""
    cfg, traffic, _, inputs = _family(False)
    assert type(traffic["rank_seed"]) is int and "rank_seed" in traffic["assumed"]
    assert "rank_seed" not in Manifest().traffic_file("job_loop_tokens_16k")
    small = {**traffic, "pool_documents": 8}
    free = {k: v for k, v in small.items() if k != "rank_seed"}
    fixed5, fixed6, free5, free6 = (inputs.dataset(s, t, cfg) for t in (small, free) for s in (5, 6))
    head = lambda pool: list(np.argsort(-np.bincount(np.concatenate(pool._docs), minlength=16160))[:3])
    assert head(fixed5) == head(fixed6) and head(free5) != head(free6) != head(fixed6)
    lengths = lambda pool: [len(d) for d in pool._docs]
    assert lengths(fixed5) == lengths(free5) != lengths(fixed6) == lengths(free6)
    assert not np.array_equal(fixed5._docs[0], fixed6._docs[0][: len(fixed5._docs[0])])
    # another number, other rows: the key is read, not its presence
    other = inputs.dataset(5, {**small, "rank_seed": traffic["rank_seed"] + 1}, cfg)
    assert head(other) != head(fixed5) and lengths(other) == lengths(fixed5)


def test_operation_counts_at_the_published_widths():
    """536 M parameters and 54.5 TFLOP a step (2 rows x 8192), half of
    them the causal attention product: from shapes alone, nothing allocated."""
    from moco_tpu.core import build_encoder

    cfg, _, ref, inputs = _family(False)
    shapes = jax.eval_shape(
        lambda r: build_encoder(cfg.moco).init(r, inputs.sample_input(cfg), train=False),
        jax.random.PRNGKey(0),
    )["params"]
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)) == 536_316_032
    step = train_cell._step_flops(cfg, ref, inputs)
    attention = load_module(Manifest().bench_dir, "required", "mla_attention")
    need = attention.work(rows=2, seq_len=8192, layers=5)
    assert need["flops"] == 4 * 2 * 5 * (8192**2 / 2) * 32 * 320 * 2
    assert step == pytest.approx(54.51e12, rel=1e-3) and 0.45 < need["flops"] / step < 0.55


def test_required_work_reads_the_run_and_says_nothing_where_there_is_nothing():
    bench = Manifest().bench_dir
    attention, ffn = (load_module(bench, "required", n) for n in ("mla_attention", "expert_ffn"))
    image_run = {"train_config": {"moco": {}, "data": {"global_batch": 256}}, "chips": 1, "train_lines": []}
    assert attention.required(image_run) is None and ffn.required(image_run) is None
    run = {
        "train_config": {"moco": {"lm_layers": 5, "expert_share": [0, 16]},
                         "data": {"global_batch": 2, "seq_len": 8192}},
        "chips": 1, "train_lines": [{"moe/tokens_per_expert": 500.0}, {"moe/tokens_per_expert": 524.0}],
    }
    assert attention.required(run) == attention.work(2, 8192, 5)
    need = ffn.required(run)
    assert need == ffn.work(512.0 * 16, 16, 4)
    assert need["flops"] == 4 * (3 * 2048 * 768 * 2) * 512 * 16 * 4
