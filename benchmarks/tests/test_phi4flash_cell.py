"""The third token-input family (`reference/phi4flash_moco_v2.py`,
`required/selective_scan.py`, `required/diff_attention.py`) and its cell,
`train_phi4_flash_16k`: a whole rehearsal run reads `correct` true, and
false with the window dropped from the program; the operation counts and
the kernels' required work at the published widths are the numbers worked
by hand here; the four kernel metrics find their own kernels; and the
configuration keeps every published number but the two it cuts."""

import json

import jax
import numpy as np
import pytest

from benchmarks.harness import common, correct, train_cell
from benchmarks.harness.manifest import Manifest, load_module
from benchmarks.harness.peaks import peaks_for

CELL = "train_phi4_flash_16k"


def _family(rehearse: bool):
    m = Manifest()
    cell = m.cell(CELL)
    cfg_file, traffic = m.config_file(cell["config"]), m.traffic_file(cell["traffic"])
    cfg = common.build_train_config(cfg_file, traffic, 13, "/nonexistent", rehearse)
    return (cfg, traffic, *m.family(cfg_file))


def _no_window(monkeypatch):
    """The program's window layer attends to every earlier key."""
    import moco_tpu.models.phi4flash as pf

    whole = pf.causal_flash_attention
    monkeypatch.setattr(pf, "causal_flash_attention", lambda *a, window=None, **kw: whole(*a, **kw))


@pytest.mark.parametrize("fault", [None, _no_window], ids=["sound", "window_dropped"])
def test_the_cell_rehearses_correct_and_a_fault_in_the_program_does_not(fault, monkeypatch, capsys):
    """The whole rehearsal run (driver, ring, one row a step, SIGTERM save,
    `correct` with the gradient): true as the program stands; false, with
    finite losses, when the program drops the window."""
    from benchmarks import run

    if fault is not None:
        fault(monkeypatch)
    seed = 2147483669 + (fault is not None)  # a seed a case: the run's directory is named by it
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "3", "--rehearse"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["compared"]["nonfinite_losses"]["value"] == 0
    assert set(result["compared"]) >= {"emb_centred_rel_error", "loss_abs_diff", "grad_cosine"}
    if fault is None:
        assert result["seed"] == result["weights_seed"] == seed
        assert result["correct"] is True
        assert result["compared"]["emb_centred_rel_error"]["value"] < 1e-5
        return
    assert result["correct"] is False
    failing = {k for k, c in result["compared"].items() if not correct.holds(c)}
    assert "emb_centred_rel_error" in failing


def test_operation_counts_at_the_published_widths():
    """One row of 16 384 tokens forward through published layers 15-19, by hand."""
    from moco_tpu.core import build_encoder

    cfg, _, ref, inputs = _family(False)
    shapes = jax.eval_shape(
        lambda r: build_encoder(cfg.moco).init(r, inputs.sample_input(cfg), train=False),
        jax.random.PRNGKey(0),
    )["params"]
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)) == 584_062_720
    s, d, e, ff = 16384, 2560, 5120, 10240
    mlp = 2 * (d * 2 * ff + ff * d)
    attention = 2 * (2 * d * d + 2 * d * 1280)  # q, o | k, v: a self layer's projections
    mamba = 2 * (d * 2 * e + e * 192 + e * d) + 2 * 160 * e + 2 * 4 * e + e * (7 * 16 + 3)
    per_token = 5 * mlp + 2 * attention + mamba + 2 * (2 * d * e) + 2 * (2 * d * d)
    assert per_token == pytest.approx(1.0266e9, rel=1e-3)
    per_pair = 20 * 2 * 2 * (64 + 128)  # 20 differential heads, 2 softmaxes, q.k and p.v
    window = (512 * s - 512 * 512 / 2) * per_pair
    whole = (s * s / 2) * per_pair  # the full layer and the cross layer each
    assert window / whole == 1 / 16 - 1 / 1024  # 2W/S - (W/S)^2 of the full layer's pairs
    head = 2 * (d * d + d * 128)
    forward = s * per_token + window + 2 * whole + head
    assert ref.forward_flops(shapes, cfg) == pytest.approx(forward, rel=1e-12)
    step = train_cell._step_flops(cfg, ref, inputs)
    assert step == pytest.approx(4 * forward + 4.0 * 1 * 128 * 65537, rel=1e-12)
    assert step == pytest.approx(84.3e12, rel=5e-3)


def test_required_work_at_the_published_widths_and_nothing_where_there_is_nothing():
    bench = Manifest().bench_dir
    diff, scan = (load_module(bench, "required", n) for n in ("diff_attention", "selective_scan"))
    run = {
        "train_config": {"moco": {"arch": "phi4_mini_flash", "lm_layers": 5, "lm_first_layer": 15},
                         "data": {"global_batch": 1, "seq_len": 16384}},
        "chips": 1,
    }
    s, b = 16384, 2
    need = diff.required(run)
    pairs = (512 * s - 512 * 512 / 2) + 2 * (s * s / 2)  # the window layer, the full and the cross layer
    assert need["flops"] == 4 * pairs * 20 * 384 * 2 == pytest.approx(17.0e12, rel=1e-2)
    # a softmax: q (20 x 64), out (20 x 128), k (10 x 64), v (10 x 128) forward x 2;
    # backward those and g read, dq, dk, dv written; two softmaxes a layer, three layers
    fwd, bwd = 20 * 192 + 10 * 192, 20 * 192 + 10 * 192 + 20 * 128 + 20 * 64 + 10 * 192
    assert need["bytes"] == s * b * (2 * fwd + bwd) * 6
    least, bound = need["flops"] / 197e12, need["bytes"] / 819e9
    assert least > 10 * bound  # compute-bound
    need = scan.required(run)
    wide, narrow = s * 5120, s * 16
    assert need["bytes"] == 2 * (wide * 8 + 2 * narrow * 4) + wide * 14 + 4 * narrow * 4
    assert need["flops"] == 4 * s * 5120 * 115
    assert need["bytes"] / 819e9 > 10 * need["flops"] / 197e12  # bound by bytes
    # a stage with no Mamba layer, a stage past the attention layers, another family, an image run
    run["train_config"]["moco"].update(lm_first_layer=17, lm_layers=1)
    assert scan.required(run) is None and diff.required(run)["flops"] == 4 * (s * s / 2) * 20 * 384 * 2
    run["train_config"]["moco"].update(lm_first_layer=18, lm_layers=1)
    assert diff.required(run) is None
    other = {"train_config": {"moco": {"arch": "smallthinker_21b", "lm_layers": 4},
                              "data": {"global_batch": 1, "seq_len": 16384}}, "chips": 1}
    image = {"train_config": {"moco": {"arch": "resnet50"}, "data": {"global_batch": 256}}, "chips": 1}
    assert all(mod.required(r) is None for mod in (diff, scan) for r in (other, image))


def test_the_new_kernel_metrics_read_their_own_kernels_by_name():
    """Each of the four per-layer metrics of this cell finds its kernel's
    events by the pattern in its file, and the other families' attention
    metrics find none of them."""
    m = Manifest()
    ours = [e for e in m.raw["per_layer"] if e.get("workloads") == [CELL]]
    assert sorted(e["name"] for e in ours) == [
        "diff_attention_ms", "diff_attention_roofline", "selective_scan_ms", "selective_scan_roofline",
    ]
    assert all(e["layer"] == "kernels" and e["moves"] == "train_img_per_s_chip" for e in ours)
    ms = 1_000_000  # the trace's clock counts nanoseconds
    ops = [("diff_attention_fwd", 0, 30 * ms), ("diff_attention_dq", 40 * ms, 40 * ms),
           ("diff_attention_dkv", 90 * ms, 50 * ms), ("selective_scan_fwd", 150 * ms, 10 * ms),
           ("selective_scan_bwd", 170 * ms, 20 * ms)]
    ctx = {
        "trace": {"steps": 1}, "trace_ops": ops, "peaks": peaks_for("TPU v5 lite"), "chips": 1,
        "train_config": {"moco": {"arch": "phi4_mini_flash", "lm_layers": 5, "lm_first_layer": 15},
                         "data": {"global_batch": 1, "seq_len": 16384}},
    }
    got = {}
    for e in ours:
        spec = m.layer_metric_file(e["name"])
        got[e["name"]] = m.reader(spec["reader"]).read(spec, ctx)
    assert got["diff_attention_ms"] == pytest.approx(120.0)
    assert got["selective_scan_ms"] == pytest.approx(30.0)
    assert all(0 < got[n] < 100 for n in got if n.endswith("roofline"))
    for name in ("gqa_attention_ms", "window_attention_ms", "mla_attention_ms"):
        spec = m.layer_metric_file(name)
        assert m.reader(spec["reader"]).read(spec, ctx) is None


def test_the_configuration_file_keeps_every_published_number_but_the_two_reduced():
    """The catalog's `config` for Phi-4-mini-flash-reasoning, key for key."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
    }
    m = Manifest()
    cfg = m.config_file("phi4_mini_flash_stage5")
    reduced = {"num_hidden_layers": 5, "vocab_size": 25008}
    assert sorted(cfg["reduced"]) == sorted(reduced) == sorted(m.configs["phi4_mini_flash_stage5"]["reduced"])
    for key, value in published.items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg["published"][key] == value
    assert cfg["vocab_size"] * 8 == 200064 and cfg["overrides"] == {
        "moco.lm_layers": 5, "moco.lm_first_layer": 15, "moco.lm_vocab_rows": 25008,
    }
    assert len(cfg["published"]["layer_map"]) == 32
    assert set(cfg["assumed"]) >= {
        "layer_map", "differential_attention", "head_pairing", "no_position_encoding", "norm",
        "mamba_sizes", "memory", "window_edge", "temperature_momentum", "optimizer",
    }
    built, traffic, _, _ = _family(False)
    assert built.data.seq_len == 16384 and built.data.global_batch == 1 and built.moco.remat
    assert built.moco.lm_first_layer == 15 and not built.moco.expert_share
    assert (traffic["doc_len_median"], traffic["doc_len_min"], traffic["doc_len_max"]) == (32768, 16384, 131072)


def test_the_seed_draws_the_inputs_and_the_weights_alike():
    """`test_weights_seed.py`'s case for every admitted cell, on this one
    (no `weights_seed`: the configuration is built as before that key
    came). That file's list of cases names the four cells it was written
    with; it is not this PR's to edit (PERF.md section 7)."""
    from benchmarks.tests import test_weights_seed

    test_weights_seed.test_the_seed_draws_the_inputs_and_the_key_alone_fixes_the_weights(CELL)
