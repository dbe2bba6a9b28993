"""The second token-input family (`reference/smallthinker_moco_v2.py`,
`required/window_attention.py`, `required/gqa_attention.py`,
`required/reglu_expert_ffn.py`) and its cell, `train_smallthinker_16k`: a
whole rehearsal run reads `correct` true, and false with the window
dropped from the program or with the router reading after attention; the
operation counts and the kernels' required work at the published widths
are the numbers worked by hand here; and the cell came as new files and
list entries alone."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import common, correct, train_cell
from benchmarks.harness.manifest import REPO_ROOT, Manifest, load_module
from benchmarks.harness.peaks import peaks_for

CELL = "train_smallthinker_16k"
ACCEPTED = os.path.join(os.path.dirname(__file__), "fixtures", "accepted_benchmark.json")


def _family(rehearse: bool):
    m = Manifest()
    cell = m.cell(CELL)
    cfg_file, traffic = m.config_file(cell["config"]), m.traffic_file(cell["traffic"])
    cfg = common.build_train_config(cfg_file, traffic, 13, "/nonexistent", rehearse)
    return (cfg, traffic, *m.family(cfg_file))


def _run(capsys, seed: int) -> dict:
    from benchmarks import run

    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "3", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _no_window(monkeypatch):
    """The program's window layers attend to every earlier key."""
    import moco_tpu.models.smallthinker as st

    whole = st.causal_flash_attention
    monkeypatch.setattr(st, "causal_flash_attention", lambda *a, window=None, **kw: whole(*a, **kw))


def _late_router(monkeypatch):
    """The program's router reads the states AFTER attention's residual add."""
    import flax.linen as nn
    from jax import lax

    import moco_tpu.models.smallthinker as st
    from moco_tpu.models.decoder import RMSNorm, valid_positions

    class LateRouterBlock(st.Block):
        @nn.compact
        def __call__(self, x, lengths):
            c, dt = self.cfg, self.dtype
            b, s, d = x.shape
            norm = RMSNorm(dt, name="attn_norm")
            router = self.param("router", nn.initializers.lecun_normal(), (d, c.experts), jnp.float32)
            attn = st.GroupedAttention(
                heads=c.heads, kv_heads=c.kv_heads, head_dim=c.head_dim, window=self.window,
                rope_theta=c.rope_theta, dtype=dt, name="attn",
            )
            x = x + attn(norm(x), lengths)
            logits = jnp.matmul(
                norm(x).astype(jnp.float32).reshape(b * s, d), router, precision=lax.Precision.HIGHEST
            )
            u = RMSNorm(dt, name="mlp_norm")(x)
            layer = st.ExpertLayer(
                experts=c.experts, top_k=c.top_k, expert_mlp=c.expert_mlp,
                first_expert=self.first_expert, experts_held=self.experts_held,
                train=self.train, dtype=dt, name="moe",
            )
            valid = valid_positions(lengths, s).reshape(-1)
            return x + layer(u.reshape(b * s, d), valid, logits).reshape(b, s, d)

    monkeypatch.setattr(st, "Block", LateRouterBlock)


@pytest.mark.parametrize("fault", [None, _no_window, _late_router], ids=["sound", "window_dropped", "router_after_attention"])
def test_the_cell_rehearses_correct_and_a_fault_in_the_program_does_not(fault, monkeypatch, capsys):
    """The whole rehearsal run (driver, ring, one row a step, SIGTERM save,
    `correct` with the gradient): true as the program stands; false, with
    finite losses, when the program drops the window or routes late."""
    if fault is not None:
        fault(monkeypatch)
    # a seed a case: the run's directory is named by cell and seed, and the cases may run at once
    result = _run(capsys, 2147483659 + [None, _no_window, _late_router].index(fault))
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["compared"]["nonfinite_losses"]["value"] == 0
    assert set(result["compared"]) >= {"emb_centred_rel_error", "loss_abs_diff", "grad_cosine"}
    if fault is None:
        # no `weights_seed` in this configuration: the timed run's weights are `--seed`'s
        assert result["seed"] == result["weights_seed"] == 2147483659
        assert result["correct"] is True
        assert result["compared"]["emb_centred_rel_error"]["value"] < 1e-5
        return
    assert result["correct"] is False
    failing = {k for k, c in result["compared"].items() if not correct.holds(c)}
    assert "emb_centred_rel_error" in failing
    assert failing <= {"emb_centred_rel_error", "loss_abs_diff", "grad_cosine", "grad_norm_ratio"}


def _published_shapes(cfg, inputs):
    from moco_tpu.core import build_encoder

    return jax.eval_shape(
        lambda r: build_encoder(cfg.moco).init(r, inputs.sample_input(cfg), train=False),
        jax.random.PRNGKey(0),
    )["params"]


def test_operation_counts_at_the_published_widths():
    """328.8 M parameters; one row of 16 384 tokens forward, by hand."""
    cfg, _, ref, inputs = _family(False)
    shapes = _published_shapes(cfg, inputs)
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)) == 328_811_648
    s = 16384
    # a token and layer: q and o 2560 x 3584, k and v 2560 x 512, the router 2560 x 64, and
    # the expected 6 x 8 / 64 = 0.75 routed experts of 3 x 2560 x 768; a multiply-add is 2
    per_token_layer = 2 * (2 * 2560 * 3584 + 2 * 2560 * 512) + 2 * 2560 * 64 + 0.75 * 2 * 3 * 2560 * 768
    assert per_token_layer == 41_943_040 + 327_680 + 8_847_360
    full = (s * s / 2) * 28 * (128 + 128) * 2  # 1.924 TFLOP
    window = (4096 * s - 4096 * 4096 / 2) * 28 * (128 + 128) * 2  # 0.842 TFLOP
    assert full == pytest.approx(1.924e12, rel=1e-3) and window == pytest.approx(0.842e12, rel=1e-3)
    assert window / full == 0.4375
    head = 2 * (2560 * 2560 + 2560 * 128)
    forward = s * 4 * per_token_layer + full + 3 * window + head
    assert ref.forward_flops(shapes, cfg) == pytest.approx(forward, rel=1e-12)
    step = train_cell._step_flops(cfg, ref, inputs)
    assert step == pytest.approx(4 * forward + 4.0 * 1 * 128 * 65537, rel=1e-12)
    assert step == pytest.approx(31.2e12, rel=5e-3)
    pairs = load_module(Manifest().bench_dir, "required", "window_attention").pairs
    assert pairs(s, None) == s * s / 2 and pairs(s, 4096) == 58_720_256
    assert pairs(2048, 4096) == 2048 * 2048 / 2  # a window longer than the row


def test_required_work_at_the_published_widths_and_nothing_where_there_is_nothing():
    bench = Manifest().bench_dir
    window, gqa, ffn = (
        load_module(bench, "required", n) for n in ("window_attention", "gqa_attention", "reglu_expert_ffn")
    )
    run = {
        "train_config": {"moco": {"lm_layers": 4, "expert_share": [0, 8]},
                         "data": {"global_batch": 1, "seq_len": 16384}},
        "chips": 1, "train_lines": [{"moe/tokens_per_expert": 1500.0}, {"moe/tokens_per_expert": 1572.0}],
    }
    s, b = 16384, 2  # positions; bytes an element
    need = window.required(run)
    assert need["flops"] == 4 * 3 * 58_720_256 * 28 * 256 * 2 == pytest.approx(10.1e12, rel=1e-2)
    # forward x 2: q, out at 28 heads and k, v at 4; backward: those, g (28) read; dq (28), dk, dv (4 + 4) written
    assert need["bytes"] == 3 * s * 128 * b * (2 * (56 + 8) + (56 + 8 + 28 + 28 + 8))
    need = gqa.required(run)
    assert need["flops"] == 4 * 1 * (s * s / 2) * 28 * 256 * 2 == pytest.approx(7.70e12, rel=1e-2)
    assert need["bytes"] == 1 * s * 128 * b * (2 * 64 + 128)
    # k, v, dk and dv are counted at the 4 KEY heads: copied out to the 28 query heads they
    # would be (2 * 3 + 2 * 2) * 24 more head-rows
    assert need["bytes"] < s * 128 * b * (2 * 112 + 224)
    least, bound = (lambda r: (r["flops"] / 197e12, r["bytes"] / 819e9))(need)
    assert least > 25 * bound  # compute-bound by far: the share divides by operations
    need = ffn.required(run)
    assert need == ffn.work(1536.0 * 8, 8, 4)
    assert need["flops"] == 4 * (3 * 2560 * 768 * 2) * 1536 * 8 * 4
    assert need["bytes"] == 4 * (5 * 8 * 3 * 2560 * 768 * b + 4 * 1536 * 8 * b * (2560 + 1536 + 768 + 2560))
    # two periods: 2 full and 6 window layers; a stack cut above its first window layer has none
    run["train_config"]["moco"]["lm_layers"] = 8
    assert window.required(run)["flops"] == 2 * 4 * 3 * 58_720_256 * 28 * 256 * 2
    assert gqa.required(run)["flops"] == 2 * 4 * (s * s / 2) * 28 * 256 * 2
    run["train_config"]["moco"]["lm_layers"] = 1
    assert window.required(run) is None and gqa.required(run) is not None
    image_run = {"train_config": {"moco": {}, "data": {"global_batch": 256}}, "chips": 1, "train_lines": []}
    assert all(mod.required(image_run) is None for mod in (window, gqa, ffn))


def test_the_new_kernel_metrics_read_their_own_kernels_by_name():
    """Each of the six per-layer metrics of this cell finds its kernel's
    events by the pattern in its file, a window call apart from a call
    without one, and a roofline share under 100 % at the predicted times."""
    m = Manifest()
    ours = [e for e in m.raw["per_layer"] if e.get("workloads") == [CELL]]
    assert sorted(e["name"] for e in ours) == [
        "gqa_attention_ms", "gqa_attention_roofline", "reglu_expert_ffn_ms",
        "reglu_expert_ffn_roofline", "window_attention_ms", "window_attention_roofline",
    ]
    assert all(e["layer"] == "kernels" and e["moves"] == "train_img_per_s_chip" for e in ours)
    gmm = ('%gmm = bf16[98304,1536]{1,0} custom-call(s32[8], bf16[98304,2560], bf16[8,2560,1536]), '
           'custom_call_target="tpu_custom_call"')
    other = 'fusion.7 = bf16[16384,2560] custom-call(), custom_call_target="tpu_custom_call" causal_infonce'
    ms = 1_000_000  # the trace's clock counts nanoseconds
    ops = [("window_attention_fwd", 0, 16 * ms), ("window_attention_dkv", 20 * ms, 18 * ms),
           ("causal_attention_dq", 40 * ms, 36 * ms), (gmm, 80 * ms, 4 * ms), (other, 90 * ms, 5 * ms)]
    ctx = {
        "trace": {"steps": 1}, "trace_ops": ops, "peaks": peaks_for("TPU v5 lite"), "chips": 1,
        "train_config": {"moco": {"lm_layers": 4, "expert_share": [0, 8]},
                         "data": {"global_batch": 1, "seq_len": 16384}},
        "train_lines": [{"moe/tokens_per_expert": 1536.0}],
    }
    got = {}
    for e in ours:
        spec = m.layer_metric_file(e["name"])
        got[e["name"]] = m.reader(spec["reader"]).read(spec, ctx)
    assert got["window_attention_ms"] == pytest.approx(34.0)
    assert got["gqa_attention_ms"] == pytest.approx(36.0)
    assert got["reglu_expert_ffn_ms"] == pytest.approx(4.0)
    assert all(0 < got[n] for n in got if n.endswith("roofline"))
    # JoyAI's metrics match the same attention kernels by their own widths, and not these experts
    spec = m.layer_metric_file("expert_ffn_ms")
    assert m.reader(spec["reader"]).read(spec, ctx) is None


def test_the_configuration_file_keeps_every_published_number_but_the_three_reduced():
    """The catalog's `config` for SmallThinker-21BA3B-Instruct, key for key."""
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28,
        "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936,
    }
    m = Manifest()
    cfg = m.config_file("smallthinker_21b_ep8")
    reduced = {"num_hidden_layers": 4, "moe_num_primary_experts": 8, "vocab_size": 18992}
    assert sorted(cfg["reduced"]) == sorted(reduced) == sorted(m.configs["smallthinker_21b_ep8"]["reduced"])
    for key, value in published.items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg["published"][key] == value
    assert cfg["vocab_size"] * 8 == 151936 and cfg["overrides"] == {
        "moco.lm_layers": 4, "moco.lm_vocab_rows": 18992, "moco.expert_share": [0, 8],
    }
    built, traffic, _, _ = _family(False)
    assert built.data.seq_len == 16384 and built.data.global_batch == 1 and built.moco.remat
    assert (traffic["doc_len_median"], traffic["doc_len_min"], traffic["doc_len_max"]) == (32768, 16384, 131072)
    # the same keys, data only (`rank_seed`, with its reason under `assumed`, is the other token
    # cell's own fixed draw, since PR 36)
    assert set(traffic) == set(m.traffic_file("job_loop_tokens_8k")) - {"rank_seed", "assumed"}


def test_the_cell_came_as_new_files_and_list_entries_alone():
    """Every benchmark file the accepted benchmark had (the fixture: PR
    36's tree; a later `benchmark` PR that edits one refreshes it) has the
    bytes it had, and every entry the accepted manifest had is in
    BENCHMARK.json as it was, a metric's list of cells appended to at most.
    (Later PRs append theirs: only what was there is held.)"""
    accepted = json.load(open(ACCEPTED))
    for path, digest in accepted["files"].items():
        with open(os.path.join(REPO_ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path
    now, old = Manifest().raw, accepted["manifest"]
    assert {k: now[k] for k in ("command", "paths", "run_seconds")} == {
        k: old[k] for k in ("command", "paths", "run_seconds")
    }
    cells = {w["name"] for w in old["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(now[group]) >= len(old[group])
        for was, is_ in zip(old[group], now[group]):  # the old entries first, in their order
            kept = dict(is_)
            if "workloads" in was:  # appended to, and nothing else
                assert is_["workloads"][: len(was["workloads"])] == was["workloads"]
                assert not set(is_["workloads"][len(was["workloads"]) :]) & cells
                kept["workloads"] = was["workloads"]
            assert kept == was
