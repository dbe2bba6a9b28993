"""The scope reader (`readers/scope.py`): device time per whole step by the
innermost `moco.` scope of each op's `op_name` path, on hand-made events
and on two whole steps of `train_r50_v2` cut from a chip run
(`fixtures/train_r50_v2_scoped_steps.json`)."""

import gzip
import json
import os

import pytest

from benchmarks.harness.manifest import Manifest
from benchmarks.readers import scope
from moco_tpu.obs.trace import STEP_SCOPES

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "train_r50_v2_scoped_steps.json.gz")
MS = 1_000_000


@pytest.mark.parametrize("path,want", [
    ("jit(step_fn)/transpose(jvp(moco.query_encoder))/MoCoEncoder/backbone/Conv_0/conv", "moco.query_encoder"),
    ("jit(step_fn)/jvp(moco.query_encoder)/layer_1/moe/moco.moe_dispatch/jvp(moco.expert_ffn)/gmm",
     "moco.expert_ffn"),
    ("jit(step_fn)/transpose(jvp(moco.query_encoder))/moco.moe_dispatch/scatter-add", "moco.moe_dispatch"),
    ("jit(_augment)/moco.augment.flip_normalize/rev", "moco.augment.flip_normalize"),
    ("jit(step_fn)/comms.grad.psum/psum", "none"),
    ("", "none"),
])
def test_innermost_scope_of_a_path(path, want):
    assert scope.innermost(path) == want


def _hand_made():
    """Four step programs and three augmentation programs. The first and
    the last module events may be cut by the trace's ends, and the first
    step's augmentation program is the first, so two steps are whole, each
    with the augmentation program that ran before it."""
    mods = [("jit__augment(2)", 0, 10 * MS), ("jit_step_fn(1)", 10 * MS, 100 * MS),
            ("jit__augment(2)", 115 * MS, 10 * MS), ("jit_step_fn(1)", 130 * MS, 100 * MS),
            ("jit__augment(2)", 235 * MS, 10 * MS), ("jit_step_fn(1)", 250 * MS, 100 * MS),
            ("jit_step_fn(1)", 360 * MS, 50 * MS)]
    ops = []
    for s0 in (10, 130, 250, 360):  # a step: a while with a nested convolution, the loss, the update
        ops += [("%while.1 = while(...)", s0 * MS, 60 * MS, "jit(step_fn)/jvp(moco.query_encoder)/while"),
                ("%convolution.2 = convolution(...)", (s0 + 10) * MS, 30 * MS,
                 "jit(step_fn)/transpose(jvp(moco.query_encoder))/Conv_0/conv"),
                ("%fusion.3 = fusion(...)", (s0 + 60) * MS, 10 * MS,
                 "jit(step_fn)/jvp(moco.contrastive_loss)/sub"),
                ("%copy.4 = copy(...)", (s0 + 70) * MS, 5 * MS, "jit(step_fn)/copy"),
                ("%fusion.5 = fusion(...)", (s0 + 80) * MS, 20 * MS, "jit(step_fn)/moco.optimizer/add")]
    for a0 in (0, 115, 235):
        ops += [("%fusion.6 = fusion(...)", a0 * MS, 4 * MS, "jit(_augment)/moco.augment.crop/dot"),
                ("%reverse.7 = reverse(...)", (a0 + 4) * MS, 6 * MS,
                 "jit(_augment)/moco.augment.flip_normalize/rev")]
    return ops, mods


def test_whole_steps_and_the_division():
    ops, mods = _hand_made()
    programs, steps = scope.whole_programs(mods)
    assert steps == 2
    assert [m[1] for m in programs] == [115 * MS, 130 * MS, 235 * MS, 250 * MS]
    acc = scope.account(ops, mods)
    assert acc["steps"] == 2 and acc["programs"] == {"step_fn": 2, "augment": 2}
    ms = {k: v["ns_per_step"] / MS for k, v in acc["scopes"].items()}
    # the while's self time is its 60 ms less the 30 ms convolution it holds
    assert ms == {"moco.query_encoder": 60.0, "moco.optimizer": 20.0, "moco.contrastive_loss": 10.0,
                  "none": 5.0, "moco.augment.flip_normalize": 6.0, "moco.augment.crop": 4.0}
    assert acc["busy_ns_per_step"] / MS == pytest.approx(105.0)
    assert acc["scoped_ns_per_step"] == pytest.approx(acc["busy_ns_per_step"])
    assert dict(map(tuple, acc["scopes"]["moco.query_encoder"]["top"])) == {
        "step_fn/while": 30 * MS, "step_fn/convolution": 30 * MS,
    }


def test_a_program_without_scopes_reads_nothing(tmp_path, monkeypatch):
    """The parent of the scopes: every op unscoped, so no metric of this reader
    is reported, and nothing raises."""
    ops, mods = _hand_made()
    bare = [(t, s, d, "jit(step_fn)/add") for t, s, d, _ in ops]
    assert scope.account(bare, mods) is None
    assert scope.account(ops, mods[:2]) is None  # no whole step
    os.makedirs(tmp_path / "profile")
    monkeypatch.setattr(scope, "load_scoped_ops", lambda path: {"ops": bare, "modules": mods})
    ctx = {"trace": {"steps": 3}, "train_config": {"workdir": str(tmp_path)}}
    assert scope.read({"reader": "scope", "scope": "none", "scale": 1e-6}, ctx) is None


def test_the_metric_files_read_the_account(tmp_path, monkeypatch):
    """Each scope metric of the manifest reads its scope's time through its
    file; the account is computed once and kept as `scopes.json`."""
    ops, mods = _hand_made()
    m = Manifest()
    ctx = {"trace": {"steps": 3}, "train_config": {"workdir": str(tmp_path)}}
    specs = [m.layer_metric_file(x["name"]) for x in m.raw["per_layer"]]
    specs = [s for s in specs if s["reader"] == "scope"]
    first = m.reader("scope")  # the harness loads the reader anew for every metric
    monkeypatch.setattr(first, "load_scoped_ops", lambda path: {"ops": ops, "modules": mods})
    assert first.read(specs[0], ctx) is not None and os.path.exists(tmp_path / "scopes.json")
    # the others read the kept account: no profile is there to load
    got = {s["scope"]: m.reader("scope").read(s, ctx) for s in specs}
    assert got["moco.query_encoder"] == pytest.approx(60.0) and got["none"] == pytest.approx(5.0)
    assert got["moco.enqueue"] == 0.0  # named by the program, no op of it in this trace
    # every scope of the step has its metric but the grouped products' (`expert_ffn_ms` reads
    # those by shape), and the unscoped time has one more; the augmentation stages run in the
    # image cells only, whose per-layer lists carry no scope metric yet, so they stay in
    # `scopes.json`
    augment = {s for s in STEP_SCOPES if s.startswith("moco.augment.")}
    assert len(augment) == 4
    assert set(got) == set(STEP_SCOPES) - {"moco.expert_ffn"} - augment | {"none"}
    # the scope metrics list the token cells alone
    cells = {c for x in m.raw["per_layer"] if m.layer_metric_file(x["name"])["reader"] == "scope"
             for c in x["workloads"]}
    assert cells == {"train_joyai_flash_8k", "train_smallthinker_16k", "train_phi4_flash_16k"}


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields) -> bytes:
    """A protobuf message of (field number, int | bytes | str) pairs."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(data)) + data
    return out


def test_op_paths_read_the_hlo_the_profiler_keeps(tmp_path):
    """The `/host:metadata` plane holds each program's HLO as an `Hlo Proto`
    stat; each instruction's op_name comes out by program and name."""
    inst = lambda name, path: _msg((1, name), (2, "fusion"), (7, _msg((1, "mul"), (2, path))))
    module = _msg((1, "jit_step_fn"), (3, _msg((1, "main"), (2, inst("fusion.3", "jit(step_fn)/moco.ema/mul")),
                                               (2, _msg((1, "copy.4"), (2, "copy"))))))
    event = _msg((1, 7), (2, "jit_step_fn(123)"), (5, _msg((1, 9), (6, _msg((1, module))))))
    plane = _msg((1, 2), (2, "/host:metadata"), (4, _msg((1, 7), (2, event))),
                 (5, _msg((1, 9), (2, _msg((1, 9), (2, "Hlo Proto"))))))
    other = _msg((1, 1), (2, "/device:TPU:0"))
    (tmp_path / "a.xplane.pb").write_bytes(_msg((1, other), (1, plane)))
    assert scope.op_paths(str(tmp_path / "a.xplane.pb")) == {
        "jit_step_fn(123)": {"fusion.3": "jit(step_fn)/moco.ema/mul", "copy.4": ""},
    }


def test_two_whole_steps_of_the_r50_cell():
    """Cut from a chip run: the ops' paths as the reader joined them from
    the profile's HLO. The innermost scope wins, a step is the step program
    and the augmentation program before it, and the scopes with the
    unscoped time make up the busy time."""
    with gzip.open(FIXTURE, "rt") as f:
        cut = json.load(f)
    ops, mods = [tuple(o) for o in cut["ops"]], [tuple(m) for m in cut["modules"]]
    acc = scope.account(ops, mods)
    assert acc["steps"] == 2 and acc["programs"] == {"step_fn": 2, "augment": 2}
    ms = {k: v["ns_per_step"] / MS for k, v in acc["scopes"].items()}
    assert sum(ms.values()) == pytest.approx(acc["busy_ns_per_step"] / MS, rel=0.02)
    assert ms["none"] < 0.15 * acc["busy_ns_per_step"] / MS
    # the backward's convolutions, under `transpose(jvp(moco.query_encoder))`, are the query's
    backward = [o for o in ops if "transpose(jvp(moco.query_encoder))" in o[3]]
    assert backward and all(scope.innermost(o[3]) == "moco.query_encoder" for o in backward)
    assert ms["moco.query_encoder"] > 2 * ms["moco.key_encoder"] > 0
    # the two InfoNCE kernels by their names, under the loss's scope, forward and backward
    top = dict(map(tuple, acc["scopes"]["moco.contrastive_loss"]["top"]))
    assert {"step_fn/infonce_fwd", "step_fn/infonce_bwd"} <= set(top)
    assert {"moco.augment.crop", "moco.augment.colour", "moco.augment.blur",
            "moco.augment.flip_normalize"} <= set(ms)
    # one whole step's time is the sum of its two programs' ops
    step_ms = sum(d for _, s, d, _ in ops if any(m[1] <= s < m[1] + m[2] for m in mods[1:-1])) / MS / 2
    assert acc["busy_ns_per_step"] / MS == pytest.approx(step_ms, rel=0.05)
