"""Sharded weight update (ZeRO over the data axis — parallel/zero.py,
after arXiv:2004.13336): the sharded step must produce the same
training trajectory as the replicated update, with opt state held as
(n, m) shards — and, at stage 2/3, the params themselves persisting as
shards with bucketed collectives, equal to stage 1 up to what
separately compiled programs guarantee (stated above
`_assert_losses_within_ulps`)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.core import (
    build_encoder,
    build_predictor,
    create_state,
    full_param_shapes,
    make_train_step,
    place_state,
    reshard_state,
)
from moco_tpu.parallel import create_mesh, shard_batch
from moco_tpu.parallel.zero import (
    AsyncParamGather,
    BucketPlan,
    unshard_tree_host,
)
from moco_tpu.utils.config import (
    DataConfig,
    MocoConfig,
    OptimConfig,
    ParallelConfig,
    TrainConfig,
)
from moco_tpu.utils.schedules import build_optimizer

IMG, BATCH = 16, 16


def _config(
    zero: bool,
    optimizer: str = "sgd",
    v3: bool = False,
    stage: int = 1,
    layer: bool = False,
) -> TrainConfig:
    return TrainConfig(
        moco=MocoConfig(
            arch="resnet18" if not v3 else "vit_tiny",
            dim=32,
            num_negatives=0 if v3 else 256,
            momentum=0.99,
            temperature=0.2,
            mlp=not v3,
            v3=v3,
            shuffle="none" if v3 else "gather_perm",
            cifar_stem=True,
            compute_dtype="float32",
            vit_patch_size=4 if v3 else None,
        ),
        optim=OptimConfig(
            optimizer=optimizer,
            lr=0.05 if optimizer == "sgd" else 1e-3,
            weight_decay=1e-4 if optimizer == "sgd" else 0.1,
            epochs=2,
            cos=True,
        ),
        data=DataConfig(dataset="synthetic", image_size=IMG, global_batch=BATCH),
        parallel=ParallelConfig(
            num_data=8, shard_weight_update=zero, zero_stage=stage,
            # tiny fusion buckets so even the toy model exercises
            # multi-bucket packing (and the ragged tail)
            zero_bucket_mb=0.002,
            zero_layer_granular=layer,
        ),
    )


def _run_steps(config: TrainConfig, n_steps: int = 2, return_step: bool = False):
    mesh = create_mesh(num_data=8)
    encoder = build_encoder(config.moco, num_data=8)
    predictor = build_predictor(config.moco, num_data=8)
    tx = build_optimizer(config.optim, steps_per_epoch=4)
    sample = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
    zero = config.parallel.shard_weight_update
    state = create_state(
        jax.random.PRNGKey(0), config, encoder, tx, sample, predictor=predictor,
        zero_num_data=8 if zero else None,
    )
    step = make_train_step(
        config, encoder, tx, mesh, predictor=predictor, total_steps=8,
        state_template=state if zero else None,
    )
    state = place_state(
        state, mesh, zero=zero,
        zero_params=zero and config.parallel.zero_stage >= 2,
    )
    rng = jax.device_put(
        jax.random.PRNGKey(3),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    losses = []
    for i in range(n_steps):
        ims = jax.random.normal(jax.random.PRNGKey(10 + i), (2, BATCH, IMG, IMG, 3))
        batch = shard_batch(mesh, {"im_q": ims[0], "im_k": ims[1]})
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    if return_step:
        return state, losses, step
    return state, losses


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
@pytest.mark.slow  # replicated-vs-ZeRO A/B compiles both step programs per optimizer
def test_zero_matches_replicated_update(optimizer):
    s_rep, l_rep = _run_steps(_config(zero=False, optimizer=optimizer))
    s_zero, l_zero = _run_steps(_config(zero=True, optimizer=optimizer))
    np.testing.assert_allclose(l_zero, l_rep, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_rep.params_q), jax.tree.leaves(s_zero.params_q)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


@pytest.mark.slow  # compiles two full v3 steps over the mesh (~1 min on CPU)
def test_zero_v3_step_runs_and_matches():
    s_rep, l_rep = _run_steps(_config(zero=False, optimizer="adamw", v3=True))
    s_zero, l_zero = _run_steps(_config(zero=True, optimizer="adamw", v3=True))
    np.testing.assert_allclose(l_zero, l_rep, rtol=1e-5)
    # frozen patch embed must stay at init under ZeRO too
    pe_rep = jax.tree.leaves(s_rep.params_q["backbone"]["patch_embed"])
    pe_zero = jax.tree.leaves(s_zero.params_q["backbone"]["patch_embed"])
    for a, b in zip(pe_rep, pe_zero):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_opt_state_is_sharded():
    config = _config(zero=True, optimizer="adamw")
    # opt state leaves (other than scalars) are (8, m): 1/8 per device
    state, _ = _run_steps(config, n_steps=1)
    leaves = [x for x in jax.tree.leaves(state.opt_state) if x.ndim == 2]
    assert leaves, "expected sharded (n, m) opt-state leaves"
    for leaf in leaves:
        assert leaf.shape[0] == 8
        assert len(leaf.addressable_shards) == 8
        assert leaf.addressable_shards[0].data.shape[0] == 1  # one row per device


@pytest.mark.parametrize("stage", [1, 3])
def test_zero_rejects_lars(stage):
    config = _config(zero=True, optimizer="sgd", stage=stage)
    config = dataclasses.replace(
        config, optim=dataclasses.replace(config.optim, optimizer="lars")
    )
    mesh = create_mesh(num_data=8)
    encoder = build_encoder(config.moco, num_data=8)
    tx = build_optimizer(config.optim, steps_per_epoch=4)
    state = create_state(
        jax.random.PRNGKey(0), config, encoder, tx,
        jnp.zeros((1, IMG, IMG, 3), jnp.float32), zero_num_data=8,
    )
    with pytest.raises(ValueError, match="element-wise"):
        make_train_step(config, encoder, tx, mesh, state_template=state)


# ---------------------------------------------------------------------------
# ZeRO-2/3: persistently sharded params + bucketed collectives (ISSUE 7)
# ---------------------------------------------------------------------------


# What two SEPARATELY COMPILED step programs guarantee on jax 0.9.0.
# The bucket transforms preserve per-leaf partitioning, so the stages
# compute the same reductions — but XLA fuses and orders each program's
# f32 arithmetic on its own, and the bits are not the same: the very
# first forward (same params, same batch, before any update) already
# reports 1.9157708883 under stage 1 and the layer-granular step and
# 1.9157705307 under whole-tree stage 2/3, 3 ULP apart, and two SGD
# steps through 2-rows-per-device BatchNorm amplify that. Measured after
# two steps (this mesh, this config, both pairs the tests compare; the
# figures are reproducible to every digit, whatever the core count):
# losses 3 and 2 ULP apart; per leaf, ||a-b||/||a|| at most 4.97e-4 on
# params_q, 9.78e-4 on the momentum buffers, 4.35e-6 on params_k,
# 1.70e-6 on the BN statistics. The params_q figure belongs to the
# 64-element BN biases, which start at zero and have next to no norm to
# be relative to; every leaf of 10k elements or more is within 1.65e-5.
# The bounds are those figures with ~1.2x room. A schedule bug is far
# outside them: a lost or mis-scaled bucket (512 elements here) moves
# its momentum leaf by a relative 0.1 or more. Bit equality held on the
# older jax these tests were written on; it was a property of that
# compiler, not of the schedule.
FIRST_LOSS_ULPS = 3  # the first forward: no update has amplified anything yet
LOSS_ULPS = 4
REL_L2 = {"params_q": 6e-4, "opt_state": 1.2e-3, "params_k": 5.2e-6, "batch_stats": 2.1e-6}
LARGE_LEAF, REL_L2_LARGE_PARAMS_Q = 10_000, 2e-5


def _assert_losses_within_ulps(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ulps = np.full(a.shape, LOSS_ULPS)
    ulps[0] = FIRST_LOSS_ULPS
    bound = ulps * np.spacing(np.maximum(np.abs(a), np.abs(b)))
    assert (np.abs(a - b) <= bound).all(), f"loss trajectories diverged: {a} vs {b}"


def _assert_leaves_close(xs, ys, rel_l2, what, rel_l2_large=None):
    xs, ys = jax.tree.leaves(xs), jax.tree.leaves(ys)
    assert len(xs) == len(ys), what
    for i, (x, y) in enumerate(zip(xs, ys)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        err, ref = np.linalg.norm(x - y), np.linalg.norm(x)
        bound = rel_l2_large if rel_l2_large and x.size >= LARGE_LEAF else rel_l2
        assert err <= bound * ref, (
            f"{what} leaf {i} {x.shape}: ||a-b||/||a|| = {err / max(ref, 1e-300):.3e} "
            f"> {bound:g}"
        )


def _assert_params_q_close(xs, ys):
    _assert_leaves_close(
        xs, ys, REL_L2["params_q"], "params_q", rel_l2_large=REL_L2_LARGE_PARAMS_Q
    )


@functools.cache
def _zero23_two_steps():
    """The whole-tree stage-2/3 reference run, compiled once for the two
    tests that compare against it (tier-1 wall time)."""
    return _run_steps(_config(zero=True, stage=3), n_steps=2, return_step=True)


def test_zero23_update_matches_zero1_to_compile_noise():
    """The stage-2/3 step (persistent shards, bucketed collectives,
    gather-at-step-start, shard-local EMA) must reproduce the validated
    stage-1 sharded update — to what separately compiled programs
    guarantee (the bounds and their reason are stated above)."""
    s1, l1 = _run_steps(_config(zero=True), n_steps=2)
    s23, l23, _ = _zero23_two_steps()
    _assert_losses_within_ulps(l1, l23)
    cfg = _config(zero=True, stage=3)
    shapes = full_param_shapes(cfg, build_encoder(cfg.moco, num_data=8))
    q_full = unshard_tree_host(s23.params_q, shapes["enc"])
    k_full = unshard_tree_host(s23.params_k, shapes["enc"])
    _assert_params_q_close(s1.params_q, q_full)
    _assert_leaves_close(s1.params_k, k_full, REL_L2["params_k"], "params_k")
    # opt state shares the (n, m) layout across stages: compared directly
    _assert_leaves_close(s1.opt_state, s23.opt_state, REL_L2["opt_state"], "opt_state")
    # ... and the stage-2/3 params PERSIST as (8, m), one row per device,
    # shrinking the at-rest per-device state footprint (same runs reused
    # so the suite pays no extra compiles for the layout assertions)
    from moco_tpu.obs.stepstats import tree_shard_bytes

    for leaf in jax.tree.leaves(s23.params_q):
        assert leaf.ndim == 2 and leaf.shape[0] == 8
        assert len(leaf.addressable_shards) == 8
        assert leaf.addressable_shards[0].data.shape[0] == 1
    assert tree_shard_bytes(s23) < 0.5 * tree_shard_bytes(s1)


def test_zero_layer_granular_matches_zero23_and_peak():
    """Tentpole invariant (ISSUE 20): the layer-granular schedule —
    per-group just-in-time gathers inside rematerialized segments, one
    group prefetched ahead, AD-transpose psum_scatter landing summed
    cotangents on the shards — reproduces the whole-tree stage-2/3 step
    on ResNet (losses, params, opt state, both stats collections) to
    what separately compiled programs guarantee (bounds and reason
    above `_assert_losses_within_ulps`), while the
    analytic peak model bytes drop >= 2x below the whole-tree gather's."""
    s23, l23, st23 = _zero23_two_steps()
    sl, ll, stl = _run_steps(
        _config(zero=True, stage=3, layer=True), return_step=True
    )
    _assert_losses_within_ulps(l23, ll)
    cfg = _config(zero=True, stage=3)
    shapes = full_param_shapes(cfg, build_encoder(cfg.moco, num_data=8))
    q23, k23, ql, kl = (
        unshard_tree_host(p, shapes["enc"])
        for p in (s23.params_q, s23.params_k, sl.params_q, sl.params_k)
    )
    _assert_params_q_close(q23, ql)
    _assert_leaves_close(k23, kl, REL_L2["params_k"], "params_k")
    _assert_leaves_close(s23.opt_state, sl.opt_state, REL_L2["opt_state"], "opt_state")
    for coll in ("batch_stats_q", "batch_stats_k"):
        _assert_leaves_close(
            getattr(s23, coll), getattr(sl, coll), REL_L2["batch_stats"], coll
        )
    # the memory claim, analytically: shards + one live group pair vs
    # shards + the whole gathered tree
    assert stl.layer_granular and not st23.layer_granular
    assert stl.hbm_model_peak_bytes * 2 <= st23.hbm_model_peak_bytes, (
        f"layer-granular peak {stl.hbm_model_peak_bytes} not >=2x below "
        f"whole-tree {st23.hbm_model_peak_bytes}"
    )
    # the schedule is the model's declared group order
    assert [g.name for g in stl.group_plan.groups] == list(
        build_encoder(cfg.moco, num_data=8).backbone.group_names
    ) + ["head"]


@pytest.mark.slow  # two extra v3 step compiles (ViT + predictor path)
def test_zero_layer_granular_v3_loss_bitwise():
    """The v3 (ViT + predictor) layer schedule: loss trajectory bitwise
    vs whole-tree zero23. Params are NOT asserted bitwise here:
    `jax.checkpoint` alone shifts ViT backward gradients by ~1e-9 on CPU
    (XLA fuses the rematerialized backward differently), and adamw's
    sign-like step-1 normalization amplifies that — see the note in
    core/moco.py's `_make_q_segment`."""
    _, l23 = _run_steps(_config(zero=True, stage=3, v3=True, optimizer="adamw"))
    _, ll = _run_steps(
        _config(zero=True, stage=3, v3=True, optimizer="adamw", layer=True)
    )
    assert l23 == ll, f"v3 loss trajectories diverged: {l23} vs {ll}"


def test_zero_layer_granular_requires_stage23():
    """The layer flag without persistent param shards is a config error,
    not a silent fallback."""
    config = _config(zero=True, stage=1, layer=True)
    mesh = create_mesh(num_data=8)
    encoder = build_encoder(config.moco, num_data=8)
    tx = build_optimizer(config.optim, steps_per_epoch=4)
    state = create_state(
        jax.random.PRNGKey(0), config, encoder, tx,
        jnp.zeros((1, IMG, IMG, 3), jnp.float32), zero_num_data=8,
    )
    with pytest.raises(ValueError, match="zero_layer_granular"):
        make_train_step(config, encoder, tx, mesh, state_template=state)


def test_zero_layer_step_donates_shards():
    """Donation audit: with donate=True the layer-granular step consumes
    the input state's shard buffers (no silent double-buffering of the
    persistent (n, m) shards next to the per-group transients)."""
    config = _config(zero=True, stage=3, layer=True)
    mesh = create_mesh(num_data=8)
    encoder = build_encoder(config.moco, num_data=8)
    tx = build_optimizer(config.optim, steps_per_epoch=4)
    state = create_state(
        jax.random.PRNGKey(0), config, encoder, tx,
        jnp.zeros((1, IMG, IMG, 3), jnp.float32), zero_num_data=8,
    )
    step = make_train_step(
        config, encoder, tx, mesh, total_steps=8, state_template=state,
        donate=True,
    )
    state = place_state(state, mesh, zero=True, zero_params=True)
    rng = jax.device_put(
        jax.random.PRNGKey(3),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    ims = jax.random.normal(jax.random.PRNGKey(10), (2, BATCH, IMG, IMG, 3))
    batch = shard_batch(mesh, {"im_q": ims[0], "im_k": ims[1]})
    old_params = jax.tree.leaves(state.params_q)
    new_state, _ = step(state, batch, rng)
    jax.block_until_ready(new_state.params_q)
    assert all(x.is_deleted() for x in old_params), "input shards not donated"


def test_bucket_plan_packing_ragged_tail():
    """Greedy per-dtype packing: buckets close at the byte threshold,
    the ragged tail leaf lands in a final smaller bucket, every leaf is
    covered exactly once with contiguous offsets."""
    n = 8
    leaves = [
        jax.ShapeDtypeStruct((1000,), jnp.float32),  # m=125, 500B shard
        jax.ShapeDtypeStruct((1000,), jnp.float32),
        jax.ShapeDtypeStruct((1000,), jnp.float32),
        jax.ShapeDtypeStruct((7,), jnp.float32),  # the ragged tail
    ]
    plan = BucketPlan(leaves, n, bucket_bytes=1000)
    assert len(plan.buckets) == 2
    covered = sorted(s.index for b in plan.buckets for s in b.slots)
    assert covered == [0, 1, 2, 3]
    for b in plan.buckets:
        off = 0
        for s in b.slots:
            assert s.offset == off
            off += s.m
        assert off == b.total_m
    # the tail bucket holds the leftover leaf 2 + the tiny leaf 3
    tail = plan.buckets[-1]
    assert {s.index for s in tail.slots} == {2, 3}
    assert tail.slots[-1].m == 1  # padded_cols(7, 8)


def test_bucket_plan_splits_dtypes():
    n = 8
    leaves = [
        jax.ShapeDtypeStruct((64,), jnp.float32),
        jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((64,), jnp.float32),
    ]
    plan = BucketPlan(leaves, n, bucket_bytes=1 << 20)
    assert len(plan.buckets) == 2  # one open bucket per dtype
    by_dtype = {str(b.dtype): {s.index for s in b.slots} for b in plan.buckets}
    assert by_dtype["float32"] == {0, 2}
    assert by_dtype["int32"] == {1}


def test_group_plan_partition_errors_and_peak():
    """GroupPlan construction is a total partition check: overlapping
    and missing leaves are errors at build time, and peak_full_bytes is
    the largest ADJACENT pair (the one-group-ahead liveness bound), not
    the largest single group or the total."""
    from moco_tpu.parallel.zero import GroupPlan

    leaves = [
        jax.ShapeDtypeStruct((64,), jnp.float32),  # 256 B
        jax.ShapeDtypeStruct((32,), jnp.float32),  # 128 B
        jax.ShapeDtypeStruct((128,), jnp.float32),  # 512 B
        jax.ShapeDtypeStruct((8,), jnp.float32),  # 32 B
    ]
    with pytest.raises(ValueError, match="re-claims"):
        GroupPlan(leaves, [("a", (0, 1)), ("b", (1, 2, 3))], n=8)
    with pytest.raises(ValueError, match="misses"):
        GroupPlan(leaves, [("a", (0, 1)), ("b", (3,))], n=8)
    plan = GroupPlan(leaves, [("a", (0,)), ("b", (1, 2)), ("c", (3,))], n=8)
    assert [g.name for g in plan.groups] == ["a", "b", "c"]
    assert [g.full_bytes for g in plan.groups] == [256, 640, 32]
    assert plan.peak_full_bytes() == 256 + 640  # adjacent pair a+b
    assert plan.total_full_bytes() == 928
    assert [d["group"] for d in plan.describe()] == ["a", "b", "c"]
    # single-group degenerate case: the peak is the group itself
    solo = GroupPlan(leaves[:1], [("only", (0,))], n=8)
    assert solo.peak_full_bytes() == 256


def test_group_plan_gather_matches_whole_tree_gather():
    """Per-group bucketed gathers reassemble EXACTLY the same full
    leaves as one whole-tree BucketPlan gather (and the source values):
    the element->chunk assignment invariant extends across the group
    partition, so the layer schedule changes memory, not bits."""
    from jax import shard_map
    from moco_tpu.parallel.zero import GroupPlan

    P = jax.sharding.PartitionSpec
    n = 8
    rng = np.random.default_rng(0)
    full = [
        jnp.asarray(rng.standard_normal(s).astype(np.float32))
        for s in ((40,), (33,), (8, 8), (5,))
    ]
    descs = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in full]
    whole = BucketPlan(descs, n, bucket_bytes=128)
    gp = GroupPlan(descs, [("a", (0, 1)), ("b", (2, 3))], n, bucket_bytes=128)
    sharded = whole.shard_leaves(full)  # (n, m) rows, shared layout

    def run(*rows):
        loc = [r.reshape(-1) for r in rows]
        out_whole = whole.gather(loc, site="test.zero.gather")
        ga = gp.gather_group(gp.group_shards(loc, 0), 0, site_prefix="test.zero.layer")
        gb = gp.gather_group(gp.group_shards(loc, 1), 1, site_prefix="test.zero.layer")
        return tuple(out_whole), tuple(ga + gb)

    mesh = create_mesh(num_data=n)
    f = jax.jit(
        shard_map(
            run,
            mesh=mesh,
            in_specs=tuple(P("data") for _ in sharded),
            out_specs=(tuple(P() for _ in full), tuple(P() for _ in full)),
            check_vma=False,
        )
    )
    out_whole, out_groups = f(*sharded)
    for src, w, g in zip(full, out_whole, out_groups):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(src))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(src))


def test_reshard_state_layout_roundtrips():
    """Host-side layout conversion (the 'compatible but resharded'
    resume): zero1 -> zero23 and zero23 -> replicated both reproduce a
    directly-created state of the target layout, bit-for-bit — no step
    compile needed, the init values make the comparison exact."""
    cfg_rep = _config(zero=False)
    cfg_z1 = _config(zero=True, stage=1)
    cfg_z23 = _config(zero=True, stage=3)
    encoder = build_encoder(cfg_rep.moco, num_data=8)
    tx = build_optimizer(cfg_z1.optim, steps_per_epoch=4)
    sample = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
    rng = jax.random.PRNGKey(0)
    s_rep = create_state(rng, cfg_rep, encoder, tx, sample)
    s_z1 = create_state(rng, cfg_z1, encoder, tx, sample, zero_num_data=8)  # mocolint: disable=JX003  (same seed on purpose: the three layouts must hold identical values for the bitwise comparison)
    s_z23 = create_state(rng, cfg_z23, encoder, tx, sample, zero_num_data=8)  # mocolint: disable=JX003  (same seed on purpose, see above)

    up = reshard_state(s_z1, live_template=s_z23, full_template=s_rep)
    for a, b in zip(jax.tree.leaves(up.params_q), jax.tree.leaves(s_z23.params_q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(up.opt_state), jax.tree.leaves(s_z23.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    down = reshard_state(s_z23, live_template=s_rep, full_template=s_rep)
    for a, b in zip(jax.tree.leaves(down.params_q), jax.tree.leaves(s_rep.params_q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(down.params_k), jax.tree.leaves(s_rep.params_k)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_state_unequal_mesh_widths():
    """The elastic-rescale conversion path (ISSUE 12): a checkpoint's
    (n, m) flat shards restore onto a NARROWER, non-divisor mesh width —
    8 -> 5 -> 3 — through the flat-vector converter, bit-for-bit. The
    queue rows, pointer, and batch stats pass through untouched (they
    are replicated, width-independent), and every opt-state leaf lands
    exactly as a directly-created state of the target width would."""
    widths = (8, 5, 3)
    cfg = {n: _config(zero=True, stage=3) for n in widths}
    encoder = build_encoder(cfg[8].moco, num_data=8)
    tx = build_optimizer(cfg[8].optim, steps_per_epoch=4)
    sample = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
    rng = jax.random.PRNGKey(0)
    s_rep = create_state(rng, _config(zero=False), encoder, tx, sample)
    states = {
        n: create_state(rng, cfg[n], encoder, tx, sample, zero_num_data=n)  # mocolint: disable=JX003  (same seed on purpose: every width must hold identical values for the bitwise cross-width comparison)
        for n in widths
    }
    # make the queue content distinctive so "passes through" is a real check
    marked = jnp.arange(states[8].queue.size, dtype=jnp.float32).reshape(
        states[8].queue.shape
    )
    states = {
        n: s.replace(queue=marked, queue_ptr=jnp.asarray(7, jnp.int32))
        for n, s in states.items()
    }

    def assert_matches(converted, target):
        for name in ("params_q", "params_k", "opt_state"):
            for a, b in zip(
                jax.tree.leaves(getattr(converted, name)),
                jax.tree.leaves(getattr(target, name)),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(converted.queue), np.asarray(target.queue)
        )
        assert int(converted.queue_ptr) == int(target.queue_ptr)

    down_5 = reshard_state(states[8], live_template=states[5], full_template=s_rep)
    assert_matches(down_5, states[5])
    down_3 = reshard_state(down_5, live_template=states[3], full_template=s_rep)
    assert_matches(down_3, states[3])
    # and back out to replicated: the full roundtrip loses nothing
    back = reshard_state(down_3, live_template=s_rep, full_template=s_rep)
    for a, b in zip(jax.tree.leaves(back.params_q), jax.tree.leaves(s_rep.params_q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_layer_granular_roundtrips_and_resume_compat():
    """Satellite (ISSUE 20): the layer-granular stage rides the zero23
    persistent layout, so reshard_state round-trips zero1 <-> zero23 <->
    layer-granular bitwise (including across mesh widths 8 -> 5), and
    toggling `zero_layer_granular` across a resume is NOT a structural
    incompatibility (it is a schedule, not a layout)."""
    from moco_tpu.utils.config import config_to_dict, resume_compat_diff

    cfg_z1 = _config(zero=True, stage=1)
    cfg_layer = _config(zero=True, stage=3, layer=True)
    encoder = build_encoder(cfg_z1.moco, num_data=8)
    tx = build_optimizer(cfg_z1.optim, steps_per_epoch=4)
    sample = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
    rng = jax.random.PRNGKey(0)
    s_rep = create_state(rng, _config(zero=False), encoder, tx, sample)
    s_z1 = create_state(rng, cfg_z1, encoder, tx, sample, zero_num_data=8)  # mocolint: disable=JX003  (same seed on purpose: bitwise layout roundtrip)
    s_layer = create_state(rng, cfg_layer, encoder, tx, sample, zero_num_data=8)  # mocolint: disable=JX003  (same seed on purpose, see above)
    s_layer5 = create_state(rng, cfg_layer, encoder, tx, sample, zero_num_data=5)  # mocolint: disable=JX003  (same seed on purpose, see above)

    up = reshard_state(s_z1, live_template=s_layer, full_template=s_rep)
    for a, b in zip(jax.tree.leaves(up.params_q), jax.tree.leaves(s_layer.params_q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    narrow = reshard_state(up, live_template=s_layer5, full_template=s_rep)
    for a, b in zip(
        jax.tree.leaves(narrow.opt_state), jax.tree.leaves(s_layer5.opt_state)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    back = reshard_state(narrow, live_template=s_z1, full_template=s_rep)
    for a, b in zip(jax.tree.leaves(back.params_q), jax.tree.leaves(s_z1.params_q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # resume-compat: the flag flip produces NO structural diff entries
    saved = {"config": config_to_dict(_config(zero=True, stage=3)), "num_data": 8}
    assert resume_compat_diff(saved, cfg_layer, num_data=8) == []


def test_embedding_index_rows_survive_width_shrink():
    """The dictionary side of the elastic shrink: EmbeddingIndex rows
    carried on an 8-wide mesh land bitwise-identical on a 5-wide (then
    3-wide) mesh, the valid-count mask still hides the capacity padding
    (which differs per width), and top-k retrieval returns the same
    neighbors after the move."""
    from moco_tpu.serve.index import EmbeddingIndex

    rng = np.random.default_rng(0)
    dim, valid = 16, 50
    rows = rng.standard_normal((valid, dim)).astype(np.float32)
    rows /= np.linalg.norm(rows, axis=1, keepdims=True)
    queries = rows[:4] + 0.01 * rng.standard_normal((4, dim)).astype(np.float32)
    queries = (queries / np.linalg.norm(queries, axis=1, keepdims=True)).astype(
        np.float32
    )

    results = {}
    for n in (8, 5, 3):
        mesh = create_mesh(num_data=n, num_model=1, devices=jax.devices()[:n])
        idx = EmbeddingIndex(capacity=valid + 3, dim=dim, mesh=mesh)
        # capacity pads up to the axis width, differently per width
        assert idx.capacity % n == 0 and idx.capacity >= valid + 3
        idx.snapshot(rows)
        assert idx.count == valid  # the valid-count mask, not the padding
        stored = np.asarray(idx.rows)[:valid]
        np.testing.assert_array_equal(stored, rows)  # bitwise row preservation
        assert not np.any(np.asarray(idx.rows)[valid:])  # padding stays zero
        idx.prepare(buckets=(4,), k=5)
        idx.freeze()
        _, ids = idx.query(queries, k=5)
        assert (ids < valid).all(), f"width {n} returned padded/invalid rows: {ids}"
        results[n] = ids
    np.testing.assert_array_equal(results[8], results[5])
    np.testing.assert_array_equal(results[5], results[3])


def test_zero23_eval_gather_matches_replicated_init():
    """The eval-side one-shot gather (unshard_tree_host): a freshly
    created stage-2/3 state gathers back to exactly the replicated
    init — the invariant export/knn/lincls rely on."""
    cfg = _config(zero=True, stage=3)
    encoder = build_encoder(cfg.moco, num_data=8)
    tx = build_optimizer(cfg.optim, steps_per_epoch=4)
    sample = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
    rng = jax.random.PRNGKey(0)
    s_rep = create_state(rng, _config(zero=False), encoder, tx, sample)
    s_z = create_state(rng, cfg, encoder, tx, sample, zero_num_data=8)  # mocolint: disable=JX003  (same seed on purpose: gather must reproduce the replicated init bit-for-bit)
    shapes = full_param_shapes(cfg, encoder)
    gathered = unshard_tree_host(s_z.params_q, shapes["enc"])
    for a, b in zip(jax.tree.leaves(s_rep.params_q), jax.tree.leaves(gathered)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_param_gather_overlap_and_hygiene():
    """AsyncParamGather unit: the gather is DISPATCHED on the caller's
    thread (submit calls gather_fn — the concurrent-Execute deadlock
    contract) while the worker absorbs the injected delay fault;
    overlap accounting reads hidden when taken late, exposed when taken
    immediately; resubmit drops the poisoned lineage; close() joins the
    worker (mocolint JX011 contract)."""
    import threading as _threading
    import time as _time

    from moco_tpu.utils import faults

    dispatch_threads = []

    def gather(state):
        dispatch_threads.append(_threading.get_ident())
        return state * 2

    faults.install(f"delay@site={AsyncParamGather.FAULT_SITE}:seconds=0.05")
    try:
        g = AsyncParamGather(gather)
        g.submit(1)
        _time.sleep(0.15)  # "compute" hides the whole (delayed) gather
        assert g.take() == 2
        assert g.last_overlap is not None and g.last_overlap > 0.5
        g.submit(2)
        assert g.take() == 4  # immediate take: the delay is fully exposed
        assert g.last_overlap < 0.5
        # every dispatch ran on THIS thread, never the worker
        assert set(dispatch_threads) == {_threading.get_ident()}
        # rollback path: drop the in-flight gather, adopt the clean state
        g.submit(3)
        g.resubmit(10)
        assert g.take() == 20
    finally:
        faults.clear()

    # a post-hand-off ripen failure is an async-value error: take()
    # still returns the value (it surfaces at the consumer, as jax
    # async errors always do) and the worker SURVIVES to serve more
    class Boom:
        def block_until_ready(self):
            raise RuntimeError("boom")

    g2 = AsyncParamGather(lambda s: Boom() if s == "bad" else s)
    g2.submit("bad")
    assert isinstance(g2.take(), Boom)
    g2.submit("fine")
    assert g2.take() == "fine"
    # without any absorbed stall there is nothing to report
    assert g2.last_overlap is None
    for worker in (g, g2):
        worker.close()
        assert not worker._thread.is_alive()
    with pytest.raises(RuntimeError, match="closed"):
        g.submit(4)


@pytest.mark.slow  # full step + probe-surgery chain
def test_zero_checkpoint_restores_into_lincls(tmp_path):
    """A ZeRO-trained checkpoint must restore through the downstream
    template builders: the driver records the train-time mesh width in
    extras, and load_pretrained_backbone rebuilds the (num_data, m)
    opt-state layout from it (regression: it used to build a replicated
    template and fail the StandardRestore shape match)."""
    from moco_tpu.data.datasets import SyntheticDataset
    from moco_tpu.lincls import load_pretrained_backbone
    from moco_tpu.train import train

    config = _config(zero=True, optimizer="adamw")
    config = dataclasses.replace(
        config,
        optim=dataclasses.replace(config.optim, epochs=1),
        workdir=str(tmp_path / "pre_zero"),
        log_every=100,
    )
    dataset = SyntheticDataset(num_examples=2 * BATCH, image_size=IMG)
    train(config, dataset=dataset)

    # config=None: arch/optimizer/ZeRO layout all come from the checkpoint
    params, stats, cfg = load_pretrained_backbone(config.workdir)
    assert cfg.parallel.shard_weight_update
    assert jax.tree.leaves(params)


@pytest.mark.slow  # three driver runs (zero1 -> zero23 -> replicated resumes)
def test_zero_resume_resharded_roundtrip(tmp_path):
    """The 'compatible but resharded' resume, end to end: a zero1
    checkpoint resumes at stage 2/3 (restore into the checkpoint's own
    layout, host reshard), the stage-2/3 checkpoint resumes replicated,
    and the final stage-2/3 checkpoint loads through the eval-path
    gather in load_pretrained_backbone."""
    from moco_tpu.data.datasets import SyntheticDataset
    from moco_tpu.lincls import load_pretrained_backbone
    from moco_tpu.train import train

    base = _config(zero=True, optimizer="adamw", stage=1)
    wd = str(tmp_path / "pre_reshard")
    cfg1 = dataclasses.replace(
        base,
        optim=dataclasses.replace(base.optim, epochs=1),
        workdir=wd,
        log_every=100,
    )
    ds = SyntheticDataset(num_examples=2 * BATCH, image_size=IMG)
    train(cfg1, dataset=ds)

    # zero1 -> zero23: resume the same workdir one epoch further
    cfg2 = dataclasses.replace(
        cfg1,
        optim=dataclasses.replace(cfg1.optim, epochs=2),
        parallel=dataclasses.replace(cfg1.parallel, zero_stage=3),
    )
    train(cfg2, dataset=ds)

    # the stage-2/3 checkpoint serves the probe loader via the one-shot
    # eval gather (the layout is discovered from the checkpoint config)
    params, stats, cfg = load_pretrained_backbone(wd)
    assert cfg.parallel.zero_stage >= 2
    leaves = jax.tree.leaves(params)
    assert leaves and all(np.asarray(l).ndim >= 1 for l in leaves)

    # zero23 -> replicated: the downshard direction of the same machinery
    cfg3 = dataclasses.replace(
        cfg2,
        optim=dataclasses.replace(cfg2.optim, epochs=3),
        parallel=dataclasses.replace(
            cfg2.parallel, shard_weight_update=False, zero_stage=1
        ),
    )
    result = train(cfg3, dataset=ds)
    assert result["epoch"] == 2
