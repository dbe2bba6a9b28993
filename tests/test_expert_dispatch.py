"""The expert dispatch's ladder of buffer sizes (`models/decoder.py`): each
rung gives what the worst-case program and the plain references give, the
rung follows the step's own count and drops nothing, the uncut layer is one
program, and inside a bounded rung nothing floating-point has one row an
assignment. CPU, tiny widths; 1024 tokens x 2 choices and a share of 3
experts in 16 give a bounded rung of two row tiles under the worst case."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.reference import joyai_moco_v2, smallthinker_moco_v2
from moco_tpu.models import decoder, joyai, smallthinker

TOKENS, PADDED, HIDDEN, EXPERT_MLP, EXPERTS, TOP_K, FIRST, HELD = 1024, 5, 32, 16, 16, 2, 3, 3
ROWS = TOKENS * TOP_K
LADDER = (1024, 2048)
FAMILIES = ("joyai", "smallthinker")
SIZES = {"top_k": TOP_K, "routed_scale": 2.5}
# `steered`: logits (or, for the first family, biases) put every valid token
# on this many of the held experts: 0 leaves about the even share of 384
# assignments, 1 passes twice it, 2 makes every assignment live


def _layer(family, held=HELD, first=FIRST, experts=EXPERTS):
    common = dict(experts=experts, top_k=TOP_K, expert_mlp=EXPERT_MLP, first_expert=first,
                  experts_held=held, train=True)
    if family == "joyai":
        return joyai.ExpertLayer(shared_experts=0, routed_scale=SIZES["routed_scale"], **common)
    return smallthinker.ExpertLayer(**common)


def _case(family, steered, held=HELD, first=FIRST, experts=EXPERTS, tokens=TOKENS):
    """(layer, variables, the call's arguments beside x, x, a cotangent)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (tokens, HIDDEN))
    valid = jnp.arange(tokens) < tokens - PADDED
    push = 30.0 * (jnp.arange(experts) - first < steered) * (jnp.arange(experts) >= first)
    layer = _layer(family, held, first, experts)
    if family == "joyai":
        variables = layer.init(ks[1], x, valid)
        variables = {"params": variables["params"],
                     "batch_stats": {**variables["batch_stats"], "bias": push}}
        rest = ()
    else:
        rest = (jax.random.normal(ks[2], (tokens, experts)) + push,)
        variables = layer.init(ks[1], x, valid, *rest)
    return layer, variables, valid, rest, x, jax.random.normal(ks[3], x.shape)


def _live(family, variables, valid, rest, x):
    """The valid tokens' choices that land on the held experts, counted
    from the family's routing function alone."""
    if family == "joyai":
        scores = nn.sigmoid(jnp.matmul(x, variables["params"]["router"], precision=lax.Precision.HIGHEST))
        chosen, _ = joyai.route(scores, variables["batch_stats"]["bias"], TOP_K, 1.0)
    else:
        chosen, _ = smallthinker.route(*rest, TOP_K)
    return int(jnp.sum(valid[:, None] & ((chosen - FIRST) % EXPERTS < HELD)))


def _program(layer, variables, valid, r):
    """(loss, what the layer left in batch_stats), differentiable in the
    parameters, the tokens and (second family) the router's logits."""
    def loss(params, x, *rest):
        y, mut = layer.apply({"params": params, "batch_stats": variables["batch_stats"]},
                             x, valid, *rest, mutable=["batch_stats"])
        return jnp.sum(y * r), (y, mut["batch_stats"])
    return loss


def _reference(family, variables, r):
    length = TOKENS - PADDED

    def loss(params, x, *rest):
        if family == "joyai":
            y, _ = joyai_moco_v2._experts(x, params, variables["batch_stats"], length, SIZES)
        else:
            y = smallthinker_moco_v2._experts(x, *rest, params, variables["batch_stats"], length, SIZES)
        return jnp.sum(y * r), y
    return loss


def _assert_close(got, want, atol=2e-5):
    flat_g, flat_w = jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
    assert len(flat_g) == len(flat_w)
    for (path, a), b in zip(flat_g, flat_w):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        np.testing.assert_allclose(a / scale, b / scale, atol=atol, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("steered", [0, 1, 2], ids=["even_share", "past_twice_it", "every_row_live"])
@pytest.mark.parametrize("family", FAMILIES)
def test_every_rung_is_the_worst_case_program_and_the_reference(family, steered, monkeypatch):
    """A step whose live count fits twice the even share runs on the bounded
    rung, one that passes it on the worst case: output and gradients (tokens, both stacked weights,
    the routing weights through the router or its logits) are the plain
    reference's and the one worst-case program's, every assignment is in
    `load`, and `moe/bounded_share`, `moe/buffer_rows` read the rung."""
    assert decoder.rung_ladder(ROWS, EXPERTS, HELD) == LADDER
    layer, variables, valid, rest, x, r = _case(family, steered)
    args = (variables["params"], x, *rest)
    wrt = tuple(range(len(args)))
    grad = lambda f: jax.jit(jax.value_and_grad(f, wrt, has_aux=True))
    (_, (y, stats)), grads = grad(_program(layer, variables, valid, r))(*args)

    live = _live(family, variables, valid, rest, x)
    rung = min(steered, 1)
    assert (0, *LADDER)[rung] < live <= LADDER[rung]
    assert float(jnp.sum(stats["load"])) == live  # nothing dropped
    assert float(stats["buffer_rows"]) == LADDER[rung]
    metrics = decoder.routing_metrics({"layer_0": {"moe": stats}, "layer_1": {"moe": stats}})
    assert float(metrics["moe/buffer_rows"]) == LADDER[rung]
    assert float(metrics["moe/bounded_share"]) == float(rung == 0)

    (_, y_ref), grads_ref = grad(_reference(family, variables, r))(*args)
    assert float(jnp.max(jnp.abs(y_ref))) > 0.1
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_array_equal(y[TOKENS - PADDED :], 0.0)  # padding is routed nowhere
    _assert_close(grads, grads_ref)

    monkeypatch.setattr(decoder, "rung_ladder", lambda rows, experts, held: (rows,))
    (_, (y_worst, stats_worst)), grads_worst = grad(_program(layer, variables, valid, r))(*args)
    assert float(stats_worst["buffer_rows"]) == ROWS and float(stats_worst["bounded"]) == 0.0
    np.testing.assert_allclose(y, y_worst, atol=1e-6)
    _assert_close(grads, grads_worst, atol=2e-6)


@pytest.mark.parametrize(
    "rows,experts,held,ladder",
    [(131072, 256, 16, (16384, 131072)), (98304, 64, 8, (24576, 98304)),
     (98304, 64, 64, (98304,)), (96, 8, 2, (96,)), (4096, 8, 1, (1024, 4096)),
     (4096, 8, 4, (4096,)), (6000, 16, 3, (2560, 6000))],
    ids=["joyai_flash_ep16", "smallthinker_ep8", "uncut", "tiny", "an_eighth", "a_half", "ragged"],
)
def test_the_ladder_is_twice_the_even_share_in_whole_tiles_and_the_worst_case(rows, experts, held, ladder):
    assert decoder.rung_ladder(rows, experts, held) == ladder


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            if hasattr(v, "eqns"):
                yield v
            elif hasattr(getattr(v, "jaxpr", None), "eqns"):
                yield v.jaxpr


def _conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in _subjaxprs(eqn):
            yield from _conds(sub)


def _one_row_an_assignment(aval, rows):
    return (getattr(aval, "ndim", 0) >= 2 and aval.shape[0] == rows
            and jnp.issubdtype(aval.dtype, jnp.floating))


def _assignment_rows(jaxpr, rows):
    """Every floating-point matrix with `rows` rows that `jaxpr` reads,
    makes or returns, the programs it calls included."""
    found = []
    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            if _one_row_an_assignment(var.aval, rows):
                found.append(f"{eqn.primitive.name}: {var.aval.str_short()}")
        for sub in _subjaxprs(eqn):
            found += _assignment_rows(sub, rows)
    return found


def _bounded_rungs_hold(jaxpr, rows):
    """The m-row floating-point matrices inside every conditional's bounded
    branches (all but its last) and among what the conditional hands out."""
    conds = list(_conds(jaxpr))
    found = []
    for eqn in conds:
        for branch in eqn.params["branches"][:-1]:
            found += _assignment_rows(branch.jaxpr, rows)
        found += [f"cond result: {v.aval.str_short()}" for v in eqn.outvars
                  if _one_row_an_assignment(v.aval, rows)]
    return conds, found


@pytest.mark.parametrize("family", FAMILIES)
def test_no_buffer_of_a_bounded_rung_has_a_row_an_assignment(family):
    """The traced value-and-gradient of a cut layer: a conditional of two
    branches in the forward pass and one in the backward pass, and inside
    their bounded branches, and among what they return, no floating-point
    matrix of tokens x top_k rows (the worst-case branch has them)."""
    layer, variables, valid, rest, x, r = _case(family, 0)
    args = (variables["params"], x, *rest)
    f = _program(layer, variables, valid, r)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(f, tuple(range(len(args))), has_aux=True))(*args)
    conds, found = _bounded_rungs_hold(jaxpr.jaxpr, ROWS)
    assert [len(c.params["branches"]) for c in conds] == [2, 2]
    assert found == []
    worst = [b for c in conds for b in _assignment_rows(c.params["branches"][-1].jaxpr, ROWS)]
    assert any(f"[{ROWS},{HIDDEN}]" in b for b in worst)


def test_a_conditional_differentiated_as_it_stands_is_what_the_count_catches():
    """The same rungs under `lax.switch` without the custom rule: the
    forward conditional hands out the worst case's residuals from every
    branch, the bounded ones' filled with zeros, and the count finds them."""
    layer, variables, valid, _, x, r = _case("smallthinker", 0)
    p = variables["params"]
    key = jnp.where(valid[:, None] & (jnp.arange(TOP_K) < 1), 0, HELD).reshape(-1)
    order, sizes = jnp.argsort(key, stable=True), jnp.bincount(key, length=HELD + 1)[:HELD]
    weights = jnp.full((TOKENS, TOP_K), 0.5)

    def plain(x, w_in, w_out, weights):
        rungs = [lambda *a, rows=rows: decoder._rung(rows, TOP_K, nn.relu, *a) for rows in LADDER]
        y = lax.switch(decoder.rung_taken(LADDER, sizes), rungs, x, w_in, w_out, weights, order, sizes)
        return jnp.sum(y * r)

    jaxpr = jax.make_jaxpr(jax.grad(plain, (0, 1, 2, 3)))(x, p["experts_in"], p["experts_out"], weights)
    _, found = _bounded_rungs_hold(jaxpr.jaxpr, ROWS)
    assert any("cond result" in f for f in found) and any("broadcast_in_dim" in f for f in found)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_uncut_layer_is_one_program_with_no_conditional(family):
    """Every expert held: twice the even share is already the worst case,
    so there is nothing to choose and the layer lowers without a branch;
    its counters say worst case."""
    layer, variables, valid, rest, x, r = _case(family, 0, held=8, first=0, experts=8, tokens=64)
    args = (variables["params"], x, *rest)
    f = _program(layer, variables, valid, r)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(f, tuple(range(len(args))), has_aux=True))(*args)
    assert list(_conds(jaxpr.jaxpr)) == []
    assert "stablehlo.case" not in jax.jit(f).lower(*args).as_text()
    _, (_, stats) = jax.jit(f)(*args)
    assert float(stats["buffer_rows"]) == 64 * TOP_K and float(stats["bounded"]) == 0.0
    assert float(jnp.sum(stats["load"])) == (64 - PADDED) * TOP_K
