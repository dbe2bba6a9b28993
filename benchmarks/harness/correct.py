"""`correct`: the system against the plain references, outside the window.

Train cells: on the weights the timed run starts from (the program's
`create_state` from `config.seed`, one jitted call) and the sample the
family's input module draws from `--seed`
(`benchmarks/inputs/<name>.py::correct_views`), the system's
own modules (`build_encoder`/`build_predictor` in the configuration's
compute dtype, its `l2_normalize`, its loss: the fused Pallas InfoNCE
where the step would use it) against `benchmarks/reference/` in float32
at highest precision: the normalised query embeddings, the loss, and the
gradient of the loss with respect to the query encoder.

Serve cells: the embeddings the replica returned over HTTP against the
reference's evaluation-mode forward of the key encoder, and the
`/neighbors` ids against a numpy exact top-k over the index rows.

The family is two modules the caller hands in (`Manifest.family`): its
plain reference, which also states the tolerances that are its own
(`TOLERANCES`; the defaults are below, each with its reason) and, where
its step freezes some leaves, which (`trained_gradient`), and its input
module. Nothing here names a family, a modality or a leaf.
"""

from __future__ import annotations

import numpy as np

# -- tolerances --------------------------------------------------------
# The system computes in bfloat16 (8 significant bits, unit round-off
# 2^-9 ~ 0.002) with float32 accumulation, parameters and statistics;
# the reference in float32 at highest precision. Measured on the chip
# (PERF.md section 6, PR 24) the figures below sit at a few times the
# observed error and well under what one step lower in precision (fp8,
# unit round-off 2^-4) would give, which is ~16x the bf16 error.
#
# embeddings (`emb_centred_rel`, stated by every family's reference
# module): ||sys - ref||_F over ||ref - mean row of ref||_F, i.e. the
# error relative to how much the sample's embeddings differ from one
# another (a plain cosine is ~1 for any two encoders whose outputs
# cluster, so it would prove nothing). How deep the encoder is and what
# it normalises over decide the size of the error, so the limit is the
# family's: there is no default.
# loss: absolute, on a loss of order log(1+K) ~ 11 (v2) or 2T*2*log(B) ~ 3 (v3)
# gradient of the loss w.r.t. every query-encoder parameter, flattened:
# direction and length against the float32 reference
DEFAULT_TOLERANCES = {"loss_abs": 0.02, "grad_cosine_min": 0.98, "grad_norm_ratio": (0.9, 1.1)}
# /neighbors: an id is right if its exact score is within this of the
# k-th best exact score (ties and bf16 scoring reorder near-equal rows)
NEIGHBOR_SCORE_TOL = 4e-3


def tolerances(ref) -> dict:
    """The defaults with the family's own over them."""
    tol = {**DEFAULT_TOLERANCES, **ref.TOLERANCES}
    if "emb_centred_rel" not in tol:
        raise ValueError(f"{ref.__name__} states no `emb_centred_rel` in its TOLERANCES")
    return tol


def holds(entry: dict) -> bool:
    """An entry of `compared`: a finite value inside its limit(s)."""
    v = entry["value"]
    return bool(
        v is not None and np.isfinite(v)
        and entry.get("at_least", -np.inf) <= v <= entry.get("at_most", np.inf)
    )


def compared(check: dict, ref) -> dict:
    """Every number a check compares, beside its limit, under short plain
    names: what decides the check's `ok`, and what a result line carries
    as its last key."""
    tol = tolerances(ref)
    out = {}
    if "loss_system" in check:
        out["loss_abs_diff"] = {
            "value": abs(check["loss_system"] - check["loss_reference"]), "at_most": tol["loss_abs"],
        }
    for key, value in check.items():
        if key.endswith("emb_centred_rel_error"):
            out[key] = {"value": value, "at_most": tol["emb_centred_rel"]}
        elif key.endswith(("neighbor_worst_shortfall", "neighbor_score_error")):
            out[key] = {"value": value, "at_most": NEIGHBOR_SCORE_TOL}
        elif key == "grad_cosine":
            out[key] = {"value": value, "at_least": tol["grad_cosine_min"]}
        elif key == "grad_norm_ratio":
            lo, hi = tol["grad_norm_ratio"]
            out[key] = {"value": value, "at_least": lo, "at_most": hi}
    return out


def centred_rel_error(sys_emb: np.ndarray, ref_emb: np.ndarray) -> float:
    spread = np.linalg.norm(ref_emb - ref_emb.mean(axis=0, keepdims=True))
    return float(np.linalg.norm(sys_emb - ref_emb) / max(spread, 1e-12))


def _flat(tree) -> np.ndarray:
    import jax

    return np.concatenate([np.asarray(x, np.float64).ravel() for x in jax.tree.leaves(tree)])


def seeded_state(config, seed: int, inputs):
    """The program's `create_state` as one jitted call from the seed: the
    weights a train run with this seed starts from, and the checkpoint a
    serve run boots. `inputs`: the family's input module."""
    import jax

    from moco_tpu.core import build_encoder, build_predictor, create_state
    from moco_tpu.utils.schedules import build_optimizer

    encoder, predictor = build_encoder(config.moco), build_predictor(config.moco)
    tx = build_optimizer(config.optim, steps_per_epoch=1)
    sample = inputs.sample_input(config)
    init_rng, _ = jax.random.split(jax.random.PRNGKey(int(seed)))
    make = jax.jit(lambda rng: create_state(rng, config, encoder, tx, sample, predictor=predictor))
    return make(init_rng), encoder, predictor


def check_train(config, ref, inputs, seed: int, sample_n: int, gradient: bool,
                control=None) -> dict:
    """`ref`, `inputs`: the family's reference and input modules.
    `control`: a dtype one step under the configuration's compute dtype
    (float8_e4m3fn or int8 for bfloat16). The reference then stands in the
    system's place with every matmul's and convolution's operands rounded
    to it (`reference/common.py::operands_rounded_to`), and the check has
    to come out not `ok`: the limits sit under what that reads. No run of
    the benchmark passes it; `tests/test_control.py` and the chip readings
    in PERF.md do."""
    import jax
    import jax.numpy as jnp

    from moco_tpu.ops.losses import cross_entropy, infonce_logits, l2_normalize

    tolerances(ref)  # a family that states no embedding limit is refused before any work
    # the weights the timed run starts from (`config.seed`: `--seed`, or the configuration
    # file's `weights_seed`); `seed` draws the sample
    state, encoder, predictor = seeded_state(config, config.seed, inputs)
    cfg = config.moco
    x1, x2 = inputs.correct_views(seed, sample_n, config)

    def apply(module, params, stats, x):
        out, _ = module.apply(
            {"params": params, "batch_stats": stats}, x, train=True, mutable=["batch_stats"]
        )
        return out

    fused = False
    if cfg.num_negatives and jax.default_backend() == "tpu":
        from moco_tpu.ops.fused_infonce import DEFAULT_BLOCK_K

        fused = cfg.fused_infonce is not False and cfg.num_negatives % DEFAULT_BLOCK_K == 0

    # everything the two sides read is an ARGUMENT of the jitted function:
    # closed over, the weights would be baked into the program as constants
    # (hundreds of MB to compile and to key the cache on)
    def sys_loss(trainable, c):
        x1, x2 = c["x1"], c["x2"]
        if cfg.v3:
            x = jnp.concatenate([x1, x2], axis=0)
            feats = apply(encoder, trainable["enc"], c["stats_q"], x)
            preds = apply(predictor, trainable["pred"], c["stats_pred"], feats)
            q1, q2 = jnp.split(l2_normalize(preds), 2, axis=0)
            keys = apply(encoder, c["params_k"], c["stats_k"], x)
            k1, k2 = jnp.split(jax.lax.stop_gradient(l2_normalize(keys)), 2, axis=0)
            labels = jnp.arange(sample_n, dtype=jnp.int32)
            ctr = lambda q, k: 2.0 * cfg.temperature * cross_entropy(
                q @ k.T / cfg.temperature, labels
            )
            return ctr(q1, k2) + ctr(q2, k1), q1
        q = l2_normalize(apply(encoder, trainable["enc"], c["stats_q"], x1))
        k = l2_normalize(apply(encoder, c["params_k"], c["stats_k"], x2))
        if fused:
            from moco_tpu.ops.fused_infonce import fused_infonce_loss

            loss, _ = fused_infonce_loss(q, k, c["queue"], cfg.temperature)
            return loss, q
        logits, labels = infonce_logits(q, k, c["queue"], cfg.temperature)
        return cross_entropy(logits, labels), q

    def ref_loss(trainable, c):
        if cfg.v3:
            return ref.loss_and_embeddings(
                trainable["enc"], c["stats_q"], trainable["pred"], c["stats_pred"],
                c["params_k"], c["stats_k"], c["x1"], c["x2"], cfg.temperature,
            )
        return ref.loss_and_embeddings(
            trainable["enc"], c["stats_q"], c["params_k"], c["stats_k"],
            c["queue"], c["x1"], c["x2"], cfg.temperature,
        )

    trainable = {"enc": state.params_q, "pred": state.params_pred}
    consts = {
        "x1": x1, "x2": x2, "queue": state.queue, "params_k": state.params_k,
        "stats_q": state.batch_stats_q, "stats_k": state.batch_stats_k,
        "stats_pred": state.batch_stats_pred,
    }
    def evaluate(f):
        if gradient:
            return jax.jit(jax.value_and_grad(f, has_aux=True))(trainable, consts)
        return jax.jit(f)(trainable, consts), None

    if control is None:
        (loss_s, q_s), g_s = evaluate(sys_loss)
    else:
        from benchmarks.reference.common import operands_rounded_to

        with operands_rounded_to(control):  # a function of its own: jit caches traces by function
            (loss_s, q_s), g_s = evaluate(lambda t, c: ref_loss(t, c))
    (loss_r, q_r), g_r = evaluate(ref_loss)
    q_s, q_r = np.asarray(q_s, np.float64), np.asarray(q_r, np.float64)
    out = {
        "fused_infonce": bool(fused),
        "loss_system": float(loss_s),
        "loss_reference": float(loss_r),
        "emb_centred_rel_error": centred_rel_error(q_s, q_r),
        "emb_min_cosine": float(np.min(np.sum(q_s * q_r, axis=1))),
    }
    if gradient:
        # a family whose step leaves some leaves untrained says which
        # (`trained_gradient`): they are no part of what the system trains on
        trained = getattr(ref, "trained_gradient", lambda g, cfg: g)
        g_s, g_r = trained(g_s, cfg), trained(g_r, cfg)
        a, b = _flat(g_s), _flat(g_r)
        out["grad_cosine"] = float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))
        out["grad_norm_ratio"] = float(np.linalg.norm(a) / max(np.linalg.norm(b), 1e-30))
    out["ok"] = all(holds(c) for c in compared(out, ref).values())
    return out


def check_serve(state, config, ref, inputs, seed: int, sample: dict, k: int) -> dict:
    """`sample`: {route: answer JSON} for the rows of `inputs.correct_rows`;
    `ref`, `inputs`: the family's reference and input modules."""
    import jax

    out: dict = {}
    ok = True  # what is no number: the answers' shape, ids that repeat
    rows = np.asarray(state.queue, np.float32)
    emb_ref = None
    for route, answer in sorted(sample.items()):
        emb = np.asarray(answer["embedding"], np.float32)
        if emb_ref is None:
            rows_in = inputs.correct_rows(seed, emb.shape[0], config)
            fwd = jax.jit(lambda p, s, x: ref.embed(p, s, x))
            emb_ref = np.asarray(fwd(state.params_k, state.batch_stats_k, rows_in), np.float64)
        err = centred_rel_error(emb.astype(np.float64), emb_ref)
        out[f"{route}:emb_centred_rel_error"] = err
        out[f"{route}:emb_min_cosine"] = float(np.min(np.sum(emb * emb_ref, axis=1)))
        if "indices" in answer:
            ids = np.asarray(answer["indices"])
            exact = emb @ rows.T  # scored on what the replica returned
            kth = np.sort(exact, axis=1)[:, -k]
            picked = np.take_along_axis(exact, ids, axis=1)
            distinct = all(len(set(r)) == len(r) for r in ids.tolist())
            worst = float(np.max(kth[:, None] - picked))
            out[f"{route}:neighbor_worst_shortfall"] = worst
            score_err = float(np.max(np.abs(np.asarray(answer["scores"]) - picked)))
            out[f"{route}:neighbor_score_error"] = score_err
            ok = ok and ids.shape == (emb.shape[0], k) and distinct
    out["ok"] = bool(ok) and all(holds(c) for c in compared(out, ref).values())
    return out
