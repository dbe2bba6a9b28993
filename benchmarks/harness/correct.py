"""`correct`: the system against the plain references, outside the window.

Train cells: on weights made from the seed (the program's `create_state`,
one jitted call) and a seeded sample of structured images, the system's
own modules (`build_encoder`/`build_predictor` in the configuration's
compute dtype, its `l2_normalize`, its loss: the fused Pallas InfoNCE
where the step would use it) against `benchmarks/reference/` in float32
at highest precision: the normalised query embeddings, the loss, and the
gradient of the loss with respect to the query encoder.

Serve cells: the embeddings the replica returned over HTTP against the
reference's evaluation-mode forward of the key encoder, and the
`/neighbors` ids against a numpy exact top-k over the index rows.

Tolerances sit beside each comparison with their reason.
"""

from __future__ import annotations

import importlib

import numpy as np

# -- tolerances --------------------------------------------------------
# The system computes in bfloat16 (8 significant bits, unit round-off
# 2^-9 ~ 0.002) with float32 accumulation, parameters and statistics;
# the reference in float32 at highest precision. Measured on the chip
# (PERF.md section 6, PR 24) the figures below sit at a few times the
# observed error and well under what one step lower in precision (fp8,
# unit round-off 2^-4) would give, which is ~16x the bf16 error.
#
# embeddings: ||sys - ref||_F over ||ref - mean row of ref||_F, i.e. the
# error relative to how much the sample's embeddings differ from one
# another (a plain cosine is ~1 for any two encoders whose outputs
# cluster, so it would prove nothing).
# Measured on the chip (my chip runs, PR 24): ResNet-50 in training mode
# 0.25-0.31 over five seeds (53 convolutions deep, BN over 32 rows, at a
# random init where the sample's embeddings differ little from one
# another); ViT-B/16 0.068-0.070. Each bound is ~1.5x the worst seen.
EMB_CENTRED_REL_TOL = {"resnet_moco_v2": 0.45, "vit_moco_v3": 0.12}
# loss: absolute, on a loss of order log(1+K) ~ 11 (v2) or 2T*2*log(B) ~ 3 (v3)
LOSS_ABS_TOL = 0.02
# gradient of the loss w.r.t. every query-encoder parameter, flattened:
# direction and length against the float32 reference
GRAD_COSINE_MIN = 0.98
GRAD_NORM_RATIO = (0.9, 1.1)
# /neighbors: an id is right if its exact score is within this of the
# k-th best exact score (ties and bf16 scoring reorder near-equal rows)
NEIGHBOR_SCORE_TOL = 4e-3


def centred_rel_error(sys_emb: np.ndarray, ref_emb: np.ndarray) -> float:
    spread = np.linalg.norm(ref_emb - ref_emb.mean(axis=0, keepdims=True))
    return float(np.linalg.norm(sys_emb - ref_emb) / max(spread, 1e-12))


def _flat(tree) -> np.ndarray:
    import jax

    return np.concatenate([np.asarray(x, np.float64).ravel() for x in jax.tree.leaves(tree)])


def seeded_state(config, seed: int):
    """The program's `create_state` as one jitted call from the seed: the
    weights a train run with this seed starts from, and the checkpoint a
    serve run boots."""
    import jax
    import jax.numpy as jnp

    from moco_tpu.core import build_encoder, build_predictor, create_state
    from moco_tpu.utils.schedules import build_optimizer

    encoder, predictor = build_encoder(config.moco), build_predictor(config.moco)
    tx = build_optimizer(config.optim, steps_per_epoch=1)
    size = config.data.image_size
    sample = jnp.zeros((1, size, size, 3), jnp.float32)
    init_rng, _ = jax.random.split(jax.random.PRNGKey(int(seed)))
    make = jax.jit(lambda rng: create_state(rng, config, encoder, tx, sample, predictor=predictor))
    return make(init_rng), encoder, predictor


def check_train(config, reference: str, seed: int, sample_n: int, gradient: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.loadgen.schedule import structured_images
    from benchmarks.reference.common import preprocess
    from moco_tpu.ops.losses import cross_entropy, infonce_logits, l2_normalize

    ref = importlib.import_module(f"benchmarks.reference.{reference}")
    state, encoder, predictor = seeded_state(config, seed)
    cfg = config.moco
    imgs = structured_images(seed, 2 * sample_n, config.data.image_size)
    x1, x2 = preprocess(imgs[:sample_n]), preprocess(imgs[sample_n:])

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

    (loss_s, q_s), g_s = evaluate(sys_loss)
    (loss_r, q_r), g_r = evaluate(ref_loss)
    q_s, q_r = np.asarray(q_s, np.float64), np.asarray(q_r, np.float64)
    out = {
        "fused_infonce": bool(fused),
        "loss_system": float(loss_s),
        "loss_reference": float(loss_r),
        "emb_centred_rel_error": centred_rel_error(q_s, q_r),
        "emb_min_cosine": float(np.min(np.sum(q_s * q_r, axis=1))),
    }
    ok = (
        np.isfinite(out["loss_system"])
        and abs(out["loss_system"] - out["loss_reference"]) <= LOSS_ABS_TOL
        and out["emb_centred_rel_error"] <= EMB_CENTRED_REL_TOL[reference]
    )
    if gradient:
        if cfg.v3 and cfg.freeze_patch_embed:
            # the step zeroes this gradient (the v3 stability trick), so
            # it is no part of what the system trains on
            for g in (g_s, g_r):
                g["enc"]["backbone"]["patch_embed"] = jax.tree.map(
                    jnp.zeros_like, g["enc"]["backbone"]["patch_embed"]
                )
        a, b = _flat(g_s), _flat(g_r)
        out["grad_cosine"] = float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))
        out["grad_norm_ratio"] = float(np.linalg.norm(a) / max(np.linalg.norm(b), 1e-30))
        ok = ok and out["grad_cosine"] >= GRAD_COSINE_MIN and (
            GRAD_NORM_RATIO[0] <= out["grad_norm_ratio"] <= GRAD_NORM_RATIO[1]
        )
    out["ok"] = bool(ok)
    return out


def check_serve(state, config, reference: str, seed: int, sample: dict, k: int) -> dict:
    """`sample`: {route: answer JSON} for the structured sample images."""
    import jax

    from benchmarks.loadgen.schedule import structured_images
    from benchmarks.reference.common import preprocess

    ref = importlib.import_module(f"benchmarks.reference.{reference}")
    out: dict = {}
    ok = True
    rows = np.asarray(state.queue, np.float32)
    emb_ref = None
    for route, answer in sorted(sample.items()):
        emb = np.asarray(answer["embedding"], np.float32)
        if emb_ref is None:
            imgs = structured_images(seed, emb.shape[0], config.data.image_size)
            fwd = jax.jit(lambda p, s, x: ref.embed(p, s, x))
            emb_ref = np.asarray(
                fwd(state.params_k, state.batch_stats_k, preprocess(imgs)), np.float64
            )
        err = centred_rel_error(emb.astype(np.float64), emb_ref)
        out[f"{route}:emb_centred_rel_error"] = err
        out[f"{route}:emb_min_cosine"] = float(np.min(np.sum(emb * emb_ref, axis=1)))
        ok = ok and np.isfinite(emb).all() and err <= EMB_CENTRED_REL_TOL[reference]
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
            ok = ok and ids.shape == (emb.shape[0], k) and distinct and (
                worst <= NEIGHBOR_SCORE_TOL and score_err <= NEIGHBOR_SCORE_TOL
            )
    out["ok"] = bool(ok)
    return out
