"""Compiled-Mosaic kernel tests on a REAL TPU (VERDICT r1 item 6).

The rest of the suite runs Pallas kernels in interpret mode on the CPU
mesh; Mosaic-vs-interpret divergence (block shape constraints, layout
rules) only surfaces on hardware. Run with:

    MOCO_TPU_TESTS=1 python -m pytest tests/test_tpu_kernels.py -q

Skipped automatically when no TPU backend is visible (i.e. in the
default CPU-pinned suite).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend"
)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


# The main path's own shapes (imagenet_v2: C=128, K=65536, block 2048):
# B=256 is the global batch on one chip, B=64 its per-chip share on four.
MAIN_PATH_SHAPES = pytest.mark.parametrize(
    "b,kk", [(64, 8192), (64, 65536), (256, 65536)], ids=["b64-k8k", "b64-k64k", "b256-k64k"]
)


class TestFusedInfoNCE:
    C = 128
    BLOCK = 2048

    @MAIN_PATH_SHAPES
    def test_stats_match_dense_oracle(self, b, kk):
        from moco_tpu.ops.fused_infonce import _reference, infonce_stats

        q = _rand((b, self.C), 0)
        k = _rand((b, self.C), 1)
        queue = _rand((kk, self.C), 2)
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        queue = queue / jnp.linalg.norm(queue, axis=-1, keepdims=True)

        pos, lse, above = jax.jit(
            lambda q, k, qu: infonce_stats(q, k, qu, 0.2, self.BLOCK, False)
        )(q, k, queue)
        rpos, rlse, rabove = _reference(q, k, queue, 0.2)
        np.testing.assert_allclose(np.asarray(pos), np.asarray(rpos), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse), rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(above), np.asarray(rabove))

    @MAIN_PATH_SHAPES
    def test_loss_grads_match_dense(self, b, kk):
        from moco_tpu.ops.fused_infonce import fused_infonce_loss
        from moco_tpu.ops.losses import cross_entropy, infonce_logits

        q = _rand((b, self.C), 3)
        k = _rand((b, self.C), 4)
        queue = _rand((kk, self.C), 5)
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        queue = queue / jnp.linalg.norm(queue, axis=-1, keepdims=True)

        def fused(q):
            qn = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
            loss, _ = fused_infonce_loss(qn, k, queue, 0.2, self.BLOCK, False)
            return loss

        def dense(q):
            qn = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
            logits, labels = infonce_logits(qn, k, queue, 0.2)
            return cross_entropy(logits, labels)

        lf, gf = jax.jit(jax.value_and_grad(fused))(q)
        ld, gd = jax.jit(jax.value_and_grad(dense))(q)
        np.testing.assert_allclose(float(lf), float(ld), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), rtol=1e-3, atol=1e-5)


class TestFlashAttention:
    B, H, D = 2, 4, 64

    @pytest.mark.parametrize("seq", [256, 197], ids=["block-divisible", "padded"])
    def test_forward_matches_dense(self, seq):
        from moco_tpu.ops.flash_attention import _attn_reference, flash_attention_with_lse

        q, k, v = (_rand((self.B, self.H, seq, self.D), i) for i in range(3))
        out, lse = jax.jit(
            lambda q, k, v: flash_attention_with_lse(q, k, v, None, 128, 128, False)
        )(q, k, v)
        ref_out, ref_lse = _attn_reference(q, k, v, self.D**-0.5)
        # TPU fp32 dots run as bf16 passes by default; flash and dense
        # also sum in different orders — tolerances sized accordingly
        # (exactness is enforced by the interpret-mode CPU tests).
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), rtol=2e-2, atol=5e-3)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), rtol=2e-2, atol=5e-3)

    def test_forward_bf16_at_vit_b16_shape(self):
        """ViT-B/16 as the v3 step feeds it: 12 heads of 64, 197 tokens,
        bf16 operands (the dots run in the input dtype)."""
        from moco_tpu.ops.flash_attention import _attn_reference, flash_attention_with_lse

        q, k, v = (_rand((2, 12, 197, 64), 40 + i, jnp.bfloat16) for i in range(3))
        out, lse = jax.jit(
            lambda q, k, v: flash_attention_with_lse(q, k, v, None, 128, 128, False)
        )(q, k, v)
        ref_out, ref_lse = _attn_reference(
            *(x.astype(jnp.float32) for x in (q, k, v)), 64**-0.5
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref_out), rtol=5e-2, atol=2e-2
        )
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), rtol=5e-2, atol=2e-2)

    @pytest.mark.parametrize("seq", [256, 197], ids=["block-divisible", "padded"])
    def test_grads_match_dense(self, seq):
        from moco_tpu.ops.flash_attention import _attn_reference, flash_attention

        q, k, v = (_rand((self.B, self.H, seq, self.D), 10 + i) for i in range(3))

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, None, 128, 128, False) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(_attn_reference(q, k, v, self.D**-0.5)[0] ** 2)

        g_flash = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
        g_dense = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
        for gf, gd in zip(g_flash, g_dense):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), rtol=2e-2, atol=2e-2)

    def test_vit_forward_with_flash(self):
        """The wired consumer: a ViT forward on TPU using the kernel."""
        from moco_tpu.models import create_vit

        # patch 4 on 64px -> 257 tokens: above one block, exercises the
        # padded kernel (not the short-seq dense fallback)
        vit = create_vit("vit_tiny", image_size=64, patch_size=4, use_flash_attention=True)
        vit_dense = create_vit("vit_tiny", image_size=64, patch_size=4)
        x = _rand((2, 64, 64, 3), 20)
        params = jax.jit(vit.init)(jax.random.PRNGKey(0), x)
        out_flash = jax.jit(vit.apply)(params, x)
        out_dense = jax.jit(vit_dense.apply)(params, x)
        np.testing.assert_allclose(
            np.asarray(out_flash), np.asarray(out_dense), rtol=2e-2, atol=2e-2
        )


class TestFusedIVFScan:
    """The fused IVF cell scan (serve/index.py) compiled by Mosaic: the
    kernel alone at the serving dictionary's geometry, then through
    `EmbeddingIndex` where a TPU backend selects it by default."""

    def test_cell_scores_match_dense(self):
        from moco_tpu.serve.index import _fused_cell_scores_pallas

        # K=65536, d=128 -> train_ivf's defaults: nlist=256, cell_cap=512
        m, d, nlist, cell_cap, nprobe = 8, 128, 256, 512, 8
        # unit rows, like the dictionary's: scores are cosines
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        queries = unit(_rand((m, d), 30))
        cell_rows = unit(_rand((nlist, cell_cap, d), 31))
        probes = jax.random.randint(jax.random.PRNGKey(32), (m, nprobe), 0, nlist)
        got = jax.jit(_fused_cell_scores_pallas)(queries, cell_rows, probes)
        want = jnp.einsum(
            "md,mpcd->mpc", queries, cell_rows[probes],
            precision=jax.lax.Precision.HIGHEST,
        )
        # the kernel's f32 dot runs as bf16 MXU passes by default
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)

    def test_index_fused_matches_composed(self):
        from moco_tpu.ops.losses import l2_normalize
        from moco_tpu.serve.index import EmbeddingIndex

        rng = np.random.default_rng(0)
        centers = rng.normal(size=(64, 128)).astype(np.float32)
        rows = np.repeat(centers, 128, axis=0) + 0.2 * rng.normal(
            size=(8192, 128)
        ).astype(np.float32)
        rows = np.asarray(l2_normalize(jnp.asarray(rows)))[rng.permutation(8192)]
        q = np.asarray(l2_normalize(jnp.asarray(
            rows[:32] + 0.05 * rng.normal(size=(32, 128)).astype(np.float32)
        )))
        idx = EmbeddingIndex(8192, 128)
        assert idx._fused_pallas and not idx._fused_interpret
        idx.snapshot(rows)
        idx.train_ivf(nprobe=8)
        sc, ic = idx.query(q, 5, mode="ivf")
        sf, i_f = idx.query(q, 5, mode="ivf_fused")
        np.testing.assert_allclose(sf, sc, rtol=2e-2, atol=2e-2)
        # bf16-pass scores may reorder near-ties: compare neighbour SETS
        overlap = np.mean([len(set(a) & set(b)) / 5 for a, b in zip(ic, i_f)])
        assert overlap >= 0.9, overlap


class TestExpertDispatch:
    """`models/decoder.py`'s dispatch over the compiled grouped product,
    at a cut share (2 of 16 experts held, 4096 tokens x 2 choices: rungs of
    2048 and 8192 rows). On the chip the grouped product's gradient
    leaves the rows of no group unwritten, which the CPU's does not: the
    dispatch has to keep them out of the tokens' gradient."""

    @pytest.mark.parametrize("skew,rung", [(0.0, 2048), (1.2, 8192), (30.0, 8192)], ids=str)
    def test_output_and_gradients_match_dense(self, skew, rung):
        import flax.linen as nn

        from moco_tpu.models import decoder

        class Layer(decoder.ExpertDispatch):
            @nn.compact
            def __call__(self, x, valid, chosen, weights):
                return self.routed(x, valid, chosen, weights, nn.silu)

        t, k, e, held, d, ff = 4096, 2, 16, 2, 256, 128
        x, r = _rand((t, d), 40), _rand((t, d), 41)
        logits = _rand((t, e), 42) + skew * (jnp.arange(e) == 0)
        picked, chosen = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(picked, axis=-1)
        valid = jnp.arange(t) < t - 7
        layer = Layer(experts=e, top_k=k, expert_mlp=ff, first_expert=0, experts_held=held, train=True)
        variables = layer.init(jax.random.PRNGKey(43), x, valid, chosen, weights)

        def program(params, x, weights):
            y, mut = layer.apply({"params": params, "batch_stats": variables["batch_stats"]},
                                 x, valid, chosen, weights, mutable=["batch_stats"])
            return jnp.sum(y * r), mut["batch_stats"]

        def dense(params, x, weights):
            y = 0.0
            for h in range(held):
                gate_up = jnp.matmul(x, params["experts_in"][h], precision="highest")
                hidden = nn.silu(gate_up[:, :ff]) * gate_up[:, ff:]
                share = jnp.sum(jnp.where((chosen == h) & valid[:, None], weights, 0.0), axis=1)
                y = y + share[:, None] * jnp.matmul(hidden, params["experts_out"][h], precision="highest")
            return jnp.sum(y * r)

        args = (variables["params"], x, weights)
        (loss, stats), grads = jax.jit(jax.value_and_grad(program, (0, 1, 2), has_aux=True))(*args)
        want_loss, want = jax.jit(jax.value_and_grad(dense, (0, 1, 2)))(*args)
        assert float(stats["buffer_rows"]) == rung
        # the grouped product's float32 runs as bf16 passes on the matrix unit
        assert abs(float(loss) - float(want_loss)) <= 0.03 * abs(float(want_loss))
        for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
            assert float(jnp.max(jnp.abs(got - ref))) <= 0.01 * float(jnp.max(jnp.abs(ref)))


class TestSelectiveScan:
    """The Mamba scan's two kernels compiled, against the sequential
    recurrence: 8 chunks and 2 channel blocks, a row shorter than the
    others, forward and all six gradients."""

    def test_forward_and_gradients_match_the_recurrence(self):
        from moco_tpu.ops.selective_scan import selective_scan, selective_scan_reference

        bt, length, width, states = 2, 1024, 1024, 16
        x = _rand((bt, length, width), 50)
        dt = jax.nn.softplus(_rand((bt, length, width), 51) - 2.0)
        a_log = jnp.log(jnp.broadcast_to(jnp.arange(1, states + 1, dtype=jnp.float32), (width, states)))
        b, c = _rand((bt, length, states), 52), _rand((bt, length, states), 53)
        d, gy = _rand((width,), 54), _rand((bt, length, width), 55)
        lens = jnp.asarray([length, 700], jnp.int32)
        valid = (jnp.arange(length)[None] < lens[:, None])[..., None]

        def loss(fn):
            return lambda *a: jnp.sum(jnp.where(valid, fn(*a, lens), 0.0) * gy)

        args = (x, dt, a_log, b, c, d)
        got = jax.jit(jax.value_and_grad(loss(selective_scan), tuple(range(6))))(*args)
        want = jax.jit(jax.value_and_grad(loss(selective_scan_reference), tuple(range(6))))(*args)
        assert abs(float(got[0]) - float(want[0])) <= 1e-4 * float(jnp.sum(jnp.abs(gy)))
        for name, g, r in zip(("x", "dt", "a_log", "b", "c", "d"), got[1], want[1]):
            assert float(jnp.max(jnp.abs(g - r))) <= 1e-3 * float(jnp.max(jnp.abs(r))), name


class TestDiffAttention:
    """The causal kernels as the differential layers call them: 64-wide q
    and k, 128-wide v, two query heads a key head, with and without the
    512-key window, under their own names."""

    @pytest.mark.parametrize("window", [None, 512], ids=["full", "window"])
    def test_kernels_match_dense(self, window):
        from moco_tpu.ops.flash_attention import _causal_attn_reference, causal_flash_attention

        q = _rand((1, 4, 2048, 64), 60, jnp.bfloat16)
        k = _rand((1, 2, 2048, 64), 61, jnp.bfloat16)
        v = _rand((1, 2, 2048, 128), 62, jnp.bfloat16)
        g = _rand((1, 4, 2048, 128), 63)
        lens = jnp.asarray([1900], jnp.int32)

        def loss(fn, **kw):
            return lambda q, k, v: jnp.sum(fn(q, k, v, lens, 0.125, **kw).astype(jnp.float32)[..., :1900, :] * g[..., :1900, :])

        got = jax.jit(jax.value_and_grad(loss(causal_flash_attention, window=window, name="diff_attention"), (0, 1, 2)))
        want = jax.jit(jax.value_and_grad(loss(_causal_attn_reference, window=window), (0, 1, 2)))
        (lg, gg), (lw, gw) = got(q, k, v), want(q, k, v)
        assert abs(float(lg) - float(lw)) <= 0.02 * float(jnp.sum(jnp.abs(g)))
        for a, b in zip(gg, gw):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            assert float(jnp.max(jnp.abs(a - b))) <= 0.05 * float(jnp.max(jnp.abs(b)))
        text = jax.jit(got).lower(q, k, v).compile().as_text()
        assert all(f"diff_attention_{w}" in text for w in ("fwd", "dq", "dkv"))


class TestColourJitter:
    """The v2 colour stage's kernel compiled, against the batched jnp
    composition on the same keys, at one R50 view (256 images, 224 px)."""

    @pytest.mark.parametrize("hue,apply_prob", [(0.1, 0.8), (0.4, 1.0), (0.0, 0.8)])
    def test_stage_matches_jitter_then_grayscale(self, hue, apply_prob):
        from moco_tpu.data.augment import color_jitter, colour_stage, random_grayscale

        k_jit, k_gray = jax.random.split(jax.random.PRNGKey(2147483659))
        x = jax.random.uniform(jax.random.PRNGKey(70), (256, 224, 224, 3))
        jitter = (0.4, 0.4, 0.4, hue)
        got = jax.jit(lambda x: colour_stage(k_jit, k_gray, x, jitter, apply_prob, 0.2))(x)
        want = jax.jit(
            # the kernel's own keys on purpose: the same draws, the batched ops' arithmetic
            lambda x: random_grayscale(k_gray, color_jitter(k_jit, x, *jitter, apply_prob=apply_prob), 0.2)  # mocolint: disable=JX003
        )(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=0)
