"""Input module: token sequences, `config.data.seq_len` int32 ids a row
and the row's length, as `{"ids": (N, S), "lengths": (N,)}`.

What the harness needs of a modality, and nothing else (see
`inputs/images.py`). The traffic file's keys that belong to this input are
read here and nowhere in the harness: `pool_documents` (how many seeded
documents the pool holds), `doc_len_median` / `doc_len_sigma` /
`doc_len_min` / `doc_len_max` (document lengths: log-normal, clipped) and
`zipf_exponent` (token ids: rank r of the held vocabulary rows drawn with
probability proportional to r^-exponent, so that routing is uneven) and,
in one cell's file as a stop-gap, `rank_seed` (which id has which rank is a
permutation drawn from it and no longer from `--seed`: every run's
commonest tokens then read the same embedding rows; the documents' lengths
and the ranks they hold stay `--seed`'s. The two windows cut from a
document are not drawn here: the program's pipeline draws them, and the
order it reads the pool in, from `cfg.seed`). The
vocabulary is the rows the configuration holds (`moco.lm_vocab_rows`): a
sliced vocabulary is a smaller vocabulary, and ids are drawn from it.
"""

from __future__ import annotations

import numpy as np

# what `__len__` reports: a corpus of 40 000 documents, so that at the
# configuration's 2 rows a step an epoch is 20 000 steps: the preset's one
# warm-up epoch of 25 is then the 20 000 warm-up steps of 500 000 its recipe
# states (and an epoch is longer than any run)
CORPUS_DOCUMENTS = 40_000


class TokenPool:
    """A seeded pool of documents in memory (`benchmarks/data/pool.py`
    says why a pool): `load_tokens(i)` hands out document `i mod n`, a 1-D
    int32 array. The token protocol of `moco_tpu.data.datasets`."""

    def __init__(self, seed: int, documents: int, vocab: int, traffic: dict):
        rng = np.random.default_rng(int(seed))
        lengths = np.exp(rng.normal(np.log(traffic["doc_len_median"]), traffic["doc_len_sigma"],
                                    int(documents)))
        lengths = np.clip(lengths, traffic["doc_len_min"], traffic["doc_len_max"]).astype(np.int64)
        # Zipf over a finite vocabulary by the inverse of its cumulative
        # distribution; which id has which rank is a seeded permutation
        weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(traffic["zipf_exponent"])
        cdf = np.cumsum(weights / weights.sum())
        # (a traffic file with `rank_seed` fixes which rows the Zipf head reads; its `assumed` says why)
        perm_rng = np.random.default_rng(int(traffic["rank_seed"])) if "rank_seed" in traffic else rng
        id_of_rank = perm_rng.permutation(vocab).astype(np.int32)
        ranks = np.searchsorted(cdf, rng.random(int(lengths.sum())), side="left")
        tokens = id_of_rank[np.minimum(ranks, vocab - 1)]
        self._docs = np.split(tokens, np.cumsum(lengths)[:-1])

    def __len__(self) -> int:
        return CORPUS_DOCUMENTS

    def load_tokens(self, index: int) -> np.ndarray:
        return self._docs[int(index) % len(self._docs)]


def _vocab(config) -> int:
    rows = config.moco.lm_vocab_rows
    if not rows:
        raise ValueError("a token configuration states the vocabulary rows it holds (moco.lm_vocab_rows)")
    return int(rows)


def dataset(seed: int, traffic: dict, config):
    """What `moco_tpu.train.train(config, dataset=...)` is fed from."""
    return TokenPool(seed, traffic["pool_documents"], _vocab(config), traffic)


def sample_input(config):
    """One row as the encoder takes it, for `create_state` and
    `jax.eval_shape`: the program's own (a short row: no parameter's shape
    depends on the row's length)."""
    from moco_tpu.core import sample_input as program_sample

    return program_sample(config)


def correct_rows(seed: int, n: int, config):
    """The correctness sample as the encoder takes it: `n` full rows,
    uniform ids over the held vocabulary, every position valid (what the
    cell's traffic is: two full windows a document)."""
    import jax.numpy as jnp

    rng = np.random.default_rng((int(seed), 0x70C))
    ids = rng.integers(0, _vocab(config), (n, config.data.seq_len), dtype=np.int32)
    return {"ids": jnp.asarray(ids), "lengths": jnp.full((n,), config.data.seq_len, jnp.int32)}


def correct_views(seed: int, n: int, config):
    """Two views of `n` rows each for a training forward: the two halves
    of one sample of 2n different rows."""
    rows = correct_rows(seed, 2 * n, config)
    half = lambda lo, hi: {k: v[lo:hi] for k, v in rows.items()}
    return half(0, n), half(n, 2 * n)
