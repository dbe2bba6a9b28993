"""Cross-replica batch shuffling (Shuffle-BN) — TPU-native redesigns.

Reference: `moco/builder.py:~L79-126` (`_batch_shuffle_ddp` /
`_batch_unshuffle_ddp`, "*** Only support DDP model. ***"). There, rank 0
draws a random permutation of the global key batch and *broadcasts* it
over NCCL; every rank all-gathers the images, takes its permuted slice,
runs `encoder_k` with per-GPU BatchNorm, and the embeddings are
all-gathered back and inverse-permuted. Purpose: per-device BN statistics
must not contain a query's own positive key (the BN "cheating" signature
leak).

TPU-native redesigns (all used inside `shard_map` over the `data` axis):

1. `gather_perm` (reference-exact semantics): the broadcast is replaced
   by *deterministic same-seed randomness* — every replica computes the
   identical permutation from the replicated step RNG, so no collective
   is needed to agree on it. Data still moves via `all_gather` exactly as
   upstream.

2. `a2a` (cheaper, statistically equivalent decorrelation): a *balanced
   random permutation* — local permutation, `all_to_all` chunk exchange,
   local permutation. Every device's key batch then contains a random
   B/n-sized slice from each device, so the positive key is normalized
   with (in expectation) only 1/n of its own co-batch — the same
   expected composition a uniform global permutation gives — while
   moving only (n-1)/n of the batch over ICI instead of a full
   all_gather. (An earlier `ring` mode that ppermuted batches *intact*
   was removed: moving an unchanged batch to another device leaves BN
   statistics bit-identical to no shuffle at all — composition, not
   device identity, is what leaks.)

A third alternative — no shuffle, subgroup cross-replica BN (SyncBN, as
the reference's detection configs use) — lives in the model's
`bn_cross_replica_axis` knob, not here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.obs import comms


def _merge_gather(x: jax.Array, axis_name: str, site: str) -> jax.Array:
    """all_gather with the device dim folded into the batch dim:
    (N_global, ...). `site` names the collective in the comms ledger +
    HLO metadata (obs/comms.py)."""
    with comms.tag(site, "all_gather", x, lax.axis_size(axis_name)):
        g = lax.all_gather(x, axis_name)  # (n_dev, B_local, ...)
    return g.reshape((-1,) + g.shape[2:])


def make_permutation(rng: jax.Array, global_batch: int) -> tuple[jax.Array, jax.Array]:
    """(perm, inv_perm) for the global batch. Called with a *replicated* rng
    inside the step so every device computes the same permutation —
    deterministic seeding replaces the reference's `broadcast(src=0)`."""
    perm = jax.random.permutation(rng, global_batch)
    inv_perm = jnp.argsort(perm)
    return perm, inv_perm


def shuffle_gather(x: jax.Array, perm: jax.Array, axis_name: str) -> jax.Array:
    """Give this device the rows `perm[rank*B:(rank+1)*B]` of the global batch."""
    local_b = x.shape[0]
    rank = lax.axis_index(axis_name)
    x_all = _merge_gather(x, axis_name, "shuffle.gather_images")
    my_rows = lax.dynamic_slice_in_dim(perm, rank * local_b, local_b)
    return jnp.take(x_all, my_rows, axis=0)


def unshuffle_gather(
    k: jax.Array, inv_perm: jax.Array, axis_name: str
) -> tuple[jax.Array, jax.Array]:
    """Invert `shuffle_gather` on the key embeddings.

    Returns (k_local, k_global): this device's keys in original order, and
    the full global key batch in original order (reused for the queue
    update, saving the reference's third all_gather in
    `_dequeue_and_enqueue`).
    """
    local_b = k.shape[0]
    rank = lax.axis_index(axis_name)
    # this gather is ALSO the queue's key source (the enqueue reuses
    # k_global, saving the reference's third all_gather)
    k_all = _merge_gather(k, axis_name, "shuffle.gather_keys")  # rows in perm order
    k_global = jnp.take(k_all, inv_perm, axis=0)  # original order
    k_local = lax.dynamic_slice_in_dim(k_global, rank * local_b, local_b)
    return k_local, k_global


def _local_perms(rng: jax.Array, local_b: int, axis_name: str) -> tuple[jax.Array, jax.Array]:
    """Per-device (pre, post) permutations of the local batch, derived from
    the replicated step rng + the device's rank."""
    rank = lax.axis_index(axis_name)
    pre = jax.random.permutation(jax.random.fold_in(jax.random.fold_in(rng, 17), rank), local_b)
    post = jax.random.permutation(jax.random.fold_in(jax.random.fold_in(rng, 29), rank), local_b)
    return pre, post


def balanced_shuffle(rng: jax.Array, x: jax.Array, axis_name: str) -> jax.Array:
    """Random *balanced* permutation of the global batch: each device ends
    up with a random B/n-slice from every device.

    local-perm → tiled all_to_all (device d's chunk j → device j) →
    local-perm. Requires local batch divisible by the axis size."""
    n = lax.axis_size(axis_name)
    b = x.shape[0]
    if b % n:
        raise ValueError(f"a2a shuffle needs local batch {b} divisible by axis size {n}")
    pre, post = _local_perms(rng, b, axis_name)
    x = jnp.take(x, pre, axis=0)
    with comms.tag("shuffle.a2a", "all_to_all", x, n):
        x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=True)
    return jnp.take(x, post, axis=0)


def balanced_unshuffle(rng: jax.Array, y: jax.Array, axis_name: str) -> jax.Array:
    """Exact inverse of `balanced_shuffle` with the same rng (the tiled
    chunk exchange is an involution; the local perms invert via argsort)."""
    n = lax.axis_size(axis_name)
    b = y.shape[0]
    pre, post = _local_perms(rng, b, axis_name)
    y = jnp.take(y, jnp.argsort(post), axis=0)
    with comms.tag("shuffle.a2a_unshuffle", "all_to_all", y, n):
        y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0, tiled=True)
    return jnp.take(y, jnp.argsort(pre), axis=0)
