"""The open-loop schedule: a pure function of the traffic file and the seed.

One general generator for every serving mix. The traffic file gives the
rate, the arrival process, the size mix and the route mix; the seed gives
the order. Every seed sees the same multiset of sizes and routes (the
counts are fixed from the mix, only their order is shuffled) and arrivals
whose gaps are the same multiset in another order, so two seeds offer the
same work and a difference between runs is the system's, not the draw's.

No JAX here: the load generator's process must never touch the chip.
"""

from __future__ import annotations

import numpy as np


def _counts(weights: dict, n: int) -> list[tuple[str, int]]:
    """Largest-remainder apportionment of n draws over `weights`."""
    keys = sorted(weights)
    total = float(sum(weights[k] for k in keys))
    exact = [weights[k] / total * n for k in keys]
    base = [int(e) for e in exact]
    order = sorted(range(len(keys)), key=lambda i: (exact[i] - base[i], -i), reverse=True)
    for i in order[: n - sum(base)]:
        base[i] += 1
    return list(zip(keys, base))


def build_schedule(traffic: dict, seed: int, seconds: float) -> dict:
    """{"due_s": float64[n], "size": int32[n], "route": list[str]} for a
    window of `seconds`.

    arrivals: "poisson" draws n = rate*seconds exponential gaps from a
    FIXED generator (the traffic file's `gap_seed`), rescales them to fill
    the window exactly, and lets `seed` permute them: the same gaps in
    another order. "uniform" spaces them evenly. `burst` (optional:
    {"period_s", "on_share"}) squeezes each period's arrivals into its
    first `on_share`, an on/off source at the same mean rate."""
    rate = float(traffic["rate_rps"])
    n = int(round(rate * seconds))
    if n <= 0:
        raise ValueError(f"rate {rate} over {seconds}s offers no request")
    rng = np.random.default_rng(int(seed))
    if traffic.get("arrivals", "poisson") == "poisson":
        gaps = np.random.default_rng(int(traffic.get("gap_seed", 0))).exponential(1.0, n)
        gaps *= seconds / gaps.sum()
        gaps = gaps[rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
    else:
        due = np.arange(n, dtype=np.float64) / rate
    burst = traffic.get("burst")
    if burst:
        period, on = float(burst["period_s"]), float(burst["on_share"])
        due = np.floor(due / period) * period + (due % period) * on
    sizes = np.concatenate([
        np.full(c, int(k), np.int32) for k, c in _counts(traffic["images_per_request"], n)
    ])
    routes = np.concatenate([
        np.full(c, k, dtype=object) for k, c in _counts(traffic["routes"], n)
    ])
    return {
        "due_s": due.astype(np.float64),
        "size": sizes[rng.permutation(n)],
        "route": list(routes[rng.permutation(n)]),
    }


def structured_images(seed: int, n: int, image_size: int) -> np.ndarray:
    """The correctness sample: seeded uint8 images with structure (a coarse
    random colour field per image, upsampled, at its own contrast, plus
    fine noise). Pure noise images all look alike to a convolutional
    encoder, so their embeddings would nearly coincide and any comparison
    of them would pass; these differ from one another."""
    rng = np.random.default_rng(int(seed) ^ 0xC0FFEE)
    cells = 8
    reps = -(-image_size // cells)
    coarse = rng.uniform(0.0, 1.0, (n, cells, cells, 3))
    field = np.repeat(np.repeat(coarse, reps, axis=1), reps, axis=2)[:, :image_size, :image_size]
    contrast = rng.uniform(0.3, 1.0, (n, 1, 1, 1))
    img = 0.5 + (field - 0.5) * contrast + rng.normal(0.0, 0.05, (n, image_size, image_size, 3))
    return (np.clip(img, 0.0, 1.0) * 255.0).astype(np.uint8)
