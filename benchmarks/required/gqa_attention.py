"""Required work: the full layers' causal attention product over grouped
key heads, over one training step of momentum contrast on token rows.

`required/window_attention.py`'s arithmetic with no window: S^2/2 (query,
key) pairs a query head, 28 query heads of 128 + 128, k, v, dk and dv
counted once a KEY head (4), forward x 4 a row and full layer (1 of a
period of 4).
"""

from benchmarks.required import window_attention


def work(rows: int, seq_len: int, layers: int, itemsize: int = 2) -> dict:
    return window_attention.work(rows, seq_len, layers, None, itemsize)


def required(ctx: dict):
    return window_attention.required(ctx, kind=0)
