"""Required work: the window layers' attention product over grouped key
heads, over one training step of momentum contrast on token rows.

What the algorithm needs of one chip in one step, whatever implements it.
Under a window W a query sees its own key and the W - 1 before it: a row
of S positions has W*S - W^2/2 (query, key) pairs a query head (S^2/2
where the window covers the row), each one multiply-add over the q.k
width and one over the v width, 28 query heads of 128:

    F = (W*S - W^2/2) * 28 * (128 + 128) * 2

and backward twice that (the scores recomputed in the backward pass are
recomputation and do not count). A step forwards the query view and the
key view through every window layer and goes backward through the query
view only: 4 F a row and window layer. Bytes: q read and the output
written at the 28 query heads, k and v read at the 4 KEY heads (grouped
heads: a key head is stored and fetched once, whatever reads it); backward
those and the output's gradient read, dq written at 28 heads, dk and dv at
4; in the compute type. The full layers of the period are
`required/gqa_attention.py`'s.
"""

# the published sizes (config.json): num_attention_heads, num_key_value_heads,
# head_dim, sliding_window_size, one period of sliding_window_layout
HEADS, KV_HEADS, WIDTH, WINDOW, LAYOUT = 28, 4, 128, 4096, (0, 1, 1, 1)


def pairs(seq_len: int, window) -> float:
    if window is None or window >= seq_len:
        return seq_len * seq_len / 2.0
    return window * seq_len - window * window / 2.0


def layers_of_kind(layers: int, kind: int) -> int:
    """How many of the first `layers` layers the layout marks `kind`
    (1: window, 0: full)."""
    return sum(1 for i in range(layers) if LAYOUT[i % len(LAYOUT)] == kind)


def work(rows: int, seq_len: int, layers: int, window, itemsize: int = 2) -> dict:
    forward = pairs(seq_len, window) * HEADS * (WIDTH + WIDTH) * 2.0
    heads_fwd = 2 * HEADS + 2 * KV_HEADS  # q, out | k, v
    heads_bwd = heads_fwd + HEADS + HEADS + 2 * KV_HEADS  # + g; dq | dk, dv
    bytes_row = seq_len * WIDTH * itemsize * (2 * heads_fwd + heads_bwd)  # 2 forwards, 1 backward
    return {"flops": 4.0 * forward * rows * layers, "bytes": float(bytes_row * rows * layers)}


def required(ctx: dict, kind: int = 1):
    cfg = ctx["train_config"]
    layers = cfg["moco"].get("lm_layers")
    seq_len = cfg["data"].get("seq_len")
    if not layers or not seq_len or not layers_of_kind(layers, kind):
        return None
    return work(cfg["data"]["global_batch"] // ctx["chips"], seq_len,
                layers_of_kind(layers, kind), WINDOW if kind else None)
