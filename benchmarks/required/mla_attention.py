"""Required work: the causal attention product of latent attention (MLA)
over one training step of momentum contrast on token rows.

What the algorithm needs of one chip in one step, whatever implements it.
A row of S positions and H heads, q and k 192 wide (128 + 64 rotary), v
128 wide, costs forward S^2/2 (query, key) pairs a head, each one multiply-
add over the q.k width and one over the v width:

    F = (S^2 / 2) * H * (192 + 128) * 2

and backward twice that: ds.k and ds^T.q over 192, p^T.g and g.v^T over
128 (the scores recomputed in the backward pass are recomputation and do
not count). A step forwards the query view and the key view through every
layer (2 F a row and layer) and goes backward through the query view only
(2 F more): 4 F a row and layer. Bytes: q, k, v read and the output
written forward; backward those four and the output's gradient read, dq,
dk, dv written; in the compute type.
"""

# the published sizes (config.json): num_attention_heads, qk_nope_head_dim +
# qk_rope_head_dim, v_head_dim
HEADS, QK_WIDTH, V_WIDTH = 32, 192, 128


def work(rows: int, seq_len: int, layers: int, itemsize: int = 2) -> dict:
    forward = (seq_len * seq_len / 2.0) * HEADS * (QK_WIDTH + V_WIDTH) * 2.0
    widths_fwd = 2 * QK_WIDTH + 2 * V_WIDTH  # q, k, v, out
    widths_bwd = widths_fwd + V_WIDTH + 2 * QK_WIDTH + V_WIDTH  # + g; dq, dk, dv
    bytes_row = seq_len * HEADS * itemsize * (2 * widths_fwd + widths_bwd)  # 2 forwards, 1 backward
    return {"flops": 4.0 * forward * rows * layers, "bytes": float(bytes_row * rows * layers)}


def required(ctx: dict):
    cfg = ctx["train_config"]
    layers = cfg["moco"].get("lm_layers")
    seq_len = cfg["data"].get("seq_len")
    if not layers or not seq_len:
        return None
    return work(cfg["data"]["global_batch"] // ctx["chips"], seq_len, layers)
