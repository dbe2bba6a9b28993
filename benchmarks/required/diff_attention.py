"""Required work: the differential attention of Phi-4-mini-flash's window,
full and cross-attention layers, over one training step of momentum
contrast on token rows.

What the algorithm needs of one chip in one step, whatever implements it.
A differential head is two softmax attentions over one value head,
softmax(q1 k1^T) V - lambda softmax(q2 k2^T) V: 20 pairs of 64-wide query
heads over 10 pairs of 64-wide key heads and 10 value heads of 128. Each
(query, key) pair of a head costs, for each of its two softmaxes, a
multiply-add over the q.k width and one over the v width:

    F = pairs * 20 * (2 * 64 + 2 * 128) * 2

with pairs = S^2/2 on the full and the cross layers and W*S - W^2/2 under
the window (`required/window_attention.py::pairs`), and backward twice
that (scores recomputed in the backward pass are recomputation and do not
count). A step forwards the query view and the key view and goes backward
through the query view only: 4 F a row and layer. Bytes, each softmax as
the attention it is: q and its output at the 20 pairs, k and v at the 10
key pairs read and written once (grouped heads); backward those and the
output's gradient read, dq, dk and dv written; in the compute type. The
layer map is the published one (`assumed.layer_map` of the configuration).
"""

from benchmarks.required.window_attention import pairs

# the published sizes (config.json; the head pairing and the map are the
# configuration file's `assumed`)
LAYERS, MB_PER_LAYER, PAIRS, KV_PAIRS, WIDTH, V_WIDTH, WINDOW = 32, 2, 20, 10, 64, 128, 512


def layer_kind(layer: int) -> str:
    """The mixer of published layer `layer` (SambaY's map)."""
    half = LAYERS // 2
    if layer % MB_PER_LAYER == 0:
        return "mamba" if layer <= half else "gmu"
    if layer < half:
        return "window"
    return "full" if layer == half + 1 else "cross"


def kinds(cfg: dict) -> list:
    """The mixers of the layers a run holds, or [] where the run states
    no stage of this stack."""
    moco = cfg["moco"]
    layers, first = moco.get("lm_layers"), moco.get("lm_first_layer", 0)
    if not layers or not cfg["data"].get("seq_len"):
        return []
    return [layer_kind(l) for l in range(first, first + layers)]


def work(rows: int, seq_len: int, windowed: int, whole: int, itemsize: int = 2) -> dict:
    """`windowed` window layers and `whole` full or cross layers."""
    per_pair = PAIRS * (2 * WIDTH + 2 * V_WIDTH) * 2.0
    forward = (windowed * pairs(seq_len, WINDOW) + whole * pairs(seq_len, None)) * per_pair
    fwd = PAIRS * (WIDTH + V_WIDTH) + KV_PAIRS * (WIDTH + V_WIDTH)  # q, out | k, v
    bwd = fwd + PAIRS * V_WIDTH + PAIRS * WIDTH + KV_PAIRS * (WIDTH + V_WIDTH)  # + g; dq | dk, dv
    softmaxes = 2 * (windowed + whole)
    bytes_row = seq_len * itemsize * (2 * fwd + bwd) * softmaxes  # 2 forwards, 1 backward
    return {"flops": 4.0 * forward * rows, "bytes": float(bytes_row * rows)}


def required(ctx: dict):
    cfg = ctx["train_config"]
    held = kinds(cfg)
    windowed = held.count("window")
    whole = held.count("full") + held.count("cross")
    if not windowed + whole or cfg["moco"].get("arch", "").split("_")[0] != "phi4":
        return None
    return work(cfg["data"]["global_batch"] // ctx["chips"], cfg["data"]["seq_len"], windowed, whole)
