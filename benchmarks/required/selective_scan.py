"""Required work: the selective scan of Phi-4-mini-flash's Mamba layers,
over one training step of momentum contrast on token rows.

What the algorithm needs of one chip in one step, whatever implements it.
The scan s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t, y_t = s_t C_t + D x_t
over 5120 channels and 16 states is elementwise: per token, channel and
state a multiply and an exp for the decay, two multiplies and an add for
the state, a multiply-add for C (7), and per token and channel the input's
dt multiply and the skip's multiply-add (3). That is a few operations a
byte, so the scan is bound by what it moves: x read and y written in the
compute type, dt, B and C read in float32 (the scan's precision: an
exp of dt A); backward x, dt, B, C and y's gradient read, the gradients
of x (compute type), dt, B and C (float32) written. A step forwards the
query view and the key view and goes backward through the query view
only: 2 forwards and a backward a row and Mamba layer; the backward's
operations are counted at twice the forward's.
"""

from benchmarks.required.diff_attention import kinds

# the sizes (the configuration file's `assumed.mamba_sizes`)
CHANNELS, STATES = 5120, 16
OPS_PER_STATE, OPS_PER_CHANNEL = 7, 3


def work(rows: int, seq_len: int, layers: int, itemsize: int = 2) -> dict:
    forward = seq_len * CHANNELS * (OPS_PER_STATE * STATES + OPS_PER_CHANNEL)
    wide = seq_len * CHANNELS  # elements of x, dt, y and their gradients
    narrow = seq_len * STATES  # of B, C and theirs
    fwd = wide * (itemsize + 4 + itemsize) + 2 * narrow * 4  # x, dt | y; B, C
    bwd = wide * (itemsize + 4 + itemsize + itemsize + 4) + 4 * narrow * 4  # x, dt, gy | dx, ddt; B, C | dB, dC
    return {"flops": 4.0 * forward * rows * layers, "bytes": float((2 * fwd + bwd) * rows * layers)}


def required(ctx: dict):
    cfg = ctx["train_config"]
    layers = kinds(cfg).count("mamba")
    if not layers or cfg["moco"].get("arch", "").split("_")[0] != "phi4":
        return None
    return work(cfg["data"]["global_batch"] // ctx["chips"], cfg["data"]["seq_len"], layers)
