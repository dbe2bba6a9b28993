"""Required work: the routed ReGLU experts' grouped products over one
training step, for the experts this chip holds.

What the algorithm needs of one chip in one step, whatever implements it.
One (token, expert) assignment that lands on a held expert costs forward
the expert's gated unit: 3 matrices of 2560 x 768, one multiply-add each
per element: 3 * 2560 * 768 * 2 operations. Backward costs twice that. A
step forwards the query view and the key view and goes backward through
the query view: 4 forwards' worth. The assignments are the run's own: the
log lines' mean `moe/tokens_per_expert` (tokens on a held expert, a layer
and a view) times the experts held, times the layers (every layer of this
stack has experts); the key view is taken to route as the query view does.
Bytes: the held weights read once a pass (forward q, forward k, backward
for tokens, backward for weights, whose gradient is also written), and the
assignments' rows in and out of each product, in the compute type.
"""

HIDDEN, EXPERT_WIDTH = 2560, 768  # hidden_size, moe_ffn_hidden_size


def work(assignments: float, held: int, layers: int, itemsize: int = 2) -> dict:
    per_assignment = 3.0 * HIDDEN * EXPERT_WIDTH * 2.0
    weights = held * 3.0 * HIDDEN * EXPERT_WIDTH * itemsize
    # rows a pass moves: x in, gate|up out, act in, y out
    rows = assignments * itemsize * (HIDDEN + 2 * EXPERT_WIDTH + EXPERT_WIDTH + HIDDEN)
    return {
        "flops": 4.0 * per_assignment * assignments * layers,
        "bytes": (5.0 * weights + 4.0 * rows) * layers,
    }


def required(ctx: dict):
    moco = ctx["train_config"]["moco"]
    share, layers = moco.get("expert_share"), moco.get("lm_layers")
    seen = [ln["moe/tokens_per_expert"] for ln in ctx.get("train_lines") or []
            if ln.get("moe/tokens_per_expert") is not None]
    if not share or not layers or not seen:
        return None
    held = int(share[1])
    return work(sum(seen) / len(seen) * held, held, layers)
