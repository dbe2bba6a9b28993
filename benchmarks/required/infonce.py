"""Required work: the streaming InfoNCE over (q, k, queue) of MoCo v1/v2.

What the algorithm needs of one chip in one step, forward and backward
together, with f32 operands as the fused kernel takes them, whatever
implements it. Operations: `harness/flops.py::infonce_flops` (logits
once forward, dq once backward; recomputed logits do not count). Bytes:
the queue streams through once in each direction; q, k, dq and the
per-row statistics are small but counted. A queue-free configuration
(`num_negatives` 0) has no such kernel: nothing to divide by.
"""

from benchmarks.harness.flops import infonce_flops


def work(batch: int, dim: int, num_keys: int) -> dict:
    queue_bytes = 4.0 * num_keys * dim
    small = 4.0 * (3 * batch * dim + 6 * batch)
    return {"flops": infonce_flops(batch, dim, num_keys), "bytes": 2 * queue_bytes + small}


def required(ctx: dict):
    moco = ctx["train_config"]["moco"]
    if not moco["num_negatives"]:
        return None
    rows = ctx["train_config"]["data"]["global_batch"] // ctx["chips"]
    return work(rows, moco["dim"], moco["num_negatives"])
