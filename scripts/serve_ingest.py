#!/usr/bin/env python
"""Stream a LIVE training run's dictionary into a serving replica.

The training queue and the serving index share their FIFO kernel
(serve/index.py:fifo_write) — this script closes the remaining gap in
the ROADMAP "streaming index updates from a live run" item: it tails a
training run's checkpoint directory and FIFO-ingests the freshly
enqueued queue rows into a RUNNING replica over the server's `/ingest`
endpoint, so a long-lived serving process tracks the dictionary the
trainer is still building without a restart or a bulk reload.

    python scripts/serve_ingest.py --ckpt-dir /run/workdir \
        --server http://127.0.0.1:8000 [--poll-s 10] [--once] [--fanout]

With `--fanout` the `--server` URL is a fleet ROUTER
(serve/router.py): each poll discovers the replica topology from
`GET /admin/replicas` and posts the fresh block to EVERY replica
directly (the router does not proxy /ingest — a dictionary update must
reach all of them, not one). Each replica gets its own retry site
(`ingest.post.r<i>` in the io_retries ledger), and one replica failing
its retries degrades to a logged warning, not a lost block for the
others — a restarting replica catches up through the supervisor's warm
replay anyway.

Per new checkpoint step: restore the queue + write head, diff against
the last seen head (the freshly enqueued region is `[old_ptr, new_ptr)`
circular; the FIRST sighting ingests the full queue oldest-first so the
replica starts aligned), POST the block as raw f32 rows. The replica's
IVF cell membership and int8 mirror follow each ingest incrementally
(serve/server.py `/ingest` → `EmbeddingIndex.add`), and
`serve/ingested_rows` / `serve/index_rows` advance in its metric flush
— which is exactly what the smoke asserts.

Assumes fewer than K rows are enqueued between polled checkpoints (a
full-queue turnover with an identical head is indistinguishable from
no-op; shorten --poll-s if the trainer outruns it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

DEFAULT_BLOCK = 512  # rows per POST: bounds request size and replica compiles

# injectable for tests (a flaky replica is simulated by swapping this)
_urlopen = urllib.request.urlopen


def fresh_rows(queue: np.ndarray, old_ptr, new_ptr: int) -> np.ndarray:
    """The block the trainer enqueued since the last sighting, in FIFO
    (oldest-first) order. `old_ptr=None` = first sighting: the whole
    valid queue, oldest-first from the write head."""
    if old_ptr is None:
        return np.concatenate([queue[new_ptr:], queue[:new_ptr]])
    old_ptr = int(old_ptr)
    if new_ptr == old_ptr:
        return queue[:0]
    if new_ptr > old_ptr:
        return queue[old_ptr:new_ptr]
    return np.concatenate([queue[old_ptr:], queue[:new_ptr]])


def post_rows(
    server: str, rows: np.ndarray, block: int = DEFAULT_BLOCK,
    site: str = "ingest.post", ckpt_step: int = None,
) -> int:
    """POST `rows` to the replica's /ingest in bounded blocks; returns
    the replica's reported index row count after the last block.
    `ckpt_step` (the checkpoint step the rows came from) travels as the
    `X-Ckpt-Step` header so the replica's `serve/ingest_ckpt_step`
    gauge tracks WHICH encoder's dictionary it is serving — the
    freshness SLO's `serve/row_age_max_s` is wall-clock, this is the
    training-step twin.

    Each POST runs through the `utils/retry.py` backoff layer (`site`,
    counted in the per-site io_retries ledger — fanout mode names one
    site per replica): a replica restart or transient connection reset
    mid-tail degrades to a logged retry instead of dropping the ingest
    block — `urllib`'s URLError is an OSError, so the default retry_on
    covers both network and HTTP transport failures."""
    from moco_tpu.utils import retry

    def _post(chunk: np.ndarray) -> int:
        headers = {"X-Rows-Shape": f"{chunk.shape[0]},{chunk.shape[1]}"}
        if ckpt_step is not None:
            headers["X-Ckpt-Step"] = str(int(ckpt_step))
        req = urllib.request.Request(
            server.rstrip("/") + "/ingest",
            data=chunk.tobytes(),
            headers=headers,
        )
        with _urlopen(req, timeout=60) as r:
            return json.loads(r.read())["index_rows"]

    index_rows = -1
    for lo in range(0, rows.shape[0], block):
        chunk = np.ascontiguousarray(rows[lo : lo + block], np.float32)
        index_rows = retry.retry_call(_post, chunk, site=site)
    return index_rows


def discover_replicas(router: str) -> dict:
    """{replica_index: base_url} from a fleet router's /admin/replicas
    (serve/router.py). Every known replica is returned, draining or
    not — an ingest a drained replica rejects is retried and then
    skipped with a warning; the supervisor's warm replay realigns it."""
    with _urlopen(router.rstrip("/") + "/admin/replicas", timeout=10) as r:
        body = json.loads(r.read())
    return {int(rep["index"]): rep["url"] for rep in body["replicas"]}


def fanout_rows(
    router: str, rows: np.ndarray, block: int = DEFAULT_BLOCK,
    ckpt_step: int = None,
) -> dict:
    """POST `rows` to every replica behind `router`, each under its own
    retry site (`ingest.post.r<i>`). Returns {index: index_rows | None}
    — None marks a replica whose retries were exhausted (logged; the
    other replicas still got the block)."""
    results: dict = {}
    for index, url in sorted(discover_replicas(router).items()):
        try:
            results[index] = post_rows(
                url, rows, block, site=f"ingest.post.r{index}",
                ckpt_step=ckpt_step,
            )
        except OSError as e:
            print(
                f"WARNING: replica {index} ({url}) dropped an ingest block "
                f"after retries: {e!r}",
                flush=True,
            )
            results[index] = None
    return results


def poll_once(
    ckpt_dir: str, server: str, seen: dict, block: int = DEFAULT_BLOCK,
    fanout: bool = False,
) -> int:
    """One tail step: ingest anything new; returns rows ingested.
    `seen` carries {'step', 'ptr'} across polls. With `fanout`,
    `server` is a router URL and the block goes to every replica."""
    from moco_tpu.lincls import restore_pretrain_state
    from moco_tpu.utils.checkpoint import CheckpointManager

    step = CheckpointManager(ckpt_dir).latest_step()
    if step is None or step == seen.get("step"):
        return 0
    state, _ = restore_pretrain_state(ckpt_dir)
    queue = np.asarray(state.queue, np.float32)
    new_ptr = int(state.queue_ptr)
    rows = fresh_rows(queue, seen.get("ptr"), new_ptr)
    if rows.shape[0]:
        if fanout:
            results = fanout_rows(server, rows, block, ckpt_step=step)
            summary = ", ".join(
                f"r{i}={'FAILED' if n is None else n}"
                for i, n in sorted(results.items())
            )
            print(
                f"step {step}: fanned {rows.shape[0]} fresh rows to "
                f"{len(results)} replicas (index_rows: {summary})",
                flush=True,
            )
        else:
            index_rows = post_rows(server, rows, block, ckpt_step=step)
            print(
                f"step {step}: ingested {rows.shape[0]} fresh rows "
                f"(replica index_rows={index_rows})",
                flush=True,
            )
    seen["step"], seen["ptr"] = step, new_ptr
    return int(rows.shape[0])


def main() -> int:
    from moco_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        pin_platform_from_env,
    )

    pin_platform_from_env()
    enable_persistent_compilation_cache()
    ap = argparse.ArgumentParser(description="tail a training checkpoint dir into a serving replica")
    ap.add_argument("--ckpt-dir", required=True, help="the training run's workdir")
    ap.add_argument("--server", required=True, help="replica base URL, e.g. http://127.0.0.1:8000")
    ap.add_argument("--poll-s", type=float, default=10.0)
    ap.add_argument("--block", type=int, default=DEFAULT_BLOCK, help="rows per /ingest POST")
    ap.add_argument("--once", action="store_true", help="one poll, then exit (smoke/test mode)")
    ap.add_argument(
        "--fanout", action="store_true",
        help="--server is a fleet router: discover replicas via "
        "/admin/replicas and ingest into every one",
    )
    args = ap.parse_args()
    from moco_tpu.utils import retry

    seen: dict = {}
    while True:
        poll_once(args.ckpt_dir, args.server, seen, args.block, fanout=args.fanout)
        retries = retry.snapshot()
        if retries:
            # the per-site retry ledger (ingest.post + checkpoint-restore
            # sites), surfaced like the train driver's io_retries field
            print(f"io_retries: {json.dumps(retries)}", flush=True)
        if args.once:
            return 0
        time.sleep(args.poll_s)


if __name__ == "__main__":
    sys.exit(main())
