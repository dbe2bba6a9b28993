#!/usr/bin/env python
"""Gate and promote training checkpoints into the serving fleet.

The auditable train→serve handoff (serve/promote.py holds the pieces):
watch a candidate checkpoint directory, and for each new step run the
promotion gate battery against the LIVE serving checkpoint —
embedding-space compatibility (`serve/compat_cosine`,
`serve/recall_overlap` vs the live index), the dimensional-collapse
floor, and the EMA-drift ceiling — writing every verdict as a schema'd
line in an append-only `promotions.jsonl` ledger. A candidate that
clears the gates rolls out through the fleet router ONE replica at a
time (`POST /admin/promote` → drain → restart onto the candidate →
wait for its digest to land), soaking on the fleet burn gauges between
replicas; a burn breach or a stuck swap auto-rolls every touched
replica back to the live checkpoint.

    python scripts/serve_promote.py --candidate-dir /run/new \
        --live-dir /run/current [--router http://127.0.0.1:9000] \
        [--ledger promotions.jsonl] [--watch-s 10] [--probes 32] [--k 5]

Without `--router` this is gates-only (verdict `accepted`/`rejected`
in the ledger, nothing touches traffic) — the CI shape. With a router
the final verdict is `promoted` or `rolled_back`. One-shot by default;
`--watch-s N` tails the candidate directory like serve_ingest tails
the queue. Exit code: 0 when the last verdict was accepted/promoted,
1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.parse
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# injectable for tests (a fleet is simulated by swapping this)
_urlopen = urllib.request.urlopen


def _get_json(url: str, timeout: float = 10.0) -> dict:
    with _urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _post_json(url: str, timeout: float = 30.0) -> dict:
    req = urllib.request.Request(url, data=b"")
    with _urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def load_engine_for_gates(workdir: str, n_probes: int, side: str = "k"):
    """(engine, params, queue, queue_ptr, config) for one checkpoint —
    a single AOT bucket sized to the probe set (the battery embeds
    exactly one batch, compiling the serving buckets would be waste)."""
    from moco_tpu.serve.engine import InferenceEngine, load_serving_encoder

    module, params, stats, queue, queue_ptr, config = load_serving_encoder(
        workdir, side=side
    )
    engine = InferenceEngine(
        module, params, stats,
        image_size=config.data.image_size, buckets=(int(n_probes),),
    )
    return engine, params, queue, queue_ptr, config


def gate_candidate(
    live_dir: str,
    candidate_dir: str,
    n_probes: int = 32,
    k: int = 5,
    floors: dict = None,
    live_recall: float = None,
) -> tuple:
    """Run the full battery for the newest candidate checkpoint.
    Returns (battery_result, candidate_digest, candidate_step)."""
    from moco_tpu.obs import quality
    from moco_tpu.serve.index import EmbeddingIndex
    from moco_tpu.serve.promote import run_gate_battery
    from moco_tpu.utils.checkpoint import CheckpointManager

    mgr = CheckpointManager(candidate_dir)
    step = mgr.latest_step()
    mgr.close()
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {candidate_dir}")
    live_engine, _, queue, queue_ptr, config = load_engine_for_gates(
        live_dir, n_probes
    )
    index = EmbeddingIndex.from_train_queue(queue, queue_ptr)
    cand_engine, cand_params_k, _, _, _ = load_engine_for_gates(
        candidate_dir, n_probes
    )
    # the query-side twin, for the EMA-drift ceiling (a second restore
    # of the same checkpoint — cheap next to the gate embeds)
    _, cand_params_q, _, _, _ = load_engine_for_gates(
        candidate_dir, n_probes, side="q"
    )
    probes = quality.synthetic_probes(n_probes, config.data.image_size)
    result = run_gate_battery(
        live_engine, cand_engine, probes, index=index, k=k, floors=floors,
        cand_params_q=cand_params_q, cand_params_k=cand_params_k,
        live_recall=live_recall,
    )
    return result, quality.params_digest(cand_params_k), int(step)


def fleet_burn(router: str):
    """The rollout soak gauge: the worst reading across the router's
    latency AND freshness burn families (client-observed plus the
    per-replica aggregates) — any of them breaching pauses a rollout."""
    stats = _get_json(router.rstrip("/") + "/stats")
    vals = [
        v
        for key, v in stats.items()
        if key.startswith("fleet_serve/")
        and ("burn_rate_" in key)
        and isinstance(v, (int, float))
    ]
    return max(vals) if vals else None


def live_recall_estimate(router: str):
    """The fleet's current sampled online recall (the promotion
    baseline gate) — the max over replicas' serve/recall_estimate
    aggregate; None where no replica has sampled yet."""
    stats = _get_json(router.rstrip("/") + "/stats")
    v = stats.get("fleet_serve/recall_estimate_max")
    return v if isinstance(v, (int, float)) else None


def rollout(
    router: str,
    candidate_dir: str,
    live_dir: str,
    target_digest: str = None,
    soak_s: float = 2.0,
    swap_timeout_s: float = 60.0,
    burn_ceiling: float = None,
    poll_s: float = 0.25,
) -> dict:
    """Staged rollout over every replica behind `router`, auto-rollback
    to `live_dir` on breach (serve/promote.py StagedRollout does the
    sequencing; this wires its callables to the router HTTP surface)."""
    from moco_tpu.obs.slo import DEFAULT_FAST_BURN
    from moco_tpu.serve.promote import StagedRollout

    base = router.rstrip("/")
    replicas = _get_json(base + "/admin/replicas")["replicas"]

    def _swap_to(ckpt_dir):
        quoted = urllib.parse.quote(str(ckpt_dir), safe="")

        def _swap(i):
            _post_json(f"{base}/admin/promote?replica={i}&ckpt_dir={quoted}")

        return _swap

    def _status(i):
        for rep in _get_json(base + "/admin/replicas")["replicas"]:
            if rep["index"] == i:
                return rep
        return {}

    machine = StagedRollout(
        len(replicas),
        swap=_swap_to(candidate_dir),
        status=_status,
        burn=lambda: fleet_burn(base),
        swap_back=_swap_to(live_dir),
        target_digest=target_digest,
        soak_s=soak_s,
        swap_timeout_s=swap_timeout_s,
        burn_ceiling=DEFAULT_FAST_BURN if burn_ceiling is None else burn_ceiling,
        poll_s=poll_s,
    )
    return machine.run()


def promote_once(args, ledger) -> str:
    """One full pipeline pass: gates → ledger → (optionally) rollout →
    ledger. Returns the final verdict string."""
    from moco_tpu.serve.promote import ledger_record

    floors = {
        "compat_cosine": args.floor_cosine,
        "recall_overlap": args.floor_overlap,
        "feature_std": args.floor_feature_std,
        "ema_drift_max": args.max_ema_drift,
        "live_recall": args.floor_live_recall,
    }
    live_recall = None
    if args.router and args.floor_live_recall is not None:
        live_recall = live_recall_estimate(args.router)
    result, digest, step = gate_candidate(
        args.live_dir, args.candidate_dir,
        n_probes=args.probes, k=args.k, floors=floors, live_recall=live_recall,
    )
    verdict = "accepted" if result["ok"] else "rejected"
    ledger.append(ledger_record(
        step, verdict, "gates", digest=digest,
        failed_gate=result["failed_gate"], gates=result["gates"],
        compat=result["compat"],
    ))
    print(
        f"step {step} ({digest}): gates {verdict}"
        + (f" (failed: {result['failed_gate']})" if result["failed_gate"] else ""),
        flush=True,
    )
    if verdict == "rejected" or not args.router:
        return verdict
    out = rollout(
        args.router, args.candidate_dir, args.live_dir, target_digest=digest,
        soak_s=args.soak_s, swap_timeout_s=args.swap_timeout_s,
        burn_ceiling=args.burn_ceiling, poll_s=args.poll_s,
    )
    # a rollout failure's evidence is the breaching burn reading vs the
    # ceiling, in the same gate shape the battery uses
    gates = None
    if out["verdict"] == "rolled_back" and out["burn"] is not None:
        gates = {"burn": {
            "value": out["burn"],
            "floor": args.burn_ceiling,
            "ok": False,
        }}
    ledger.append(ledger_record(
        step, out["verdict"], "rollout", digest=digest,
        failed_gate=out["reason"], replica=out["replica"], gates=gates,
    ))
    print(
        f"step {step} ({digest}): rollout {out['verdict']}"
        + (f" (replica {out['replica']}: {out['reason']})"
           if out["reason"] else f" across {len(out['swapped'])} replicas"),
        flush=True,
    )
    return out["verdict"]


def main() -> int:
    from moco_tpu.utils.platform import (
        enable_persistent_compilation_cache,
        pin_platform_from_env,
    )

    pin_platform_from_env()
    enable_persistent_compilation_cache()
    ap = argparse.ArgumentParser(
        description="gate + promote checkpoints into the serving fleet"
    )
    ap.add_argument("--candidate-dir", required=True, help="checkpoint dir to watch")
    ap.add_argument("--live-dir", required=True, help="the fleet's current checkpoint dir")
    ap.add_argument("--router", default=None, help="fleet router base URL (omit for gates-only)")
    ap.add_argument("--ledger", default=None, help="promotions.jsonl path (default: <candidate-dir>/promotions.jsonl)")
    ap.add_argument("--probes", type=int, default=32, help="held-back probe batch size")
    ap.add_argument("--k", type=int, default=5, help="top-k for the recall-overlap gate")
    ap.add_argument("--floor-cosine", type=float, default=0.90)
    ap.add_argument("--floor-overlap", type=float, default=0.60)
    ap.add_argument("--floor-feature-std", type=float, default=0.25)
    ap.add_argument("--max-ema-drift", type=float, default=0.50)
    ap.add_argument("--floor-live-recall", type=float, default=None,
                    help="also require the fleet's live recall_estimate above this")
    ap.add_argument("--soak-s", type=float, default=2.0, help="burn-gauge soak between replica swaps")
    ap.add_argument("--swap-timeout-s", type=float, default=60.0)
    ap.add_argument("--burn-ceiling", type=float, default=14.4, help="rollback above this fleet burn reading")
    ap.add_argument("--poll-s", type=float, default=0.25)
    ap.add_argument("--watch-s", type=float, default=0.0,
                    help="poll the candidate dir every N seconds (0 = one shot)")
    args = ap.parse_args()

    from moco_tpu.serve.promote import PromotionLedger
    from moco_tpu.utils.checkpoint import CheckpointManager

    ledger_path = args.ledger or os.path.join(args.candidate_dir, "promotions.jsonl")
    ledger = PromotionLedger(ledger_path)

    if args.watch_s <= 0:
        verdict = promote_once(args, ledger)
        return 0 if verdict in ("accepted", "promoted") else 1

    last_step = None
    while True:
        mgr = CheckpointManager(args.candidate_dir)
        step = mgr.latest_step()
        mgr.close()
        if step is not None and step != last_step:
            promote_once(args, ledger)
            last_step = step
        time.sleep(args.watch_s)


if __name__ == "__main__":
    sys.exit(main())
