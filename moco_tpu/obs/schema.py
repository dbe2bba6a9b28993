"""The metrics.jsonl line schema, as code.

README's "metrics.jsonl line format" section is the human contract;
this module is the machine-checkable one — the golden schema test
(tests/test_obs.py), `scripts/obs_smoke.py`, and `scripts/obs_report.py
--strict` all validate against it, so the README can't silently rot.

Line kinds (all carry `step` int + `time` float):

- *training lines*: `loss` present -> require `epoch`/`lr`/`acc1`/
  `acc5`; optionally the step-time breakdown (`t_data`/`t_step`, the
  `phase/*` account of every step since the last line, and
  `t_dispatch`/`t_device`/`t_probe_step` once a step after the first
  has been probe-sampled), the input-wire
  gauges (`t_transfer`/`transfer_bytes`/`prefetch_depth_live` when the
  device prefetch ring is on), device-memory gauges
  (`hbm_live_bytes`/`hbm_peak_bytes`, number or null), health gauges
  (`ema_drift*`, `logit_*`, `feature_*`, `queue_age_*`), and the fault
  counters (`nan_steps`/`decode_failures`/`io_retries` when nonzero,
  `compile_cache_misses` under --strict-tracing);
- *event lines*: `event` in EVENT_KINDS instead of the metric fields
  (alert events additionally carry `alert`/`severity` and an
  `alert/<rule>` Prometheus gauge);
- *aux lines*: neither (e.g. the periodic `knn_top1` line).

Fleet-observability fields (obs/fleet.py, obs/comms.py) ride training
lines: `straggler_skew`/`fleet_hosts` plus the
`fleet/<field>_{min,mean,max,argmax}` family on process 0, and the
analytic `comms/<site>` bytes-per-step counters on every process.

Serving lines (serve/server.py's flusher) carry the `serve/*` family,
including the request-scoped surface (PR 10): `serve/trace_<stage>_ms`
stage-waterfall means, `serve/burn_rate_<w>s` SLO burn rates,
`serve/latency_hist` (a structured cumulative-histogram payload), and
the `serve/p99_exemplar` request id — the one STRING inside the
numeric family, which is why explicit field validators take precedence
over the prefix families in `validate_line`.

Numbers are finite or null — NaN/Inf literals are rejected at parse
time (`loads_strict`), matching the writer's scrubbing.

Deliberately stdlib-only so report tooling can import it anywhere.
"""

from __future__ import annotations

import json
from typing import Any, Iterable

EVENT_KINDS = frozenset(
    {"nonfinite_loss", "stall", "recompile_after_warmup", "alert",
     # graceful-preemption exit (SIGTERM -> emergency checkpoint) and
     # the elastic checkpoint-and-rescale (parallel/elastic.py): the
     # rescale line carries the rescale/* family below — old/new mesh
     # shape, old/new global batch, and the re-derived hyperparameters
     "preempt", "rescale",
     # once a run, when the first step's outputs are ready: what each
     # part of set-up cost (the setup/<part>_s family below)
     "setup",
     # checkpoint-promotion audit lines (serve/promote.py
     # PromotionLedger): verdict + per-gate evidence in the promotion/*
     # family below
     "promotion"}
)

TRAIN_REQUIRED = ("epoch", "lr", "loss", "acc1", "acc5")

# field -> validator; a field listed here, when present, must satisfy it
_NUMBER = (int, float)


def _num(v: Any) -> bool:
    return isinstance(v, _NUMBER) and not isinstance(v, bool)


def _num_or_null(v: Any) -> bool:
    return v is None or _num(v)


def _int_like(v: Any) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def _num_list(v: Any) -> bool:
    return isinstance(v, list) and all(_num_or_null(x) for x in v)


def _counter_map(v: Any) -> bool:
    return isinstance(v, dict) and all(
        isinstance(k, str) and _int_like(n) for k, n in v.items()
    )


def _nonneg_or_null(v: Any) -> bool:
    return v is None or (_num(v) and v >= 0)


def _str_or_null(v: Any) -> bool:
    return v is None or isinstance(v, str)


def _latency_hist(v: Any) -> bool:
    """The cumulative-histogram payload the Prometheus sink renders as
    `<name>_bucket{le=...}`: finite ascending bucket bounds (ms), one
    count per bucket plus the +Inf overflow slot, and the sum/count
    pair. Counts are PER-BUCKET here; the sink cumulates at render."""
    if not isinstance(v, dict):
        return False
    le, counts = v.get("le"), v.get("counts")
    return (
        isinstance(le, list)
        and all(_num(x) for x in le)
        and le == sorted(le)
        and isinstance(counts, list)
        and len(counts) == len(le) + 1
        and all(_int_like(c) and c >= 0 for c in counts)
        and _num(v.get("sum"))
        and _int_like(v.get("count"))
    )


FIELD_VALIDATORS = {
    "step": _int_like,
    "time": _num,
    "epoch": _int_like,
    "lr": _num_or_null,
    "loss": _num_or_null,
    "acc1": _num_or_null,
    "acc5": _num_or_null,
    "knn_top1": _num_or_null,
    # step-time breakdown (obs/stepstats.py)
    "t_data": _num,
    "t_step": _num,
    "t_dispatch": _num_or_null,
    "t_device": _num,
    # the step the pair above was sampled on (a line repeats the pair
    # until the next sample; the process's first step is never sampled)
    "t_probe_step": _int_like,
    # input wire (data/device_prefetch.py — present when the device
    # prefetch ring is on): last batch's host→device transfer seconds,
    # its uint8 wire bytes, and how many staged batches were resident
    # when the driver consumed the last one (0 = the wire is the
    # bottleneck, depth = the device is)
    "t_transfer": _num,
    "transfer_bytes": _int_like,
    "prefetch_depth_live": _int_like,
    # device memory gauges (null where the backend lacks memory_stats)
    "hbm_live_bytes": _num_or_null,
    "hbm_peak_bytes": _num_or_null,
    # remaining HBM at the live watermark (bytes_limit - live; null
    # where the backend reports no capacity) — the headroom the ZeRO
    # stages compete on
    "hbm_headroom_bytes": _num_or_null,
    # analytic per-device at-rest bytes of the persistent train state
    # (obs/stepstats.py tree_shard_bytes) — backend-independent, so the
    # ZeRO-1 vs ZeRO-2/3 memory A/B works on CPU meshes too
    "hbm_state_bytes": _int_like,
    # token input: the valid tokens of both views this step, over the mesh
    "tokens_per_step": _num,
    # ZeRO-2/3 hoisted-gather overlap efficiency (parallel/zero.py
    # AsyncParamGather): 1 - wait/duration of the gather-side stall the
    # worker absorbed off the critical path (the synthetic
    # delay@site=zero.gather slow collective in the smokes); null when
    # nothing was absorbed — device-side gather/compute overlap is read
    # from the merged trace's zero_gather spans
    "overlap/zero": _num_or_null,
    # layer-granular ZeRO-3 (parallel/zero.py GroupPlan): same gauge
    # under its own key when the per-layer-group gather/free schedule is
    # active, so dashboards can A/B the two stages from the same run set
    "overlap/zero_layer": _num_or_null,
    # analytic per-device PEAK model-param bytes under ZeRO-2/3: shards
    # + the transient gathered full params (whole tree, or the largest
    # adjacent group pair when layer-granular) — the memory-claim gauge
    # that works on CPU meshes where memory_stats is absent
    "hbm_model_peak_bytes": _num_or_null,
    # MoCo health gauges (obs/health.py)
    "ema_drift": _num_or_null,
    "logit_pos_mean": _num_or_null,
    "logit_pos_std": _num_or_null,
    "logit_neg_mean": _num_or_null,
    "logit_neg_std": _num_or_null,
    "feature_std": _num_or_null,
    "feature_dim_active": _num_or_null,
    "queue_age_mean": _num_or_null,
    "queue_age_max": _num_or_null,
    "queue_age_hist": _num_list,
    # fault-tolerance counters (present only when nonzero)
    "nan_steps": _int_like,
    "decode_failures": _int_like,
    "io_retries": _counter_map,
    # mocolint runtime arm (present on every line under --strict-tracing)
    "compile_cache_misses": _int_like,
    # collective-schedule sanitizer (--sanitize-collectives): short hash
    # of this process's traced (site, kind, shape) collective schedule —
    # flat on a healthy run, and every process's must agree
    "collective_schedule_hash": lambda v: isinstance(v, str),
    "watchdog_timeout": _num,
    # serving retrieval tier (serve/server.py): the sampled online
    # recall of the approximate tier vs the exact oracle (a fraction —
    # null until the first sample), the IVF probe width (null when the
    # default tier is exact), whether scoring runs int8 anywhere (0/1),
    # and the streaming-ingest row counter. The generic serve/ prefix
    # family below still applies; these four get the tighter checks.
    "serve/recall_estimate": lambda v: v is None or (_num(v) and 0.0 <= v <= 1.0),
    "serve/nprobe": lambda v: v is None or (_int_like(v) and v >= 1),
    "serve/int8": lambda v: v in (0, 1),
    "serve/ingested_rows": _int_like,
    # raw-speed serving tiers (ISSUE 11): the engine quantization tier
    # (0=off, 1=w8 weight-only, 2=w8a8 activation-quantized int8) and
    # the IVF coarse-quantizer health gauges — rows the inverted file
    # could not place (spill; the exact tier still serves them) and the
    # mean cell fill over cell capacity. Both null until train_ivf runs;
    # ROADMAP names them as the background re-fit trigger.
    "serve/quant_tier": lambda v: v in (0, 1, 2),
    "serve/ivf_spill": lambda v: v is None or (_int_like(v) and v >= 0),
    "serve/ivf_occupancy": lambda v: v is None or (_num(v) and 0.0 <= v <= 1.0),
    # request-scoped serving observability (obs/reqtrace.py, obs/slo.py,
    # obs/flight.py — PR 10): the latency histogram the Prometheus sink
    # exposes with real cumulative buckets, the p99 exemplar linking the
    # latency gauges to the offending request id (a STRING — exempted
    # from the numeric serve/ prefix family below), its latency, the
    # declared SLO objective
    "serve/latency_hist": _latency_hist,
    "serve/p99_exemplar": _str_or_null,
    "serve/p99_exemplar_ms": _nonneg_or_null,
    "serve/slo_objective": lambda v: _num(v) and 0.0 < v < 1.0,
    # served-model identity (obs/quality.py): the checkpoint step the
    # live encoder came from (null when unknown — e.g. a hand-built
    # engine), its params content digest (a STRING, exempted from the
    # numeric serve/ family), and the checkpoint step of the last
    # /ingest block (X-Ckpt-Step; null until a tailer reports one)
    "serve/model_step": lambda v: v is None or _int_like(v),
    "serve/model_digest": _str_or_null,
    "serve/ingest_ckpt_step": lambda v: v is None or _int_like(v),
    # freshness SLO (obs/slo.py FreshnessBurnTracker + index row
    # stamps): wall-clock age of the oldest/mean stamped index row
    # (null while the index has no stamped rows) and the declared
    # max-age objective (strictly positive — a replica without a
    # freshness objective omits the whole family)
    "serve/row_age_max_s": _nonneg_or_null,
    "serve/row_age_mean_s": _nonneg_or_null,
    "serve/fresh_max_age_s": lambda v: _num(v) and v > 0,
    # embedding-space compatibility gauges (obs/quality.py): mean
    # probe cosine between live and candidate encoders, and top-k
    # neighbor overlap against the live index (null = not measured)
    "serve/compat_cosine": lambda v: v is None or (_num(v) and -1.0 <= v <= 1.0),
    "serve/recall_overlap": lambda v: v is None or (_num(v) and 0.0 <= v <= 1.0),
    # elastic rescale event lines (parallel/elastic.py): the lost host
    # indices (list of ints) ride the otherwise-numeric rescale/ family
    "rescale/dead_hosts": _num_list,
    "rescale/old_num_data": _int_like,
    "rescale/new_num_data": _int_like,
    "rescale/old_global_batch": _int_like,
    "rescale/new_global_batch": _int_like,
    # fleet observability (obs/fleet.py; process-0 lines only)
    "fleet_hosts": _int_like,
    "straggler_skew": _num_or_null,
    # serving-fleet router gauges (serve/router.py FleetRouter.stats):
    # topology counts are ints; the objective mirrors serve/slo_objective
    "fleet_serve/replicas": lambda v: _int_like(v) and v >= 1,
    "fleet_serve/replicas_healthy": lambda v: _int_like(v) and v >= 0,
    "fleet_serve/slo_objective": lambda v: _num(v) and 0.0 < v < 1.0,
    # cumulative cost of cancelled hedge lanes (serve/router.py hedge-
    # loser accounting) — a counter in ms, never negative
    "fleet_serve/hedge_wasted_ms": _nonneg_or_null,
    # fleet version skew (serve/router.py stats): distinct served model
    # digests minus one — 0 homogeneous, >0 mid-rollout; null until any
    # replica reports a digest
    "fleet_serve/model_skew": lambda v: v is None or (_int_like(v) and v >= 0),
    # promotion audit lines (serve/promote.py ledger_record): the
    # verdict enum, the pipeline stage, the candidate's params digest,
    # the first failed gate (null on success), and which replica a
    # rollout event refers to (null for fleet-wide lines). Per-gate
    # evidence rides the numeric promotion/ prefix family below.
    "promotion/verdict": lambda v: v in (
        "accepted", "rejected", "promoted", "rolled_back"
    ),
    "promotion/stage": lambda v: isinstance(v, str),
    "promotion/digest": _str_or_null,
    "promotion/failed_gate": _str_or_null,
    "promotion/replica": lambda v: v is None or _int_like(v),
    "promotion/step": _int_like,
    # scaling-law harness verdict lines (scripts/scaling_smoke.py): the
    # per-leg identity and the battery verdict are strings; every other
    # scaling/ field rides the numeric prefix family below
    "scaling/leg": lambda v: isinstance(v, str),
    "scaling/verdict": lambda v: isinstance(v, str),
    # alert event lines (obs/alerts.py)
    "alert": lambda v: isinstance(v, str),
    "severity": lambda v: v in ("warn", "fatal"),
}

# key-prefix families sharing one validator: per-layer-group EMA drift,
# the fleet min/mean/max/argmax gauges (null where no host reports the
# field), comms bytes counters (analytic, always numeric), the per-rule
# Prometheus alert gauges, and the serving metric family
# (serve/server.py flushes ServeMetrics.payload() through the sinks:
# p50_ms/p99_ms null before the first completed request, occupancy null
# before the first flush, the rest numeric — qps, requests,
# slo_violations, slo_ms, bucket_<b> histogram counts)
PREFIX_VALIDATORS = {
    "ema_drift/": _num_or_null,
    # the host's phase account (obs/stepstats.py phase_account): per-step
    # mean seconds under each driver and ring span since the last line,
    # and phase/steps, the steps it covers. Measured on every step, so
    # never null.
    "phase/": _num,
    # an expert layer's routing this step (models/decoder.py
    # routing_metrics): moe/load_max_over_mean, moe/tokens_per_expert, and
    # the rung the dispatch took: moe/bounded_share, moe/buffer_rows
    "moe/": _num,
    # the `setup` event line's parts (obs/stepstats.py setup_account)
    "setup/": _num,
    # elastic rescale event fields (kappa, derived lr/momentum, ...);
    # the explicit entries above (dead_hosts list, int mesh shapes) win
    "rescale/": _num_or_null,
    # scaling-law battery numerics (kappa, ema-drift ratios, logit gap,
    # feature_std floor, peak-bytes legs); the explicit string entries
    # above (scaling/leg, scaling/verdict) win
    "scaling/": _num_or_null,
    "fleet/": _num_or_null,
    "comms/": _num,
    "alert/": _num,
    "serve/": _num_or_null,
    # request-trace stage means (ms) and the multi-window SLO burn-rate
    # family — tighter than the generic serve/ family (burn/stage time
    # can be null while a window is empty, never negative). Longest
    # matching prefix wins (see validate_line), so these shadow serve/.
    "serve/trace_": _nonneg_or_null,
    "serve/burn_rate_": _nonneg_or_null,
    # the freshness-SLO burn twin (obs/slo.py FreshnessBurnTracker
    # payload) — same null-while-empty / never-negative contract
    "serve/fresh_burn_rate_": _nonneg_or_null,
    # the fleet-router family (serve/router.py): latency gauges null
    # before the first proxied request, counters numeric; the burn
    # sub-family (router client-observed + per-replica min/mean/max
    # aggregates) is never negative, like its serve/ twin
    "fleet_serve/": _num_or_null,
    # the router renames each replica's serve/burn_rate_* gauges into
    # this family dynamically ("fleet_serve/" + key.split("/", 1)[1]),
    # so no literal emission exists for JX015 to see; the runtime
    # contract-coverage gate proves the family live instead
    "fleet_serve/burn_rate_": _nonneg_or_null,  # mocolint: disable=JX015
    # the freshness burn aggregates ride the same dynamic rename, so
    # the same no-literal-emission exemption applies
    "fleet_serve/fresh_burn_rate_": _nonneg_or_null,  # mocolint: disable=JX015
    # critical-path hop attribution (obs/critpath.py metrics_payload):
    # mean ms on the request critical path per hop — never negative,
    # null while the aggregation window is empty
    "fleet_serve/critpath_": _nonneg_or_null,
    # promotion-ledger per-gate evidence (serve/promote.py):
    # promotion/gate/<name> measured value (null where a gate could not
    # run), promotion/floor/<name> declared threshold,
    # promotion/gate_ok/<name> 0/1 — the explicit entries above
    # (verdict/stage/digest/...) take precedence over this family
    "promotion/": _num_or_null,
}


def _reject_nonfinite(val: str):
    raise ValueError(f"non-finite JSON literal {val!r} (writer must scrub to null)")


def loads_strict(line: str) -> dict:
    """json.loads that rejects NaN/Infinity literals — the writer's
    scrub-to-null contract, enforced at parse time."""
    rec = json.loads(line, parse_constant=_reject_nonfinite)
    if not isinstance(rec, dict):
        raise ValueError("metrics line is not a JSON object")
    return rec


# Runtime contract-coverage arm (analysis/contracts.py): when a
# callback is installed, every validator that actually applies to a
# line — explicit field key or winning prefix family — is reported, so
# a smoke leg can prove its metrics stream still exercises the schema
# entries it claims to. None-checked per use: zero cost when off.
_COVERAGE_CB = None


def set_coverage_callback(cb) -> None:
    """Install/clear the `cb(validator_key)` applied-validator callback."""
    global _COVERAGE_CB
    _COVERAGE_CB = cb


def validate_line(rec: dict) -> list[str]:
    """Schema errors for one parsed line (empty list = valid)."""
    errors = []
    for k in ("step", "time"):
        if k not in rec:
            errors.append(f"missing required key {k!r}")
    if "event" in rec:
        if rec["event"] not in EVENT_KINDS:
            errors.append(f"unknown event kind {rec['event']!r}")
        if "loss" in rec:
            errors.append("event line must not carry metric field 'loss'")
    elif "loss" in rec:
        missing = [k for k in TRAIN_REQUIRED if k not in rec]
        if missing:
            errors.append(f"training line missing {missing}")
    for k, check in FIELD_VALIDATORS.items():
        if k in rec:
            if _COVERAGE_CB is not None:
                _COVERAGE_CB(k)
            if not check(rec[k]):
                errors.append(f"field {k!r} has invalid value {rec[k]!r}")
    # prefix families (ema_drift/<group>, fleet/<field>_<stat>,
    # comms/<site>, alert/<rule>, serve/...) share per-family
    # validators. An explicit FIELD_VALIDATORS entry wins outright
    # (serve/p99_exemplar is a string inside the numeric serve/
    # family); otherwise the LONGEST matching prefix applies, so
    # serve/burn_rate_* gets its non-negative check rather than the
    # looser serve/ one.
    for k, v in rec.items():
        if k in FIELD_VALIDATORS:
            continue
        matches = [p for p in PREFIX_VALIDATORS if k.startswith(p)]
        if matches:
            winner = max(matches, key=len)
            if _COVERAGE_CB is not None:
                _COVERAGE_CB(winner)
            if not PREFIX_VALIDATORS[winner](v):
                errors.append(f"field {k!r} has invalid value {v!r}")
    return errors


def validate_lines(lines: Iterable[str]) -> list[str]:
    """Errors across a whole metrics.jsonl body, tagged with 1-based
    line numbers. Parse failures (including NaN literals) are schema
    errors, not exceptions."""
    errors = []
    for i, line in enumerate(lines, 1):
        if not line.strip():
            continue
        try:
            rec = loads_strict(line)
        except ValueError as e:
            errors.append(f"line {i}: unparseable: {e}")
            continue
        errors.extend(f"line {i}: {e}" for e in validate_line(rec))
    return errors


def validate_file(path: str) -> list[str]:
    with open(path) as f:
        return validate_lines(f)


def read_metrics(path: str, strict: bool = True) -> list[dict]:
    """Parsed records of a metrics.jsonl — the loader obs_report builds
    on. `strict=True` raises on NaN literals / junk lines; with
    `strict=False` bad lines are skipped (the report of a crashed run
    must still render — validate_file reports them separately)."""
    out = []
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            try:
                out.append(loads_strict(line))
            except ValueError:
                if strict:
                    raise
    return out


def required_train_keys(strict_tracing: bool = False) -> tuple:
    """The keys every training line must carry (README contract);
    `strict_tracing` adds the always-present compile counter."""
    base = TRAIN_REQUIRED + ("t_data", "t_step", "hbm_live_bytes")
    return base + ("compile_cache_misses",) if strict_tracing else base
