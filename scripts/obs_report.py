#!/usr/bin/env python
"""Post-run telemetry report: one readable summary from a run's JSONL.

    python scripts/obs_report.py WORKDIR            # or a metrics.jsonl path
    python scripts/obs_report.py WORKDIR --output report.md
    python scripts/obs_report.py WORKDIR --strict   # exit 1 on schema errors

Renders, from `metrics.jsonl` (+ per-process `metrics.p<i>.jsonl`
siblings, `trace.json`, `alerts.jsonl`, and `heartbeat.p*.json` when
present):

- run shape: steps/epochs covered, wall time, logging cadence;
- step-time breakdown: where the average step went (data wait vs
  dispatch vs device compute), as an ASCII "pie";
- fleet view: straggler skew trend, the fleet-max step time vs the
  mean, the most-blamed host, and a per-host heartbeat table that
  flags hosts whose heartbeat went stale (died mid-run) — merged from
  the out-of-band heartbeat files, so a dead host still appears;
- comms: per-collective-site analytic wire bytes per step (from the
  `comms/*` counters) with a share-of-total bar;
- serving: the request-stage waterfall as an ASCII pie (from the
  `serve/trace_<stage>_ms` window means), qps/p99 trends, the SLO
  burn-rate curve per window (`serve/burn_rate_*` sparkline), and the
  top-N slowest requests with their full stage waterfalls from the
  newest flight-recorder dump (`flight_*.json`) when one exists;
- model quality & freshness: the served model's identity (checkpoint
  step + params digest + last ingested step), compatibility gauges
  (`serve/compat_cosine`, `serve/recall_overlap`), the index row-age
  trend vs the declared freshness objective with the
  `serve/fresh_burn_rate_*` sparklines, the fleet's version-skew
  trend, and every `promotions.jsonl` verdict with its failing gate;
- alerts: every fired alert from alerts.jsonl, grouped by rule;
- training-health trends: loss/accuracy, EMA drift, InfoNCE pos/neg
  logit margin, feature-collapse gauges, queue staleness — first→last
  with min/max, so a drifting gauge is visible without plotting;
- device memory: peak HBM seen (or "not reported by backend");
- fault ledger: NaN steps, decode failures, per-site I/O retries,
  compile-cache misses, and every event line verbatim;
- trace summary: total/self time by span name from the Chrome trace.

When the source is a workdir, co-hosted processes' metrics files are
globbed and merged (the per-process-filename satellite); `--strict`
validates EVERY file against the schema.

Needs only the stdlib + moco_tpu.obs.schema (no jax import, so it runs
on any machine the JSONL was copied to). CI's obs-smoke step runs this
against the driver smoke's artifacts on every PR, so report rendering
cannot rot.
"""

from __future__ import annotations

import argparse
import glob as globmod
import json
import os
import sys

# allow running from a checkout without installation
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from moco_tpu.obs import schema  # noqa: E402


BAR_WIDTH = 36


def _bar(frac: float, width: int = BAR_WIDTH) -> str:
    n = max(0, min(width, round(frac * width)))
    return "#" * n + "." * (width - n)


def _fmt(v) -> str:
    if v is None:
        return "null"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


_SPARK_CHARS = " .:-=+*#%@"


def _spark(vals: list, width: int = 32) -> str:
    """Tiny ASCII sparkline of a series (downsampled to `width`), scaled
    to its own max — the burn-rate curve without a plotting dep."""
    if not vals:
        return ""
    if len(vals) > width:
        step = len(vals) / width
        vals = [vals[int(i * step)] for i in range(width)]
    top = max(max(vals), 1e-12)
    idx = [min(int(v / top * (len(_SPARK_CHARS) - 1) + 0.5), len(_SPARK_CHARS) - 1)
           for v in vals]
    return "[" + "".join(_SPARK_CHARS[i] for i in idx) + "]"


def _trend(lines: list[dict], key: str) -> str | None:
    vals = [(r["step"], r[key]) for r in lines if isinstance(r.get(key), (int, float))]
    if not vals:
        return None
    nums = [v for _, v in vals]
    first, last = vals[0][1], vals[-1][1]
    return (
        f"{_fmt(first)} -> {_fmt(last)}"
        f"  (min {_fmt(min(nums))}, max {_fmt(max(nums))}, n={len(nums)})"
    )


def _flight_dumps(workdir: str | None, role: str | None) -> list[tuple[str, dict]]:
    """(path, dump) for every parseable flight_*.json under `workdir`,
    oldest first, filtered by the dump's `role` stamp — `"router"` for
    the fleet router's stitched-waterfall dumps, None for a replica's
    own (unstamped or role="serve") dumps."""
    out = []
    for path in sorted(globmod.glob(os.path.join(workdir, "flight_*.json"))) if workdir else []:
        try:
            with open(path) as f:
                dump = json.load(f)
        except (ValueError, OSError):
            continue
        if (dump.get("role") == "router") == (role == "router"):
            out.append((path, dump))
    return out


def _promotion_ledger(workdir: str | None) -> list[dict]:
    """Parsed `promotions.jsonl` verdict lines (oldest first), [] when
    the run has no promotion ledger. Tolerant parse — the report must
    render even next to a half-written ledger."""
    if not workdir:
        return []
    path = os.path.join(workdir, "promotions.jsonl")
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("event") == "promotion":
                out.append(rec)
    return out


def metrics_paths_for(source: str) -> list[str]:
    """All per-process metrics files of a workdir (process 0's
    `metrics.jsonl` first), or the single file the caller named."""
    if not os.path.isdir(source):
        return [source]
    paths = []
    base = os.path.join(source, "metrics.jsonl")
    if os.path.exists(base):
        paths.append(base)
    paths.extend(sorted(globmod.glob(os.path.join(source, "metrics.p*.jsonl"))))
    return paths


def render_report(
    metrics_path: str | list[str],
    trace_path: str | None = None,
    workdir: str | None = None,
) -> str:
    paths = [metrics_path] if isinstance(metrics_path, str) else list(metrics_path)
    records = []
    for p in paths:
        records.extend(schema.read_metrics(p, strict=False))
    if len(paths) > 1:  # merged multi-process view: one timeline
        records.sort(key=lambda r: (r.get("time", 0.0), r.get("step", 0)))
    train_lines = [r for r in records if "loss" in r and "event" not in r]
    events = [r for r in records if "event" in r]
    out: list[str] = []
    w = out.append

    src = paths[0] if len(paths) == 1 else f"{len(paths)} per-process files"
    w("# Telemetry report")
    w("")
    w(f"source: `{src}` — {len(records)} lines "
      f"({len(train_lines)} training, {len(events)} events)")
    if not records:
        w("")
        w("(empty metrics file — nothing to report)")
        return "\n".join(out)
    steps = [r["step"] for r in records]
    wall = records[-1]["time"] - records[0]["time"]
    epochs = sorted({r["epoch"] for r in records if "epoch" in r})
    w(f"steps {min(steps)}..{max(steps)}"
      + (f", epochs {epochs[0]}..{epochs[-1]}" if epochs else "")
      + f", {wall:.1f}s of wall time between first and last line")
    w("")

    # -- step-time breakdown --------------------------------------------
    w("## Step-time breakdown")
    w("")
    t_data = [r["t_data"] for r in train_lines if isinstance(r.get("t_data"), (int, float))]
    t_step = [r["t_step"] for r in train_lines if isinstance(r.get("t_step"), (int, float))]
    if t_step:
        mean_step = sum(t_step) / len(t_step)
        mean_data = sum(t_data) / len(t_data) if t_data else 0.0
        other = max(mean_step - mean_data, 0.0)
        w(f"mean logged step: {mean_step * 1e3:.1f} ms")
        for name, sec in (("data wait", mean_data), ("dispatch+device", other)):
            frac = sec / mean_step if mean_step else 0.0
            w(f"  {name:<16} {_bar(frac)} {frac * 100:5.1f}%  ({sec * 1e3:.1f} ms)")
        disp = [r["t_dispatch"] for r in train_lines
                if isinstance(r.get("t_dispatch"), (int, float))]
        dev = [r["t_device"] for r in train_lines
               if isinstance(r.get("t_device"), (int, float))]
        if dev:
            w(f"  probe samples: dispatch {sum(disp) / len(disp) * 1e3:.1f} ms, "
              f"device {sum(dev) / len(dev) * 1e3:.1f} ms "
              f"(block_until_ready on {len(dev)} sampled lines)")
    else:
        w("(no t_step fields — run predates the telemetry layer?)")
    w("")

    # -- input wire (device prefetch ring) -------------------------------
    t_xfer = [r["t_transfer"] for r in train_lines
              if isinstance(r.get("t_transfer"), (int, float))]
    if t_xfer:
        xbytes = [r["transfer_bytes"] for r in train_lines
                  if isinstance(r.get("transfer_bytes"), (int, float))]
        depth = [r["prefetch_depth_live"] for r in train_lines
                 if isinstance(r.get("prefetch_depth_live"), (int, float))]
        mean_xfer = sum(t_xfer) / len(t_xfer)
        w("## Input wire (device prefetch ring)")
        w("")
        w(f"mean transfer: {mean_xfer * 1e3:.1f} ms/batch"
          + (f" ({sum(xbytes) / len(xbytes) / 1e6:.1f} MB -> "
             f"{sum(xbytes) / len(xbytes) / 1e6 / max(mean_xfer, 1e-9):.0f} MB/s"
             if xbytes else "")
          + ")")
        if depth:
            starved = sum(1 for d in depth if d == 0)
            w(f"staged depth at consume: mean {sum(depth) / len(depth):.1f}, "
              f"empty on {starved}/{len(depth)} lines "
              "(empty = the wire or the host is the bottleneck; "
              "full = the device is)")
        if t_step:
            frac = mean_xfer / mean_step if mean_step else 0.0
            w(f"wire/step ratio: {frac * 100:.0f}% "
              "(>100% means transfer bounds throughput even when overlapped)")
        w("")

    # -- fleet view ------------------------------------------------------
    skew = _trend(train_lines, "straggler_skew")
    hosts = [r["fleet_hosts"] for r in train_lines if isinstance(r.get("fleet_hosts"), int)]
    beats = {}
    if workdir:
        from moco_tpu.obs.fleet import read_heartbeats

        beats = read_heartbeats(workdir)
    if skew or hosts or beats:
        w("## Fleet")
        w("")
        if hosts:
            w(f"hosts reporting: {max(hosts)}")
        if skew:
            w(f"- `straggler_skew`: {skew}")
        tmax = _trend(train_lines, "fleet/t_step_max")
        tmean = _trend(train_lines, "fleet/t_step_mean")
        if tmax:
            w(f"- `fleet/t_step_max`: {tmax}")
        if tmean:
            w(f"- `fleet/t_step_mean`: {tmean}")
        blames = [r["fleet/t_step_argmax"] for r in train_lines
                  if isinstance(r.get("fleet/t_step_argmax"), int)]
        if blames:
            worst = max(set(blames), key=blames.count)
            w(f"- slowest host (mode of `fleet/t_step_argmax`): "
              f"host {worst} on {blames.count(worst)}/{len(blames)} lines")
        if beats:
            newest = max(b.get("time", 0.0) for b in beats.values())
            w("")
            w("heartbeats (out-of-band; a stale one means the host died mid-run):")
            for p in sorted(beats):
                b = beats[p]
                lag = newest - b.get("time", 0.0)
                flag = "  ** STALE — host died mid-run? **" if lag > 60.0 else ""
                w(f"- host {p} ({b.get('host', '?')}): last beat at step "
                  f"{b.get('step', '?')}, {lag:.0f}s behind the newest{flag}")
        w("")

    # -- comms (analytic wire bytes per collective site) -----------------
    comms_line = next(
        (r for r in reversed(train_lines)
         if any(k.startswith("comms/") and k != "comms/total" for k in r)),
        None,
    )
    if comms_line:
        w("## Comms (analytic wire bytes per device, per step)")
        w("")
        sites = {
            k[len("comms/"):]: v for k, v in comms_line.items()
            if k.startswith("comms/") and k != "comms/total"
            and isinstance(v, (int, float))
        }
        total = sum(sites.values()) or 1.0
        for name, nbytes in sorted(sites.items(), key=lambda kv: -kv[1]):
            frac = nbytes / total
            w(f"  {name:<28} {_bar(frac)} {frac * 100:5.1f}%  "
              f"({nbytes / 2**20:.2f} MiB/step)")
        w(f"  total: {total / 2**20:.2f} MiB/step per device "
          f"(collective cost model: moco_tpu/obs/comms.py)")
        w("")

    # -- serving (request-scoped observability) --------------------------
    serve_lines = [r for r in records if any(k.startswith("serve/") for k in r)]
    if serve_lines:
        w("## Serving")
        w("")
        last = serve_lines[-1]
        reqs = last.get("serve/requests")
        if isinstance(reqs, (int, float)):
            w(f"requests: {int(reqs)}, slo {_fmt(last.get('serve/slo_ms'))} ms "
              f"(objective {_fmt(last.get('serve/slo_objective'))}), "
              f"violations {_fmt(last.get('serve/slo_violations'))}")
        for key in ("serve/qps", "serve/p99_ms", "serve/p50_ms", "serve/occupancy"):
            t = _trend(serve_lines, key)
            if t is not None:
                w(f"- `{key}`: {t}")
        ex = next(
            (r["serve/p99_exemplar"] for r in reversed(serve_lines)
             if isinstance(r.get("serve/p99_exemplar"), str)),
            None,
        )
        if ex is not None:
            w(f"- worst recent request (p99 exemplar): `{ex}`")
        # stage waterfall pie: the latest line carrying trace means
        stage_line = next(
            (r for r in reversed(serve_lines)
             if any(k.startswith("serve/trace_") and k.endswith("_ms") for k in r)),
            None,
        )
        if stage_line:
            stages = {
                k[len("serve/trace_"):-len("_ms")]: v
                for k, v in stage_line.items()
                if k.startswith("serve/trace_") and k.endswith("_ms")
                and isinstance(v, (int, float))
            }
            total = sum(stages.values()) or 1.0
            w("")
            w("stage waterfall (mean ms/request, latest window):")
            for name, ms in sorted(stages.items(), key=lambda kv: -kv[1]):
                frac = ms / total
                w(f"  {name:<16} {_bar(frac)} {frac * 100:5.1f}%  ({ms:.1f} ms)")
        # burn-rate curve: one sparkline per window
        burn_keys = sorted(
            {k for r in serve_lines for k in r if k.startswith("serve/burn_rate_")}
        )
        for key in burn_keys:
            vals = [r[key] for r in serve_lines if isinstance(r.get(key), (int, float))]
            if vals:
                w(f"- `{key}`: {_spark(vals)}  last {_fmt(vals[-1])} "
                  f"(max {_fmt(max(vals))}; >1 = burning budget faster "
                  "than the SLO period sustains)")
        # top-N slowest requests from the newest REPLICA flight dump
        # (router dumps carry role="router" and render in Fleet tracing)
        if _flight_dumps(workdir, role=None):
            path, dump = _flight_dumps(workdir, role=None)[-1]
            if dump.get("slowest"):
                w("")
                w(f"slowest requests (flight recorder `{os.path.basename(path)}`, "
                  f"reason: {dump.get('reason', '?')}):")
                for wf in dump["slowest"][:5]:
                    stages_str = " ".join(
                        f"{s['stage']}={s['dur_ms']:.0f}ms"
                        for s in wf.get("stages", [])
                    )
                    w(f"- `{wf.get('request_id', '?')}` "
                      f"({wf.get('total_ms', 0):.0f} ms, {wf.get('rows', '?')} rows): "
                      f"{stages_str}")
        w("")

    # -- model quality & freshness (the train->serve loop) ----------------
    quality_lines = [
        r for r in records
        if any(
            k in r
            for k in (
                "serve/model_step", "serve/compat_cosine", "serve/fresh_max_age_s",
            )
        )
    ]
    promotions = _promotion_ledger(workdir)
    if quality_lines or promotions:
        w("## Model quality & freshness")
        w("")
        last = quality_lines[-1] if quality_lines else {}
        if last.get("serve/model_step") is not None or last.get("serve/model_digest"):
            w(f"served model: step {_fmt(last.get('serve/model_step'))}, "
              f"digest `{_fmt(last.get('serve/model_digest'))}`, "
              f"last ingested block from step "
              f"{_fmt(last.get('serve/ingest_ckpt_step'))}")
        for key in ("serve/compat_cosine", "serve/recall_overlap"):
            t = _trend(quality_lines, key)
            if t is not None:
                w(f"- `{key}`: {t}")
        fresh_obj = last.get("serve/fresh_max_age_s")
        if isinstance(fresh_obj, (int, float)):
            w(f"- freshness objective: rows no older than {_fmt(fresh_obj)}s")
            for key in ("serve/row_age_max_s", "serve/row_age_mean_s"):
                t = _trend(quality_lines, key)
                if t is not None:
                    w(f"- `{key}`: {t}")
        fresh_keys = sorted(
            {k for r in quality_lines for k in r
             if k.startswith("serve/fresh_burn_rate_")}
        )
        for key in fresh_keys:
            vals = [r[key] for r in quality_lines
                    if isinstance(r.get(key), (int, float))]
            if vals:
                w(f"- `{key}`: {_spark(vals)}  last {_fmt(vals[-1])} "
                  f"(max {_fmt(max(vals))}; >1 = the index is going stale "
                  "faster than the objective sustains)")
        skew = _trend(
            [r for r in records if "fleet_serve/model_skew" in r],
            "fleet_serve/model_skew",
        )
        if skew is not None:
            w(f"- `fleet_serve/model_skew`: {skew} "
              "(0 = every replica serves the same encoder)")
        if promotions:
            w("")
            w("promotion ledger (append-only, newest last):")
            for p in promotions[-10:]:
                gate = p.get("promotion/failed_gate")
                detail = ""
                if gate:
                    val = p.get(f"promotion/gate/{gate}")
                    floor = p.get(f"promotion/floor/{gate}")
                    detail = f" — failed `{gate}`" + (
                        f" ({_fmt(val)} vs floor {_fmt(floor)})"
                        if val is not None else ""
                    )
                w(f"- step {p.get('promotion/step', '?')} "
                  f"`{_fmt(p.get('promotion/digest'))}`: "
                  f"**{p.get('promotion/verdict', '?')}** "
                  f"at {p.get('promotion/stage', '?')}{detail}")
            if len(promotions) > 10:
                w(f"- ... {len(promotions) - 10} earlier entries in "
                  "promotions.jsonl")
        w("")

    # -- fleet tracing (stitched distributed waterfalls) ------------------
    fleet_lines = [
        r for r in records if any(k.startswith("fleet_serve/") for k in r)
    ]
    if fleet_lines:
        w("## Fleet tracing")
        w("")
        last = fleet_lines[-1]
        reqs = last.get("fleet_serve/requests")
        if isinstance(reqs, (int, float)):
            w(f"requests through the front door: {int(reqs)}, "
              f"slo {_fmt(last.get('fleet_serve/slo_ms'))} ms, "
              f"p99 {_fmt(last.get('fleet_serve/p99_ms'))} ms")
        # critical-path pie: which hop of the distributed request ate
        # the milliseconds (obs/critpath.py attribution, latest window)
        crit_line = next(
            (r for r in reversed(fleet_lines)
             if any(k.startswith("fleet_serve/critpath_") for k in r)),
            None,
        )
        if crit_line:
            hops = {
                k[len("fleet_serve/critpath_"):-len("_ms")]: v
                for k, v in crit_line.items()
                if k.startswith("fleet_serve/critpath_") and k.endswith("_ms")
                and isinstance(v, (int, float))
            }
            total = sum(hops.values()) or 1.0
            w("")
            w("critical path (mean ms/request, latest window):")
            for name, ms in sorted(hops.items(), key=lambda kv: -kv[1]):
                frac = ms / total
                w(f"  {name:<22} {_bar(frac)} {frac * 100:5.1f}%  ({ms:.1f} ms)")
        hedges = last.get("fleet_serve/hedges")
        if isinstance(hedges, (int, float)) and hedges:
            wins = last.get("fleet_serve/hedge_wins") or 0
            w(f"- hedges: {int(hedges)} (win rate {wins / hedges * 100:.0f}%); "
              f"{_fmt(last.get('fleet_serve/hedge_wasted_ms'))} ms burned in "
              "cancelled loser lanes")
        retries = last.get("fleet_serve/retries")
        if isinstance(retries, (int, float)) and retries:
            retry_ms = (
                crit_line.get("fleet_serve/critpath_retry_failed_ms")
                if crit_line else None
            )
            w(f"- retries: {int(retries)}; failed-attempt wait on the "
              f"critical path: {_fmt(retry_ms)} ms (mean over traced requests)")
        # top-5 slowest stitched multi-hop waterfalls
        router_dumps = _flight_dumps(workdir, role="router")
        if router_dumps and router_dumps[-1][1].get("slowest"):
            path, dump = router_dumps[-1]
            w("")
            w(f"slowest distributed waterfalls (router flight "
              f"`{os.path.basename(path)}`, reason: {dump.get('reason', '?')}):")
            for wf in dump["slowest"][:5]:
                stages_str = " ".join(
                    f"{s['stage']}={s['dur_ms']:.0f}ms"
                    for s in wf.get("stages", [])
                )
                w(f"- `{wf.get('trace_id', '?')}` -> "
                  f"`{wf.get('request_id', '?')}` "
                  f"({wf.get('total_ms', 0):.0f} ms, "
                  f"status {wf.get('status', '?')}, "
                  f"{len(wf.get('attempts') or ())} attempt(s)): {stages_str}")
        w("")

    # -- alerts ----------------------------------------------------------
    alerts = []
    if workdir:
        from moco_tpu.obs.alerts import read_alerts

        alerts = read_alerts(os.path.join(workdir, "alerts.jsonl"))
    if alerts:
        w("## Alerts")
        w("")
        by_rule: dict[str, int] = {}
        for a in alerts:
            by_rule[a.get("rule", "?")] = by_rule.get(a.get("rule", "?"), 0) + 1
        w("fired: " + ", ".join(f"`{r}` x{n}" for r, n in sorted(by_rule.items())))
        for a in alerts[:20]:
            w(f"- [{a.get('severity', '?')}] step {a.get('step', '?')} "
              f"`{a.get('rule', '?')}`: {a.get('message', '')}")
        if len(alerts) > 20:
            w(f"- ... {len(alerts) - 20} more in alerts.jsonl")
        w("")

    # -- device memory ---------------------------------------------------
    w("## Device memory")
    w("")
    hbm = [r["hbm_peak_bytes"] for r in train_lines
           if isinstance(r.get("hbm_peak_bytes"), (int, float))]
    live = [r["hbm_live_bytes"] for r in train_lines
            if isinstance(r.get("hbm_live_bytes"), (int, float))]
    if hbm or live:
        if hbm:
            w(f"peak HBM: {max(hbm) / 2**30:.2f} GiB")
        if live:
            w(f"live bytes, last line: {live[-1] / 2**30:.2f} GiB")
    else:
        w("not reported by backend (hbm gauges are null — a CPU run)")
    w("")

    # -- health trends ---------------------------------------------------
    w("## Training health (first -> last)")
    w("")
    for key in (
        "loss", "acc1", "acc5", "lr", "knn_top1",
        "ema_drift", "logit_pos_mean", "logit_neg_mean",
        "logit_pos_std", "logit_neg_std",
        "feature_std", "feature_dim_active",
        "queue_age_mean", "queue_age_max",
    ):
        # knn_top1 rides aux lines, not train lines
        src = records if key == "knn_top1" else train_lines
        t = _trend(src, key)
        if t is not None:
            w(f"- `{key}`: {t}")
    groups = sorted(
        {k for r in train_lines for k in r if k.startswith("ema_drift/")}
    )
    for g in groups:
        t = _trend(train_lines, g)
        if t is not None:
            w(f"- `{g}`: {t}")
    pos = _trend(train_lines, "logit_pos_mean")
    if pos is None:
        w("- (no health gauges on these lines — --no-health-metrics run?)")
    w("")

    # -- fault ledger ----------------------------------------------------
    w("## Fault ledger")
    w("")
    ledger = []
    nan = [r["nan_steps"] for r in records if "nan_steps" in r]
    if nan:
        ledger.append(f"- non-finite loss steps: {max(nan)}")
    dec = [r["decode_failures"] for r in records if "decode_failures" in r]
    if dec:
        ledger.append(f"- decode failures (cumulative): {max(dec)}")
    io: dict[str, int] = {}
    for r in records:
        for site, n in (r.get("io_retries") or {}).items():
            io[site] = max(io.get(site, 0), n)
    if io:
        ledger.append(f"- io retries by site: {io}")
    ccm = [r["compile_cache_misses"] for r in records if "compile_cache_misses" in r]
    if ccm:
        flat = " (flat after warmup)" if len(set(ccm[1:])) <= 1 else " (STILL RISING)"
        ledger.append(f"- compile cache misses: last={ccm[-1]}{flat}")
    for e in events:
        ledger.append(f"- event @ step {e['step']}: {e['event']}")
    w("\n".join(ledger) if ledger else "clean run — no faults, no events.")
    w("")

    # -- trace summary ---------------------------------------------------
    if trace_path and os.path.exists(trace_path):
        w("## Trace summary (Chrome trace; open in ui.perfetto.dev)")
        w("")
        with open(trace_path) as f:
            trace = json.load(f)
        totals: dict[str, tuple[float, int]] = {}
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            t, n = totals.get(ev["name"], (0.0, 0))
            totals[ev["name"]] = (t + ev.get("dur", 0.0), n + 1)
        for name, (dur, n) in sorted(totals.items(), key=lambda kv: -kv[1][0])[:12]:
            w(f"- `{name}`: {dur / 1e6:.2f}s total over {n} spans")
        w("")
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("source", help="run workdir, or a metrics.jsonl path")
    ap.add_argument("--trace", default=None, help="chrome trace json (default: <workdir>/trace.json)")
    ap.add_argument("--output", "-o", default=None, help="write the report here (default: stdout)")
    ap.add_argument(
        "--strict", action="store_true",
        help="validate every line against the schema; exit 1 on violations",
    )
    args = ap.parse_args()

    trace_path = args.trace
    workdir = None
    if os.path.isdir(args.source):
        workdir = args.source
        if trace_path is None:
            # prefer the multi-process merged trace when one was built
            for cand in ("merged_trace.json", "trace.json"):
                cand = os.path.join(workdir, cand)
                if os.path.exists(cand):
                    trace_path = cand
                    break
        metrics_paths = metrics_paths_for(workdir)
    else:
        metrics_paths = [args.source]
    missing = [p for p in metrics_paths if not os.path.exists(p)]
    if missing or not metrics_paths:
        print(f"error: {missing or args.source} not found", file=sys.stderr)
        return 2

    errors = []
    for p in metrics_paths:
        tag = f"{os.path.basename(p)}: " if len(metrics_paths) > 1 else ""
        errors.extend(tag + e for e in schema.validate_file(p))
    report = render_report(metrics_paths, trace_path, workdir=workdir)
    if errors:
        report += "\n## Schema violations\n\n" + "\n".join(f"- {e}" for e in errors) + "\n"
    if args.output:
        with open(args.output, "w") as f:
            f.write(report + "\n")
        print(f"wrote {args.output}")
    else:
        print(report)
    if errors:
        print(f"{len(errors)} schema violation(s)", file=sys.stderr)
        return 1 if args.strict else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
