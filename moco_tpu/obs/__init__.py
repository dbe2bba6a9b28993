"""moco_tpu.obs — the telemetry layer.

One cross-cutting subsystem, five parts (see each module's docstring):

- `trace`     hierarchical span tracer + Chrome-trace (Perfetto) export
- `stepstats` step-time breakdown probe + device-memory gauges
- `health`    jitted MoCo training-health reductions (EMA drift, logit
              stats, collapse detection, queue staleness)
- `sinks`     pluggable metric sinks (JSONL/CSV/TensorBoard/Prometheus
              `/metrics` HTTP endpoint) behind one write() surface
- `schema`    the machine-checkable metrics.jsonl line contract
- `fleet`     cross-host stats aggregation + out-of-band heartbeats
- `comms`     named collective sites + analytic bytes-moved counters
- `alerts`    declarative in-stream alert rules -> alerts.jsonl
- `reqtrace`  request-scoped stage-stamped traces for the serving stack
- `slo`       multi-window SLO burn-rate accounting over `slo_ms`
- `flight`    tail-latency flight recorder (bounded ring + atomic dump)

`span`/`instant` are re-exported eagerly because they are the
high-traffic wiring surface (`from moco_tpu import obs; obs.span(...)`)
and, like `trace` and `schema`, are stdlib-only. Everything touching
jax (`sinks`, `stepstats`, `health`) resolves lazily, so report tooling
can `import moco_tpu.obs.schema` on a machine without a backend."""

from moco_tpu.obs.trace import (  # stdlib-only, eager
    Tracer,
    counter,
    get_tracer,
    instant,
    set_annotator,
    set_tracer,
    span,
    spans_to_chrome_events,
)

_LAZY = {
    "Sink": "sinks",
    "JsonlSink": "sinks",
    "CsvSink": "sinks",
    "TensorBoardSink": "sinks",
    "PrometheusSink": "sinks",
    "MultiSink": "sinks",
    "build_sinks": "sinks",
    "register_sink": "sinks",
    "gather_payload": "sinks",
    "sanitize": "sinks",
    "StepTimeProbe": "stepstats",
    "device_memory_stats": "stepstats",
    "memory_payload": "stepstats",
    "health_summary": "health",
    # fleet observability (obs/fleet.py — jax) + comms ledger + alerts
    "FleetAggregator": "fleet",
    "Heartbeat": "fleet",
    "read_heartbeats": "fleet",
    "AlertEngine": "alerts",
    "FatalAlertError": "alerts",
    "parse_rules": "alerts",
    # request-scoped serving observability (all stdlib-only, lazy for
    # symmetry with the other non-eager modules)
    "RequestTrace": "reqtrace",
    "RequestIdAllocator": "reqtrace",
    "emit_request_spans": "reqtrace",
    "SLOBurnTracker": "slo",
    "serve_alert_spec": "slo",
    "FlightRecorder": "flight",
    "read_flight_dumps": "flight",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(f"moco_tpu.obs.{_LAZY[name]}"), name)
    raise AttributeError(f"module 'moco_tpu.obs' has no attribute {name!r}")


__all__ = [
    "Tracer",
    "counter",
    "get_tracer",
    "set_annotator",
    "set_tracer",
    "span",
    "instant",
    "spans_to_chrome_events",
    *sorted(_LAZY),
]
