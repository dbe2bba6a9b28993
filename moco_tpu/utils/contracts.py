"""Single source of truth for the repo's stringly-typed contracts.

The fleet (router <-> replicas <-> ingest <-> chaos harnesses) is wired
together by literals: magic exit codes, the port-offset rule, HTTP
routes and their required headers, and fault-grammar site names. Each
of those used to live wherever it was first needed (the stall code in
`utils/watchdog.py`, the kill code in `utils/faults.py`, the rescale
code in `parallel/elastic.py`, the serve-port stride in
`obs/sinks.py`), which is exactly how contracts drift: a test hard-
codes 42, a handler grows a route the router never learns about, a
`slow@site=` spec outlives the hook it targeted.

This module hosts the constants; the original homes re-export them so
existing imports (`from moco_tpu.utils.faults import KILL_EXIT_CODE`)
keep working. mocolint v4 (JX015-JX018, `analysis/contracts.py`) lints
the tree against these registries, and the `--contract-coverage`
runtime arm records which entries actually fire during the smoke legs.

Adding a metric family, HTTP route, or fault site? Ship the registry
entry in the same change (see CONTRIBUTING.md) or JX016/JX017 will flag
the orphan.

Stdlib-only, import-light: this is imported by `utils/faults.py` and
the analyzer alike.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# exit codes
#
# Names kept verbatim from their original homes; the `EXIT_CODES` map is
# what the chaos harnesses and JX018 key on.

STALL_EXIT_CODE = 42  # utils/watchdog.py: watchdog fired, no heartbeat
RESCALE_EXIT_CODE = 75  # parallel/elastic.py: durable save done, relaunch me
KILL_EXIT_CODE = 113  # utils/faults.py: kill@replica / kill@host sudden death

EXIT_CODES = {
    "stall": STALL_EXIT_CODE,
    "rescale": RESCALE_EXIT_CODE,
    "kill": KILL_EXIT_CODE,
}

# ---------------------------------------------------------------------------
# port-offset rule (obs/sinks.py holds the arithmetic; this is the knob)
#
# Prometheus owns `metrics_port + process_index`; the serve endpoint
# claims `serve_port + process_index` and shifts up by the stride when
# the two bases collide. derive_metrics_port / resolve_serve_port in
# obs/sinks.py are the ONLY sanctioned implementations (JX018 flags
# hand-computed offsets anywhere else).

SERVE_PORT_STRIDE = 16

# ---------------------------------------------------------------------------
# HTTP routes
#
# route -> (methods, required request headers, idempotent?, which server
# handles it). "replica" = serve/server.py ServeServer, "router" =
# serve/router.py FleetRouter, "both" = the router proxies or mirrors
# the replica surface. `idempotent` is the retry/hedge contract: the
# router may retry and hedge exactly these routes and nothing else —
# in particular it must NEVER retry /ingest (appends queue rows; the
# fan-out writer in scripts/serve_ingest.py owns its own idempotence
# via row-count reconciliation).
#
# `opt_headers` are the PROPAGATED headers (obs/ctxprop.py): a plain
# client may omit them, but every handler of the route must read them —
# JX016 checks the handler side only, so adding one here never flags
# existing clients.

# distributed-tracing context headers (obs/ctxprop.py mints/parses them)
TRACE_HEADERS = ("X-Trace-Id", "X-Parent-Span")


class Route:
    __slots__ = ("path", "methods", "headers", "opt_headers", "idempotent", "server")

    def __init__(
        self, path, methods, headers=(), opt_headers=(), idempotent=False,
        server="both",
    ):
        self.path = path
        self.methods = tuple(methods)
        self.headers = tuple(headers)
        self.opt_headers = tuple(opt_headers)
        self.idempotent = idempotent
        self.server = server


ROUTES = {
    r.path: r
    for r in (
        Route("/healthz", ("GET",), idempotent=True, server="both"),
        # the Prometheus scrape endpoint (obs/sinks.py PrometheusSink)
        Route("/metrics", ("GET",), idempotent=True, server="metrics"),
        Route("/stats", ("GET",), idempotent=True, server="both"),
        Route("/debug/flight", ("GET",), idempotent=True, server="both"),
        Route("/admin/replicas", ("GET",), idempotent=True, server="router"),
        Route(
            "/embed",
            ("POST",),
            headers=("X-Image-Shape",),
            opt_headers=TRACE_HEADERS,
            idempotent=True,
            server="both",
        ),
        Route(
            "/neighbors",
            ("POST",),
            headers=("X-Image-Shape",),
            opt_headers=TRACE_HEADERS,
            idempotent=True,
            server="both",
        ),
        Route(
            "/ingest",
            ("POST",),
            headers=("X-Rows-Shape",),
            # the source checkpoint step of the posted rows: the replica
            # reads it into its serve/ingest_ckpt_step gauge so encoder/
            # index skew is visible (scripts/serve_ingest.py sends it)
            opt_headers=("X-Ckpt-Step",),
            idempotent=False,
            server="replica",
        ),
        Route("/admin/drain", ("POST",), idempotent=False, server="both"),
        Route("/admin/undrain", ("POST",), idempotent=False, server="router"),
        # served-model identity (step + params digest + last ingest step)
        Route("/admin/model", ("GET",), idempotent=True, server="replica"),
        # one staged-rollout step: retarget the supervisor's checkpoint
        # dir and drain/restart one replica onto it. NOT idempotent (a
        # retry would double-drain a replica mid-swap) — the promotion
        # controller polls /admin/replicas instead of retrying.
        Route("/admin/promote", ("POST",), idempotent=False, server="router"),
    )
}

IDEMPOTENT_ROUTES = tuple(sorted(p for p, r in ROUTES.items() if r.idempotent))
REQUIRED_HEADERS = {p: r.headers for p, r in ROUTES.items() if r.headers}
OPTIONAL_HEADERS = {p: r.opt_headers for p, r in ROUTES.items() if r.opt_headers}


def route_methods(path: str) -> tuple:
    """Declared methods for a route ('' query strings already stripped),
    or () for an undeclared route."""
    r = ROUTES.get(path)
    return r.methods if r else ()


# ---------------------------------------------------------------------------
# fault-grammar sites (utils/faults.py holds the grammar; these are the
# site vocabularies per kind). kill/stall/nan/preempt/ckpt_truncate are
# site-less; diverge sites are dynamic comms tags (per-bucket schedule
# entries like `zero.gather_q.b0`) and are validated at runtime by the
# sanitizer, not here.

SERVE_STAGE_SITES = (
    "serve.ingress",
    "serve.batch_assemble",
    "serve.engine_execute",
    "serve.index_query",
    "serve.scatter",
    "serve.respond",
)

# tsan.make_lock names — the deadlock@site=<lock> fault inverts the
# acquisition order around the named lock.
LOCK_SITES = (
    "data.transfer_stats",
    "fleet.supervisor",
    "obs.comms",
    "obs.flight",
    "obs.prometheus",
    "obs.slo",
    "obs.trace",
    "promote.ledger",
    "router.fleet",
    "router.metrics",
    "serve.index",
    "serve.metrics",
    "utils.retry",
)

FAULT_SITES = {
    "slow": SERVE_STAGE_SITES,
    # "ingest": stalls the replica's /ingest handler before the body
    # read (serve/server.py) — the freshness-SLO chaos lever: rows age
    # past the declared max while the tail pipeline is stuck.
    "delay": ("data.read", "input.h2d", "zero.gather", "ingest"),
    "io": ("data.read",),
    "deadlock": LOCK_SITES,
}

# ---------------------------------------------------------------------------
# runtime contract-coverage gates (analysis/contracts.py recorder)
#
# The serve/* schema validators the serving stack itself must exercise
# in a full smoke (everything explicit under serve/).

SERVE_GATED_VALIDATORS = (
    "serve/ingested_rows",
    "serve/int8",
    "serve/ivf_occupancy",
    "serve/ivf_spill",
    "serve/latency_hist",
    "serve/nprobe",
    "serve/p99_exemplar",
    "serve/p99_exemplar_ms",
    "serve/quant_tier",
    "serve/recall_estimate",
    "serve/slo_objective",
)

# Model-quality / freshness validators a replica with a declared
# freshness objective must exercise (served-model identity, row-age
# gauges, and the freshness burn family's prefix).

QUALITY_GATED_VALIDATORS = (
    "serve/fresh_burn_rate_",
    "serve/fresh_max_age_s",
    "serve/ingest_ckpt_step",
    "serve/model_digest",
    "serve/model_step",
    "serve/row_age_max_s",
    "serve/row_age_mean_s",
)

# The distributed-tracing validators the ROUTER's metric stream must
# exercise in a full fleet smoke (critical-path attribution + the
# hedge-loser cost counter — both only emitted by serve/router.py).

FLEET_GATED_VALIDATORS = (
    "fleet_serve/critpath_",
    "fleet_serve/hedge_wasted_ms",
)

# The promotion pipeline's ledger validators the fleet smoke's
# promotion leg must exercise (serve/promote.py writes them through
# schema.validate_line, so coverage proves real verdict lines landed).

PROMOTION_GATED_VALIDATORS = (
    "fleet_serve/model_skew",
    "promotion/",
    "promotion/digest",
    "promotion/failed_gate",
    "promotion/stage",
    "promotion/verdict",
)

# The scaling-law battery's validators scripts/scaling_smoke.py must
# exercise (per-leg verdict lines + the numeric kappa/drift/peak family
# all flow through schema.validate_line, so coverage proves the battery
# emitted real evidence, not just an exit code).

SCALING_GATED_VALIDATORS = (
    "scaling/",
    "scaling/leg",
    "scaling/verdict",
)
