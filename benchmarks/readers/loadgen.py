"""Reader: the load generator's own record of the window.

spec: {"reader": "loadgen", "what": "late_p95_ms" | "within_slo_pct"}
"""

from benchmarks.harness.stats import percentile


def read(spec: dict, ctx: dict):
    win = ctx.get("loadgen")
    if not win:
        return None
    if spec["what"] == "late_p95_ms":
        return percentile(win["late_ms"], 95)
    if spec["what"] == "within_slo_pct":
        slo = ctx["serve_config"]["slo_ms"]
        ok = sum(1 for s, l in zip(win["status"], win["latency_ms"]) if s == 200 and l <= slo)
        return 100.0 * ok / len(win["status"])
    raise ValueError(f"unknown loadgen quantity {spec['what']!r}")
