"""Admission queue: the engine's own request.queue_ms (submit -> slot won), p95.
It moves ``ttft_p50_ms``: no cell's TTFT tail stands end to end (PERF.md,
section 2), so the name says which reading this is not."""
from benchmark.harness.stats import percentile


def read(ctx):
    by_index = {r.index for r in ctx.records}
    waits = [req.queue_ms for i, (_t, req) in ctx.submits.items()
             if i in by_index and req.queue_observed]
    return percentile(waits, 95) if waits else None
