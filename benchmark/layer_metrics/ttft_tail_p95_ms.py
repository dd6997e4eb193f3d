"""Admission queue: the 95th percentile of due -> first streamed token over
ALL requests of the window, the arithmetic of end-to-end ``ttft_p95_ms``.
Reported per layer, with no bound, in the cells where a window of
``run_seconds`` holds too few requests for that tail to repeat (PERF.md, section 2);
the traced run's profiler span lies inside the window it is taken over."""
from benchmark.harness.stats import end_to_end


def read(ctx):
    return end_to_end("ttft_p95_ms", ctx.records, ctx.window, 0.0)
