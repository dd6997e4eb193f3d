"""Decode step: p95 over the window's decode steps of retire-to-retire time
(the engine's step timeline; time the dispatch thread had nothing to do is
left out): TPOT as the engine sees it, stalls behind prefill included."""
from benchmark.harness import timeline_view
from benchmark.harness.stats import percentile


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    intervals = [s * 1e3 for _lo, _hi, s in
                 timeline_view.retire_intervals(view, ctx.window)]
    if not intervals:
        return None
    ctx.notes["decode.retire_interval_ms"] = {
        "n": len(intervals), "p50": percentile(intervals, 50),
        "p95": percentile(intervals, 95), "max": max(intervals)}
    return percentile(intervals, 95)
