"""Decode step: of the summed retire-to-retire time of the window's decode
steps, the share the dispatch thread spent inside ``prefill.*`` spans (dense
prefill, history prefill, chunk rounds), in %: how much of every running
request's time between tokens is another request's prefill."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    by_span: dict[str, float] = {}
    for lo, hi, _seconds in timeline_view.retire_intervals(view, ctx.window):
        for name, seconds in view.cover(lo, hi).items():
            by_span[name] = by_span.get(name, 0.0) + seconds
    split = timeline_view.by_category(by_span)
    total = sum(split.values()) - split["loop.wait"]
    if total <= 0:
        return None
    ctx.notes["decode.retire_interval_split_s"] = split
    return 100.0 * split["prefill"] / total
