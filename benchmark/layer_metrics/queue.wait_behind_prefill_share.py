"""Admission queue: of the summed submit -> slot-won waits of the requests
admitted in the window, the share the dispatch thread spent inside
``prefill.*`` spans, in %. A request's own prefill starts after it has won its
slot, so these are other requests' prefills. The note gives the whole split:
prefill / decode / drain / loop.wait / rest."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    lo, hi = ctx.window
    by_span: dict[str, float] = {}
    waits = 0
    for stamps in view.requests.values():
        if "submit" in stamps and lo <= stamps.get("admit", -1.0) <= hi:
            waits += 1
            for name, seconds in view.cover(stamps["submit"],
                                            stamps["admit"]).items():
                by_span[name] = by_span.get(name, 0.0) + seconds
    split = timeline_view.by_category(by_span)
    total = sum(split.values())
    if not waits or total <= 0:
        return None
    ctx.notes["queue.wait_split_s"] = {"requests": waits, **split}
    return 100.0 * split["prefill"] / total
