"""Decode step: mean over the window's decode steps of what a window layer
sees of the rows' context, ``window_keys / context_keys`` in %, from the step
records of the engine's timeline (the step program sums its live rows' context
and their ``min(context, window)`` and the engine reads both back with the
step's tokens). 100: every row still inside its window. A program whose step
records carry no such counts reports nothing."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    shares = [s.counts.window_keys / s.counts.context_keys
              for s in view.decode_steps(ctx.window)
              if getattr(getattr(s, "counts", None), "context_keys", 0)]
    if not shares:
        return None
    ctx.notes["window.visible_share"] = {
        "steps": len(shares), "min": min(shares), "max": max(shares)}
    return 100.0 * sum(shares) / len(shares)
