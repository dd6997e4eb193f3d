"""Decode step: mean over the window's decode steps of the rows' selected /
context share, in %, from the step records of the engine's timeline (the
step program counts what its selector kept and the engine reads it back with
the step's tokens). A program whose step records carry no counts reports
nothing."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    shares = [s.counts.selected_share for s in view.decode_steps(ctx.window)
              if getattr(s, "counts", None) is not None]
    if not shares:
        return None
    ctx.notes["sparse.selected_share"] = {
        "steps": len(shares), "min": min(shares), "max": max(shares)}
    return 100.0 * sum(shares) / len(shares)
