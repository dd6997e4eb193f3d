"""Decode step: mean over the window's decode steps of the live per-sequence
state rows the step read, updated and wrote, from the step records of the
engine's timeline (the step program counts the rows that are not the trash row
and the engine reads the count back with the step's tokens). A program whose
step records carry no such count reports nothing."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    live = [s.counts.state_rows_live for s in view.decode_steps(ctx.window)
            if hasattr(getattr(s, "counts", None), "state_rows_live")]
    if not live or not any(live):
        return None
    ctx.notes["state.rows_live"] = {"steps": len(live), "min": min(live),
                                    "max": max(live)}
    return sum(live) / len(live)
