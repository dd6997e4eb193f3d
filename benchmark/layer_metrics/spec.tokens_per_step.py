"""Decode step: tokens a verify dispatch emits a live row, over the window's
verify steps, from the step records of the engine's timeline (a record's rows
are its live rows; the engine counts the tokens the step emitted). 1.0 where
no draft is accepted (random weights: a draft agrees once in a vocabulary),
2.0 where every row's one draft is. A program whose verify steps carry no such
count (no family that drafts on the device) reports nothing."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    steps = [s for s in view.decode_steps(ctx.window) if s.kind == "spec"
             and getattr(getattr(s, "counts", None), "draft_rows", 0) > 0]
    rows = sum(s.rows for s in steps)
    if not rows:
        return None
    tokens = sum(s.counts.spec_tokens for s in steps)
    ctx.notes["spec.tokens_per_step"] = {
        "steps": len(steps), "rows": rows, "tokens": tokens,
        "draft_rows": sum(s.counts.draft_rows for s in steps),
        "drafts_accepted": sum(s.counts.drafts_accepted for s in steps)}
    return tokens / rows
