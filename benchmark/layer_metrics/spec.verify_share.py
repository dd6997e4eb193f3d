"""Decode step: of the window's decode dispatches that had a row a draft
could serve (a greedy row with more than one token to go; the engine counts
them on every decode dispatch of a program that drafts on the device), the
share that were verify steps carrying a draft for every such row, in %, from
the step records of the engine's timeline. A plain decode dispatch with such a
row is one that did not speculate and counts against; a verify step counts
against where a row that wanted a draft rode without one (no draft kept with
the request, or no page for its position). 100 when the mechanism runs: a
change that makes the cell faster by not speculating shows here. A program
whose steps carry no such count reports nothing."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    steps = view.decode_steps(ctx.window)
    wanting = [s for s in steps
               if getattr(getattr(s, "counts", None), "draft_wanted", 0) > 0]
    if not wanting:
        return None
    served = sum(1 for s in wanting if s.kind == "spec"
                 and s.counts.draft_rows >= s.counts.draft_wanted)
    ctx.notes["spec.verify_share"] = {
        "decode_steps": len(steps), "with_a_row_to_draft_for": len(wanting),
        "verify_steps_of_them": sum(1 for s in wanting if s.kind == "spec"),
        "every_such_row_drafted": served}
    return 100.0 * served / len(wanting)
