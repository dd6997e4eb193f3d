"""Prefill step (and every other step): token-expert pairs that landed on the
experts held here per token through an expert layer, over the window's steps,
from the step records of the engine's timeline (``EngineStats.moe_local_pairs``
/ ``moe_tokens`` hold the same counts since the engine was built). 0.5 under
uniform routing with 16 of 256 experts held and 8 a token."""
from benchmark.harness import timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    lo, hi = ctx.window
    counted = [s.counts for s in view.steps if lo <= s.t_retired <= hi
               and getattr(s, "counts", None) is not None]
    tokens = sum(c.moe_tokens for c in counted)
    if not tokens:
        return None
    pairs = sum(c.moe_local_pairs for c in counted)
    ctx.notes["moe.local_pairs"] = {"steps": len(counted), "tokens": tokens,
                                    "pairs": pairs}
    return pairs / tokens
