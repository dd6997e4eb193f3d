"""Decode step: tokens a block commits per forward pass it goes through, over
the window's block steps, from the step records of the engine's timeline (a
record's rows are its blocks; the step program counts its denoise passes and
the engine the tokens it emitted, and one commit pass a step is added). A
block of 4 positions filled one a pass and then committed reads 0.8; a fused
commit or a fill rule that takes several positions a pass reads higher. A
program whose step records carry no such count reports nothing."""
from benchmark.harness import block_cost, timeline_view


def read(ctx):
    view = timeline_view.load()
    if view is None:
        return None
    steps = block_cost.block_steps(view, ctx.window)
    passes = sum(s.rows * (s.counts.denoise_passes + 1) for s in steps)
    if not passes:
        return None
    tokens = sum(s.counts.block_tokens for s in steps)
    ctx.notes["diffusion.tokens_per_pass"] = {
        "steps": len(steps), "blocks": sum(s.rows for s in steps),
        "tokens": tokens,
        "denoise_passes": sum(s.counts.denoise_passes for s in steps),
        "filled_by_threshold": sum(s.counts.filled_by_threshold for s in steps)}
    return tokens / passes
