"""Kernel: the paged chunk kernel inside block steps (``paged_attention`` in
decode programs of a model that generates by diffusion over blocks). Least
time for the NEEDED work (``harness/block_cost.py``): each block the client
saw arrive in the traced span, after the tokens cached before it, once a layer
and a denoise pass of the step that produced it (the step records' counts),
over the summed device time of the kernel's calls there, in %. The commit
pass, rows riding along a lockstep dispatch and idle rows count against the
kernel. Nothing from a model of another family or a program without the
counts."""
from benchmark.harness import block_cost, kernel_cost, timeline_view
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    m = ctx.model
    block = block_cost.block_length(m)
    kernel_s, calls = ctx.trace.op_time("paged_attention", DECODE_PROGRAMS)
    view = timeline_view.load()
    if not block or not calls or view is None:
        return None
    steps = block_cost.block_steps(view, ctx.window)
    if not steps:
        return None
    passes = block_cost.passes_at(steps)
    lo, hi = ctx.trace_span
    ops = nbytes = 0.0
    blocks = 0
    for r in ctx.records:
        for cached, at in block_cost.blocks_of(r.prompt_tokens, r.token_times, block):
            if lo <= at < hi:
                o, b = block_cost.block_attention(block, cached, m.n_heads,
                                                  m.n_kv_heads, m.head_dim)
                n = passes(at) * m.n_layers
                ops, nbytes, blocks = ops + o * n, nbytes + b * n, blocks + 1
    if not blocks:
        return None
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["block_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "blocks": blocks, "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
