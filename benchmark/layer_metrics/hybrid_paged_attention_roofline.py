"""Kernel: paged decode attention in a model where only SOME layers attend.
As ``paged_attention_roofline`` (least time for the decode tokens that arrived
in the traced span over the summed device time of the ``paged_attention``
calls inside decode programs, in %), but a token reads its context's K and V
once a FULL-ATTENTION layer, not once a layer of the model: that reader
multiplies by ``n_layers`` and would read four times too high here."""
from benchmark.harness import kernel_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("paged_attention", DECODE_PROGRAMS)
    m = ctx.model
    if not calls or not hasattr(m, "layers_of"):
        return None
    attending = len(m.layers_of("full_attention"))
    lo, hi = ctx.trace_span
    ops = nbytes = 0.0
    for r in ctx.records:
        for j, at in enumerate(r.token_times):
            if j >= 1 and lo <= at < hi:
                o, b = kernel_cost.decode_attention(
                    r.prompt_tokens + j, m.n_heads, m.n_kv_heads, m.head_dim)
                ops, nbytes = ops + o * attending, nbytes + b * attending
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["hybrid_paged_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "attending_layers": attending, "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
