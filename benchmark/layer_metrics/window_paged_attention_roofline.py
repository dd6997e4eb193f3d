"""Kernel: paged decode attention in a model whose layers are WINDOW or FULL
attention. As ``paged_attention_roofline`` (least time for the decode tokens
that arrived in the traced span over the summed device time of the
``paged_attention`` calls inside decode programs, in %), but a token reads its
whole context's K and V in a full layer and its ``window`` newest keys in a
window layer (``harness/window_cost.py``); both kinds' calls carry the one
kernel name and are summed. A model without window layers reports nothing."""
from benchmark.harness import kernel_cost, window_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("paged_attention", DECODE_PROGRAMS)
    kinds = window_cost.layers_of(ctx.model)
    if not calls or kinds is None:
        return None
    m, (lo, hi) = ctx.model, ctx.trace_span
    ops = nbytes = 0.0
    for r in ctx.records:
        # token 0 comes from prefill; token j >= 1 from a decode step that
        # attends to prompt + j tokens
        for j, at in enumerate(r.token_times):
            if j >= 1 and lo <= at < hi:
                o, b = window_cost.decode_token(
                    r.prompt_tokens + j, *kinds, m.n_heads, m.n_kv_heads, m.head_dim)
                ops, nbytes = ops + o, nbytes + b
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["window_paged_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "window_layers": kinds[0], "full_layers": kinds[1], "window": kinds[2],
        "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
