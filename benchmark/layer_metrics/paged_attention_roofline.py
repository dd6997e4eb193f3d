"""Kernel: paged decode attention. Least time the chip needs for the decode
tokens that arrived in the traced span (each token of each layer reads its
own context's K and V once) over the summed device time of the
``paged_attention`` calls inside decode programs, in %."""
from benchmark.harness import kernel_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("paged_attention", DECODE_PROGRAMS)
    if not calls:
        return None
    m, (lo, hi) = ctx.model, ctx.trace_span
    ops = nbytes = 0.0
    for r in ctx.records:
        # token 0 comes from prefill; token j >= 1 from a decode step that
        # attends to prompt + j tokens
        for j, at in enumerate(r.token_times):
            if j >= 1 and lo <= at < hi:
                o, b = kernel_cost.decode_attention(
                    r.prompt_tokens + j, m.n_heads, m.n_kv_heads, m.head_dim)
                ops, nbytes = ops + o * m.n_layers, nbytes + b * m.n_layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["paged_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
