"""Kernel: prefill attention (``flash_attention`` for a prompt inside the
bucket, the paged chunk kernel for chunk rounds) in a model whose layers are
WINDOW or FULL attention. As ``prefill_attention_roofline`` (least time for
the causal attention of the prompts prefilled in the traced span over the
summed device time of the attention calls inside prefill programs, in %; a
prompt's prefill is taken to run between its send and its first token, and the
part of that span inside the trace is the part of its cost counted), but query
i sees ``min(i, window)`` keys in a window layer (``harness/window_cost.py``).
The bytes are a prompt's K and V read once, however many chunk rounds re-read
them. A model without window layers reports nothing."""
from benchmark.harness import kernel_cost, window_cost
from benchmark.harness.layers import PREFILL_PROGRAMS, overlap


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    flash_s, flash_n = ctx.trace.op_time("flash_attention", PREFILL_PROGRAMS)
    paged_s, paged_n = ctx.trace.op_time("paged_attention", PREFILL_PROGRAMS)
    kinds = window_cost.layers_of(ctx.model)
    if not flash_n + paged_n or kinds is None:
        return None
    m = ctx.model
    ops = nbytes = 0.0
    for r in ctx.records:
        if not r.token_times:
            continue
        span = (r.sent, r.token_times[0])
        share = overlap(span, ctx.trace_span) / max(span[1] - span[0], 1e-9)
        o, b = window_cost.chunk(r.prompt_tokens, 0, *kinds, m.n_heads,
                                 m.n_kv_heads, m.head_dim)
        ops, nbytes = ops + share * o, nbytes + share * b
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["window_chunk_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "kernel_s": flash_s + paged_s, "flash_calls": flash_n, "paged_calls": paged_n}
    return 100.0 * least / (flash_s + paged_s)
