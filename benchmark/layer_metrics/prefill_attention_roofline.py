"""Kernel: prefill attention (``flash_attention``, or the paged chunk kernel
for prompts above the bucket). Least time for the causal attention of the
prompts prefetched in the traced span over the summed device time of the
attention calls inside prefill programs, in %. A prompt's prefill is taken to
run between its send and its first token; the part of that span inside the
trace is the part of its cost counted. The cost is a GQA trunk's (kv heads x
head_dim of K and V), so BENCHMARK.json lists the cells it is read in: a cell
of another family brings a reader of its own for its own kernels."""
from benchmark.harness import kernel_cost
from benchmark.harness.layers import PREFILL_PROGRAMS, overlap


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    flash_s, flash_n = ctx.trace.op_time("flash_attention", PREFILL_PROGRAMS)
    paged_s, paged_n = ctx.trace.op_time("paged_attention", PREFILL_PROGRAMS)
    if not flash_n + paged_n:
        return None
    m = ctx.model
    ops = nbytes = 0.0
    for r in ctx.records:
        if not r.token_times:
            continue
        span = (r.sent, r.token_times[0])
        share = overlap(span, ctx.trace_span) / max(span[1] - span[0], 1e-9)
        o, b = kernel_cost.prefill_attention(
            r.prompt_tokens, 0, m.n_heads, m.n_kv_heads, m.head_dim)
        ops, nbytes = ops + share * o * m.n_layers, nbytes + share * b * m.n_layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["prefill_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "kernel_s": flash_s + paged_s, "flash_calls": flash_n, "paged_calls": paged_n}
    return 100.0 * least / (flash_s + paged_s)
