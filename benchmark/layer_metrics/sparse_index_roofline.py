"""Kernel: the selector's index scores (``sparse_index_scores`` inside prefill
programs; a decode step scores its one query a row in XLA). Least time for
``index_n_heads * index_head_dim`` multiply-adds a causal (query, token) pair
with the index-key pages read once a query block (``harness/mla_cost.py``) over
the summed device time of the kernel's calls, in %. Prompts are counted as in
``mla_prefill_attention_roofline``."""
from benchmark.harness import kernel_cost, mla_cost
from benchmark.harness.layers import PREFILL_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("sparse_index_scores", PREFILL_PROGRAMS)
    m = ctx.model
    if not calls or not hasattr(m, "index_n_heads"):
        return None
    ops = nbytes = 0.0
    for r, share in mla_cost.prompts_in_span(ctx.records, ctx.trace_span):
        o, b = mla_cost.index_scores(r.prompt_tokens, 0, m.index_n_heads,
                                     m.index_head_dim)
        ops, nbytes = ops + share * o * m.n_layers, nbytes + share * b * m.n_layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["sparse_index_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
