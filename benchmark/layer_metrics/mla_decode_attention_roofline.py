"""Kernel: latent attention in decode steps (``mla_paged_attention`` inside
decode programs). Least time for the decode tokens that arrived in the traced
span, each of each layer reading the ``min(context, index_topk)`` cached
vectors it selected once (``harness/mla_cost.py``), over the summed device time
of the kernel's calls there, in %. Idle slots, pages walked past a row's
selection and masked tokens count against the kernel."""
from benchmark.harness import kernel_cost, mla_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("mla_paged_attention", DECODE_PROGRAMS)
    m = ctx.model
    if not calls or not hasattr(m, "kv_lora_rank"):
        return None
    lo, hi = ctx.trace_span
    ops = nbytes = 0.0
    for r in ctx.records:
        for j, at in enumerate(r.token_times):
            if j >= 1 and lo <= at < hi:
                o, b = mla_cost.mla_attention(1, r.prompt_tokens + j - 1, m.n_heads,
                                              m.latent_dim, m.kv_lora_rank,
                                              m.index_topk)
                ops, nbytes = ops + o * m.n_layers, nbytes + b * m.n_layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["mla_decode_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
