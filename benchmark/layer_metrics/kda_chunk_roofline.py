"""Kernel: Kimi Delta Attention in prefill steps (``kda_chunk`` inside prefill
programs: the dense prefill and every chunk round). Least time for the REAL
prompt tokens whose prefill fell in the traced span: the recurrence's seven
operations an entry of the state a token, each token's q, k, v, its ``d_k``
decays a head and beta read and o written once, the row's state read and
written once a KDA layer (``harness/kda_cost.py``), over the summed device
time of the kernel's calls there, in %. The bucket's padding, a batch's
padding rows, the state moved once a chunk ROUND and whatever extra arithmetic
the implementation does count against the kernel. A program without the
kernel (every commit before PR 50) reports nothing."""
from benchmark.harness import kda_cost, kernel_cost, mla_cost
from benchmark.harness.layers import PREFILL_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("kda_chunk", PREFILL_PROGRAMS)
    layers = kda_cost.kda_layers(ctx.model)
    if not calls or not layers:
        return None
    m = ctx.model
    ops = nbytes = tokens = 0.0
    for r, share in mla_cost.prompts_in_span(ctx.records, ctx.trace_span):
        o, b = kda_cost.kda_chunk(r.prompt_tokens, m.linear_n_heads,
                                  m.linear_key_dim, m.linear_value_dim)
        ops, nbytes = ops + share * o * layers, nbytes + share * b * layers
        tokens += share * r.prompt_tokens
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["kda_chunk_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "prompt_tokens": tokens, "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s if kernel_s > 0 else None
