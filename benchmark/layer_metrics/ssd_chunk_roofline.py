"""Kernel: the Mamba-2 recurrence in prefill steps (``ssd_chunk`` inside
prefill programs). Least time for the REAL prompt tokens whose prefill fell in
the traced span: the recurrence's five operations an entry of the state a
token, each token's x, dt, B, C read and y written once, the row's state read
and written once a Mamba layer (``harness/ssd_cost.py``), over the summed
device time of the kernel's calls there, in %. The bucket's padding, a batch's
padding rows and the chunkwise form's own products count against the kernel.
A program without the kernel reports nothing."""
from benchmark.harness import kernel_cost, mla_cost, ssd_cost
from benchmark.harness.layers import PREFILL_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("ssd_chunk", PREFILL_PROGRAMS)
    layers = ssd_cost.mamba_layers(ctx.model)
    if not calls or not layers or kernel_s <= 0:
        return None
    m = ctx.model
    ops = nbytes = tokens = 0.0
    for r, share in mla_cost.prompts_in_span(ctx.records, ctx.trace_span):
        o, b = ssd_cost.ssd_chunk(r.prompt_tokens, m.mamba_n_heads,
                                  m.mamba_head_dim, m.mamba_d_state)
        ops, nbytes = ops + share * o * layers, nbytes + share * b * layers
        tokens += share * r.prompt_tokens
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["ssd_chunk_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "prompt_tokens": tokens, "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
