"""Kernel: latent attention at verify width (``mla_paged_attention`` inside
the decode programs of a model that drafts on the device: every decode
dispatch is a verify step of the last token and one draft a row). Least time
for the verify steps whose tokens arrived in the traced span, each row's
query positions against every cached vector they may see, in the model's
layers AND its multi-token-prediction block's, a row's visible vectors read
once a layer (``harness/mla_verify_cost.py``), over the summed device time of
the kernel's calls there, in %. Idle slots, padding positions and pages walked
more than once count against the kernel. A program without the block, or a
trace without the kernel in its decode programs, reports nothing."""
from benchmark.harness import kernel_cost, mla_verify_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    m = ctx.model
    if not getattr(m, "n_mtp_blocks", 0) or getattr(m, "index_topk", 0):
        return None
    kernel_s, calls = ctx.trace.op_time("mla_paged_attention", DECODE_PROGRAMS)
    if not calls:
        return None
    layers = m.n_layers + m.n_mtp_blocks
    ops = nbytes = 0.0
    steps = 0
    for r in ctx.records:
        for queries, context in mla_verify_cost.verify_steps(r, ctx.trace_span):
            o, b = mla_verify_cost.verify_attention(
                queries, context, m.n_heads, m.latent_dim, m.kv_lora_rank)
            ops, nbytes, steps = ops + o * layers, nbytes + b * layers, steps + 1
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["mla_verify_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "kernel_s": kernel_s, "calls": calls, "row_steps": steps}
    return 100.0 * least / kernel_s
