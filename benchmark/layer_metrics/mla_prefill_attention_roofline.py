"""Kernel: latent attention in prefill and chunk rounds
(``mla_paged_attention`` inside prefill programs). Least time for the NEEDED
work, each prompt's queries against the ``min(context, index_topk)`` tokens
they select (absorbed-form operations, latent bytes: ``harness/mla_cost.py``),
over the summed device time of the kernel's calls there, in %. The kernel reads
every page up to a row's last position and masks what was not selected, so it
reads low at long contexts, which is the point. A prompt's prefill is taken to
run between its send and its first token; the part of that span inside the
trace is the part of its cost counted. A program without the kernel (another
family, the parent) reports nothing."""
from benchmark.harness import kernel_cost, mla_cost
from benchmark.harness.layers import PREFILL_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("mla_paged_attention", PREFILL_PROGRAMS)
    m = ctx.model
    if not calls or not hasattr(m, "kv_lora_rank"):
        return None
    ops = nbytes = 0.0
    for r, share in mla_cost.prompts_in_span(ctx.records, ctx.trace_span):
        o, b = mla_cost.mla_attention(r.prompt_tokens, 0, m.n_heads, m.latent_dim,
                                      m.kv_lora_rank, m.index_topk)
        ops, nbytes = ops + share * o * m.n_layers, nbytes + share * b * m.n_layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["mla_prefill_attention_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
