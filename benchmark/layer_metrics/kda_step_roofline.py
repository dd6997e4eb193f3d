"""Kernel: Kimi Delta Attention in decode steps (``kda_step`` inside decode
programs). Least time for the decode tokens that arrived in the traced span,
each of each KDA layer reading and writing its row's float32 state once and
running the recurrence's seven operations an entry of it, with a token's
``d_k`` decay floats a head among its bytes (``harness/kda_cost.py``), over
the summed device time of the kernel's calls there, in %. Idle bucket rows
(they walk the trash row) count against the kernel. A program without the
kernel (every commit before PR 50) reports nothing."""
from benchmark.harness import kda_cost, kernel_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("kda_step", DECODE_PROGRAMS)
    layers = kda_cost.kda_layers(ctx.model)
    if not calls or not layers:
        return None
    m, (lo, hi) = ctx.model, ctx.trace_span
    tokens = sum(1 for r in ctx.records for j, at in enumerate(r.token_times)
                 if j >= 1 and lo <= at < hi)       # token 0 comes from prefill
    ops, nbytes = kda_cost.kda_step(m.linear_n_heads, m.linear_key_dim,
                                    m.linear_value_dim)
    ops, nbytes = ops * tokens * layers, nbytes * tokens * layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["kda_step_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "decode_tokens": tokens, "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s if kernel_s > 0 else None
