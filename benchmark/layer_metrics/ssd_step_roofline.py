"""Kernel: the Mamba-2 recurrence in decode steps (``ssd_step`` inside decode
programs). Least time for the decode tokens that arrived in the traced span,
each of each Mamba layer reading and writing its row's float32 state once and
running the recurrence's five operations an entry of it
(``harness/ssd_cost.py``), over the summed device time of the kernel's calls
there, in %. Idle rows of the decode width cost the kernel a grid step and
nothing here. A program without the kernel reports nothing."""
from benchmark.harness import kernel_cost, ssd_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("ssd_step", DECODE_PROGRAMS)
    layers = ssd_cost.mamba_layers(ctx.model)
    if not calls or not layers or kernel_s <= 0:
        return None
    m, (lo, hi) = ctx.model, ctx.trace_span
    tokens = sum(1 for r in ctx.records for j, at in enumerate(r.token_times)
                 if j >= 1 and lo <= at < hi)       # token 0 comes from prefill
    ops, nbytes = ssd_cost.ssd_step(m.mamba_n_heads, m.mamba_head_dim,
                                    m.mamba_d_state)
    ops, nbytes = ops * tokens * layers, nbytes * tokens * layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["ssd_step_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "decode_tokens": tokens, "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
