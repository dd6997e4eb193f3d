"""Kernel: the gated delta rule in decode steps (``gated_delta_step`` inside
decode programs). Least time for the decode tokens that arrived in the traced
span, each of each linear layer reading and writing its row's float32 state
once, UNPADDED, and running the recurrence's seven operations an entry of it
(``harness/gdn_cost.py``), over the summed device time of the kernel's calls
there, in %. Idle bucket rows (they walk the trash row) count against the
kernel."""
from benchmark.harness import gdn_cost, kernel_cost
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None or ctx.trace_span is None or ctx.peak is None:
        return None
    kernel_s, calls = ctx.trace.op_time("gated_delta_step", DECODE_PROGRAMS)
    layers = gdn_cost.linear_layers(ctx.model)
    if not calls or not layers:
        return None
    m, (lo, hi) = ctx.model, ctx.trace_span
    tokens = sum(1 for r in ctx.records for j, at in enumerate(r.token_times)
                 if j >= 1 and lo <= at < hi)       # token 0 comes from prefill
    ops, nbytes = gdn_cost.delta_step(m.linear_n_heads, m.linear_key_dim,
                                      m.linear_value_dim)
    ops, nbytes = ops * tokens * layers, nbytes * tokens * layers
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.notes["gdn_step_roofline"] = {
        "bound": bound, "needed_ops": ops, "needed_bytes": nbytes,
        "decode_tokens": tokens, "kernel_s": kernel_s, "calls": calls}
    return 100.0 * least / kernel_s
