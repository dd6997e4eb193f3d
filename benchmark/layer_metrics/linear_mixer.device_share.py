"""Kernels: the linear-attention mixer's own work. Share of the step programs'
device time (prefill, chunk and decode programs) inside the two gated
delta-rule kernels, ``gated_delta_chunk`` and ``gated_delta_step``, in %. The
mixer's causal convolution, its norms and its gates are elementwise XLA work
fused into their neighbours under no name of their own and are not counted;
its projections are the dense matmuls every layer has."""
from benchmark.harness.layers import DECODE_PROGRAMS, PREFILL_PROGRAMS

STEP_PROGRAMS = PREFILL_PROGRAMS + DECODE_PROGRAMS
KERNELS = ("gated_delta_chunk", "gated_delta_step")


def read(ctx):
    if ctx.trace is None:
        return None
    parts = {name: ctx.trace.op_time(name, STEP_PROGRAMS) for name in KERNELS}
    steps_s, runs = ctx.trace.module_time(STEP_PROGRAMS)
    calls = sum(c for _, c in parts.values())
    if not calls or not runs or steps_s <= 0:
        return None
    kernel_s = sum(s for s, _ in parts.values())
    ctx.notes["linear_mixer.device_share"] = {
        "step_programs_s": steps_s,
        **{name: {"kernel_s": s, "calls": c} for name, (s, c) in parts.items()}}
    return 100.0 * kernel_s / steps_s
