"""Kernels: the Kimi-Delta-Attention mixer's own work. Share of the step
programs' device time (prefill, chunk and decode programs) inside the two
channel-decay delta-rule kernels, ``kda_chunk`` and ``kda_step``, in %: the
number that says whether the mechanism does the work in the cell. The mixer's
causal convolution, its norms, its low-rank decay and gate are XLA work under
no name of their own and are not counted; its projections are the dense
matmuls every layer has. A program without the kernels reports nothing."""
from benchmark.harness.layers import DECODE_PROGRAMS, PREFILL_PROGRAMS

STEP_PROGRAMS = PREFILL_PROGRAMS + DECODE_PROGRAMS
KERNELS = ("kda_chunk", "kda_step")


def read(ctx):
    if ctx.trace is None:
        return None
    parts = {name: ctx.trace.op_time(name, STEP_PROGRAMS) for name in KERNELS}
    steps_s, runs = ctx.trace.module_time(STEP_PROGRAMS)
    calls = sum(c for _, c in parts.values())
    if not calls or not runs or steps_s <= 0:
        return None
    kernel_s = sum(s for s, _ in parts.values())
    ctx.notes["kda_mixer.device_share"] = {
        "step_programs_s": steps_s,
        **{name: {"kernel_s": s, "calls": c} for name, (s, c) in parts.items()}}
    return 100.0 * kernel_s / steps_s
