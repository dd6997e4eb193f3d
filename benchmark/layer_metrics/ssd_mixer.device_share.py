"""Kernels: the state-space mixer's own work. Share of the device's busy time
in the traced window inside the two Mamba-2 kernels, ``ssd_chunk`` and
``ssd_step`` (in prefill, chunk and decode programs), in %: the number that
says whether the mechanism is the largest part of the device's time in the
cell. The mixer's convolution, its gate and norm are XLA work under no name of
their own and are not counted; its projections are the dense matmuls every
layer has. A program without the kernels reports nothing."""
from benchmark.harness.layers import DECODE_PROGRAMS, PREFILL_PROGRAMS

STEP_PROGRAMS = PREFILL_PROGRAMS + DECODE_PROGRAMS
KERNELS = ("ssd_chunk", "ssd_step")


def read(ctx):
    if ctx.trace is None:
        return None
    parts = {name: ctx.trace.op_time(name, STEP_PROGRAMS) for name in KERNELS}
    busy_s = ctx.trace.busy_s()
    if not sum(c for _, c in parts.values()) or busy_s <= 0:
        return None
    ctx.notes["ssd_mixer.device_share"] = {
        "busy_s": busy_s,
        **{name: {"kernel_s": s, "calls": c} for name, (s, c) in parts.items()}}
    return 100.0 * sum(s for s, _ in parts.values()) / busy_s
