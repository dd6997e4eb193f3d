"""Kernel: the exact top-k selection. Share of the step programs' device time
(prefill, chunk and decode programs) inside the ``sparse_select`` kernel, in %.
The selection is a Pallas kernel of that name in every step program of the
latent family (a threshold search, no sort), so it is told from sampling's
vocabulary sort by its name alone: that one is XLA's ``sort`` and is not
counted. The mask built from the threshold is elementwise XLA work fused into
its neighbours and is not counted either."""
from benchmark.harness.layers import DECODE_PROGRAMS, PREFILL_PROGRAMS

STEP_PROGRAMS = PREFILL_PROGRAMS + DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s, calls = ctx.trace.op_time("sparse_select", STEP_PROGRAMS)
    steps_s, runs = ctx.trace.module_time(STEP_PROGRAMS)
    if not calls or not runs or steps_s <= 0:
        return None
    ctx.notes["sparse_select.device_share"] = {
        "kernel_s": kernel_s, "calls": calls, "step_programs_s": steps_s}
    return 100.0 * kernel_s / steps_s
