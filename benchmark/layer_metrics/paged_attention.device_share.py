"""Kernel: paged decode attention. Share of the decode programs' device time
inside the ``paged_attention`` kernel (window and full layers' calls carry the
one name), in %: whether what a cache layout or a page walk can move is a
large part of a decode step."""
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s, calls = ctx.trace.op_time("paged_attention", DECODE_PROGRAMS)
    steps_s, runs = ctx.trace.module_time(DECODE_PROGRAMS)
    if not calls or not runs or steps_s <= 0:
        return None
    ctx.notes["paged_attention.device_share"] = {
        "kernel_s": kernel_s, "calls": calls, "decode_programs_s": steps_s,
        "decode_programs": runs}
    return 100.0 * kernel_s / steps_s
