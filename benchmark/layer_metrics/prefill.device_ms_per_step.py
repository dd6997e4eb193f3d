"""Prefill step: device time of the prefill programs (dense, history and
chunk rounds) per execution, from the trace: the device-side twin of
``prefill.step_ms_mean``."""
from benchmark.harness.layers import PREFILL_PROGRAMS


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, runs = ctx.trace.module_time(PREFILL_PROGRAMS)
    return seconds * 1e3 / runs if runs else None
