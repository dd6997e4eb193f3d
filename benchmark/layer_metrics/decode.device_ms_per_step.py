"""Decode step: device time of the decode step programs (plain and
device-fed twin) per execution, from the trace."""
from benchmark.harness.layers import DECODE_PROGRAMS


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, runs = ctx.trace.module_time(DECODE_PROGRAMS)
    return seconds * 1e3 / runs if runs else None
