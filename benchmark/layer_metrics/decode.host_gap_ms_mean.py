"""Decode step: the engine's host-clock stall between decode dispatches
(EngineStats.dispatch_gap_ms_total / decode_dispatches). Host time, named so:
the device's idle share comes from the trace."""


def read(ctx):
    dispatches = ctx.stats.get("decode_dispatches", 0)
    return ctx.stats["dispatch_gap_ms_total"] / dispatches if dispatches else None
