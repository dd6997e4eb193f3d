"""Prefill step: host wall of a prefill dispatch, which ends in device_get
(EngineStats.prefill_ms_total / prefill_batches over the window)."""


def read(ctx):
    batches = ctx.stats.get("prefill_batches", 0)
    return ctx.stats["prefill_ms_total"] / batches if batches else None
