"""Prefill batching: requests per prefill dispatch (chunk rounds included)."""


def read(ctx):
    batches = ctx.stats.get("prefill_batches", 0)
    return ctx.stats["prefill_requests"] / batches if batches else None
