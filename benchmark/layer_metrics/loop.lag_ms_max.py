"""Gateway + /v1 route (its event loop): the longest ``loop_lag`` pause of the
window, ms: a tick of the gateway's loop-lag sampler that ran that much later
than it was due. The note counts and sums the lags of 1 ms and more and names
the five longest, each with the span of the dispatch thread it fell in and the
collections that overlap it."""
from benchmark.harness import gateway_parts


def read(ctx):
    return gateway_parts.lag_ms_max(ctx)
