"""Gateway + /v1 route, the way out, second piece: the ``emit`` stamp -> the
``deliver`` stamp (the token entered the request's stream on the loop's
thread), median, ms: the event loop's latency for the flush's callback, read
once a first token. The note has p95, the longest, and the count over 5 ms."""
from benchmark.harness import gateway_parts


def read(ctx):
    return gateway_parts.hop_ms_p50(ctx)
