"""Gateway + /v1 route, the way out, first piece: the ``first`` stamp (the
token sampled, on the dispatch thread) -> the ``emit`` stamp (the same
thread's flush hands it to the loop), median, ms: the token waiting for the
dispatch thread to finish its step."""
from benchmark.harness import gateway_parts


def read(ctx):
    return gateway_parts.emit_wait_ms_p50(ctx)
