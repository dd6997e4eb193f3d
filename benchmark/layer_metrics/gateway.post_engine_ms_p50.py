"""Gateway + /v1 route, the way out: the engine's ``first`` stamp (the token
sampled, on the dispatch thread) -> the client's own receive time of the
request's first token, median over the window's requests, ms. The note splits
it at the ``deliver`` stamp: ``first -> deliver`` is the wait in the step's
emit buffer and the hop to the loop, ``deliver -> client`` the route, the SSE
writer, the socket and the client."""
from benchmark.harness import host_parts


def read(ctx):
    return host_parts.post_engine_ms_p50(ctx)
