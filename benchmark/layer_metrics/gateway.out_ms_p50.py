"""Gateway + /v1 route, the way out, third piece: the ``deliver`` stamp ->
the ``written`` stamp (the first SSE write returned), median, ms: detokenise,
build the chunk, ``prepare()``, write. The note splits it at ``chunk`` and
gives ``written -> client``, what is left of the way outside the program."""
from benchmark.harness import gateway_parts


def read(ctx):
    return gateway_parts.out_ms_p50(ctx)
