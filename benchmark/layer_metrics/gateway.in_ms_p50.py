"""Gateway + /v1 route, the way in, measured inside the program: the ``recv``
stamp (the flight recorder's middleware entered) -> the ``submit`` stamp (the
engine's request made), median over the window's requests, ms: the in-program
twin of ``gateway.pre_engine_ms_p50``, which starts at the client's send. The
note splits it at ``authed`` / ``parsed`` / ``tokenized``, gives the client's
``sent -> recv`` (socket, aiohttp's parse, the outer middleware), and under
``gateway.sum_check_ms`` rebuilds a first token from its seven pieces (and how
late the generator sent it) beside what the client measured."""
from benchmark.harness import gateway_parts


def read(ctx):
    return gateway_parts.in_ms_p50(ctx)
