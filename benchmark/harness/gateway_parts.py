"""A request's way through the gateway as the step timeline holds it since
PR 55, for the five readers that share it (``gateway.in_ms_p50``,
``gateway.emit_wait_ms_p50``, ``loop.hop_ms_p50``, ``gateway.out_ms_p50``,
``loop.lag_ms_max``), over the WHOLE measured window of a traced run.

The program stamps a streamed request eleven times before its first token is
on the socket, all ``time.perf_counter()`` of the serving process, which is
the client's clock here too (one process): ``recv`` (the flight recorder's
middleware entered), ``authed``, ``parsed`` (JSON read, shed check passed,
model resolved), ``tokenized`` (template rendered, prompt tokenised),
``submit``, ``admit``, ``first`` (the token sampled, dispatch thread),
``emit`` (the dispatch thread's flush hands it to the loop), ``deliver`` (it
entered the request's stream on the loop), ``chunk`` (detokenised, the SSE
chunk built) and ``written`` (its ``resp.write`` returned, ``prepare()``
before it). With the client's own ``sent`` and first receive the pieces
rebuild the request's first token::

    sent -> recv | recv -> submit | submit -> first | first -> emit |
    emit -> deliver | deliver -> written | written -> client

and with ``due -> sent`` before them (how late the generator sent: the
end-to-end metric counts from when the request was DUE) its ``ttft_ms``.

The event loop's lag stands on the same ring as ``pause`` events of cause
``loop_lag`` (the gateway's ``LoopLagSampler``: a tick that ran 1 ms or more
late, from when it was due to when it ran).

A program without the stamps (the parent of PR 55) gives None from
:func:`load`, and every reader built on it reports nothing and raises nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from . import host_parts
from .stats import percentile

STAMPS = ("recv", "authed", "parsed", "tokenized", "submit", "first", "emit",
          "deliver", "chunk", "written")
# the generator's lateness and the seven pieces of a first token, by the
# instants that bound each (``due``, ``sent`` and ``client`` are the client's
# own, the rest the ring's)
PIECES = (("generator_due_to_sent", "due", "sent"),
          ("client_send_to_recv", "sent", "recv"),
          ("gateway_in", "recv", "submit"),
          ("queue_and_prefill", "submit", "first"),
          ("emit_wait", "first", "emit"),
          ("loop_hop", "emit", "deliver"),
          ("gateway_out", "deliver", "written"),
          ("written_to_client", "written", "client"))


@dataclass
class Way:
    """One request of the window with every stamp: instants in seconds."""
    at: dict[str, float]        # STAMPS, and the client's "due", "sent", "client"
    ttft_ms: float              # as the end-to-end metric's arithmetic has it

    def ms(self, start: str, end: str) -> float:
        return (self.at[end] - self.at[start]) * 1e3


def load(ctx: Any) -> list[Way] | None:
    """The window's requests that reached the client and carry every stamp,
    or None where there is none (a program without the stamps, or an
    untraced run: the harness keeps the requests' ids in traced runs only).
    Read once a context."""
    if "_gateway_parts" in ctx.__dict__:
        return ctx.__dict__["_gateway_parts"]
    ctx.__dict__["_gateway_parts"] = ways = _load(ctx)
    return ways


def _load(ctx: Any) -> list[Way] | None:
    host = host_parts.load(ctx)
    if host is None:
        return None
    by_index = {r.index: r for r in ctx.records if r.ok and r.token_times}
    ways = []
    for index, (_entered, request) in ctx.submits.items():
        stamps = host.view.requests.get(request.request_id, {})
        record = by_index.get(index)
        if record is None or any(name not in stamps for name in STAMPS):
            continue
        at = {name: stamps[name] for name in STAMPS}
        at["due"], at["sent"] = record.due, record.sent
        at["client"] = record.token_times[0]
        ways.append(Way(at, record.ttft_ms))
    return ways or None


def _spread(values: list[float]) -> dict[str, float]:
    return {"p50": percentile(values, 50), "p95": percentile(values, 95)}


def _between(ways: list[Way], *names: str) -> dict[str, dict[str, float]]:
    """p50 / p95 of each step between consecutive ``names``, ms."""
    return {f"{a}_to_{b}": _spread([w.ms(a, b) for w in ways])
            for a, b in zip(names, names[1:])}


def in_ms_p50(ctx: Any) -> float | None:
    """Median ``recv -> submit``, ms. The note splits it at the marks, gives
    the client's ``sent -> recv`` beside it, and checks that the pieces
    rebuild the client's first token."""
    ways = load(ctx)
    if ways is None:
        return None
    values = [w.ms("recv", "submit") for w in ways]
    ctx.notes["gateway.in_ms"] = {
        "n": len(ways), **_spread(values),
        **_between(ways, "recv", "authed", "parsed", "tokenized", "submit"),
        "client_send_to_recv": _spread([w.ms("sent", "recv") for w in ways])}
    pieces = {name: [w.ms(a, b) for w in ways] for name, a, b in PIECES}
    sums = [sum(piece[i] for piece in pieces.values())
            for i in range(len(ways))]
    ctx.notes["gateway.sum_check_ms"] = {
        "n": len(ways),
        **{name: percentile(values, 50) for name, values in pieces.items()},
        # a first token rebuilt from its pieces, request by request, beside
        # the end-to-end metric's own arithmetic over the same requests
        "sum_p50": percentile(sums, 50),
        "ttft_p50": percentile([w.ttft_ms for w in ways], 50),
        # a piece below zero says two clocks, or a stamp out of its place
        "negative_pieces": sum(1 for values in pieces.values()
                               for v in values if v < 0.0)}
    return percentile(values, 50)


def emit_wait_ms_p50(ctx: Any) -> float | None:
    """Median ``first -> emit``, ms: the sampled token waiting for the
    dispatch thread to finish its step and flush."""
    ways = load(ctx)
    if ways is None:
        return None
    values = [w.ms("first", "emit") for w in ways]
    ctx.notes["gateway.emit_wait_ms"] = {"n": len(ways), **_spread(values),
                                         "max": max(values)}
    return percentile(values, 50)


def hop_ms_p50(ctx: Any) -> float | None:
    """Median ``emit -> deliver``, ms: how long the loop took to run the
    flush's callback, once a first token."""
    ways = load(ctx)
    if ways is None:
        return None
    values = [w.ms("emit", "deliver") for w in ways]
    ctx.notes["loop.hop_ms"] = {
        "n": len(ways), **_spread(values), "max": max(values),
        "over_5_ms": sum(1 for v in values if v > 5.0)}
    return percentile(values, 50)


def out_ms_p50(ctx: Any) -> float | None:
    """Median ``deliver -> written``, ms: detokenise, chunk, ``prepare()``,
    SSE write. The note splits it at ``chunk`` and gives what is left of the
    way to the client outside the program."""
    ways = load(ctx)
    if ways is None:
        return None
    values = [w.ms("deliver", "written") for w in ways]
    ctx.notes["gateway.out_ms"] = {
        "n": len(ways), **_spread(values),
        **_between(ways, "deliver", "chunk", "written"),
        "written_to_client": _spread([w.ms("written", "client") for w in ways])}
    return percentile(values, 50)


def lag_ms_max(ctx: Any) -> float | None:
    """The longest ``loop_lag`` pause of the window, ms (0 where the program
    stamps the way and no tick ran 1 ms late). The note counts and sums them
    and names the five longest: the span of the dispatch thread each fell in
    and the collections that overlap it."""
    host = host_parts.load(ctx)
    if host is None:
        return None
    lags = [p for p in host.pauses if p.cause == "loop_lag"]
    if not lags and load(ctx) is None:
        return None
    collections = [p for p in host.pauses if p.cause == "gc"]

    def row(lag: Any) -> dict[str, Any]:
        # the accepted pause note's row (cause, ms, thread, the span of the
        # dispatch thread it fell in), for the lag and for what overlaps it
        return {**host_parts._pause_row(host.view, lag),
                "at_s": lag.t0 - ctx.window[0],
                "collections": [host_parts._pause_row(host.view, p)
                                for p in collections
                                if p.t0 < lag.t1 and p.t1 > lag.t0]}

    longest = sorted(lags, key=lambda p: p.t0 - p.t1)
    ctx.notes["loop.lag_ms"] = {
        "n": len(lags), "total_ms": sum(p.t1 - p.t0 for p in lags) * 1e3,
        "longest": [row(p) for p in longest[:5]]}
    return (longest[0].t1 - longest[0].t0) * 1e3 if lags else 0.0
