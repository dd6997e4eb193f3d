"""The host's side of a dispatch as the step timeline names it since PR 39,
for the seven readers that share it (``hostfed.*``, ``host.*``,
``gateway.post_engine_ms_p50``), over the WHOLE measured window.

A HOST-FED dispatch (every kind but ``decode_fb``) is built with the device
drained, from the start of its ``<p>.build`` span to the end of its
``<p>.dispatch`` span. Inside them the program nests ``<p>.build.rows`` /
``.sampling`` / ``.rng`` and ``<p>.dispatch.upload`` / ``.launch``; every span
carries ``cpu``, the seconds its thread spent on the CPU; the ring holds
``pause`` events (what held the whole process: a garbage collection, with its
generation) and a ``deliver`` stamp a request (its first token entered the
request's stream on the loop's thread).

A program without these (the parent of PR 39: no child span, no ``cpu``, no
pause, no ``deliver``) gives None from the function that needs them, and the
reader built on it reports nothing and raises nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from . import timeline_view
from .stats import percentile

# the four named parts of a host-fed dispatch, by the spans that make each
PARTS = {
    "rows": ("build.rows",),
    "rng": ("build.rng",),
    "upload": ("build.sampling", "table_sync", "dispatch.upload"),
    "launch": ("dispatch.launch",),
}
CHILDREN = ("build.rows", "build.sampling", "build.rng", "dispatch.upload",
            "dispatch.launch")
# spans in which the dispatch thread WORKS (outermost only: a parent's cpu
# covers its children); *.sync, *.readback, loop.drain and loop.wait wait for
# the device or for work by design
WORK = ("prefill.build", "prefill.dispatch", "prefill.emit", "decode.build",
        "decode.table_sync", "decode.dispatch", "decode.emit", "admit",
        "loop.flush")


@dataclass
class Dispatch:
    """One host-fed dispatch: its spans by the name after ``<p>.``."""
    step: int
    kind: str
    spans: dict[str, Any]

    @property
    def t0(self) -> float:
        return self.spans["build"].t0

    @property
    def t1(self) -> float:
        return self.spans["dispatch"].t1

    def wall(self, *names: str) -> float:
        return sum(s.t1 - s.t0 for s in map(self.spans.get, names) if s)


@dataclass
class Host:
    view: timeline_view.View
    pauses: list            # timeline.PauseEvent, those that touch the window
    dispatches: list[Dispatch]      # host-fed, whole, inside the window


def load(ctx: Any) -> Host | None:
    """The window's host-fed dispatches with their parts, or None where the
    program's ring has no child span. Read once a context."""
    if "_host_parts" in ctx.__dict__:
        return ctx.__dict__["_host_parts"]
    ctx.__dict__["_host_parts"] = host = _load(ctx.window)
    return host


def _load(window: tuple[float, float]) -> Host | None:
    view = timeline_view.load()
    if view is None:
        return None
    by_step: dict[int, Dispatch] = {}
    for span in view.spans:
        family, _, part = span.name.partition(".")
        if family not in ("prefill", "decode") or span.kind == "decode_fb" \
                or not span.kind:
            continue
        row = by_step.setdefault(span.step, Dispatch(span.step, span.kind, {}))
        row.spans[part] = span
    lo, hi = window
    whole = [d for d in by_step.values()
             if "build" in d.spans and "dispatch" in d.spans
             and all(child in d.spans for child in CHILDREN)
             and lo <= d.t0 and d.t1 <= hi]
    if not whole:
        return None
    from mcp_context_forge_tpu.observability.timeline import get_timeline
    pauses = get_timeline("0").snapshot().get("pause", [])
    return Host(view, [p for p in pauses if p.t1 >= lo and p.t0 <= hi],
                sorted(whole, key=lambda d: d.t0))


def part_ms_mean(ctx: Any, part: str) -> float | None:
    """Mean over the window's host-fed dispatches of ``part``'s wall, ms; the
    note gives it by kind, and once for all four parts the check that they
    and the unnamed remainder of the parent spans make up ``build.t0 ->
    dispatch.t1``."""
    host = load(ctx)
    if host is None:
        return None
    rows = host.dispatches
    kinds: dict[str, list[Dispatch]] = {}
    for d in rows:
        kinds.setdefault(d.kind, []).append(d)

    def mean(ds: list[Dispatch], *names: str) -> float:
        return sum(d.wall(*names) for d in ds) * 1e3 / len(ds)

    ctx.notes[f"hostfed.{part}_ms"] = {
        "mean": mean(rows, *PARTS[part]), "n": len(rows),
        # one stalled dispatch moves a mean of hundreds; the median says so
        "p50": percentile([d.wall(*PARTS[part]) * 1e3 for d in rows], 50),
        "by_kind": {k: {"mean": mean(ds, *PARTS[part]), "n": len(ds)}
                    for k, ds in sorted(kinds.items())}}
    named = {p: mean(rows, *names) for p, names in PARTS.items()}
    # what the parents hold beside their children: bucket and program
    # lookups, the spans' own bookkeeping
    unnamed = mean(rows, "build", "dispatch") - mean(rows, *CHILDREN)
    whole = sum(d.t1 - d.t0 for d in rows) * 1e3 / len(rows)
    ctx.notes["hostfed.sum_check_ms"] = {
        **named, "unnamed": unnamed, "sum": sum(named.values()) + unnamed,
        "build_to_dispatch_mean": whole, "n": len(rows)}
    return named[part]


def off_cpu_share(ctx: Any) -> float | None:
    """100 x sum(wall - cpu) / sum(wall) over the window's spans in which the
    dispatch thread works; the note has the same by span name, the children
    too. None where spans carry no ``cpu``."""
    host = load(ctx)
    if host is None:
        return None
    lo, hi = ctx.window
    by_name: dict[str, list[float]] = {}
    for span in host.view.spans:
        if lo <= span.t0 and span.t1 <= hi:
            acc = by_name.setdefault(span.name, [0.0, 0.0, 0])
            acc[0] += span.t1 - span.t0
            acc[1] += span.cpu
            acc[2] += 1
    wall = sum(by_name[name][0] for name in WORK if name in by_name)
    cpu = sum(by_name[name][1] for name in WORK if name in by_name)
    if wall <= 0 or cpu <= 0:       # a span that ran has been on the CPU
        return None
    ctx.notes["host.off_cpu"] = {
        "wall_s": wall, "cpu_s": cpu,
        "by_span": {name: {"wall_ms_mean": w * 1e3 / n,
                           "off_cpu_share": 100.0 * (w - c) / w if w else 0.0,
                           "n": n}
                    for name, (w, c, n) in sorted(by_name.items())}}
    return 100.0 * (wall - cpu) / wall


def _span_at(view: timeline_view.View, t0: float, t1: float) -> str:
    cover = view.cover(t0, max(t1, t0 + 1e-9))
    return max(cover, key=cover.get)


def stall_ms_max(ctx: Any) -> float | None:
    """The longest host-fed ``build.t0 -> dispatch.t1`` of the window, ms.
    The note names it (step, kind, the child that held most of it with its
    wall and cpu, the pauses that overlap it), and gives the window's
    dispatches beyond ``STALL_S`` and its pauses (count, total, longest, by
    generation, and the span of the dispatch thread each fell in)."""
    host = load(ctx)
    if host is None:
        return None
    from mcp_context_forge_tpu.observability.timeline import STALL_S
    worst = max(host.dispatches, key=lambda d: d.t1 - d.t0)
    held = max(CHILDREN + ("table_sync",), key=lambda n: worst.wall(n))
    span = worst.spans[held]
    pauses = host.pauses
    by_generation: dict[str, dict[str, float]] = {}
    for p in pauses:
        row = by_generation.setdefault(f"{p.cause}{p.detail}",
                                       {"n": 0, "total_ms": 0.0, "longest_ms": 0.0})
        row["n"] += 1
        row["total_ms"] += (p.t1 - p.t0) * 1e3
        row["longest_ms"] = max(row["longest_ms"], (p.t1 - p.t0) * 1e3)
    ctx.notes["host.stall"] = {
        "ms": (worst.t1 - worst.t0) * 1e3, "step": worst.step,
        "kind": worst.kind, "at_s": worst.t0 - ctx.window[0],
        "held_by": held, "held_wall_ms": (span.t1 - span.t0) * 1e3,
        "held_cpu_ms": span.cpu * 1e3,
        "pauses_in_it": [_pause_row(host.view, p) for p in pauses
                         if p.t0 < worst.t1 and p.t1 > worst.t0],
        "dispatch_stalls": sum(1 for d in host.dispatches
                               if d.t1 - d.t0 > STALL_S),
        "stall_limit_ms": STALL_S * 1e3, "dispatches": len(host.dispatches)}
    ctx.notes["host.pauses"] = {
        "n": len(pauses),
        "total_ms": sum(p.t1 - p.t0 for p in pauses) * 1e3,
        "by_generation": by_generation,
        "longest": [_pause_row(host.view, p) for p in
                    sorted(pauses, key=lambda p: p.t0 - p.t1)[:5]]}
    return (worst.t1 - worst.t0) * 1e3


def _pause_row(view: timeline_view.View, pause: Any) -> dict[str, Any]:
    return {"cause": f"{pause.cause}{pause.detail}",
            "ms": (pause.t1 - pause.t0) * 1e3, "thread": pause.thread,
            "dispatch_thread_in": _span_at(view, pause.t0, pause.t1)}


def post_engine_ms_p50(ctx: Any) -> float | None:
    """Median over the window's requests of client's first token minus the
    engine's ``first`` stamp, ms; the note splits it at ``deliver``. None
    where the ring has no ``deliver`` stamp."""
    host = load(ctx)
    if host is None:
        return None
    view = host.view
    by_index = {r.index: r for r in ctx.records if r.ok and r.token_times}
    out, held, onward = [], [], []
    for index, (_entered, request) in ctx.submits.items():
        stamps = view.requests.get(request.request_id, {})
        record = by_index.get(index)
        if record is None or "first" not in stamps or "deliver" not in stamps:
            continue
        out.append((record.token_times[0] - stamps["first"]) * 1e3)
        held.append((stamps["deliver"] - stamps["first"]) * 1e3)
        onward.append((record.token_times[0] - stamps["deliver"]) * 1e3)
    if not out:
        return None
    ctx.notes["gateway.post_engine_ms"] = {
        "n": len(out), "p50": percentile(out, 50), "p95": percentile(out, 95),
        "first_to_deliver": {"p50": percentile(held, 50),
                             "p95": percentile(held, 95)},
        "deliver_to_client": {"p50": percentile(onward, 50),
                              "p95": percentile(onward, 95)}}
    return percentile(out, 50)
