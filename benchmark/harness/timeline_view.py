"""The engine's step timeline (``observability/timeline.py``) as the
per-layer readers see it: the host's phase spans flattened to disjoint
segments (the innermost span owns each instant), the step records, each
request's four stamps, and the bridge onto the profiler trace's clock.

Ring stamps are ``time.perf_counter()`` seconds, the clock of
``LayerContext.window`` and ``trace_span``. ``trace_span`` and the trace's
``window_ns`` are the same two marks, so one offset carries a stamp onto the
trace: ``t_ns = window_ns[0] + (t - trace_span[0]) * 1e9``.

The profiler places the device's events on the host's clock only to within
a few milliseconds (2.4 ms too early in the first captures: PERF.md), which
is as long as the gaps to be explained. :func:`clock_skew` bounds that error
from causality and the idle reader moves the device's events by it.

A program without the timeline (a commit before it) gives ``None`` from
:func:`load`, and every reader built on it then reports nothing.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import Any

UNLABELLED = "(no span)"
WAIT = "loop.wait"


def category(name: str) -> str:
    """The five classes a wait or a stall is split into."""
    if name.startswith("prefill."):
        return "prefill"
    if name.startswith("decode."):
        return "decode"
    return {"loop.drain": "drain", WAIT: "loop.wait"}.get(name, "rest")


def flatten(spans: list) -> list[tuple[float, float, str]]:
    """Disjoint ``(t0, t1, name)`` segments in time order from the properly
    nested spans of one thread: the innermost open span owns each instant;
    time under no span is left out."""
    out: list[tuple[float, float, str]] = []
    stack: list = []
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1].t1 <= t:
            top = stack.pop()
            if top.t1 > cursor:
                out.append((cursor, top.t1, top.name))
                cursor = top.t1

    for span in sorted(spans, key=lambda s: (s.t0, -s.t1)):
        close_until(span.t0)
        if stack and span.t0 > cursor:
            out.append((cursor, span.t0, stack[-1].name))
        cursor = max(cursor, span.t0)
        stack.append(span)
    close_until(float("inf"))
    return out


@dataclass
class View:
    spans: list                       # timeline.SpanEvent, ring order
    steps: list                       # timeline.StepEvent, by t_retired
    requests: dict[str, dict[str, float]]   # request id -> phase -> stamp
    segments: list[tuple[float, float, str]]
    _starts: list[float]

    def cover(self, lo: float, hi: float) -> dict[str, float]:
        """Seconds of ``[lo, hi]`` by the span that owns them; the rest is
        under ``UNLABELLED``."""
        out: dict[str, float] = {}
        if hi <= lo:
            return out
        i = max(0, bisect.bisect_right(self._starts, lo) - 1)
        labelled = 0.0
        while i < len(self.segments) and self.segments[i][0] < hi:
            t0, t1, name = self.segments[i]
            part = min(t1, hi) - max(t0, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                labelled += part
            i += 1
        if hi - lo - labelled > 1e-12:
            out[UNLABELLED] = hi - lo - labelled
        return out

    def decode_steps(self, window: tuple[float, float]) -> list:
        lo, hi = window
        return [s for s in self.steps
                if s.kind in ("decode", "decode_fb", "spec")
                and lo <= s.t_retired <= hi]


def load(replica_id: str = "0") -> View | None:
    """The newest engine's ring, or None where the program has none."""
    try:
        from mcp_context_forge_tpu.observability.timeline import get_timeline
    except ImportError:
        return None
    timeline = get_timeline(replica_id)
    if timeline is None:
        return None
    ring = timeline.snapshot()
    requests: dict[str, dict[str, float]] = {}
    for event in ring["req"]:
        requests.setdefault(event.request_id, {})[event.phase] = event.t
    segments = flatten(ring["span"])
    return View(spans=ring["span"],
                steps=sorted(ring["step"], key=lambda s: s.t_retired),
                requests=requests, segments=segments,
                _starts=[s[0] for s in segments])


def by_category(seconds_by_span: dict[str, float]) -> dict[str, float]:
    out = {"prefill": 0.0, "decode": 0.0, "drain": 0.0, "loop.wait": 0.0,
           "rest": 0.0}
    for name, seconds in seconds_by_span.items():
        out[category(name)] += seconds
    return out


def retire_intervals(view: View, window: tuple[float, float]
                     ) -> list[tuple[float, float, float]]:
    """``(start, end, seconds)`` between consecutive decode retires in the
    window, ``seconds`` without the time the thread had nothing to do."""
    steps = view.decode_steps(window)
    out = []
    for before, after in zip(steps, steps[1:]):
        lo, hi = before.t_retired, after.t_retired
        out.append((lo, hi, hi - lo - view.cover(lo, hi).get(WAIT, 0.0)))
    return out


FAMILY = {"prefill": "prefill", "prefill_hist": "prefill", "chunk": "prefill",
          "decode": "decode", "decode_fb": "decode", "spec": "decode"}
MATCH_S = 0.02          # a program farther from a step's retire is not that step's


def ring_clock(ctx: Any, skew_s: float = 0.0):
    """Trace nanoseconds -> ring seconds: the two window marks give the
    offset, ``skew_s`` moves the device's events later on the host's clock."""
    w0, origin = ctx.trace.window_ns[0], ctx.trace_span[0]
    return lambda ns: origin + (ns - w0) / 1e9 + skew_s


def match_programs(ctx: Any, view: View) -> list[tuple[Any, float, float]]:
    """``(step record, program start, program end)`` on the ring's clock,
    unskewed, for the traced span's steps: the program of the step's family
    whose end lies nearest the step's retire. (The first device's programs:
    every device of one engine runs the same ones.)"""
    clock = ring_clock(ctx)
    device = next(iter(ctx.trace.devices.values()))
    programs: dict[str, list[tuple[float, float]]] = {"prefill": [], "decode": []}
    for start, end, _name, kind in device.modules:
        family = FAMILY.get(kind)
        if family:
            programs[family].append((clock(end), clock(start)))
    for rows in programs.values():
        rows.sort()
    lo, hi = ctx.trace_span
    out = []
    for step in view.steps:
        rows = programs.get(FAMILY.get(step.kind, ""), [])
        if not rows or not lo <= step.t_retired <= hi:
            continue
        i = bisect.bisect_left(rows, (step.t_retired, 0.0))
        end, start = min(rows[max(0, i - 1):i + 1],
                         key=lambda row: abs(row[0] - step.t_retired))
        if abs(end - step.t_retired) < MATCH_S:
            out.append((step, start, end))
    return out


def clock_skew(matched: list[tuple[Any, float, float]]) -> dict[str, Any] | None:
    """How far the device's events sit from the host's clock after the
    bridge, from what cannot happen: no program starts before the call that
    dispatches it begins (the device's events are at least ``lower`` too
    early), and no result reaches the host before its program has ended (at
    most ``upper`` too early). Robust extremes (5 % of the samples are let
    go), seconds. ``applied`` is ``upper``: the fastest read-back of the
    capture is taken as immediate."""
    if len(matched) < 8:
        return None
    from .stats import percentile
    upper = percentile([s.t_retired - end for s, _start, end in matched], 5)
    lower = percentile([s.t_dispatched - start for s, start, _end in matched], 95)
    return {"lower": lower, "upper": upper, "applied": upper,
            "consistent": lower <= upper, "samples": len(matched)}


def traced_on_device(ctx: Any) -> bool:
    """Whether the context holds a trace with a device plane and its span."""
    trace = ctx.trace
    return bool(trace is not None and trace.devices
                and ctx.trace_span is not None and trace.window_s > 0)


def device_idle(ctx: Any, skew_s: float = 0.0
                ) -> list[list[tuple[float, float]]]:
    """Per device, the spans of the traced window in which no operation ran,
    in seconds on the ring's clock."""
    trace = ctx.trace
    w0, w1 = trace.window_ns
    clock = ring_clock(ctx, skew_s)
    out = []
    for device in trace.devices.values():
        gaps, reach = [], w0
        for start, end in sorted(e[:2] for e in (device.ops or device.modules)):
            if start > reach:
                gaps.append((clock(reach), clock(start)))
            reach = max(reach, end)
        if w1 > reach:
            gaps.append((clock(reach), clock(w1)))
        out.append(gaps)
    return out
