"""One step timeline per engine, on the profiler's clock.

The dispatch thread of a ``TPUEngine`` reads a clock for step timing here
and nowhere else. Three kinds of event share one bounded ring:

- **spans** — ``with timeline.span(name, step=seq, kind=...)`` enters a
  ``jax.profiler.TraceAnnotation`` (so a profiler capture shows the host
  phase beside the device's programs on one clock) and appends
  ``("span", name, t0, t1, step, kind, replica)``;
- **steps** — one ``("step", seq, kind, width, rows, shape, t_dispatched,
  t_retired, replica, counts)`` per device dispatch (``shape`` is the prefill
  bucket or the decode context pages; ``counts`` is None, or for a model
  family whose step programs count on the device (``models/deepseek.py``)
  the step's ``StepCounts``: mean selected / context share of its rows,
  tokens through expert layers, token-expert pairs on held experts, and for
  a family with per-sequence state (``models/olmo_hybrid.py``) the live state
  rows and the real tokens its recurrence scanned, and for a family whose
  decode dispatch is a block step (``models/sdar.py``) its denoise passes,
  the tokens it emitted and the positions its threshold filled);
- **request stamps** — ``("req", phase, t, request_id, slot, replica)`` for
  ``submit`` / ``admit`` / ``first`` / ``done``.

All stamps are ``time.perf_counter()`` seconds. The ring is always on and
has no setting: it is a ``deque`` appended from the dispatch thread (and,
for ``submit``, the asyncio thread) and only ever copied by readers.
Readers without an engine handle find a timeline through
:func:`get_timeline` (the idiom of ``observability.tracing.get_tracer``).
"""

from __future__ import annotations

import weakref
from collections import deque
from time import perf_counter
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

# ~two minutes of chat traffic: a decode step leaves about nine events
RING_EVENTS = 1 << 16

SPAN, STEP, REQ = "span", "step", "req"


class SpanEvent(NamedTuple):
    name: str
    t0: float
    t1: float
    step: int
    kind: str
    replica: str


class StepCounts(NamedTuple):
    """What a step program counted on the device, read back with its tokens."""
    selected_share: float   # mean over the step's rows of selected / context
    moe_tokens: float       # tokens through expert layers
    moe_local_pairs: float  # token-expert pairs that landed on held experts
    state_rows_live: float = 0.0   # live per-sequence state rows the step touched
    scanned_tokens: float = 0.0    # real (unpadded) tokens the recurrence scanned
    # a block step (a family that generates by diffusion over blocks; the
    # record's ``rows`` are its blocks): forward passes that sampled, the
    # tokens the step emitted, positions filled by the confidence threshold
    denoise_passes: float = 0.0
    block_tokens: float = 0.0
    filled_by_threshold: float = 0.0


class StepEvent(NamedTuple):
    seq: int
    kind: str
    width: int
    rows: int
    shape: int | None       # prefill bucket, or decode context pages
    t_dispatched: float
    t_retired: float
    replica: str
    counts: StepCounts | None = None


class RequestEvent(NamedTuple):
    phase: str              # submit | admit | first | done
    t: float
    request_id: str
    slot: int
    replica: str


_EVENT_TYPES = {SPAN: SpanEvent, STEP: StepEvent, REQ: RequestEvent}


class _Span:
    """One open phase span; ``t0``/``t1`` stay readable after the block."""

    __slots__ = ("_timeline", "_row", "_annotation", "t0", "t1")

    def __init__(self, timeline: "StepTimeline", name: str, step: int,
                 kind: str) -> None:
        self._timeline = timeline
        self._row = (name, step, kind)
        # keyword metadata is only formatted while a capture runs
        self._annotation = TraceAnnotation(name, step=step, kind=kind)
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.t1 = perf_counter()
        self._annotation.__exit__(*exc)
        name, step, kind = self._row
        self._timeline.add_span(name, self.t0, self.t1, step, kind)

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


class StepTimeline:
    """The recorder one engine owns (``engine.timeline``)."""

    def __init__(self, replica_id: str = "0") -> None:
        self.replica = replica_id
        self._ring: deque[tuple] = deque(maxlen=RING_EVENTS)
        self._seq = 0
        # when the last device dispatch retired (None before the first):
        # the base of the next host-fed dispatch's gap, and step staleness
        self.last_retired: float | None = None
        _timelines[replica_id] = self

    def next_seq(self) -> int:
        """The sequence number of the dispatch about to be built."""
        self._seq += 1
        return self._seq

    def span(self, name: str, step: int = 0, kind: str = "") -> _Span:
        return _Span(self, name, step, kind)

    def add_span(self, name: str, t0: float, t1: float, step: int = 0,
                 kind: str = "") -> None:
        self._ring.append((SPAN, name, t0, t1, step, kind, self.replica))

    def step(self, seq: int, kind: str, width: int, rows: int,
             shape: int | None, t_dispatched: float, t_retired: float,
             counts: StepCounts | None = None) -> None:
        self._ring.append((STEP, seq, kind, width, rows, shape, t_dispatched,
                           t_retired, self.replica, counts))
        self.last_retired = t_retired

    def stamp(self, phase: str, request_id: str, slot: int,
              t: float | None = None) -> float:
        """Record one instant of a request's life; returns the stamp."""
        if t is None:
            t = perf_counter()
        self._ring.append((REQ, phase, t, request_id, slot, self.replica))
        return t

    def since_last_retired(self) -> float | None:
        if self.last_retired is None:
            return None
        return max(0.0, perf_counter() - self.last_retired)

    def snapshot(self) -> dict[str, list]:
        """A copy of the ring, split by kind, oldest first."""
        out: dict[str, list] = {SPAN: [], STEP: [], REQ: []}
        for event in list(self._ring):
            out[event[0]].append(_EVENT_TYPES[event[0]](*event[1:]))
        return out


_timelines: "weakref.WeakValueDictionary[str, StepTimeline]" = \
    weakref.WeakValueDictionary()


def get_timeline(replica_id: str = "0") -> StepTimeline | None:
    """The timeline of the newest live engine built as ``replica_id``."""
    return _timelines.get(replica_id)
