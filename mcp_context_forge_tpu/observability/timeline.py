"""One step timeline per engine, on the profiler's clock.

The dispatch thread of a ``TPUEngine`` reads a clock for step timing here
and nowhere else. Four kinds of event share one bounded ring:

- **spans** — ``with timeline.span(name, step=seq, kind=...)`` enters a
  ``jax.profiler.TraceAnnotation`` (so a profiler capture shows the host
  phase beside the device's programs on one clock) and appends
  ``("span", name, t0, t1, step, kind, replica, cpu)``; ``cpu`` is the
  seconds the span's thread spent ON the CPU (``time.thread_time()``), so
  ``(t1 - t0) - cpu`` is time the thread did not run: waiting for the
  interpreter lock, or blocked in a call. The CPU clock is a system call
  (6 us on the chip's host, whose kernel also counts it in 10 ms ticks:
  there a span's ``cpu`` means something summed over many spans, or for a
  long one), so a reading is shared by the span boundaries that fall within
  ``CPU_REUSE_S`` of it: a child's end and its parent's, a part's end and
  the next part's start. Spans nest: a dispatch's
  ``<p>.build`` holds ``.rows`` / ``.sampling`` / ``.rng`` and its
  ``<p>.dispatch`` holds ``.upload`` / ``.launch`` (``<p>`` is ``prefill``
  or ``decode``), with the parent's ``step`` and ``kind``;
- **steps** — one ``("step", seq, kind, width, rows, shape, t_dispatched,
  t_retired, replica, counts)`` per device dispatch (``shape`` is the length a
  prefill was padded to or the decode context pages; ``counts`` is None, or
  for a model family whose step programs count on the device
  (``models/deepseek.py``)
  the step's ``StepCounts``: mean selected / context share of its rows,
  tokens through expert layers, token-expert pairs on held experts, and for
  a family with per-sequence state (``models/olmo_hybrid.py``) the live state
  rows and the real tokens its recurrence scanned, and for a family whose
  decode dispatch is a block step (``models/sdar.py``) its denoise passes,
  the tokens it emitted and the positions its threshold filled);
- **request stamps** — ``("req", phase, t, request_id, slot, replica)``. The
  engine takes ``submit`` / ``admit`` / ``first`` / ``emit`` / ``deliver`` /
  ``done`` (``emit``: the dispatch thread's flush hands the request's first
  token to the loop; ``deliver``: it entered ``request.stream`` on the loop's
  thread). Around them stand the gateway's marks of the same request, handed
  over by its ``PhaseClock`` once the provider has the request's id
  (``observability/phases.py``; slot -1, all on the loop's thread): ``recv``,
  ``authed``, ``parsed``, ``tokenized`` before ``submit``, ``chunk`` and
  ``written`` after ``deliver``;
- **pauses** — ``("pause", cause, t0, t1, detail, thread)``: something held
  the whole process, or one thread that everything waits for. ``"gc"``
  (``detail`` its generation): a process-wide ``gc.callbacks`` hook writes
  every collection of at least ``PAUSE_S`` to every live timeline
  (:class:`_GcWatch`). ``"loop_lag"`` (``detail`` 0): the gateway's
  ``LoopLagSampler`` found the event loop at least ``PAUSE_S`` late for its
  tick (``t0`` when the tick was due, ``t1`` when it ran; ``thread`` is the
  loop's, which tells a loop that could not run from a collection that held
  every thread). A pause is NOT a span: it may come from any thread and cut
  across the dispatch thread's nesting.

All stamps are ``time.perf_counter()`` seconds. The ring is always on and
has no setting: it is a ``deque`` appended from the dispatch thread (and,
for the loop's stamps and pauses, other threads) and only ever copied
by readers. A span, eleven of a decode step's thirteen events, is held as one
packed ``bytes`` (:data:`_SPAN`), not a tuple: a tuple is a container the
collector counts, and while the ring grows every one kept is one more towards
the next collection. The ring's tuples were three quarters of this process's
whole stream of kept containers (a young collection a second in the chat
cell), and a third more spans a step brought the process's full collections
— 0.7 s each under a gateway's heap — into three windows of four where the
parent met one in four (``PERF.md`` section 6, PR 39).
Readers without an engine handle find a timeline through
:func:`get_timeline` (the idiom of ``observability.tracing.get_tracer``).
"""

from __future__ import annotations

import gc
import struct
import weakref
from bisect import bisect_left
from collections import deque
from threading import Lock, current_thread, get_ident
from time import perf_counter, thread_time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

# two minutes of the busiest cell: a decode step leaves about thirteen
# events, ~90 steps a second (tests/tpu_local/test_step_timeline.py counts)
RING_EVENTS = 1 << 18

SPAN, STEP, REQ, PAUSE = "span", "step", "req", "pause"

PAUSE_S = 1e-3      # a collection shorter than this is counted, not recorded
# span boundaries of one thread this close together share one reading of its
# CPU clock: a span's ``cpu`` is good to this, and never above wall + this
CPU_REUSE_S = 20e-6
# a host-fed dispatch's build.t0 -> dispatch.t1 beyond this is a stall (ten
# times the usual 2 ms): counted and logged where it happens (engine.py)
STALL_S = 0.02


class SpanEvent(NamedTuple):
    name: str
    t0: float
    t1: float
    step: int
    kind: str
    replica: str
    cpu: float = 0.0        # seconds on the CPU of the thread that ran it


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
    # a verify step of a family that drafts on the device: rows a draft could
    # have served (greedy, more than one token to go), rows that carried one,
    # drafts the step's own samples bore out, and the tokens the step emitted
    draft_wanted: float = 0.0
    draft_rows: float = 0.0
    drafts_accepted: float = 0.0
    spec_tokens: float = 0.0
    # a step of a model with window layers: the live rows' summed context,
    # and what of it a window layer sees (min(context, window) a row)
    context_keys: float = 0.0
    window_keys: float = 0.0


class StepEvent(NamedTuple):
    seq: int
    kind: str
    width: int
    rows: int
    shape: int | None       # prefill length dispatched, or decode context pages
    t_dispatched: float
    t_retired: float
    replica: str
    counts: StepCounts | None = None


class RequestEvent(NamedTuple):
    phase: str              # recv | authed | parsed | tokenized | submit |
    #                         admit | first | emit | deliver | chunk |
    #                         written | done
    t: float
    request_id: str
    slot: int
    replica: str


class PauseEvent(NamedTuple):
    cause: str              # "gc" | "loop_lag"
    t0: float
    t1: float
    detail: int             # gc: the generation collected; loop_lag: 0
    thread: str             # the thread the pause ran on


_EVENT_TYPES = {STEP: StepEvent, REQ: RequestEvent, PAUSE: PauseEvent}
# a span in the ring: t0, t1, cpu, step, and its name and kind by their
# numbers in the timeline's table of strings
_SPAN = struct.Struct("<dddqII")


class _Span:
    """One open phase span; ``t0``/``t1`` stay readable after the block."""

    __slots__ = ("_timeline", "_row", "_annotation", "t0", "t1", "cpu")

    def __init__(self, timeline: "StepTimeline", name: str, step: int,
                 kind: str) -> None:
        self._timeline = timeline
        self._row = (name, step, kind)
        # keyword metadata is only formatted while a capture runs
        self._annotation = TraceAnnotation(name, step=step, kind=kind)
        self.t0 = self.t1 = self.cpu = 0.0

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self.t0 = perf_counter()
        self.cpu = self._timeline.cpu_at(self.t0)
        return self

    def __exit__(self, *exc: Any) -> None:
        self.cpu = self._timeline.cpu_at(perf_counter()) - self.cpu
        self.t1 = perf_counter()
        self._annotation.__exit__(*exc)
        name, step, kind = self._row
        self._timeline.add_span(name, self.t0, self.t1, step, kind, self.cpu)

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
        # the newest pauses again, apart from the ring: a stall's log line
        # names the ones that overlap it without a walk of 2**18 events
        self._pauses: deque[PauseEvent] = deque(maxlen=64)
        # the last reading of a thread's CPU clock: (thread, when, seconds)
        self._cpu_mark = (0, 0.0, 0.0)
        # span names and kinds by number (a few dozen: the code's literals)
        self._strings: list[str] = []
        self._numbers: dict[str, int] = {}
        self._strings_lock = Lock()
        _timelines[replica_id] = self
        gc_watch.watch(self)

    def next_seq(self) -> int:
        """The sequence number of the dispatch about to be built."""
        self._seq += 1
        return self._seq

    def span(self, name: str, step: int = 0, kind: str = "") -> _Span:
        return _Span(self, name, step, kind)

    def cpu_at(self, now: float) -> float:
        """The calling thread's CPU seconds at ``now`` (a ``perf_counter``
        stamp just taken): the last reading where this thread took it less
        than ``CPU_REUSE_S`` ago, else a fresh one."""
        ident, at, cpu = self._cpu_mark
        if now - at < CPU_REUSE_S and ident == get_ident():
            return cpu
        cpu = thread_time()
        self._cpu_mark = (get_ident(), now, cpu)
        return cpu

    def _number(self, string: str) -> int:
        number = self._numbers.get(string)
        if number is None:
            with self._strings_lock:
                number = self._numbers.get(string)
                if number is None:
                    number = len(self._strings)
                    self._strings.append(string)
                    self._numbers[string] = number
        return number

    def add_span(self, name: str, t0: float, t1: float, step: int = 0,
                 kind: str = "", cpu: float = 0.0) -> None:
        self._ring.append(_SPAN.pack(t0, t1, cpu, step, self._number(name),
                                     self._number(kind)))

    def add_pause(self, cause: str, t0: float, t1: float, detail: int,
                  thread: str) -> None:
        self._ring.append((PAUSE, cause, t0, t1, detail, thread))
        self._pauses.append(PauseEvent(cause, t0, t1, detail, thread))

    def pauses_between(self, t0: float, t1: float) -> list[PauseEvent]:
        """The recent pauses that overlap ``[t0, t1]``."""
        return [p for p in list(self._pauses) if p.t0 < t1 and p.t1 > t0]

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

    @staticmethod
    def gc_stats() -> dict[str, dict[str, float]]:
        """The process's collections by generation, as the gc watch that
        feeds every timeline counted them."""
        return gc_watch.stats()

    def since_last_retired(self) -> float | None:
        if self.last_retired is None:
            return None
        return max(0.0, perf_counter() - self.last_retired)

    def snapshot(self) -> dict[str, list]:
        """A copy of the ring, split by kind, oldest first."""
        out: dict[str, list] = {SPAN: [], STEP: [], REQ: [], PAUSE: []}
        spans, strings, replica = out[SPAN], self._strings, self.replica
        for event in list(self._ring):
            if type(event) is bytes:
                t0, t1, cpu, step, name, kind = _SPAN.unpack(event)
                spans.append(SpanEvent(strings[name], t0, t1, step,
                                       strings[kind], replica, cpu))
            else:
                out[event[0]].append(_EVENT_TYPES[event[0]](*event[1:]))
        return out


class _GcWatch:
    """The collector's pauses, on the ring's clock. One ``gc.callbacks``
    entry for the process, installed when the first timeline is made and
    left in place (without a live timeline it only counts). The hook runs on
    every collection, gen-0 ones included, so a short one costs two clock
    reads, a bisection and two additions; one of ``PAUSE_S`` or more is written to every
    live timeline as a ``pause`` event. The collector is not re-entrant and
    runs under the interpreter lock: the hook needs no lock of its own."""

    # upper bounds of the pause histogram (mcpforge_gc_pause_seconds)
    BOUNDS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
              0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
    GENERATIONS = 3

    def __init__(self) -> None:
        n = self.GENERATIONS
        self.total_s = [0.0] * n
        self.longest_s = [0.0] * n
        # collections by generation and bucket (the last: above every bound)
        self.buckets = [[0] * (len(self.BOUNDS) + 1) for _ in range(n)]
        self._t0 = 0.0
        self._live: "weakref.WeakSet[StepTimeline]" = weakref.WeakSet()
        self.installed = False

    def watch(self, timeline: "StepTimeline") -> None:
        self._live.add(timeline)
        if not self.installed:
            self.installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict[str, int]) -> None:
        if phase == "start":
            self._t0 = perf_counter()
            return
        t1 = perf_counter()
        took = t1 - self._t0
        generation = info["generation"]
        self.total_s[generation] += took
        self.buckets[generation][bisect_left(self.BOUNDS, took)] += 1
        if took > self.longest_s[generation]:
            self.longest_s[generation] = took
        if took >= PAUSE_S:
            self.add_pause("gc", self._t0, t1, generation,
                           current_thread().name)

    def add_pause(self, cause: str, t0: float, t1: float, detail: int,
                  thread: str) -> None:
        """One pause onto every live timeline."""
        for timeline in list(self._live):
            timeline.add_pause(cause, t0, t1, detail, thread)

    def stats(self) -> dict[str, dict[str, float]]:
        """Count, total and longest by generation (``/admin/engine/stats``)."""
        return {f"gen{g}": {"collections": sum(self.buckets[g]),
                            "total_ms": round(self.total_s[g] * 1e3, 3),
                            "longest_ms": round(self.longest_s[g] * 1e3, 3)}
                for g in range(self.GENERATIONS)}


gc_watch = _GcWatch()

_timelines: "weakref.WeakValueDictionary[str, StepTimeline]" = \
    weakref.WeakValueDictionary()


def get_timeline(replica_id: str = "0") -> StepTimeline | None:
    """The timeline of the newest live engine built as ``replica_id``."""
    return _timelines.get(replica_id)
