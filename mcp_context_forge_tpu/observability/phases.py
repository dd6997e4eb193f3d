"""Per-request wall-time phase attribution (gateway flight recorder).

The engine got its "where do the milliseconds go" answer in the
step timeline (``observability/timeline.py``); this is
the GATEWAY-side twin. A :class:`PhaseClock` rides each HTTP request in
a contextvar: the flight-recorder middleware opens it, and every layer
that owns a distinguishable phase — auth resolution, the plugin hook
pipeline, DB statements, the engine handoff, response serialization —
adds its measured wall into a named bucket. The clock is deliberately
layer-agnostic (plugins/framework.py and db/core.py must not import the
gateway package), which is why it lives under ``observability/``.

Attribution semantics:

- phases are **self-time**: ``phase()`` blocks nest, and a child's wall
  is subtracted from its enclosing phase, so the vector sums to at most
  the request wall instead of double-counting wrapped layers;
- the residue (request wall minus every attributed phase) is reported
  by the middleware as the ``handler`` phase — request parsing, route
  matching, business logic nobody instrumented — so the invariant
  ``sum(phases) ≈ wall`` holds by construction and is tolerance-gated
  in tests (a layer double-charging time breaks it);
- everything is wall time on the event loop: a phase that spans an
  ``await`` includes the loop's time servicing OTHER requests. That is
  the honest per-request latency attribution (it is what the client
  waited), and the loop-lag sampler is the signal that separates "slow
  phase" from "starved loop".

Beside the sums the clock keeps **marks**: the FIRST ``time.perf_counter()``
under a name, taken where a bucket is already bracketed (a ``phase()`` block
hands its end reading to a mark, so one instant is read once). A sum cannot
say what happened before the first token; the marks can: ``recv`` (the
middleware's entry), ``authed``, ``parsed`` (JSON, shed check and model
resolved), ``tokenized`` (template rendered and tokenised), ``chunk`` (the
first content chunk built) and ``written`` (its SSE write returned). Once
the provider has the engine's request it ties the clock to the request's id
(:meth:`PhaseClock.tie`), and the marks become request stamps on the
engine's step timeline, between the ones the engine takes itself
(``docs/observability.md``, "Step timeline").
"""

from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager
from typing import Callable, Iterator

_current_clock: contextvars.ContextVar["PhaseClock | None"] = \
    contextvars.ContextVar("mcpforge_phase_clock", default=None)


class PhaseClock:
    """Named wall-time buckets for one request, self-time on nesting."""

    __slots__ = ("phases", "_stack", "marks", "_stamp", "_request_id")

    def __init__(self) -> None:
        self.phases: dict[str, float] = {}
        # (name, start, child_seconds) of every open phase() block
        self._stack: list[list] = []
        # name -> the first perf_counter() taken under it
        self.marks: dict[str, float] = {}
        # set by tie(): a step timeline's ``stamp`` and the engine's id of
        # this request; from then on a mark is a request stamp on that ring
        self._stamp: Callable[..., float] | None = None
        self._request_id = ""

    def mark(self, name: str, t: float | None = None) -> float:
        """Keep the FIRST instant under ``name`` (``t``, or now) and return
        what is kept; a later call under the same name changes nothing."""
        kept = self.marks.get(name)
        if kept is not None:
            return kept
        if t is None:
            t = time.perf_counter()
        self.marks[name] = t
        if self._stamp is not None:
            self._stamp(name, self._request_id, -1, t)
        return t

    def tie(self, stamp: Callable[..., float], request_id: str) -> None:
        """From here on the marks are request stamps under ``request_id``
        (``stamp`` is ``StepTimeline.stamp``); the ones taken before the id
        existed are handed over now, with their own times. A request that
        never reaches an engine is never tied and leaves no stamp."""
        if self._stamp is not None:
            return
        self._stamp, self._request_id = stamp, request_id
        for name, t in self.marks.items():
            stamp(name, request_id, -1, t)

    def add(self, name: str, seconds: float) -> None:
        """Charge ``seconds`` to ``name`` directly (pre-measured work,
        e.g. a DB statement's in-lock time). Counts as child time of any
        enclosing phase() block so wrappers don't double-charge."""
        if seconds < 0.0:
            return
        self.phases[name] = self.phases.get(name, 0.0) + seconds
        if self._stack:
            self._stack[-1][2] += seconds

    @contextmanager
    def phase(self, name: str, mark: str | None = None) -> Iterator[None]:
        """Charge the block's SELF time to ``name`` (elapsed minus any
        nested phase()/add() time); the block's end is also the mark
        ``mark``, where one is named."""
        frame = [name, time.perf_counter(), 0.0]
        self._stack.append(frame)
        try:
            yield
        finally:
            ended = time.perf_counter()
            elapsed = ended - frame[1]
            if mark is not None:
                self.mark(mark, ended)
            # tolerate mis-nesting from concurrent same-request tasks:
            # pop OUR frame wherever it sits rather than corrupting the
            # stack (attribution degrades, accounting never crashes)
            try:
                self._stack.remove(frame)
            except ValueError:  # pragma: no cover - defensive
                pass
            self.add(name, max(0.0, elapsed - frame[2]))

    def total(self) -> float:
        return sum(self.phases.values())

    def vector_ms(self) -> dict[str, float]:
        """{phase: milliseconds} rounded for logs/rings/JSON."""
        return {name: round(seconds * 1e3, 3)
                for name, seconds in sorted(self.phases.items())}


def current_phases() -> PhaseClock | None:
    """The request's clock, or None outside an instrumented request —
    producers must treat None as "attribution off" (zero cost)."""
    return _current_clock.get()


def set_phase_clock(clock: PhaseClock | None) -> contextvars.Token:
    return _current_clock.set(clock)


def reset_phase_clock(token: contextvars.Token) -> None:
    try:
        _current_clock.reset(token)
    except ValueError:  # foreign-context reset (generator teardown)
        pass


def add_phase(name: str, seconds: float) -> None:
    """Charge time to the current request's clock, if any. The one-line
    producer API for layers that only ever add (db/core.py)."""
    clock = _current_clock.get()
    if clock is not None:
        clock.add(name, seconds)


@contextmanager
def phase(name: str, mark: str | None = None) -> Iterator[None]:
    """Self-time phase block against the current clock; no-op without
    one (the same code path serves instrumented and bare calls)."""
    clock = _current_clock.get()
    if clock is None:
        yield
        return
    with clock.phase(name, mark):
        yield
