"""Gateway data-plane flight recorder + event-loop health.

The serving engine is legible (step attribution, live roofline,
``/admin/engine/steps``); the gateway tier in front of it was not — the
r05 bench tail shows ``http.request: 3786 ms`` warnings with no
breakdown, and gateway RPS has been flat at ~900–1200 req/s across five
rounds while the engine got 4–60× faster. This module is the gateway's
instrument panel:

- :class:`FlightRecorder` — a bounded per-worker ring of completed
  requests (recent window + slowest-N retained by duration) with the
  phase vector each request's :class:`~..observability.phases.PhaseClock`
  accumulated, served at ``GET /admin/gateway/requests`` and mirrored
  into ``mcpforge_gw_request_phase_seconds{route,phase}``;
- an in-flight registry, so the loop-lag sampler can name the probable
  culprit request (longest-running in-flight) when the loop stalls;
- :class:`LoopLagSampler` — the runtime complement of mcpforge-lint's
  static ``async-blocking-call`` rule: a scheduled-callback delta
  sampler that measures how late the event loop runs a timer that asked
  for ``interval`` seconds. Sustained lag means a callback is blocking
  the loop (sync I/O, a long JSON encode, GC) — exactly the class of
  bug the linter catches statically, now measured in production;
- :func:`queue_state` — engine/pool admission depth and saturation, the
  pool→HTTP backpressure signal the middleware surfaces as
  ``X-Queue-Depth`` / ``Retry-After`` response headers.

Everything here runs on the gateway's asyncio loop; nothing is touched
from engine dispatch threads. The one thing written for the engine's
readers: a loop lag of a millisecond or more goes onto every live step
timeline as a ``pause`` of cause ``loop_lag`` (``observability/timeline.py``),
on the ring's own clock, so a reader of the ring sees a loop that could not
run beside the collections and the dispatch thread's spans.
"""

from __future__ import annotations

import asyncio
import bisect
import itertools
import logging
import math
import sys
import threading
import time
from collections import deque
from typing import Any

from ..observability.logging import trace_extra

logger = logging.getLogger(__name__)

# the step timelines' module, looked up and never imported from here: it
# pulls in jax, and a process that holds an engine has imported it already
# (tpu_local/engine.py). No module, no ring to write a pause to
_TIMELINE = __name__.replace("gateway.flight_recorder",
                             "observability.timeline")


class FlightRecorder:
    """Bounded request-attribution rings + in-flight registry.

    ``recent`` keeps the last ``ring_size`` completed requests in
    arrival order; ``slowest`` retains the ``slowest_size`` worst by
    wall duration across the worker's lifetime (an operator chasing the
    p99.9 tail needs the outliers to SURVIVE churn — a recency ring
    alone forgets them within seconds at 1k rps). Both are plain lists
    of dicts, mutated only on the event loop."""

    def __init__(self, metrics: Any = None, ring_size: int = 256,
                 slowest_size: int = 32,
                 slow_request_s: float = 1.0,
                 worker: str = "") -> None:
        self.metrics = metrics
        # multi-worker attribution (docs/scaleout.md): every row carries
        # the serving worker's id so a merged fleet view can say WHICH
        # process served the outlier
        self.worker = worker
        self.ring_size = max(1, int(ring_size))
        self.slowest_size = max(1, int(slowest_size))
        self.slow_request_s = max(0.0, float(slow_request_s))
        self.recent: deque[dict[str, Any]] = deque(maxlen=self.ring_size)
        self._slowest: list[tuple[float, int, dict[str, Any]]] = []
        self._seq = itertools.count()
        self.recorded = 0
        self.slow_requests = 0
        # request_id -> {started, path, trace} of requests mid-handling
        self.inflight: dict[int, dict[str, Any]] = {}

    # ------------------------------------------------------------- in-flight

    def start_request(self, path: str,
                      trace: tuple[str, str] | None) -> int:
        rid = next(self._seq)
        self.inflight[rid] = {"started": time.monotonic(), "path": path,
                              "trace": trace}
        return rid

    def finish_request(self, rid: int) -> None:
        self.inflight.pop(rid, None)

    def longest_inflight(self) -> dict[str, Any] | None:
        """The oldest request still being handled — the loop-lag
        sampler's best guess at "who blocked the loop"."""
        if not self.inflight:
            return None
        entry = min(self.inflight.values(), key=lambda e: e["started"])
        return {"path": entry["path"], "trace": entry["trace"],
                "age_s": round(time.monotonic() - entry["started"], 3)}

    # ------------------------------------------------------------- recording

    def record(self, *, method: str, path: str, route: str, status: int,
               duration_s: float, phases_ms: dict[str, float],
               trace_id: str | None = None, span_id: str | None = None,
               correlation_id: str | None = None,
               tenant: str | None = None,
               error: str | None = None,
               client_disconnected: bool = False) -> dict[str, Any]:
        """Append one completed request to the rings + Prometheus."""
        entry = {
            "ts": time.time(),
            "method": method,
            "path": path,
            "route": route,
            "status": status,
            "duration_ms": round(duration_s * 1e3, 3),
            "phases_ms": phases_ms,
        }
        if self.worker:
            entry["worker"] = self.worker
        if tenant:
            # rows keep the EXACT tenant (bounded ring, no cardinality
            # concern); only the Prometheus label below is clamped
            entry["tenant"] = tenant
        if trace_id:
            entry["trace_id"] = trace_id
            if span_id:
                entry["span_id"] = span_id
        if correlation_id:
            entry["correlation_id"] = correlation_id
        if error:
            entry["error"] = error
        if client_disconnected:
            entry["client_disconnected"] = True
        self.recorded += 1
        self.recent.append(entry)
        # slowest-N: keep sorted ascending by duration, evict the fastest
        key = (entry["duration_ms"], next(self._seq))
        if (len(self._slowest) < self.slowest_size
                or key[0] > self._slowest[0][0]):
            bisect.insort(self._slowest, (key[0], key[1], entry))
            if len(self._slowest) > self.slowest_size:
                self._slowest.pop(0)
        metrics = self.metrics
        if metrics is not None:
            tenant_label = metrics.tenant_clamp.label(tenant or "anonymous")
            for phase_name, ms in phases_ms.items():
                metrics.gw_request_phase.labels(
                    route=route, phase=phase_name,
                    tenant=tenant_label).observe(ms / 1e3)
        # strictly-greater, matching PerformanceTracker.record's slow
        # branch — the two consumers of gw_slow_request_s must agree on
        # one bar (the walls differ by the recorder's own µs overhead;
        # the operator at least must not add a systematic disagreement)
        slow = self.slow_request_s and duration_s > self.slow_request_s
        if slow:
            self.slow_requests += 1
            if metrics is not None:
                metrics.gw_slow_requests.labels(route=route).inc()
            # the r05 tail's "http.request: 3786 ms" line, upgraded: the
            # phase vector says WHERE the milliseconds went, and the
            # explicit trace ctx joins the line to its OTel trace even
            # from producers off the contextvar chain
            logger.warning(
                "slow request %s %s -> %s: %.1f ms (threshold %.1f ms) "
                "phases=%s", method, path, status, duration_s * 1e3,
                self.slow_request_s * 1e3, phases_ms,
                extra=trace_extra((trace_id, span_id or "")
                                  if trace_id else None))
        return entry

    # ------------------------------------------------------------- reporting

    def slowest(self) -> list[dict[str, Any]]:
        """Worst-duration-first."""
        return [entry for _, _, entry in reversed(self._slowest)]

    def find_trace(self, trace_id: str) -> dict[str, Any] | None:
        """The recorder row for one trace id (slowest ring first — the
        waterfall endpoint's gateway-side join; a row present in both
        rings is the same dict object)."""
        for entry in self.slowest():
            if entry.get("trace_id") == trace_id:
                return entry
        for entry in reversed(self.recent):
            if entry.get("trace_id") == trace_id:
                return entry
        return None

    def snapshot(self, limit: int = 64,
                 tenant: str | None = None) -> dict[str, Any]:
        """Ring contents; ``tenant`` filters both rings to one tenant's
        rows (exact match on the row's unclamped tenant)."""
        limit = max(1, limit)
        slowest = self.slowest()
        recent = list(self.recent)[::-1]  # newest first
        if tenant:
            slowest = [r for r in slowest if r.get("tenant") == tenant]
            recent = [r for r in recent if r.get("tenant") == tenant]
        out = {
            "worker": self.worker or None,
            "recorded": self.recorded,
            "slow_requests": self.slow_requests,
            "slow_request_ms": round(self.slow_request_s * 1e3, 1),
            "ring_size": self.ring_size,
            "inflight": len(self.inflight),
            "slowest": slowest[:limit],
            "recent": recent[:limit],
        }
        if tenant:
            out["tenant"] = tenant
        return out


class LoopLagSampler:
    """Asyncio event-loop health: scheduled-callback delta sampling.

    Each tick asks the loop for ``interval`` seconds of sleep and
    measures how much LATER it actually ran; that delta is the time the
    loop spent unable to service timers — i.e. blocked in somebody's
    callback. Observed into ``mcpforge_gw_loop_lag_seconds`` and kept as
    a max-lag high-water mark; a tick beyond ``warn_s`` logs a
    long-callback warning naming the longest in-flight request (the
    probable culprit) with its trace ids, so the line joins the same
    OTel trace the flight-recorder row is in."""

    def __init__(self, metrics: Any = None, interval_s: float = 0.25,
                 warn_s: float = 0.25,
                 recorder: FlightRecorder | None = None) -> None:
        self.metrics = metrics
        self.interval_s = max(0.01, float(interval_s))
        self.warn_s = max(0.0, float(warn_s))
        self.recorder = recorder
        # optional live-signal bus (observability/signals.py): every
        # sample is also pushed as gw.loop_lag_ms so the serving
        # controller sees gateway loop health at its own tick
        self.signals = None
        self.samples = 0
        self.long_callbacks = 0
        self.max_lag_s = 0.0
        self.last_lag_s = 0.0
        self._task: asyncio.Task | None = None
        self._warn_bucket = 0.0  # rate limit: at most 1 warn / 5 s

    async def start(self) -> None:
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(
                self._run(), name="gw-loop-lag-sampler")

    async def stop(self) -> None:
        task, self._task = self._task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass

    async def _run(self) -> None:
        # perf_counter, the step timelines' clock (and finer than a loop's
        # own time() where that is cached a loop iteration)
        while True:
            due = time.perf_counter() + self.interval_s
            await asyncio.sleep(self.interval_s)
            ran = time.perf_counter()
            self._observe(max(0.0, ran - due), ran)

    def _observe(self, lag: float, ran: float) -> None:
        """One tick that ran at ``ran``, ``lag`` seconds after it was due."""
        self.samples += 1
        self.last_lag_s = lag
        self.max_lag_s = max(self.max_lag_s, lag)
        timeline = sys.modules.get(_TIMELINE)
        if timeline is not None and lag >= timeline.PAUSE_S:
            timeline.gc_watch.add_pause("loop_lag", ran - lag, ran, 0,
                                        threading.current_thread().name)
        if self.metrics is not None:
            self.metrics.gw_loop_lag.observe(lag)
        if self.signals is not None:
            self.signals.publish("gw.loop_lag_ms", lag * 1e3)  # lint: allow[signal-name-conformance] dashboard-only export via the /signals snapshot; no steering consumer
        if self.warn_s and lag >= self.warn_s:
            self.long_callbacks += 1
            now = time.monotonic()
            if now >= self._warn_bucket:
                self._warn_bucket = now + 5.0
                culprit = (self.recorder.longest_inflight()
                           if self.recorder is not None else None)
                logger.warning(
                    "event loop lagged %.1f ms (bar %.1f ms) — a callback "
                    "blocked the loop%s", lag * 1e3, self.warn_s * 1e3,
                    (f"; longest in-flight: {culprit['path']} "
                     f"({culprit['age_s']} s)" if culprit else ""),
                    extra=trace_extra(culprit["trace"] if culprit else None))

    def snapshot(self) -> dict[str, Any]:
        return {
            "interval_s": self.interval_s,
            "warn_ms": round(self.warn_s * 1e3, 1),
            "samples": self.samples,
            "last_lag_ms": round(self.last_lag_s * 1e3, 3),
            "max_lag_ms": round(self.max_lag_s * 1e3, 3),
            "long_callbacks": self.long_callbacks,
        }


def compute_queue_state(pool: Any, engine: Any) -> dict[str, Any] | None:
    """Depth/capacity/saturation from a replica pool or single engine —
    the pure half of ``queue_state`` (no app, no metrics side effect),
    shared with the shared-engine-plane's ``pool.queue_state`` RPC so
    every worker reports the SAME arithmetic."""
    no_replicas = False
    if pool is not None:
        ready = [r for r in pool.replicas if r.state == "ready"]
        depth = sum(r.engine.stats.queue_depth for r in ready)
        capacity = sum(r.engine.config.max_queue for r in ready)
        no_replicas = not ready  # every replica dead/draining
    elif engine is not None:
        depth = engine.stats.queue_depth
        capacity = engine.config.max_queue
    else:
        return None
    if no_replicas:
        saturation = 1.0  # nothing routable: saturated by definition
    elif capacity > 0:
        saturation = min(1.0, depth / capacity)
    else:
        # max_queue<=0 means an UNBOUNDED admission queue (queue.Queue
        # maxsize semantics) — never "full", not permanently saturated
        saturation = 0.0
    return {"depth": int(depth), "capacity": int(capacity),
            "saturation": round(saturation, 4)}


def queue_state(app: Any) -> dict[str, Any] | None:
    """Engine/pool admission state as the HTTP tier's backpressure
    signal: queued work summed over ROUTABLE replicas, capacity from the
    per-engine admission bound, saturation = depth/capacity. None when
    no engine is wired (nothing to backpressure against). Every
    computation refreshes the ``mcpforge_gw_engine_saturation`` gauge —
    here rather than in the header-writing branch, so SSE responses
    (headers set pre-prepare) and header-disabled deployments still
    feed the metric.

    Shared-engine-plane topology (tpu_local/pool_rpc.py): only the
    leader-elected owner has local engine objects; every other worker
    reads the LEADER's admission state through the plane's short-TTL
    bus-RPC cache — a non-owner must never report a worker-local zero
    while the owner's queue is drowning (the in-process bench masked
    this; the real-process arm exposed it)."""
    state = compute_queue_state(app.get("tpu_engine_pool"),
                                app.get("tpu_engine"))
    if state is None:
        plane = app.get("engine_plane")
        if plane is not None:
            state = plane.queue_state_sync()
    if state is None:
        return None
    ctx = app.get("ctx")
    metrics = getattr(ctx, "metrics", None) if ctx is not None else None
    if metrics is not None:
        metrics.gw_engine_saturation.set(state["saturation"])
    return state


def retry_after_s(saturation: float, advisory_at: float = 0.8) -> int:
    """Suggested client backoff once saturation crosses the advisory
    bar: scales 1 s at the bar → 8 s at full saturation (a fixed
    punitive value would just synchronize retries)."""
    at = min(advisory_at, 1.0 - 1e-6)  # a bar AT 1.0 still ramps
    frac = max(0.0, saturation - at) / (1.0 - at)
    return max(1, min(8, math.ceil(frac * 8.0)))


def backpressure_headers(state: dict[str, Any] | None,
                         settings: Any) -> dict[str, str]:
    """THE header contract for engine-admission backpressure, shared by
    the unary middleware path and the SSE pre-prepare path (a change to
    the contract must land in both at once)."""
    if state is None:
        return {}
    headers = {"X-Queue-Depth": str(state["depth"])}
    advisory_at = settings.gw_backpressure_retry_after_at
    if state["saturation"] >= advisory_at:
        headers["Retry-After"] = str(
            retry_after_s(state["saturation"], advisory_at))
    return headers
