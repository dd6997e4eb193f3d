"""Replica health monitoring for the EnginePool.

Two failure modes, two signals (both read-only, both host-side):

- **crashed** — the dispatch thread died (device fault without
  ``auto_restart``, or restarts exhausted): ``engine.dispatch_alive()``
  goes false. The engine's own ``_fail_outstanding`` already terminated
  every stream with ``finish_reason="error"``, so the pool's per-request
  pumps see the terminals and requeue; the monitor's job is to mark the
  replica dead so the router stops sending it new work, and to catch any
  record whose pump raced the crash.
- **wedged** — the thread is alive but stuck inside a device call (a
  post-warmup runtime fault): the dispatch-loop heartbeat
  goes stale while the replica still holds in-flight work. An IDLE
  engine also beats (the idle wait is bounded at 50 ms), so staleness
  is only read against replicas with outstanding requests — and only
  against WARMED engines. On an unwarmed engine any dispatch, first or
  mid-traffic (a new batch width, a bigger ctx bucket), may
  legitimately sit in an XLA compile longer than any sane heartbeat
  bar, and killing a compiling replica cascades: its work requeues onto
  an equally unwarmed survivor that compiles the same shapes. A warmed
  engine has no compiles left (the grid is precompiled under the
  traffic cache key), so staleness there is a genuine stall. Unwarmed
  pools keep crash detection only — run ``tpu_local_warmup`` with
  pools (docs/serving_pool.md).

On detection the monitor kills the engine (signal, no join — a wedged
thread must not delay failover), marks the replica dead, and asks the
pool to requeue its in-flight requests onto healthy replicas.

Runs as an asyncio task on the gateway loop — all pool state stays
single-threaded (the ``thread[pool]`` lint boundary); only the engines'
own dispatch threads are separate, and the monitor touches them through
the read-only liveness API + kill().
"""

from __future__ import annotations

import asyncio
import logging
from typing import TYPE_CHECKING

if TYPE_CHECKING:  # pragma: no cover - typing only
    from .pool import EnginePool

logger = logging.getLogger(__name__)


class HealthMonitor:
    """Periodic liveness sweep over the pool's replicas."""

    def __init__(self, pool: "EnginePool", interval_s: float = 0.5,
                 heartbeat_timeout_s: float = 10.0) -> None:
        self.pool = pool
        self.interval_s = max(0.01, interval_s)
        self.heartbeat_timeout_s = max(0.05, heartbeat_timeout_s)
        self._task: asyncio.Task | None = None
        self.sweeps = 0           # lint: thread[pool]
        self.failures = 0         # lint: thread[pool]

    async def start(self) -> None:
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(
                self._run(), name="engine-pool-health")

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    async def _run(self) -> None:  # lint: runs-on[pool]
        while True:
            await asyncio.sleep(self.interval_s)
            try:
                self.sweep()
            except Exception:  # the monitor must outlive a bad sweep
                logger.exception("engine pool health sweep failed")

    def sweep(self) -> None:  # lint: runs-on[pool]
        """One liveness pass; synchronous so tests can drive it directly."""
        self.sweeps += 1
        for replica in self.pool.replicas:
            if replica.state not in ("ready", "draining"):
                continue
            verdict = self.verdict(replica)
            if verdict is not None:
                self.failures += 1
                logger.error("engine pool: replica %s (role %s) %s — "
                             "failing over", replica.id, replica.role,
                             verdict)
                self.pool.fail_replica(replica, reason=verdict)

    def verdict(self, replica) -> str | None:
        """None = healthy; otherwise a short reason string."""
        engine = replica.engine
        if not engine.dispatch_alive():
            return "dispatch thread dead"
        if replica.outstanding and engine.warmed:
            # wedge detection is armed only on WARMED engines: on an
            # unwarmed one ANY dispatch — first or mid-traffic (a new
            # batch width, a bigger ctx bucket) — may legitimately sit in
            # an XLA compile longer than the heartbeat bar, and killing a
            # compiling replica requeues its work onto an equally
            # unwarmed survivor that compiles the same shapes: a
            # monitor-induced cascade. A warmed engine has no compiles
            # left (the grid is precompiled under the traffic cache key),
            # so staleness there is a genuine stall. Unwarmed pools keep
            # crash detection (dispatch_alive, above) only — run
            # tpu_local_warmup with pools (docs/serving_pool.md).
            age = engine.heartbeat_age()
            step_age = engine.last_step_age()
            if step_age is None:
                # no traffic step retired yet: a stale heartbeat is a
                # wedge (device lost before the first step), and without
                # this arm the request would hang forever (step_age never
                # becomes non-None on a replica that cannot retire a
                # step).
                if age > self.heartbeat_timeout_s:
                    return (f"wedged: heartbeat stale {age:.1f}s before "
                            f"first step with "
                            f"{len(replica.outstanding)} in-flight")
            # both signals must agree once the replica has proven it can
            # retire steps
            elif (age > self.heartbeat_timeout_s
                    and step_age > self.heartbeat_timeout_s):
                return (f"wedged: heartbeat stale {age:.1f}s with "
                        f"{len(replica.outstanding)} in-flight")
        return None
