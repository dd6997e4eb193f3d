"""Operator diagnostics: system stats, performance tracking, support bundle.

Reference analogs, rebuilt for this stack (async sqlite + aiohttp + the
in-proc ring logger) rather than translated:

- ``SystemStatsService`` — comprehensive deployment-scale counts across
  every entity family (reference
  ``services/system_stats_service.py:90-458``, surfaced at
  ``admin.py:18142``). One aggregate SQL pass per family over the single
  discriminated schema instead of per-model ORM counts.
- ``PerformanceTracker`` — in-process operation timing with percentile
  summaries, configurable slow-op thresholds and degradation checks
  (reference ``services/performance_tracker.py:28-370`` +
  ``performance_service.py``). Bounded ring per operation; zero cost
  when disabled.
- ``SupportBundleService`` — one-call sanitized diagnostics zip:
  version/platform info, effective settings (redacted via
  ``utils.redact``), allowlisted env, recent in-proc logs, DB/table
  stats and engine state (reference
  ``services/support_bundle_service.py:76-493``, ``admin.py:18212``).
  Built fully in memory — no temp files to leak on a crashed worker.
"""

from __future__ import annotations

import asyncio
import io
import json
import logging
import os
import platform
import sys
import threading
import time
import zipfile
from collections import deque
from contextlib import contextmanager
from typing import Any

from .. import PROTOCOL_VERSION, __version__
from ..observability.logging import ring_buffer
from ..utils.redact import redact_env, redact_settings, redact_text
from .base import AppContext, ConflictError

logger = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# engine step introspection + profiler capture
# --------------------------------------------------------------------------

def live_tpu_engine(container: Any) -> Any:
    """The CURRENT engine behind the single-engine admin surfaces
    (/admin/engine/stats|steps|profile, the bundle's engine.json).

    When the replica pool is enabled, read THROUGH it: a pool reload
    swaps replica 0's engine object, so a ``tpu_engine`` reference
    captured at app build time goes stale after the first hot-swap
    (frozen stats, dead step ring). ``container`` is the aiohttp app or
    ``ctx.extras`` — anything dict-like."""
    pool = container.get("tpu_engine_pool")
    if pool is not None:
        return pool.replicas[0].engine
    return container.get("tpu_engine")


def engine_introspection(engine: Any, limit: int = 64) -> dict[str, Any]:
    """The engine's step ring buffer plus the scheduler counters an
    operator needs to read it (served by GET /admin/engine/steps and
    included in the support bundle)."""
    stats = engine.stats
    return {
        "model": engine.config.model,
        "max_batch": engine.config.max_batch,
        "queue_depth": stats.queue_depth,
        "decode_steps": stats.decode_steps,
        "decode_dispatches": stats.decode_dispatches,
        "superstep": engine.config.superstep,
        "prefill_batches": stats.prefill_batches,
        # host-to-device transfers made for dispatches (one packed call
        # each, a table sync where rows were dirty): per step in "steps"
        "host_uploads": stats.host_uploads,
        # dense prefills: prompt tokens carried, positions dispatched, and
        # dispatches through a bucket's half-length program
        "dense_prefill_tokens": stats.dense_prefill_tokens,
        "dense_prefill_positions": stats.dense_prefill_positions,
        "half_prefill_batches": stats.half_prefill_batches,
        "chunking": stats.chunking,
        # overlapped-pipeline health (docs/perf_decode.md): device-fed
        # dispatches, barrier-forced drains, and the host-stall total the
        # pipeline exists to hide
        "overlap_steps": stats.overlap_steps,
        "pipeline_drains": stats.pipeline_drains,
        # flushes made early for first tokens alone, ahead of the
        # iteration's decode or verify dispatch
        "first_flushes": stats.first_flushes,
        "dispatch_gap_ms_total": round(stats.dispatch_gap_ms_total, 3),
        "device_idle_fraction": round(engine.device_idle_fraction(), 4),
        # step attribution + live roofline + compile tracking
        # (docs/observability.md "Step attribution, live roofline, and
        # SLOs"): a phase row rides every host-fed step in "steps" below;
        # a stall is a host-fed dispatch that held the drained device past
        # timeline.STALL_S (each also logged with the part that held it)
        "dispatch_stalls": getattr(stats, "dispatch_stalls", 0),
        "roofline": (engine.roofline_snapshot()
                     if hasattr(engine, "roofline_snapshot") else None),
        "xla_compiles": (engine.compile_stats()
                         if hasattr(engine, "compile_stats") else None),
        "kv": {
            "pages_in_use": engine.allocator.pages_in_use,
            "free_pages": engine.allocator.free_pages,
            # the DTYPE-AWARE pool size (int8 pools hold ~2x the pages
            # config.num_pages denominates in engine-dtype bytes)
            "num_pages": engine.num_kv_pages,
            "page_size": engine.config.page_size,
            "quant": engine.config.kv_quant or "off",
            "bytes_in_use": engine.kv_bytes_in_use(),
            "bytes_capacity": engine.kv_bytes_capacity(),
            "bytes_resident": engine.kv_bytes_resident(),
        },
        "steps": engine.recent_steps(limit),
    }


class JaxProfilerCapture:
    """Opt-in ``jax.profiler`` trace capture of the live engine (SURVEY
    §5.1: jax.profiler integration alongside the OTel layer).

    start()/stop() let an operator bracket exactly the traffic they care
    about on a production v5e slice; the trace lands in the
    server-configured ``jax_profile_dir`` (never a client-supplied path —
    that would be a filesystem-write primitive). The profiler is
    process-global, so captures are serialized through this object."""

    def __init__(self, trace_dir: str) -> None:
        self.trace_dir = trace_dir
        self._started_at: float | None = None
        # start/stop run via asyncio.to_thread (start_trace/stop_trace
        # write trace files — blocking the gateway loop for a disk flush
        # defeats the capture); the lock keeps the active-check + the
        # process-global profiler call atomic across those threads
        self._mutex = threading.Lock()

    @property
    def active(self) -> bool:
        return self._started_at is not None

    def status(self) -> dict[str, Any]:
        return {"active": self.active, "trace_dir": self.trace_dir,
                "started_at": self._started_at}

    def start(self) -> dict[str, Any]:
        with self._mutex:
            if self.active:
                raise ConflictError("a profiler capture is already running")
            import jax

            jax.profiler.start_trace(self.trace_dir)  # lint: allow[await-holding-lock] runs via asyncio.to_thread; the mutex exists to serialize exactly these transitions
            self._started_at = time.time()
            return self.status()

    def stop(self, expect_started_at: float | None = None) -> dict[str, Any]:
        """``expect_started_at`` lets a timed capture stop only the capture
        it started — without it, a concurrent operator's stop+start window
        would let the timed handler silently kill the operator's capture."""
        with self._mutex:
            if not self.active:
                raise ConflictError("no profiler capture is running")
            if (expect_started_at is not None
                    and self._started_at != expect_started_at):
                raise ConflictError("the running capture belongs to another "
                                    "caller; leaving it alone")
            import jax

            started = self._started_at
            try:
                jax.profiler.stop_trace()  # lint: allow[await-holding-lock] runs via asyncio.to_thread; the mutex exists to serialize exactly these transitions
            finally:
                self._started_at = None
            return {"active": False, "trace_dir": self.trace_dir,
                    "duration_ms": round(
                        (time.time() - (started or 0.0)) * 1e3, 1),
                    "hint": "open with TensorBoard or xprof: the trace "
                            "contains XLA op timelines for prefill/decode"}


# --------------------------------------------------------------------------
# system stats
# --------------------------------------------------------------------------

class SystemStatsService:
    """Deployment-scale counters for the admin dashboard.

    The reference walks 9 stat families with per-ORM-model queries and an
    admin-stats TTL cache; here each family is one aggregate SELECT over
    the discriminated tables, cached in ``AppContext.extras`` under the
    same TTL knob the other dashboard aggregations use.
    """

    _CACHE_KEY = "_system_stats_cache"

    def __init__(self, ctx: AppContext) -> None:
        self._ctx = ctx

    async def stats(self) -> dict[str, Any]:
        settings = self._ctx.settings
        if settings.admin_stats_cache_enabled:
            cached = self._ctx.extras.get(self._CACHE_KEY)
            if cached and cached[1] > time.monotonic():
                return cached[0]
        out = {
            "users": await self._users(),
            "teams": await self._teams(),
            "entities": await self._entities(),
            "tokens": await self._tokens(),
            "metrics": await self._metrics(),
            "security": await self._security(),
            "workflows": await self._workflows(),
            "timestamp": time.time(),
        }
        if settings.admin_stats_cache_enabled:
            self._ctx.extras[self._CACHE_KEY] = (
                out, time.monotonic() + settings.admin_stats_cache_ttl_s)
        return out

    async def _one(self, sql: str, params: tuple = ()) -> dict[str, Any]:
        # every caller passes a string literal (the one f-string interpolates
        # a fixed table-name tuple two scopes up)
        row = await self._ctx.db.fetchone(sql, params)  # seclint: allow S006 literal call sites only
        return {k: (v or 0) for k, v in (row or {}).items()}

    async def _users(self) -> dict[str, Any]:
        return await self._one(
            "SELECT COUNT(*) AS total,"
            " SUM(CASE WHEN is_active THEN 1 ELSE 0 END) AS active,"
            " SUM(CASE WHEN is_admin THEN 1 ELSE 0 END) AS admins,"
            " SUM(CASE WHEN auth_provider != 'local' THEN 1 ELSE 0 END)"
            "   AS sso_provisioned FROM users")

    async def _teams(self) -> dict[str, Any]:
        out = await self._one(
            "SELECT COUNT(*) AS total,"
            " SUM(CASE WHEN is_personal THEN 1 ELSE 0 END) AS personal"
            " FROM teams")
        out.update(await self._one(
            "SELECT COUNT(*) AS members,"
            " COUNT(DISTINCT user_email) AS distinct_members"
            " FROM team_members"))
        out.update(await self._one(
            "SELECT SUM(CASE WHEN accepted_at IS NULL THEN 1 ELSE 0 END)"
            " AS pending_invitations FROM team_invitations"))
        return out

    async def _entities(self) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for table in ("tools", "resources", "prompts", "servers",
                      "gateways", "a2a_agents", "llm_providers",
                      "llm_models"):
            row = await self._one(
                f"SELECT COUNT(*) AS total,"
                f" SUM(CASE WHEN enabled THEN 1 ELSE 0 END) AS enabled"
                f" FROM {table}")
            out[table] = row
        out["resource_subscriptions"] = (await self._one(
            "SELECT COUNT(*) AS total FROM resource_subscriptions"))["total"]
        out["plugin_bindings"] = (await self._one(
            "SELECT COUNT(*) AS total FROM plugin_bindings"))["total"]
        return out

    async def _tokens(self) -> dict[str, Any]:
        return await self._one(
            "SELECT COUNT(*) AS total,"
            " SUM(CASE WHEN revoked_at IS NOT NULL THEN 1 ELSE 0 END)"
            "   AS revoked,"
            " SUM(CASE WHEN expires_at IS NOT NULL AND expires_at < ?"
            "     THEN 1 ELSE 0 END) AS expired"
            " FROM api_tokens", (time.time(),))

    async def _metrics(self) -> dict[str, Any]:
        buffer = self._ctx.extras.get("metrics_buffer")
        if buffer is not None:
            await buffer.flush()
        out = await self._one(
            "SELECT COUNT(*) AS raw_rows,"
            " SUM(CASE WHEN success THEN 0 ELSE 1 END) AS errors,"
            " AVG(duration_ms) AS avg_duration_ms FROM tool_metrics")
        out["rollup_rows"] = (await self._one(
            "SELECT COUNT(*) AS total FROM metrics_rollups"))["total"]
        out["traces"] = (await self._one(
            "SELECT COUNT(*) AS total FROM observability_traces"))["total"]
        cache = self._ctx.extras.get("registry_cache")
        if cache is not None:
            out["registry_cache_hits"] = cache.hits
            out["registry_cache_misses"] = cache.misses
        return out

    async def _security(self) -> dict[str, Any]:
        out = await self._one(
            "SELECT COUNT(*) AS audit_rows FROM audit_trail")
        # lockout posture lives on the users table (auth_service lockout)
        out.update(await self._one(
            "SELECT SUM(CASE WHEN failed_login_attempts > 0 THEN 1 ELSE 0"
            " END) AS users_with_failed_logins,"
            " SUM(CASE WHEN locked_until IS NOT NULL AND locked_until > ?"
            " THEN 1 ELSE 0 END) AS locked_users FROM users",
            (time.time(),)))
        out["roles"] = (await self._one(
            "SELECT COUNT(*) AS total FROM roles"))["total"]
        out["role_assignments"] = (await self._one(
            "SELECT COUNT(*) AS total FROM user_roles"))["total"]
        return out

    async def _workflows(self) -> dict[str, Any]:
        rows = await self._ctx.db.fetchall(
            "SELECT state, COUNT(*) AS n FROM a2a_tasks GROUP BY state")
        return {r["state"]: r["n"] for r in rows}


# --------------------------------------------------------------------------
# performance tracking
# --------------------------------------------------------------------------

class PerformanceTracker:
    """Bounded per-operation timing registry.

    ``track("tool.invoke")`` wraps any block; summaries expose count /
    avg / p50 / p95 / p99 / max plus threshold breaches. The reference
    keeps unbounded per-operation lists trimmed on read; here each op is
    a fixed ``deque`` so a hot gateway can never grow the tracker.
    """

    def __init__(self, max_samples: int = 512,
                 thresholds: dict[str, float] | None = None) -> None:
        self._samples: dict[str, deque[float]] = {}
        self._totals: dict[str, int] = {}
        self._slow: dict[str, int] = {}
        self._max = max_samples
        # seconds per operation-class; checked on every record
        self.thresholds = dict(thresholds or {})

    @contextmanager
    def track(self, operation: str, component: str | None = None):
        start = time.perf_counter()
        try:
            yield
        finally:
            self.record(operation, time.perf_counter() - start, component)

    def will_warn(self, operation: str, seconds: float) -> bool:
        """THE slow-op predicate — public so callers that build an
        expensive ``component`` (the flight recorder's phase vector) can
        skip the work when record() won't warn, without re-deriving the
        threshold rule."""
        limit = self._threshold_for(operation)
        return bool(limit) and seconds > limit

    def record(self, operation: str, seconds: float,
               component: str | None = None) -> None:
        buf = self._samples.get(operation)
        if buf is None:
            buf = self._samples[operation] = deque(maxlen=self._max)
        buf.append(seconds)
        self._totals[operation] = self._totals.get(operation, 0) + 1
        if self.will_warn(operation, seconds):
            limit = self._threshold_for(operation)
            self._slow[operation] = self._slow.get(operation, 0) + 1
            logger.warning("slow operation %s: %.1f ms (threshold %.1f ms)%s",
                           operation, seconds * 1e3, limit * 1e3,
                           f" [{component}]" if component else "")

    def _threshold_for(self, operation: str) -> float | None:
        if operation in self.thresholds:
            return self.thresholds[operation]
        # class thresholds match on prefix: "db." / "http." / "tool." ...
        prefix = operation.split(".", 1)[0]
        return self.thresholds.get(prefix)

    def summary(self, operation: str | None = None) -> dict[str, Any]:
        names = [operation] if operation else sorted(self._samples)
        ops = {}
        for name in names:
            buf = self._samples.get(name)
            if not buf:
                continue
            vals = sorted(buf)
            n = len(vals)

            def pct(p: float) -> float:
                return vals[min(n - 1, int(p * n))]

            ops[name] = {
                "count": self._totals.get(name, n),
                "window": n,
                "avg_ms": round(sum(vals) / n * 1e3, 3),
                "p50_ms": round(pct(0.50) * 1e3, 3),
                "p95_ms": round(pct(0.95) * 1e3, 3),
                "p99_ms": round(pct(0.99) * 1e3, 3),
                "max_ms": round(vals[-1] * 1e3, 3),
                "slow": self._slow.get(name, 0),
            }
        return {"operations": ops}

    def degradation(self, operation: str,
                    multiplier: float = 2.0) -> dict[str, Any]:
        """Is the recent half of the window `multiplier`x the older half?

        The reference compares current average against a stored baseline;
        a split-window comparison needs no persisted baseline and answers
        the same operator question ("did this op just get slower?").
        """
        buf = list(self._samples.get(operation, ()))
        if len(buf) < 8:
            return {"operation": operation, "degraded": False,
                    "reason": "insufficient samples"}
        half = len(buf) // 2
        old = sum(buf[:half]) / half
        new = sum(buf[half:]) / (len(buf) - half)
        degraded = old > 0 and new > old * multiplier
        return {"operation": operation, "degraded": degraded,
                "baseline_avg_ms": round(old * 1e3, 3),
                "recent_avg_ms": round(new * 1e3, 3),
                "multiplier": multiplier}

    def clear(self, operation: str | None = None) -> None:
        if operation is None:
            self._samples.clear()
            self._totals.clear()
            self._slow.clear()
        else:
            self._samples.pop(operation, None)
            self._totals.pop(operation, None)
            self._slow.pop(operation, None)


def tracker_from_settings(settings: Any) -> PerformanceTracker:
    """Build the app tracker with the reference's four class thresholds
    (performance_threshold_* fields, ms in config, seconds here)."""
    return PerformanceTracker(
        max_samples=settings.performance_max_samples,
        thresholds={
            "db": settings.performance_threshold_database_query_ms / 1e3,
            "http": settings.performance_threshold_http_request_ms / 1e3,
            # exact-op threshold wins over the "http" class prefix: the
            # flight recorder's configurable gw_slow_request_ms and the
            # tracker's slow-op count must agree on one bar
            "http.request": settings.gw_slow_request_s,
            "tool": settings.performance_threshold_tool_invocation_ms / 1e3,
            "resource": settings.performance_threshold_resource_read_ms / 1e3,
        })


# --------------------------------------------------------------------------
# support bundle
# --------------------------------------------------------------------------

class SupportBundleService:
    """Sanitized one-file diagnostics for a support ticket."""

    def __init__(self, ctx: AppContext) -> None:
        self._ctx = ctx

    async def generate(self, *, include_logs: bool = True,
                       include_env: bool = True,
                       log_tail: int = 1000) -> tuple[str, bytes]:
        """Return (filename, zip bytes). Everything passes the shared
        redaction policy before it reaches the archive.

        The awaitable pieces (DB stats) gather here on the loop; the
        CPU-bound part — per-record log redaction plus DEFLATE over the
        whole archive — runs in a worker thread. On a loaded gateway a
        bundle download must not stall every in-flight request
        (async-blocking-call lint rule; the heartbeat test in
        tests/async_safety/ is its runtime twin)."""
        stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime())
        name = f"mcpforge-support-{stamp}.zip"
        sections: list[tuple[str, Any]] = [("version.json", {
            "version": __version__,
            "protocol_version": PROTOCOL_VERSION,
            "python": sys.version,
            "worker_id": self._ctx.worker_id,
        })]
        sections.append(("system.json", self._system_info()))
        sections.append(("settings.json", redact_settings(self._ctx.settings)))
        if include_env:
            sections.append(("environment.json", redact_env(os.environ)))
        sections.append(("database.json", await self._db_info()))
        engine = live_tpu_engine(self._ctx.extras)
        if engine is not None:
            try:
                stats = engine.stats
                sections.append(("engine.json", {
                    "model": engine.config.model,
                    "mesh": dict(engine.mesh.shape),
                    "requests": stats.requests,
                    "completion_tokens": stats.completion_tokens,
                    "decode_steps": stats.decode_steps,
                    "queue_depth": stats.queue_depth,
                }))
                if hasattr(engine, "recent_steps"):
                    sections.append(("engine_steps.json",
                                     engine_introspection(engine, limit=128)))
            except Exception as exc:  # diagnostics must not fail the bundle
                sections.append(("engine.json", {"error": str(exc)}))
        pool = self._ctx.extras.get("tpu_engine_pool")
        if pool is not None:
            # replica pool topology + PER-REPLICA step rings: the support
            # bundle must show which replica wedged/crashed and what each
            # one dispatched last, not just replica 0's view
            try:
                sections.append(("engine_pool.json", pool.status()))
            except Exception as exc:
                sections.append(("engine_pool.json", {"error": str(exc)}))
            for replica in pool.replicas:
                name = f"engine_pool/replica-{replica.id}-steps.json"
                try:
                    sections.append((
                        name, engine_introspection(replica.engine,
                                                   limit=128)))
                except Exception as exc:
                    # per-replica error entry keeps zip names unique AND
                    # shows which replica's ring was unreadable (e.g.
                    # mid-reload) instead of truncating the loop
                    sections.append((name, {"error": str(exc)}))
        trace_store = self._ctx.extras.get("trace_store")
        if trace_store is not None:
            # request forensics: retention stats + summaries, plus full
            # span dumps of the newest retained traces so the waterfall
            # can be stitched OFFLINE from the bundle alone (trace ids
            # are random hex; span attributes carry no free-text bodies)
            try:
                sections.append(("traces.json", {
                    **trace_store.snapshot(limit=64),
                    "exported_spans": trace_store.export(limit=16),
                }))
            except Exception as exc:
                sections.append(("traces.json", {"error": str(exc)}))
        records = (ring_buffer.search(limit=log_tail) if include_logs
                   else None)
        perf = self._ctx.extras.get("perf_tracker")
        if perf is not None:
            sections.append(("performance.json", perf.summary()))
        payload = await asyncio.to_thread(self._build_zip, stamp, sections,
                                          records)
        return name, payload

    @staticmethod
    def _build_zip(stamp: str, sections: list[tuple[str, Any]],
                   records: list[Any] | None) -> bytes:
        """Worker-thread half: redact log records, serialize, compress."""
        buf = io.BytesIO()
        entries: list[str] = []
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
            def put(path: str, payload: Any) -> None:
                entries.append(path)
                body = payload if isinstance(payload, str) else json.dumps(
                    payload, indent=2, default=str)
                zf.writestr(path, body)

            for path, payload in sections:
                put(path, payload)
            if records is not None:
                # log MESSAGES are free text: exception strings and
                # third-party libraries embed DSNs/bearer tokens that the
                # name-keyed settings redaction never sees — run every
                # serialized record through the content redaction pass
                # before it reaches the 'sanitized: true' archive
                put("logs/recent.jsonl",
                    "\n".join(redact_text(json.dumps(r, default=str))
                              for r in records))
            put("manifest.json", {
                "generated_at": stamp,
                "entries": sorted(entries),
                "sanitized": True,
            })
        return buf.getvalue()

    def _system_info(self) -> dict[str, Any]:
        info: dict[str, Any] = {
            "platform": platform.platform(),
            "machine": platform.machine(),
            "python_implementation": platform.python_implementation(),
            "pid": os.getpid(),
            "cpu_count": os.cpu_count(),
        }
        try:
            load1, load5, load15 = os.getloadavg()
            info["loadavg"] = {"1m": load1, "5m": load5, "15m": load15}
        except OSError:
            pass
        try:
            import resource
            usage = resource.getrusage(resource.RUSAGE_SELF)
            info["max_rss_kb"] = usage.ru_maxrss
        except Exception:
            pass
        return info

    async def _db_info(self) -> dict[str, Any]:
        db = self._ctx.db
        tables = await db.fetchall(
            "SELECT name FROM sqlite_master WHERE type='table'"
            " AND name NOT LIKE 'sqlite_%' ORDER BY name")
        counts = {}
        for row in tables:
            table = row["name"]
            one = await db.fetchone(  # seclint: allow S006 table names read from sqlite_master
                f"SELECT COUNT(*) AS n FROM {table}")
            counts[table] = one["n"] if one else 0
        version = await db.fetchone("SELECT MAX(version) AS v FROM schema_migrations")
        return {"schema_version": (version or {}).get("v"),
                "table_rows": counts}
