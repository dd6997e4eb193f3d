"""EnginePool: an affinity-routed multi-replica serving tier.

One ``TPUEngine`` is one mesh, one dispatch thread, one failure domain.
The pool owns N of them — device-subset meshes carved out of
``jax.devices()`` (N full-overlap CPU replicas in tests) — behind the
same submit/generate surface the provider already speaks, adding what a
single replica cannot have:

- **routing** (router.py): prefix-cache affinity first, then least
  outstanding decode tokens, per-priority admission carried through to
  each replica's own scheduler;
- **failover** (health.py): a crashed or wedged replica's in-flight
  requests REQUEUE onto healthy replicas as continuations — the new
  prompt is (original prompt + tokens already emitted), so consumers
  see every token exactly once and greedy streams continue
  byte-identically. Composes with the engine's once-only admission
  guard: requeued shadows carry ``queue_observed=True`` so the logical
  request's queue-wait is observed exactly once;
- **drain/reload**: rolling checkpoint hot-swap per replica
  (``drain -> swap weights -> readmit``) while the rest of the pool
  keeps serving;
- **disaggregated prefill/decode** (docs/disaggregation.md): replicas
  carry ROLES (``prefill`` / ``decode`` / ``any``) and the router
  classes each admission by prompt length (or an explicit
  ``route_class``). A prefill-classed request lands on a prefill
  replica capped at ONE decode token, its prompt KV chain is exported
  through the pool-shared spill tiers, verified page-by-page against
  the token content (the same verify-before-serve gate admission
  restores ride), and the request continues on a decode replica as a
  pool-shadow continuation — the exact mechanism failover already
  uses, so greedy streams stay byte-identical across the hop. ANY
  failed step degrades to decode-in-place on the prefill replica;
  migration never loses a stream.

Requests are never handed to an engine directly: the pool submits a
*shadow* request and pumps its stream into the client's, which is the
interception point failover needs (the engine's terminal "error" post
must not reach the consumer when a survivor can finish the request).

All pool state lives on the gateway's asyncio loop (the ``thread[pool]``
lint context); engines' dispatch threads are reached only through their
thread-safe submit/kill/liveness surfaces.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from dataclasses import dataclass
from typing import Any, AsyncIterator, Callable, Sequence

from ...observability.logging import trace_extra
from ..engine import EngineConfig, EngineStats, GenRequest, TPUEngine, probe_devices
from ..parallel import mesh_shape_from_string
from .health import HealthMonitor
from .router import ReplicaRouter

logger = logging.getLogger(__name__)

#: legal replica roles (docs/disaggregation.md). "prefill"/"decode" are
#: the phase split; "any" is the generalist default every pool starts
#: with. The field is deliberately a plain string so future fleet
#: classes (model-size tiers, tenant SLO classes) ride the same router
#: narrowing without a schema change.
REPLICA_ROLES = ("prefill", "decode", "any")


def partition_devices(devices: list, n: int) -> list[list]:
    """Split the device list into n replica meshes.

    With at least n devices each replica gets an equal contiguous slice
    (remainder devices are dropped with a warning — a 3-replica pool on
    8 chips serves 2+2+2 and idles 2; pick divisors). With fewer devices
    than replicas (CPU tests, single-chip dev boxes) every replica runs
    the FULL set: correctness-identical, throughput shared."""
    if n <= 1:
        return [list(devices)]
    if len(devices) >= n:
        per = len(devices) // n
        dropped = len(devices) - per * n
        if dropped:
            logger.warning(
                "engine pool: %d device(s) idle (%d devices / %d replicas)",
                dropped, len(devices), n)
        return [list(devices[i * per:(i + 1) * per]) for i in range(n)]
    logger.info("engine pool: %d replicas sharing %d device(s) "
                "(test/dev topology)", n, len(devices))
    return [list(devices) for _ in range(n)]


@dataclass
class PoolRecord:
    """One logical client request as the pool tracks it: the client-facing
    GenRequest (never submitted to any engine) plus the engine-facing
    shadow currently serving it."""
    request: GenRequest
    shadow: GenRequest
    replica: "EngineReplica"
    attempts: int = 1            # dispatches so far (1 = never requeued)
    pump: asyncio.Task | None = None
    done: bool = False
    # disaggregation: this shadow is the one-token PREFILL leg of a
    # migration — its "length" terminal means "hand off to a decode
    # replica", not "budget spent" (docs/disaggregation.md)
    migrate_leg: bool = False


class EngineReplica:
    """One engine plus the pool's view of it."""

    STATES = ("ready", "draining", "reloading", "dead")

    def __init__(self, rid: str, index: int, engine: TPUEngine,
                 role: str = "any") -> None:
        self.id = rid
        self.index = index
        self.engine = engine
        self.state = "ready"
        self.role = role
        self.outstanding: dict[str, PoolRecord] = {}
        self.routed = 0
        self.requeued_off = 0
        self.reloads = 0
        self.failures = 0
        self.last_failure = ""
        self.migrations_out = 0   # prefill legs this replica handed off
        self.migrations_in = 0    # decode continuations it received

    def outstanding_tokens(self) -> int:
        """Budgeted work still owed: the router's least-loaded signal."""
        return sum(max(0, rec.request.max_tokens - len(rec.request.generated))
                   for rec in self.outstanding.values())

    def status(self) -> dict[str, Any]:
        engine = self.engine
        stats = engine.stats
        return {
            "id": self.id,
            "state": self.state,
            "role": self.role,
            "model": engine.config.model,
            "mesh_devices": int(engine.mesh.size),
            "dispatch_alive": engine.dispatch_alive(),
            "heartbeat_age_s": round(engine.heartbeat_age(), 3),
            # occupancy: slots carrying work right now vs capacity
            "occupancy": len(engine._running) + len(engine._chunking),
            "max_batch": engine.config.max_batch,
            "outstanding": len(self.outstanding),
            "outstanding_tokens": self.outstanding_tokens(),
            "kv_pages_in_use": engine.allocator.pages_in_use,
            "queue_depth": stats.queue_depth,
            "requests": stats.requests,
            "completion_tokens": stats.completion_tokens,
            "decode_steps": stats.decode_steps,
            "engine_restarts": stats.engine_restarts,
            "first_flushes": stats.first_flushes,
            "routed": self.routed,
            "requeued_off": self.requeued_off,
            "migrations_out": self.migrations_out,
            "migrations_in": self.migrations_in,
            "reloads": self.reloads,
            "failures": self.failures,
            "last_failure": self.last_failure,
            # mid-traffic XLA compiles (compile_events.py): serving-stage
            # count > 0 on a warmed replica is the PR-5 catastrophe — the
            # health monitor's wedge bar assumes it stays 0
            "xla_compiles": engine.compile_stats(),
            # live cost-model roofline over the recent decode window
            "roofline": engine.roofline_snapshot(),
            # tiered prefix cache: this replica's per-tier hit split +
            # spill/restore counters (None when tiers and index are off)
            "prefix_tiers": engine.tier_stats(),
        }


class EnginePool:
    """N TPUEngine replicas behind the single-engine serving surface."""

    def __init__(self, config: EngineConfig, replicas: int = 2,
                 tracer=None, metrics=None,
                 affinity_routing: bool = True,
                 health_interval_s: float = 0.5,
                 heartbeat_timeout_s: float = 10.0,
                 requeue_max: int = 2,
                 devices: list | None = None,
                 engine_factory: Callable[..., TPUEngine] | None = None,
                 ledger=None, signals=None,
                 roles: str | Sequence[str] | None = None,
                 disagg_prompt_tokens: int = 64,
                 role_penalty_tokens: int = 256):
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.config = config
        self.tracer = tracer
        self.metrics = metrics
        # one live-signal bus shared by every replica (and every
        # reload-rebuilt engine): per-replica aggregates the serving
        # controller consumes must survive hot-swap
        self.signals = signals
        # one tenant ledger shared by every replica (and every rebuilt
        # engine a reload produces): per-tenant token accounting must
        # survive failover and hot-swap with nothing lost or double-billed
        self.ledger = ledger
        # pool-global prefix plane (docs/kv_tiering.md): ONE index maps
        # hashed prefix chains -> (replica | tier) locations — replicas
        # publish their HBM registrations into it and the router scores
        # it as affinity — and, with prefix_tiers on, ONE spill store is
        # shared by every replica so admission can fetch-on-miss: a
        # prefix prefilled (then evicted) on any replica restores into
        # the admitting replica's own HBM. Both survive reload-rebuilt
        # engines (content-addressed by token chain, not replica state).
        self.prefix_index = None
        self.tier_store = None
        if config.prefix_cache:
            from ..kv.prefix_index import PrefixIndex
            self.prefix_index = PrefixIndex()
            if config.prefix_tiers:
                from ..kv.fabric.object_store import object_store_or_none
                from ..kv.tiers import TieredPageStore
                self.tier_store = TieredPageStore(
                    host_bytes=config.tier_host_bytes,
                    disk_bytes=config.tier_disk_bytes,
                    disk_dir=config.tier_disk_dir,
                    index=self.prefix_index, metrics=metrics,
                    io_retry_max=config.tier_io_retry_max,
                    io_retry_backoff_ms=config.tier_io_retry_backoff_ms,
                    object_store=object_store_or_none(
                        config.tier_object_url),
                    object_namespace=config.fabric_namespace)
        self.requeue_max = max(0, requeue_max)
        self._factory = engine_factory or (
            lambda cfg, tracer, metrics, devices, ledger=None,
            tier_store=None, prefix_index=None: TPUEngine(
                cfg, tracer=tracer, metrics=metrics, devices=devices,
                ledger=ledger, tier_store=tier_store,
                prefix_index=prefix_index))
        if devices is None:
            devices = probe_devices(config.init_timeout_s)
        self._device_sets = partition_devices(devices, replicas)
        # an explicit tpu_local_mesh_shape is sized for the FULL machine;
        # replicas get a device subset, so the spec would fail every
        # per-replica make_mesh (e.g. "1x8" on a 2-replica v5e-8 pool
        # where each replica holds 4 chips). Fall back to the auto mesh
        # (1 x subset) rather than refusing to boot.
        self._mesh_shape = config.mesh_shape
        if self._mesh_shape and replicas > 1:
            per = len(self._device_sets[0])
            try:
                mesh_shape_from_string(self._mesh_shape, per)
            except ValueError:
                logger.warning(
                    "engine pool: mesh shape %r does not fit the %d "
                    "device(s) each of %d replicas receives — using the "
                    "auto (1, %d) mesh per replica",
                    self._mesh_shape, per, replicas, per)
                self._mesh_shape = ""
        # disaggregation (docs/disaggregation.md): per-replica roles,
        # assignable statically here (comma string from config or a
        # sequence) and dynamically over set_role / the admin surface /
        # the BusRpc lease plane. Short lists pad with "any"; bad role
        # names refuse to boot rather than silently routing everything.
        role_list: list[str] = []
        if roles:
            parts = (roles.split(",") if isinstance(roles, str)
                     else list(roles))
            role_list = [str(p).strip().lower() for p in parts
                         if str(p).strip()]
            for role in role_list:
                if role not in REPLICA_ROLES:
                    raise ValueError(
                        f"unknown replica role {role!r} "
                        f"(roles are {list(REPLICA_ROLES)})")
        self.disagg_prompt_tokens = max(1, int(disagg_prompt_tokens))
        self.replicas: list[EngineReplica] = []
        for i in range(replicas):
            self.replicas.append(
                EngineReplica(str(i), i, self._build_engine(i),
                              role=(role_list[i] if i < len(role_list)
                                    else "any")))
        self.router = ReplicaRouter(affinity=affinity_routing,
                                    index=self.prefix_index,
                                    page_size=config.page_size,
                                    role_penalty_tokens=role_penalty_tokens)
        self.health = HealthMonitor(self, interval_s=health_interval_s,
                                    heartbeat_timeout_s=heartbeat_timeout_s)
        self.tokenizer = self.replicas[0].engine.tokenizer
        self.requeues = 0            # lint: thread[pool]
        # migration accounting (conservation gate: pages spilled ==
        # pages restored + pages degraded-in-place — pinned in tests)
        self.migrations = {"ok": 0, "degraded": 0}        # lint: thread[pool]
        self.migration_pages = {"spilled": 0, "restored": 0,
                                "degraded": 0}            # lint: thread[pool]
        self.migration_bytes = 0     # lint: thread[pool]
        self._started = False        # lint: thread[pool]
        self._stopping = False       # lint: thread[pool]
        self._set_up_gauges()

    def _build_engine(self, index: int) -> TPUEngine:
        cfg = dataclasses.replace(self.config, replica_id=str(index),
                                  mesh_shape=self._mesh_shape)
        engine = self._factory(cfg, self.tracer, self.metrics,
                               self._device_sets[index], ledger=self.ledger,
                               tier_store=self.tier_store,
                               prefix_index=self.prefix_index)
        if self.signals is not None:
            engine.signals = self.signals
        return engine

    # --------------------------------------------------------------- lifecycle

    async def start(self) -> None:  # lint: runs-on[pool]
        if self._started:
            return
        self._started = True
        self._stopping = False
        for replica in self.replicas:
            if replica.state == "ready":
                await replica.engine.start()
        await self.health.start()

    async def stop(self) -> None:  # lint: runs-on[pool]
        self._stopping = True
        self._started = False
        await self.health.stop()
        for replica in self.replicas:
            try:
                await replica.engine.stop()
            except Exception:
                logger.exception("engine pool: replica %s stop failed",
                                 replica.id)
        # the shared spill store outlives every replica engine (reloads
        # rebuild engines against it); close it only with the pool
        if self.tier_store is not None:
            self.tier_store.close()

    def warmup(self, mode: str | None = None) -> None:
        """Precompile every replica's shape grid (bench/boot path)."""
        for replica in self.replicas:
            replica.engine.warmup(mode)

    # -------------------------------------------------------------- submission

    async def submit(self, request: GenRequest) -> GenRequest:  # lint: runs-on[pool]
        """Route and dispatch one request; same contract as
        TPUEngine.submit (tokens arrive on request.stream, None-terminated,
        finish_reason filled)."""
        await self._dispatch(request, attempts=1)
        return request

    async def generate(self, prompt_ids: list[int],
                       **kwargs) -> AsyncIterator[int]:  # lint: runs-on[pool]
        from ...utils.ids import new_id
        request = GenRequest(request_id=new_id(), prompt_ids=prompt_ids,
                             **kwargs)
        await self.submit(request)
        while True:
            token = await request.stream.get()
            if token is None:
                break
            yield token

    def cancel(self, request_id: str) -> bool:  # lint: runs-on[pool]
        """Cancel a logical request wherever the router placed it. The
        record is keyed by the CLIENT-facing id; the engine is told the
        shadow's id (which carries a ``~rN`` suffix after a requeue), so
        post-failover requests stay cancellable by their original id.
        The engine posts the ``cancelled`` terminal through the normal
        stream path, which the pump forwards to the client."""
        for replica in self.replicas:
            record = replica.outstanding.get(request_id)
            if record is not None:
                return replica.engine.request_cancel(
                    record.shadow.request_id)
        return False

    def _routable(self) -> list[EngineReplica]:
        return [r for r in self.replicas if r.state == "ready"]

    # ------------------------------------------------------------------- roles

    @property
    def roles_active(self) -> bool:
        """True once any replica holds a non-generalist role — the gate
        on classification and migration (a uniform pool routes exactly
        as it did before roles existed)."""
        return any(r.role != "any" for r in self.replicas)

    def set_role(self, rid: str, role: str) -> dict[str, Any]:  # lint: runs-on[pool]
        """Reassign one replica's role live (admin surface / lease
        plane). Routing-only state: nothing needs draining — in-flight
        work finishes where it runs; only FUTURE admissions see the new
        narrowing."""
        replica = self._replica(rid)
        role = str(role).strip().lower()
        if role not in REPLICA_ROLES:
            raise ValueError(f"role must be one of {list(REPLICA_ROLES)}, "
                             f"got {role!r}")
        if replica.role != role:
            logger.info("engine pool: replica %s role %s -> %s",
                        rid, replica.role, role)
            replica.role = role
        return replica.status()

    def _classify(self, request: GenRequest) -> str:
        """The admission's route class. An explicit ``route_class`` on
        the request wins (the fleet-class hook); otherwise prompt length
        splits the phase: long prompts are prefill-heavy, short ones
        (chat turns, continuations) are decode-heavy."""
        if not self.roles_active:
            return ""
        if request.route_class:
            return request.route_class
        return ("prefill"
                if len(request.prompt_ids) >= self.disagg_prompt_tokens
                else "decode")

    def _migration_eligible(self, request: GenRequest, attempts: int,
                            replica: EngineReplica) -> bool:
        """Should this dispatch run as a one-token prefill leg that
        hands off to a decode replica? Only a FIRST dispatch (a requeued
        continuation already carries generated tokens and re-migrating
        it re-pays the hop for no TTFT win), only on an actual prefill
        replica (a spill onto "any" can just decode in place), only
        with the shared tiers to carry the pages, at least one full
        page to carry, more than one token still owed, and somewhere
        decode-capable to land."""
        return (attempts == 1 and not request.generated
                and replica.role == "prefill"
                and self.tier_store is not None
                and request.max_tokens > 1
                and len(request.prompt_ids) >= self.config.page_size
                and any(r is not replica and r.state == "ready"
                        and r.role in ("decode", "any")
                        for r in self.replicas))

    # ---------------------------------------------------------------- dispatch

    async def _dispatch(self, request: GenRequest, attempts: int,
                        pin: EngineReplica | None = None
                        ) -> EngineReplica | None:
        """Pick a replica, submit the shadow, start the pump. Retries
        across replicas when a submit itself fails (racing a crash).
        Returns the replica the request landed on (None = capacity
        exhausted, stream terminated "unavailable"). A non-None ``pin``
        is tried FIRST (the migration path's chosen decode target, or
        its decode-in-place degrade) and never re-classified or
        re-migrated — a pin that refuses falls back to normal routing
        so a dying target can never strand the stream."""
        last_error: Exception | None = None
        route_class = "" if pin is not None else self._classify(request)
        for _ in range(len(self.replicas) + (1 if pin is not None else 0)):
            if pin is not None and pin.state == "ready":
                replica, affinity_hit = pin, False
            else:
                routable = self._routable()
                if not routable:
                    break
                replica, affinity_hit = self.router.route(
                    routable, request.prompt_ids, route_class)
            migrate_leg = (pin is None and route_class == "prefill"
                           and self._migration_eligible(request, attempts,
                                                        replica))
            shadow = self._make_shadow(request, attempts,
                                       cap=1 if migrate_leg else 0)
            record = PoolRecord(request=request, shadow=shadow,
                                replica=replica, attempts=attempts,
                                migrate_leg=migrate_leg)
            try:
                await replica.engine.submit(shadow)
            except RuntimeError as exc:
                # dispatch thread died between the health sweep and now:
                # mark it so the router stops offering it, try the next
                last_error = exc
                self.fail_replica(replica, reason="submit refused: "
                                  f"{exc}")
                if replica is pin:
                    pin = None  # fall back to normal routing
                continue
            if replica.state == "dead":
                # the health sweep failed the replica while submit awaited
                # backpressure and has already swept its outstanding map —
                # registering now would park the record on a corpse no
                # sweep revisits. Abandon the shadow (the dead engine's
                # terminal lands in it unobserved) and route a fresh one.
                last_error = RuntimeError(
                    f"replica {replica.id} died during submit")
                if replica is pin:
                    pin = None
                continue
            replica.routed += 1
            replica.outstanding[request.request_id] = record
            record.pump = asyncio.get_running_loop().create_task(
                self._pump(record), name=f"pool-pump-{request.request_id}")
            m = self.metrics
            if m is not None:
                m.llm_pool_routed.labels(
                    replica=replica.id,
                    affinity="hit" if affinity_hit else "miss").inc()
                m.llm_pool_outstanding.labels(replica=replica.id).set(
                    len(replica.outstanding))
            return replica
        # no replica could take it: this is CAPACITY loss, not a broken
        # request — terminate with the "unavailable" reason the serving
        # surface maps to a clean 503 + Retry-After (backpressure-header
        # contract, docs/resilience.md) instead of a bare error
        logger.error("engine pool: no routable replica for %s (%s)",
                     request.request_id, last_error,
                     extra=trace_extra(request.trace_ctx))
        if request.finish_reason is None:
            request.finish_reason = "unavailable"
        request.stream.put_nowait(None)
        return None

    def _make_shadow(self, request: GenRequest, attempts: int,
                     cap: int = 0) -> GenRequest:
        """The engine-facing request. On a requeue the prompt is the
        CONTINUATION — original prompt plus every token already delivered
        — so the survivor resumes where the failed replica stopped and
        nothing is emitted twice; ``queue_observed`` rides the engine's
        once-only guard so the logical request's queue phase is observed
        exactly once across attempts. A non-zero ``cap`` bounds the
        shadow's budget below the logical request's remainder: the
        migration prefill leg runs with cap=1 (prefill + first token,
        then hand off)."""
        suffix = "" if attempts == 1 else f"~r{attempts - 1}"
        budget = max(1, request.max_tokens - len(request.generated))
        if cap:
            budget = min(budget, cap)
        return GenRequest(
            request_id=f"{request.request_id}{suffix}",
            prompt_ids=list(request.prompt_ids) + list(request.generated),
            max_tokens=budget,
            temperature=request.temperature,
            top_k=request.top_k,
            top_p=request.top_p,
            stop_ids=request.stop_ids,
            priority=request.priority,
            created=request.created,
            t_submit=request.t_submit,
            # billing identity must ride EVERY shadow, including requeued
            # continuations — a failover must not turn a tenant's tail
            # tokens into unattributed work (token-conservation gate)
            tenant=request.tenant,
            trace_ctx=request.trace_ctx,
            queue_observed=attempts > 1,
            # once-only TTFT/llm.prefill: if the failed attempt already
            # delivered a first token, the logical request's TTFT has
            # been observed — the continuation must not observe a second
            # sample spanning the failed attempt + failover
            ttft_observed=len(request.generated) > 0,
        )

    async def _pump(self, record: PoolRecord) -> None:
        """Forward the shadow's tokens to the client stream; on the
        terminal, either finish the client or hand the record to the
        failover path. Cancelled (without side effects) when the health
        monitor takes over a failed replica's records."""
        shadow = record.shadow
        request = record.request
        while True:
            token = await shadow.stream.get()
            if token is None:
                break
            request.generated.append(token)
            request.stream.put_nowait(token)
        await self._on_shadow_done(record)

    async def _on_shadow_done(self, record: PoolRecord) -> None:
        replica = record.replica
        request = record.request
        replica.outstanding.pop(request.request_id, None)
        if self.metrics is not None:
            self.metrics.llm_pool_outstanding.labels(
                replica=replica.id).set(len(replica.outstanding))
        reason = record.shadow.finish_reason or "stop"
        if reason == "error" and not self._stopping:
            # the engine only posts "error" terminals from its crash /
            # fail-outstanding paths — treat it as replica evidence, then
            # try to finish the request elsewhere
            if not record.replica.engine.dispatch_alive():
                self.fail_replica(replica,
                                  reason="stream error + dead dispatch")
            await self._requeue(record)
            return
        if (record.migrate_leg and reason == "length"
                and not self._stopping
                and request.finish_reason is None
                and len(request.generated) < request.max_tokens):
            # the one-token prefill leg retired its cap, not the
            # request's budget: hand the KV chain to a decode replica.
            # (A "stop" terminal here means the first token really
            # finished the request — it falls through as a normal
            # terminal, nothing to migrate.)
            await self._migrate(record)
            return
        record.done = True
        if request.finish_reason is None:
            request.finish_reason = reason
        request.stream.put_nowait(None)

    # --------------------------------------------------------------- migration

    async def _migrate(self, record: PoolRecord) -> None:
        """The prefill->decode hop (docs/disaggregation.md): export the
        prompt's KV chain through the pool-shared spill tiers at the
        source engine's drain barrier, verify every page against its
        token content (the same verify-before-serve gate admission
        restores use — a corrupt payload degrades to a MISS, never a
        wrong page), then continue the request on a decode replica as a
        pool-shadow continuation. ANY failed step decodes in place on
        the prefill replica instead; the stream never dies to a
        migration. Conservation: every spilled page is counted restored
        (hop landed on the target) or degraded (anything else) —
        spilled == restored + degraded, pinned in tests."""
        from ...observability.faults import fault_point
        from ..kv.prefix_index import chain_pages
        request = record.request
        src = record.replica
        started = time.time()
        page_size = self.config.page_size
        expected = len(request.prompt_ids) // page_size
        spilled = 0
        moved_bytes = 0
        corrupt = False
        target: EngineReplica | None = None
        failure = ""
        try:
            # fault point pool.migrate (docs/resilience.md): error fails
            # the hop (degrade to decode-in-place), latency stretches it
            # (the slow-migration chaos arm), corrupt mangles the chain
            # identity below so verify-before-serve rejects the payload.
            act = fault_point("pool.migrate", scope=request.request_id)
            if act is not None:
                if act.kind == "corrupt":
                    corrupt = True
                else:
                    await act.async_apply()
            # 1) export: the source engine copies the prompt chain's
            # resident pages into the shared store at its dispatch-loop
            # drain barrier (quiesced device state, same seam reload's
            # spill-on-drain uses). COPY, not move — on any later
            # failure the pages are still resident for decode-in-place.
            spilled = await asyncio.wait_for(
                asyncio.wrap_future(
                    src.engine.request_chain_export(request.prompt_ids)),
                timeout=30.0)
            if spilled < expected:
                raise RuntimeError(
                    f"chain export covered {spilled}/{expected} pages")
            # 2) verify-before-serve, pool-side: walk the exported chain
            # through the store's payload gate with the token content we
            # KNOW the decode replica will request. An injected corrupt
            # mangles the first page's expected chunk, so the store's
            # comparison fails exactly as a real collision would — the
            # entry is dropped and the migration degrades.
            steps = chain_pages(request.prompt_ids, page_size)
            if corrupt and steps:
                key_hash, parent, chunk = steps[0]
                steps[0] = (key_hash, parent, (chunk[0] + 1,) + chunk[1:])
            verified, moved_bytes = self.tier_store.verify_chain(steps)
            if verified < expected:
                raise RuntimeError(
                    f"verify-before-serve passed {verified}/{expected} "
                    f"pages")
            # 3) pick the decode target: role-aware routing over the
            # decode-capable survivors (never the source), scored on the
            # continuation prompt so tier affinity counts.
            candidates = [r for r in self._routable()
                          if r is not src and r.role in ("decode", "any")]
            if not candidates:
                raise RuntimeError("no decode-capable target replica")
            target, _ = self.router.route(
                candidates,
                list(request.prompt_ids) + list(request.generated),
                route_class="decode")
        except Exception as exc:  # FaultError included: degrade, never die
            failure = str(exc)
            target = None
        if target is None:
            logger.warning(
                "engine pool: migration of %s degrading to "
                "decode-in-place on replica %s (%s)", request.request_id,
                src.id, failure or "no target",
                extra=trace_extra(request.trace_ctx))
        # 4) continue as a pool-shadow continuation (the requeue
        # contract: prompt + generated, once-only TTFT/queue guards) —
        # pinned to the chosen target, or to the source for the
        # decode-in-place degrade. A pin that refuses falls back to
        # normal routing inside _dispatch; a lost stream is impossible
        # short of total pool capacity loss ("unavailable" terminal).
        landed = await self._dispatch(request, attempts=record.attempts + 1,
                                      pin=target if target is not None
                                      else src)
        outcome = ("ok" if target is not None and landed is target
                   else "degraded")
        self.migrations[outcome] += 1
        self.migration_pages["spilled"] += spilled
        self.migration_pages[
            "restored" if outcome == "ok" else "degraded"] += spilled
        self.migration_bytes += moved_bytes
        if outcome == "ok":
            src.migrations_out += 1
            landed.migrations_in += 1
        to_id = landed.id if landed is not None else src.id
        m = self.metrics
        if m is not None:
            m.llm_pool_migrations.labels(src.id, to_id, outcome).inc()
            m.llm_pool_migration_seconds.observe(time.time() - started)
            if spilled:
                m.llm_pool_migration_pages.labels("spilled").inc(spilled)
                m.llm_pool_migration_pages.labels(
                    "restored" if outcome == "ok" else "degraded"
                ).inc(spilled)
            if moved_bytes:
                m.llm_pool_migration_bytes.inc(moved_bytes)
        if self.tracer is not None and request.trace_ctx is not None:
            # the hop as a span: joins the prefill replica's llm.* spans
            # to the decode replica's in ONE trace (span-stitch contract)
            try:
                attrs = {"llm.from_replica": src.id,
                         "llm.to_replica": to_id,
                         "llm.pages": spilled,
                         "llm.outcome": outcome}
                if failure:
                    attrs["llm.failure"] = failure[:200]
                if request.tenant:
                    attrs["llm.tenant"] = request.tenant
                self.tracer.emit_span("pool.migrate", started, time.time(),
                                      trace_ctx=request.trace_ctx,
                                      attributes=attrs)
            except Exception:
                pass  # telemetry must never break the hop

    # ---------------------------------------------------------------- failover

    def fail_replica(self, replica: EngineReplica,
                     reason: str = "") -> None:  # lint: runs-on[pool]
        """Take a replica out of rotation and requeue its in-flight
        requests. Idempotent; called by the health monitor (wedge/crash
        sweep) and the submit/pump paths (stream evidence)."""
        if replica.state == "dead":
            return
        replica.state = "dead"
        replica.failures += 1
        replica.last_failure = reason or "failed"
        logger.error("engine pool: replica %s marked dead (%s)",
                     replica.id, replica.last_failure)
        if self.metrics is not None:
            self.metrics.llm_pool_replica_up.labels(replica=replica.id).set(0)
        # signal, never join: a wedged dispatch thread must not delay the
        # requeue, and a zombie that later revives exits at its next loop
        # check (its late emissions land in abandoned shadow streams)
        replica.engine.kill()
        client = getattr(replica.engine, "_tier_client", None)
        if client is not None:
            # the dead engine's HBM pages are unreachable — forget its
            # prefix-index entries so affinity scoring can't chase
            # ghosts (pages already SPILLED are content-addressed in the
            # shared store and keep serving every survivor)
            client.drop_replica()
        survivors = self._take_over_records(replica)
        if survivors:
            asyncio.get_running_loop().create_task(
                self._requeue_batch(survivors),
                name=f"pool-requeue-{replica.id}")

    def _take_over_records(self, replica: EngineReplica
                           ) -> list[PoolRecord]:  # lint: runs-on[pool]
        """Detach a replica's in-flight records from it: cancel the pumps,
        forward whatever each shadow stream already holds (tokens the
        consumer must not lose OR see twice), deliver any terminal that
        raced the takeover, and return the records that still need a
        home. Used by the failover sweep and by reload when a drain
        times out with work still in flight."""
        records = list(replica.outstanding.values())
        replica.outstanding.clear()
        if self.metrics is not None:
            self.metrics.llm_pool_outstanding.labels(
                replica=replica.id).set(0)
        survivors: list[PoolRecord] = []
        for record in records:
            if record.pump is not None:
                record.pump.cancel()
            finished = self._drain_shadow(record)
            if finished and (record.shadow.finish_reason or "stop") \
                    != "error":
                # the shadow actually completed (terminal raced the
                # takeover): deliver it, nothing to requeue
                record.done = True
                if record.request.finish_reason is None:
                    record.request.finish_reason = \
                        record.shadow.finish_reason or "stop"
                record.request.stream.put_nowait(None)
                continue
            survivors.append(record)
        return survivors

    def _drain_shadow(self, record: PoolRecord) -> bool:
        """Forward whatever the failed replica already emitted into the
        shadow stream (tokens the consumer must not lose OR see twice),
        returning True if the terminal None was present."""
        while True:
            try:
                token = record.shadow.stream.get_nowait()
            except asyncio.QueueEmpty:
                return False
            if token is None:
                return True
            record.request.generated.append(token)
            record.request.stream.put_nowait(token)

    async def _requeue_batch(self, records: list[PoolRecord]) -> None:
        for record in records:
            await self._requeue(record)

    async def _requeue(self, record: PoolRecord) -> None:
        from ...observability.faults import FaultError, fault_point
        request = record.request
        if record.done or request.finish_reason is not None:
            return
        old = record.replica
        if len(request.generated) >= request.max_tokens:
            # the failed replica had already emitted the full budget
            record.done = True
            request.finish_reason = "length"
            request.stream.put_nowait(None)
            return
        if (self._stopping or record.attempts - 1 >= self.requeue_max
                or not self._routable()):
            # requeue budget spent / nowhere to go: the stream ends with
            # "unavailable" — the provider raises LLMUnavailable and the
            # HTTP surface answers 503 + Retry-After (clean terminal,
            # never a bare mid-stream error; pinned in the pool tests)
            record.done = True
            request.finish_reason = "unavailable"
            request.stream.put_nowait(None)
            return
        # fault point pool.requeue (docs/resilience.md): an injected
        # error fails THIS failover hop the same way a spent budget
        # does; latency delays the continuation (the chaos matrix's
        # slow-failover arm). Unarmed: one dict miss.
        act = fault_point("pool.requeue", scope=request.request_id)
        if act is not None:
            try:
                await act.async_apply()
            except FaultError:
                record.done = True
                request.finish_reason = "unavailable"
                request.stream.put_nowait(None)
                return
        self.requeues += 1
        # counted here — not in fail_replica — so the status card's
        # requeued_off and mcpforge_llm_pool_requeues_total agree no
        # matter which path (health sweep or pump error terminal)
        # triggered the requeue
        old.requeued_off += 1
        if self.metrics is not None:
            self.metrics.llm_pool_requeues.labels(replica=old.id).inc()
        # trace correlation: the failover line joins to the request's
        # OTel trace in the JSON/ring logs (observability/logging.py)
        logger.warning("engine pool: requeueing %s off replica %s "
                       "(%d tokens already delivered)", request.request_id,
                       old.id, len(request.generated),
                       extra=trace_extra(request.trace_ctx))
        requeued_at = time.time()
        await self._dispatch(request, attempts=record.attempts + 1)
        if self.tracer is not None and request.trace_ctx is not None:
            # the failover hop as a span: joins the killed replica's
            # llm.* spans to the successor's in ONE trace, tenant
            # intact — the forensics waterfall renders the hop instead
            # of two disconnected half-requests
            try:
                attrs = {"llm.from_replica": old.id,
                         "llm.attempt": record.attempts + 1,
                         "llm.tokens_delivered": len(request.generated)}
                if request.tenant:
                    attrs["llm.tenant"] = request.tenant
                self.tracer.emit_span("pool.requeue", requeued_at,
                                      time.time(),
                                      trace_ctx=request.trace_ctx,
                                      attributes=attrs)
            except Exception:
                pass  # telemetry must never break failover

    # ------------------------------------------------------------ drain/reload

    def _replica(self, rid: str) -> EngineReplica:
        for replica in self.replicas:
            if replica.id == rid:
                return replica
        raise KeyError(f"no replica {rid!r} "
                       f"(have {[r.id for r in self.replicas]})")

    async def drain(self, rid: str,  # lint: runs-on[pool]
                    timeout_s: float = 60.0) -> dict[str, Any]:
        """Stop routing new work to the replica and wait for its in-flight
        requests to finish on it. Idempotent; ``undrain`` reverses."""
        replica = self._replica(rid)
        if replica.state == "ready":
            replica.state = "draining"
            if self.metrics is not None:
                self.metrics.llm_pool_replica_up.labels(
                    replica=replica.id).set(0)
        deadline = time.monotonic() + max(0.0, timeout_s)
        while replica.outstanding and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        status = replica.status()
        status["drained"] = not replica.outstanding
        return status

    async def undrain(self, rid: str) -> dict[str, Any]:  # lint: runs-on[pool]
        """Readmit a drained (or draining) replica to the router."""
        replica = self._replica(rid)
        if replica.state != "draining":
            raise ValueError(
                f"replica {rid} is {replica.state}, not draining")
        replica.state = "ready"
        if self.metrics is not None:
            self.metrics.llm_pool_replica_up.labels(replica=replica.id).set(1)
        return replica.status()

    async def reload(self, rid: str,  # lint: runs-on[pool]
                     timeout_s: float = 60.0) -> dict[str, Any]:
        """Rolling weight hot-swap: drain -> rebuild the engine (fresh
        checkpoint read from ``config.checkpoint``) -> readmit. The rest
        of the pool serves throughout; a dead replica can be reloaded
        too (that IS its recovery path)."""
        replica = self._replica(rid)
        if replica.state == "reloading":
            raise ValueError(f"replica {rid} is already reloading")
        was_dead = replica.state == "dead"
        if not was_dead:
            await self.drain(rid, timeout_s=timeout_s)
            if replica.outstanding:
                # the drain timed out with generations still running.
                # engine.stop() would terminate them with
                # finish_reason="cancelled" — a truncated stream for the
                # client — while the rest of the pool could finish them
                # exactly as the wedge/crash path does: hand the
                # stragglers to the survivors as continuations. (The
                # replica is already off the router: "draining".)
                stragglers = self._take_over_records(replica)
                if stragglers:
                    logger.warning(
                        "engine pool: reload of replica %s requeueing %d "
                        "request(s) the drain window did not cover",
                        rid, len(stragglers))
                    await self._requeue_batch(stragglers)
        replica.state = "reloading"
        try:
            await replica.engine.stop()
        except Exception:
            logger.exception("engine pool: replica %s stop during reload "
                             "failed (continuing with rebuild)", rid)
        # a kill()ed engine was never joined (stop() returns immediately
        # once _started is false) and its zombie thread pins the old
        # params + KV pool on the replica's devices; give it a bounded
        # window to exit before committing a second footprint to the
        # same HBM (docs/serving_pool.md, reload section)
        thread = getattr(replica.engine, "_thread", None)
        if thread is not None and thread.is_alive():
            await asyncio.to_thread(thread.join, min(max(timeout_s, 0.0), 30.0))
            if thread.is_alive():
                logger.warning(
                    "engine pool: replica %s dispatch thread is still "
                    "wedged; rebuilding anyway — device memory may be "
                    "double-committed until it exits", rid)
        # spill-on-drain (docs/resilience.md): with the dispatch thread
        # quiesced and the old engine's device state still intact, copy
        # its ref==0 resident prefix pages into the pool-shared spill
        # store — the rebuilt engine (and every sibling) then restores
        # the prefix corpus by fetch-on-miss instead of losing it with
        # the torn-down HBM pool. A dead/wedged engine is skipped: its
        # device state is suspect and must not poison the shared tiers.
        thread_quiesced = thread is None or not thread.is_alive()
        if not was_dead and thread_quiesced \
                and self.tier_store is not None:
            try:
                spilled = await asyncio.to_thread(
                    replica.engine.spill_prefix_pages)
                if spilled:
                    logger.info("engine pool: reload of replica %s "
                                "spilled %d resident prefix page(s)",
                                rid, spilled)
            except Exception:
                logger.exception("engine pool: spill-on-drain failed for "
                                 "replica %s (continuing with rebuild)",
                                 rid)
        try:
            # engine construction compiles + loads weights: off the loop
            engine = await asyncio.to_thread(self._build_engine,
                                             replica.index)
        except Exception:
            replica.state = "dead"
            if self.metrics is not None:
                self.metrics.llm_pool_replica_up.labels(
                    replica=replica.id).set(0)
            raise
        replica.engine = engine
        if self._started:
            await engine.start()
        replica.state = "ready"
        replica.reloads += 1
        if self.metrics is not None:
            self.metrics.llm_pool_reloads.labels(replica=replica.id).inc()
            self.metrics.llm_pool_replica_up.labels(replica=replica.id).set(1)
        logger.info("engine pool: replica %s reloaded%s", rid,
                    " (was dead)" if was_dead else "")
        return replica.status()

    # ------------------------------------------------------------- aggregation

    @property
    def stats(self) -> EngineStats:
        """Aggregated scheduler counters across replicas (the facade the
        bench and stats surfaces read; recomputed per access)."""
        total = EngineStats()
        for replica in self.replicas:
            stats = replica.engine.stats
            for name, value in vars(stats).items():
                setattr(total, name, getattr(total, name) + value)
        return total

    def kv_pages_in_use(self) -> int:
        return sum(r.engine.allocator.pages_in_use for r in self.replicas)

    def kv_bytes_in_use(self) -> int:
        return sum(r.engine.kv_bytes_in_use() for r in self.replicas)

    def device_idle_fraction(self) -> float:
        fracs = [r.engine.device_idle_fraction() for r in self.replicas]
        return sum(fracs) / len(fracs) if fracs else 0.0

    def status(self) -> dict[str, Any]:
        """The /admin/engine/pool payload: per-replica health, occupancy,
        and routing/failover counters."""
        return {
            "replicas": [r.status() for r in self.replicas],
            "router": {**self.router.counters(),
                       "affinity_routing": self.router.affinity_routing},
            "prefix_tiers": {
                "enabled": self.tier_store is not None,
                "store": (self.tier_store.stats()
                          if self.tier_store is not None else None),
                "index": (self.prefix_index.stats()
                          if self.prefix_index is not None else None),
            },
            "roles": {
                "active": self.roles_active,
                "assignment": {r.id: r.role for r in self.replicas},
                "disagg_prompt_tokens": self.disagg_prompt_tokens,
            },
            "migrations": {
                **self.migrations,
                "pages": dict(self.migration_pages),
                "bytes": self.migration_bytes,
            },
            "requeues": self.requeues,
            "requeue_max": self.requeue_max,
            "health": {
                "sweeps": self.health.sweeps,
                "failures": self.health.failures,
                "interval_s": self.health.interval_s,
                "heartbeat_timeout_s": self.health.heartbeat_timeout_s,
            },
        }

    def _set_up_gauges(self) -> None:
        if self.metrics is None:
            return
        for replica in self.replicas:
            self.metrics.llm_pool_replica_up.labels(replica=replica.id).set(1)
            self.metrics.llm_pool_outstanding.labels(replica=replica.id).set(0)
