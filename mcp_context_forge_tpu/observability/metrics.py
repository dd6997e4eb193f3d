"""Prometheus metrics (reference: services/metrics.py setup_metrics :306)."""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
    CONTENT_TYPE_LATEST,
)
from prometheus_client.core import HistogramMetricFamily
from prometheus_client.openmetrics import exposition as openmetrics
from prometheus_client.utils import floatToGoString

from .. import __version__
from .tenant import TenantClamp
from .trace_store import ExemplarLedger


class _GcPauseCollector:
    """``mcpforge_gc_pause_seconds{generation}``: the collector's pauses as
    the step timeline's gc watch counted them (``observability/timeline.py:
    _GcWatch``; its hook runs on every collection, so the counting is plain
    additions there and the histogram is put together here, at scrape
    time). Process-wide, like the collector itself."""

    def __init__(self, watch) -> None:
        self._watch = watch

    def collect(self):
        watch = self._watch
        family = HistogramMetricFamily(
            "mcpforge_gc_pause_seconds",
            "Seconds a garbage collection held the process, by generation",
            labels=["generation"])
        bounds = [floatToGoString(b) for b in watch.BOUNDS] + ["+Inf"]
        for generation in range(watch.GENERATIONS):
            seen, buckets = 0, []
            for bound, count in zip(bounds, watch.buckets[generation]):
                seen += count
                buckets.append((bound, seen))
            family.add_metric([str(generation)], buckets,
                              sum_value=watch.total_s[generation])
        yield family


class PrometheusRegistry:
    """Gateway-wide Prometheus metrics, own registry (hermetic for tests).

    ``tenant_clamp`` bounds every ``tenant`` label in this registry:
    first-N-observed tenants keep their own label child, the rest clamp
    to ``"other"``, so per-tenant slicing can never explode series
    cardinality (docs/multitenancy.md). The app replaces the default
    clamp with one sized by ``tenant_label_clamp`` and shares the SAME
    instance with the :class:`~.metering.TenantLedger` so metric labels
    and ledger admission agree."""

    def __init__(self, tenant_clamp: TenantClamp | None = None,
                 exemplars: ExemplarLedger | None = None) -> None:
        self.registry = CollectorRegistry()
        self.tenant_clamp = tenant_clamp or TenantClamp()
        # per-bucket trace-id exemplars for the latency histograms
        # (observability/trace_store.py): observe sites call
        # self.exemplar(...) and pass the result to observe(), and the
        # trace store keeps every live exemplar's trace retained so the
        # OpenMetrics click-through never dangles
        self.exemplars = exemplars if exemplars is not None \
            else ExemplarLedger()
        self._gc_collector: _GcPauseCollector | None = None
        self.app_info = Gauge(  # lint: allow[dead-metric] fully populated at registration
            "mcpforge_app_info", "Application info", ["version"], registry=self.registry
        )
        self.app_info.labels(version=__version__).set(1)
        self.http_requests = Counter(
            "mcpforge_http_requests_total", "HTTP requests",
            ["method", "path", "status"], registry=self.registry,
        )
        # tenant label (clamped): the per-tenant http_p95 SLO-class
        # objective slices this histogram by label child
        self.http_duration = Histogram(
            "mcpforge_http_request_duration_seconds", "HTTP request latency",
            ["method", "path", "tenant"], registry=self.registry,
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
        )
        self.tool_invocations = Counter(
            "mcpforge_tool_invocations_total", "Tool invocations",
            ["tool", "status"], registry=self.registry,
        )
        self.tool_duration = Histogram(
            "mcpforge_tool_invocation_duration_seconds", "Tool invocation latency",
            ["tool"], registry=self.registry,
            buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0),
        )
        self.plugin_duration = Histogram(
            "mcpforge_plugin_hook_duration_seconds", "Plugin hook latency",
            ["plugin", "hook"], registry=self.registry,
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
        )
        self.llm_tokens = Counter(
            "mcpforge_llm_tokens_total", "LLM tokens processed by tpu_local",
            ["model", "kind"], registry=self.registry,  # kind: prompt|completion
        )
        self.llm_requests = Counter(
            "mcpforge_llm_requests_total", "LLM requests", ["model", "status"],
            registry=self.registry,
        )
        # every engine-fed GAUGE carries a replica label: gauges are
        # last-writer-wins, so N replicas' dispatch threads writing one
        # unlabeled series would flap between replicas' values (counters
        # and histograms aggregate correctly unlabeled and keep only the
        # labels their queries need)
        self.llm_queue_depth = Gauge(
            "mcpforge_llm_queue_depth", "tpu_local scheduler queue depth",
            ["replica"], registry=self.registry,
        )
        self.llm_kv_pages_in_use = Gauge(
            "mcpforge_llm_kv_pages_in_use", "Paged KV cache pages in use",
            ["replica"], registry=self.registry,
        )
        # dtype-aware twin of the page-count gauge: pages x page bytes
        # under the active KV storage dtype (int8 pages cost ~half their
        # bf16 twin), so mixed-mode fleets compare on one byte axis.
        # Replica-labeled: under an EnginePool each replica owns its own
        # KV pool, and a per-replica byte view is what capacity planning
        # and the drain decision read.
        self.llm_kv_bytes_in_use = Gauge(
            "mcpforge_llm_kv_bytes_in_use",
            "HBM bytes the in-use KV pages occupy under the active KV dtype",
            ["replica"], registry=self.registry,
        )
        # per-sequence state beside the pages (a model family whose cache
        # keeps a fixed-size recurrent state a sequence): rows live slots
        # own, and the HBM bytes those rows occupy. Zero for families whose
        # cache grows a token only.
        self.llm_state_rows_in_use = Gauge(
            "mcpforge_llm_state_rows_in_use",
            "Per-sequence recurrent-state rows live slots own",
            ["replica"], registry=self.registry,
        )
        self.llm_state_bytes = Gauge(
            "mcpforge_llm_state_bytes",
            "HBM bytes the live per-sequence state rows occupy",
            ["replica"], registry=self.registry,
        )
        # token-level SLO signals (fed by the engine dispatch thread):
        # TTFT = submit -> first token (queue + prefill), TPOT = mean
        # inter-token latency over the decode phase of one request.
        # The replica label separates a degraded replica's tail from the
        # pool aggregate (sum across label children for the fleet view).
        # the tenant label (clamped to top-N + "other" by tenant_clamp)
        # turns these into the per-tenant SLO-class evidence /admin/slo
        # evaluates — a noisy neighbor's tail separates from the fleet's
        self.llm_ttft = Histogram(
            "mcpforge_llm_ttft_seconds", "Time to first token",
            ["model", "replica", "tenant"], registry=self.registry,
            buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                     10.0, 30.0),
        )
        self.llm_tpot = Histogram(
            "mcpforge_llm_tpot_seconds",
            "Per-token decode latency (mean over one request)",
            ["model", "replica", "tenant"], registry=self.registry,
            buckets=(0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.15, 0.3,
                     0.6, 1.2, 2.5),
        )
        self.llm_queue_wait = Histogram(
            "mcpforge_llm_queue_wait_seconds",
            "Submit -> batch admission wait", ["tenant"],
            registry=self.registry,
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0,
                     60.0),
        )
        self.llm_batch_occupancy = Gauge(
            "mcpforge_llm_batch_occupancy",
            "Active decode slots at the last engine step",
            ["replica"], registry=self.registry,
        )
        self.llm_kv_page_utilization = Gauge(
            "mcpforge_llm_kv_page_utilization",
            "Fraction of the paged KV pool in use (0..1)",
            ["replica"], registry=self.registry,
        )
        # a model family whose decode dispatch is a BLOCK step (generation
        # by diffusion over blocks, models/sdar.py): dispatches, the forward
        # passes inside them that sampled, the tokens they emitted and the
        # positions filled by the confidence threshold (the rest by rank).
        # Counted at the step's retire; never incremented by another family.
        self.llm_block_steps = Counter(
            "mcpforge_llm_block_steps_total",
            "Block-step dispatches (one block a live row each)",
            ["replica"], registry=self.registry,
        )
        self.llm_denoise_passes = Counter(
            "mcpforge_llm_denoise_passes_total",
            "Forward passes of block steps that sampled (commit passes excluded)",
            ["replica"], registry=self.registry,
        )
        self.llm_block_tokens = Counter(
            "mcpforge_llm_block_tokens_total",
            "Tokens block steps emitted",
            ["replica"], registry=self.registry,
        )
        self.llm_block_threshold_fills = Counter(
            "mcpforge_llm_block_threshold_fills_total",
            "Block positions filled because their confidence passed the threshold",
            ["replica"], registry=self.registry,
        )
        # dense prefill dispatches (no cached history, no chunk round): the
        # positions they ran, split into the prompts' own and the padding up
        # to the dispatched rows x length, and those that took a bucket's
        # half-length program (a lone prompt that fits half its bucket)
        self.llm_dense_prefill_positions = Counter(
            "mcpforge_llm_dense_prefill_positions_total",
            "Positions dense prefill dispatches ran (kind: prompt|padding)",
            ["replica", "kind"], registry=self.registry,
        )
        self.llm_half_prefill_batches = Counter(
            "mcpforge_llm_half_prefill_batches_total",
            "Dense prefill dispatches through a bucket's half-length program",
            ["replica"], registry=self.registry,
        )
        self.llm_kv_alloc_failures = Counter(
            "mcpforge_llm_kv_alloc_failures_total",
            "Admissions deferred or requests truncated for lack of KV pages",
            registry=self.registry,
        )
        # --- tiered prefix/KV cache (tpu_local/kv/tiers.py,
        # docs/kv_tiering.md) --- per-tier split of the prefix-cache hit
        # stream (hbm = resident pages, host/disk = pages restored from a
        # spill tier at admission); counted at the same consume site as
        # allocator.prefix_hit_tokens, so summing tiers reproduces it
        self.llm_prefix_tier_hits = Counter(
            "mcpforge_llm_prefix_tier_hits_total",
            "Prefix-cache page hits by serving tier (hbm = resident, "
            "host/disk = restored on match from a spill tier)",
            ["replica", "tier"], registry=self.registry,
        )
        # bytes resident per tier: hbm is per-replica (registered prefix
        # pages x page bytes); host/disk report the POOL-SHARED store, so
        # every replica's child carries the same value — read one child,
        # never sum across replicas for the shared tiers
        self.llm_prefix_tier_bytes = Gauge(
            "mcpforge_llm_prefix_tier_bytes",
            "Bytes resident in each prefix-cache tier (hbm per replica; "
            "host/disk are the pool-shared spill store)",
            ["replica", "tier"], registry=self.registry,
        )
        # spill/restore dataflow latency: spill = device->host page read
        # + T1 admit at eviction, restore = verified fetch + host->device
        # upload at match, writeback = the worker's T1->T2 persist
        self.llm_prefix_tier_io = Histogram(
            "mcpforge_llm_prefix_tier_io_seconds",
            "Tiered prefix-cache page movement latency by operation "
            "(spill, restore, writeback) and tier touched",
            ["op", "tier"], registry=self.registry,
            buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                     0.025, 0.05, 0.1, 0.25, 1.0),
        )
        # spill-tier IO failure accounting (tiers.py disk hardening):
        # transient errors retry with bounded backoff, then the entry is
        # quarantined (clean MISS, never a hang or a poisoned serve) —
        # this counter is the evidence trail per (tier, op)
        self.llm_prefix_tier_io_errors = Counter(
            "mcpforge_llm_prefix_tier_io_errors_total",
            "Tiered prefix-cache IO failures after retries, by tier and "
            "operation (the entry is quarantined — dropped to a clean "
            "MISS, counted here)",
            ["tier", "op"], registry=self.registry,
        )
        # cross-host prefix-cache fabric (tpu_local/kv/fabric/,
        # docs/cache_fabric.md): advert gossip volume — "sent" counts
        # pushes this host delivered to a peer (bus or HTTP), "merged"
        # counts NEW chain hashes learned from peers (refreshes of
        # already-known hashes don't count)
        self.llm_fabric_adverts = Counter(
            "mcpforge_llm_fabric_adverts_total",
            "Prefix-fabric advert gossip by direction (sent = pushes "
            "delivered to peers, merged = new chain hashes learned)",
            ["direction"], registry=self.registry,
        )
        self.llm_step_tokens_per_sec = Gauge(
            "mcpforge_llm_step_tokens_per_sec",
            "Tokens emitted per second by the last engine step (over the "
            "true retire-to-retire step wall, so superstep K>1 and the "
            "overlap pipeline both report truthfully)",
            ["replica"], registry=self.registry,
        )
        # K-step super-step accounting: tokens retired per device
        # dispatch (≈ batch × superstep at steady state). One host sync
        # retires this many tokens — the token-loop-fusion win is this
        # gauge rising while dispatch-gap stays flat
        self.llm_tokens_per_dispatch = Gauge(
            "mcpforge_llm_tokens_per_dispatch",
            "Tokens emitted by the last decode dispatch (superstep K>1 "
            "retires up to K per slot per host sync)",
            ["replica"], registry=self.registry,
        )
        # smoothed twin of the instantaneous gauge: a single dispatch's
        # token count whipsaws with batch occupancy, so alerts and the
        # serving controller act on this EWMA instead
        self.llm_tokens_per_dispatch_ewma = Gauge(
            "mcpforge_llm_tokens_per_dispatch_ewma",
            "EWMA of tokens per decode dispatch (alpha 0.2; the smoothed "
            "form the serving controller and alerts consume)",
            ["replica"], registry=self.registry,
        )
        # overlapped-decode health: the gap histogram is the host-side
        # stall between device dispatches (the thing the pipeline hides —
        # collapses to ~0 when overlap is on), and the idle fraction is
        # gaps / (gaps + in-step wall) over the recent decode window
        self.llm_dispatch_gap = Histogram(
            "mcpforge_llm_dispatch_gap_seconds",
            "Host-side stall between consecutive decode dispatches",
            ["replica"], registry=self.registry,
            buckets=(0.00001, 0.00005, 0.0001, 0.00025, 0.0005, 0.001,
                     0.0025, 0.005, 0.01, 0.025, 0.05, 0.1),
        )
        self.llm_device_idle_frac = Gauge(
            "mcpforge_llm_device_idle_fraction",
            "Host dispatch-gap share of decode wall: host-clock gaps before "
            "host-fed decode dispatches / (gaps + dispatch-to-retire wall), "
            "0..1. Not the device's idle time: that comes from a profiler "
            "trace",
            ["replica"], registry=self.registry,
        )
        # step phase attribution: how the host's side of every HOST-FED
        # dispatch (the device sits drained while it is built) splits into
        # the parts its timeline spans name, and how long the host then
        # waited for the result — read off the spans, no forced sync
        self.llm_step_phase = Histogram(
            "mcpforge_llm_step_phase_seconds",
            "Host phases of every host-fed dispatch, off its timeline "
            "spans (rows, sampling, rng, table_sync, upload, launch, "
            "readback)",
            ["replica", "phase"], registry=self.registry,
            buckets=(0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                     0.005, 0.01, 0.025, 0.05, 0.1, 0.25),
        )
        # live roofline gauges: warmup-captured XLA cost_analysis()
        # (FLOPs / bytes accessed per executable) divided by each decode
        # step's measured wall — the bench-only MFU / hbm_roofline_frac
        # numbers as always-on production signals (tpu_local/roofline.py)
        self.llm_mfu = Gauge(
            "mcpforge_llm_mfu",
            "Model FLOPs utilization of the last decode step (XLA "
            "cost-model FLOPs / wall / peak)",
            ["replica"], registry=self.registry,
        )
        self.llm_hbm_roofline = Gauge(
            "mcpforge_llm_hbm_roofline_frac",
            "Fraction of the HBM-bandwidth roofline the last decode step "
            "achieved (XLA cost-model bytes / wall / peak BW)",
            ["replica"], registry=self.registry,
        )
        # XLA compile tracking (tpu_local/compile_events.py): a compile
        # at stage="serving" on a warmed engine is the PR-5 silent
        # catastrophe resurfacing — alert on it
        self.llm_xla_compiles = Counter(
            "mcpforge_llm_xla_compiles_total",
            "XLA backend compilations attributed to the engine, by "
            "lifecycle stage (warmup|serving)",
            ["replica", "stage"], registry=self.registry,
        )
        self.llm_xla_compile_time = Histogram(
            "mcpforge_llm_xla_compile_seconds",
            "Duration of XLA backend compilations attributed to the engine",
            ["replica"], registry=self.registry,
            buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 180.0),
        )
        # EnginePool (tpu_local/pool/) serving tier: per-replica health,
        # load, and routing outcomes — fed by the pool router/health
        # monitor on the gateway loop
        self.llm_pool_replica_up = Gauge(
            "mcpforge_llm_pool_replica_up",
            "1 while the replica is routable (ready), 0 otherwise",
            ["replica"], registry=self.registry,
        )
        self.llm_pool_outstanding = Gauge(
            "mcpforge_llm_pool_outstanding_requests",
            "In-flight requests the pool has routed to the replica",
            ["replica"], registry=self.registry,
        )
        self.llm_pool_routed = Counter(
            "mcpforge_llm_pool_routed_total",
            "Requests routed to the replica (affinity: prefix-cache hit "
            "steered the choice)",
            ["replica", "affinity"], registry=self.registry,
        )
        self.llm_pool_requeues = Counter(
            "mcpforge_llm_pool_requeues_total",
            "In-flight requests requeued off a failed replica onto a "
            "healthy one",
            ["replica"], registry=self.registry,
        )
        self.llm_pool_reloads = Counter(
            "mcpforge_llm_pool_reloads_total",
            "Rolling drain->swap->readmit reloads completed per replica",
            ["replica"], registry=self.registry,
        )
        # disaggregated prefill/decode serving (docs/disaggregation.md):
        # KV-page migration hops between role-specialized replicas
        self.llm_pool_migrations = Counter(
            "mcpforge_llm_pool_migrations_total",
            "Prefill->decode KV-page migration hops (outcome: ok = decode "
            "continued on the target, degraded = decode-in-place fallback)",
            ["from", "to", "outcome"], registry=self.registry,
        )
        self.llm_pool_migration_seconds = Histogram(
            "mcpforge_llm_pool_migration_seconds",
            "Wall time of one KV-page migration hop (export + verify + "
            "re-dispatch)",
            registry=self.registry,
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
        )
        self.llm_pool_migration_pages = Counter(
            "mcpforge_llm_pool_migration_pages_total",
            "KV pages moved by migration per stage (spilled off the "
            "prefill replica, restored toward the decode target, degraded "
            "= served in place after a failed hop)",
            ["stage"], registry=self.registry,
        )
        self.llm_pool_migration_bytes = Counter(
            "mcpforge_llm_pool_migration_bytes_total",
            "Serialized KV bytes verified through the tier store during "
            "migration hops",
            registry=self.registry,
        )
        self.llm_providers_wired = Gauge(
            "mcpforge_llm_providers_wired",
            "External LLM providers currently wired into the registry",
            registry=self.registry,
        )
        # --- gateway data-plane flight recorder (gateway/flight_recorder.py,
        # docs/observability.md "Gateway flight recorder & loop health") ---
        # per-request wall time split into attributed phases (edge
        # middleware pre-work, authn, plugin pipeline, db, engine
        # handoff, serialization, handler residue, error residue) — the
        # gateway twin of mcpforge_llm_step_phase_seconds
        self.gw_request_phase = Histogram(
            "mcpforge_gw_request_phase_seconds",
            "Gateway request wall time attributed to a phase "
            "(edge, auth, plugins, routing, db, engine, serialize, "
            "handler, error)",
            ["route", "phase", "tenant"], registry=self.registry,
            buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                     0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
        )
        # slow requests past gw_slow_request_ms — the counter twin of the
        # phase-vector warning log line
        self.gw_slow_requests = Counter(
            "mcpforge_gw_slow_requests_total",
            "Requests slower than the configured gw_slow_request_ms "
            "threshold (each also logs its phase vector)",
            ["route"], registry=self.registry,
        )
        # event-loop health: how late the loop ran a timer that asked
        # for gw_loop_lag_interval_s — sustained mass in the upper
        # buckets means a callback is blocking the loop (the runtime
        # complement of mcpforge-lint's static async-blocking rule).
        # Per-worker by construction: each process owns its registry.
        self.gw_loop_lag = Histogram(
            "mcpforge_gw_loop_lag_seconds",
            "Scheduled-callback delta of the gateway event loop "
            "(per worker; lag = blocked-loop time)",
            registry=self.registry,
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0),
        )
        # engine/pool admission saturation (0..1) as seen by the HTTP
        # tier — the value behind the X-Queue-Depth / Retry-After
        # backpressure headers (ROADMAP item 5's pool→HTTP wiring)
        self.gw_engine_saturation = Gauge(
            "mcpforge_gw_engine_saturation",
            "Engine admission-queue saturation the gateway last surfaced "
            "to clients (queued work / admission capacity, 0..1)",
            registry=self.registry,
        )
        # --- per-tenant usage metering (observability/metering.py,
        # docs/multitenancy.md) --- exported views of the TenantLedger;
        # every tenant label below rides the registry's clamp, so the
        # child set is bounded at tenant_label_clamp + 1 ("other")
        self.llm_tenant_tokens = Counter(
            "mcpforge_llm_tenant_tokens_total",
            "Tokens accounted to a tenant by the metering ledger "
            "(kind: prompt|generated|cache_hit; cache_hit = prefill "
            "tokens served from shared prefix-cache pages)",
            ["tenant", "kind"], registry=self.registry,
        )
        self.llm_tenant_kv_page_seconds = Counter(
            "mcpforge_llm_tenant_kv_page_seconds_total",
            "KV-page-seconds of HBM residency accounted to a tenant "
            "(pages held x seconds resident, summed at request retire)",
            ["tenant"], registry=self.registry,
        )
        self.gw_tenant_quota_used_ratio = Gauge(
            "mcpforge_gw_tenant_quota_used_ratio",
            "Fraction of the per-tenant token quota consumed in the "
            "current rollup window (0 when no quota is configured) — "
            "the admission signal the distributed rate limiter reads",
            ["tenant"], registry=self.registry,
        )
        # --- fault-injection plane + degradation ladder
        # (observability/faults.py, observability/degradation.py,
        # docs/resilience.md) ---
        # every fault an armed rule injected, by point and kind — the
        # chaos matrix gates on "the fault actually fired" so a scenario
        # whose fault never armed cannot pass vacuously
        self.faults_injected = Counter(
            "mcpforge_faults_injected_total",
            "Faults injected by the fault plane, by fault point and kind "
            "(error, latency, corrupt); only counts when "
            "fault_injection_enabled is set and a rule fired",
            ["point", "kind"], registry=self.registry,
        )
        # per-component breaker state: 0 closed (healthy), 1 half-open
        # (probing recovery), 2 open (degraded path active). Components:
        # tier.disk, federation (worst peer), ledger.rollup, llm.overload
        self.degradation_state = Gauge(
            "mcpforge_degradation_state",
            "Degradation-ladder state per component (0=closed, "
            "1=half_open, 2=open); multi-member components report their "
            "worst member",
            ["component"], registry=self.registry,
        )
        # admission-time load shedding on the LLM surface: 429 +
        # Retry-After, lowest SLO class first (docs/resilience.md)
        self.gw_requests_shed = Counter(
            "mcpforge_gw_requests_shed_total",
            "LLM-surface requests shed with 429 + Retry-After, by the "
            "tenant's SLO class and cause (overload = saturation past "
            "the class's shed bar, quota = tenant window exhausted)",
            ["slo_class", "reason"], registry=self.registry,
        )
        # --- multi-worker scale-out (docs/scaleout.md) ---
        # cross-worker session handoff outcomes: an SSE stream or elicit
        # request landing on a non-owning worker is relayed to the owner
        # over the bus RPC seam (kind: stream|elicit); stream_lost
        # counts relays terminated because the OWNING worker died
        # mid-stream (clean EOF to the client, loss counted — never a
        # hang), refused counts the 409 fallback when no owner answers
        self.gw_session_handoffs = Counter(
            "mcpforge_gw_session_handoffs_total",
            "Cross-worker session handoffs by outcome (stream / elicit "
            "served via the owning worker; stream_lost = owner died "
            "mid-relay; refused = the 409 fallback)",
            ["kind"], registry=self.registry,
        )
        self.sessions_active = Gauge(
            "mcpforge_sessions_active", "Active MCP sessions", registry=self.registry,
        )
        self.client_disconnects = Counter(
            "mcpforge_client_disconnects_total",
            "Requests whose client went away mid-flight",
            registry=self.registry,
        )
        # --- OTLP export health (observability/otlp.py): a collector
        # outage used to log at debug and silently drop the batch; the
        # exporter now retries with backoff and accounts every span's
        # fate here, so "traces stopped arriving" is a dashboard fact
        # rather than a grep through debug logs
        self.otel_spans_exported = Counter(
            "mcpforge_otel_spans_exported_total",
            "Spans successfully delivered to the OTLP collector",
            registry=self.registry,
        )
        self.otel_spans_dropped = Counter(
            "mcpforge_otel_spans_dropped_total",
            "Spans dropped by the OTLP exporter, by cause (buffer_full, "
            "rejected = collector 4xx, retry_exhausted, shutdown = "
            "undeliverable at process exit)",
            ["reason"], registry=self.registry,
        )
        # --- closed-loop serving controller (tpu_local/controller.py,
        # docs/controller.md) --- every knob move is a counted, labeled
        # event; the knob gauges mirror the CURRENT actuated posture so
        # a dashboard can overlay knob position on the signals that
        # drove it
        self.controller_decisions = Counter(
            "mcpforge_controller_decisions_total",
            "Serving-controller knob decisions, by knob (superstep, "
            "spec, shed_bar) and direction (up, down, on, "
            "off, hold_rejected = the engine refused the staged value)",
            ["knob", "direction"], registry=self.registry,
        )
        self.controller_knob = Gauge(
            "mcpforge_controller_knob",
            "Current serving-knob posture per replica (superstep = "
            "active K, spec = 0/1, "
            "shed_bar = OverloadShedder shed_at; gateway-scope knobs "
            "use replica '-')",
            ["knob", "replica"], registry=self.registry,
        )
        # exemplar bucket registration: the ledger places an observed
        # value into its bucket without re-deriving prometheus internals
        # (docs/observability.md "Request forensics & exemplars")
        for attr in ("llm_ttft", "llm_tpot", "llm_queue_wait",
                     "http_duration"):
            metric = getattr(self, attr)
            self.exemplars.register(attr, metric._upper_bounds)

    def exemplar(self, metric: str, value: float, trace_id: str | None,
                 labels: tuple = ()) -> dict[str, str] | None:
        """The exemplar dict for ``Histogram.observe(value, exemplar=)``
        — None when exemplars are off or the request is unattributed.
        Also pins ``trace_id`` in the trace store's retention set via
        the shared :class:`~.trace_store.ExemplarLedger`. ``labels``
        must be the SAME label values the ``.labels(...)`` child was
        selected with: prometheus stores exemplars per labeled child,
        so a label-blind ledger cell would let tenant B's observe unpin
        tenant A's trace while A's bucket line still renders it — a
        dangling click-through."""
        try:
            return self.exemplars.note(metric, value, trace_id, labels)
        except Exception:
            return None  # telemetry must never break an observe site

    def watch_gc(self, watch) -> None:
        """Expose the gc watch an engine's timeline feeds (once a registry,
        however many engines share it)."""
        if self._gc_collector is None:
            self._gc_collector = _GcPauseCollector(watch)
            self.registry.register(self._gc_collector)

    def render(self, accept: str = "") -> tuple[bytes, str]:
        """Exposition bytes + content type. A scraper that negotiates
        OpenMetrics (``Accept: application/openmetrics-text``) gets the
        exemplar-bearing format; everyone else keeps the classic text
        format (exemplars are syntactically illegal there)."""
        if "application/openmetrics-text" in (accept or ""):
            return (openmetrics.generate_latest(self.registry),
                    openmetrics.CONTENT_TYPE_LATEST)
        return generate_latest(self.registry), CONTENT_TYPE_LATEST
