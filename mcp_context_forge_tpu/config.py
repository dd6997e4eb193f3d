"""Environment-driven settings.

Capability parity with the reference's ~300-field pydantic-settings ``Settings``
(`/root/reference/mcpgateway/config.py:187`), rebuilt without the
pydantic-settings dependency: a plain pydantic v2 model hydrated from the
process environment (prefix ``MCPFORGE_`` or the bare field name, reference-
compatible) plus an optional ``.env`` file. Security posture carried over:
startup fails hard on weak/default secrets unless explicitly in dev mode
(reference `config.py` validate_security_configuration, wired at
`main.py:1583`).
"""

from __future__ import annotations

import logging
import os
from functools import lru_cache
from pathlib import Path
from typing import Any, Literal

from pydantic import BaseModel, Field, field_validator

_WEAK_SECRETS = {
    "", "changeme", "secret", "password", "my-test-key", "mysecretkey",
    "default", "admin", "test", "jwt-secret", "dev-only-do-not-use",
}


class Settings(BaseModel):
    """Gateway + engine configuration. Every field is env-overridable."""

    # --- identity / serving ---
    app_name: str = "MCP Context Forge TPU"
    host: str = "0.0.0.0"
    port: int = 4444
    environment: Literal["development", "production"] = "development"
    app_domain: str = "http://localhost:4444"
    dev_mode: bool = True

    # --- persistence ---
    database_url: str = "sqlite:///./mcpforge.db"
    db_pool_size: int = 8

    # --- coordination (reference: Redis; here: pluggable bus) ---
    # memory: one process; file: N workers one host; tcp: cross-host hub
    bus_backend: Literal["memory", "file", "tcp"] = "memory"
    bus_dir: str = "/tmp/mcpforge-bus"
    bus_tcp_host: str = "127.0.0.1"
    bus_tcp_port: int = 7077
    bus_tcp_serve: bool = False  # this worker also hosts the hub
    bus_tcp_secret: str = ""     # hub auth; empty = fall back to jwt secret
    leader_lease_ttl: float = 15.0

    # --- multi-worker gateway scale-out (supervisor.py, coordination/rpc.py,
    # docs/scaleout.md) ---
    # informational worker count + index, stamped by the supervisor per
    # worker; no code path reads them back — they surface through the
    # diagnostics settings.json dump for per-worker bundle attribution
    gw_workers: int = 1    # lint: allow[config-key-liveness] supervisor-stamped identity, surfaced via diagnostics settings.json
    worker_index: int = 0  # lint: allow[config-key-liveness] supervisor-stamped identity, surfaced via diagnostics settings.json
    # all workers bind ONE listening port with SO_REUSEPORT (the kernel
    # spreads accepts); off = the legacy port-per-worker layout
    gw_reuse_port: bool = False
    # listen(2) backlog: the aiohttp default of 128 resets connections
    # under a 10k-concurrent open-loop burst before a worker ever sees
    # them; sized for the scale-out posture
    gw_listen_backlog: int = 1024
    # event-loop policy for the serving process: "" / "asyncio" = stdlib
    # loop; "uvloop" = opt-in libuv loop when the package is importable,
    # FALLING BACK to asyncio with a warning when it is not (the serving
    # image does not bake uvloop in; the knob must never be a boot error)
    gw_event_loop: str = ""
    # cross-worker session handoff: an SSE stream or elicit request
    # landing on a non-owning worker is served over the bus RPC seam
    # instead of refused (the 409 survives only as the fallback when the
    # owner is unreachable)
    gw_session_handoff: bool = True
    gw_rpc_timeout_s: float = 30.0
    # streaming RPC idle bar: no chunk for this long triggers an owner
    # liveness check (dead owner => clean termination, never a hang)
    gw_stream_idle_timeout_s: float = 15.0
    # per-worker metrics aggregation: each worker publishes its exposition
    # on the bus so /metrics/prometheus?scope=fleet and
    # /admin/slo?scope=fleet report fleet-wide truth from any worker
    gw_fleet_metrics: bool = False
    gw_fleet_metrics_interval_s: float = 2.0
    # --- distributed tenant rate limiter (coordination/ratelimit.py) ---
    # enforce tenant_quota_tokens_per_window against ONE shared counter
    # (hub-backed token bucket) instead of per-worker ledgers: N workers
    # admit at most quota + one bucket burst, never N x quota
    gw_distributed_limiter: bool = True
    # tokens a worker draws from the shared budget per grant — the
    # "one configured bucket burst" of over-admission the limiter allows
    tenant_quota_burst_tokens: int = 2048
    # shared quota window length; 0 = inherit the rollup interval (the
    # window behind mcpforge_gw_tenant_quota_used_ratio)
    tenant_quota_window_s: float = 0.0
    # how often each worker reconciles ledger actuals into the shared
    # counter (the conservation-gated signal the limiter consumes)
    tenant_limiter_sync_interval_s: float = 0.25
    # --- shared engine plane (tpu_local/pool_rpc.py): ONE worker owns
    # the EnginePool (leader-elected via the coordination leases); the
    # others serve LLM traffic through the bus RPC seam without
    # duplicating HBM state. Requires a cross-process bus backend.
    tpu_local_pool_shared: bool = False

    # --- MCP Apps (ui:// AppBridge, reference main.py:10508) ---
    mcp_apps_enabled: bool = True
    mcp_apps_session_ttl: float = 300.0

    # --- auth ---
    auth_required: bool = True
    jwt_secret_key: str = "dev-only-do-not-use"
    jwt_algorithm: Literal["HS256", "HS384", "HS512"] = "HS256"
    jwt_audience: str = "mcpforge-api"
    jwt_issuer: str = "mcpforge"
    token_expiry: int = 10080  # minutes
    basic_auth_user: str = "admin"
    basic_auth_password: str = "changeme"
    platform_admin_email: str = "admin@example.com"
    platform_admin_password: str = "changeme"
    auth_encryption_secret: str = "dev-only-do-not-use"
    # password policy for local accounts (reference
    # services/password_policy_service.py)
    password_min_length: int = 12
    password_require_uppercase: bool = True
    password_require_lowercase: bool = True
    password_require_digit: bool = True
    password_require_special: bool = False
    password_max_length: int = 256  # argon2 DoS guard

    # --- HTTP edge (reference middleware stack) ---
    trust_proxy_headers: bool = False     # honor X-Forwarded-* from the LB
    max_header_bytes: int = 32768         # 431 above this (0 = unlimited)
    cors_allowed_origins: str = ""        # csv; "*" = any; "" = CORS off

    # --- auth resolution cache (reference auth_cache_* family): resolve_*
    # re-reads users/teams/roles per request; short TTLs bound staleness
    # and explicit invalidation (role grants, membership changes, toggles)
    # keeps the must-be-immediate paths immediate ---
    auth_cache_enabled: bool = True
    auth_cache_user_ttl: float = 30.0
    auth_cache_teams_ttl: float = 30.0
    auth_cache_role_ttl: float = 30.0
    auth_cache_revocation_ttl: float = 30.0
    auth_cache_max_entries: int = 4096

    # --- CSRF / session protections (reference csrf_middleware.py +
    # password_change_enforcement.py) ---
    csrf_enabled: bool = True
    csrf_trusted_origins_csv: str = ""   # extra allowed Origin values
    csrf_token_ttl_s: float = 8 * 3600.0
    csrf_cookie_name: str = "csrf_token"
    csrf_header_name: str = "X-CSRF-Token"
    csrf_cookie_secure: bool = False     # set true behind TLS
    csrf_exempt_paths_csv: str = ""      # exact-or-prefix exemptions
    # fail-closed Origin/Referer requirement for ambient-credential
    # mutations (reference csrf_check_referer): off by default — it
    # rejects non-browser basic-auth clients that send neither header
    csrf_check_referer: bool = False
    password_change_enforcement_enabled: bool = True
    # bootstrap admin must rotate the seed password before using the
    # surface (reference admin_require_password_change_on_bootstrap)
    admin_require_password_change_on_bootstrap: bool = False
    # --- token usage accounting (reference token_usage_middleware.py) ---
    token_usage_logging_enabled: bool = True
    token_usage_log_retention: int = 10000   # rows kept per maintenance pass
    # --- DB query logging (reference middleware/db_query_logging.py) ---
    db_query_logging: bool = False
    db_query_logging_slow_ms: float = 100.0  # WARN above this per query
    db_query_n1_threshold: int = 3           # same-shape repeats => suspect

    # --- protocol / transports ---
    protocol_version: str = "2025-06-18"
    supported_protocol_versions_csv: str = "2025-06-18,2025-03-26,2024-11-05"
    streamable_http_stateful: bool = False
    sse_keepalive_interval: float = 30.0
    session_ttl: int = 3600
    websocket_ping_interval: float = 20.0

    # --- limits / validation (reference validation_* family,
    # config.py: validation_max_name_length .. validation_max_tag_length;
    # enforced centrally on every create/update body in routers._body) ---
    max_request_size_bytes: int = 8 * 1024 * 1024
    max_header_bytes: int = 64 * 1024
    max_header_count: int = 128            # 431 past this many fields
    max_header_field_bytes: int = 16384    # 431 past this per-field size
    rate_limit_rps: int = 0  # 0 = disabled
    rate_limit_burst: int = 200
    validation_max_name_length: int = 255
    validation_max_description_length: int = 8192
    validation_max_url_length: int = 2048
    validation_max_tag_length: int = 64
    validation_max_tags: int = 32
    max_prompt_size: int = 1024 * 1024
    max_resource_size: int = 4 * 1024 * 1024

    # --- team governance (reference allow_team_* family) ---
    allow_team_creation: bool = True
    allow_team_invitations: bool = True
    allow_public_visibility: bool = True
    default_team_member_role: str = "member"
    invitation_expiry_hours: float = 72.0
    # --- SSO provisioning policy (reference sso_* long tail) ---
    sso_trusted_domains_csv: str = ""     # ""=any; else allowlist
    sso_require_admin_approval: bool = False  # provision deactivated
    sso_auto_admin_domains_csv: str = ""  # domains granted is_admin
    # --- API token policy ---
    api_token_max_lifetime_minutes: float = 0.0  # 0 = unlimited
    # --- outbound/identity plumbing ---
    auth_header_name: str = "authorization"  # custom ingress auth header
    # --- correlation ids (reference correlation_id_* family) ---
    correlation_id_header: str = "x-correlation-id"
    correlation_id_response_header: str = "x-correlation-id"
    correlation_id_preserve: bool = True  # honor inbound ids; else mint
    # --- DB resilience (reference db_* tuning family) ---
    db_sqlite_busy_timeout_ms: int = 10000
    db_max_retries: int = 3               # on SQLITE_BUSY/locked
    db_retry_interval_ms: float = 50.0
    # --- content validation (reference content_* family) ---
    allowed_resource_mime_types_csv: str = ""  # ""=any
    # --- metrics retention ---
    metrics_retention_hours: float = 24.0
    # --- admin stats cache (reference admin_stats_cache_*) ---
    admin_stats_cache_enabled: bool = False
    admin_stats_cache_ttl_s: float = 5.0
    # --- performance tracking (reference performance_tracker.py +
    # performance_threshold_* family; thresholds in ms) ---
    performance_tracking_enabled: bool = True
    performance_max_samples: int = 512
    performance_threshold_database_query_ms: float = 100.0
    performance_threshold_http_request_ms: float = 1000.0
    performance_threshold_tool_invocation_ms: float = 5000.0
    performance_threshold_resource_read_ms: float = 500.0
    performance_degradation_multiplier: float = 2.0
    # --- support bundle (reference support_bundle_service.py) ---
    support_bundle_enabled: bool = True
    support_bundle_log_tail: int = 1000
    # --- hot/cold gateway classification (reference
    # server_classification_service.py + hot_cold_classification_enabled;
    # gated health polling for large federations) ---
    hot_cold_classification_enabled: bool = False
    hot_cold_hot_cap: int = 50
    hot_cold_hot_window_s: float = 3600.0
    hot_cold_cold_poll_multiplier: int = 5
    # --- SMTP email notifications (reference smtp_* family +
    # email_notification_service.py) ---
    smtp_enabled: bool = False
    smtp_host: str = ""
    smtp_port: int = 587
    smtp_user: str = ""
    smtp_password: str = ""
    smtp_from_email: str = "noreply@localhost"
    smtp_from_name: str = "MCP Gateway"
    smtp_use_tls: bool = True     # STARTTLS on a plain connection
    smtp_use_ssl: bool = False    # implicit TLS (SMTPS, port 465)
    smtp_timeout_seconds: float = 10.0
    account_lockout_notification_enabled: bool = False
    team_invitation_email_enabled: bool = True  # only fires when smtp is on
    # --- password reset (reference password_reset_* family) ---
    password_reset_enabled: bool = False
    password_reset_token_expiry_minutes: float = 60.0
    password_reset_rate_limit: int = 3          # requests per window/email
    password_reset_rate_window_minutes: float = 60.0
    password_reset_min_response_ms: float = 100.0  # user-enumeration guard
    password_reset_invalidate_sessions: bool = True
    # --- chat agent ---
    llmchat_max_steps: int = 6
    # --- CORS detail (reference cors long tail) ---
    cors_allowed_methods_csv: str = "GET,POST,PUT,DELETE,OPTIONS"
    cors_allowed_headers_csv: str = ("authorization,content-type,"
                                     "mcp-protocol-version,mcp-session-id,"
                                     "x-correlation-id,x-csrf-token")
    cors_max_age_s: int = 600

    # --- per-entity caps (reference max_teams_per_user /
    # max_members_per_team / mcpgateway_a2a_max_agents /
    # mcpgateway_bulk_import_max_tools; 0 = unlimited) ---
    max_teams_per_user: int = 50
    max_members_per_team: int = 100
    a2a_max_agents: int = 100
    bulk_import_max_entities: int = 1000

    # --- pagination (reference pagination_* family) ---
    pagination_default_page_size: int = 50
    pagination_max_page_size: int = 500
    pagination_min_page_size: int = 1
    pagination_include_links: bool = False  # RFC 8288-style next link
    # --- baggage propagation (reference otel_baggage_* family) ---
    otel_baggage_enabled: bool = False
    otel_baggage_max_items: int = 10
    otel_baggage_max_size_bytes: int = 1024
    # "header=baggage.key" pairs, e.g. "x-tenant-id=tenant.id"
    otel_baggage_header_mappings_csv: str = ""
    # --- endpoint deprecation (reference middleware/deprecation.py +
    # legacy_api_* family; RFC 8594 Sunset) ---
    deprecated_path_prefixes_csv: str = ""
    legacy_api_sunset_date: str = ""   # e.g. "Sat, 31 Dec 2026 23:59:59 GMT"
    # --- registry list cache (reference registry_cache_* family):
    # TTL-cached list endpoints, bus-invalidated on entity changes ---
    registry_cache_enabled: bool = False
    registry_cache_default_ttl_s: float = 30.0
    registry_cache_tools_ttl_s: float = 30.0  # lint: allow[config-key-liveness] read via f-string getattr in gateway/registry_cache.py
    registry_cache_resources_ttl_s: float = 30.0  # lint: allow[config-key-liveness] read via f-string getattr in gateway/registry_cache.py
    registry_cache_prompts_ttl_s: float = 30.0  # lint: allow[config-key-liveness] read via f-string getattr in gateway/registry_cache.py
    registry_cache_servers_ttl_s: float = 30.0  # lint: allow[config-key-liveness] read via f-string getattr in gateway/registry_cache.py
    registry_cache_gateways_ttl_s: float = 30.0  # lint: allow[config-key-liveness] read via f-string getattr in gateway/registry_cache.py
    # --- SSRF guard for catalog URLs (reference ssrf_* family) ---
    ssrf_protection_enabled: bool = False  # off: localhost upstreams are
                                           # the common single-host posture
    ssrf_allow_localhost: bool = True
    ssrf_allow_private_networks: bool = True
    ssrf_blocked_hosts_csv: str = ""
    ssrf_allowed_networks_csv: str = ""    # explicit allow beats all blocks
    ssrf_blocked_networks_csv: str = ""
    ssrf_dns_fail_closed: bool = True
    # --- file logging + rotation (reference log_to_file/log_rotation_*) ---
    log_to_file: bool = False
    log_folder: str = "logs"
    log_file: str = "mcpforge.log"
    log_rotation_enabled: bool = False
    log_max_size_mb: float = 1.0
    log_backup_count: int = 5

    # --- outbound invocation ---
    tool_timeout: float = 60.0
    # outbound REST pool sizing (reference: httpx limits / aiohttp connector
    # knobs). per_host=0 = unlimited per host: a gateway fronting ONE busy
    # upstream must not self-throttle below its own concurrency (the global
    # cap still bounds sockets)
    outbound_pool_limit: int = 1024
    outbound_pool_limit_per_host: int = 0
    max_tool_retries: int = 3
    retry_base_delay: float = 0.25
    retry_max_delay: float = 8.0
    gateway_health_interval: float = 60.0
    gateway_failure_threshold: int = 3
    max_concurrent_health_checks: int = 10  # health-loop fan-out bound
    federation_timeout: float = 30.0
    # wizard dry-run probe bound (reference gateway_validation_timeout)
    gateway_validation_timeout: float = 10.0
    skip_ssl_verify: bool = False
    # outbound HTTP pool shaping (reference httpx_* family)
    http_max_connections: int = 512
    http_max_keepalive: int = 128
    http_connect_timeout: float = 10.0
    # --- TLS: serving + outbound contexts (reference ssl_context_cache,
    # utils/ssl_context_cache; contexts are built once per distinct
    # (ca, cert, key) triple and cached — building one per request costs
    # ~10 ms and re-reads the bundle from disk) ---
    ssl_enabled: bool = False     # serve HTTPS (cert+key below)
    ssl_cert_file: str = ""
    ssl_key_file: str = ""
    ssl_ca_bundle: str = ""       # custom CA bundle for OUTBOUND verification
    # upstream MCP session pooling (reference session registry caps)
    upstream_max_sessions: int = 128
    upstream_idle_ttl: float = 300.0
    # external (out-of-process) plugin servers
    external_plugin_timeout: float = 10.0
    # gRPC translation: streamed-RPC tool results are bounded collections
    # (reference mcpgateway_grpc_max_message_size family)
    grpc_max_stream_messages: int = 256

    # --- account lockout (reference email_auth lockout policy) ---
    auth_max_failed_attempts: int = 5
    auth_lockout_seconds: float = 300.0

    # --- admin log search ring buffer ---
    log_buffer_capacity: int = 5000

    # --- plugins ---
    plugins_enabled: bool = True
    plugin_config_file: str = "plugins/config.yaml"

    # --- observability ---
    otel_enable: bool = True
    otel_exporter: Literal["none", "console", "otlp", "memory"] = "memory"
    otel_db_store: bool = True           # persist notable spans to the DB
    otel_db_min_duration_ms: float = 50  # slow-span threshold (errors always kept)
    otel_service_name: str = "mcpforge"
    otel_otlp_endpoint: str = ""   # e.g. http://collector:4318 (OTLP/HTTP)
    otel_otlp_headers: str = ""    # JSON object of extra headers
    # transient OTLP delivery failures retry with exponential backoff
    # this many times before the batch drops (counted in
    # mcpforge_otel_spans_dropped_total{reason="retry_exhausted"})
    otel_otlp_retry_max: int = 3
    # --- request forensics plane (observability/trace_store.py,
    # docs/observability.md "Request forensics & exemplars") ---
    # in-process tail-sampled trace store behind GET /admin/trace/{id}:
    # keeps every error trace, every SLO-breaching trace, the slowest-N
    # per route/tenant, exemplar-pinned traces, and a deterministic
    # 1-in-M sample of the rest, bounded at trace_store_max_traces
    trace_store_enabled: bool = True
    trace_store_max_traces: int = 512
    trace_store_max_spans: int = 256
    trace_store_sample_every: int = 32       # 0 = no background sample
    trace_store_slowest_per_key: int = 4     # per route AND per tenant
    # rootless traces (engine driven without a gateway span) finalize
    # after this idle window instead of leaking in the open table
    trace_store_idle_finalize_s: float = 30.0
    # per-bucket trace-id exemplars on the TTFT/TPOT/queue-wait/http
    # histograms, exported in OpenMetrics syntax when the scraper
    # negotiates it (Accept: application/openmetrics-text)
    metrics_exemplars: bool = True
    jax_profile_dir: str = "/tmp/mcpforge-jaxprof"  # /admin/engine/profile sink
    # opt-in production profiler capture: the /admin/engine/profile*
    # endpoints (duration capture + start/stop) 404 unless enabled —
    # profiling writes device traces to disk and stalls the runtime, so
    # a fleet operator must turn it on deliberately
    jax_profile_enabled: bool = False
    log_level: str = "INFO"
    log_json: bool = False
    # rollup cadence (renamed from the misleading
    # metrics_buffer_flush_interval — it drives ROLLUPS, in minutes)
    metrics_rollup_interval_minutes: float = 5.0
    # --- metrics write buffer (reference metrics_buffer_service.py):
    # hot-path invocations append in memory; one executemany per flush ---
    metrics_buffer_enabled: bool = True
    metrics_buffer_max_size: int = 500
    metrics_buffer_flush_interval_s: float = 1.0

    # --- LLM / tpu_local ---
    llm_api_prefix: str = "/v1"
    tpu_local_enabled: bool = True
    tpu_local_model: str = "llama3-tiny"  # llama3-8b on real v5e-8
    tpu_local_checkpoint: str = ""  # orbax/safetensors dir; empty = random init
    tpu_local_max_batch: int = 64
    tpu_local_max_seq_len: int = 2048
    tpu_local_page_size: int = 128
    tpu_local_num_pages: int = 512
    tpu_local_prefill_buckets: tuple[int, ...] = (128, 512, 2048)  # padded dense prefill lengths, at every width; each also gets a width-1 program at half its length for lone short prompts
    tpu_local_prefill_max_batch: int = 4  # admissions fused into one prefill
    tpu_local_mesh_shape: str = ""  # 'DxM' (e.g. 1x8 on v5e-8); '' = auto (1 x all devices)
    tpu_local_sp_impl: Literal["none", "ring", "ulysses"] = "none"
    tpu_local_sp_threshold: int = 1024  # prefill BUCKETS > this use SP prefill
    # K-step decode super-steps (token-loop fusion): one jitted on-device
    # loop runs K decode iterations — fused sampling, in-loop paged-KV
    # append, per-slot budget/EOS masking freezing finished rows — and
    # the host syncs once per K tokens. Raise on host-dispatch-bound TPU
    # decode (8-16);
    # trade: up to K-1 tokens of lookahead compute waste past EOS, and
    # admissions wait out the in-flight super-step (TTFT vs throughput).
    tpu_local_superstep: int = 1
    # depth-2 overlapped decode pipeline: step N+1 dispatches fed by step
    # N's on-device sampled tokens while N's results transfer and emit one
    # step behind — host bookkeeping hides behind device execution. Drain
    # barriers keep token streams identical to the serial path; disable
    # only to A/B or to debug scheduling.
    tpu_local_decode_overlap: bool = True
    tpu_local_dtype: str = "bfloat16"
    tpu_local_embedding_model: str = "encoder-tiny"
    # backend-init watchdog: a wedged accelerator runtime (a chip another
    # process holds) can block jax.devices() forever; past this budget the
    # engine raises EngineInitTimeout so the gateway FAILS instead of never
    # binding its port — it never moves to another platform (0 = no watchdog)
    tpu_local_init_timeout_s: float = 120.0
    # precompile the full shape grid (prefill buckets x pow-2 admission
    # batches + decode block) at boot so first traffic never pays XLA
    # compile latency (~20-40s/shape on TPU); off by default because it
    # lengthens gateway boot
    tpu_local_warmup: bool = False
    # warmup grid scope: 'full' (no mid-traffic compiles ever) or 'fast'
    # (cold-TPU-friendly subset; a rare cache miss pays one compile)
    tpu_local_warmup_mode: Literal["full", "fast"] = "full"
    # prefix cache: resident KV pages of shared full-page prompt prefixes
    # are reused across requests, so repeated plugin/chat templates only
    # prefill their suffix (vLLM automatic-prefix-caching analog)
    tpu_local_prefix_cache: bool = True
    # tiered prefix/KV cache (docs/kv_tiering.md): evicted prefix pages
    # spill HBM -> bounded host RAM (int8 + scales; quantize-on-spill for
    # bf16 pools) -> bounded disk (async write-behind), and admission
    # restores tier-resident pages on match (fetch-on-miss). Under a
    # replica pool the store + prefix index are shared by every replica,
    # so a prefix prefilled anywhere serves hits everywhere. Requires
    # tpu_local_prefix_cache.
    tpu_local_prefix_tiers: bool = False
    tpu_local_tier_host_bytes: int = 256 * 1024 * 1024
    tpu_local_tier_disk_bytes: int = 1024 * 1024 * 1024
    tpu_local_tier_disk_dir: str = ""  # "" = private tempdir per store
    # spill storage for full-precision pools: "int8" (default; 2-4x
    # cheaper tiers, restored pages carry resident-int8-grade greedy
    # drift) or "" for resident-precision spills (lossless round trip).
    # int8-resident pools always spill verbatim (bit-exact).
    tpu_local_tier_spill_quant: str = "int8"
    # cross-host prefix-cache fabric (docs/cache_fabric.md): a T3
    # object-store hop below disk shared by EVERY host pointed at the
    # same URL — "file://<dir>" (shared directory) or "gcs://<bucket>
    # [/prefix]" (optional google-cloud-storage dep; a missing client
    # refuses at startup, T3 simply stays off). "" disables the fabric.
    tpu_local_tier_object_url: str = ""
    # tenant namespace segment every object key is qualified by —
    # namespaces are mutually invisible AND mutually unreachable (the
    # key embeds the namespace)
    tpu_local_fabric_namespace: str = "shared"
    # gossip cadence + entry lifetime for fabric adverts: each host
    # advertises its object-resident chains every interval; an entry a
    # peer merged expires ttl seconds after its last refresh
    tpu_local_fabric_advert_interval_s: float = 2.0
    tpu_local_fabric_advert_ttl_s: float = 300.0
    # cross-supervisor peers: comma-separated base URLs (e.g.
    # "http://hostb:4444") whose POST /admin/fabric/adverts we gossip
    # with; in-fleet workers ride the bus (fabric.advert) automatically
    tpu_local_fabric_peers: str = ""
    # speculative decoding via prompt-lookup (n-gram) drafting: verify k
    # drafted tokens per dispatch — decode is bandwidth-bound, so accepted
    # drafts are nearly free. Greedy requests only; off by default.
    tpu_local_spec_decode: bool = False
    tpu_local_spec_k: int = 4
    tpu_local_spec_ngram: int = 2
    # weight-only quantization: "" (full precision) or "int8" — per-channel
    # scales, dequant fused into the matmul; halves HBM footprint+traffic
    # (how Llama-3-8B fits one 16 GB v5e chip)
    tpu_local_quant: str = ""
    # KV-cache quantization: "" (pages in the engine dtype) or "int8" —
    # pages store int8 with per-page, per-kv-head scales, halving
    # decode-attention HBM traffic; at the byte budget tpu_local_num_pages
    # denotes, the pool holds ~2x the pages (kv/paged_cache.py)
    tpu_local_kv_quant: str = ""
    tpu_local_moe_impl: str = ""  # ""=model default | dense | grouped | grouped_pallas
    # moderation classify granularity: texts longer than the window are
    # scored over fixed windows (max-pooled) — 'full' strides the whole
    # text (bounded by max_windows; the default covers 1024 tokens, a
    # superset of the old single-row 512-token scan), 'sample' scores
    # head+tail only (cheapest, weakest)
    tpu_local_classify_window: int = 128
    tpu_local_classify_coverage: str = "full"
    tpu_local_classify_max_windows: int = 8
    tpu_local_classify_cache_size: int = 8192
    # encoder microbatch coalescing (embed/classify traffic)
    tpu_local_encoder_max_batch: int = 32
    tpu_local_encoder_max_wait_ms: float = 2.0
    # smallest encoder seq bucket: moderation texts are ~20 tokens, and
    # padding every row to 64 doubles the classify forward for nothing
    tpu_local_encoder_min_seq: int = 32
    # engine admission queue bound (backpressure past this)
    tpu_local_max_queue: int = 1024
    # device-fault recovery: crashed dispatch thread rebuilds KV, re-queues
    # pending requests and restarts itself (bounded); off = fail fast
    tpu_local_auto_restart: bool = False
    tpu_local_auto_restart_max: int = 3
    # step-introspection ring size (per-dispatch summaries served by
    # GET /admin/engine/steps)
    tpu_local_step_log_size: int = 256
    # --- live roofline (docs/observability.md, "Step attribution, live
    # roofline, and SLOs") ---
    # capture XLA cost_analysis() per warmed executable so live step
    # timing feeds mcpforge_llm_mfu / mcpforge_llm_hbm_roofline_frac
    tpu_local_cost_analysis: bool = True
    # per-chip roofline peaks the live gauges divide by (defaults: v5e)
    tpu_local_peak_tflops_per_chip: float = 197.0
    tpu_local_hbm_gbps_per_chip: float = 819.0
    # --- serving SLOs (GET /admin/slo, observability/slo.py) ---
    # p95 targets per objective; burn rate = fraction of window samples
    # over target / error budget (>1 means the budget is burning down)
    slo_ttft_p95_ms: float = 2500.0
    slo_tpot_p95_ms: float = 250.0
    slo_queue_wait_p95_ms: float = 1500.0
    # gateway-side objective over the HTTP duration histogram (all
    # routes); the load harness asserts it per scenario window
    slo_http_p95_ms: float = 1000.0
    slo_error_budget: float = 0.05
    # --- SLO classes + tenant metering (observability/metering.py,
    # docs/multitenancy.md) ---
    # named target bundles assignable per tenant, JSON object of
    # {"<name>": {"ttft_p95_ms": .., "tpot_p95_ms": .., "http_p95_ms": ..}}
    # (the conceptual slo_class_<name>_{ttft,tpot,http}_p95_ms family);
    # unset fields inherit the flat slo_* defaults. '' = default class only
    slo_classes: str = ""
    # tenant id -> class name, JSON object ({"team:abc": "premium"});
    # unassigned tenants evaluate against the "default" class
    slo_tenant_classes: str = ""
    # per-tenant usage ledger (prompt/generated/cache-hit tokens +
    # KV-page-seconds) fed by the engine at the same sites as its
    # untagged counters, rolled up into the tenant_usage DB table and
    # served at GET /admin/tenants/usage
    tenant_metering_enabled: bool = True
    # bounded-cardinality tenant label: the first N distinct tenants get
    # their own Prometheus label child, the rest clamp to "other" (the
    # exported set never exceeds N+1); size above your tenant count
    tenant_label_clamp: int = 8
    # exact per-tenant ledger rows kept in memory (overflow -> "other")
    tenant_ledger_max_tenants: int = 512
    # async rollup cadence: ledger window -> tenant_usage rows
    tenant_usage_rollup_interval_s: float = 60.0
    # tokens (prompt + generated) a tenant may consume per rollup window
    # before mcpforge_gw_tenant_quota_used_ratio reads >= 1.0 — the
    # saturation signal ROADMAP item 5's distributed rate limiter will
    # enforce; 0 = no quota (gauge stays 0)
    tenant_quota_tokens_per_window: int = 0
    # --- gateway flight recorder & loop health (gateway/flight_recorder.py,
    # docs/observability.md "Gateway flight recorder & loop health") ---
    gw_flight_recorder_enabled: bool = True
    # completed-request ring (recency window) and the slowest-N retained
    # by duration across the worker's lifetime (GET /admin/gateway/requests)
    gw_flight_ring_size: int = 256
    gw_flight_slowest_size: int = 32
    # slow-request bar: past this the request WARNs with its phase
    # vector + trace ids (the r05 "http.request: 3786 ms" line, now with
    # a breakdown); 0 = inherit performance_threshold_http_request_ms
    gw_slow_request_ms: float = 0.0
    # event-loop lag sampler cadence and the long-callback warning bar
    gw_loop_lag_interval_s: float = 0.25
    gw_loop_lag_warn_ms: float = 250.0
    # surface engine admission depth/saturation as X-Queue-Depth +
    # Retry-After response headers on the LLM serving surface, and
    # advise backoff past this saturation fraction
    gw_backpressure_headers: bool = True
    gw_backpressure_retry_after_at: float = 0.8
    # --- fault injection + graceful degradation (observability/faults.py,
    # observability/degradation.py, docs/resilience.md) ---
    # master arm switch for the fault plane: with it UNSET (default) no
    # rule can be installed and every fault point is a single dict-miss
    # no-op (pinned in test); set it for chaos runs / the bench matrix
    fault_injection_enabled: bool = False
    # boot-time rules (JSON array of FaultRule objects) for headless
    # harnesses; runtime arming goes through POST /admin/faults
    fault_rules: str = ""
    # circuit breakers (disk spill tier, federation peers, rollup):
    # consecutive failures before a breaker opens, and how long it stays
    # open before admitting one half-open recovery probe
    degradation_failure_threshold: int = 3
    degradation_cooldown_s: float = 5.0
    # spill-tier disk IO hardening: transient read/write errors retry
    # this many times with jittered backoff before the entry is
    # quarantined (dropped to a clean MISS, counted in
    # mcpforge_llm_prefix_tier_io_errors_total)
    tier_io_retry_max: int = 2
    tier_io_retry_backoff_ms: float = 10.0
    # bounded buffer of rollup windows a DB outage could not flush:
    # beyond this many pending windows the OLDEST drops (loss counted in
    # rollup stats) instead of growing without bound
    tenant_rollup_pending_max: int = 8
    # overload shedding on the LLM chat surface: past this engine
    # saturation the LOWEST SLO class sheds with 429 + Retry-After;
    # gw_shed_class_order (JSON array, lowest first) lists the SHEDDABLE
    # classes — classes not listed never shed on saturation, which is
    # how higher classes hold their targets. '' = no class sheds on
    # saturation (quota shedding still applies when a quota is set)
    gw_shed_enabled: bool = True
    gw_shed_saturation_at: float = 0.95
    gw_shed_class_order: str = ""
    # chat SSE waits up to this long for the FIRST engine chunk before
    # sending response headers: an immediately-refused request (pool
    # capacity gone) gets a clean 503 + Retry-After instead of a 200
    # stream that dies, while a long-TTFT request still gets its
    # headers inside proxy first-byte timeouts (the stream then starts
    # when the first chunk lands). 0 = send headers immediately.
    gw_stream_first_chunk_wait_s: float = 1.0

    # --- closed-loop serving controller (tpu_local/controller.py +
    # observability/signals.py, docs/controller.md) ---
    # master switch: off (default) keeps every serving knob at its
    # frozen-config value — behavior is bit-identical to a build without
    # the controller (the A/B baseline the bench arms compare against)
    controller_enabled: bool = False
    # observe-only mode: signals flow and decisions land in the audit
    # ring/metrics/spans, but NO knob is actually moved — the dry-run
    # posture for qualifying the policy against live traffic
    controller_safe_mode: bool = False
    # signal-bus publication tick and controller evaluation cadence
    controller_tick_s: float = 1.0
    # per-knob cooldown: after a move the knob holds at least this long
    # before the controller may move it again (actuation-settling guard)
    controller_cooldown_s: float = 10.0
    # observed-effect window: each decision's "after" signal snapshot is
    # taken this long after actuation and written back into its ring row
    controller_eval_window_s: float = 5.0
    # hysteresis band: a signal must clear its threshold by this
    # fraction before the controller reverses a prior move (flap guard)
    controller_hysteresis: float = 0.1
    # bounded decision audit ring served at GET /admin/controller
    controller_ring_size: int = 256
    # superstep ladder pre-compiled at warmup: adaptive K only moves
    # along these rungs, so a knob change can never trigger a
    # mid-traffic XLA compile. () = derive {1, superstep} from the
    # static knob (controller off => just the static K: zero extra
    # compiles)
    controller_k_ladder: tuple[int, ...] = ()
    # TTFT-vs-throughput ladder bars: queue-wait p95 above the high bar
    # steps K down (admission latency dominates); device-idle fraction
    # above its bar with queue-wait below the low bar steps K up
    # (host-dispatch-bound; fuse more). Bars in ms / fraction.
    controller_queue_wait_high_ms: float = 500.0
    controller_queue_wait_low_ms: float = 50.0
    controller_idle_frac_high: float = 0.35
    # spec-decode toggle bars: measured acceptance (accepted drafts per
    # verify step, 0..spec_k) below the off bar disables drafting;
    # the controller re-probes (re-enables) after cooldown to re-measure
    controller_spec_accept_off: float = 0.5
    controller_spec_accept_on: float = 1.0
    # dynamic OverloadShedder bars: SLO burn rate above burn_high
    # tightens shed_at toward the floor; burn below burn_low relaxes it
    # toward the configured static bar (gw_shed_saturation_at)
    controller_burn_high: float = 1.0
    controller_burn_low: float = 0.25
    controller_shed_floor: float = 0.5
    controller_shed_step: float = 0.05
    # --- live signal bus (observability/signals.py): bounded per-
    # (signal, replica) windows + EWMA the controller consumes ---
    signal_window: int = 64
    signal_ewma_alpha: float = 0.3

    # --- engine replica pool (tpu_local/pool/, docs/serving_pool.md) ---
    # N > 1 serves LLM traffic from N engine replicas on device-subset
    # meshes (e.g. 2 replicas x 4 chips on a v5e-8) behind an
    # affinity-routing, failover-capable pool; 1 = the single engine,
    # no pool layer at all
    tpu_local_replicas: int = 1
    # routing: prefer the replica whose prefix cache already holds the
    # prompt's prefix (suffix-only prefill there); load balance by least
    # outstanding decode tokens otherwise
    tpu_local_pool_affinity_routing: bool = True
    # health monitor cadence + the heartbeat-staleness bar for declaring
    # a replica wedged (its in-flight requests then requeue onto healthy
    # replicas as continuations)
    tpu_local_pool_health_interval_s: float = 0.5
    tpu_local_pool_heartbeat_timeout_s: float = 10.0
    # failovers allowed per logical request before it errors out
    tpu_local_pool_requeue_max: int = 2
    # disaggregated prefill/decode serving (docs/disaggregation.md):
    # comma-separated role per replica index ("prefill,decode",
    # "prefill,decode,any", ...); "" = every replica serves both phases
    # (the uniform pool, no migration). Roles are free-form strings so a
    # heterogeneous fleet can route by request/SLO class behind the same
    # field; "prefill"/"decode"/"any" carry the phase semantics.
    tpu_local_pool_roles: str = ""
    # prompts at/above this token count class as prefill-heavy when
    # roles are active: they land on a prefill replica, prefill there,
    # then migrate their KV pages to a decode replica
    tpu_local_disagg_prompt_tokens: int = 64
    # routing penalty (in outstanding-token units) for placing a classed
    # request on an "any" replica instead of its exact role — small
    # enough that an oversubscribed prefill tier spills to idle "any"
    # capacity, large enough that exact-role replicas win at parity
    tpu_local_pool_role_penalty_tokens: int = 256

    # --- header passthrough (reference config.py:3489-3499: off by
    # default for security; sensitive headers need per-gateway opt-in) ---
    enable_header_passthrough: bool = False
    default_passthrough_headers: str = "x-tenant-id,x-trace-id"
    # passthrough may REPLACE headers the gateway itself set (auth headers
    # from tool config, content negotiation) — off: gateway wins
    enable_overwrite_base_headers: bool = False
    # allow authorization/cookie through the GLOBAL default list (per-
    # gateway allowlists always may) — reference
    # enable_sensitive_header_passthrough, off for credential hygiene
    enable_sensitive_header_passthrough: bool = False
    # --- response compression (reference SSEAwareCompressMiddleware) ---
    compression_enabled: bool = True
    compression_min_bytes: int = 1024
    # --- host validation: comma-separated allowed Host headers; '' = any
    # (reference forwarded-host validation tier) ---
    allowed_hosts: str = ""
    cors_allow_credentials: bool = False

    # --- well-known files (reference well_known_* family:
    # routers/well_known.py serves robots/security/custom files) ---
    well_known_robots_txt: str = "User-agent: *\nDisallow: /"
    well_known_security_txt: str = ""      # '' = 404
    well_known_custom_files: str = ""      # JSON object {filename: content}
    well_known_cache_max_age: int = 3600

    # --- SSO (JSON list: [{name, issuer, client_id, client_secret}]) ---
    sso_providers: str = ""

    # --- audit / SIEM ---
    siem_export_url: str = ""  # OpenSearch-compatible endpoint; '' = disabled
    audit_enabled: bool = True

    # --- admin / UI ---
    admin_ui_enabled: bool = True

    @field_validator("database_url")
    @classmethod
    def _check_db_url(cls, v: str) -> str:
        if not v.startswith(("sqlite:///", "sqlite+aiosqlite:///",
                             "postgres://", "postgresql://")):
            raise ValueError(
                "database URL must be sqlite:/// or postgresql:// "
                "(reference config.py:14 dual-DB support)")
        return v

    @property
    def is_postgres(self) -> bool:
        return self.database_url.startswith(("postgres://", "postgresql://"))

    @property
    def cors_origins(self) -> set[str]:
        return {o.strip() for o in self.cors_allowed_origins.split(",")
                if o.strip()}

    @property
    def csrf_trusted_origins(self) -> tuple[str, ...]:
        return tuple(o.strip() for o in self.csrf_trusted_origins_csv.split(",")
                     if o.strip())

    @staticmethod
    def _csv(raw: str) -> tuple[str, ...]:
        return tuple(v.strip() for v in raw.split(",") if v.strip())

    @property
    def csrf_exempt_paths(self) -> tuple[str, ...]:
        return self._csv(self.csrf_exempt_paths_csv)

    @property
    def deprecated_path_prefixes(self) -> tuple[str, ...]:
        return self._csv(self.deprecated_path_prefixes_csv)

    @property
    def otel_baggage_header_mappings(self) -> tuple[tuple[str, str], ...]:
        """Parsed (header, baggage-key) pairs."""
        return tuple(tuple(pair.split("=", 1))  # type: ignore[misc]
                     for pair in self._csv(
                         self.otel_baggage_header_mappings_csv)
                     if "=" in pair)

    @property
    def sso_trusted_domains(self) -> tuple[str, ...]:
        return tuple(d.lower() for d in self._csv(self.sso_trusted_domains_csv))

    @property
    def sso_auto_admin_domains(self) -> tuple[str, ...]:
        return tuple(d.lower()
                     for d in self._csv(self.sso_auto_admin_domains_csv))

    @property
    def allowed_resource_mime_types(self) -> tuple[str, ...]:
        return self._csv(self.allowed_resource_mime_types_csv)

    @property
    def cors_allowed_methods(self) -> str:
        return ", ".join(self._csv(self.cors_allowed_methods_csv))

    @property
    def cors_allowed_headers(self) -> str:
        # protocol-required headers always ride along, deduped (an empty
        # csv must not yield a leading ', ' — malformed header value)
        merged = list(self._csv(self.cors_allowed_headers_csv))
        for required in ("mcp-session-id", "last-event-id"):
            if required not in merged:
                merged.append(required)
        return ", ".join(merged)

    @property
    def supported_protocol_versions(self) -> set[str]:
        return {v.strip() for v in self.supported_protocol_versions_csv.split(",")
                if v.strip()}

    def default_passthrough_list(self) -> list[str]:
        return [h.strip() for h in self.default_passthrough_headers.split(",")
                if h.strip()]

    @property
    def gw_slow_request_s(self) -> float:
        """Effective slow-request bar in seconds: the dedicated knob, or
        the perf tracker's http threshold when unset (one bar, two
        consumers — the phase-vector log and the tracker's slow count
        must agree on what 'slow' means)."""
        ms = self.gw_slow_request_ms or \
            self.performance_threshold_http_request_ms
        return max(0.0, ms) / 1e3

    @property
    def allowed_host_set(self) -> set[str]:
        return {h.strip().lower() for h in self.allowed_hosts.split(",")
                if h.strip()}

    @property
    def database_path(self) -> str:
        path = self.database_url.split("///", 1)[-1]
        return path or ":memory:"

    @property
    def is_sqlite_memory(self) -> bool:
        return self.database_path in (":memory:", "")

    def validate_security(self) -> list[str]:
        """Return a list of fatal security problems (empty = OK).

        Mirrors the reference's hard startup failure on weak secrets
        (CHANGELOG 1.0.6: weak-secret rejection)."""
        problems: list[str] = []
        if self.environment == "production" or not self.dev_mode:
            if self.jwt_secret_key.lower() in _WEAK_SECRETS or len(self.jwt_secret_key) < 16:
                problems.append("jwt_secret_key is weak/default")
            if self.auth_encryption_secret.lower() in _WEAK_SECRETS or len(self.auth_encryption_secret) < 16:
                problems.append("auth_encryption_secret is weak/default")
            if self.basic_auth_password.lower() in _WEAK_SECRETS or len(self.basic_auth_password) < 8:
                problems.append("basic_auth_password is weak/default")
            if self.platform_admin_password.lower() in _WEAK_SECRETS or len(self.platform_admin_password) < 8:
                problems.append("platform_admin_password is weak/default")
        return problems


def _load_env_file(path: Path) -> dict[str, str]:
    out: dict[str, str] = {}
    if not path.exists():
        return out
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("#") or "=" not in line:
            continue
        k, _, v = line.partition("=")
        out[k.strip()] = v.strip().strip('"').strip("'")
    return out


def load_settings(env: dict[str, str] | None = None, env_file: str | None = ".env") -> Settings:
    """Build Settings from (explicit env dict | process env | .env file).

    Precedence (highest first): explicit ``env`` dict (keys ``MCPFORGE_X``,
    ``X`` or bare ``x``) > process environment (``MCPFORGE_X`` only, so
    unrelated host vars like ``PORT``/``ENVIRONMENT`` cannot reconfigure the
    gateway) > .env file (``MCPFORGE_X`` or ``X``) > field defaults.
    """
    file_source = _load_env_file(Path(env_file)) if env_file else {}
    explicit = env or {}

    def lookup(name: str) -> str | None:
        upper = f"MCPFORGE_{name.upper()}"
        for key in (upper, name.upper(), name):
            if key in explicit:
                return explicit[key]
        if upper in os.environ:
            return os.environ[upper]
        for key in (upper, name.upper()):
            if key in file_source:
                return file_source[key]
        return None

    # renamed fields: the old env key keeps working as an alias so an
    # upgrade cannot silently revert an operator's tuning to defaults
    _ALIASES = {"metrics_rollup_interval_minutes":
                "metrics_buffer_flush_interval"}

    values: dict[str, Any] = {}
    for name, field in Settings.model_fields.items():
        raw = lookup(name)
        if raw is None and name in _ALIASES:
            raw = lookup(_ALIASES[name])
            if raw is not None:
                logging.getLogger(__name__).warning(
                    "config: MCPFORGE_%s is deprecated; use MCPFORGE_%s",
                    _ALIASES[name].upper(), name.upper())
        if raw is None:
            continue
        if "tuple" in str(field.annotation):
            values[name] = tuple(int(x) for x in str(raw).replace(",", " ").split())
        else:
            values[name] = raw
    return Settings(**values)


@lru_cache(maxsize=1)
def get_settings() -> Settings:
    return load_settings()


def reset_settings_cache() -> None:
    get_settings.cache_clear()
