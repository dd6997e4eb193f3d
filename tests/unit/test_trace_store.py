"""Request forensics plane, unit tier: tail retention (errors, SLO
breaches, slowest-N per route/tenant, exemplar pins, deterministic
sampling, hard budget), waterfall stitching invariants (containment,
sum-of-children, overlap-tolerant cover), the exemplar ledger's
pin/replace lifecycle, and the OTLP exporter's retry-with-backoff
hardening (exported/dropped accounting replacing the silent debug-drop).
"""

from __future__ import annotations

import asyncio
import time

from mcp_context_forge_tpu.observability.trace_store import (
    STITCH_SPANS, ExemplarLedger, TraceStore, span_dict, stitch_waterfall)
from mcp_context_forge_tpu.observability.tracing import Span

T0 = 1_700_000_000.0


def mk(name, tid, sid, parent=None, start=T0, dur_ms=10.0, status="OK",
       attrs=None, events=None) -> Span:
    span = Span(name=name, trace_id=tid, span_id=sid,
                parent_span_id=parent, start_ts=start,
                attributes=dict(attrs or {}))
    span.end_ts = start + dur_ms / 1e3
    span.status = status
    if events:
        span.events = events
    return span


def tid(n: int) -> str:
    return f"{n:032x}"


def store(**kw) -> TraceStore:
    defaults = dict(max_traces=16, sample_every=0, slowest_per_key=2,
                    idle_finalize_s=60.0)
    defaults.update(kw)
    return TraceStore(**defaults)


def feed(st: TraceStore, trace, *, dur_ms=10.0, status="OK", route="/x",
         tenant=None, children=()):
    """One http.request-rooted trace: children sunk first (real span
    finish order), root last (triggers finalization)."""
    attrs = {"http.path": route}
    if tenant:
        attrs["gw.tenant"] = tenant
    for child in children:
        st.sink(child)
    st.sink(mk("http.request", trace, "root" + trace[-4:], None,
               dur_ms=dur_ms, status=status, attrs=attrs))


# ------------------------------------------------------------- tail retention

def test_error_traces_always_retained_boring_dropped():
    st = store()
    feed(st, tid(1), status="ERROR")
    feed(st, tid(2))  # boring: no error, no breach, sampling off
    assert st.get(tid(1)) is not None
    assert "error" in st.get(tid(1))["reasons"]
    assert st.get(tid(2)) is None or \
        "slowest_route" in st.get(tid(2))["reasons"]


def test_slo_breach_retained_with_named_objective():
    st = store(slo_targets={"http": 0.05})
    feed(st, tid(3), dur_ms=80.0)   # 80 ms > 50 ms target
    feed(st, tid(4), dur_ms=10.0)
    entry = st.get(tid(3))
    assert entry is not None
    assert "slo_breach" in entry["reasons"]
    assert entry["breaches"] == ["http"]


def test_ttft_and_tpot_breaches_from_engine_spans():
    st = store(slo_targets={"ttft": 0.05, "tpot": 0.001})
    trace = tid(5)
    children = [
        mk("llm.queue", trace, "q", "root" + trace[-4:], start=T0,
           dur_ms=30.0),
        mk("llm.prefill", trace, "p", "root" + trace[-4:], start=T0 + 0.03,
           dur_ms=40.0),  # queue start -> prefill end = 70 ms > 50 ms
        mk("llm.decode", trace, "d", "root" + trace[-4:], start=T0 + 0.07,
           dur_ms=100.0,
           attrs={"gen_ai.usage.completion_tokens": 10}),  # 10ms/tok > 1ms
    ]
    feed(st, trace, dur_ms=200.0, children=children)
    entry = st.get(trace)
    assert entry is not None
    assert set(entry["breaches"]) >= {"ttft", "tpot"}


def test_slowest_per_route_keeps_top_n_and_displaces():
    st = store(slowest_per_key=2)
    for i, dur in enumerate((10.0, 20.0, 30.0, 40.0)):
        feed(st, tid(10 + i), dur_ms=dur, route="/r")
    # only the two slowest survive; the displaced lose their only reason
    assert st.get(tid(10)) is None
    assert st.get(tid(11)) is None
    assert "slowest_route" in st.get(tid(12))["reasons"]
    assert "slowest_route" in st.get(tid(13))["reasons"]


def test_slowest_per_tenant_is_its_own_table():
    st = store(slowest_per_key=1)
    feed(st, tid(20), dur_ms=50.0, route="/a", tenant="user:t@x")
    feed(st, tid(21), dur_ms=10.0, route="/b", tenant="user:t@x")
    # 21 is not the slowest for its tenant, but IS for its route
    assert "slowest_tenant" in st.get(tid(20))["reasons"]
    assert st.get(tid(21)) is not None
    assert "slowest_route" in st.get(tid(21))["reasons"]
    assert st.get(tid(20))["tenant"] == "user:t@x"


def test_deterministic_sampling_is_reason_of_last_resort():
    st = store(sample_every=4, slowest_per_key=1)
    feed(st, tid(30), dur_ms=99.0)          # slowest for "/x"
    # the sample keys on the FIRST 8 hex chars of the trace id:
    # 0x20 % 4 == 0 -> sampled; 0x21 % 4 == 1 -> dropped
    feed(st, "00000020" + "0" * 24, dur_ms=1.0)
    feed(st, "00000021" + "0" * 24, dur_ms=1.0)
    sampled = st.get("00000020" + "0" * 24)
    assert sampled is not None and sampled["reasons"] == ["sampled"]
    assert st.get("00000021" + "0" * 24) is None


def test_budget_is_a_hard_bound_even_for_protected_traces():
    st = store(max_traces=8)
    for i in range(40):
        feed(st, tid(100 + i), status="ERROR")
    snap = st.snapshot()
    assert snap["retained"] <= 8
    assert snap["evicted"] >= 32


def test_rootless_trace_finalizes_on_idle():
    st = store(idle_finalize_s=0.01, sample_every=1)  # keep everything
    st.sink(mk("llm.decode", tid(50), "d", "parent-elsewhere",
               status="ERROR"))
    time.sleep(0.02)
    st.sink(mk("llm.decode", tid(51), "d2", "parent-elsewhere"))
    # the stale open trace got classified (error -> retained)
    entry = st.get(tid(50))
    assert entry is not None and "error" in entry["reasons"]


def test_nested_llm_request_does_not_finalize_the_http_trace_early():
    """A chat-agent turn emits several llm.request spans INSIDE one
    http.request trace; the retention decision must wait for the http
    root — finalizing at the first llm.request would classify a
    subtree and lose the rest."""
    st = store(slo_targets={"http": 0.05})
    trace = tid(55)
    root_id = "root" + trace[-4:]
    # two nested llm.request turns (parented), each fast on its own
    st.sink(mk("llm.request", trace, "lr1", root_id, dur_ms=5.0))
    st.sink(mk("llm.request", trace, "lr2", root_id, start=T0 + 0.01,
               dur_ms=5.0))
    assert st.get(trace) is None or not st.get(trace)["reasons"] \
        or st.snapshot()["finalized"] == 0
    # the http root lands last: ONE trace, classified over everything
    # (80 ms wall -> http breach)
    st.sink(mk("http.request", trace, root_id, None, dur_ms=80.0,
               attrs={"http.path": "/llmchat"}))
    entry = st.get(trace)
    assert entry is not None
    assert entry["span_count"] == 3
    assert "slo_breach" in entry["reasons"]


def test_late_root_refinalizes_an_idle_finalized_trace():
    """A slow in-flight request can outlive the idle window between its
    spans; when the root finally lands, the early partial decision must
    be REDONE over the full trace (duration/route/breaches recomputed,
    slowest rankings updated) — not left stale."""
    st = store(idle_finalize_s=0.01, sample_every=1,
               slo_targets={"http": 0.05})
    trace = tid(56)
    root_id = "root" + trace[-4:]
    st.sink(mk("llm.prefill", trace, "p", root_id, dur_ms=5.0))
    time.sleep(0.02)
    # another trace's sink trips the stale finalizer on the first
    st.sink(mk("llm.decode", tid(57), "d", "elsewhere"))
    early = st.get(trace)
    assert early is not None  # partial decision ran (fallback root)
    assert early["route"] != "/v1/chat/completions"
    # the root lands late: re-finalized over everything
    st.sink(mk("http.request", trace, root_id, None, dur_ms=90.0,
               attrs={"http.route": "/v1/chat/completions"}))
    entry = st.get(trace)
    assert entry is not None
    assert entry["duration_ms"] is not None
    assert entry["route"] == "/v1/chat/completions"
    assert "slo_breach" in entry["reasons"]  # 90 ms > 50 ms target
    assert st.snapshot()["refinalized"] == 1


def test_route_keys_on_template_not_raw_path():
    """slowest-per-route must key on the route TEMPLATE (http.route) so
    scanned/parametrized paths cannot mint one-member routes that are
    each trivially their own 'slowest'."""
    st = store(slowest_per_key=1)
    for i in range(4):
        st.sink(mk("http.request", tid(240 + i), f"r{i}", None,
                   dur_ms=10.0 + i,
                   attrs={"http.route": "unmatched",
                          "http.path": f"/scan/{i}"}))
    # one shared key: only the slowest survives, not one per raw path
    retained = [i for i in range(4) if st.get(tid(240 + i)) is not None]
    assert retained == [3], retained
    assert st.get(tid(243))["route"] == "unmatched"


def test_evicted_slowest_key_strips_orphaned_reasons():
    """When the bounded key table forgets a route, its members must lose
    the slowest_route claim (and drop if that was their only reason) —
    a table-less 'slowest' reason would protect them from eviction
    forever."""
    st = store(slowest_per_key=1, max_keys=2)
    for i, route in enumerate(("/a", "/b", "/c")):
        st.sink(mk("http.request", tid(250 + i), f"r{i}", None,
                   dur_ms=10.0, attrs={"http.route": route}))
    # "/a" was the LRU key when "/c" arrived: its member is gone
    assert st.get(tid(250)) is None
    assert st.get(tid(251)) is not None
    assert st.get(tid(252)) is not None


def test_root_span_survives_the_span_cap():
    # the root finishes LAST: a trace that overflows on children (e.g.
    # hundreds of tier.restore spans) must still store the root the
    # waterfall re-roots on, flagged truncated
    st = store(max_spans_per_trace=8)
    trace = tid(45)
    for i in range(12):
        st.sink(mk("tier.restore", trace, f"t{i}", "root" + trace[-4:],
                   dur_ms=1.0))
    st.sink(mk("http.request", trace, "root" + trace[-4:], None,
               dur_ms=500.0, status="ERROR", attrs={"http.path": "/x"}))
    entry = st.get(trace)
    assert entry is not None and entry["truncated"]
    names = [s["name"] for s in entry["spans"]]
    assert "http.request" in names
    wf = stitch_waterfall(entry["spans"])
    assert wf["root"]["name"] == "http.request"


def test_parentless_utility_span_is_not_an_http_breach():
    # llm.xla_compile has no trace_ctx -> it roots its own single-span
    # trace; its multi-second wall is a compile, not an http latency,
    # and must not become a budget-protected "http breach" trace
    st = store(slo_targets={"http": 0.05})
    st.sink(mk("llm.xla_compile", tid(46), "c", None, dur_ms=2000.0))
    entry = st.get(tid(46))
    if entry is not None:                    # slowest_route may keep it
        assert entry["breaches"] == []
        assert "slo_breach" not in entry["reasons"]


def test_span_cap_truncates_not_grows():
    st = store(max_spans_per_trace=8)
    trace = tid(60)
    for i in range(50):
        st.sink(mk("llm.decode", trace, f"s{i}", "r", status="ERROR"))
    st.sink(mk("http.request", trace, "r", None, status="ERROR"))
    entry = st.get(trace)
    assert entry["truncated"] is True
    assert entry["span_count"] <= 9  # 8 children cap + the root attempt


# ------------------------------------------------------------------ exemplars

def test_exemplar_ledger_pins_and_replaces():
    ledger = ExemplarLedger()
    ledger.register("llm_ttft", [0.1, 1.0])
    ex = ledger.note("llm_ttft", 0.5, tid(70))
    assert ex == {"trace_id": tid(70)}
    assert ledger.pinned(tid(70))
    # same bucket, new trace: the old exemplar unpins
    ledger.note("llm_ttft", 0.6, tid(71))
    assert not ledger.pinned(tid(70))
    assert ledger.pinned(tid(71))
    # different bucket: both pinned
    ledger.note("llm_ttft", 0.01, tid(72))
    assert ledger.pinned(tid(71)) and ledger.pinned(tid(72))
    # unattributed / unregistered observations yield no exemplar
    assert ledger.note("llm_ttft", 0.5, None) is None
    assert ledger.note("nope", 0.5, tid(73)) is None
    assert ExemplarLedger(enabled=False).note("llm_ttft", 1, tid(1)) is None


def test_exemplar_pin_retains_trace_in_store():
    ledger = ExemplarLedger()
    ledger.register("http_duration", [0.1, 1.0])
    st = store(exemplars=ledger)
    ledger.note("http_duration", 0.5, tid(80))
    feed(st, tid(80), dur_ms=1.0, route="/pinned")
    feed(st, tid(81), dur_ms=0.5, route="/pinned")  # not pinned, not slowest
    entry = st.get(tid(80))
    assert entry is not None and "exemplar" in entry["reasons"]


def test_exemplar_ledger_cells_are_per_label_child():
    # prometheus stores exemplars per LABELED child: tenant B's observe
    # must not unpin tenant A's trace while A's bucket line still
    # renders it (the dangling-click-through regression)
    ledger = ExemplarLedger()
    ledger.register("http_duration", [0.1, 1.0])
    ledger.note("http_duration", 0.5, tid(85), ("GET", "/x", "tenantA"))
    ledger.note("http_duration", 0.6, tid(86), ("GET", "/x", "tenantB"))
    assert ledger.pinned(tid(85)) and ledger.pinned(tid(86))
    # the SAME label child's bucket replaces its own exemplar only
    ledger.note("http_duration", 0.7, tid(87), ("GET", "/x", "tenantA"))
    assert not ledger.pinned(tid(85))
    assert ledger.pinned(tid(86)) and ledger.pinned(tid(87))


def test_exemplar_only_trace_released_when_unpinned():
    # every request is its bucket's CURRENT exemplar the instant it
    # finishes; without the unpin reap, 'exemplar' would retain every
    # trace and tail sampling would degenerate to retain-everything
    ledger = ExemplarLedger()
    ledger.register("http_duration", [0.1, 1.0])
    st = store(exemplars=ledger, slowest_per_key=1)
    feed(st, tid(88), dur_ms=100.0)          # slowest for the route
    ledger.note("http_duration", 0.5, tid(89))
    feed(st, tid(89), dur_ms=1.0)            # retained as exemplar ONLY
    assert st.get(tid(89))["reasons"] == ["exemplar"]
    # its bucket cell is replaced by the next request's observe ...
    ledger.note("http_duration", 0.6, tid(90))
    feed(st, tid(90), dur_ms=1.0)            # finalize runs the reap
    assert st.get(tid(89)) is None           # ... and the trace releases
    assert st.exemplar_released >= 1
    # the live exemplar's trace stays retained (click-through contract)
    assert "exemplar" in st.get(tid(90))["reasons"]


def test_forced_eviction_prefers_non_pinned_protected_entries():
    # all-protected overflow: the hard bound still wins, but a live
    # /metrics exemplar's trace must be the LAST to go — evicting it
    # while its bucket line still renders the trace id would dangle
    # the documented click-through
    ledger = ExemplarLedger()
    ledger.register("http_duration", [0.1, 1.0])
    st = store(max_traces=2, exemplars=ledger)
    ledger.note("http_duration", 0.5, tid(95))
    feed(st, tid(95), status="ERROR")        # oldest, protected + pinned
    feed(st, tid(96), status="ERROR")        # protected, not pinned
    feed(st, tid(97), status="ERROR")        # overflow -> forced eviction
    assert st.get(tid(95)) is not None       # live exemplar survives
    assert st.get(tid(96)) is None           # older non-pinned went
    assert st.get(tid(97)) is not None


def test_sampled_exemplar_trace_survives_unpin_reap():
    # the deterministic 1-in-M sample is evaluated even for traces that
    # are (transiently) exemplar-pinned at finalize: the pin is going to
    # be replaced, and a trace the sample keeps must survive the reap
    ledger = ExemplarLedger()
    ledger.register("http_duration", [0.1, 1.0])
    st = store(exemplars=ledger, sample_every=4, slowest_per_key=1)
    feed(st, tid(91), dur_ms=100.0)          # slowest for the route
    sampled_id = "00000020" + "0" * 24       # 0x20 % 4 == 0 -> sampled
    ledger.note("http_duration", 0.5, sampled_id)
    feed(st, sampled_id, dur_ms=1.0)
    assert set(st.get(sampled_id)["reasons"]) == {"exemplar", "sampled"}
    ledger.note("http_duration", 0.6, tid(92))   # unpin ...
    feed(st, tid(92), dur_ms=1.0)                # ... and reap
    assert st.get(sampled_id) is not None        # sample keeps it


# ------------------------------------------------------------------ waterfall

def _fake_engine(rows):
    class E:
        def recent_steps(self):
            return rows
    return E()


def test_waterfall_tree_invariants_and_engine_join():
    trace = tid(90)
    spans = [
        mk("http.request", trace, "r", None, start=T0, dur_ms=100.0,
           attrs={"http.path": "/v1/chat/completions"}),
        mk("llm.request", trace, "lr", "r", start=T0 + 0.001, dur_ms=95.0),
        mk("llm.queue", trace, "q", "lr", start=T0 + 0.001, dur_ms=5.0,
           attrs={"llm.replica_id": "0", "llm.tenant": "user:a@x"}),
        mk("llm.prefill", trace, "p", "lr", start=T0 + 0.006, dur_ms=20.0,
           attrs={"llm.replica_id": "0", "llm.tenant": "user:a@x"}),
        mk("llm.decode", trace, "d", "lr", start=T0 + 0.026, dur_ms=60.0,
           attrs={"llm.replica_id": "0", "llm.tenant": "user:a@x",
                  "gen_ai.usage.completion_tokens": 8}),
        mk("tier.restore", trace, "t", "lr", start=T0 + 0.002, dur_ms=1.0,
           attrs={"llm.replica_id": "0", "tier.tier": "host"}),
    ]
    engine_rows = [
        {"ts": T0 + 0.05, "duration_ms": 10.0, "seq": 1, "kind": "decode",
         "batch": 2, "tokens": 16, "superstep": 8, "frozen": 0,
         "gap_ms": 0.0, "phases": {"launch_ms": 8.0}, "mfu": 0.1,
         "hbm_frac": 0.2},
        {"ts": T0 + 5.0, "duration_ms": 10.0, "seq": 2, "kind": "decode",
         "batch": 2, "tokens": 16, "superstep": 8, "frozen": 0,
         "gap_ms": 0.0, "phases": None, "mfu": None, "hbm_frac": None},
    ]
    row = {"trace_id": trace, "duration_ms": 100.0,
           "phases_ms": {"auth": 10.0, "engine": 85.0, "handler": 5.0}}
    wf = stitch_waterfall([span_dict(s) for s in spans],
                          gateway_row=row,
                          engines={"0": _fake_engine(engine_rows)})
    assert wf["complete"], wf["invariants"]
    assert wf["invariants"]["children_within_parent"]
    assert wf["invariants"]["child_sum_le_wall"]
    assert wf["invariants"]["child_cover_le_wall"]
    assert wf["root"]["name"] == "http.request"
    assert wf["replica_hops"] == ["0"]
    assert wf["tenants"] == ["user:a@x"]
    assert wf["gateway"]["phase_sum_ms"] == 100.0
    assert len(wf["tier_io"]) == 1
    # the decode node joined ONLY the overlapping step-ring row
    decode = next(c for c in wf["tree"][0]["children"][0]["children"]
                  if c["name"] == "llm.decode")
    assert [r["seq"] for r in decode["engine_steps"]] == [1]
    assert decode["engine_steps"][0]["superstep"] == 8
    assert wf["engine_steps_joined"] == 1
    assert wf["layers"]["engine"] == 3
    assert wf["layers"]["kv_tier"] == 1


def test_waterfall_flags_child_escaping_parent():
    trace = tid(91)
    spans = [
        mk("http.request", trace, "r", None, start=T0, dur_ms=10.0),
        mk("llm.decode", trace, "d", "r", start=T0 + 0.005, dur_ms=500.0),
    ]
    wf = stitch_waterfall([span_dict(s) for s in spans])
    assert not wf["invariants"]["children_within_parent"]
    assert not wf["complete"]


def test_waterfall_requeue_overlap_breaks_sum_not_cover():
    """A failover's two attempts overlap on the wall clock: the plain
    child SUM can exceed the parent wall, but the union COVER cannot —
    and the waterfall shows both replica hops + the requeue span."""
    trace = tid(92)
    spans = [
        mk("http.request", trace, "r", None, start=T0, dur_ms=100.0),
        mk("llm.request", trace, "lr", "r", start=T0, dur_ms=100.0),
        # attempt 1 on replica 0 (killed mid-decode)
        mk("llm.decode", trace, "d0", "lr", start=T0 + 0.005, dur_ms=60.0,
           status="ERROR", attrs={"llm.replica_id": "0",
                                  "llm.tenant": "user:a@x"}),
        # continuation on replica 1 — queue span overlaps attempt 1's
        # decode (shadow.created == request.created)
        mk("pool.requeue", trace, "rq", "lr", start=T0 + 0.06, dur_ms=2.0,
           attrs={"llm.from_replica": "0", "llm.tenant": "user:a@x"}),
        mk("llm.queue", trace, "q1", "lr", start=T0 + 0.001, dur_ms=61.0,
           attrs={"llm.replica_id": "1", "llm.tenant": "user:a@x"}),
        mk("llm.decode", trace, "d1", "lr", start=T0 + 0.065, dur_ms=30.0,
           attrs={"llm.replica_id": "1", "llm.tenant": "user:a@x"}),
    ]
    wf = stitch_waterfall([span_dict(s) for s in spans])
    assert wf["replica_hops"] == ["1", "0"] or \
        wf["replica_hops"] == ["0", "1"]
    assert len(wf["requeues"]) == 1
    assert wf["tenants"] == ["user:a@x"]  # conserved across the hop
    assert not wf["invariants"]["child_sum_le_wall"]   # overlap: expected
    assert wf["invariants"]["child_cover_le_wall"]     # union still fits
    assert wf["invariants"]["children_within_parent"]


def test_stitch_table_covers_the_emitting_layers():
    layers = set(STITCH_SPANS.values())
    assert {"gateway", "provider", "engine", "kv_tier", "pool"} <= layers


# ------------------------------------------------------------ otlp hardening

class _Resp:
    def __init__(self, status_code):
        self.status_code = status_code
        self.text = "nope"


class _FlakyClient:
    def __init__(self, failures, status_after=200, exc=None):
        self.failures = failures
        self.status_after = status_after
        self.exc = exc or ConnectionError("collector down")
        self.calls = 0

    async def post(self, url, json=None, headers=None):
        self.calls += 1
        if self.calls <= self.failures:
            raise self.exc
        return _Resp(self.status_after)


class _Ctx:
    def __init__(self, client, metrics=None):
        self.http_client = client
        self.metrics = metrics


def _exporter(client, metrics=None, **kw):
    from mcp_context_forge_tpu.observability.otlp import OTLPExporter
    kw.setdefault("backoff_base_s", 0.01)
    return OTLPExporter(_Ctx(client, metrics), "http://collector:4318",
                        "test", **kw)


def _span(n=0):
    return mk("http.request", tid(200 + n), "s", None)


def test_otlp_transient_failure_retries_then_exports():
    from mcp_context_forge_tpu.observability.metrics import \
        PrometheusRegistry
    metrics = PrometheusRegistry()
    client = _FlakyClient(failures=2)
    exporter = _exporter(client, metrics, max_retries=3)

    async def run():
        exporter.sink(_span())
        await exporter.flush()                    # fails -> deferred
        assert exporter.exported == 0 and exporter.dropped == 0
        for _ in range(6):
            await asyncio.sleep(0.02)             # let backoff elapse
            await exporter.flush()
            if exporter.exported:
                break
        assert exporter.exported == 1
        assert exporter.dropped == 0
        assert exporter.retries >= 1
    asyncio.run(run())
    assert metrics.otel_spans_exported._value.get() == 1


def test_otlp_retry_exhaustion_drops_with_reason():
    from mcp_context_forge_tpu.observability.metrics import \
        PrometheusRegistry
    metrics = PrometheusRegistry()
    client = _FlakyClient(failures=99)
    exporter = _exporter(client, metrics, max_retries=2)

    async def run():
        exporter.sink(_span())
        for _ in range(8):
            await exporter.flush()
            await asyncio.sleep(0.02)
            if exporter.dropped:
                break
        assert exporter.dropped == 1
    asyncio.run(run())
    assert metrics.otel_spans_dropped.labels(
        reason="retry_exhausted")._value.get() == 1
    assert client.calls == 3  # initial + 2 retries


def test_otlp_4xx_rejection_drops_immediately_5xx_retries():
    metrics = None
    rejected = _exporter(_FlakyClient(failures=0, status_after=400),
                         metrics)
    flaky5xx = _exporter(_FlakyClient(failures=0, status_after=503),
                         metrics, max_retries=1)

    async def run():
        rejected.sink(_span(1))
        await rejected.flush()
        assert rejected.dropped == 1          # 4xx: no retry can help
        assert rejected._retry_batch is None
        flaky5xx.sink(_span(2))
        await flaky5xx.flush()
        assert flaky5xx.dropped == 0          # 5xx: deferred, not dropped
        assert flaky5xx._retry_batch is not None
    asyncio.run(run())


def test_otlp_buffer_overflow_counts_reason():
    from mcp_context_forge_tpu.observability.metrics import \
        PrometheusRegistry
    metrics = PrometheusRegistry()
    exporter = _exporter(_FlakyClient(failures=0), metrics, max_buffer=2)
    for i in range(5):
        exporter.sink(_span(i))
    assert exporter.dropped == 3
    assert metrics.otel_spans_dropped.labels(
        reason="buffer_full")._value.get() == 3


def test_otlp_stop_forces_final_retry_attempt():
    client = _FlakyClient(failures=1)
    exporter = _exporter(client, max_retries=3, backoff_base_s=60.0)

    async def run():
        exporter.sink(_span())
        await exporter.flush()        # fails, deferred 60 s out
        assert exporter.exported == 0
        await exporter.stop()         # final flush ignores the backoff
        assert exporter.exported == 1
    asyncio.run(run())


def test_otlp_stop_accounts_undeliverable_spans():
    # a collector still down at shutdown: the final attempt fails and
    # the process exits — the batch must land in the dropped counter
    # (reason=shutdown), not vanish behind a "retrying in Xs" log for
    # a retry that will never run
    from mcp_context_forge_tpu.observability.metrics import \
        PrometheusRegistry
    metrics = PrometheusRegistry()
    exporter = _exporter(_FlakyClient(failures=99), metrics,
                         max_retries=5, backoff_base_s=60.0)

    async def run():
        exporter.sink(_span(0))
        await exporter.flush()        # fails, deferred 60 s out
        exporter.sink(_span(1))       # still buffered at shutdown
        await exporter.stop()
        assert exporter.exported == 0
        assert exporter.dropped == 2
        assert exporter._retry_batch is None
    asyncio.run(run())
    assert metrics.otel_spans_dropped.labels(
        reason="shutdown")._value.get() == 2
