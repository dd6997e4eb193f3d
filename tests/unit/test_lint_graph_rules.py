"""Fixture suites for the six whole-program (ProjectGraph-backed) rules.

Every rule gets the same trio: a FIRING fixture (the violation the rule
exists for), a CLEAN twin (the idiomatic fix — the rule must not flag the
shape it recommends), and a SUPPRESSED case (the ``# lint: allow[...]``
escape hatch lands the finding in ``result.suppressed``, not silence).
Cross-file behavior is exercised with multi-file source dicts — that is
the whole point of these rules.

The live-tree non-vacuity pins (each rule actually fires on the real
package and is suppressed with a written reason) live in
test_lint_clean.py; the graph extraction itself is additionally
mutation-gated via testing/oracles.py::lint_project_oracle.
"""

from __future__ import annotations

import textwrap

from mcp_context_forge_tpu.tools.lint import lint_sources
from mcp_context_forge_tpu.tools.lint.core import FileContext
from mcp_context_forge_tpu.tools.lint.project import ProjectGraph
from mcp_context_forge_tpu.tools.lint.rules.await_lock import \
    AwaitHoldingLockRule
from mcp_context_forge_tpu.tools.lint.rules.bus_rpc import \
    BusRpcConformanceRule
from mcp_context_forge_tpu.tools.lint.rules.config_keys import \
    ConfigKeyLivenessRule
from mcp_context_forge_tpu.tools.lint.rules.lock_order import \
    LockOrderCycleRule
from mcp_context_forge_tpu.tools.lint.rules.metric_labels import \
    MetricLabelCardinalityRule
from mcp_context_forge_tpu.tools.lint.rules.signal_names import \
    SignalNameConformanceRule


def run(rule, sources: dict[str, str]):
    result = lint_sources(
        {path: textwrap.dedent(src) for path, src in sources.items()},
        [rule])
    assert not result.errors, result.errors
    return result


# ------------------------------------------------------- await-holding-lock

DB_FIXTURE = """
    import threading
    import time

    class Db:
        def __init__(self):
            self._mutex = threading.Lock()

        async def commit(self, conn):
            with self._mutex:
                await conn.commit()

        def retry(self):
            with self._mutex:
                time.sleep(0.1)
"""


def test_await_lock_fires_on_await_and_blocking_call_under_lock():
    result = run(AwaitHoldingLockRule(), {"pkg/db.py": DB_FIXTURE})
    assert len(result.findings) == 2, result.findings
    assert [f.lineno for f in result.findings] == [11, 15]
    assert "await while holding sync lock" in result.findings[0].message
    assert "self._mutex" in result.findings[0].message
    assert "blocking call under sync lock" in result.findings[1].message


def test_await_lock_clean_twin_is_silent():
    # the fixes the rule recommends: asyncio.Lock held across awaits
    # (designed for it), the await moved out of the critical section,
    # and deferred work in a nested sync def (runs on another frame)
    result = run(AwaitHoldingLockRule(), {"pkg/db.py": """
        import asyncio
        import threading
        import time

        class Db:
            def __init__(self):
                self._alock = asyncio.Lock()
                self._mutex = threading.Lock()

            async def commit(self, conn):
                async with self._alock:
                    await conn.commit()

            async def snapshot(self, conn):
                with self._mutex:
                    state = dict(x=1)
                await conn.write(state)

            def defer(self):
                with self._mutex:
                    def cb():
                        time.sleep(0.1)
                    return cb
        """})
    assert result.findings == []


def test_await_lock_allow_suppresses_with_reason():
    source = DB_FIXTURE.replace(
        "time.sleep(0.1)",
        "time.sleep(0.1)  "
        "# lint: allow[await-holding-lock] bounded WAL retry off-loop")
    result = run(AwaitHoldingLockRule(), {"pkg/db.py": source})
    assert len(result.findings) == 1          # the await still fires
    assert len(result.suppressed) == 1
    assert result.suppressed[0].lineno == 15


# -------------------------------------------------------- lock-order-cycle

CYCLE_FIXTURE = """
    import threading

    class Pool:
        def __init__(self):
            self._sched_lock = threading.Lock()   # lint: lock[sched]
            self._stats_lock = threading.Lock()

        def schedule(self):
            with self._sched_lock:
                with self._stats_lock:
                    pass

        def report(self):
            with self._stats_lock:
                with self._sched_lock:
                    pass
"""


def test_lock_order_cycle_fires_at_every_declaration():
    result = run(LockOrderCycleRule(), {"pkg/pool.py": CYCLE_FIXTURE})
    assert len(result.findings) == 2, result.findings
    # anchored at the two DECLARATION lines so one allow[] cannot
    # swallow the whole cycle
    assert {f.lineno for f in result.findings} == {6, 7}
    assert all("cycle" in f.message for f in result.findings)
    assert "[ctx sched]" not in result.findings[0].message  # cycles: no tag


ONE_WAY_FIXTURE = """
    import threading

    class Pool:
        def __init__(self):
            self._sched_lock = threading.Lock()   # lint: lock[sched]
            self._stats_lock = threading.Lock()

        def schedule(self):
            with self._sched_lock:
                with self._stats_lock:
                    pass
"""


def test_lock_order_one_way_edge_fires_once_at_outer_site():
    result = run(LockOrderCycleRule(), {"pkg/pool.py": ONE_WAY_FIXTURE})
    assert len(result.findings) == 1
    f = result.findings[0]
    assert f.lineno == 10                      # the OUTER acquisition
    assert "while holding Pool._sched_lock" in f.message
    assert "[ctx sched]" in f.message          # thread tag rides along


def test_lock_order_self_edge_via_helper_fires_rlock_exempt():
    helper = """
        import threading

        class Q:
            def __init__(self):
                self._q_lock = threading.{ctor}()

            def push(self):
                with self._q_lock:
                    self._size()

            def _size(self):
                with self._q_lock:
                    return 0
    """
    result = run(LockOrderCycleRule(),
                 {"pkg/q.py": helper.format(ctor="Lock")})
    assert len(result.findings) == 1
    assert "re-acquired" in result.findings[0].message
    # the same shape over an RLock is legal reentrancy
    result = run(LockOrderCycleRule(),
                 {"pkg/q.py": helper.format(ctor="RLock")})
    assert result.findings == []


def test_lock_order_cross_class_edge_resolved_through_attr_typing():
    """The in-tree shape: TenantLedger.add holds the ledger lock and
    calls into TenantClamp.label which takes the clamp lock — the edge
    spans two files and only the graph can see it."""
    result = run(LockOrderCycleRule(), {
        "pkg/clamp.py": """
            import threading

            class TenantClamp:
                def __init__(self):
                    self._clamp_lock = threading.Lock()

                def label(self, tenant):
                    with self._clamp_lock:
                        return tenant
        """,
        "pkg/ledger.py": """
            import threading

            from .clamp import TenantClamp

            class TenantLedger:
                def __init__(self):
                    self._ledger_lock = threading.Lock()
                    self._clamp = TenantClamp()

                def add(self, tenant, n):
                    with self._ledger_lock:
                        return self._clamp.label(tenant)
        """})
    assert len(result.findings) == 1, result.findings
    f = result.findings[0]
    assert f.path == "pkg/ledger.py"
    assert "TenantClamp._clamp_lock" in f.message
    assert "TenantLedger._ledger_lock" in f.message


def test_lock_order_allow_on_outer_site_suppresses_the_edge():
    source = ONE_WAY_FIXTURE.replace(
        "with self._sched_lock:",
        "with self._sched_lock:  "
        "# lint: allow[lock-order-cycle] one-way: stats never calls back")
    result = run(LockOrderCycleRule(), {"pkg/pool.py": source})
    assert result.findings == []
    assert len(result.suppressed) == 1


# ----------------------------------------------------- bus-rpc-conformance

RPC_SERVER = """
    class PoolRpcServer:
        def __init__(self, rpc):
            rpc.register("pool.status", self._status)
            rpc.register_stream("pool.tail", self._tail)
            rpc.register("pool.orphan", self._orphan)
"""

RPC_CLIENT = """
    class PoolClient:
        def __init__(self, rpc):
            self._rpc = rpc

        async def status(self, worker):
            return await self._rpc.call(worker, "pool.status")

        def tail(self, worker):
            return self._rpc.call_stream(worker, "pool.tail",
                                         idle_timeout_s=5.0)

        async def ghost(self, worker):
            return await self._rpc.call(worker, "pool.ghost")

        async def tail_as_unary(self, worker):
            return await self._rpc.call(worker, "pool.tail")

        def tail_no_liveness(self, worker):
            return self._rpc.call_stream(worker, "pool.tail")
"""


def test_bus_rpc_flags_all_four_conformance_classes():
    result = run(BusRpcConformanceRule(), {"pkg/server.py": RPC_SERVER,
                                           "pkg/client.py": RPC_CLIENT})
    by_msg = sorted(f.message for f in result.findings)
    assert len(result.findings) == 4, by_msg
    assert any("'pool.ghost'" in m and "no handler" in m for m in by_msg)
    assert any("kind mismatch for 'pool.tail'" in m for m in by_msg)
    assert any("without idle_timeout_s" in m for m in by_msg)
    assert any("'pool.orphan'" in m and "no\nin-tree caller"
               .replace("\n", " ") in m for m in by_msg)
    # call-side findings anchor in the client, dead-handler in the server
    assert {f.path for f in result.findings} == {"pkg/server.py",
                                                 "pkg/client.py"}


def test_bus_rpc_clean_when_both_sides_agree():
    client = """
        class PoolClient:
            def __init__(self, rpc):
                self._rpc = rpc

            async def status(self, worker):
                return await self._rpc.call(worker, "pool.status")

            def tail(self, worker):
                return self._rpc.call_stream(worker, "pool.tail",
                                             idle_timeout_s=5.0)

            async def orphan(self, worker):
                return await self._rpc.call(worker, "pool.orphan")
    """
    result = run(BusRpcConformanceRule(), {"pkg/server.py": RPC_SERVER,
                                           "pkg/client.py": client})
    assert result.findings == []


def test_bus_rpc_silent_without_a_registry_in_scope():
    """Subset-run degradation: linting just the client file must not
    flag every call as handler-less."""
    result = run(BusRpcConformanceRule(), {"pkg/client.py": RPC_CLIENT})
    assert result.findings == []


def test_bus_rpc_operator_surface_acknowledged_with_allow():
    server = RPC_SERVER.replace(
        'rpc.register("pool.orphan", self._orphan)',
        'rpc.register("pool.orphan", self._orphan)  '
        '# lint: allow[bus-rpc-conformance] operator CLI calls this')
    client = RPC_CLIENT.replace(
        """    async def ghost(self, worker):
            return await self._rpc.call(worker, "pool.ghost")

        async def tail_as_unary(self, worker):
            return await self._rpc.call(worker, "pool.tail")

        def tail_no_liveness(self, worker):
            return self._rpc.call_stream(worker, "pool.tail")
""", "")
    result = run(BusRpcConformanceRule(), {"pkg/server.py": server,
                                           "pkg/client.py": client})
    assert result.findings == []
    assert len(result.suppressed) == 1
    assert result.suppressed[0].path == "pkg/server.py"


# ------------------------------------------------- signal-name-conformance

SIGNAL_ENGINE = """
    class Engine:
        def step(self, signals):
            signals.publish("llm.occupancy", 0.5)
            signals.publish("llm.orphan_export", 1.0)
"""

SIGNAL_CONTROLLER = """
    class Controller:
        def tick(self, bus, rid):
            occ = bus.get("llm.occupancy", rid)
            ghost = bus.ewma("llm.ghost", rid)
            return occ, ghost
"""


def test_signal_names_flag_both_directions_of_drift():
    result = run(SignalNameConformanceRule(),
                 {"pkg/engine.py": SIGNAL_ENGINE,
                  "pkg/controller.py": SIGNAL_CONTROLLER})
    assert len(result.findings) == 2, result.findings
    reads = [f for f in result.findings if "consumed here" in f.message]
    pubs = [f for f in result.findings if "published but" in f.message]
    assert len(reads) == 1 and reads[0].path == "pkg/controller.py"
    assert "'llm.ghost'" in reads[0].message
    assert len(pubs) == 1 and pubs[0].path == "pkg/engine.py"
    assert "'llm.orphan_export'" in pubs[0].message


def test_signal_names_clean_when_sides_agree_including_forwarder():
    """_view-style forwarders and _EFFECT_SIGNALS const-tuple loops are
    real reads — the idioms the controller actually uses."""
    controller = """
        class Controller:
            _EFFECT_SIGNALS = ("llm.orphan_export",)

            def _view(self, name, rid):
                return self.bus.get(name, rid)

            def tick(self, rid):
                occ = self._view("llm.occupancy", rid)
                for name in self._EFFECT_SIGNALS:
                    self.bus.ewma(name, rid)
                return occ
    """
    result = run(SignalNameConformanceRule(),
                 {"pkg/engine.py": SIGNAL_ENGINE,
                  "pkg/controller.py": controller})
    assert result.findings == [], result.findings


def test_signal_names_dynamic_prefix_always_needs_allow():
    engine = SIGNAL_ENGINE.replace(
        'signals.publish("llm.orphan_export", 1.0)',
        'signals.publish(f"slo.burn.{cls_}", 1.0)')
    result = run(SignalNameConformanceRule(),
                 {"pkg/engine.py": "cls_ = 'x'\n" + textwrap.dedent(engine),
                  "pkg/controller.py": SIGNAL_CONTROLLER.replace(
                      '"llm.ghost"', '"slo.burn.premium"')})
    # the prefix-matching read is NOT flagged; the dynamic publish IS
    msgs = [f.message for f in result.findings]
    assert len(result.findings) == 1, msgs
    assert "cannot be" in msgs[0] and "slo.burn." in msgs[0]
    # ...and the allow[] on the publish site settles it
    result = run(SignalNameConformanceRule(),
                 {"pkg/engine.py": ("cls_ = 'x'\n" + textwrap.dedent(
                     engine)).replace(
                     'signals.publish(f"slo.burn.{cls_}", 1.0)',
                     'signals.publish(f"slo.burn.{cls_}", 1.0)  '
                     '# lint: allow[signal-name-conformance] per-class '
                     'burn family, consumed by dashboards'),
                  "pkg/controller.py": SIGNAL_CONTROLLER.replace(
                      '"llm.ghost"', '"slo.burn.premium"')})
    assert result.findings == []
    assert len(result.suppressed) == 1


def test_signal_names_silent_when_one_side_missing():
    for sources in ({"pkg/engine.py": SIGNAL_ENGINE},
                    {"pkg/controller.py": SIGNAL_CONTROLLER}):
        result = run(SignalNameConformanceRule(), sources)
        assert result.findings == [], sources.keys()


# --------------------------------------------------- config-key-liveness

CONFIG_FIXTURE = """
    class Settings:
        request_timeout_s: float = 30.0
        ghost_knob: int = 3
"""


def test_config_liveness_flags_field_nothing_reads():
    result = run(ConfigKeyLivenessRule(), {
        "pkg/config.py": CONFIG_FIXTURE,
        "pkg/server.py": "def f(s):\n    return s.request_timeout_s\n"})
    assert len(result.findings) == 1, result.findings
    f = result.findings[0]
    assert f.path == "pkg/config.py" and f.lineno == 4
    assert "Settings.ghost_knob" in f.message
    assert "read by no other" in f.message


def test_config_liveness_getattr_string_read_counts():
    """The forward-compat idiom: getattr(settings, "name", default) is
    how EngineConfig hydrates optional knobs — it must count as a read."""
    result = run(ConfigKeyLivenessRule(), {
        "pkg/config.py": CONFIG_FIXTURE,
        "pkg/server.py": ("def f(s):\n    s.request_timeout_s\n"
                          "    return getattr(s, 'ghost_knob', 3)\n")})
    assert result.findings == []


def test_config_liveness_engine_config_fields_are_policed_too():
    result = run(ConfigKeyLivenessRule(), {
        "pkg/engine.py": """
            from dataclasses import dataclass

            @dataclass
            class EngineConfig:
                max_batch: int = 8
                unused_dial: int = 0

            def boot(cfg):
                return cfg.max_batch
        """})
    assert len(result.findings) == 1
    assert "EngineConfig.unused_dial" in result.findings[0].message


def test_config_liveness_a_setting_is_read_where_the_field_it_fills_is():
    """``EngineConfig.from_settings`` walks the fields: ``tpu_local_<f>`` is
    live exactly when the engine field ``<f>`` is read."""
    sources = {
        "pkg/config.py": """
            class Settings:
                tpu_local_max_batch: int = 8
                tpu_local_unused_dial: int = 0
                tpu_local_no_such_field: int = 0
        """,
        "pkg/engine.py": """
            from dataclasses import dataclass

            @dataclass
            class EngineConfig:
                max_batch: int = 8
                unused_dial: int = 0

            def boot(cfg):
                return cfg.max_batch
        """}
    result = run(ConfigKeyLivenessRule(), sources)
    assert sorted(f.message.split(" is read")[0] for f in result.findings) == [
        "EngineConfig.unused_dial", "Settings.tpu_local_no_such_field",
        "Settings.tpu_local_unused_dial"]


def test_config_liveness_docs_clause_uses_injected_docs_text():
    """Undocumented-but-live fields flag only when a docs tree exists;
    in-memory runs (docs_text None) skip the clause entirely."""
    rule = ConfigKeyLivenessRule()
    sources = {
        "pkg/config.py": textwrap.dedent(CONFIG_FIXTURE),
        "pkg/server.py": ("def f(s):\n    s.request_timeout_s\n"
                          "    return s.ghost_knob\n")}
    contexts = [FileContext.from_source(src, path)
                for path, src in sorted(sources.items())]
    documented = ProjectGraph.build(
        contexts, docs_text="request_timeout_s and ghost_knob")
    assert list(rule.check_graph(documented, contexts)) == []
    partial = ProjectGraph.build(contexts, docs_text="request_timeout_s")
    findings = list(rule.check_graph(partial, contexts))
    assert len(findings) == 1
    assert "ghost_knob" in findings[0].message
    assert "no docs/*.md" in findings[0].message
    no_docs = ProjectGraph.build(contexts)   # fixture paths: no docs dir
    assert no_docs.docs_text is None
    assert list(rule.check_graph(no_docs, contexts)) == []


def test_config_liveness_allow_on_declaration_line_suppresses():
    source = CONFIG_FIXTURE.replace(
        "ghost_knob: int = 3",
        "ghost_knob: int = 3  "
        "# lint: allow[config-key-liveness] read via f-string getattr")
    result = run(ConfigKeyLivenessRule(), {
        "pkg/config.py": source,
        "pkg/server.py": "def f(s):\n    return s.request_timeout_s\n"})
    assert result.findings == []
    assert len(result.suppressed) == 1


# ---------------------------------------------- metric-label-cardinality

METRIC_REGISTRY = """
    from prometheus_client import Counter

    class PrometheusRegistry:
        def __init__(self):
            self.llm_tpot = Counter("llm_tpot", "d", ["tenant", "phase"])
            self.http_total = Counter("http_total", "d", ["code"])
"""


def test_metric_labels_flag_unclamped_tenant_value():
    result = run(MetricLabelCardinalityRule(), {
        "pkg/observability/metrics.py": METRIC_REGISTRY,
        "pkg/engine.py": """
            class Engine:
                def emit(self, reg, request):
                    reg.llm_tpot.labels(request.tenant, "decode").inc()
        """})
    assert len(result.findings) == 1
    f = result.findings[0]
    assert f.path == "pkg/engine.py"
    assert "not provably" in f.message and "llm_tpot" in f.message


def test_metric_labels_splat_flags_even_on_bare_name_receiver():
    """metering's generic _child: ``metric.labels(**labels)`` — the
    splat hides every value from the proof regardless of receiver
    shape or which metric flows in."""
    result = run(MetricLabelCardinalityRule(), {
        "pkg/observability/metrics.py": METRIC_REGISTRY,
        "pkg/observability/metering.py": """
            def child(metric, labels):
                return metric.labels(**labels)
        """})
    assert len(result.findings) == 1
    assert "labels(**...)" in result.findings[0].message


def test_metric_labels_clean_for_every_clamp_idiom():
    result = run(MetricLabelCardinalityRule(), {
        "pkg/observability/metrics.py": METRIC_REGISTRY,
        "pkg/engine.py": """
            class Engine:
                def _tenant_label(self, t):
                    return self._tenant_clamp.label(t)

                def emit(self, reg, request):
                    reg.llm_tpot.labels(
                        self._tenant_clamp.label(request.tenant),
                        "decode").inc()
                    t = self._tenant_clamp.label(request.tenant)
                    reg.llm_tpot.labels(t, "prefill").inc()
                    reg.llm_tpot.labels(self._tenant_label(request.tenant),
                                        "queue").inc()
                    reg.llm_tpot.labels(tenant="other", phase="x").inc()
                    reg.http_total.labels(request.code).inc()
        """})
    assert result.findings == [], result.findings


def test_metric_labels_tenant_keyword_position_is_checked():
    result = run(MetricLabelCardinalityRule(), {
        "pkg/observability/metrics.py": METRIC_REGISTRY,
        "pkg/engine.py": """
            class Engine:
                def emit(self, reg, request):
                    reg.llm_tpot.labels(tenant=request.tenant,
                                        phase="decode").inc()
        """})
    assert len(result.findings) == 1


def test_metric_labels_allow_states_where_the_clamp_happened():
    result = run(MetricLabelCardinalityRule(), {
        "pkg/observability/metrics.py": METRIC_REGISTRY,
        "pkg/observability/metering.py": """
            def child(metric, labels):
                return metric.labels(**labels)  # lint: allow[metric-label-cardinality] values pre-clamped by _label_for
        """})
    assert result.findings == []
    assert len(result.suppressed) == 1


def test_metric_labels_silent_without_metric_declarations():
    result = run(MetricLabelCardinalityRule(), {
        "pkg/engine.py": """
            class Engine:
                def emit(self, reg, request):
                    reg.llm_tpot.labels(request.tenant, "decode").inc()
        """})
    assert result.findings == []
