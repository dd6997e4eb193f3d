"""Closed-loop serving controller (tpu_local/controller.py) and the
live signal bus (observability/signals.py) it steers by.

The satellite-3 focus: the SLO burn-rate edge cases FEEDING the
controller. A burn the evaluator labels unmeasurable — empty first
window with no lifetime data, or a target above the histogram's top
finite bucket — must publish NOTHING onto the bus, and every downstream
ladder must HOLD (no decision row, no shed-bar move). A controller that
acts on a vacuous number is worse than no controller.
"""

import types

from mcp_context_forge_tpu.observability.metrics import PrometheusRegistry
from mcp_context_forge_tpu.observability.signals import (GATEWAY_REPLICA,
                                                         SignalBus)
from mcp_context_forge_tpu.observability.slo import (SloClass, SloEvaluator,
                                                     SloObjective)
from mcp_context_forge_tpu.tpu_local.controller import (RING_SCHEMA,
                                                        ServingController)


class FakeEngine:
    """Engine-shaped stub: warmed grids + a request_knobs that applies
    (or refuses) like the real drain-barrier path."""

    def __init__(self, rid="0", superstep=8, warmed_k=(1, 4, 8),
                 spec_built=False, spec_enabled=False):
        self.config = types.SimpleNamespace(replica_id=rid)
        self.state = {
            "superstep": superstep,
            "spec_built": spec_built,
            "spec_enabled": spec_enabled,
            "warmed_k": sorted(warmed_k),
        }
        self.requests = []
        self.accept = True

    def knob_state(self):
        return dict(self.state)

    def request_knobs(self, **kwargs):
        self.requests.append(kwargs)
        out = {}
        for key, value in kwargs.items():
            out[key] = self.accept
            if self.accept:
                if key == "spec_enabled":
                    self.state["spec_enabled"] = bool(value)
                else:
                    self.state[key] = value
        return out


class FakeShedder:
    enabled = True

    def __init__(self, shed_at=0.9):
        self.shed_at = shed_at


def _rig(engine=None, *, shedder=None, slo=None, metrics=None, **kw):
    """(clock cell, bus, controller) with a shared injectable clock."""
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    bus = SignalBus(clock=clock)
    engines = [engine] if engine is not None else []
    defaults = dict(tick_s=0.1, cooldown_s=1.0, eval_window_s=0.5,
                    hysteresis=0.25, queue_wait_high_ms=100.0,
                    queue_wait_low_ms=10.0, idle_frac_high=0.3,
                    burn_high=1.0, burn_low=0.25,
                    shed_floor=0.5, shed_step=0.05, clock=clock)
    defaults.update(kw)
    ctrl = ServingController(bus, lambda: engines, shedder=shedder,
                             slo_evaluator=slo, metrics=metrics, **defaults)
    return t, bus, ctrl


def _publish(bus, name, value, replica="0", n=6):
    for _ in range(n):
        bus.publish(name, value, replica)


# ------------------------------------------------------------- signal bus

def test_bus_aggregates_and_staleness():
    t = [0.0]
    bus = SignalBus(window=4, ewma_alpha=0.5, clock=lambda: t[0])
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        bus.publish("llm.queue_wait_ms", v, "0")
    view = bus.get("llm.queue_wait_ms", "0")
    # window bounded at 4: the 1.0 fell off; count keeps the full tally
    assert view["n"] == 4 and view["count"] == 5
    assert view["min"] == 2.0 and view["max"] == 5.0 and view["last"] == 5.0
    # nearest-rank convention (same as the SLO evaluator): over a
    # 4-sample window the 0.95 rank lands one below the max
    assert view["p95"] == 4.0
    assert view["age_s"] == 0.0
    t[0] = 7.5
    assert bus.get("llm.queue_wait_ms", "0")["age_s"] == 7.5
    # the staleness-guarded read path the controller uses
    assert bus.ewma("llm.queue_wait_ms", "0", max_age_s=5.0) is None
    assert bus.ewma("llm.queue_wait_ms", "0", max_age_s=10.0) is not None
    assert bus.get("llm.queue_wait_ms", "1") is None


def test_bus_series_cap_drops_never_grows():
    bus = SignalBus(max_series=2)
    bus.publish("a", 1.0, "0")
    bus.publish("b", 1.0, "0")
    bus.publish("c", 1.0, "0")  # past the cap: counted, dropped
    stats = bus.stats()
    assert stats["series"] == 2 and stats["dropped"] == 1
    assert bus.get("c", "0") is None
    # existing series still accept publishes at the cap
    bus.publish("a", 2.0, "0")
    assert bus.get("a", "0")["last"] == 2.0


def test_bus_snapshot_keys_and_prefix():
    bus = SignalBus()
    bus.publish("llm.mfu", 0.4, "0")
    bus.publish("slo.burn_rate", 2.0)
    snap = bus.snapshot()
    assert set(snap) == {"llm.mfu@0", f"slo.burn_rate@{GATEWAY_REPLICA}"}
    assert set(bus.snapshot(prefix="slo.")) == {
        f"slo.burn_rate@{GATEWAY_REPLICA}"}


# --------------------------------------------------------- superstep ladder

def test_superstep_steps_down_on_queue_wait():
    engine = FakeEngine(superstep=8)
    t, bus, ctrl = _rig(engine)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    (row,) = ctrl.tick()
    assert row["knob"] == "superstep" and row["direction"] == "down"
    assert row["from"] == 8 and row["to"] == 4  # ONE rung, not a jump to 1
    assert row["actuated"] is True
    assert engine.requests == [{"superstep": 4}]
    assert engine.state["superstep"] == 4
    # the audit row stands alone: schema + the triggering evidence
    assert row["schema"] == RING_SCHEMA
    assert row["signals"]["llm.queue_wait_ms.p95"] == 400.0
    assert ctrl.decisions(1)[0]["seq"] == row["seq"]


def test_superstep_steps_up_when_calm_and_host_bound():
    engine = FakeEngine(superstep=4)
    t, bus, ctrl = _rig(engine)
    _publish(bus, "llm.queue_wait_ms", 2.0)
    _publish(bus, "llm.idle_frac", 0.6)
    (row,) = ctrl.tick()
    assert (row["knob"], row["direction"], row["to"]) == ("superstep",
                                                          "up", 8)
    assert engine.state["superstep"] == 8


def test_superstep_holds_without_a_warmed_ladder():
    # single-rung grid (no k_ladder configured): adaptive K never moves
    engine = FakeEngine(superstep=8, warmed_k=(8,))
    t, bus, ctrl = _rig(engine)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    assert ctrl.tick() == []
    assert engine.requests == []


def test_cooldown_blocks_then_releases():
    engine = FakeEngine(superstep=8)
    t, bus, ctrl = _rig(engine, cooldown_s=5.0)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    assert len(ctrl.tick()) == 1
    t[0] = 1.0
    _publish(bus, "llm.queue_wait_ms", 400.0)
    assert ctrl.tick() == []            # inside cooldown: hold
    t[0] = 6.0
    _publish(bus, "llm.queue_wait_ms", 400.0)
    (row,) = ctrl.tick()                # released: next rung down
    assert (row["from"], row["to"]) == (4, 1)


def test_reversal_hysteresis_demands_extra_margin():
    engine = FakeEngine(superstep=8)
    t, bus, ctrl = _rig(engine, cooldown_s=0.0, hysteresis=0.25)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    assert ctrl.tick()[0]["direction"] == "down"
    # reversal (up) trigger barely over threshold: 0.33 < 0.3*1.25 —
    # hold. (n=64 floods the window so the old 400 ms samples are gone
    # and the queue reads calm.)
    t[0] = 1.0
    _publish(bus, "llm.queue_wait_ms", 2.0, n=64)
    _publish(bus, "llm.idle_frac", 0.33, n=64)
    assert ctrl.tick() == []
    # clears the margined threshold: the reversal is allowed
    _publish(bus, "llm.idle_frac", 0.9, n=64)
    (row,) = ctrl.tick()
    assert row["direction"] == "up"


def test_stale_signals_hold_position():
    engine = FakeEngine(superstep=8)
    t, bus, ctrl = _rig(engine, tick_s=1.0, eval_window_s=2.0)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    t[0] = 60.0  # a dead replica's last breath is not a signal
    assert ctrl.tick() == []
    assert engine.requests == []


def test_safe_mode_records_without_actuating():
    engine = FakeEngine(superstep=8)
    t, bus, ctrl = _rig(engine, safe_mode=True)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    (row,) = ctrl.tick()
    assert row["direction"] == "down" and row["safe_mode"] is True
    assert row["actuated"] is False
    assert engine.requests == []        # the engine never heard about it
    assert engine.state["superstep"] == 8


def test_engine_refusal_records_hold_rejected_and_skips_cooldown():
    engine = FakeEngine(superstep=8)
    engine.accept = False               # the warmed-grid rail holds
    t, bus, ctrl = _rig(engine, cooldown_s=5.0)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    (row,) = ctrl.tick()
    assert row["direction"] == "hold_rejected" and row["actuated"] is False
    # a refusal must not burn the cooldown: the controller may re-ask
    t[0] = 0.2
    _publish(bus, "llm.queue_wait_ms", 400.0)
    assert ctrl.tick()[0]["direction"] == "hold_rejected"


# ------------------------------------------------------------- other knobs

def test_spec_disables_on_low_acceptance_and_reprobes():
    engine = FakeEngine(superstep=8, warmed_k=(8,), spec_built=True,
                        spec_enabled=True)
    t, bus, ctrl = _rig(engine, cooldown_s=1.0)
    _publish(bus, "llm.spec_accept", 0.1)
    (row,) = ctrl.tick()
    assert (row["knob"], row["direction"]) == ("spec", "off")
    assert engine.state["spec_enabled"] is False
    # off, acceptance unobservable: after reprobe_after_s it re-enables
    t[0] = ctrl.reprobe_after_s + 2.0
    (row,) = ctrl.tick()
    assert (row["knob"], row["direction"]) == ("spec", "on")
    assert engine.state["spec_enabled"] is True


def test_shed_bar_tightens_on_burn_and_relaxes_to_ceiling():
    shedder = FakeShedder(shed_at=0.9)
    t, bus, ctrl = _rig(shedder=shedder, cooldown_s=0.0)
    _publish(bus, "slo.burn_rate", 3.0, replica=GATEWAY_REPLICA)
    (row,) = ctrl.tick()
    assert (row["knob"], row["direction"]) == ("shed_bar", "down")
    assert abs(shedder.shed_at - 0.85) < 1e-9
    # burn collapses: the bar relaxes back toward the STATIC ceiling,
    # never past it
    _publish(bus, "slo.burn_rate", 0.0, replica=GATEWAY_REPLICA, n=60)
    for _ in range(10):
        t[0] += 0.1
        ctrl.tick()
    assert abs(shedder.shed_at - 0.9) < 1e-9
    snap = ctrl.snapshot()
    assert snap["shed_ceiling"] == 0.9 and snap["shed_bar"] == 0.9


def test_shed_bar_respects_floor():
    shedder = FakeShedder(shed_at=0.55)
    t, bus, ctrl = _rig(shedder=shedder, cooldown_s=0.0, shed_floor=0.5)
    _publish(bus, "slo.burn_rate", 5.0, replica=GATEWAY_REPLICA, n=30)
    for _ in range(10):
        t[0] += 0.1
        _publish(bus, "slo.burn_rate", 5.0, replica=GATEWAY_REPLICA)
        ctrl.tick()
    assert shedder.shed_at >= 0.5 - 1e-9  # premium admission never dies


# ----------------------------------------- SLO burn feeding the controller
# (satellite 3: the evaluator edge cases the loop must HOLD on)

def _ttft_evaluator(budget=0.05, **kw):
    metrics = PrometheusRegistry()
    evaluator = SloEvaluator(
        metrics, [SloObjective("ttft_p95", "llm_ttft", 0.95, 1000.0)],
        error_budget=budget, **kw)
    return metrics, evaluator


def _observe_ttft(metrics, seconds, n=1, tenant="unattributed"):
    for _ in range(n):
        metrics.llm_ttft.labels(
            model="m", replica="0",
            tenant=metrics.tenant_clamp.label(tenant)).observe(seconds)


def test_vacuous_first_window_publishes_nothing_and_holds():
    """Empty first window AND no lifetime data: burn is unmeasurable.
    Nothing lands on the bus, and the shed ladder emits NO decision —
    the hold is the controller's answer to a vacuous SLO."""
    metrics, evaluator = _ttft_evaluator()
    shedder = FakeShedder(shed_at=0.9)
    t, bus, ctrl = _rig(shedder=shedder, slo=evaluator, cooldown_s=0.0)
    assert ctrl.tick() == []
    assert bus.get("slo.burn_rate", GATEWAY_REPLICA) is None
    assert shedder.shed_at == 0.9
    assert ctrl.decisions(8) == []


def test_target_above_buckets_is_vacuous_not_a_burn():
    """A target beyond the top finite histogram bucket makes fraction-
    over optimistic fiction: the objective is excluded from the burn
    feed entirely (acting on it would steer by an unmeasurable number).
    """
    metrics = PrometheusRegistry()
    # llm_tpot's top finite bucket is 2.5 s; a 60 s target is unmeasurable
    evaluator = SloEvaluator(
        metrics, [SloObjective("tpot_p95", "llm_tpot", 0.95, 60000.0)],
        error_budget=0.05)
    for _ in range(20):
        metrics.llm_tpot.labels(model="m", replica="0",
                                tenant="unattributed").observe(3.0)
    shedder = FakeShedder(shed_at=0.9)
    t, bus, ctrl = _rig(shedder=shedder, slo=evaluator, cooldown_s=0.0)
    assert ctrl.tick() == []
    assert bus.get("slo.burn_rate", GATEWAY_REPLICA) is None
    assert shedder.shed_at == 0.9


def test_first_window_with_lifetime_data_burns_from_lifetime():
    """Empty first window but real from-boot samples: the evaluator
    falls back to lifetime buckets (labeled window_samples == 0) and the
    burn IS actionable — a gateway that has been breaching since boot
    must not read as healthy just because the controller booted late."""
    metrics, evaluator = _ttft_evaluator()
    _observe_ttft(metrics, 2.0, n=20)       # every sample over the 1 s target
    t, bus, ctrl = _rig(slo=evaluator)
    ctrl.tick()
    view = bus.get("slo.burn_rate", GATEWAY_REPLICA)
    assert view is not None
    assert view["last"] == 20.0             # fraction 1.0 / budget 0.05


def test_post_eviction_reappearance_restarts_the_window():
    """The evaluator bounds its consumer table; a controller evicted by
    16 other consumers re-appears as a FIRST SIGHT — empty window, burn
    from lifetime. The bus keeps receiving a measurable burn (no gap in
    the feed) and no stale from-boot delta is dressed up as a window."""
    metrics, evaluator = _ttft_evaluator()
    _observe_ttft(metrics, 2.0, n=10)
    t, bus, ctrl = _rig(slo=evaluator)
    ctrl.tick()
    assert bus.get("slo.burn_rate", GATEWAY_REPLICA)["last"] == 20.0
    # crowd the table until the controller's window snapshot is evicted
    for i in range(SloEvaluator.MAX_CONSUMERS + 2):
        evaluator.evaluate(consumer=f"crowd-{i}")
    assert not any(k.startswith("controller") for k in evaluator._prev)
    _observe_ttft(metrics, 2.0, n=5)
    t[0] = 0.5
    ctrl.tick()
    view = bus.get("slo.burn_rate", GATEWAY_REPLICA)
    assert view["count"] == 2 and view["last"] == 20.0


def test_tenant_class_burn_publishes_per_class_slice():
    """slo.burn_rate.<class> series: one bus slice per assigned tenant
    class, evaluated against that tenant's metric label slice only."""
    metrics = PrometheusRegistry()
    premium = SloClass("premium", ttft_p95_ms=100.0, tpot_p95_ms=250.0,
                       http_p95_ms=1000.0)
    evaluator = SloEvaluator(
        metrics, [SloObjective("ttft_p95", "llm_ttft", 0.95, 30000.0)],
        error_budget=0.05,
        slo_classes={"premium": premium},
        tenant_classes={"t-prem": "premium"},
        tenant_label=metrics.tenant_clamp.label)
    # t-prem breaches ITS class target (100 ms) while the overall
    # objective (30 s) stays green
    _observe_ttft(metrics, 0.5, n=10, tenant="t-prem")
    t, bus, ctrl = _rig(slo=evaluator)
    ctrl.tick()
    overall = bus.get("slo.burn_rate", GATEWAY_REPLICA)
    sliced = bus.get("slo.burn_rate.premium", GATEWAY_REPLICA)
    assert overall is not None and overall["last"] == 0.0
    assert sliced is not None and sliced["last"] == 20.0


# ------------------------------------------------------------ audit surface

def test_effect_settles_after_eval_window():
    engine = FakeEngine(superstep=8)
    t, bus, ctrl = _rig(engine, eval_window_s=0.5, cooldown_s=10.0)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    (row,) = ctrl.tick()
    assert row["effect"] is None        # not judged yet
    _publish(bus, "llm.queue_wait_ms", 50.0, n=20)
    t[0] = 1.0
    ctrl.tick()
    effect = row["effect"]
    assert effect is not None
    judged = effect["llm.queue_wait_ms@0"]
    assert judged["after"] < judged["before"]   # the move helped


def test_ring_is_bounded_and_newest_first():
    engine = FakeEngine(superstep=8, warmed_k=(4, 8))
    t, bus, ctrl = _rig(engine, cooldown_s=0.0, hysteresis=0.0,
                        ring_size=8)
    for i in range(20):
        t[0] = float(i)
        if i % 2 == 0:      # saturate: step down (flush the window)
            _publish(bus, "llm.queue_wait_ms", 400.0, n=64)
        else:               # calm + host-bound: step back up
            _publish(bus, "llm.queue_wait_ms", 2.0, n=64)
            _publish(bus, "llm.idle_frac", 0.9, n=64)
        ctrl.tick()
    rows = ctrl.decisions(64)
    assert len(rows) == 8   # 20 decisions made, ring keeps the newest 8
    assert rows[0]["seq"] > rows[-1]["seq"]


def test_decision_metrics_and_snapshot():
    metrics = PrometheusRegistry()
    engine = FakeEngine(superstep=8)
    t, bus, ctrl = _rig(engine, metrics=metrics)
    _publish(bus, "llm.queue_wait_ms", 400.0)
    ctrl.tick()
    text = metrics.render()[0].decode()
    assert ('mcpforge_controller_decisions_total{'
            'direction="down",knob="superstep"} 1.0') in text
    assert 'mcpforge_controller_knob{knob="superstep",replica="0"} 4.0' \
        in text
    snap = ctrl.snapshot()
    assert snap["enabled"] is True and snap["safe_mode"] is False
    assert snap["ticks"] == 1
    assert snap["knobs"]["0"]["superstep"] == 4
    assert snap["decisions"][0]["knob"] == "superstep"
    assert "llm.queue_wait_ms@0" in snap["signals"]
    assert snap["bus"]["series"] >= 1
