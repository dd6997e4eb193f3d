"""Flight-recorder internals: PhaseClock self-time accounting, ring
bounds under churn, slowest-N retention, loop-lag sampling, and the
backpressure helpers — the pure-python layer under the gateway
middleware (tests/integration/test_gateway_flight_recorder.py covers
the wired end-to-end behavior)."""

import asyncio
import logging
import time

import pytest

from mcp_context_forge_tpu.gateway.flight_recorder import (FlightRecorder,
                                                           LoopLagSampler,
                                                           queue_state,
                                                           retry_after_s)
from mcp_context_forge_tpu.observability import phases
from mcp_context_forge_tpu.observability.metrics import PrometheusRegistry


# ------------------------------------------------------------- PhaseClock

def test_phase_clock_add_and_vector():
    clock = phases.PhaseClock()
    clock.add("db", 0.010)
    clock.add("db", 0.005)
    clock.add("auth", 0.001)
    assert clock.vector_ms() == {"auth": 1.0, "db": 15.0}
    assert abs(clock.total() - 0.016) < 1e-9


def test_phase_clock_nesting_is_self_time():
    """A child phase's wall must be SUBTRACTED from its enclosing phase:
    the vector sums to elapsed wall, never more (the invariant the
    end-to-end sum≈wall gate rests on)."""
    clock = phases.PhaseClock()
    with clock.phase("outer"):
        time.sleep(0.02)
        with clock.phase("inner"):
            time.sleep(0.02)
        time.sleep(0.01)
    total = clock.total()
    assert set(clock.phases) == {"outer", "inner"}
    assert clock.phases["inner"] >= 0.018
    assert clock.phases["outer"] >= 0.025
    # no double counting: outer's self time excludes inner entirely
    assert total < 0.09
    assert clock.phases["outer"] < 0.05


def test_phase_clock_add_inside_phase_counts_as_child():
    clock = phases.PhaseClock()
    with clock.phase("outer"):
        clock.add("db", 0.5)  # pre-measured work inside the block
    assert clock.phases["db"] == 0.5
    assert clock.phases["outer"] < 0.1  # NOT charged the db half-second


def test_contextvar_helpers_no_op_without_clock():
    phases.add_phase("db", 1.0)  # must not raise
    with phases.phase("engine"):
        pass
    assert phases.current_phases() is None


def test_contextvar_clock_reaches_producers():
    clock = phases.PhaseClock()
    token = phases.set_phase_clock(clock)
    try:
        phases.add_phase("db", 0.25)
        with phases.phase("plugins"):
            time.sleep(0.001)
    finally:
        phases.reset_phase_clock(token)
    assert clock.phases["db"] == 0.25
    assert clock.phases["plugins"] > 0.0
    assert phases.current_phases() is None


# ------------------------------------------------------- PhaseClock marks

def test_a_mark_keeps_the_first_instant_under_its_name():
    clock = phases.PhaseClock()
    before = time.perf_counter()
    first = clock.mark("recv")
    assert before <= first <= time.perf_counter()
    assert clock.mark("recv") == first and clock.mark("recv", 1.0) == first
    assert clock.mark("authed", 7.5) == 7.5
    assert clock.marks == {"recv": first, "authed": 7.5}
    assert clock.phases == {}               # a mark charges no bucket


def test_a_phase_block_hands_its_end_to_the_mark_it_names():
    """One reading for the bucket's end and the mark: the mark plus nothing
    is the block's end, and a second block under the same mark keeps the
    first (``serialize`` runs a chunk; ``written`` is the first chunk's)."""
    clock = phases.PhaseClock()
    token = phases.set_phase_clock(clock)
    try:
        started = time.perf_counter()
        with phases.phase("serialize", mark="written"):
            time.sleep(0.002)
        ended = time.perf_counter()
        with phases.phase("serialize", mark="written"):
            pass
        with phases.phase("auth"):
            pass
    finally:
        phases.reset_phase_clock(token)
    written = clock.marks["written"]
    assert started + 0.002 <= written <= ended
    assert set(clock.marks) == {"written"}
    # the bucket saw the same end: it holds at most start -> mark (+ the
    # second, empty block)
    assert clock.phases["serialize"] <= written - started + 1e-4


class _Ring:
    """What a clock is tied to: ``StepTimeline.stamp``'s signature."""

    def __init__(self):
        self.stamps = []

    def stamp(self, phase, request_id, slot, t=None):
        self.stamps.append((phase, request_id, slot, t))
        return t


def test_tie_hands_over_the_marks_taken_before_the_id_with_their_own_times():
    clock, ring = phases.PhaseClock(), _Ring()
    clock.mark("recv", 1.0)
    clock.mark("authed", 2.0)
    assert ring.stamps == []                # never tied: no stamp, as a shed
    clock.tie(ring.stamp, "q7")
    assert ring.stamps == [("recv", "q7", -1, 1.0), ("authed", "q7", -1, 2.0)]
    clock.mark("chunk", 3.0)                # from here on straight to the ring
    clock.mark("chunk", 4.0)                # the first is kept, and sent once
    assert ring.stamps[2:] == [("chunk", "q7", -1, 3.0)]
    other = _Ring()
    clock.tie(other.stamp, "q8")            # one request, one id
    clock.mark("written", 5.0)
    assert other.stamps == [] and ring.stamps[-1] == ("written", "q7", -1, 5.0)


# ---------------------------------------------------------- FlightRecorder

def _record(recorder, duration_s, path="/x", status=200, **kw):
    return recorder.record(method="GET", path=path, route=path,
                           status=status, duration_s=duration_s,
                           phases_ms={"handler": duration_s * 1e3}, **kw)


def test_rings_stay_bounded_under_churn():
    recorder = FlightRecorder(ring_size=8, slowest_size=4,
                              slow_request_s=0.0)
    for i in range(1000):
        _record(recorder, duration_s=i / 1e5)
    assert len(recorder.recent) == 8
    assert len(recorder.slowest()) == 4
    assert recorder.recorded == 1000


def test_slowest_retention_survives_fast_churn():
    """The tail outliers must SURVIVE later fast traffic — that is the
    whole point of a separate slowest-N ring."""
    recorder = FlightRecorder(ring_size=4, slowest_size=3,
                              slow_request_s=0.0)
    _record(recorder, duration_s=9.0, path="/slowest")
    _record(recorder, duration_s=7.0, path="/slow2")
    _record(recorder, duration_s=8.0, path="/slow1")
    for _ in range(100):
        _record(recorder, duration_s=0.001)
    slowest = recorder.slowest()
    assert [e["path"] for e in slowest] == ["/slowest", "/slow1", "/slow2"]
    # ...while the recency ring has long forgotten them
    assert all(e["path"] == "/x" for e in recorder.recent)


def test_slow_request_logs_phase_vector_and_trace(caplog):
    recorder = FlightRecorder(ring_size=4, slowest_size=2,
                              slow_request_s=0.05)
    with caplog.at_level(logging.WARNING,
                         logger="mcp_context_forge_tpu.gateway."
                                "flight_recorder"):
        entry = recorder.record(
            method="POST", path="/v1/chat/completions", route="/v1/chat",
            status=200, duration_s=0.2,
            phases_ms={"engine": 180.0, "handler": 20.0},
            trace_id="ab" * 16, span_id="cd" * 8)
    assert recorder.slow_requests == 1
    assert entry["trace_id"] == "ab" * 16
    record = next(r for r in caplog.records if "slow request" in r.message)
    # the phase vector rides the line (never a bare duration again), and
    # the explicit trace ctx joins it to the OTel trace
    assert "engine" in record.getMessage()
    assert record.ctx["trace_id"] == "ab" * 16


def test_fast_requests_do_not_log(caplog):
    recorder = FlightRecorder(slow_request_s=10.0)
    with caplog.at_level(logging.WARNING):
        _record(recorder, duration_s=0.01)
    assert recorder.slow_requests == 0
    assert not [r for r in caplog.records if "slow request" in r.message]


def test_inflight_registry_and_longest():
    recorder = FlightRecorder()
    rid1 = recorder.start_request("/old", ("t1" * 16, "s1" * 8))
    time.sleep(0.01)
    rid2 = recorder.start_request("/new", None)
    culprit = recorder.longest_inflight()
    assert culprit["path"] == "/old"
    assert culprit["trace"][0] == "t1" * 16
    recorder.finish_request(rid1)
    assert recorder.longest_inflight()["path"] == "/new"
    recorder.finish_request(rid2)
    assert recorder.longest_inflight() is None
    assert recorder.inflight == {}


def test_snapshot_shape_and_metrics_observed():
    metrics = PrometheusRegistry()
    recorder = FlightRecorder(metrics, ring_size=4, slowest_size=2,
                              slow_request_s=0.001)
    recorder.record(method="GET", path="/a", route="/a", status=500,
                    duration_s=0.5, phases_ms={"error": 500.0},
                    tenant="team:t1", error="RuntimeError")
    snap = recorder.snapshot(limit=8)
    assert snap["recorded"] == 1 and snap["slow_requests"] == 1
    assert snap["slowest"][0]["error"] == "RuntimeError"
    assert snap["recent"][0]["status"] == 500
    # rows carry the EXACT tenant; the Prometheus label is clamped
    assert snap["recent"][0]["tenant"] == "team:t1"
    rendered = metrics.render()[0].decode()
    assert ('mcpforge_gw_request_phase_seconds_count{phase="error",'
            'route="/a",tenant="team:t1"} 1.0') in rendered
    assert 'mcpforge_gw_slow_requests_total{route="/a"} 1.0' in rendered


def test_snapshot_tenant_filter():
    recorder = FlightRecorder(None, ring_size=8, slowest_size=4)
    for tenant in ("team:a", "team:b", "team:a", None):
        recorder.record(method="GET", path="/x", route="/x", status=200,
                        duration_s=0.01, phases_ms={"handler": 10.0},
                        tenant=tenant)
    snap = recorder.snapshot(limit=8, tenant="team:a")
    assert snap["tenant"] == "team:a"
    assert len(snap["recent"]) == 2
    assert all(r["tenant"] == "team:a" for r in snap["recent"])
    assert all(r.get("tenant") == "team:a" for r in snap["slowest"])
    # unfiltered snapshot still returns everything
    assert len(recorder.snapshot(limit=8)["recent"]) == 4


# --------------------------------------------------------- LoopLagSampler

def test_loop_lag_sampler_measures_blocked_loop(caplog):
    """A synchronous sleep on the loop must show up as lag ≥ the block,
    and the long-callback warning must name the in-flight culprit with
    its trace ids (the log↔trace join satellite)."""
    metrics = PrometheusRegistry()
    recorder = FlightRecorder()

    async def main():
        sampler = LoopLagSampler(metrics, interval_s=0.02, warn_s=0.05,
                                 recorder=recorder)
        await sampler.start()
        rid = recorder.start_request("/culprit", ("ee" * 16, "ff" * 8))
        await asyncio.sleep(0.05)      # let a clean tick land
        time.sleep(0.15)               # BLOCK the loop (the bug class)
        await asyncio.sleep(0.05)      # lagged tick fires + observes
        recorder.finish_request(rid)
        await sampler.stop()
        return sampler

    with caplog.at_level(logging.WARNING):
        sampler = asyncio.run(main())
    assert sampler.samples >= 2
    assert sampler.max_lag_s >= 0.1
    assert sampler.long_callbacks >= 1
    snap = sampler.snapshot()
    assert snap["max_lag_ms"] >= 100.0
    record = next(r for r in caplog.records if "event loop lagged" in
                  r.message)
    assert "/culprit" in record.getMessage()
    assert record.ctx["trace_id"] == "ee" * 16
    rendered = metrics.render()[0].decode()
    assert "mcpforge_gw_loop_lag_seconds_count" in rendered


def test_loop_lag_quiet_loop_stays_quiet(caplog):
    async def main():
        sampler = LoopLagSampler(interval_s=0.01, warn_s=0.2)
        await sampler.start()
        await asyncio.sleep(0.08)
        await sampler.stop()
        return sampler

    with caplog.at_level(logging.WARNING):
        sampler = asyncio.run(main())
    assert sampler.samples >= 3
    assert sampler.long_callbacks == 0
    assert not [r for r in caplog.records if "event loop lagged" in
                r.message]


def _blocked_tick(interval_s=0.01, block_s=0.03, lead_s=0.003):
    """Run a sampler beside a live step timeline and block the loop for
    ``block_s`` from ``lead_s`` before a tick is due. Returns the sampler,
    the timeline, the block's (start, end) and when the tick before it ran;
    None where the loop was too late to set that up (a loaded machine)."""
    from mcp_context_forge_tpu.observability.timeline import StepTimeline

    async def main():
        timeline = StepTimeline("lag-test")
        sampler = LoopLagSampler(interval_s=interval_s, warn_s=0.0)
        ticks = []
        observe = sampler._observe

        def observed(lag, ran):
            ticks.append(ran)
            observe(lag, ran)

        sampler._observe = observed
        await sampler.start()
        try:
            seen = len(ticks)
            while len(ticks) == seen:           # a tick has just run
                await asyncio.sleep(0)
            await asyncio.sleep(interval_s - lead_s)
            start = time.perf_counter()
            if start > ticks[-1] + interval_s or len(ticks) != seen + 1:
                return None
            time.sleep(block_s)                 # the bug class: a blocked loop
            end = time.perf_counter()
            await asyncio.sleep(interval_s)     # the late tick lands
        finally:
            await sampler.stop()
        return sampler, timeline, (start, end), ticks[seen]

    return asyncio.run(main())


def test_a_blocked_loop_leaves_one_loop_lag_pause_that_covers_the_block():
    """30 ms of ``time.sleep`` in a callback, begun 3 ms before a tick was
    due: one ``loop_lag`` pause of 25-40 ms on the live ring, from when the
    tick was due (inside the block's first milliseconds) to when it ran
    (after the block), on ``perf_counter``, with the loop's thread."""
    import threading

    for _attempt in range(5):
        ran = _blocked_tick()
        if ran is not None and ran[2][1] - ran[2][0] < 0.036:
            break
    else:
        pytest.skip("the loop never woke in time to place the block")
    sampler, timeline, (start, end), tick = ran
    lags = [p for p in timeline.snapshot()["pause"]
            if p.cause == "loop_lag" and p.t1 > start and p.t0 < end]
    assert len(lags) == 1
    lag = lags[0]
    assert 0.025 <= lag.t1 - lag.t0 <= 0.040
    assert lag.t0 == pytest.approx(tick + 0.01, abs=0.002)      # when due
    assert start <= lag.t0 <= start + 0.005 and lag.t1 >= end   # covers it
    assert lag.detail == 0 and lag.thread == threading.current_thread().name
    assert timeline.pauses_between(start, end) == [lag]
    assert sampler.max_lag_s == pytest.approx(lag.t1 - lag.t0, abs=1e-9)


def test_a_quiet_loop_and_a_process_without_rings_leave_no_pause(monkeypatch):
    """Below the ring's floor nothing is written; and where no step timeline
    module was ever imported (a gateway without an engine) the sampler
    imports none and counts as before."""
    import sys

    from mcp_context_forge_tpu.observability import timeline as tl

    ring = tl.StepTimeline("lag-quiet")
    sampler = LoopLagSampler()
    sampler._observe(tl.PAUSE_S * 0.9, 50.0)
    assert ring.snapshot()["pause"] == []
    sampler._observe(tl.PAUSE_S, 50.0)
    assert [(p.cause, p.t0, p.t1) for p in ring.snapshot()["pause"]] == [
        ("loop_lag", 50.0 - tl.PAUSE_S, 50.0)]
    monkeypatch.delitem(sys.modules, tl.__name__)
    sampler._observe(0.5, 60.0)
    assert tl.__name__ not in sys.modules
    assert len(ring.snapshot()["pause"]) == 1
    assert sampler.samples == 3 and sampler.max_lag_s == 0.5


# ------------------------------------------------------------ backpressure

class _Stats:
    def __init__(self, depth):
        self.queue_depth = depth


class _Cfg:
    def __init__(self, max_queue):
        self.max_queue = max_queue


class _Engine:
    def __init__(self, depth, max_queue):
        self.stats = _Stats(depth)
        self.config = _Cfg(max_queue)


class _Replica:
    def __init__(self, depth, max_queue, state="ready"):
        self.engine = _Engine(depth, max_queue)
        self.state = state


class _Pool:
    def __init__(self, replicas):
        self.replicas = replicas


def test_queue_state_single_engine():
    app = {"tpu_engine": _Engine(depth=25, max_queue=100)}
    state = queue_state(app)
    assert state == {"depth": 25, "capacity": 100, "saturation": 0.25}


def test_queue_state_pool_sums_ready_replicas_only():
    app = {"tpu_engine_pool": _Pool([
        _Replica(10, 100), _Replica(30, 100),
        _Replica(999, 100, state="dead")])}
    state = queue_state(app)
    assert state["depth"] == 40
    assert state["capacity"] == 200
    assert state["saturation"] == 0.2


def test_queue_state_no_engine_and_all_dead():
    assert queue_state({}) is None
    app = {"tpu_engine_pool": _Pool([_Replica(0, 100, state="dead")])}
    assert queue_state(app)["saturation"] == 1.0


def test_retry_after_scales_and_bounds():
    # ramps 1 s at the advisory bar -> 8 s at full saturation (a fixed
    # value would synchronize client retries)
    assert retry_after_s(0.8, advisory_at=0.8) == 1
    assert retry_after_s(0.9, advisory_at=0.8) == 4
    assert retry_after_s(1.0, advisory_at=0.8) == 8
    assert retry_after_s(0.5, advisory_at=0.8) == 1  # below bar: floor
    assert retry_after_s(1.0, advisory_at=1.0) == 8  # degenerate bar
