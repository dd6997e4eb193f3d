"""The per-layer readers of the engine's step timeline, each on a synthetic
``Reduced`` trace plus a synthetic ring with known overlaps, and the whole
path once through ``run.measure`` on the CPU (where the trace has no device
plane: the ``program_span`` readers report, the ``device_trace`` ones do not).

The synthetic clock: the ring runs on seconds 100..101 (``trace_span``), the
trace on nanoseconds 5e9..6e9 (``window_ns``): the same second, two clocks.
"""

import pytest

from benchmark.harness import layers, timeline_view, trace_reduce
from mcp_context_forge_tpu.observability.timeline import StepTimeline

T0, NS0 = 100.0, 5e9


def ns(t):
    """Ring seconds -> trace nanoseconds, the bridge the readers use."""
    return NS0 + (t - T0) * 1e9


def reduced(ops=(), modules=()):
    device = trace_reduce.DeviceTrace(
        modules=[(ns(a), ns(b), name, kind) for a, b, name, kind in modules],
        ops=[(ns(a), ns(b), name) for a, b, name in ops])
    devices = {"/device:TPU:0": device} if ops or modules else {}
    return trace_reduce.Reduced(devices, (NS0, NS0 + 1e9))


def context(trace, window=(T0, T0 + 1.0)):
    return layers.LayerContext(records=[], window=window, stats={}, model=None,
                               trace=trace, trace_span=(T0, T0 + 1.0))


@pytest.fixture()
def ring():
    """A ring registered as replica "0", which the readers look up."""
    return StepTimeline("0")


def read(name, ctx):
    return layers.load_reader(name)(ctx)


def test_flatten_gives_each_instant_to_the_innermost_span(ring):
    ring.add_span("decode.readback", 1.0, 2.0)
    ring.add_span("decode.emit", 2.0, 2.5)
    ring.add_span("loop.drain", 0.5, 3.0)        # appended at exit: after its children
    ring.add_span("loop.wait", 4.0, 5.0)
    view = timeline_view.load()
    assert view.segments == [(0.5, 1.0, "loop.drain"), (1.0, 2.0, "decode.readback"),
                             (2.0, 2.5, "decode.emit"), (2.5, 3.0, "loop.drain"),
                             (4.0, 5.0, "loop.wait")]
    cover = view.cover(0.0, 4.5)
    assert cover == pytest.approx({"loop.drain": 1.0, "decode.readback": 1.0,
                                   "decode.emit": 0.5, "loop.wait": 0.5,
                                   timeline_view.UNLABELLED: 1.5})
    assert timeline_view.by_category(cover) == pytest.approx(
        {"prefill": 0.0, "decode": 1.5, "drain": 1.0, "loop.wait": 0.5, "rest": 1.5})


@pytest.mark.parametrize("name", ["device.idle_share.host.serve",
                                  "device.idle_share.host.sat"])
def test_idle_share_is_split_by_what_the_host_was_doing(ring, name):
    # the device runs 0-0.1, 0.2-0.5 and 0.7-1.0 s: idle 0.1-0.2 (the thread
    # reads back, then dispatches for 0.5 ms, then 0.5 ms under no span) and
    # 0.5-0.7 (0.15 s waiting for work, 0.05 s under no span)
    ops = [(T0, T0 + 0.1, "fusion"), (T0 + 0.2, T0 + 0.5, "fusion"),
           (T0 + 0.7, T0 + 1.0, "fusion")]
    modules = [(T0, T0 + 0.1, "jit__decode_and_sample_fb", "decode"),
               (T0 + 0.2, T0 + 0.5, "jit__decode_and_sample", "decode"),
               (T0 + 0.7, T0 + 1.0, "jit__prefill_and_sample", "prefill")]
    ring.add_span("decode.readback", T0 + 0.05, T0 + 0.199, 7, "decode_fb")
    ring.add_span("decode.dispatch", T0 + 0.199, T0 + 0.1995, 8, "decode")
    ring.add_span("loop.wait", T0 + 0.5, T0 + 0.65)
    ring.step(8, "decode", 32, 8, 4, T0 + 0.199, T0 + 0.5003)   # host-fed
    ctx = context(reduced(ops, modules))
    host = read(name, ctx)
    note = ctx.notes["device.idle_share.host"]
    assert host == pytest.approx(9.95) and note["host"] == pytest.approx(9.95)
    assert note["no_work"] == pytest.approx(15.0)
    assert note["unattributed"] == pytest.approx(5.05)
    # the three are the cell's idle share, as its accepted reader computes it
    assert note["host"] + note["no_work"] + note["unattributed"] \
        == pytest.approx(layers.idle_share(ctx)) == pytest.approx(note["idle_share"])
    assert note["of_it_inside_programs"] == pytest.approx(0.0)
    assert list(note["idle_s_by_span"]) == [
        "loop.wait", "decode.readback", timeline_view.UNLABELLED,
        "decode.dispatch"]
    longest = note["longest_gaps"]
    assert [round(g["ms"]) for g in longest] == [200, 100]
    assert longest[0]["span"] == "loop.wait" and longest[0]["span_share"] \
        == pytest.approx(0.75)
    assert longest[1]["span"] == "decode.readback"
    # the host-fed dispatch ended 0.5 ms before its program started; one
    # step is too few to say anything of the clocks
    assert note["lag_samples"] == 1 and note["clock_skew_ms"] is None
    assert note["dispatch_to_device_lag_ms_p50"] == pytest.approx(0.5, abs=1e-6)


def test_device_clock_skew_is_bounded_from_causality_and_taken_out(ring):
    """The profiler put the device's events 2 ms too early: programs then
    seem to start before their dispatch call begins (by up to 1.5 ms: the
    lower bound) and read-backs end >= 2.1 ms after their program (the upper
    bound, applied). Without the correction the 3 ms gap after each program
    lands on the read-back that waited for it; with it, on the build and the
    dispatch that really held the device up."""
    skew, ops, modules = 0.002, [], []
    for i in range(10):
        start = T0 + 0.1 * i + 0.004          # true times on the host's clock
        end = start + 0.095                   # then 5 ms idle: 1 ms before a
        ops.append((start - skew, end - skew, "fusion"))        # window mark
        modules.append((start - skew, end - skew, "jit__decode_and_sample",
                        "decode"))
        ring.add_span("decode.build", start - 0.004, start - 0.0015, i + 1, "decode")
        ring.add_span("decode.dispatch", start - 0.0015, start - 0.0005, i + 1,
                      "decode")
        ring.add_span("decode.readback", start - 0.0005, end + 0.0001, i + 1,
                      "decode")
        ring.step(i + 1, "decode", 32, 8, 4, start - 0.0015, end + 0.0001)
        ring.add_span("decode.emit", end + 0.0001, end + 0.001, i + 1, "decode")
    ctx = context(reduced(ops, modules))
    host = read("device.idle_share.host.serve", ctx)
    note = ctx.notes["device.idle_share.host"]
    assert note["clock_skew_ms"] == pytest.approx(
        {"lower": 0.5, "upper": 2.1, "applied": 2.1, "consistent": True,
         "samples": 10}, abs=1e-6)
    assert note["dispatch_to_device_lag_ms_p50_raw"] == pytest.approx(-1.5, abs=1e-6)
    assert note["dispatch_to_device_lag_ms_p50"] == pytest.approx(0.6, abs=1e-6)
    # all of it the host's, but the last 2.1 ms: moved past the last span
    assert note["idle_share"] == pytest.approx(5.0, abs=1e-6)
    assert note["unattributed"] == pytest.approx(0.21, abs=1e-6)
    assert host == pytest.approx(4.79, abs=1e-6)
    by_span = note["idle_s_by_span"]
    # per step, shifted 0.1 ms too far: emit 0.8, build 2.5, dispatch 1.0,
    # read-back 0.6 ms (the launch lag); not 2 ms of read-back
    assert by_span["decode.build"] == pytest.approx(10 * 0.0025, rel=0.1)
    assert by_span["decode.dispatch"] == pytest.approx(10 * 0.001, rel=0.1)
    assert by_span["decode.readback"] == pytest.approx(10 * 0.0006, rel=0.2)


def test_idle_inside_a_program_is_told_apart(ring):
    """Gaps between the operations of one running program are idle time too
    (the accepted idle share counts them); the note says how much."""
    ops = [(T0, T0 + 0.4, "fusion"), (T0 + 0.6, T0 + 1.0, "fusion")]
    modules = [(T0, T0 + 1.0, "jit__decode_and_sample_fb", "decode")]
    ring.add_span("decode.readback", T0, T0 + 1.0, 3, "decode_fb")
    ctx = context(reduced(ops, modules))
    assert read("device.idle_share.host.serve", ctx) == pytest.approx(20.0)
    assert ctx.notes["device.idle_share.host"]["of_it_inside_programs"] \
        == pytest.approx(20.0)


def _decode_steps(ring, retires):
    for seq, t in enumerate(retires, 1):
        ring.step(seq, "decode_fb", 32, 8, 4, t - 0.01, t)


def test_retire_interval_p95_and_prefill_stall_share(ring):
    # 21 decode steps retire 10 ms apart, but for two stalled intervals: one
    # of 60 ms behind a prefill (50 ms of spans), one of 40 ms of which 30 ms
    # the thread had nothing to do
    retires, t = [], T0
    for i in range(21):
        t += {7: 0.060, 14: 0.040}.get(i, 0.010)
        retires.append(t)
    _decode_steps(ring, retires)
    stall = retires[6]
    ring.add_span("prefill.build", stall + 0.002, stall + 0.004, 50, "prefill")
    ring.add_span("prefill.dispatch", stall + 0.004, stall + 0.005, 50, "prefill")
    ring.add_span("prefill.sync", stall + 0.005, stall + 0.050, 50, "prefill")
    ring.add_span("prefill.emit", stall + 0.050, stall + 0.052, 50, "prefill")
    ring.add_span("loop.wait", retires[13] + 0.005, retires[13] + 0.035)
    ring.add_span("decode.readback", retires[0], retires[1], 2, "decode_fb")
    ctx = context(reduced())
    p95 = read("decode.retire_interval_ms_p95", ctx)
    # 20 intervals: eighteen of 10 ms, one of 60 ms, the waiting one also 10 ms
    assert ctx.notes["decode.retire_interval_ms"]["n"] == 20
    assert ctx.notes["decode.retire_interval_ms"]["max"] == pytest.approx(60.0)
    assert p95 == pytest.approx(10.0 + 0.05 * 50.0)      # rank 18.05 of 0..19
    share = read("decode.prefill_stall_share", ctx)
    total = 18 * 0.010 + 0.060 + 0.010
    assert share == pytest.approx(100.0 * 0.050 / total)
    split = ctx.notes["decode.retire_interval_split_s"]
    assert split["prefill"] == pytest.approx(0.050)
    assert split["decode"] == pytest.approx(0.010)
    assert split["loop.wait"] == pytest.approx(0.030)


def test_steps_outside_the_window_are_left_out(ring):
    _decode_steps(ring, [T0 - 0.5, T0 + 0.1, T0 + 0.2, T0 + 2.0])
    ctx = context(reduced())
    assert read("decode.retire_interval_ms_p95", ctx) == pytest.approx(100.0)
    assert ctx.notes["decode.retire_interval_ms"]["n"] == 1


def test_queue_wait_behind_prefill_share(ring):
    # a waits 40 ms: 30 ms of another request's prefill, 10 ms of a drain;
    # b waits 10 ms, all of it a decode read-back; c never won a slot
    ring.stamp("submit", "a", -1, T0 + 0.100)
    ring.stamp("admit", "a", 2, T0 + 0.140)
    ring.stamp("submit", "b", -1, T0 + 0.300)
    ring.stamp("admit", "b", 3, T0 + 0.310)
    ring.stamp("submit", "c", -1, T0 + 0.900)
    ring.stamp("submit", "late", -1, T0 + 1.5)
    ring.stamp("admit", "late", 1, T0 + 1.6)         # outside the window
    ring.add_span("prefill.sync", T0 + 0.080, T0 + 0.130, 4, "prefill")
    ring.add_span("loop.drain", T0 + 0.130, T0 + 0.140, 5)
    ring.add_span("decode.readback", T0 + 0.295, T0 + 0.320, 6, "decode_fb")
    ctx = context(reduced())
    assert read("queue.wait_behind_prefill_share", ctx) == pytest.approx(60.0)
    assert ctx.notes["queue.wait_split_s"] == pytest.approx(
        {"requests": 2, "prefill": 0.030, "decode": 0.010, "drain": 0.010,
         "loop.wait": 0.0, "rest": 0.0})


def test_prefill_device_ms_per_step_counts_dense_history_and_chunk_programs():
    modules = [(T0, T0 + 0.050, "jit__prefill_and_sample", "prefill"),
               (T0 + 0.1, T0 + 0.220, "jit__prefill_hist_and_sample", "prefill_hist"),
               (T0 + 0.3, T0 + 0.314, "jit__decode_and_sample", "decode")]
    ctx = context(reduced(modules=modules))
    assert read("prefill.device_ms_per_step", ctx) == pytest.approx(85.0)
    assert read("prefill.device_ms_per_step", context(reduced(modules=modules[2:]))) is None


@pytest.mark.parametrize("name", ["device.idle_share.host.serve",
                                  "device.idle_share.host.sat",
                                  "prefill.device_ms_per_step"])
def test_device_readers_report_nothing_without_a_device_plane(ring, name):
    ring.add_span("decode.readback", T0, T0 + 1.0, 1, "decode")
    ctx = context(reduced())                     # the CPU rehearsal's trace
    assert read(name, ctx) is None and not ctx.notes
    ctx.trace = None                             # an untraced context
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", ["device.idle_share.host.serve",
                                  "decode.retire_interval_ms_p95",
                                  "decode.prefill_stall_share",
                                  "queue.wait_behind_prefill_share"])
def test_readers_report_nothing_where_the_program_has_no_timeline(
        name, monkeypatch):
    """The parent commit has no ``observability/timeline.py``: the import
    fails, nothing is read and nothing raises."""
    import sys
    monkeypatch.setitem(
        sys.modules, "mcp_context_forge_tpu.observability.timeline", None)
    assert timeline_view.load() is None
    ctx = context(reduced([(T0, T0 + 0.5, "fusion")]))
    assert read(name, ctx) is None and not ctx.notes


def test_unknown_replica_has_no_ring(ring):
    assert timeline_view.load("no-such-replica") is None
    assert timeline_view.load() is not None


def test_rehearsal_prints_the_span_metrics_and_leaves_the_trace_ones_out(capsys):
    """The whole path on the CPU: ``run.measure`` as the rehearsal drives it,
    with the chat cell's metric list. The ring is the real engine's."""
    import test_benchmark_rehearsal as rehearsal

    result, notes = rehearsal._measure("mistral-7b.chat", "open", True, capsys)
    assert result["correct"] is True, notes
    metrics = result["metrics"]
    for name in ("decode.retire_interval_ms_p95", "decode.prefill_stall_share",
                 "queue.wait_behind_prefill_share"):
        assert metrics[name]["value"] >= 0.0, name
    assert metrics["decode.retire_interval_ms_p95"]["unit"] == "ms"
    assert metrics["decode.prefill_stall_share"]["value"] <= 100.0
    for name in ("device.idle_share.host.serve", "prefill.device_ms_per_step",
                 "device.idle_share.serve", "decode.device_ms_per_step"):
        assert name not in metrics
    per_layer = notes["per_layer_notes"]
    # requests admitted inside the window (the last may win its slot after it)
    assert 0 < per_layer["queue.wait_split_s"]["requests"] <= result["attempted"]
    assert per_layer["decode.retire_interval_ms"]["n"] > 0
    assert "device.idle_share.host" not in per_layer
