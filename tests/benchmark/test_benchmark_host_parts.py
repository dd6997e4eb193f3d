"""The seven readers of PR 39 (``hostfed.*``, ``host.off_cpu_share``,
``host.stall_ms_max``, ``gateway.post_engine_ms_p50``), each on a synthetic
ring with hand-computed parts; what they report on a ring as the parent
program leaves it (nothing, and nothing raised); the five accepted ring
readers on one fixture with and without the child spans (the same values);
and the whole path once through ``run.measure`` on the CPU.

The synthetic clock is ``test_benchmark_timeline_readers``': the ring runs
on seconds 100..101, the trace on nanoseconds 5e9..6e9.
"""

import sys

import pytest
from test_benchmark_timeline_readers import T0, context, read, reduced

from benchmark.harness import host_parts, manifest, stats
from mcp_context_forge_tpu.observability.timeline import (STALL_S,
                                                          SpanEvent,
                                                          StepTimeline)

NEW = ("hostfed.rows_ms_mean", "hostfed.rng_ms_mean", "hostfed.upload_ms_mean",
       "hostfed.launch_ms_mean", "host.off_cpu_share", "host.stall_ms_max",
       "gateway.post_engine_ms_p50")
MS = 1e-3


@pytest.fixture()
def ring():
    """A ring registered as replica "0", which the readers look up."""
    return StepTimeline("0")


def dispatch(ring, family, seq, kind, t, parts, children=True, cpu_share=1.0,
             table_sync=None):
    """One dispatch's host spans from ``t``: ``parts`` are the milliseconds of
    rows, sampling, rng, upload, launch; each parent holds 0.1 ms more than
    its children at its end. Returns the end of the dispatch span."""
    rows, sampling, rng, upload, launch = (p * MS for p in parts)

    def add(name, t0, seconds):
        if children or name.count(".") == 1:
            ring.add_span(name, t0, t0 + seconds, seq, kind,
                          cpu=seconds * cpu_share)
        return t0 + seconds

    at = add(f"{family}.build.rows", t, rows)
    at = add(f"{family}.build.sampling", at, sampling)
    at = add(f"{family}.build.rng", at, rng)
    at = add(f"{family}.build", t, at - t + 0.1 * MS)
    if table_sync is not None:
        at = add("decode.table_sync", at, table_sync * MS)
    start = at
    at = add(f"{family}.dispatch.upload", start, upload)
    at = add(f"{family}.dispatch.launch", at, launch)
    return add(f"{family}.dispatch", start, at - start + 0.1 * MS)


def window_of_dispatches(ring, children=True, cpu_share=1.0):
    """Two host-fed decode steps, a prefill, a chunk round; a device-fed step
    and a dispatch before the window, both of which no reader may count."""
    dispatch(ring, "decode", 1, "decode", T0 + 0.10, (1.0, 0.4, 0.5, 0.6, 0.8),
             children, cpu_share, table_sync=0.2)
    dispatch(ring, "decode", 2, "decode_fb", T0 + 0.20, (9, 9, 9, 9, 9),
             children, cpu_share, table_sync=9)
    dispatch(ring, "decode", 3, "decode", T0 + 0.30, (3.0, 0.6, 0.3, 1.0, 1.2),
             children, cpu_share, table_sync=0.0)
    dispatch(ring, "prefill", 4, "prefill", T0 + 0.40, (2.0, 0.5, 0.7, 0.8, 1.0),
             children, cpu_share)
    dispatch(ring, "prefill", 5, "chunk", T0 + 0.50, (2.0, 0.5, 0.5, 0.6, 2.0),
             children, cpu_share)
    dispatch(ring, "decode", 6, "decode", T0 - 0.5, (50, 50, 50, 50, 50),
             children, cpu_share, table_sync=50)


# what window_of_dispatches holds, by hand: the four host-fed dispatches
ROWS = (1.0 + 3.0 + 2.0 + 2.0) / 4
RNG = (0.5 + 0.3 + 0.7 + 0.5) / 4
UPLOAD = ((0.4 + 0.2 + 0.6) + (0.6 + 0.0 + 1.0) + (0.5 + 0.8) + (0.5 + 0.6)) / 4
LAUNCH = (0.8 + 1.2 + 1.0 + 2.0) / 4
BY_HAND = {"rows": ROWS, "rng": RNG, "upload": UPLOAD, "launch": LAUNCH}


@pytest.mark.parametrize("part", sorted(BY_HAND))
def test_part_means_by_kind_and_their_sum_beside_build_to_dispatch(ring, part):
    window_of_dispatches(ring)
    ctx = context(reduced())
    assert read(f"hostfed.{part}_ms_mean", ctx) == pytest.approx(BY_HAND[part])
    note = ctx.notes[f"hostfed.{part}_ms"]
    assert note["n"] == 4 and note["mean"] == pytest.approx(BY_HAND[part])
    median = {"rows": 2.0, "rng": 0.5, "upload": (1.2 + 1.3) / 2, "launch": 1.1}
    assert note["p50"] == pytest.approx(median[part])
    assert {k: v["n"] for k, v in note["by_kind"].items()} == {
        "chunk": 1, "decode": 2, "prefill": 1}
    decode = {"rows": 2.0, "rng": 0.4, "upload": 1.4, "launch": 1.0}
    assert note["by_kind"]["decode"]["mean"] == pytest.approx(decode[part])
    check = ctx.notes["hostfed.sum_check_ms"]
    assert {p: check[p] for p in BY_HAND} == pytest.approx(BY_HAND)
    assert check["unnamed"] == pytest.approx(0.2)        # 0.1 ms a parent
    assert check["sum"] == pytest.approx(sum(BY_HAND.values()) + 0.2)
    # the spans are laid end to end here, so the two agree exactly; on the
    # chip the gaps between a build, a table sync and a dispatch are the rest
    assert check["build_to_dispatch_mean"] == pytest.approx(check["sum"])
    assert check["n"] == 4


def test_off_cpu_share_is_wall_minus_cpu_over_the_outermost_working_spans(ring):
    window_of_dispatches(ring, cpu_share=0.75)
    ring.add_span("decode.readback", T0 + 0.6, T0 + 0.7, 3, "decode", cpu=0.0)
    ring.add_span("loop.wait", T0 + 0.7, T0 + 0.8, cpu=0.0)
    ring.add_span("decode.emit", T0 + 0.80, T0 + 0.81, 3, "decode", cpu=0.0025)
    ctx = context(reduced())
    # parents and table syncs at three quarters on the CPU, the device-fed
    # step's too (its host work is work); one emit of 10 ms at a quarter;
    # the waits are not work
    # ms by dispatch: build + dispatch (each 0.1 over its children) + sync
    parents = (2.0 + 1.5 + 0.2) + (27.1 + 18.1 + 9.0) + (4.0 + 2.3 + 0.0) \
        + (3.3 + 1.9) + (3.1 + 2.7)
    wall = parents + 10.0
    cpu = 0.75 * parents + 2.5
    assert read("host.off_cpu_share", ctx) == pytest.approx(
        100.0 * (wall - cpu) / wall)
    note = ctx.notes["host.off_cpu"]
    assert note["wall_s"] == pytest.approx(wall * MS)
    assert note["by_span"]["decode.build.rows"]["off_cpu_share"] \
        == pytest.approx(25.0)
    assert note["by_span"]["decode.emit"]["off_cpu_share"] == pytest.approx(75.0)
    assert note["by_span"]["loop.wait"]["n"] == 1         # shown, not summed


def test_stall_max_names_the_step_the_child_and_the_pauses(ring):
    window_of_dispatches(ring)
    # a host-fed step held 31 ms by its upload, asleep (a tenth on the CPU)
    ring.add_span("decode.build.rows", T0 + 0.600, T0 + 0.601, 9, "decode", 0.001)
    ring.add_span("decode.build.sampling", T0 + 0.601, T0 + 0.6015, 9, "decode")
    ring.add_span("decode.build.rng", T0 + 0.6015, T0 + 0.602, 9, "decode")
    ring.add_span("decode.build", T0 + 0.600, T0 + 0.602, 9, "decode", 0.002)
    ring.add_span("decode.table_sync", T0 + 0.602, T0 + 0.602, 9, "decode")
    ring.add_span("decode.dispatch.upload", T0 + 0.602, T0 + 0.633, 9, "decode",
                  0.0031)
    ring.add_span("decode.dispatch.launch", T0 + 0.633, T0 + 0.634, 9, "decode")
    ring.add_span("decode.dispatch", T0 + 0.602, T0 + 0.634, 9, "decode", 0.004)
    ring.add_span("loop.wait", T0 + 0.90, T0 + 0.95)
    ring.add_pause("gc", T0 + 0.605, T0 + 0.630, 2, "MainThread")   # inside it
    ring.add_pause("gc", T0 + 0.91, T0 + 0.912, 1, "tpu-engine-dispatch")
    ring.add_pause("gc", T0 + 0.92, T0 + 0.924, 1, "MainThread")
    ring.add_pause("gc", T0 - 0.4, T0 - 0.3, 2, "MainThread")       # before
    ctx = context(reduced())
    assert read("host.stall_ms_max", ctx) == pytest.approx(34.0)
    note = ctx.notes["host.stall"]
    assert (note["step"], note["kind"]) == (9, "decode")
    assert note["at_s"] == pytest.approx(0.6)
    assert note["held_by"] == "dispatch.upload"
    assert note["held_wall_ms"] == pytest.approx(31.0)
    assert note["held_cpu_ms"] == pytest.approx(3.1)
    assert [(p["cause"], round(p["ms"]), p["dispatch_thread_in"])
            for p in note["pauses_in_it"]] == [("gc2", 25, "decode.dispatch.upload")]
    assert note["dispatch_stalls"] == 1 and note["dispatches"] == 5
    assert note["stall_limit_ms"] == STALL_S * 1e3
    pauses = ctx.notes["host.pauses"]
    assert pauses["n"] == 3 and pauses["total_ms"] == pytest.approx(31.0)
    assert pauses["by_generation"] == {
        "gc2": pytest.approx({"n": 1, "total_ms": 25.0, "longest_ms": 25.0}),
        "gc1": pytest.approx({"n": 2, "total_ms": 6.0, "longest_ms": 4.0})}
    assert [(p["cause"], p["thread"], p["dispatch_thread_in"])
            for p in pauses["longest"]] == [
        ("gc2", "MainThread", "decode.dispatch.upload"),
        ("gc1", "MainThread", "loop.wait"),
        ("gc1", "tpu-engine-dispatch", "loop.wait")]


def test_a_clean_window_reports_its_longest_dispatch_and_no_pause(ring):
    window_of_dispatches(ring)
    ctx = context(reduced())
    # step 3: 3.9 + 0.1 of build, no table sync, 2.2 + 0.1 of dispatch
    assert read("host.stall_ms_max", ctx) == pytest.approx(6.3)
    assert ctx.notes["host.stall"]["step"] == 3
    assert ctx.notes["host.stall"]["held_by"] == "build.rows"
    assert ctx.notes["host.stall"]["dispatch_stalls"] == 0
    assert ctx.notes["host.pauses"] == {"n": 0, "total_ms": 0.0,
                                        "by_generation": {}, "longest": []}


class Request:
    def __init__(self, request_id):
        self.request_id = request_id


def requests_context(ring, deliver=True):
    """Three requests with a first token in the window, one failed, one the
    ring never stamped ``first``: (first -> deliver, deliver -> client) ms."""
    ways = {0: (2.0, 1.0), 1: (6.0, 3.0), 2: (4.0, 0.5)}
    ctx = context(reduced())
    for index, (held, onward) in ways.items():
        first = T0 + 0.1 * (index + 1)
        ring.stamp("first", f"q{index}", index, first)
        if deliver:
            ring.stamp("deliver", f"q{index}", index, first + held * MS)
        record = stats.Record(index, first - 0.05, 64, 8)
        record.token_times = [first + (held + onward) * MS, first + 0.05]
        record.ok = True
        ctx.records.append(record)
        ctx.submits[index] = (first - 0.04, Request(f"q{index}"))
    failed = stats.Record(3, T0 + 0.5, 64, 8)
    ring.stamp("first", "q3", 3, T0 + 0.5)
    ring.stamp("deliver", "q3", 3, T0 + 0.501)
    ctx.records.append(failed)
    ctx.submits[3] = (T0 + 0.5, Request("q3"))
    unstamped = stats.Record(4, T0 + 0.6, 64, 8)
    unstamped.token_times, unstamped.ok = [T0 + 0.7], True
    ctx.records.append(unstamped)
    ctx.submits[4] = (T0 + 0.6, Request("q4"))
    return ctx


def test_post_engine_is_first_stamp_to_client_split_at_deliver(ring):
    window_of_dispatches(ring)
    ctx = requests_context(ring)
    assert read("gateway.post_engine_ms_p50", ctx) == pytest.approx(4.5)
    note = ctx.notes["gateway.post_engine_ms"]
    assert note["n"] == 3 and note["p50"] == pytest.approx(4.5)
    assert note["p95"] == pytest.approx(4.5 + 0.9 * 4.5)
    assert note["first_to_deliver"]["p50"] == pytest.approx(4.0)
    assert note["deliver_to_client"]["p50"] == pytest.approx(1.0)


# ------------------------------------------- a ring without the new fields

@pytest.mark.parametrize("name", NEW)
def test_new_readers_report_nothing_on_the_parents_ring(ring, name):
    """The parent of PR 39: parent spans only, no ``cpu``, no pause, no
    ``deliver``. Every new reader returns None, writes no note, raises
    nothing."""
    window_of_dispatches(ring, children=False, cpu_share=0.0)
    ctx = requests_context(ring, deliver=False)
    assert read(name, ctx) is None and not ctx.notes


@pytest.mark.parametrize("name", NEW)
def test_new_readers_report_nothing_where_there_is_no_timeline(name, monkeypatch):
    monkeypatch.setitem(
        sys.modules, "mcp_context_forge_tpu.observability.timeline", None)
    ctx = context(reduced())
    assert read(name, ctx) is None and not ctx.notes


def test_each_field_is_needed_by_the_reader_that_reads_it(ring):
    """Children without ``cpu``: the parts and the stall report, the off-CPU
    share does not. No ``deliver``: the way out does not. A span event as the
    parent's tuple had it (six fields) is read like one with ``cpu`` 0."""
    window_of_dispatches(ring, cpu_share=0.0)
    ctx = requests_context(ring, deliver=False)
    assert read("hostfed.rows_ms_mean", ctx) == pytest.approx(ROWS)
    assert read("host.stall_ms_max", ctx) == pytest.approx(6.3)
    assert ctx.notes["host.stall"]["held_cpu_ms"] == 0.0
    assert read("host.off_cpu_share", ctx) is None
    assert read("gateway.post_engine_ms_p50", ctx) is None
    assert "host.off_cpu" not in ctx.notes
    assert "gateway.post_engine_ms" not in ctx.notes
    assert SpanEvent("decode.build", 0.0, 1.0, 1, "decode", "0").cpu == 0.0
    # one load a context, whatever the number of readers
    assert host_parts.load(ctx) is host_parts.load(ctx)


# ------------------- the accepted ring readers, with and without children

def _accepted_fixture(ring, children):
    """Ten host-fed decode steps, each after a prefill, under a device trace
    whose programs start 1 ms after their dispatch ends; two requests waiting
    behind the prefills. The parents' bounds are the same in both forms."""
    ops, modules = [], []
    for i in range(10):
        at = T0 + 0.1 * i
        end = dispatch(ring, "prefill", 2 * i + 1, "prefill", at + 0.001,
                       (1.0, 0.3, 0.4, 0.5, 0.6), children)
        ring.add_span("prefill.sync", end, at + 0.040, 2 * i + 1, "prefill")
        ring.step(2 * i + 1, "prefill", 4, 1, 64, end - 1.2 * MS, at + 0.040)
        ring.add_span("prefill.emit", at + 0.040, at + 0.041, 2 * i + 1, "prefill")
        modules.append((end + 0.001, at + 0.0395, "jit__prefill_and_sample",
                        "prefill"))
        end = dispatch(ring, "decode", 2 * i + 2, "decode", at + 0.041,
                       (0.8, 0.3, 0.4, 0.5, 0.6), children, table_sync=0.1)
        ring.add_span("decode.readback", end, at + 0.090, 2 * i + 2, "decode")
        ring.step(2 * i + 2, "decode", 4, 2, 4, end - 1.2 * MS, at + 0.090)
        ring.add_span("decode.emit", at + 0.090, at + 0.0905, 2 * i + 2, "decode")
        modules.append((end + 0.001, at + 0.0895, "jit__decode_and_sample",
                        "decode"))
    ops = [(a, b, "fusion") for a, b, _name, _kind in modules]
    ring.stamp("submit", "a", -1, T0 + 0.095)
    ring.stamp("admit", "a", 1, T0 + 0.1005)
    ring.stamp("submit", "b", -1, T0 + 0.310)
    ring.stamp("admit", "b", 2, T0 + 0.4005)
    return context(reduced(ops, modules))


@pytest.mark.parametrize("name", [
    "device.idle_share.host.serve", "device.idle_share.host.sat",
    "decode.retire_interval_ms_p95", "decode.prefill_stall_share",
    "queue.wait_behind_prefill_share"])
def test_accepted_ring_readers_read_the_same_with_and_without_children(name):
    values, notes = [], []
    for children in (False, True):
        ring = StepTimeline("0")          # the registry holds it weakly
        ctx = _accepted_fixture(ring, children)
        values.append(read(name, ctx))
        notes.append(ctx.notes)
    assert values[0] is not None and values[0] > 0
    assert values[1] == pytest.approx(values[0], rel=1e-9)
    if name.startswith("device.idle_share.host"):
        plain, nested = (n["device.idle_share.host"] for n in notes)
        for key in ("idle_share", "host", "no_work", "unattributed",
                    "dispatch_to_device_lag_ms_p50", "lag_samples"):
            assert nested[key] == pytest.approx(plain[key], rel=1e-9), key
        # the same idle seconds, now under the names of the parts
        assert "decode.build.rows" in nested["idle_s_by_span"]
        assert "decode.build.rows" not in plain["idle_s_by_span"]
        assert sum(nested["idle_s_by_span"].values()) == pytest.approx(
            sum(plain["idle_s_by_span"].values()))
        for gaps, build_ms in ((plain["longest_gaps"], 1.8),
                               (nested["longest_gaps"], 0.1)):
            assert gaps[0]["ms"] == pytest.approx(15.5)
            # a parent keeps what no part of it owns
            assert gaps[0]["spans_ms"]["prefill.build"] == pytest.approx(build_ms)
        assert nested["longest_gaps"][0]["spans_ms"]["prefill.build.rows"] \
            == pytest.approx(1.0)


# ------------------------------------------------------------ the manifest

def test_the_seven_are_the_last_entries_and_reported_by_every_cell():
    """The seven stand together in the order they were appended, and EVERY
    cell reports them (no list), however many entries later PRs append."""
    doc = manifest.load()
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(NEW[0])
    tail = doc["per_layer"][first:first + len(NEW)]
    assert tuple(m["name"] for m in tail) == NEW
    for metric in tail:
        assert "workloads" not in metric and metric["moves"] == "ttft_p50_ms"
        assert metric["source"] == "program_span" and metric["better"] == "lower"
    assert {m["layer"] for m in tail} == {"device", "gateway and /v1 route"}
    for cell in doc["workloads"]:
        names = {m["name"] for m in manifest.cell(doc, cell["name"]).per_layer}
        assert set(NEW) <= names


def test_rehearsal_prints_all_seven_and_their_notes(capsys, tmp_path, monkeypatch):
    """The whole path on the CPU: ``run.measure`` with the chat cell's metric
    list and the real engine's ring (the parts sum to the dispatches they were
    cut from; the way out is split at ``deliver``)."""
    import test_benchmark_rehearsal as rehearsal

    from benchmark import run

    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    result, notes = rehearsal._measure("mistral-7b.chat", "open", True, capsys)
    assert result["correct"] is True, notes
    metrics, per_layer = result["metrics"], notes["per_layer_notes"]
    assert set(NEW) <= set(metrics)
    assert all(metrics[name]["value"] >= 0.0 for name in NEW)
    assert 0.0 <= metrics["host.off_cpu_share"]["value"] <= 100.0
    check = per_layer["hostfed.sum_check_ms"]
    assert check["n"] >= result["attempted"]     # a prefill a request, at least
    assert check["sum"] == pytest.approx(check["build_to_dispatch_mean"], rel=0.1)
    assert check["sum"] <= check["build_to_dispatch_mean"] + 1e-6
    for part in ("rows", "rng", "upload", "launch"):
        assert per_layer[f"hostfed.{part}_ms"]["by_kind"]["prefill"]["n"] >= 1
        assert metrics[f"hostfed.{part}_ms_mean"]["value"] == check[part]
    assert metrics["host.stall_ms_max"]["value"] >= check["build_to_dispatch_mean"]
    assert per_layer["host.stall"]["held_by"] in host_parts.CHILDREN + ("table_sync",)
    assert per_layer["host.pauses"]["n"] >= 0
    way_out = per_layer["gateway.post_engine_ms"]
    assert way_out["n"] == result["attempted"]
    assert way_out["first_to_deliver"]["p50"] >= 0.0
    assert way_out["deliver_to_client"]["p50"] > 0.0
    assert way_out["p50"] == metrics["gateway.post_engine_ms_p50"]["value"]
