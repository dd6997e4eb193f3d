"""The five readers of PR 55 (``gateway.in_ms_p50``, ``gateway.emit_wait_ms_p50``,
``loop.hop_ms_p50``, ``gateway.out_ms_p50``, ``loop.lag_ms_max``), each on a
hand-made ring with hand-made numbers; the pieces rebuild the client's
first token; what they report on a ring as the parent program leaves it
(nothing, and nothing raised); what a ``loop_lag`` pause does to the accepted
``host.pauses`` note and ``host.stall_ms_max`` (a new key, the same value);
and the whole path once through ``run.measure`` on the CPU.

The synthetic clock is ``test_benchmark_timeline_readers``': the ring runs
on seconds 100..101.
"""

import sys

import pytest
from test_benchmark_host_parts import window_of_dispatches
from test_benchmark_timeline_readers import T0, context, read, reduced

from benchmark.harness import gateway_parts, manifest, stats
from mcp_context_forge_tpu.observability.timeline import StepTimeline

NEW = ("gateway.in_ms_p50", "gateway.emit_wait_ms_p50", "loop.hop_ms_p50",
       "gateway.out_ms_p50", "loop.lag_ms_max")
MS = 1e-3
# one request's way, ms after the client sent it: the eleven instants between
# the client's send and its first receive, in order
ORDER = ("recv", "authed", "parsed", "tokenized", "submit", "first", "emit",
         "deliver", "chunk", "written", "client")
WAYS = {
    #   recv authed parsed tokenized submit first emit deliver chunk written client
    0: (0.3, 0.5, 0.9, 1.4, 1.5, 41.5, 42.0, 43.0, 43.4, 44.4, 44.9),
    1: (0.5, 0.9, 1.5, 2.4, 2.5, 62.5, 65.5, 73.5, 74.1, 76.1, 77.1),
    2: (0.4, 0.7, 1.2, 1.9, 2.0, 52.0, 53.0, 55.0, 55.5, 57.0, 57.7),
}
LATE = {0: 0.1, 1: 0.3, 2: 0.2}      # how late the generator sent each, ms


@pytest.fixture()
def ring():
    """A ring registered as replica "0", which the readers look up."""
    return StepTimeline("0")


class Request:
    def __init__(self, request_id):
        self.request_id = request_id


def requests_context(ring, stamped=ORDER[:-1]):
    """Three requests with every stamp in ``stamped``; one that failed; one
    whose first chunk was never written (no ``chunk`` / ``written``)."""
    ctx = context(reduced())

    def add(index, way, stamps, ok=True):
        sent = T0 + 0.1 * (index + 1)
        at = {name: sent + ms * MS for name, ms in zip(ORDER, way)}
        for name in stamps:
            ring.stamp(name, f"q{index}", -1, at[name])
        ring.stamp("admit", f"q{index}", index, at["submit"] + 0.5 * MS)
        record = stats.Record(index, sent - LATE.get(index, 0.0) * MS, 64, 8)
        record.sent = sent
        if ok:
            record.token_times, record.ok = [at["client"], at["client"] + 0.05], True
        ctx.records.append(record)
        ctx.submits[index] = (at["submit"] + 0.05 * MS, Request(f"q{index}"))

    for index, way in WAYS.items():
        add(index, way, stamped)
    add(3, WAYS[0], stamped, ok=False)
    add(4, WAYS[1], [s for s in stamped if s not in ("chunk", "written")])
    return ctx


def pauses(ring):
    """Three lags of the loop (one under the window's edge, one before it) and
    two collections, one inside the longest lag."""
    ring.add_span("loop.wait", T0 + 0.70, T0 + 0.80)
    ring.add_pause("loop_lag", T0 + 0.305, T0 + 0.335, 0, "MainThread")
    ring.add_pause("loop_lag", T0 + 0.72, T0 + 0.722, 0, "MainThread")
    ring.add_pause("loop_lag", T0 + 0.9995, T0 + 1.0035, 0, "MainThread")
    ring.add_pause("loop_lag", T0 - 0.4, T0 - 0.3, 0, "MainThread")
    ring.add_pause("gc", T0 + 0.31, T0 + 0.33, 2, "MainThread")
    ring.add_pause("gc", T0 + 0.91, T0 + 0.913, 1, "tpu-engine-dispatch")


# what the three whole requests hold, by hand (p50 of three is the middle one)
BY_HAND = {
    "gateway.in_ms_p50": 1.6,             # recv -> submit: 1.2, 2.0, 1.6
    "gateway.emit_wait_ms_p50": 1.0,      # first -> emit: 0.5, 3.0, 1.0
    "loop.hop_ms_p50": 2.0,               # emit -> deliver: 1.0, 8.0, 2.0
    "gateway.out_ms_p50": 2.0,            # deliver -> written: 1.4, 2.6, 2.0
    "loop.lag_ms_max": 30.0,
}


@pytest.mark.parametrize("name", NEW)
def test_each_reader_gives_the_hand_made_number(ring, name):
    window_of_dispatches(ring)
    pauses(ring)
    ctx = requests_context(ring)
    assert read(name, ctx) == pytest.approx(BY_HAND[name])


def test_the_way_in_is_split_at_its_marks_beside_the_clients_send(ring):
    window_of_dispatches(ring)
    ctx = requests_context(ring)
    read("gateway.in_ms_p50", ctx)
    note = ctx.notes["gateway.in_ms"]
    assert note["n"] == 3 and note["p50"] == pytest.approx(1.6)
    assert note["p95"] == pytest.approx(1.6 + 0.9 * 0.4)
    assert {k: v["p50"] for k, v in note.items() if isinstance(v, dict)} \
        == pytest.approx({"recv_to_authed": 0.3, "authed_to_parsed": 0.5,
                          "parsed_to_tokenized": 0.7, "tokenized_to_submit": 0.1,
                          "client_send_to_recv": 0.4})


def test_the_seven_pieces_rebuild_the_clients_first_token(ring):
    window_of_dispatches(ring)
    ctx = requests_context(ring)
    read("gateway.in_ms_p50", ctx)
    check = ctx.notes["gateway.sum_check_ms"]
    assert check["n"] == 3 and check["negative_pieces"] == 0
    assert {name: check[name] for name, _a, _b in gateway_parts.PIECES} \
        == pytest.approx({"generator_due_to_sent": 0.2,
                          "client_send_to_recv": 0.4, "gateway_in": 1.6,
                          "queue_and_prefill": 50.0, "emit_wait": 1.0,
                          "loop_hop": 2.0, "gateway_out": 2.0,
                          "written_to_client": 0.7})
    # request by request the pieces are the walk from when the request was
    # DUE to its first token: what the end-to-end metric's arithmetic gives
    assert check["sum_p50"] == pytest.approx(57.7 + 0.2)
    assert check["ttft_p50"] == pytest.approx(check["sum_p50"])
    # a stamp out of its place shows: the emit of request 2 before its first
    ring.stamp("emit", "q2", 2, T0 + 0.3 + 51.0 * MS)
    late = context(reduced())
    late.records, late.submits = ctx.records, ctx.submits
    read("gateway.in_ms_p50", late)
    assert late.notes["gateway.sum_check_ms"]["negative_pieces"] == 1


def test_the_way_out_is_split_at_emit_deliver_chunk_and_written(ring):
    window_of_dispatches(ring)
    ctx = requests_context(ring)
    for name in NEW[1:4]:
        read(name, ctx)
    assert ctx.notes["gateway.emit_wait_ms"] == pytest.approx(
        {"n": 3, "p50": 1.0, "p95": 1.0 + 0.9 * 2.0, "max": 3.0})
    assert ctx.notes["loop.hop_ms"] == pytest.approx(
        {"n": 3, "p50": 2.0, "p95": 2.0 + 0.9 * 6.0, "max": 8.0, "over_5_ms": 1})
    out = ctx.notes["gateway.out_ms"]
    assert out["n"] == 3 and out["p50"] == pytest.approx(2.0)
    assert out["deliver_to_chunk"]["p50"] == pytest.approx(0.5)
    assert out["chunk_to_written"]["p50"] == pytest.approx(1.5)
    assert out["written_to_client"]["p50"] == pytest.approx(0.7)
    # with the accepted reader's split of the same way: first -> deliver is
    # emit_wait + hop, deliver -> client is out + written -> client
    read("gateway.post_engine_ms_p50", ctx)
    accepted = ctx.notes["gateway.post_engine_ms"]
    assert accepted["n"] == 4           # it needs no ``written``
    assert gateway_parts.load(ctx) is gateway_parts.load(ctx)


def test_the_longest_lags_are_named_with_the_span_and_the_collections(ring):
    window_of_dispatches(ring)
    pauses(ring)
    ctx = requests_context(ring)
    assert read("loop.lag_ms_max", ctx) == pytest.approx(30.0)
    note = ctx.notes["loop.lag_ms"]
    assert note["n"] == 3 and note["total_ms"] == pytest.approx(36.0)
    assert [round(row["ms"], 6) for row in note["longest"]] == [30.0, 4.0, 2.0]
    first, edge, short = note["longest"]
    assert first["at_s"] == pytest.approx(0.305) and first["thread"] == "MainThread"
    # it covers the third decode dispatch's spans (6.3 ms of 30): most of it
    # the dispatch thread spent under no span
    assert first["dispatch_thread_in"] == "(no span)"
    assert first["cause"] == "loop_lag0"
    assert [(c["cause"], round(c["ms"], 6), c["thread"])
            for c in first["collections"]] == [("gc2", 20.0, "MainThread")]
    assert short["dispatch_thread_in"] == "loop.wait" and not short["collections"]
    assert edge["collections"] == []


def test_no_lag_of_a_millisecond_reads_zero_on_a_program_that_stamps(ring):
    window_of_dispatches(ring)
    ctx = requests_context(ring)
    assert read("loop.lag_ms_max", ctx) == 0.0
    assert ctx.notes["loop.lag_ms"] == {"n": 0, "total_ms": 0.0, "longest": []}


def test_a_loop_lag_is_one_more_row_of_the_accepted_pause_note(ring):
    """``host.pauses`` counts it under ``loop_lag0`` beside ``gc2``, and
    ``host.stall_ms_max`` reads what it read without it."""
    window_of_dispatches(ring)
    plain = context(reduced())
    assert read("host.stall_ms_max", plain) == pytest.approx(6.3)
    pauses(ring)
    ctx = context(reduced())
    assert read("host.stall_ms_max", ctx) == pytest.approx(6.3)
    assert ctx.notes["host.stall"]["step"] == plain.notes["host.stall"]["step"]
    note = ctx.notes["host.pauses"]
    assert note["n"] == 5
    assert note["by_generation"] == {
        "loop_lag0": pytest.approx({"n": 3, "total_ms": 36.0, "longest_ms": 30.0}),
        "gc2": pytest.approx({"n": 1, "total_ms": 20.0, "longest_ms": 20.0}),
        "gc1": pytest.approx({"n": 1, "total_ms": 3.0, "longest_ms": 3.0})}
    assert [(p["cause"], p["thread"]) for p in note["longest"][:2]] == [
        ("loop_lag0", "MainThread"), ("gc2", "MainThread")]
    # the longest dispatch (0.300-0.3063) reaches into the long lag and ends
    # before the collection: its row names the one
    assert [p["cause"] for p in ctx.notes["host.stall"]["pauses_in_it"]] == [
        "loop_lag0"]


# ------------------------------------------- a ring without the new stamps

@pytest.mark.parametrize("name", NEW)
def test_new_readers_report_nothing_on_the_parents_ring(ring, name):
    """The parent of PR 55: ``submit`` / ``admit`` / ``first`` / ``deliver``
    and collections, no mark of the gateway, no ``emit``, no ``loop_lag``.
    Every new reader returns None, writes no note, raises nothing."""
    window_of_dispatches(ring)
    ring.add_pause("gc", T0 + 0.31, T0 + 0.33, 2, "MainThread")
    ctx = requests_context(ring, stamped=("submit", "first", "deliver"))
    assert read(name, ctx) is None and not ctx.notes


@pytest.mark.parametrize("name", NEW)
def test_new_readers_report_nothing_where_there_is_no_timeline(name, monkeypatch):
    monkeypatch.setitem(
        sys.modules, "mcp_context_forge_tpu.observability.timeline", None)
    ctx = context(reduced())
    assert read(name, ctx) is None and not ctx.notes


@pytest.mark.parametrize("missing", ["recv", "tokenized", "emit", "written"])
def test_a_request_without_one_stamp_is_left_out_of_all_four(ring, missing):
    window_of_dispatches(ring)
    ctx = requests_context(ring, stamped=[s for s in ORDER[:-1] if s != missing])
    assert [read(name, ctx) for name in NEW[:4]] == [None] * 4
    assert gateway_parts.load(ctx) is None


def test_an_untraced_run_holds_no_ids_and_reports_nothing(ring):
    window_of_dispatches(ring)
    ctx = requests_context(ring)
    ctx.submits.clear()
    assert [read(name, ctx) for name in NEW[:4]] == [None] * 4


# ------------------------------------------------------------ the manifest

def test_the_five_are_appended_and_reported_by_every_cell():
    doc = manifest.load()
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(NEW[0])
    tail = doc["per_layer"][first:first + len(NEW)]
    assert tuple(m["name"] for m in tail) == NEW
    assert first > names.index("gateway.post_engine_ms_p50")
    for metric in tail:
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves"}
        assert (metric["unit"], metric["better"], metric["source"],
                metric["layer"], metric["moves"]) == (
            "ms", "lower", "program_span", "gateway and /v1 route", "ttft_p50_ms")
    for cell in doc["workloads"]:
        reported = {m["name"] for m in manifest.cell(doc, cell["name"]).per_layer}
        assert set(NEW) <= reported
        # the accepted readers of the same layer and ring stay the cell's
        assert {"gateway.pre_engine_ms_p50", "gateway.post_engine_ms_p50",
                "host.stall_ms_max"} <= reported


def test_rehearsal_prints_all_five_and_the_sum_check_closes(capsys, tmp_path,
                                                            monkeypatch):
    """The whole path on the CPU: ``run.measure`` with the chat cell's metric
    list and the real gateway's stamps on the real engine's ring."""
    import test_benchmark_rehearsal as rehearsal

    from benchmark import run

    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    result, notes = rehearsal._measure("mistral-7b.chat", "open", True, capsys)
    assert result["correct"] is True, notes
    metrics, per_layer = result["metrics"], notes["per_layer_notes"]
    assert set(NEW) <= set(metrics)
    assert all(metrics[name]["value"] >= 0.0 for name in NEW)
    way_in, check = per_layer["gateway.in_ms"], per_layer["gateway.sum_check_ms"]
    assert way_in["n"] == check["n"] == result["attempted"]
    assert check["negative_pieces"] == 0
    assert check["sum_p50"] == pytest.approx(check["ttft_p50"], abs=1e-6)
    # the in-program way in is the harness's own, less what lies before the
    # middleware and the tracer's span after the request is made
    pre = metrics["gateway.pre_engine_ms_p50"]["value"]
    assert metrics["gateway.in_ms_p50"]["value"] < pre
    assert metrics["gateway.in_ms_p50"]["value"] == pytest.approx(
        pre - way_in["client_send_to_recv"]["p50"], abs=1.0)
    # and the way out is the accepted reader's, cut finer
    accepted = per_layer["gateway.post_engine_ms"]
    assert check["emit_wait"] + check["loop_hop"] == pytest.approx(
        accepted["first_to_deliver"]["p50"], abs=1.0)
    assert per_layer["loop.hop_ms"]["n"] == result["attempted"]
    assert per_layer["gateway.out_ms"]["written_to_client"]["p50"] > 0.0
    assert per_layer["loop.lag_ms"]["n"] >= 0
