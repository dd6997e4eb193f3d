"""The engine's step timeline (observability/timeline.py): one recorder on
the profiler's clock that every dispatch kind writes its phase spans, its
step record and its requests' stamps to — and that the engine's own
step numbers (``queue_ms``, ``prefill_ms``, ``dispatch_gap_ms_total``,
``decode_ms_total``, the llm.* span durations) are now read from.

Falsifiable form:

- every dispatch kind (dense prefill, history prefill, chunk round, host-fed
  and device-fed decode, speculative verify) leaves its full set of spans,
  the five named parts of its build and its dispatch nested inside them;
- a span carries the CPU time of its thread, a collection that pauses the
  process lands on every live ring as a ``pause`` (never a span), a first
  token is stamped ``deliver`` where it enters the request's stream, and a
  host-fed dispatch that holds the drained device too long is counted;
- the spans of one step sit around its ``t_dispatched..t_retired`` the way
  the code runs them: build and table sync before, dispatch and sync /
  read-back inside, emit after;
- ``t_submit <= t_admit <= t_first <= t_done`` for every request, on the
  dense, chunked, overlapped and serial paths;
- behind the gateway a streamed request's marks (``recv`` .. ``tokenized``,
  ``chunk``, ``written``) stand on the ring under the engine's id, around
  the engine's own stamps and in order; a shed request leaves none; a first
  token that falls into a blocked loop reads the block between ``emit`` and
  ``deliver``, beside the ``loop_lag`` pause the sampler left of it;
- the ring is bounded, and found through the registry;
- each jitted step function lowers to a module that the benchmark's
  ``trace_reduce.program_kind`` classifies by NAME, not by kernel shape.
"""

import asyncio
import itertools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from mcp_context_forge_tpu.observability import timeline as tl_mod
from mcp_context_forge_tpu.observability.tracing import Tracer
from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

BUILD_PARTS = ("rows", "sampling", "rng")          # children of <p>.build
DISPATCH_PARTS = ("upload", "launch")              # children of <p>.dispatch


def _children(family):
    return ({f"{family}.build.{part}" for part in BUILD_PARTS}
            | {f"{family}.dispatch.{part}" for part in DISPATCH_PARTS})


PREFILL_SPANS = {"prefill.build", "prefill.dispatch", "prefill.sync",
                 "prefill.emit"} | _children("prefill")
DECODE_SPANS = {"decode.build", "decode.table_sync", "decode.dispatch",
                "decode.readback", "decode.emit"} | _children("decode")
SPANS_OF = {"prefill": PREFILL_SPANS, "prefill_hist": PREFILL_SPANS,
            "chunk": PREFILL_SPANS, "decode": DECODE_SPANS,
            "decode_fb": DECODE_SPANS, "spec": DECODE_SPANS}


def _config(**overrides):
    kwargs = dict(model="llama3-test", max_batch=4, max_seq_len=128,
                  page_size=16, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference")
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


_IDS = itertools.count()     # request ids unique across the module's rings


def _serve(engine, prompts, max_tokens=6, together=True, tracer_ctx=None):
    """Run ``prompts`` through the engine; returns the finished requests."""
    async def main():
        await engine.start()
        try:
            requests = [GenRequest(request_id=f"r{next(_IDS)}",
                                   prompt_ids=list(p), max_tokens=max_tokens,
                                   trace_ctx=tracer_ctx)
                        for p in prompts]

            async def one(request):
                await engine.submit(request)
                while await request.stream.get() is not None:
                    pass
                return request

            if together:
                return await asyncio.wait_for(
                    asyncio.gather(*[one(r) for r in requests]), 300)
            return [await asyncio.wait_for(one(r), 300) for r in requests]
        finally:
            await engine.stop()
    return asyncio.run(main())


@pytest.fixture(scope="module")
def overlapped():
    """One overlapped engine driven through dense prefill, a prefix-cache hit
    (history prefill), a chunked prompt and a shared decode batch."""
    engine = TPUEngine(_config(decode_overlap=True))
    shared = list(range(40, 80))                    # 40 tokens: bucket 64
    done = _serve(engine, [shared], together=False)
    # 32 cached tokens (two full pages) + an 8-token suffix: bucket 16
    done += _serve(engine, [shared[:32] + list(range(200, 208))],
                   together=False)
    done += _serve(engine, [list(range(300, 400)),  # 100 > 64: chunk rounds
                            list(range(10, 20)), list(range(20, 32))])
    done += _staggered(engine)
    return engine, done, engine.timeline.snapshot()


def _staggered(engine):
    """A second request arrives while the first decodes: its admission is a
    drain barrier for the step in flight."""
    async def main():
        await engine.start()
        try:
            first = GenRequest(request_id="early", prompt_ids=list(range(50, 60)),
                               max_tokens=24)
            late = GenRequest(request_id="late", prompt_ids=list(range(60, 72)),
                              max_tokens=4)
            await engine.submit(first)
            for _ in range(3):
                await asyncio.wait_for(first.stream.get(), 120)
            late.t_submit = tl_mod.perf_counter()    # built early, sent now
            await engine.submit(late)
            for request in (first, late):
                while await asyncio.wait_for(request.stream.get(), 120) is not None:
                    pass
            return [first, late]
        finally:
            await engine.stop()
    return asyncio.run(main())


@pytest.fixture(scope="module")
def serial():
    engine = TPUEngine(_config(decode_overlap=False))
    done = _serve(engine, [list(range(10, 20)), list(range(30, 45))],
                  max_tokens=8)
    return engine, done, engine.timeline.snapshot()


@pytest.fixture(scope="module")
def spec():
    engine = TPUEngine(_config(spec_decode=True, spec_k=4))
    done = _serve(engine, [[7, 8, 9, 7, 8, 9, 7, 8]], max_tokens=8)
    return engine, done, engine.timeline.snapshot()


def _ring_for(kind, overlapped, serial, spec):
    return {"spec": spec, "decode": serial}.get(kind, overlapped)[2]


@pytest.mark.parametrize("kind", sorted(SPANS_OF))
def test_every_dispatch_kind_leaves_its_full_set_of_spans(
        kind, overlapped, serial, spec):
    ring = _ring_for(kind, overlapped, serial, spec)
    steps = [s for s in ring["step"] if s.kind == kind]
    assert steps, f"no {kind} step was dispatched: {set(s.kind for s in ring['step'])}"
    for step in steps:
        names = {s.name for s in ring["span"]
                 if s.step == step.seq and s.name != "loop.drain"}
        assert names == SPANS_OF[kind], (kind, step.seq, names)
        assert all(s.kind == kind for s in ring["span"]
                   if s.step == step.seq and s.name != "loop.drain")
        assert step.rows >= 1 and step.width >= step.rows
        assert step.t_retired > step.t_dispatched


@pytest.mark.parametrize("kind", sorted(SPANS_OF))
def test_spans_of_one_step_sit_around_dispatched_to_retired(
        kind, overlapped, serial, spec):
    ring = _ring_for(kind, overlapped, serial, spec)
    family = "prefill" if SPANS_OF[kind] is PREFILL_SPANS else "decode"
    wait = "sync" if family == "prefill" else "readback"
    for step in (s for s in ring["step"] if s.kind == kind):
        span = {s.name: s for s in ring["span"] if s.step == step.seq}
        assert span[f"{family}.build"].t1 <= step.t_dispatched
        assert span[f"{family}.dispatch"].t0 == step.t_dispatched
        assert span[f"{family}.{wait}"].t1 == step.t_retired
        assert span[f"{family}.dispatch"].t1 <= span[f"{family}.{wait}"].t0
        assert span[f"{family}.emit"].t0 >= step.t_retired
        if family == "decode":
            assert (span["decode.build"].t1 <= span["decode.table_sync"].t0
                    <= span["decode.table_sync"].t1 <= step.t_dispatched)


@pytest.mark.parametrize("kind", sorted(SPANS_OF))
def test_parts_of_a_dispatch_nest_in_their_parents_in_order(
        kind, overlapped, serial, spec):
    """``rows``, ``sampling``, ``rng`` inside ``<p>.build`` and ``upload``,
    ``launch`` inside ``<p>.dispatch``, one after the other, each with the
    parent's step and kind: what ``flatten`` needs to give every instant of
    a parent to the part that ran."""
    ring = _ring_for(kind, overlapped, serial, spec)
    family = "prefill" if SPANS_OF[kind] is PREFILL_SPANS else "decode"
    steps = [s for s in ring["step"] if s.kind == kind]
    assert steps
    for step in steps:
        span = {s.name: s for s in ring["span"] if s.step == step.seq}
        for parent, parts in (("build", BUILD_PARTS),
                              ("dispatch", DISPATCH_PARTS)):
            outer = span[f"{family}.{parent}"]
            inner = [span[f"{family}.{parent}.{part}"] for part in parts]
            assert outer.t0 <= inner[0].t0 and inner[-1].t1 <= outer.t1
            for before, after in zip(inner, inner[1:]):
                assert before.t1 <= after.t0
            assert all(s.kind == kind and s.step == step.seq for s in inner)
            # the parts are most of their parent: what is left is a bucket
            # lookup and the span bookkeeping, never a second's compile
            assert sum(s.t1 - s.t0 for s in inner) <= outer.t1 - outer.t0
        # the jitted call alone ends the dispatch span
        assert span[f"{family}.dispatch.launch"].t1 <= span[f"{family}.dispatch"].t1


@pytest.mark.parametrize("path", ["overlapped", "serial", "spec"])
def test_the_benchmarks_host_parts_find_every_host_fed_dispatch(
        path, request, monkeypatch):
    """``benchmark.harness.host_parts._load`` drops a dispatch that lacks one
    of the five child spans, and the seven ``hostfed.*`` / ``host.*`` readers
    then read nothing of it: it has to find every host-fed dispatch of a
    run, of every kind (read-only use of the benchmark's code)."""
    from benchmark.harness import host_parts

    engine = request.getfixturevalue(path)[0]
    monkeypatch.setattr(tl_mod, "get_timeline", lambda _replica: engine.timeline)
    host = host_parts._load((0.0, float("inf")))
    fed = {s.seq: s.kind for s in engine.timeline.snapshot()["step"]
           if s.kind != "decode_fb"}
    assert fed and {d.step: d.kind for d in host.dispatches} == fed
    for dispatch in host.dispatches:
        assert set(host_parts.CHILDREN) <= set(dispatch.spans)
        assert dispatch.t0 < dispatch.t1


@pytest.mark.parametrize("path", ["overlapped", "serial", "spec"])
def test_spans_carry_cpu_time_within_their_wall(path, request):
    _engine, _done, ring = request.getfixturevalue(path)
    assert ring["span"]
    for span in ring["span"]:
        # boundaries within CPU_REUSE_S of each other share one clock reading
        assert 0.0 <= span.cpu <= (span.t1 - span.t0) + tl_mod.CPU_REUSE_S \
            + 1e-6, span
    # the loop's wait for work sleeps on an event: off the CPU
    waits = [s for s in ring["span"] if s.name == "loop.wait"
             and s.t1 - s.t0 > 0.02]
    assert all(s.cpu < 0.5 * (s.t1 - s.t0) for s in waits)
    # the packing of rows is Python on this thread: on it
    rows = [s for s in ring["span"] if s.name.endswith(".build.rows")]
    assert sum(s.cpu for s in rows) > 0.0


def test_a_span_that_sleeps_reads_no_cpu_and_one_that_spins_reads_its_wall():
    import time

    timeline = tl_mod.StepTimeline("cpu")
    with timeline.span("sleeps") as asleep:
        time.sleep(0.05)
    with timeline.span("spins") as busy:
        until = tl_mod.perf_counter() + 0.05
        while tl_mod.perf_counter() < until:
            pass
    assert asleep.t1 - asleep.t0 >= 0.05 and asleep.cpu < 0.005
    # a spinning thread may lose the CPU to another test worker, never gain
    assert 0.01 < busy.cpu <= busy.t1 - busy.t0 + tl_mod.CPU_REUSE_S
    recorded = {s.name: s for s in timeline.snapshot()["span"]}
    assert recorded["sleeps"].cpu == asleep.cpu
    assert recorded["spins"].cpu == busy.cpu
    assert tl_mod.SpanEvent("a", 0.0, 1.0, 0, "", "0").cpu == 0.0


def test_span_boundaries_close_together_share_one_cpu_clock_reading(monkeypatch):
    """The CPU clock is a system call (6 us on the chip's host): a child's
    end and its parent's, a part's end and the next part's start read it
    once. Another thread, or the same one later, reads it afresh."""
    import threading

    reads = []
    real = tl_mod.thread_time

    def counted():
        reads.append(threading.get_ident())
        return real()

    monkeypatch.setattr(tl_mod, "thread_time", counted)
    timeline = tl_mod.StepTimeline("reuse")
    with timeline.span("decode.build", 1, "decode"):
        with timeline.span("decode.build.rows", 1, "decode"):
            until = tl_mod.perf_counter() + 10 * tl_mod.CPU_REUSE_S
            while tl_mod.perf_counter() < until:
                pass
        with timeline.span("decode.build.rng", 1, "decode"):
            pass
    # build's start (rows' with it), rows' end (rng's start, rng's end and
    # build's end with it, where the box is quick): four boundaries fewer
    assert 2 <= len(reads) <= 4, reads
    spans = {s.name: s for s in timeline.snapshot()["span"]}
    assert spans["decode.build.rows"].cpu > 5 * tl_mod.CPU_REUSE_S
    assert spans["decode.build"].cpu >= spans["decode.build.rows"].cpu
    before = len(reads)
    other = threading.Thread(target=lambda: timeline.cpu_at(tl_mod.perf_counter()))
    other.start()
    other.join(10)
    assert len(reads) == before + 1 and reads[-1] != reads[0]
    import time
    time.sleep(2 * tl_mod.CPU_REUSE_S)
    timeline.cpu_at(tl_mod.perf_counter())
    assert len(reads) == before + 2


def _cycle_heap(n=400_000):
    """Garbage only the collector can free: n two-object cycles."""
    for _ in range(n):
        a: list = []
        a.append([a])


def test_a_collection_lands_as_a_pause_on_every_live_timeline_and_no_dead_one():
    import gc

    first, second = tl_mod.StepTimeline("gc-a"), tl_mod.StepTimeline("gc-b")
    dead = tl_mod.StepTimeline("gc-dead")
    dead_ring = dead._ring                # the ring outlives its timeline here
    del dead
    before = dict(tl_mod.gc_watch.stats()["gen2"])
    was_on = gc.isenabled()
    gc.disable()                          # one collection, ours, of all of it
    try:
        gc.collect()
        _cycle_heap()
        t0 = tl_mod.perf_counter()
        gc.collect()
        t1 = tl_mod.perf_counter()
    finally:
        if was_on:
            gc.enable()
    import threading
    for timeline in (first, second):
        pauses = [p for p in timeline.snapshot()["pause"] if p.t0 >= t0]
        assert len(pauses) == 1, pauses
        (pause,) = pauses
        assert pause.cause == "gc" and pause.detail == 2
        assert pause.thread == threading.current_thread().name
        assert t0 <= pause.t0 < pause.t1 <= t1
        assert pause.t1 - pause.t0 >= tl_mod.PAUSE_S
        assert timeline.pauses_between(t0, t1) == [pause]
        assert timeline.pauses_between(t1, t1 + 1.0) == []
    assert not [e for e in dead_ring if e[0] == tl_mod.PAUSE and e[2] >= t0]
    after = tl_mod.gc_watch.stats()["gen2"]
    assert after["collections"] == before["collections"] + 2
    assert after["total_ms"] > before["total_ms"]
    assert after["longest_ms"] >= (pause.t1 - pause.t0) * 1e3 - 1e-3
    assert first.gc_stats()["gen2"] == after


def test_gc_hook_is_installed_once_and_counts_short_collections_only(serial, spec):
    """One ``gc.callbacks`` entry for the process whatever the number of
    engines and timelines; a collection under ``PAUSE_S`` adds to the counts
    and writes no event; with no timeline alive the hook still only counts."""
    import gc

    watch = tl_mod.gc_watch
    for i in range(3):
        tl_mod.StepTimeline(f"once-{i}")
    assert serial[0].timeline is not spec[0].timeline
    hooks = [cb for cb in gc.callbacks
             if getattr(cb, "__self__", None) is watch]
    assert len(hooks) == 1 and watch.installed
    assert len(gc.callbacks) == len(set(map(id, gc.callbacks)))
    timeline = tl_mod.StepTimeline("short")
    gen0 = watch.stats()["gen0"]["collections"]
    t0 = tl_mod.perf_counter()
    gc.collect(0)                         # nothing young to free: microseconds
    assert watch.stats()["gen0"]["collections"] == gen0 + 1
    assert not [p for p in timeline.snapshot()["pause"] if p.t0 >= t0
                and p.t1 - p.t0 < tl_mod.PAUSE_S]
    assert sum(watch.buckets[0]) == gen0 + 1
    # harmless without a live timeline: the hook runs, counts, writes nothing
    live = list(watch._live)
    try:
        watch._live.clear()
        watch._on_gc("start", {"generation": 2})
        watch._t0 -= 1.0                  # a second's collection
        watch._on_gc("stop", {"generation": 2})
    finally:
        watch._live.update(live)
    assert watch.stats()["gen2"]["longest_ms"] >= 1000.0
    assert not [p for p in timeline.snapshot()["pause"]
                if p.t1 - p.t0 >= 1.0]


def test_a_pause_is_not_a_span_and_never_enters_flatten():
    from benchmark.harness import timeline_view

    timeline = tl_mod.StepTimeline("flat")
    timeline.add_span("decode.build", 1.0, 2.0, 1, "decode")
    timeline.add_pause("gc", 1.2, 1.9, 2, "MainThread")   # cuts across it
    timeline.add_span("decode.dispatch", 2.0, 2.5, 1, "decode")
    ring = timeline.snapshot()
    assert [p.cause for p in ring["pause"]] == ["gc"]
    assert {s.name for s in ring["span"]} == {"decode.build", "decode.dispatch"}
    view = timeline_view.load("flat")
    assert view.segments == [(1.0, 2.0, "decode.build"),
                             (2.0, 2.5, "decode.dispatch")]
    assert view.cover(1.0, 2.5) == pytest.approx(
        {"decode.build": 1.0, "decode.dispatch": 0.5})


def test_a_dispatch_held_by_a_slow_upload_is_counted_and_logged_once(
        monkeypatch, caplog):
    """A host-fed dispatch whose build.t0 -> dispatch.t1 passes ``STALL_S``
    adds to ``dispatch_stalls`` and logs the part that held it; a device-fed
    one, however slow its host side, holds nothing up and is not one."""
    import logging
    import time

    from mcp_context_forge_tpu.tpu_local import engine as engine_mod

    engine = TPUEngine(_config(decode_overlap=True, prefix_cache=False))
    # warm every program this traffic uses, so that no compile is a stall
    # (twice: a prefill over the pool a decode step returned compiles again)
    for _ in range(2):
        _serve(engine, [list(range(10, 20))], max_tokens=6)
    assert engine.stats.dispatch_stalls >= 1     # unwarmed: the compiles
    real = engine_mod.jax.device_put
    slow = {"left": 0}
    calls = [(4, layout.width) for layout in (engine._decode_call,
                                              engine._decode_fb_call)]

    def device_put(value, *args, **kwargs):
        if slow["left"] and getattr(value, "shape", None) in calls:
            slow["left"] -= 1             # a decode step's packed call
            time.sleep(0.03)
        return real(value, *args, **kwargs)

    monkeypatch.setattr(engine_mod.jax, "device_put", device_put)
    before = engine.stats.dispatch_stalls
    slow["left"] = 2                      # a host-fed step, then a fed one
    with caplog.at_level(logging.WARNING, logger=engine_mod.logger.name):
        caplog.clear()
        _serve(engine, [list(range(10, 20))], max_tokens=6)
    assert slow["left"] == 0
    lines = [r.getMessage() for r in caplog.records
             if "dispatch stall" in r.getMessage()]
    # one line a stall (a busy box may stall another dispatch by itself)
    assert engine.stats.dispatch_stalls == before + len(lines)
    held = [line for line in lines if "upload took" in line]
    assert len(held) == 1, lines
    assert "(decode)" in held[0] and "pauses in it:" in held[0]
    ring = engine.timeline.snapshot()
    slept = [s for s in ring["span"] if s.name == "decode.dispatch.upload"
             and s.t1 - s.t0 >= 0.03]
    assert sorted(s.kind for s in slept) == ["decode", "decode_fb"]
    assert all(s.cpu < 0.02 for s in slept)     # asleep, not working


def test_step_numbers_are_the_step_rings(overlapped):
    engine, _done, ring = overlapped
    rows = {row["seq"]: row for row in engine.recent_steps()}
    by_seq = {s.seq: s for s in ring["step"]}
    assert set(rows) <= set(by_seq) and len(by_seq) == len(ring["step"])
    to_row = {"prefill": "prefill", "prefill_hist": "prefill",
              "chunk": "chunk_prefill", "decode": "decode",
              "decode_fb": "decode"}
    for seq, row in rows.items():
        assert row["kind"] == to_row[by_seq[seq].kind]
        assert row["width"] == by_seq[seq].width
        assert row["batch"] == by_seq[seq].rows
    seqs = [s.seq for s in ring["step"]]
    assert seqs == sorted(seqs)          # retire order is dispatch order


def test_loop_spans_and_drain_barrier(overlapped):
    _engine, _done, ring = overlapped
    names = {s.name for s in ring["span"]}
    assert {"loop.wait", "loop.flush", "loop.drain", "admit"} <= names
    # an iteration's flush has no kind; the early one of first tokens alone,
    # once a prefill or chunk round that emitted, is of kind "first"
    flushes = [s.kind for s in ring["span"] if s.name == "loop.flush"]
    assert set(flushes) == {"", "first"}
    assert flushes.count("first") == _engine.stats.first_flushes == sum(
        1 for s in ring["span"] if s.name == "prefill.emit"
        and any(e.phase == "first" and s.t0 <= e.t <= s.t1
                for e in ring["req"]))
    # a drain barrier retires the in-flight step: its read-back and emit
    # nest inside the loop.drain span of that step
    drains = [s for s in ring["span"] if s.name == "loop.drain"]
    for drain in drains:
        inner = [s for s in ring["span"] if s.step == drain.step
                 and s.name in ("decode.readback", "decode.emit")]
        assert len(inner) == 2
        assert all(drain.t0 <= s.t0 and s.t1 <= drain.t1 for s in inner)


@pytest.mark.parametrize("path", ["overlapped", "serial", "spec"])
def test_request_stamps_are_ordered_on_every_path(path, request):
    engine, done, ring = request.getfixturevalue(path)
    assert done
    stamps: dict[str, dict[str, float]] = {}
    for event in ring["req"]:
        stamps.setdefault(event.request_id, {})[event.phase] = event.t
    for finished in done:
        assert 0 < finished.t_submit <= finished.t_admit \
            <= finished.t_first <= finished.t_done
        # the first token reaches the request's stream after it was stamped
        # ``first``, once: the dispatch thread's flush (``emit``) and the
        # hop to the loop's thread lie between
        assert finished.t_first <= finished.t_emit <= finished.t_deliver
        # and it leaves when it is made: the dispatch thread builds no
        # dispatch (the iteration's decode or verify step, a chunk round)
        # between sampling a first token and handing it to the loop
        assert not [s for s in ring["span"]
                    if s.name in ("prefill.build", "decode.build")
                    and finished.t_first <= s.t0 <= finished.t_emit]
        assert stamps[finished.request_id] == {
            "submit": finished.t_submit, "admit": finished.t_admit,
            "first": finished.t_first, "emit": finished.t_emit,
            "deliver": finished.t_deliver, "done": finished.t_done}
        for phase in ("emit", "deliver"):
            assert sum(1 for e in ring["req"] if e.phase == phase
                       and e.request_id == finished.request_id) == 1
    slots = {e.slot for e in ring["req"] if e.phase != "submit"}
    assert slots <= set(range(engine.config.max_batch))


def test_chunked_request_is_stamped_once_and_prefill_ms_adds_rounds(overlapped):
    _engine, done, ring = overlapped
    chunked = next(r for r in done if r.chunked)
    rounds = [s for s in ring["step"] if s.kind == "chunk"]
    assert len(rounds) >= 2
    # admitted before its first round, first token after its last
    assert chunked.t_admit <= rounds[0].t_dispatched
    assert chunked.t_first >= rounds[-1].t_retired
    assert sorted(e.phase for e in ring["req"]
                  if e.request_id == chunked.request_id) == [
        "admit", "deliver", "done", "emit", "first", "submit"]
    # prefill_ms accumulates each round's build -> first-tokens-on-host wall
    walls = []
    for step in rounds:
        build = next(s for s in ring["span"] if s.step == step.seq
                     and s.name == "prefill.build")
        walls.append((step.t_retired - build.t0) * 1e3)
    assert chunked.prefill_ms == pytest.approx(sum(walls), rel=1e-9)


def test_queue_ms_and_prefill_ms_keep_their_meaning(serial):
    engine, done, ring = serial
    for finished in done:
        assert finished.queue_ms == pytest.approx(
            (finished.t_admit - finished.t_submit) * 1e3, rel=1e-9)
        assert 0 < finished.prefill_ms <= \
            (finished.t_first - finished.t_admit) * 1e3 + 1e-6
    prefills = [s for s in ring["step"] if s.kind == "prefill"]
    walls = [(s.t_retired - next(
        p for p in ring["span"]
        if p.step == s.seq and p.name == "prefill.build").t0) * 1e3
        for s in prefills]
    assert engine.stats.prefill_ms_total == pytest.approx(sum(walls), rel=1e-9)
    assert engine.stats.prefill_batches == len(prefills)


@pytest.mark.parametrize("path", ["overlapped", "serial"])
def test_gap_and_decode_wall_come_from_the_step_records(path, request):
    """``dispatch_gap_ms_total``: host time from the last retire (of any
    step) to a host-fed decode dispatch, zero for a device-fed one.
    ``decode_ms_total``: retire-to-retire where a step was dispatched over
    its predecessor, dispatch-to-retire where the device was drained."""
    engine, _done, ring = request.getfixturevalue(path)
    gap = wall = 0.0
    last = None
    fed = 0
    for step in ring["step"]:          # ring order = retire order
        if step.kind in ("decode", "decode_fb"):
            wall += step.t_retired - max(step.t_dispatched, last or 0.0)
        last = step.t_retired
    # a dispatch's gap looks back from ITS dispatch: walk in dispatch order
    retired = sorted(ring["step"], key=lambda s: s.t_retired)
    for step in sorted(ring["step"], key=lambda s: s.t_dispatched):
        if step.kind == "decode":
            before = [r.t_retired for r in retired
                      if r.t_retired <= step.t_dispatched and r.seq < step.seq]
            gap += max(0.0, step.t_dispatched - before[-1]) if before else 0.0
        fed += step.kind == "decode_fb"
    assert engine.stats.dispatch_gap_ms_total == pytest.approx(gap * 1e3, rel=1e-6)
    assert engine.stats.decode_ms_total == pytest.approx(wall * 1e3, rel=1e-6)
    assert engine.stats.overlap_steps == fed
    assert (fed > 0) == (path == "overlapped")
    rows = [r for r in engine.recent_steps() if r["kind"] == "decode"]
    assert sum(r["gap_ms"] for r in rows) == pytest.approx(gap * 1e3, abs=0.01 * len(rows))
    if path == "overlapped":
        # dispatch->retire of a fed step spans its predecessor too: the
        # per-step wall must come out smaller than the summed durations
        assert engine.stats.decode_ms_total < sum(r["duration_ms"] for r in rows)


def test_llm_spans_take_their_durations_from_the_stamps():
    tracer = Tracer(exporter="memory")
    engine = TPUEngine(_config(), tracer=tracer)
    (done,) = _serve(engine, [list(range(10, 22))], max_tokens=5,
                     tracer_ctx=("a" * 32, "b" * 16))
    spans = {s.name: s for s in tracer.finished}
    queue, prefill, decode = (spans[n] for n in
                              ("llm.queue", "llm.prefill", "llm.decode"))
    assert queue.start_ts == done.created         # wall-clock anchor
    assert queue.duration_ms == pytest.approx(done.queue_ms, abs=1e-3)
    assert prefill.start_ts == pytest.approx(queue.end_ts, abs=1e-6)
    assert prefill.duration_ms == pytest.approx(
        (done.t_first - done.t_admit) * 1e3, abs=1e-3)
    assert decode.start_ts == pytest.approx(prefill.end_ts, abs=1e-6)
    assert decode.duration_ms == pytest.approx(
        (done.t_done - done.t_first) * 1e3, abs=1e-3)


def test_ring_is_bounded_and_always_on():
    timeline = tl_mod.StepTimeline("bounded")
    for i in range(tl_mod.RING_EVENTS + 100):
        timeline.add_span("decode.build", float(i), float(i) + 0.5, i, "decode")
    ring = timeline.snapshot()
    assert len(ring["span"]) == tl_mod.RING_EVENTS
    assert ring["span"][0].step == 100            # the oldest fell out
    assert not hasattr(EngineConfig(), "timeline") # no setting, no off switch


def test_spans_kept_in_the_ring_are_not_containers_the_collector_counts():
    """A span is kept as packed bytes: the collector's count of young
    containers (what triggers a collection once 700 are kept) does not move
    with the spans a ring keeps. It did when a span was a tuple: the ring
    was most of what the serving process kept, and a third more spans a step
    brought its 0.7 s full collections into most windows (PERF.md, PR 39)."""
    import gc

    timeline = tl_mod.StepTimeline("untracked")
    timeline.add_span("decode.build", 1.0, 2.0, 1, "decode", 0.5)   # the table
    was_on = gc.isenabled()
    gc.disable()
    try:
        gc.collect()
        before = gc.get_count()[0]
        for i in range(5000):
            with timeline.span("decode.build", i, "decode"):
                pass
            timeline.add_span("decode.dispatch", 1.0, 2.0, i, "decode", 0.5)
        kept = gc.get_count()[0] - before
    finally:
        if was_on:
            gc.enable()
    assert kept < 50, kept                 # 10000 spans kept, none counted
    assert sum(type(e) is bytes and not gc.is_tracked(e)
               for e in timeline._ring) == 10001
    ring = timeline.snapshot()["span"]
    assert len(ring) == 10001
    assert ring[-1] == tl_mod.SpanEvent("decode.dispatch", 1.0, 2.0, 4999,
                                        "decode", "untracked", 0.5)
    assert ring[-2].name == "decode.build" and ring[-2].step == 4999
    assert len(timeline._strings) == 3      # two names and a kind


def test_ring_holds_two_minutes_of_the_busiest_cell(overlapped):
    """``mistral-7b.chat`` runs ~4300 decode steps, 215 prefills and 250
    requests in its 50 s window (ledger, PR 38), and the window readers read
    the ring after the drain: 120 s of that rate must fit. Events a step are
    counted on a real ring, so a span added to a dispatch shows here."""
    _engine, _done, ring = overlapped

    def events_of(kinds):
        counts = []
        for step in (s for s in ring["step"] if s.kind in kinds):
            spans = sum(1 for s in ring["span"] if s.step == step.seq)
            counts.append(spans + 1 + 1)     # + its record + a loop.flush
        return max(counts)

    decode = events_of(("decode", "decode_fb"))
    # + admit + the early flush of its first tokens
    prefill = events_of(("prefill", "prefill_hist", "chunk")) + 2
    assert 12 <= decode <= 14 and 12 <= prefill <= 14, (decode, prefill)
    stamps = len({e.phase for e in ring["req"]})
    assert stamps == 6
    # behind a gateway a request leaves six more (recv .. tokenized, chunk,
    # written) and the loop's lag at most a pause a tick, four a second
    a_second = (4300 * decode + 215 * (prefill + 2)
                + 250 * (stamps + 6)) / 50.0 + 4
    assert 120.0 * a_second <= tl_mod.RING_EVENTS, a_second
    assert 120.0 * a_second > tl_mod.RING_EVENTS // 2    # and not by 4 x


def dispatch_overhead_us(dispatches=2000):
    """CPU microseconds of ALL the timeline's bookkeeping on the host's side
    of one host-fed decode dispatch: the three spans that were there
    (build, table sync, dispatch), the five PR 39 nested in them, every
    reading of the CPU clock, the stall check and the phase row with its
    histograms. ``thread_time``, so a busy box does not inflate it."""
    from time import thread_time

    from mcp_context_forge_tpu.observability.metrics import PrometheusRegistry

    engine = TPUEngine.__new__(TPUEngine)
    engine.config = _config()
    engine.metrics = PrometheusRegistry()
    engine._phase_observers = {}
    engine.timeline = tl = tl_mod.StepTimeline("overhead")
    with tl.span("decode.readback") as readback:
        pass
    best = float("inf")
    for _ in range(3):
        start = thread_time()
        for seq in range(dispatches):
            parts = {}
            with tl.span("decode.build", seq, "decode") as build:
                for part in ("rows", "sampling", "rng"):
                    with tl.span("decode.build." + part, seq, "decode") \
                            as parts[part]:
                        pass
            with tl.span("decode.table_sync", seq, "decode") \
                    as parts["table_sync"]:
                pass
            with tl.span("decode.dispatch", seq, "decode") as dispatch:
                for part in ("upload", "launch"):
                    with tl.span("decode.dispatch." + part, seq, "decode") \
                            as parts[part]:
                        pass
            engine._host_fed(seq, "decode", build, dispatch, parts)
            engine._phase_row(parts, build, readback)
        best = min(best, (thread_time() - start) / dispatches * 1e6)
    return best


def test_bookkeeping_of_a_dispatch_stays_inside_its_budget():
    """The timeline has no off switch, so every run pays for it: the issue's
    budget for what it ADDED to a dispatch is 50 us of CPU on the chip's
    host (0.45 % of the shortest step in the benchmark, 11.5 ms); this holds
    the WHOLE of a host-fed dispatch's bookkeeping, the older spans too, to
    three times that on a slower and busier box (the sandbox reads ~30 us;
    PERF.md section 6 has the chip host's reading, where a CPU clock read
    is a 6 us system call)."""
    took = dispatch_overhead_us()
    assert 0.0 < took <= 150.0, f"{took:.1f} us a dispatch"


def test_ring_is_copied_while_two_threads_append():
    """The dispatch thread and the asyncio thread append while a reader
    copies: no copy fails, no event is torn, the bound holds."""
    import threading

    timeline = tl_mod.StepTimeline("stress")
    stop = threading.Event()

    def spans():
        i = 0
        while not stop.is_set():
            i += 1
            with timeline.span("decode.build", i, "decode_fb"):
                pass
            timeline.step(i, "decode_fb", 4, 1, 4, float(i), float(i) + 0.5)

    def stamps():
        i = 0
        while not stop.is_set():
            i += 1
            timeline.stamp("submit", f"r{i}", -1)

    workers = [threading.Thread(target=spans), threading.Thread(target=stamps),
               threading.Thread(target=stamps)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for worker in workers:
            worker.start()
        copies = 0
        deadline = tl_mod.perf_counter() + 1.5
        # at least four copies however busy the box is (the writers can
        # starve this thread for most of 1.5 s under six test workers)
        while copies <= 3 or tl_mod.perf_counter() < deadline:
            ring = timeline.snapshot()
            copies += 1
            assert sum(len(v) for v in ring.values()) <= tl_mod.RING_EVENTS
            assert all(s.t1 >= s.t0 and s.name == "decode.build"
                       for s in ring["span"][-50:])
            assert all(e.phase == "submit" for e in ring["req"][-50:])
    finally:
        stop.set()
        for worker in workers:
            worker.join(10)
        sys.setswitchinterval(interval)
    assert copies > 3 and not any(w.is_alive() for w in workers)
    assert timeline.last_retired is not None


def test_registry_finds_the_newest_live_engine(serial):
    engine = serial[0]
    other = TPUEngine(_config(replica_id="timeline-test-7"))
    assert tl_mod.get_timeline("timeline-test-7") is other.timeline
    assert other.timeline.replica == "timeline-test-7"
    assert tl_mod.get_timeline("no-such-replica") is None
    assert engine.timeline is not other.timeline
    assert other.last_step_age() is None          # nothing retired yet
    assert engine.last_step_age() >= 0.0
    del other                                     # the registry holds no engine
    import gc
    gc.collect()
    assert tl_mod.get_timeline("timeline-test-7") is None


# --------------------------------- a request's way through the gateway

WAY = ("recv", "authed", "parsed", "tokenized", "submit", "admit", "first",
       "emit", "deliver", "chunk", "written")
BLOCK_S = 0.03


class _RenderEveryToken:
    """The engine's tokenizer with a ``decode`` that renders every id (the
    byte-level one drops most ids of a random model: no content chunk)."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def decode(self, ids):
        return "".join(chr(33 + i % 94) for i in ids)


@pytest.fixture(scope="module")
def through_the_gateway():
    """The in-process gateway on the toy model, one loop: a streamed request,
    a shed one, and a streamed one whose first token finds the loop blocked
    for ``BLOCK_S`` (a ``time.sleep`` in a callback queued the moment the
    dispatch thread stamps ``first``: before the flush's own callback)."""
    import json
    import time

    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from mcp_context_forge_tpu.config import load_settings
    from mcp_context_forge_tpu.gateway.app import build_app

    admin = aiohttp.BasicAuth("admin", "changeme")
    user = aiohttp.BasicAuth("shed@example.com", "Vq8#mRt2xW!s")
    env = {"MCPFORGE_DATABASE_URL": "sqlite:///:memory:",
           "MCPFORGE_PLUGINS_ENABLED": "false",
           "MCPFORGE_GATEWAY_HEALTH_INTERVAL": "3600",
           "MCPFORGE_TPU_LOCAL_ENABLED": "true",
           "MCPFORGE_TPU_LOCAL_MODEL": "llama3-test",
           "MCPFORGE_TPU_LOCAL_MAX_BATCH": "4",
           "MCPFORGE_TPU_LOCAL_MAX_SEQ_LEN": "128",
           "MCPFORGE_TPU_LOCAL_PAGE_SIZE": "16",
           "MCPFORGE_TPU_LOCAL_NUM_PAGES": "64",
           "MCPFORGE_TPU_LOCAL_PREFILL_BUCKETS": "64",
           "MCPFORGE_TPU_LOCAL_DTYPE": "float32",
           "MCPFORGE_GW_LOOP_LAG_INTERVAL_S": "0.01",
           # the class every tenant has unless mapped sheds at any load;
           # the admin is mapped out of it
           "MCPFORGE_GW_SHED_SATURATION_AT": "0.0",
           "MCPFORGE_GW_SHED_CLASS_ORDER": '["default"]',
           "MCPFORGE_SLO_TENANT_CLASSES": json.dumps(
               {"user:admin@example.com": "premium"})}

    async def main():
        app = await build_app(load_settings(env=env, env_file=None))
        engine = app["tpu_engine"]
        engine.tokenizer = _RenderEveryToken(engine.tokenizer)
        client = TestClient(TestServer(app))
        await client.start_server()
        out = {}

        async def chat(auth, content, stream=True):
            body = {"model": "llama3-test", "stream": stream, "max_tokens": 24,
                    "messages": [{"role": "user", "content": content}]}
            before = {e.request_id for e in engine.timeline.snapshot()["req"]}
            resp = await client.post("/v1/chat/completions", auth=auth,
                                     json=body)
            text = await resp.text()
            ring = engine.timeline.snapshot()
            new = {}
            for e in ring["req"]:
                if e.request_id not in before:
                    new.setdefault(e.request_id, []).append(e)
            return {"status": resp.status, "text": text, "new": new,
                    "ring": ring}

        try:
            await chat(admin, "compile the programs")         # cold
            out["streamed"] = await chat(admin, "stream me")
            out["unary"] = await chat(admin, "answer me", stream=False)
            made = await client.post("/admin/users", auth=admin, json={
                "email": "shed@example.com", "password": "Vq8#mRt2xW!s",
                "full_name": "Shed Target"})
            assert made.status in (201, 409), await made.text()
            out["shed"] = await chat(user, "shed me")

            loop, block, stamp = asyncio.get_running_loop(), [], \
                engine.timeline.stamp

            def blocked():
                block.append(time.perf_counter())
                time.sleep(BLOCK_S)
                block.append(time.perf_counter())

            def stamp_and_block(phase, request_id, slot, t=None):
                if phase == "first" and not block:
                    loop.call_soon_threadsafe(blocked)
                return stamp(phase, request_id, slot, t)

            engine.timeline.stamp = stamp_and_block
            try:
                out["blocked"] = await chat(admin, "block me")
            finally:
                del engine.timeline.stamp
            out["blocked"]["block"] = tuple(block)
            await asyncio.sleep(0.03)            # the late tick has landed
            out["blocked"]["ring"] = engine.timeline.snapshot()
            out["rows"] = list(app["flight_recorder"].recent)
        finally:
            await client.close()
        return out

    return asyncio.run(main())


def _one(result):
    """The one request a call left on the ring: (id, phase -> t)."""
    assert result["status"] == 200, result["text"]
    (request_id, events), = result["new"].items()
    stamps = {e.phase: e.t for e in events}
    assert len(stamps) == len(events)            # each phase once
    return request_id, stamps, events


def test_a_streamed_request_leaves_its_way_under_one_id_in_order(
        through_the_gateway):
    _id, stamps, events = _one(through_the_gateway["streamed"])
    assert "data: [DONE]" in through_the_gateway["streamed"]["text"]
    assert set(stamps) == set(WAY) | {"done"}
    times = [stamps[phase] for phase in WAY]
    assert times == sorted(times), dict(zip(WAY, times))
    assert stamps["written"] <= stamps["done"]   # 24 tokens outlast a write
    # the gateway's stamps carry no slot, the engine's past ``submit`` one
    assert {e.phase for e in events if e.slot == -1} == {
        "recv", "authed", "parsed", "tokenized", "submit", "chunk", "written"}
    # the flight recorder's row starts at the same reading as ``recv``, and
    # shows what it showed
    row = next(r for r in reversed(through_the_gateway["rows"])
               if r["status"] == 200 and "engine" in r["phases_ms"])
    assert {"auth", "routing", "engine", "serialize", "handler"} \
        <= set(row["phases_ms"])
    assert "marks" not in row


def test_an_unary_request_leaves_the_way_in_and_no_way_out(
        through_the_gateway):
    """``chat`` (no stream) goes through the same ``_prepare``: the way in is
    stamped; there is no chunk and no SSE write to stamp."""
    _id, stamps, _events = _one(through_the_gateway["unary"])
    assert set(stamps) == {"recv", "authed", "tokenized", "submit", "admit",
                           "first", "emit", "deliver", "done"}
    assert stamps["recv"] <= stamps["authed"] <= stamps["tokenized"] \
        <= stamps["submit"]


def test_a_shed_request_leaves_no_stamp(through_the_gateway):
    shed = through_the_gateway["shed"]
    assert shed["status"] == 429, shed["text"]
    assert shed["new"] == {}


def test_a_first_token_that_falls_into_a_blocked_loop_reads_it_as_the_hop(
        through_the_gateway):
    blocked = through_the_gateway["blocked"]
    _id, stamps, _events = _one(blocked)
    start, end = blocked["block"]
    assert end - start >= BLOCK_S
    # the flush's callback was queued behind the block: the token was handed
    # over before the block ended and entered the stream after it
    assert stamps["first"] <= stamps["emit"] <= end <= stamps["deliver"]
    hop = stamps["deliver"] - stamps["emit"]
    assert (end - start) - 0.005 <= hop <= (end - start) + 0.025
    assert stamps["emit"] - stamps["first"] < 0.010
    # and the sampler (a tick every 10 ms here) left the same block as a
    # pause: due inside it, run after it, no longer than the hop
    lags = [p for p in blocked["ring"]["pause"]
            if p.cause == "loop_lag" and p.t0 < end and p.t1 > start]
    assert len(lags) == 1
    lag = lags[0]
    assert start - 0.002 <= lag.t0 <= start + 0.012 and lag.t1 >= end
    assert (end - start) - 0.013 <= lag.t1 - lag.t0 <= hop + 0.012
    assert lag.thread == "MainThread" and lag.detail == 0


def test_spans_reach_a_profiler_capture(tmp_path):
    """A span is a ``TraceAnnotation``: a capture (the benchmark's, or the
    admin profiler route's) shows it on the host plane, with its step."""
    from jax.profiler import ProfileData

    timeline = tl_mod.StepTimeline("capture")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with timeline.span("decode.build", 41, "decode_fb"):
            jnp.ones((8,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(root, f) for root, _d, files in os.walk(tmp_path)
             for f in files if f.endswith(".xplane.pb")]
    events = [ev for plane in ProfileData.from_file(paths[0]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("decode.build")]
    assert events, "the span did not reach the capture's host plane"
    recorded = timeline.snapshot()["span"][0]
    assert (events[0].duration_ns / 1e9
            == pytest.approx(recorded.t1 - recorded.t0, abs=2e-4))


# ---------------------------------------------------------- program names

def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _lower(engine, which: str):
    """Each step program lowered on the call a dispatch hands it: one packed
    array of idle rows and the base key (a device-fed step: and the block of
    the step in flight)."""
    B, S = engine.config.max_batch, 16
    prefill_args = (engine.params, engine.kv,
                    engine._idle_call(engine._prefill_call(S), 1), engine._rng)
    with engine.mesh:
        if which == "prefill":
            return engine._prefill_sample.lower(*prefill_args)
        if which == "prefill_hist":
            return engine._hist_fn(4).lower(*prefill_args)
        if which == "decode":
            return engine._decode_fn(4).lower(
                engine.params, engine.kv,
                engine._idle_call(engine._decode_call, B), engine._rng)
        if which == "decode_fb":
            return engine._decode_fb_fn(4).lower(
                engine.params, engine.kv,
                engine._idle_call(engine._decode_fb_call, B), engine._rng,
                jnp.zeros((1, B), jnp.int32))
        assert which == "verify"
        return engine._verify_fn(4).lower(
            engine.params, engine.kv,
            engine._idle_call(engine._verify_call, B), engine._rng)


@pytest.mark.parametrize("which,module,kind", [
    ("prefill", "jit__prefill_and_sample", "prefill"),
    ("prefill_hist", "jit__prefill_hist_and_sample", "prefill_hist"),
    ("decode", "jit__decode_and_sample", "decode"),
    ("decode_fb", "jit__decode_and_sample_fb", "decode"),
    ("verify", "jit__verify_and_sample", None),
])
def test_step_functions_lower_to_named_modules(which, module, kind, spec):
    from benchmark.harness import trace_reduce

    name = _module_name(_lower(spec[0], which))
    assert name == module and name != "jit__unknown"
    if kind is not None:
        # no paged-kernel rows offered: the NAME alone classifies it
        assert trace_reduce.program_kind(name, set()) == kind
    else:       # spec verify is no benchmark cell's program: named, unclassified
        assert trace_reduce.program_kind(name, set()) == "other"
