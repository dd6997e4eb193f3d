"""The engine's step timeline (observability/timeline.py): one recorder on
the profiler's clock that every dispatch kind writes its phase spans, its
step record and its requests' four stamps to — and that the engine's own
step numbers (``queue_ms``, ``prefill_ms``, ``dispatch_gap_ms_total``,
``decode_ms_total``, the llm.* span durations) are now read from.

Falsifiable form:

- every dispatch kind (dense prefill, history prefill, chunk round, host-fed
  and device-fed decode, speculative verify) leaves its full set of spans;
- the spans of one step sit around its ``t_dispatched..t_retired`` the way
  the code runs them: build and table sync before, dispatch and sync /
  read-back inside, emit after;
- ``t_submit <= t_admit <= t_first <= t_done`` for every request, on the
  dense, chunked, overlapped and serial paths;
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
from mcp_context_forge_tpu.tpu_local.sampling import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

PREFILL_SPANS = {"prefill.build", "prefill.dispatch", "prefill.sync",
                 "prefill.emit"}
DECODE_SPANS = {"decode.build", "decode.table_sync", "decode.dispatch",
                "decode.readback", "decode.emit"}
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
        assert stamps[finished.request_id] == {
            "submit": finished.t_submit, "admit": finished.t_admit,
            "first": finished.t_first, "done": finished.t_done}
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
    assert sum(1 for e in ring["req"] if e.request_id == chunked.request_id) == 4
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
    B, S, K = engine.config.max_batch, 16, 4
    one = SamplingParams(jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
                         jnp.ones((1,), jnp.float32))
    wide = SamplingParams(jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                          jnp.ones((B,), jnp.float32))
    key = jax.random.PRNGKey(0)
    i32 = jnp.int32
    prefill_args = (engine.params, engine.kv, jnp.zeros((1, S), i32),
                    jnp.full((1, S), -1, i32), jnp.zeros((1,), i32),
                    jnp.zeros((1,), i32), one, key)
    decode_tail = (jnp.zeros((B,), i32), jnp.arange(B, dtype=i32),
                   jnp.zeros((B,), i32), jnp.zeros((B,), i32),
                   jnp.full((B, engine._STOP_TBL_WIDTH), -1, i32), wide, key)
    with engine.mesh:
        if which == "prefill":
            return engine._prefill_sample.lower(*prefill_args)
        if which == "prefill_hist":
            return engine._hist_fn(4).lower(*prefill_args)
        if which == "decode":
            return engine._decode_fn(4).lower(
                engine.params, engine.kv, jnp.zeros((B,), i32), *decode_tail)
        if which == "decode_fb":
            return engine._decode_fb_fn(4).lower(
                engine.params, engine.kv, jnp.zeros((1, B), i32), *decode_tail)
        assert which == "verify"
        return engine._verify_fn(4).lower(
            engine.params, engine.kv, jnp.zeros((B, K), i32),
            jnp.full((B, K), -1, i32), jnp.arange(B, dtype=i32), wide, key)


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
