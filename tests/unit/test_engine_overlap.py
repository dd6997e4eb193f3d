"""Overlapped decode pipeline: token parity with the serial path, drain
barriers (admission / EOS / crash mid-pipeline), dirty block-table sync,
batched emission, and the event-driven idle wait."""

import asyncio
import functools
import threading

import jax
import pytest

from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)
from mcp_context_forge_tpu.tpu_local.kv import PageAllocator


def _config(**overrides):
    kwargs = dict(model="llama3-test", max_batch=4, max_seq_len=128,
                  page_size=16, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference")
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def _run(engine, coro):
    async def wrapper():
        await engine.start()
        try:
            return await asyncio.wait_for(coro, timeout=300)
        finally:
            await engine.stop()
    return asyncio.run(wrapper())


def _gen_all(engine, prompts, max_tokens=12, **kwargs):
    async def main():
        async def one(ids):
            return [t async for t in engine.generate(ids, max_tokens=max_tokens,
                                                     **kwargs)]
        return await asyncio.gather(*[one(ids) for ids in prompts])
    return _run(engine, main())


# ------------------------------------------------------------------ parity

def _gen_preloaded(engine, prompts, max_tokens):
    """Queue every request BEFORE the dispatch thread starts, so admission
    grouping (and thus every dispatched shape) is deterministic across the
    serial/overlap engines being compared."""
    requests = [GenRequest(request_id=f"r{i}", prompt_ids=ids,
                           max_tokens=max_tokens)
                for i, ids in enumerate(prompts)]
    engine._pending.extend(requests)

    async def main():
        await engine.start()
        try:
            outs = []
            for request in requests:
                tokens = []
                while True:
                    token = await asyncio.wait_for(request.stream.get(),
                                                   timeout=120)
                    if token is None:
                        break
                    tokens.append(token)
                outs.append(tokens)
            return outs
        finally:
            await engine.stop()

    return asyncio.run(main())


def test_overlap_matches_serial_token_streams():
    """The acceptance gate: seeded engines, identical prompts — the
    overlapped pipeline must emit byte-identical token streams to the
    serial path, across concurrent greedy requests."""
    prompts_text = ["alpha bravo", "charlie", "delta echo foxtrot golf",
                    "hotel india juliet"]
    outs = {}
    for overlap in (False, True):
        engine = TPUEngine(_config(decode_overlap=overlap))
        engine._rng = jax.random.PRNGKey(1234)
        prompts = [engine.tokenizer.encode(t) for t in prompts_text]
        outs[overlap] = _gen_preloaded(engine, prompts, max_tokens=12)
        assert engine.allocator.pages_in_use == 0
        if overlap:
            assert engine.stats.overlap_steps > 0, \
                "pipeline never engaged (no device-fed dispatches)"
        else:
            # the serial arm feeds no dispatch from the device
            assert engine.stats.overlap_steps == 0
    assert outs[True] == outs[False]


def test_overlap_matches_serial_sampled_single_stream():
    """Sampled (temperature>0) parity for a single stream: dispatch order
    and per-dispatch RNG splits line up between modes, so the sampled
    tokens themselves must match."""
    outs = {}
    for overlap in (False, True):
        engine = TPUEngine(_config(decode_overlap=overlap, max_batch=2))
        engine._rng = jax.random.PRNGKey(7)
        ids = engine.tokenizer.encode("sampled parity")
        outs[overlap] = _gen_all(engine, [ids], max_tokens=10,
                                 temperature=0.8, top_k=20)
        assert engine.allocator.pages_in_use == 0
    assert outs[True] == outs[False]


def test_overlap_with_superstep_matches_serial():
    """superstep>1 composes with the pipeline: [k,B] feedback blocks
    feed the next dispatch; parity must hold and the max_tokens tail must
    not cost extra dispatches (the all-exhausted fast path)."""
    outs, steps = {}, {}
    for overlap in (False, True):
        engine = TPUEngine(_config(decode_overlap=overlap, superstep=4))
        engine._rng = jax.random.PRNGKey(5)
        ids = engine.tokenizer.encode("block and overlap")
        outs[overlap] = _gen_all(engine, [ids], max_tokens=13)
        steps[overlap] = engine.stats.decode_steps
    assert outs[True] == outs[False]
    assert steps[True] == steps[False], \
        "overlap consumed extra dispatches on a max_tokens tail"


def test_partial_budget_row_drains_before_feedback():
    """A row whose super-step budget is cut by the per-slot page cap
    (0 < budget < k) but which SURVIVES its step must not be resumed via
    device feedback — the feedback fn reads block row k-1, its true last
    token is at budget-1. The pipeline must drain and re-feed from host.
    Geometry: context cap 32 tokens, k=4 — the final block before the cap
    is granted partially, then truncates, exactly like the serial path."""
    outs = {}
    for overlap in (False, True):
        engine = TPUEngine(_config(decode_overlap=overlap, superstep=4,
                                   max_batch=2, max_seq_len=32, num_pages=8,
                                   prefill_buckets=(16,)))
        engine._rng = jax.random.PRNGKey(3)
        ids = engine.tokenizer.encode("cap me")
        outs[overlap] = _gen_preloaded(engine, [ids], max_tokens=64)
        assert engine.allocator.pages_in_use == 0
    # both arms truncate at the context cap with identical streams
    assert outs[True] == outs[False]
    assert len(outs[True][0]) >= 1


def test_eos_mid_pipeline_discards_lookahead():
    """A stop token hit while the lookahead step is in flight must end the
    stream exactly where the serial engine does — the speculatively
    decoded continuation is discarded, and the slot's pages free."""
    serial = TPUEngine(_config(decode_overlap=False))
    ids = serial.tokenizer.encode("stop mid pipeline")
    ref = _gen_all(serial, [ids], max_tokens=12)[0]
    assert len(ref) >= 4, "need a few tokens to pick a stop id from"
    # first token with no earlier duplicate: the stream must end exactly
    # at ITS first occurrence
    idx = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    stop = ref[idx]

    for overlap in (False, True):
        engine = TPUEngine(_config(decode_overlap=overlap))
        out = _gen_all(engine, [engine.tokenizer.encode("stop mid pipeline")],
                       max_tokens=50, stop_ids=(stop,))[0]
        assert out == ref[:idx + 1], (overlap, out, ref[:idx + 1])
        assert engine.allocator.pages_in_use == 0
        assert engine._inflight is None


def test_drain_on_admission_mid_stream():
    """A request admitted while another decodes forces a pipeline drain
    (slot/page reuse safety) and both streams still match the serial
    engine's output for the same prompts."""
    results = {}
    for overlap in (False, True):
        engine = TPUEngine(_config(decode_overlap=overlap, max_batch=2))
        engine._rng = jax.random.PRNGKey(99)
        ids1 = engine.tokenizer.encode("long running first request")
        ids2 = engine.tokenizer.encode("late arrival")

        async def main():
            first = asyncio.ensure_future(_collect(engine, ids1, 24))
            # let the first stream get going so its pipeline is primed
            while engine.stats.decode_steps < 4:
                await asyncio.sleep(0.002)
            second = asyncio.ensure_future(_collect(engine, ids2, 8))
            return await asyncio.gather(first, second)

        results[overlap] = _run(engine, main())
        assert engine.allocator.pages_in_use == 0
        if overlap:
            assert engine.stats.overlap_steps > 0
    assert results[True] == results[False]


async def _collect(engine, ids, n):
    return [t async for t in engine.generate(ids, max_tokens=n)]


def test_crash_mid_pipeline_fails_streams_cleanly():
    """A device fault while a lookahead is in flight must not strand any
    consumer: every stream terminates, finish_reason is 'error', and the
    in-flight block is dropped without a read-back."""
    engine = TPUEngine(_config(decode_overlap=True))
    real = engine._decode_fb_fn
    calls = {"n": 0}

    def exploding(ctx_pages, batch=None):
        fn = real(ctx_pages, batch)

        def wrapper(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] >= 3:
                raise RuntimeError("injected device fault")
            return fn(*args, **kwargs)
        return wrapper

    engine._decode_fb_fn = exploding

    async def main():
        request = GenRequest(
            request_id="crash",
            prompt_ids=engine.tokenizer.encode("crash mid pipeline"),
            max_tokens=64)
        await engine.submit(request)
        tokens = []
        while True:
            token = await asyncio.wait_for(request.stream.get(), timeout=60)
            if token is None:
                break
            tokens.append(token)
        return request, tokens

    async def wrapper():
        await engine.start()
        try:
            return await asyncio.wait_for(main(), timeout=120)
        finally:
            engine._stop_event.set()  # thread already dead; skip join noise
            engine._started = False

    request, tokens = asyncio.run(wrapper())
    assert calls["n"] >= 3
    assert request.finish_reason == "error"
    assert engine._inflight is None


# --------------------------------------------------------- dirty table sync

def test_allocator_dirty_tracking():
    alloc = PageAllocator(num_pages=32, page_size=16, max_slots=4,
                          max_pages_per_slot=8)
    assert not alloc.dirty
    assert alloc.allocate_slot(0, 20)  # 2 pages
    assert alloc.dirty
    table = jax.device_get(alloc.tables())
    assert not alloc.dirty
    assert (table[0][:2] > 0).all() and (table[0][2:] == 0).all()

    # growth within the allocated pages: no new page, no dirt
    assert alloc.grow_slot(0, 25) >= 25
    assert not alloc.dirty
    # growth crossing a page boundary dirties the row
    assert alloc.grow_slot(0, 40) >= 40
    assert alloc.dirty
    alloc.tables()

    alloc.free_slot(0)
    assert alloc.dirty
    cleared = jax.device_get(alloc.tables())
    assert (cleared == 0).all()
    assert alloc.pages_in_use == 0


def test_grow_slot_partial_growth_persists():
    alloc = PageAllocator(num_pages=4, page_size=16, max_slots=2,
                          max_pages_per_slot=8)  # 3 usable pages
    assert alloc.allocate_slot(0, 16)
    # asks for 5 pages, pool only has 2 more: partial growth sticks
    assert alloc.grow_slot(0, 80) == 48
    assert alloc.slot_pages(0) == 3
    # the granted capacity is the whole contract: 48 tokens fit the 3
    # granted pages, 49 do not (and the shortfall is visible to callers)
    assert alloc.grow_slot(0, 48) >= 48
    assert alloc.grow_slot(0, 49) < 49


def test_engine_skips_table_upload_when_clean():
    """Steady-state decode with no page growth must NOT re-upload the
    block table: _sync_tables leaves kv.block_tables untouched."""
    engine = TPUEngine(_config())
    ids = engine.tokenizer.encode("hi")
    _gen_all(engine, [ids], max_tokens=4)
    engine._sync_tables()  # flush the final free_slot's dirt
    assert not engine.allocator.dirty
    before = engine.kv.block_tables
    engine._sync_tables()
    assert engine.kv.block_tables is before

    # and a dirty allocator triggers a fresh upload
    assert engine.allocator.allocate_slot(1, 16)
    engine._sync_tables()
    assert engine.kv.block_tables is not before
    engine.allocator.free_slot(1)
    engine._sync_tables()


# --------------------------------------------------------- batched emission

def _count_wakeups(engine, ids, max_tokens):
    """Generate through ``engine``; returns the tokens and how many times
    the dispatch thread woke the loop (``call_soon_threadsafe`` calls)."""
    counted = {"n": 0}

    async def main():
        loop = asyncio.get_running_loop()
        real = loop.call_soon_threadsafe

        def counting(*args, **kwargs):
            counted["n"] += 1
            return real(*args, **kwargs)

        loop.call_soon_threadsafe = counting
        try:
            return [t async for t in engine.generate(ids,
                                                     max_tokens=max_tokens)]
        finally:
            loop.call_soon_threadsafe = real

    return _run(engine, main()), counted["n"]


def test_one_loop_wakeup_per_step():
    """_post_tokens buffers and _flush_emits posts once per dispatch-loop
    iteration: a superstep=4 generation must produce far fewer
    call_soon_threadsafe hops than tokens."""
    engine = TPUEngine(_config(superstep=4, decode_overlap=False,
                               max_batch=2))
    out, wakeups = _count_wakeups(
        engine, engine.tokenizer.encode("count wakeups"), 16)
    assert len(out) >= 8
    # old behavior: one hop per token (>= len(out)); new: one per step
    # (prefill + ~len/4 decode blocks + slack for the done sentinel)
    assert wakeups <= len(out) // 2 + 4, wakeups


# a first token leaves when it is made: the early flush after a prefill (or a
# chunk round), and first tokens first inside any flush

_PATHS = {"overlapped": dict(decode_overlap=True),
          "serial": dict(decode_overlap=False),
          "spec": dict(spec_decode=True, spec_k=4)}


def _late_arrival(engine, early_tokens=40, late_tokens=6):
    """A second request admitted while the first decodes; returns both
    (finished) and their token streams."""
    early = GenRequest(request_id="early", prompt_ids=[7, 8, 9, 7, 8, 9, 7, 8],
                       max_tokens=early_tokens)
    late = GenRequest(request_id="late", prompt_ids=list(range(60, 72)),
                      max_tokens=late_tokens)

    async def main():
        streams = {early.request_id: [], late.request_id: []}
        await engine.submit(early)
        for _ in range(3):      # the first stream is decoding
            streams["early"].append(await early.stream.get())
        await engine.submit(late)
        for request in (early, late):
            while (token := await request.stream.get()) is not None:
                streams[request.request_id].append(token)
        return streams

    return early, late, _run(engine, main())


@functools.lru_cache(maxsize=None)
def _served(path):
    """The late arrival on ``path``, served as built (True) and with the
    early flush switched off (False: one flush an iteration, the parent's
    rule), seeded alike: ``{early_flush: (engine, early, late, streams)}``."""
    out = {}
    for early_flush in (False, True):
        engine = TPUEngine(_config(max_batch=2, **_PATHS[path]))
        engine._rng = jax.random.PRNGKey(99)
        if not early_flush:
            flush = engine._flush_emits
            engine._flush_emits = \
                lambda first_only=False: None if first_only else flush()
        out[early_flush] = (engine, *_late_arrival(engine))
    return out


@pytest.mark.parametrize("path", sorted(_PATHS))
def test_first_token_is_flushed_before_the_iterations_decode_dispatch(path):
    """The late request's prefill and the running row's next decode (or
    verify) dispatch share one iteration of the dispatch loop: the first
    token is handed to the loop (``emit``) before that dispatch is built,
    by a flush of its own that the ring tells from the iteration's; under
    the parent's rule it waited for the dispatch."""
    for early_flush, (engine, early, late, _streams) in _served(path).items():
        ring = engine.timeline.snapshot()
        prefill = [s for s in ring["step"] if s.kind == "prefill"][-1]
        after = next(s for s in ring["step"] if s.seq == prefill.seq + 1)
        assert after.kind == ("spec" if path == "spec" else "decode")
        assert early.t_done > after.t_retired     # the running row rode it
        build = next(s for s in ring["span"]
                     if s.name == "decode.build" and s.step == after.seq)
        assert prefill.t_retired <= late.t_first <= late.t_emit
        flushes = [s for s in ring["span"] if s.name == "loop.flush"]
        first = [s for s in flushes if s.kind == "first"]
        if not early_flush:
            assert late.t_emit > build.t1 and not first
            continue
        assert late.t_emit <= build.t0
        assert len(first) == 2 and len(flushes) > len(first)
        assert first[-1].t0 <= late.t_emit <= first[-1].t1 <= build.t0


@pytest.mark.parametrize("path", sorted(_PATHS))
def test_streams_are_the_parents_with_and_without_the_early_flush(path):
    """Byte-identical streams with the early flush and without it."""
    served = _served(path)
    for early_flush, (engine, _early, _late, _streams) in served.items():
        assert engine.allocator.pages_in_use == 0
        assert (engine.stats.first_flushes > 0) == early_flush
    streams = served[True][3]
    assert streams == served[False][3]
    assert len(streams["late"]) >= 1 and len(streams["early"]) >= 4


class _Recorder:
    """A request's stream that writes every put to one shared list."""

    def __init__(self, name, order):
        self.name, self.order = name, order

    def put_nowait(self, token):
        self.order.append((self.name, token))


def _buffered(engine):
    """An emit buffer as a step leaves it: two live rows' tokens, a rejected
    request's sentinel, and a fresh request's first tokens among them."""
    order = []
    requests = {name: GenRequest(request_id=name, prompt_ids=[1])
                for name in ("a", "b", "rejected", "fresh")}
    for name, request in requests.items():
        request.stream = _Recorder(name, order)
    requests["a"].t_emit = requests["b"].t_emit = 1.0   # flushed before
    for name, tokens, done in (("a", [1, 2], False), ("rejected", [], True),
                               ("b", [3], True), ("fresh", [9], False),
                               ("a", [4], False), ("fresh", [10], True)):
        engine._post_tokens(requests[name], tokens, done)
    return requests, order


_FRESH = [("fresh", 9), ("fresh", 10), ("fresh", None)]
_REST = [("a", 1), ("a", 2), ("rejected", None), ("b", 3), ("b", None),
         ("a", 4)]


@pytest.mark.parametrize("early", [False, True])
def test_first_tokens_are_put_first_and_every_stream_keeps_its_order(early):
    """One flush that holds live rows' tokens and a first token: the first
    token's ``put_nowait`` comes first, the rest in buffer order, every
    request's own tokens in order and its ``None`` last. The early flush
    takes the first tokens alone and leaves the rest where it was."""
    engine = TPUEngine(_config())       # never started: _put runs in place
    requests, order = _buffered(engine)
    if early:
        engine._flush_emits(first_only=True)
        assert order == _FRESH and engine.stats.first_flushes == 1
        assert [(r.request_id, t, d) for r, t, d in engine._emit_buf] == [
            ("a", [1, 2], False), ("rejected", [], True), ("b", [3], True),
            ("a", [4], False)]
        # nothing fresh is left: a second early flush does not wake the loop
        engine._flush_emits(first_only=True)
        assert order == _FRESH and engine.stats.first_flushes == 1
    engine._flush_emits()
    assert order == _FRESH + _REST
    assert engine._emit_buf == [] and not engine._emit_first
    assert engine.stats.first_flushes == int(early)
    assert requests["fresh"].t_emit > 0.0 == requests["rejected"].t_emit
    kinds = [s.kind for s in engine.timeline.snapshot()["span"]
             if s.name == "loop.flush"]
    assert kinds == (["first", ""] if early else [""])


def test_first_flushes_counts_admissions_that_made_a_token_only():
    """One early flush per prefill that emitted (two requests admitted in
    one prefill share it), none for a decode step, none for an admission
    that only rejected."""
    engine = TPUEngine(_config(decode_overlap=False, prefill_buckets=(64,)))
    prompts = [list(range(10, 44)), list(range(50, 90))]   # one bucket, no half
    outs = _gen_preloaded(engine, prompts, max_tokens=6)
    assert all(len(out) >= 1 for out in outs)
    assert engine.stats.prefill_batches == 1
    assert engine.stats.first_flushes == 1
    assert engine.stats.decode_dispatches >= 2
    oversized = GenRequest(request_id="big", prompt_ids=list(range(500)),
                           max_tokens=4)

    async def main():
        await engine.submit(oversized)
        return await asyncio.wait_for(oversized.stream.get(), 60)

    assert _run(engine, main()) is None and oversized.finish_reason == "length"
    assert engine.stats.first_flushes == 1
    out, wakeups = _count_wakeups(engine, prompts[0], 8)
    assert engine.stats.first_flushes == 2
    # an idle engine's first token is all its buffer holds: the early flush
    # carries it and the iteration's own flush has the first decode's token
    # alone, so a request costs at most one wake-up more than its steps
    steps = 1 + len(out) - 1            # the prefill, a decode a token after
    assert wakeups <= steps + 2, (wakeups, len(out))


def test_submit_wakes_idle_dispatch_thread():
    """The idle path blocks on an event, not a sleep poll: submit() sets
    the wake flag, and an idle engine still serves promptly."""
    engine = TPUEngine(_config())

    async def main():
        await asyncio.sleep(0.2)  # let the dispatch thread go idle
        ids = engine.tokenizer.encode("wake up")
        return [t async for t in engine.generate(ids, max_tokens=4)]

    out = _run(engine, main())
    assert len(out) >= 1


def test_wait_for_work_returns_on_stop():
    engine = TPUEngine(_config())
    engine._stop_event = threading.Event()
    engine._stop_event.set()
    engine._wake.clear()
    engine._wait_for_work()  # must not block


# ------------------------------------------------------------- introspection

def test_step_log_carries_gap_and_overlap_counters():
    engine = TPUEngine(_config(decode_overlap=True))
    ids = engine.tokenizer.encode("introspect")
    _gen_all(engine, [ids], max_tokens=8)
    decode_steps = [s for s in engine.recent_steps() if s["kind"] == "decode"]
    assert decode_steps
    assert all("gap_ms" in s for s in decode_steps)
    # device-fed dispatches report a zero gap
    assert any(s["gap_ms"] == 0 for s in decode_steps)
    assert 0.0 <= engine.device_idle_fraction() <= 1.0


def test_config_wires_decode_overlap():
    from mcp_context_forge_tpu.config import load_settings

    settings = load_settings(env_file=None)
    assert settings.tpu_local_decode_overlap is True
    cfg = EngineConfig.from_settings(settings)
    assert cfg.decode_overlap is True

    settings2 = load_settings(
        env={"MCPFORGE_TPU_LOCAL_DECODE_OVERLAP": "false"}, env_file=None)
    assert EngineConfig.from_settings(settings2).decode_overlap is False
