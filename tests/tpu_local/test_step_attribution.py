"""Decode-step phase attribution, live cost-model roofline, and XLA
compile tracking (docs/observability.md "Step attribution, live
roofline, and SLOs").

The subsystem's contract, in falsifiable form:

- every HOST-FED dispatch (a prefill, a chunk round, a decode step that
  found the device drained) carries a COMPLETE phase row read off its
  timeline spans (rows/sampling/rng/table_sync/upload/launch/readback)
  whose components sum to ~ ``build.t0 -> readback.t1``; a device-fed
  decode step carries none;
- the rows cost no sync and no drain: the overlapped and the serial engine
  emit the same greedy tokens, and no dispatch waits for the device for
  attribution's sake;
- crash- and EOS-mid-pipeline paths never surface partial/garbage rows;
- warmup populates the XLA cost registry and decode retires feed the
  live mcpforge_llm_mfu / mcpforge_llm_hbm_roofline_frac gauges;
- a WARMED engine serves traffic with zero serving-stage XLA compiles,
  while an unwarmed engine's first-dispatch compiles are counted as
  serving (the PR-5 mid-traffic-compile alarm).
"""

import asyncio

import jax
import pytest

from mcp_context_forge_tpu.observability.metrics import PrometheusRegistry
from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)

PHASE_KEYS = {"rows_ms", "sampling_ms", "rng_ms", "table_sync_ms",
              "upload_ms", "launch_ms", "readback_ms", "total_ms"}
# a prefill's tables were synced by its admission: no such phase
PREFILL_PHASE_KEYS = PHASE_KEYS - {"table_sync_ms"}


def _config(**overrides):
    kwargs = dict(model="llama3-test", max_batch=4, max_seq_len=128,
                  page_size=16, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference",
                  decode_overlap=True)
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
            return [t async for t in engine.generate(
                ids, max_tokens=max_tokens, **kwargs)]
        return await asyncio.gather(*[one(ids) for ids in prompts])
    return _run(engine, main())


def _gen_preloaded(engine, prompts, max_tokens):
    """Queue every request BEFORE the dispatch thread starts so admission
    grouping is deterministic across the engines being compared (same
    idiom as test_engine_overlap)."""
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


def _phase_rows(engine):
    return [s for s in engine.recent_steps() if s.get("phases")]


def _assert_row_complete(row):
    phases = row["phases"]
    wanted = PHASE_KEYS if "decode" in row["kind"] else PREFILL_PHASE_KEYS
    assert set(phases) == wanted, (row["kind"], phases)
    for key, value in phases.items():
        assert isinstance(value, float) and value >= 0.0, (key, value)
    parts = sum(v for k, v in phases.items() if k != "total_ms")
    # the parts are nested in the envelope: never more than it, but for the
    # rounding of eight numbers
    assert parts <= phases["total_ms"] + 0.01


# ----------------------------------------------------------- phase sampling

@pytest.mark.parametrize("overlap", [False, True])
def test_phase_rows_complete_on_every_host_fed_step_and_sum_to_wall(overlap):
    """Every host-fed dispatch carries a full phase row whose parts sum to
    ~ its ``build.t0 -> readback.t1`` on the timeline; a device-fed decode
    step (its host work overlapped the step before it) carries none. Under
    overlap a host-fed step retires after the NEXT dispatch was built and
    launched: that dispatch's spans are the rest of the envelope."""
    engine = TPUEngine(_config(prefix_cache=False, decode_overlap=overlap))
    for _ in range(3):      # the last run's steps find every program compiled
        outs = _gen_all(engine,
                        [engine.tokenizer.encode("attribute my steps")],
                        max_tokens=12)
        assert outs[0]
    ring = engine.timeline.snapshot()
    kind_of = {s.seq: s.kind for s in ring["step"]}
    span = {(s.step, s.name): s for s in ring["span"]}
    rows = engine.recent_steps()[-13:]      # a prefill and twelve steps
    kinds = {kind_of[r["seq"]] for r in rows}
    assert kinds == ({"prefill", "decode", "decode_fb"} if overlap
                     else {"prefill", "decode"})
    for row in rows:
        seq, kind = row["seq"], kind_of[row["seq"]]
        if kind == "decode_fb":
            assert row["phases"] is None
            continue
        _assert_row_complete(row)
        phases = row["phases"]
        family, wait = (("decode", "readback") if kind == "decode"
                        else ("prefill", "sync"))
        build, readback = span[seq, f"{family}.build"], span[seq, f"{family}.{wait}"]
        launch = span[seq, f"{family}.dispatch.launch"]
        assert phases["total_ms"] == pytest.approx(
            (readback.t1 - build.t0) * 1e3, abs=2e-3)
        assert phases["launch_ms"] == pytest.approx(
            (launch.t1 - launch.t0) * 1e3, abs=2e-3)
        between = sum(
            s.t1 - s.t0 for s in ring["span"]
            if s.step == seq + 1 and s.name in (
                "decode.build", "decode.table_sync", "decode.dispatch")
            and launch.t1 <= s.t0 and s.t1 <= readback.t0) * 1e3
        assert (between > 0) == (overlap and kind == "decode")
        parts = sum(v for k, v in phases.items() if k != "total_ms")
        # what is left: bucket lookups, the spans' own bookkeeping, counters
        assert phases["total_ms"] - parts - between <= max(
            2.0, 0.25 * phases["total_ms"])
    assert len([r for r in rows if r["phases"]]) == sum(
        1 for r in rows if kind_of[r["seq"]] != "decode_fb")


def test_phase_rows_preserve_greedy_parity():
    """The acceptance gate: seeded engines, identical preloaded prompts —
    the overlapped engine (few host-fed steps, so few rows) and the serial
    one (every step host-fed, a row on each) emit the same tokens: a row is
    read off spans and costs no drain and no sync."""
    texts = ["alpha bravo", "charlie", "delta echo foxtrot golf",
             "hotel india juliet"]
    outs, rows = {}, {}
    for overlap in (True, False):
        engine = TPUEngine(_config(decode_overlap=overlap))
        engine._rng = jax.random.PRNGKey(1234)
        prompts = [engine.tokenizer.encode(t) for t in texts]
        outs[overlap] = _gen_preloaded(engine, prompts, max_tokens=12)
        decode = [r for r in engine.recent_steps() if r["kind"] == "decode"]
        rows[overlap] = sum(1 for r in decode if r["phases"]), len(decode)
    assert outs[True] == outs[False]
    assert rows[False][0] == rows[False][1] > 0     # serial: every step
    assert 0 < rows[True][0] < rows[True][1]        # overlapped: the fed none


def test_no_dispatch_ever_syncs_for_attribution():
    """The forced-sync sampler is gone: no setting, no counter, no
    ``decode.device_wait`` span on any ring, and ``_decode_dispatch`` waits
    for the device nowhere — yet every host-fed step has its row and the
    per-phase histograms fill, on the default configuration."""
    import inspect

    metrics = PrometheusRegistry()
    engine = TPUEngine(_config(), metrics=metrics)
    _gen_all(engine, [engine.tokenizer.encode("quiet steady state")],
             max_tokens=8)
    assert not hasattr(engine.config, "step_sample_every")
    assert not hasattr(engine.stats, "phase_samples")
    assert not [s for s in engine.timeline.snapshot()["span"]
                if s.name == "decode.device_wait"]
    source = inspect.getsource(TPUEngine._decode_dispatch)
    assert "block_until_ready" not in source and "device_get" not in source
    assert _phase_rows(engine)
    text = metrics.render()[0].decode()
    counts = [float(line.split()[-1]) for line in text.splitlines()
              if line.startswith("mcpforge_llm_step_phase_seconds_count")]
    assert counts and all(c >= 1 for c in counts)
    assert "device_compute" not in text


def test_phase_histograms_and_span_events_emitted():
    """Host-fed rows feed mcpforge_llm_step_phase_seconds{phase=...} and
    the decode ones ride llm.decode spans as decode.step.phases events."""
    from mcp_context_forge_tpu.observability.tracing import Tracer
    tracer = Tracer(exporter="memory")
    metrics = PrometheusRegistry()
    engine = TPUEngine(_config(decode_overlap=False), tracer=tracer,
                       metrics=metrics)

    async def main():
        request = GenRequest(
            request_id="phases",
            prompt_ids=engine.tokenizer.encode("span events please"),
            max_tokens=10, trace_ctx=("ab" * 16, "cd" * 8))
        await engine.submit(request)
        while True:
            if await request.stream.get() is None:
                break
        return request

    _run(engine, main())
    text = metrics.render()[0].decode()
    for phase in ("rows", "sampling", "rng", "table_sync", "upload",
                  "launch", "readback"):
        line = (f'mcpforge_llm_step_phase_seconds_count'
                f'{{phase="{phase}",replica="0"}}')
        counts = [float(ln.split()[-1]) for ln in text.splitlines()
                  if ln.startswith(line)]
        assert counts and counts[0] >= 1, phase
    decode_spans = [s for s in tracer.finished if s.name == "llm.decode"]
    assert decode_spans
    events = [ev for span in decode_spans for ev in span.events
              if ev[1] == "decode.step.phases"]
    assert 1 <= len(events) <= 8, "no decode.step.phases span events"
    assert [ev[0] for ev in events] == sorted(ev[0] for ev in events)
    for _ts, _name, attrs in events:
        assert set(attrs) == PHASE_KEYS


def test_crash_mid_pipeline_emits_no_garbage_rows():
    """A device fault between a dispatch and its retire must never leave
    a partial phase row behind: the inflight record dies with the step,
    and every row that DID surface is complete."""
    engine = TPUEngine(_config())
    real = engine._decode_fn
    calls = {"n": 0}

    def exploding(ctx_pages, batch=None):
        fn = real(ctx_pages, batch)

        def wrapper(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] >= 2:
                raise RuntimeError("injected device fault")
            return fn(*args, **kwargs)
        return wrapper

    engine._decode_fn = exploding

    async def main():
        # a second request arrives mid-decode: its admission drains the
        # pipeline, and the host-fed dispatch that follows is the fault
        first = GenRequest(
            request_id="crash",
            prompt_ids=engine.tokenizer.encode("crash mid pipeline"),
            max_tokens=64)
        late = GenRequest(
            request_id="crash-late",
            prompt_ids=engine.tokenizer.encode("the barrier"), max_tokens=8)
        await engine.submit(first)
        for _ in range(3):
            await asyncio.wait_for(first.stream.get(), timeout=60)
        await engine.submit(late)
        for request in (first, late):
            while await asyncio.wait_for(request.stream.get(),
                                         timeout=60) is not None:
                pass
        return first

    async def wrapper():
        await engine.start()
        try:
            return await asyncio.wait_for(main(), timeout=120)
        finally:
            engine._stop_event.set()  # thread already dead; skip join noise
            engine._started = False

    request = asyncio.run(wrapper())
    assert calls["n"] >= 2
    assert request.finish_reason == "error"
    rows = _phase_rows(engine)
    assert rows
    for row in rows:
        _assert_row_complete(row)
    # the dispatch that died left its spans on the timeline and no row
    ring = engine.timeline.snapshot()
    launched = {s.step for s in ring["span"]
                if s.name == "decode.dispatch.upload"}
    assert launched - {r["seq"] for r in engine.recent_steps()}


def test_eos_mid_pipeline_rows_stay_complete():
    """Mixed-length concurrent requests (EOS/max_tokens staggered across
    the pipeline) exercise the drain-at-EOS barriers; every surfaced
    phase row must still be complete and the streams must terminate."""
    engine = TPUEngine(_config(superstep=2))
    prompts = [engine.tokenizer.encode(t)
               for t in ("one", "two words here", "three is a longer one")]
    outs = _gen_all(engine, prompts, max_tokens=7)
    assert all(outs)
    rows = _phase_rows(engine)
    assert [r for r in rows if r["kind"] == "decode"]
    for row in rows:
        _assert_row_complete(row)


# ------------------------------------------------- roofline + compile events

@pytest.fixture(scope="module")
def warmed_engine():
    """One warmed CPU engine shared by the roofline/compile tests. FULL
    warmup, deliberately: fast mode trims the shape grid, and concurrent
    admission timing can then hit an untrimmed-width/ctx executable
    mid-serving — a flaky serving-stage compile that would break the
    zero-serving-compiles invariant this fixture exists to pin."""
    metrics = PrometheusRegistry()
    config = _config(warmup=True, warmup_mode="full")
    engine = TPUEngine(config, metrics=metrics)
    outs = _gen_all(engine, [engine.tokenizer.encode("warmed traffic"),
                             engine.tokenizer.encode("second stream")],
                    max_tokens=10)
    assert all(outs)
    return engine, metrics


def test_warmup_populates_cost_registry(warmed_engine):
    engine, _ = warmed_engine
    counts = engine.cost_registry.counts()
    # the serving executables of this config (no spec decode): dense
    # prefill per bucket, plain + feedback decode per (width, ctx) pair
    assert counts.get("prefill", 0) >= 1
    assert counts.get("decode", 0) >= 1
    assert counts.get("decode_fb", 0) >= 1
    snapshot = engine.cost_registry.snapshot()
    for table in snapshot.values():
        for entry in table.values():
            assert entry["flops"] > 0 or entry["bytes_accessed"] > 0


def test_live_roofline_gauges_and_ring_fields(warmed_engine):
    """Decode retires divide warmup-captured XLA cost by measured wall:
    ring rows carry mfu/hbm_frac, the gauges hold the last step's value,
    and roofline_snapshot() aggregates the window."""
    engine, metrics = warmed_engine
    decode_rows = [s for s in engine.recent_steps() if s["kind"] == "decode"]
    assert decode_rows
    observed = [s for s in decode_rows if s.get("mfu") is not None]
    assert observed, "no decode row carried a live roofline observation"
    for row in observed:
        assert row["mfu"] > 0.0
        assert row["hbm_frac"] > 0.0
    snapshot = engine.roofline_snapshot()
    assert snapshot["window_steps"] >= len(observed)
    assert snapshot["mfu"] > 0.0
    assert snapshot["hbm_roofline_frac"] > 0.0
    text = metrics.render()[0].decode()
    for gauge in ("mcpforge_llm_mfu", "mcpforge_llm_hbm_roofline_frac"):
        values = [float(line.split()[-1]) for line in text.splitlines()
                  if line.startswith(f'{gauge}{{replica="0"}} ')]
        assert values and values[0] > 0.0, gauge


def test_warmed_engine_serves_with_zero_serving_compiles(warmed_engine):
    """The PR-5 invariant, now pinned by the tracker: after warmup, real
    traffic triggers NO XLA compiles on the dispatch thread."""
    engine, metrics = warmed_engine
    stats = engine.compile_stats()
    assert stats["warmup"]["count"] > 0
    assert stats["warmup"]["ms_total"] > 0.0
    assert stats["serving"]["count"] == 0, stats
    assert engine.compile_tracker.serving_compiles() == 0
    text = metrics.render()[0].decode()
    warm = [float(line.split()[-1]) for line in text.splitlines()
            if line.startswith('mcpforge_llm_xla_compiles_total'
                               '{replica="0",stage="warmup"}')]
    assert warm and warm[0] > 0


def test_unwarmed_engine_counts_serving_compiles():
    """Without warmup the first dispatches compile on the serving thread
    — the tracker must attribute them (this is the alarm condition)."""
    metrics = PrometheusRegistry()
    engine = TPUEngine(_config(), metrics=metrics)
    _gen_all(engine, [engine.tokenizer.encode("cold start")], max_tokens=6)
    stats = engine.compile_stats()
    assert stats["serving"]["count"] > 0
    assert stats["recent"], "recent compile ring empty"
    for event in stats["recent"]:
        assert event["stage"] in ("warmup", "serving")
        assert event["duration_ms"] >= 0.0
    text = metrics.render()[0].decode()
    serving = [float(line.split()[-1]) for line in text.splitlines()
               if line.startswith('mcpforge_llm_xla_compiles_total'
                                  '{replica="0",stage="serving"}')]
    assert serving and serving[0] > 0
    assert 'mcpforge_llm_xla_compile_seconds_count{replica="0"}' in text


def test_cost_registry_lookup_fallback():
    """Width-mismatched lookups fall back to a same-ctx entry (order of
    magnitude beats nothing for a live gauge); ctx misses return None."""
    from mcp_context_forge_tpu.tpu_local.roofline import (CostEntry,
                                                          CostRegistry)
    registry = CostRegistry()
    registry._entries["decode"] = {(1, 4): CostEntry(100.0, 200.0)}
    assert registry.lookup("decode", 1, 4).flops == 100.0
    assert registry.lookup("decode", 8, 4).flops == 100.0  # width fallback
    assert registry.lookup("decode", 1, 8) is None
    assert registry.lookup("prefill", 1, 4) is None


def test_roofline_fractions_math():
    from mcp_context_forge_tpu.tpu_local.roofline import roofline_fractions
    # 1 TFLOP + 1 GB in 1 s on one chip with 2 TFLOP/s + 2 GB/s peaks
    mfu, frac = roofline_fractions(1e12, 1e9, 1.0, 1, 2.0, 2.0)
    assert mfu == pytest.approx(0.5)
    assert frac == pytest.approx(0.5)
    # zero wall is a no-signal, not a division crash
    assert roofline_fractions(1e12, 1e9, 0.0, 1, 2.0, 2.0) == (0.0, 0.0)
    # chips scale the denominator
    mfu2, _ = roofline_fractions(1e12, 1e9, 1.0, 2, 2.0, 2.0)
    assert mfu2 == pytest.approx(0.25)
