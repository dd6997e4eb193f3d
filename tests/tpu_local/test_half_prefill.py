"""A lone short prompt runs a prefill of its own length.

Every dense prefill bucket ``b`` also compiles ``[1, b/2]`` of the same jitted
function (``engine.half_lengths``); a prompt without cached history, not
chunked, that fits ``b/2`` is admitted alone and dispatched through it
(``engine._lone_length``). Held here: what it yields against the full
program, in every family; which requests keep today's programs; the order of
admission; what the warm-up grid compiles; and the three counters.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)

BUCKET, HALF, PAGE = 32, 16, 16
# the five test configurations, each with what its family refuses switched
# off, and the tolerance its own model tests hold its float32 logits to
FAMILIES = {
    "llama3-test": ({}, 2e-4),
    "mixtral-test": ({"moe_block": 8}, 2e-4),
    "deepseek-test": ({}, 1e-4),
    "olmo-hybrid-test": ({"prefix_cache": False}, 1e-4),
    "sdar-test": ({"prefix_cache": False, "decode_overlap": False,
                   "moe_block": 4}, 1e-4),
}


@pytest.fixture(scope="module", autouse=True)
def _variants(model_variant):
    """``_engine`` registers a ``moe_block`` of its own through the conftest's
    helper: the setting is the model config's."""
    global _variant
    _variant = model_variant


def _engine(model: str = "llama3-test", devices: int | None = 1,
            **over) -> TPUEngine:
    config = dict(model=model, max_batch=4, max_seq_len=128, page_size=PAGE,
                  num_pages=64, prefill_buckets=(BUCKET,), prefill_max_batch=4,
                  dtype="float32", **FAMILIES.get(model, ({}, 0))[0])
    config.update(over)
    if "moe_block" in config:
        config["model"] = _variant(model, moe_block=config.pop("moe_block"))
    return TPUEngine(EngineConfig(**config),
                     devices=jax.devices()[:devices] if devices else None)


def _prompt(engine: TPUEngine, n: int, salt: int = 0) -> list[int]:
    return [engine.tokenizer.bos_id] + [
        32 + (7 * i + 3 + salt) % 90 for i in range(n - 1)]


async def _serve(engine: TPUEngine, body):
    await engine.start()
    try:
        return await body
    finally:
        await engine.stop()


async def _generate(engine: TPUEngine, prompt: list[int], n: int) -> list[int]:
    return [t async for t in engine.generate(list(prompt), max_tokens=n)]


def _prefill_steps(engine: TPUEngine) -> list[tuple[str, int, int, int]]:
    """(kind, rows, width, dispatched length) of every prefill dispatch."""
    return [(s.kind, s.rows, s.width, s.shape)
            for s in engine.timeline.snapshot()["step"]
            if s.kind in ("prefill", "prefill_hist", "chunk")]


def _last_logits(engine: TPUEngine, prompt: list[int], length: int):
    """The family's dense prefill over ``prompt`` padded to ``length``, on
    slot 0's pages: the logits at its last position."""
    n = len(prompt)
    tokens = np.full((1, length), engine.tokenizer.pad_id, np.int32)
    tokens[0, :n] = prompt
    positions = np.full((1, length), -1, np.int32)
    positions[0, :n] = np.arange(n)
    impl = engine._family.prefill_impl(
        engine.config.attn_impl, engine.mesh, length, engine.model_config)
    with engine.mesh:
        logits, *_ = engine._family.prefill(
            engine.params, engine.model_config, jnp.asarray(tokens),
            jnp.asarray(positions), engine.kv, jnp.zeros((1,), jnp.int32),
            attn_impl=impl, mesh=engine.mesh,
            last_idx=jnp.asarray([n - 1], jnp.int32))
    return np.asarray(logits, np.float32)[0]


@pytest.mark.parametrize("model", list(FAMILIES))
def test_a_lone_short_prompt_takes_the_half_program_and_says_what_the_full_one_says(model):
    engine = _engine(model)
    assert engine.half_lengths == {BUCKET: HALF}
    # one token more than the half holds; under a block family one block
    # more, as a prefill runs a prompt's whole blocks (``_prefill_end``)
    more = getattr(engine.model_config, "block_length", 1)
    short, over = _prompt(engine, HALF), _prompt(engine, HALF + more, salt=5)

    # the family's logits at the prompt's last position, padded to either
    # length (before the dispatch thread exists: slot 0's pages are ours)
    assert engine.allocator.allocate_slot(0, BUCKET)
    with engine.mesh:
        engine._sync_tables()
    half_logits = _last_logits(engine, short, HALF)
    full_logits = _last_logits(engine, short, BUCKET)
    engine.allocator.free_slot(0)
    tol = FAMILIES[model][1]
    np.testing.assert_allclose(half_logits, full_logits, atol=tol, rtol=tol)

    async def body():
        through_half = await _generate(engine, short, 8)
        took = engine.stats.half_prefill_batches
        halves, engine.half_lengths = engine.half_lengths, {}
        through_full = await _generate(engine, short, 8)      # forced [1, b]
        engine.half_lengths = halves
        one_more = await _generate(engine, over, 8)
        return through_half, took, through_full, one_more

    through_half, took, through_full, one_more = asyncio.run(
        _serve(engine, body()))
    assert took == 1 and engine.stats.half_prefill_batches == 1
    assert through_half == through_full and len(through_half) == 8
    assert len(one_more) == 8
    assert _prefill_steps(engine) == [("prefill", 1, 1, HALF),
                                      ("prefill", 1, 1, BUCKET),
                                      ("prefill", 1, 1, BUCKET)]
    # the step ring says the dispatched length too
    assert [r["bucket"] for r in engine.recent_steps()
            if r["kind"] == "prefill"] == [HALF, BUCKET, BUCKET]
    end = engine._prefill_end
    real = 2 * end(GenRequest("a", short)) + end(GenRequest("b", over))
    assert engine.stats.dense_prefill_tokens == real
    assert engine.stats.dense_prefill_positions == HALF + 2 * BUCKET


@pytest.mark.parametrize("path", ["prefix_hit", "chunked", "sp_bucket"])
def test_history_chunks_and_sequence_parallel_buckets_keep_their_programs(path):
    if path == "sp_bucket":
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        engine = _engine(devices=None, prefill_buckets=(BUCKET, 128),
                         max_seq_len=256, num_pages=96, sp_impl="ring",
                         sp_threshold=BUCKET, attn_impl="reference")
        # the bucket above the threshold has no half: 40 tokens fit 64, and
        # run the sequence-parallel program at 128
        assert engine.half_lengths == {BUCKET: HALF}
        asyncio.run(_serve(engine, _generate(engine, _prompt(engine, 40), 4)))
        assert _prefill_steps(engine) == [("prefill", 1, 1, 128)]
        assert engine.stats.dense_prefill_positions == 128
    elif path == "prefix_hit":
        engine = _engine(prefill_buckets=(BUCKET, 128), prefix_cache=True)
        assert engine.half_lengths == {BUCKET: HALF, 128: 64}
        first = _prompt(engine, 100)
        second = first[:80] + _prompt(engine, 11, salt=9)[1:]   # suffix of 10

        async def body():
            await _generate(engine, first, 2)
            await _generate(engine, second, 2)

        asyncio.run(_serve(engine, body()))
        assert engine.allocator.prefix_hits == 1
        # the suffix fits half its bucket, and runs the history program at
        # the bucket all the same; neither counts as a dense prefill
        assert _prefill_steps(engine) == [("prefill", 1, 1, 128),
                                          ("prefill_hist", 1, 1, BUCKET)]
        assert engine.stats.dense_prefill_positions == 128
    else:
        engine = _engine()
        asyncio.run(_serve(engine, _generate(engine, _prompt(engine, 40), 4)))
        assert _prefill_steps(engine) == [("chunk", 1, 1, BUCKET),
                                          ("chunk", 1, 1, BUCKET)]
        assert engine.stats.dense_prefill_positions == 0
    assert engine.stats.half_prefill_batches == 0


def _admit_all(engine: TPUEngine, lengths: list[int], **kw) -> list[list[str]]:
    """Queue one request a length (named by its place and length), then make
    admissions as the dispatch loop does until none is left: the names each
    prefill dispatch carried, in dispatch order."""
    requests = [GenRequest(f"{i}:{n}", _prompt(engine, n, salt=i),
                           max_tokens=1, **kw.get(f"{i}", {}))
                for i, n in enumerate(lengths)]
    engine._pending.extend(requests)
    dispatches = []
    with engine.mesh:
        while engine._pending:
            before = {r.request_id for r in requests if r.slot >= 0}
            assert engine._admit_batch()
            dispatches.append([r.request_id for r in requests
                               if r.slot >= 0 and r.request_id not in before])
            engine._flush_emits()
    return dispatches


def test_a_burst_is_admitted_in_arrival_order_and_no_short_prompt_shares_a_dispatch():
    engine = _engine()
    dispatches = _admit_all(engine, [20, 10, 30, 16, 25, 5, 17])
    # a long head takes the long ones behind it and passes over the short
    # ones, which then lead in their order, each alone
    assert dispatches == [["0:20", "2:30", "4:25", "6:17"],
                          ["1:10"], ["3:16"], ["5:5"]]
    assert _prefill_steps(engine) == [
        ("prefill", 4, 4, BUCKET), ("prefill", 1, 1, HALF),
        ("prefill", 1, 1, HALF), ("prefill", 1, 1, HALF)]
    # a short head goes alone, and the long ones behind it group as before
    engine = _engine()
    assert _admit_all(engine, [8, 9, 28, 12, 31]) == [
        ["0:8"], ["1:9"], ["2:28", "4:31"], ["3:12"]]
    stats = engine.stats
    assert stats.half_prefill_batches == 3 <= stats.prefill_batches == 4
    assert stats.dense_prefill_tokens == 8 + 9 + 28 + 12 + 31
    assert stats.dense_prefill_positions == 3 * HALF + 2 * BUCKET
    assert stats.dense_prefill_tokens <= stats.dense_prefill_positions


def test_priority_classes_admit_first_and_keep_their_order():
    engine = _engine()
    background = {"priority": 1}
    dispatches = _admit_all(engine, [10, 20, 12, 24],
                            **{"0": background, "1": background})
    # the interactive class leads, in its order; a long head still takes the
    # long ones behind it whatever their class, as before
    assert dispatches == [["2:12"], ["1:20", "3:24"], ["0:10"]]


def _warmed(**over) -> tuple[TPUEngine, int, dict[int, int]]:
    """A warmed engine, the shapes its dense prefill function compiled and
    those each of its history functions did."""
    engine = _engine(warmup=True, prefill_max_batch=2, **over)
    return (engine, engine._prefill_sample._cache_size(),
            {pages: fn._cache_size()
             for pages, fn in engine._prefill_hist_fns.items()})


def test_warmup_compiles_one_program_more_a_dense_bucket_and_traffic_compiles_none():
    engine, dense, hist = _warmed()
    assert engine.half_lengths == {BUCKET: HALF}
    # the settling call, widths 1 and 2 at the bucket, width 1 at its half;
    # the history functions widths 1 and 2 a context bucket
    assert dense == 4 and hist == {4: 2, 8: 2}
    # the same grid at a bucket whose half breaks a page: one program fewer,
    # and the history functions' shapes are the same
    odd, odd_dense, odd_hist = _warmed(prefill_buckets=(48,))
    assert odd.half_lengths == {}
    assert odd_dense == dense - 1 and odd_hist == hist

    async def body():
        lengths = [5, 30, 16, 17, 40, 9, 28, 3, 70]
        outs = await asyncio.gather(*[
            _generate(engine, _prompt(engine, n, salt=n), 4) for n in lengths])
        assert all(len(o) == 4 for o in outs)

    asyncio.run(_serve(engine, body()))
    assert engine.compile_tracker.serving_compiles() == 0
    stats = engine.stats
    assert 0 < stats.half_prefill_batches <= stats.prefill_batches
    assert 0 < stats.dense_prefill_tokens <= stats.dense_prefill_positions
    # the cost registry holds the half program beside the full one
    costed, *_ = _warmed(cost_analysis=True, prefix_cache=False)
    assert {(1, BUCKET), (1, HALF)} <= set(
        costed.cost_registry._entries["prefill"])


@pytest.mark.parametrize("case, config, want", [
    ("odd half of a page", dict(prefill_buckets=(48,)), {}),
    ("half under the bucket below", dict(prefill_buckets=(16, 32, 128)),
     {128: 64}),
    ("every bucket", dict(prefill_buckets=(32, 128)), {32: 16, 128: 64}),
    ("a family's unit", dict(prefill_buckets=(32,), unit=32), {}),
    # 4 experts, top-2: 32 tokens are 64 pairs >= 4 x 16 (row-blocks), 16 are
    # not (the scan): the half would change the expert formulation
    ("the experts' formulation", dict(model="mixtral-test", moe_block=16), {}),
    ("the experts' formulation kept", dict(model="mixtral-test", moe_block=8),
     {32: 16}),
])
def test_which_buckets_have_a_half(case, config, want, monkeypatch):
    config = dict(config)
    unit = config.pop("unit", None)
    if unit:
        from mcp_context_forge_tpu.tpu_local.models import llama
        monkeypatch.setattr(llama, "prefill_unit", lambda mesh, cfg: unit)
    assert _engine(**config).half_lengths == want


def test_each_familys_unit_off_the_chip_is_its_block_alone():
    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS, family_of
    for model in FAMILIES:
        config = MODEL_CONFIGS[model]
        unit = family_of(config).prefill_unit(None, config)
        assert unit == getattr(config, "block_length", 1), model
