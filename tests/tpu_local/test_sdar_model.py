"""The block-diffusion family (the GQA trunk with QK-norm and many small
experts under a block-causal mask, generating a block of tokens a step) on
the CPU in float32: the program's prefill and block-step forward through the
cache against the plain reference, every denoise pass with masked positions,
the fill rule, the engine serving it token for token, and that the mask
parameter at 1 leaves the other families' programs what they were."""

import asyncio
import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local import engine as engine_module
from mcp_context_forge_tpu.tpu_local import sampling
from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
from mcp_context_forge_tpu.tpu_local.kv import init_kv_state
from mcp_context_forge_tpu.tpu_local.models import family_of, sdar
from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
from mcp_context_forge_tpu.tpu_local.ops import attention as attention_ops
from mcp_context_forge_tpu.tpu_local.sampling import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from benchmark.reference import sdar_plain as plain  # noqa: E402

CFG = MODEL_CONFIGS["sdar-test"]
BL = CFG.block_length
PAGE, SLOTS, TABLE, BUCKET = 16, 2, 8, 32
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return sdar.init_params(CFG, jax.random.PRNGKey(5), jnp.float32)


def fresh_kv():
    kv = init_kv_state(CFG, 1 + SLOTS * TABLE, PAGE, SLOTS, TABLE,
                       dtype=jnp.float32)
    tables = 1 + np.arange(SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    return kv._replace(block_tables=jnp.asarray(tables))


_hist = jax.jit(partial(sdar.prefill_with_history, config=CFG),
                static_argnames=("ctx_pages", "head"))
_dense = jax.jit(partial(sdar.prefill, config=CFG), static_argnames=("head",))
SLOT = jnp.asarray([1])


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(32, 127, n).tolist()


def run_of(tokens, start, width):
    """tokens at positions start.. as one padded [1, width] row."""
    tok = np.zeros((1, width), np.int32)
    pos = np.full((1, width), -1, np.int32)
    tok[0, :len(tokens)] = tokens
    pos[0, :len(tokens)] = np.arange(start, start + len(tokens))
    return jnp.asarray(tok), jnp.asarray(pos)


def prefilled(params, prompt):
    """The cache after the engine's prefill of ``prompt``: its whole blocks,
    inside the bucket through the dense program, above it in chunk rounds.
    -> (kv, the logits [aligned, V] of the positions it ran)."""
    aligned = len(prompt) - len(prompt) % BL
    kv, rows = fresh_kv(), []
    for start in range(0, aligned, BUCKET):
        end = min(start + BUCKET, aligned)
        tokens, positions = run_of(prompt[start:end], start, BUCKET)
        step = (partial(_hist, ctx_pages=TABLE) if aligned > BUCKET else _dense)
        logits, kv = step(params, tokens=tokens, positions=positions, kv=kv,
                          slot_ids=SLOT)
        rows.append(np.asarray(logits[0, :end - start]))
    return kv, np.concatenate(rows) if rows else np.zeros((0, CFG.vocab_size))


def block_pass(params, kv, block_tokens, start):
    """One pass of the block step's forward over a block's tokens (fewer than
    Bl: a sequence that ends inside the block). -> (logits [n, V], kv)."""
    tokens, positions = run_of(block_tokens, start, BL)
    logits, kv = _hist(params, tokens=tokens, positions=positions, kv=kv,
                       slot_ids=SLOT, ctx_pages=TABLE)
    return np.asarray(logits[0, :len(block_tokens)]), kv


@pytest.mark.parametrize("length,extra", [(24, 8), (22, 9), (70, 6), (3, 4)],
                         ids=["aligned", "unaligned", "above_bucket",
                              "shorter_than_a_block"])
def test_prefill_then_blocks_match_the_reference(params, length, extra):
    """The whole blocks of a prompt through the prefill, then every further
    block (prompt remainder + forced tokens, the last one cut short) as a pass
    over its known tokens through the block step's forward and cache."""
    sequence = prompt_of(length + extra, length)
    kv, got = prefilled(params, sequence[:length])
    rows = [got]
    for start in range(len(got), len(sequence), BL):
        logits, kv = block_pass(params, kv, sequence[start:start + BL], start)
        rows.append(logits)
    want = np.asarray(plain.forward(params, CFG, sequence,
                                    list(range(len(sequence))))[0])
    np.testing.assert_allclose(np.concatenate(rows), want, atol=TOL, rtol=TOL)


def test_the_mask_is_block_causal_not_causal(params):
    """Position 0 sees position 3 (its block) and not position 4."""
    base = prompt_of(8, 1)
    first = lambda seq: np.asarray(_dense(
        params, tokens=run_of(seq, 0, BUCKET)[0],
        positions=run_of(seq, 0, BUCKET)[1], kv=fresh_kv(),
        slot_ids=SLOT)[0][0, 0])
    in_block, next_block = list(base), list(base)
    in_block[3] += 1
    next_block[4] += 1
    assert np.abs(first(in_block) - first(base)).max() > 1e-3
    np.testing.assert_array_equal(first(next_block), first(base))


@pytest.mark.parametrize("length", [10, 32], ids=["unaligned", "aligned"])
def test_every_denoise_pass_matches_the_reference(params, length):
    """Each pass the reference's generation makes, masked positions and all,
    through the program's cache: the same partly masked block gives the same
    logits, with the blocks before it committed as the program commits them."""
    prompt = prompt_of(length, 40 + length)
    passes = []
    plain.generate(params, CFG, prompt, 9, passes_out=passes)
    assert len(passes) >= 8 and any(
        0 < sum(flags) < BL for _, flags, _ in passes)
    kv, _ = prefilled(params, prompt)
    at = None
    for sequence, flags, want in passes:
        start = len(sequence) - BL
        if at is not None and start != at:
            # the block before is final: its commit pass
            _, kv = block_pass(params, kv, sequence[at:at + BL], at)
        at = start
        assert all(t == CFG.mask_token_id
                   for t, f in zip(sequence[start:], flags) if f)
        got, kv = block_pass(params, kv, sequence[start:], start)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


# --------------------------------------------------------------- the fill rule

RULE_CASES = {
    "peaked": ([0.95, 0.99, 0.5, 0.2], [1, 1, 1, 1], 1),
    "peaked_but_fewer_than_count": ([0.95, 0.5, 0.4, 0.2], [1, 1, 1, 1], 2),
    "flat": ([0.1, 0.2, 0.15, 0.05], [1, 1, 1, 1], 1),
    "tied": ([0.3, 0.3, 0.3, 0.3], [1, 1, 1, 1], 1),
    "tied_two": ([0.3, 0.3, 0.3, 0.3], [0, 1, 1, 1], 2),
    "known_positions_never_fill": ([0.99, 0.99, 0.2, 0.1], [0, 0, 1, 1], 1),
    "fewer_masked_than_count": ([0.5, 0.5, 0.5, 0.4], [0, 0, 0, 1], 2),
    "nothing_masked": ([0.5, 0.5, 0.5, 0.4], [0, 0, 0, 0], 1),
    "at_the_threshold_is_not_above_it": ([0.9, 0.2, 0.1, 0.1], [1, 1, 1, 1], 1),
}


@pytest.mark.parametrize("case", RULE_CASES)
def test_fill_rule_is_the_references(case):
    confidence, flags, count = RULE_CASES[case]
    want, by_threshold = plain.fill_rule(confidence, [bool(f) for f in flags],
                                         count, 0.9)
    fill, enough = sampling.fill_positions(
        jnp.asarray([confidence], jnp.float32), jnp.asarray([flags], bool),
        jnp.asarray(count), 0.9)
    assert np.flatnonzero(np.asarray(fill[0])).tolist() == want
    assert bool(enough[0]) == by_threshold


def test_fill_rule_on_random_blocks():
    rng = np.random.default_rng(0)
    confidence = rng.choice([0.05, 0.3, 0.3, 0.6, 0.91, 0.97], (64, 8))
    flags = rng.random((64, 8)) < 0.6
    for count in (0, 1, 2, 3):
        fill, _ = sampling.fill_positions(
            jnp.asarray(confidence, jnp.float32), jnp.asarray(flags),
            jnp.asarray(count), 0.9)
        for row in range(64):
            want, _ = plain.fill_rule(confidence[row].tolist(),
                                      flags[row].tolist(), count, 0.9)
            assert np.flatnonzero(np.asarray(fill[row])).tolist() == want


def test_fill_counts_give_the_remainder_to_the_earliest_passes():
    assert sampling.fill_counts(4, 4) == (1, 1, 1, 1)
    assert sampling.fill_counts(8, 3) == (3, 3, 2)
    assert sampling.fill_counts(4, 6) == (1, 1, 1, 1, 0, 0)
    assert list(sampling.fill_counts(8, 3)) == plain.fill_counts(8, 3)


def test_confidence_is_the_probability_of_the_chosen_token():
    logits = jnp.asarray(np.random.default_rng(3).normal(size=(6, 40)) * 3,
                         jnp.float32)
    greedy = SamplingParams(jnp.zeros(6), jnp.zeros(6, jnp.int32), jnp.ones(6))
    tokens, confidence = sampling.sample_with_confidence(
        logits, greedy, jax.random.PRNGKey(0))
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    assert tokens.tolist() == probs.argmax(-1).tolist()
    np.testing.assert_allclose(confidence, probs.max(-1), rtol=1e-5)
    # a sampled row: the probability under the scaled and filtered
    # distribution it was drawn from; a greedy row beside it keeps the plain one
    mixed = SamplingParams(jnp.asarray([0.7, 0.0] * 3), jnp.asarray([5, 0] * 3),
                           jnp.ones(6))
    key = jax.random.PRNGKey(1)
    tokens, confidence = sampling.sample_with_confidence(logits, mixed, key)
    assert tokens.tolist() == sampling.sample_tokens(logits, mixed, key).tolist()
    for row in range(6):
        if row % 2:
            want = probs[row].max()
        else:
            scaled = np.asarray(logits[row]) / 0.7
            kept = np.sort(scaled)[-5]
            dist = np.where(scaled >= kept, np.exp(scaled - scaled.max()), 0.0)
            want = dist[int(tokens[row])] / dist.sum()
        np.testing.assert_allclose(confidence[row], want, rtol=1e-4)


# ------------------------------------------- the block step's loop, by a stub

def stub_forward(monkeypatch, logits_of):
    """The block step with the family's forward replaced by ``logits_of(block
    tokens [B, Bl]) -> [B, Bl, V]``; counts the passes that asked for a head."""
    calls = {"head": 0, "commit": 0}

    def forward(params, config, tokens, positions, kv, slot_ids, head=True,
                **_):
        calls["head" if head else "commit"] += 1
        return (logits_of(tokens) if head else None), kv

    monkeypatch.setattr(sdar, "prefill_with_history", forward)
    return calls


def run_block_step(tokens, masked, positions=None):
    B = len(tokens)
    positions = (np.tile(np.arange(8, 8 + BL), (B, 1)) if positions is None
                 else positions)
    greedy = SamplingParams(jnp.zeros(B), jnp.zeros(B, jnp.int32), jnp.ones(B))
    (block, passes, by_threshold), _ = sdar.block_step(
        None, CFG, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32), jnp.asarray(masked, bool), None,
        jnp.arange(B), greedy, jax.random.PRNGKey(0))
    return np.asarray(block).tolist(), int(passes), int(by_threshold)


def test_block_step_threshold_and_count_branches_in_lockstep(monkeypatch):
    """Row 0's logits are peaked at a token that IS the mask id (one pass fills
    it by the threshold: a masked position is a flag, not an id); row 1's are
    flat (a position a pass, ties to the lower position); row 2 is idle."""
    V, mask_id = CFG.vocab_size, CFG.mask_token_id
    peaked = np.zeros((BL, V), np.float32)
    peaked[:, mask_id] = 20.0
    flat = np.zeros((BL, V), np.float32)
    flat[:, 7] = 1e-3

    def logits_of(tokens):
        return jnp.stack([jnp.asarray(peaked), jnp.asarray(flat),
                          jnp.asarray(flat)])

    calls = stub_forward(monkeypatch, logits_of)
    block, passes, by_threshold = run_block_step(
        [[5, mask_id, mask_id, mask_id], [mask_id] * BL, [0] * BL],
        [[0, 1, 1, 1], [1] * BL, [0] * BL],
        positions=[list(range(8, 12)), list(range(20, 24)), [-1] * BL])
    assert block[0] == [5, mask_id, mask_id, mask_id]
    assert block[1] == [7] * BL and block[2] == [0] * BL
    assert passes == BL and by_threshold == 3
    assert calls == {"head": 1, "commit": 1}       # traced once each


def test_block_step_with_nothing_masked_only_commits(monkeypatch):
    stub_forward(monkeypatch, lambda tokens: jnp.zeros(
        (*tokens.shape, CFG.vocab_size)))
    block, passes, by_threshold = run_block_step([[1, 2, 3, 4]], [[0] * BL])
    assert (block, passes, by_threshold) == ([[1, 2, 3, 4]], 0, 0)


# ------------------------------------------------------------------ the engine

def _engine(**overrides):
    config = dict(model="sdar-test", max_batch=4, max_seq_len=256,
                  page_size=PAGE, num_pages=64, prefill_buckets=(BUCKET,),
                  prefill_max_batch=2, prefix_cache=False,
                  decode_overlap=False, dtype="float32")
    config.update(overrides)
    return TPUEngine(EngineConfig(**config), devices=jax.devices()[:1])


async def _generate(engine, prompt, n, **kw):
    return [t async for t in engine.generate(list(prompt), max_tokens=n, **kw)]


def test_engine_generates_the_references_tokens_and_counts_them():
    """Aligned, unaligned and chunked prompts, ``max_tokens`` 1, 5 and 8, alone
    and together: token for token the plain generation, exact accounting, and
    the block counters."""
    assert family_of(CFG) is sdar
    cases = [(24, 8), (22, 5), (70, 5), (9, 1)]
    prompts = [[1] + prompt_of(n - 1, n) for n, _ in cases]

    async def run():
        engine = _engine()
        await engine.start()
        try:
            alone = [await _generate(engine, p, n)
                     for p, (_, n) in zip(prompts, cases)]
            mid = {k: getattr(engine.stats, k) for k in (
                "completion_tokens", "block_tokens", "block_steps",
                "denoise_passes")}
            together = await asyncio.gather(*[
                _generate(engine, p, n) for p, (_, n) in zip(prompts, cases)])
            steps = engine.timeline.snapshot()["step"]
            return engine, alone, together, mid, steps
        finally:
            await engine.stop()

    engine, alone, together, mid, steps = asyncio.run(run())
    weights = engine.params
    for prompt, (_, n), got, again in zip(prompts, cases, alone, together):
        want = plain.generate(weights, CFG, prompt, n)
        assert got == want and again == want and len(got) == n
    stats = engine.stats
    emitted = sum(n for _, n in cases)
    assert mid["completion_tokens"] == mid["block_tokens"] == emitted
    assert stats.completion_tokens == stats.block_tokens == 2 * emitted
    assert stats.prompt_tokens == 2 * sum(len(p) for p in prompts)
    assert stats.requests == 2 * len(cases)
    # alone: a block a dispatch; 24 + 8 is two whole blocks of four passes
    assert mid["block_steps"] == sum(
        -(-((n_p % BL) + n) // BL) for n_p, n in cases)
    assert stats.block_positions_filled_by_threshold == 0
    assert stats.denoise_passes > stats.block_steps
    assert stats.moe_scan_steps >= stats.denoise_passes + stats.block_steps
    assert stats.moe_grouped_steps >= 2 * len(cases)       # the prefills
    assert stats.decode_dispatches == stats.block_steps
    assert not engine._decode_fns and not engine._decode_fb_fns
    assert engine.allocator.pages_in_use == 0
    blocks = [s for s in steps if s.kind == "decode"]
    assert len(blocks) == stats.block_steps
    assert sum(s.counts.block_tokens for s in blocks) == stats.block_tokens
    assert sum(s.counts.denoise_passes for s in blocks) == stats.denoise_passes
    assert all(s.counts is None for s in steps if s.kind != "decode")
    ring = engine.recent_steps()
    assert {r["kind"] for r in ring} <= {"prefill", "chunk_prefill", "decode"}
    assert all(r["tokens"] == 0 for r in ring if r["kind"] != "decode")


# 32 experts top-2: the narrow side of ``expert_path`` within CI's reach
WIDE = dataclasses.replace(CFG, name="sdar-test-e32", n_experts=32)


@pytest.mark.parametrize("model,batch,bucket,block_path", [
    ("sdar-test", 4, BUCKET, "scan"), ("sdar-test-e32", 16, 64, "grouped")])
def test_block_steps_take_the_path_the_rule_gives(model, batch, bucket,
                                                  block_path, monkeypatch,
                                                  model_variant):
    """A few block dispatches under the family's rule (``expert_path``, a
    function of the step's shape) against the scan always (``moe_impl=
    "dense"``): the same tokens at temperature 0, and the counters say which
    path the block steps took.

    ``sdar-test`` is 8 experts top-2 at ``moe_block`` 8: T·k alone is E·T/4,
    the most padded rows the rule grants a narrow step, so its block steps
    (4 rows x 4 positions) SCAN, as a Mixtral decode step does, and only its
    prefills (32 tokens: 64 pairs = E·moe_block) are grouped. With 32 experts
    top-2 a block step of 16 rows x 4 positions is narrow (128 pairs under
    32·8) and its rows 128 + 32·8 = 384 are under 32·64/4 = 512: the row-block
    plan at the block its width gives (8), idle rows given no row, and with
    prefills of 64 tokens (grouped by the same rule) no step scans at all."""
    monkeypatch.setitem(MODEL_CONFIGS, WIDE.name, WIDE)
    config = MODEL_CONFIGS[model]
    one = type("Mesh", (), {"shape": {"model": 1}})()
    assert sdar.expert_path(config, one, batch * BL, jnp.float32) == block_path
    assert sdar.expert_path(config, one, bucket, jnp.float32) == "grouped"
    prompts = [[1] + prompt_of(n - 1, n) for n in (22, 29, 9)]

    async def run(model):
        engine = _engine(model=model, max_batch=batch,
                         prefill_buckets=(bucket,))
        await engine.start()
        try:
            tokens = await asyncio.gather(*[
                _generate(engine, p, 9) for p in prompts])
            return tokens, engine.stats
        finally:
            await engine.stop()

    tokens, stats = asyncio.run(run(model))
    scanned, scan_stats = asyncio.run(run(
        model_variant(model, moe_impl="dense")))
    assert tokens == scanned and all(len(t) == 9 for t in tokens)
    assert scan_stats.moe_grouped_steps == 0 < scan_stats.moe_scan_steps
    assert stats.block_steps >= 3
    block_passes = stats.denoise_passes + stats.block_steps
    assert stats.moe_grouped_steps + stats.moe_scan_steps == (
        block_passes + stats.prefill_batches)
    if block_path == "grouped":
        assert stats.moe_scan_steps == 0
    else:
        assert stats.moe_scan_steps == block_passes


def test_a_stop_token_inside_a_block_ends_the_stream_there():
    prompt = [1] + prompt_of(20, 8)

    async def run():
        engine = _engine()
        await engine.start()
        try:
            free = await _generate(engine, prompt, 8)
            stopped = await _generate(engine, prompt, 8, stop_ids=(free[5],))
            return engine, free, stopped
        finally:
            await engine.stop()

    engine, free, stopped = asyncio.run(run())
    cut = free.index(free[5]) + 1
    assert stopped == free[:cut]
    assert stopped == plain.generate(engine.params, CFG, prompt, 8,
                                     stop_ids=(free[5],))
    assert engine.stats.completion_tokens == 8 + cut


def test_a_block_without_its_page_truncates():
    """Two pages a slot at most: the block that would start the third page is
    refused by the pool and the request ends with what it has."""
    prompt = [1] + prompt_of(27, 2)

    async def run():
        engine = _engine(max_seq_len=2 * PAGE, num_pages=16)
        await engine.start()
        try:
            return await _generate(engine, prompt, 20), engine
        finally:
            await engine.stop()

    tokens, engine = asyncio.run(run())
    assert len(tokens) == 2 * PAGE - len(prompt)
    assert tokens == plain.generate(engine.params, CFG, prompt, len(tokens))
    assert engine.allocator.pages_in_use == 0


def test_warmup_compiles_block_steps_and_no_decode_program():
    engine = _engine(max_seq_len=4 * PAGE, num_pages=16)
    engine.warmup()
    assert not engine._decode_fns and not engine._decode_fb_fns
    assert sorted(engine._block_fns) == engine._ctx_buckets()
    assert engine.attn_traced["decode"] == "gather"

    async def run():
        await engine.start()
        try:
            return await _generate(engine, [1] + prompt_of(40, 3), 6)
        finally:
            await engine.stop()

    assert len(asyncio.run(run())) == 6
    assert engine.compile_tracker.serving_compiles() == 0


@pytest.mark.parametrize("setting,words", [
    (dict(superstep=4), "superstep"),
    (dict(decode_overlap=True), "decode_overlap"),
    (dict(spec_decode=True), "spec_decode"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_buckets=(30,)), "multiples of block_length"),
], ids=["superstep", "decode_overlap", "spec_decode", "prefix_cache",
        "bucket_not_whole_blocks"])
def test_unserved_settings_refuse_at_build(setting, words):
    with pytest.raises(NotImplementedError, match=words):
        _engine(**setting)


def test_refusals_name_each_setting_once():
    mesh = type("M", (), {"shape": {"model": 1}})()
    served = EngineConfig(model="sdar-test", prefix_cache=False,
                          decode_overlap=False)
    assert sdar.refusals(CFG, served, mesh, tiers=False) == []
    config = EngineConfig(model="sdar-test", k_ladder=(1, 4), sp_impl="ring",
                          page_size=6, max_seq_len=62, prefill_buckets=(16,))
    why = sdar.refusals(CFG, config, mesh, tiers=True)
    assert [w.split(":")[0].split(" ")[0] for w in why] == [
        "superstep", "decode_overlap", "prefix_cache", "sp_impl=\'ring\'",
        "page_size,"]
    assert "[6, 62]" in why[-1]


# ----------------------------- mask_block = 1 is the causal mask, bit for bit

def _parent_attention_reference(q, k, v, valid=None, mask_block=1):
    """``ops/attention.py: attention_reference`` as it was before it took
    ``mask_block`` (which these families never set)."""
    import math
    assert mask_block == 1
    B, S, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    qf = q.astype(jnp.float32).reshape(B, S, KV, group, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qf, kf) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    mask = causal[None, None, None]
    if valid is not None:
        mask = mask & valid[:, None, None, None, :]
    scores = jnp.where(mask, scores, attention_ops.NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, vf)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _parent_sample_tokens(logits, params, key):
    """``sampling.py: sample_tokens`` as it was before its draw was shared."""
    greedy = jnp.argmax(logits, axis=-1)
    samples, filters = params.tiers()

    def draw():
        temp = jnp.maximum(params.temperature, 1e-6)[:, None]
        scaled = logits / temp
        masked = jax.lax.cond(filters, sampling._filtered, lambda s, _: s,
                              scaled, params)
        sampled = jax.random.categorical(key, masked, axis=-1)
        return jnp.where(params.temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(samples, draw, lambda: greedy)


def _step_programs(engine):
    """The jaxprs of the engine's three step programs at one small shape."""
    B, S = 2, 32
    samp = SamplingParams(jnp.zeros(B), jnp.zeros(B, jnp.int32), jnp.ones(B))
    tokens = jnp.zeros((B, S), jnp.int32)
    positions = jnp.tile(jnp.arange(S), (B, 1))
    rows, key = jnp.arange(B), jax.random.PRNGKey(0)
    with engine.mesh:
        return [
            str(jax.make_jaxpr(engine._prefill_and_sample)(
                engine.params, engine.kv, tokens, positions, rows, rows, samp,
                key)),
            str(jax.make_jaxpr(partial(engine._prefill_hist_and_sample,
                                       ctx_pages=4))(
                engine.params, engine.kv, tokens, positions, rows, rows, samp,
                key)),
            str(jax.make_jaxpr(partial(engine._decode_and_sample, ctx_pages=4,
                                       k=2))(
                engine.params, engine.kv, rows, rows, rows, rows + 1, rows,
                jnp.full((B, 4), -1), samp, key))]


@pytest.mark.parametrize("model", ["llama3-test", "mixtral-test"])
def test_other_families_step_programs_are_what_they_were(model, monkeypatch):
    """The GQA trunk's traced step programs with the shared mask and sampling
    code as this family left it, against the same programs traced with the
    code as it was before: the same jaxpr, equation for equation."""
    engine = TPUEngine(EngineConfig(
        model=model, max_batch=2, max_seq_len=128, page_size=16, num_pages=32,
        prefill_buckets=(32,), prefix_cache=False, dtype="float32"),
        devices=jax.devices()[:1])
    now = _step_programs(engine)
    monkeypatch.setattr(attention_ops, "attention_reference",
                        _parent_attention_reference)
    monkeypatch.setattr(engine_module, "sample_tokens", _parent_sample_tokens)
    jax.clear_caches()
    before = _step_programs(engine)
    assert now == before
    assert all("argmax" in program for program in now)


def test_flash_kernel_at_mask_block_one_is_the_causal_kernel():
    """The kernel's jaxpr with no ``mask_block`` and with 1 are one program,
    with 4 another, and block-causal agrees with the reference."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 4, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
    valid = jnp.ones((1, 128), bool)
    flash = attention_ops.flash_attention_pallas.__wrapped__
    trace = lambda **kw: str(jax.make_jaxpr(
        partial(flash, interpret=True, **kw))(q, k, k, valid))
    assert trace() == trace(mask_block=1)
    assert trace() != trace(mask_block=4)
    assert " or " not in trace() and " or " in trace(mask_block=4)
    got = attention_ops.flash_attention_pallas(q, k, k, valid, interpret=True,
                                               mask_block=4)
    want = attention_ops.attention_reference(q, k, k, valid, mask_block=4)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    causal = attention_ops.attention_reference(q, k, k, valid)
    np.testing.assert_array_equal(
        causal, _parent_attention_reference(q, k, k, valid))
    assert np.abs(np.asarray(want - causal)).max() > 1e-3
    with pytest.raises(ValueError, match="must divide the q block"):
        attention_ops.flash_attention_pallas(q, k, k, valid, interpret=True,
                                             mask_block=3)


def test_block_last_rounds_up_and_keeps_padding():
    at = jnp.asarray([-1, 0, 3, 4, 9])
    assert attention_ops.block_last(at, 1) is at
    assert attention_ops.block_last(at, 4).tolist() == [-1, 3, 3, 7, 11]
    assert attention_ops.block_last(at, 3).tolist() == [-1, 2, 5, 5, 11]
