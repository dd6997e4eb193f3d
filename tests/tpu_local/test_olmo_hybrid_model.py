"""The hybrid family (gated delta-rule linear-attention layers between
full-attention layers) on the CPU in float32: the program's step functions
through the cache against the plain token-by-token reference, the state pool's
row discipline, and the engine serving it."""

import asyncio
import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
from mcp_context_forge_tpu.tpu_local.kv import (PageAllocator, init_kv_state,
                                                kv_page_bytes, kv_pools,
                                                kv_state_bytes,
                                                state_rows_for)
from mcp_context_forge_tpu.tpu_local.models import family_of, olmo_hybrid
from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
from mcp_context_forge_tpu.tpu_local.ops import gated_delta
from mcp_context_forge_tpu.tpu_local.quantize import quantize_tree

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from benchmark.reference import olmo_hybrid_plain as plain  # noqa: E402

CFG = MODEL_CONFIGS["olmo-hybrid-test"]
PAGE, SLOTS, TABLE, BUCKET = 16, 4, 16, 64
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return olmo_hybrid.init_params(CFG, jax.random.PRNGKey(3), jnp.float32)


def fresh_kv(slot_rows=(1, 2, 3, 4)):
    """A pool of SLOTS slots, slot s owning pages [1 + s * TABLE, ...) and
    state row ``slot_rows[s]``."""
    kv = init_kv_state(CFG, 1 + SLOTS * TABLE, PAGE, SLOTS, TABLE,
                       dtype=jnp.float32)
    tables = 1 + np.arange(SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    return kv._replace(block_tables=jnp.asarray(tables),
                       state_rows=jnp.asarray(slot_rows, jnp.int32))


_hist = jax.jit(partial(olmo_hybrid.prefill_with_history, config=CFG),
                static_argnames=("ctx_pages",))
_dense = jax.jit(partial(olmo_hybrid.prefill, config=CFG))
_decode = jax.jit(partial(olmo_hybrid.decode_step, config=CFG))


def pack(rows, width=BUCKET):
    """[(prompt, start, end)] -> tokens, positions [B, width]."""
    tokens = np.zeros((len(rows), width), np.int32)
    positions = np.full((len(rows), width), -1, np.int32)
    for i, (prompt, start, end) in enumerate(rows):
        tokens[i, :end - start] = prompt[start:end]
        positions[i, :end - start] = np.arange(start, end)
    return jnp.asarray(tokens), jnp.asarray(positions)


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(32, 127, n).tolist()


def reference(params, tokens, positions):
    return np.asarray(plain.forward(params, CFG, tokens, positions)[0])


def test_dense_prefill_of_unequal_rows_matches_reference(params):
    prompts = [prompt_of(n, n) for n in (64, 37, 5)]
    tokens, positions = pack([(p, 0, len(p)) for p in prompts])
    logits, kv, aux = _dense(params, tokens=tokens, positions=positions,
                             kv=fresh_kv(), slot_ids=jnp.arange(3))
    for i, p in enumerate(prompts):
        want = reference(params, p, list(range(len(p))))
        np.testing.assert_allclose(np.asarray(logits[i, :len(p)]), want,
                                   atol=TOL, rtol=TOL)
    # rows, live state rows, real tokens scanned
    np.testing.assert_allclose(np.asarray(aux), [0, 0, 0, 3, 3, 64 + 37 + 5])


@pytest.mark.parametrize("length", [100, 150], ids=["2_rounds", "3_rounds"])
def test_chunk_rounds_carry_the_state(params, length):
    """A prompt longer than the bucket, in chunk rounds with a padded last
    chunk, beside a row that is all padding; then decode through the pool."""
    prompt = prompt_of(length, length)
    kv = fresh_kv()
    for start in range(0, length, BUCKET):
        end = min(start + BUCKET, length)
        tokens, positions = pack([(prompt, start, end), (prompt, 0, 0)])
        logits, kv, _ = _hist(params, tokens=tokens, positions=positions,
                              kv=kv, slot_ids=jnp.asarray([2, 0]),
                              ctx_pages=TABLE)
    forced = prompt_of(4, 9)
    rows = [np.asarray(logits[0, end - start - 1])]
    for j, token in enumerate(forced):
        at = length + j
        step, kv, _ = _decode(
            params, tokens=jnp.asarray([token, 0]),
            positions=jnp.asarray([at, 0]), kv=kv,
            slot_ids=jnp.asarray([2, 1]), seq_lens=jnp.asarray([at + 1, 0]),
            write_mask=jnp.asarray([True, False]))
        rows.append(np.asarray(step[0]))
    want = reference(params, prompt + forced,
                     list(range(length - 1, length + len(forced))))
    np.testing.assert_allclose(np.stack(rows), want, atol=TOL, rtol=TOL)
    # slot 0's row (all padding) and slot 1's (idle decode row) were never written
    assert not np.asarray(kv.state[:, 1]).any()
    assert not np.asarray(kv.state[:, 2]).any()
    assert np.asarray(kv.state[:, 3]).any()


def test_stale_step_and_next_tenant(params):
    """A decode step in flight for a finished request writes its old row; the
    row's next tenant starts from zero all the same, and no other live row
    moves."""
    a, b = prompt_of(40, 1), prompt_of(30, 2)
    tokens, positions = pack([(a, 0, 40), (b, 0, 30)])
    _, kv, _ = _dense(params, tokens=tokens, positions=positions,
                      kv=fresh_kv(), slot_ids=jnp.arange(2))
    other = np.asarray(kv.state[:, 2]).copy()
    # the stale step: slot 0 decodes once more after its request finished
    _, kv, _ = _decode(params, tokens=jnp.asarray([7]),
                       positions=jnp.asarray([40]), kv=kv,
                       slot_ids=jnp.asarray([0]), seq_lens=jnp.asarray([41]))
    np.testing.assert_array_equal(np.asarray(kv.state[:, 2]), other)
    # the next tenant of slot 0 / row 1
    c = prompt_of(20, 3)
    tokens, positions = pack([(c, 0, 20)])
    logits, kv, _ = _hist(params, tokens=tokens, positions=positions, kv=kv,
                          slot_ids=jnp.asarray([0]), ctx_pages=TABLE)
    np.testing.assert_allclose(np.asarray(logits[0, :20]),
                               reference(params, c, list(range(20))),
                               atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(np.asarray(kv.state[:, 2]), other)


def test_int8_weights_agree_with_their_dequantised_twin(params):
    quant = quantize_tree(params, olmo_hybrid.params_logical(CFG),
                          scale_dtype=jnp.float32)
    layer = quant["layers"][0]
    assert isinstance(layer["wq"], dict) and isinstance(layer["wg"], dict)
    assert not isinstance(layer["wa"], dict) and not isinstance(layer["conv"], dict)
    prompt = prompt_of(48, 5)
    tokens, positions = pack([(prompt, 0, 48)])
    logits, _, _ = _dense(quant, tokens=tokens, positions=positions,
                          kv=fresh_kv(), slot_ids=jnp.arange(1))
    want = reference(quant, prompt, list(range(48)))
    np.testing.assert_allclose(np.asarray(logits[0, :48]), want, atol=TOL, rtol=TOL)


def _delta_inputs(B, S, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -0.5 * jax.random.uniform(ks[3], (B, S, H))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, S, H)))
    state = jax.random.normal(ks[5], (B, H, dk, dv))
    return q, k, v, g, beta, state


def test_chunked_form_is_the_recurrence():
    """Including beta > 1, a stored S_0 != 0 and a length the chunk does not
    divide."""
    q, k, v, g, beta, state = _delta_inputs(2, 150, 3, 16, 32)
    assert float(beta.max()) > 1.5
    want_o, want_s = gated_delta.gated_delta_recurrence(q, k, v, g, beta, state)
    got_o, got_s = gated_delta.gated_delta_chunked(q, k, v, g, beta, state)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("tokens", [1, 128], ids=["step", "chunk"])
def test_kernel_is_its_jnp_twin(tokens):
    """The Pallas body (interpreted): rows with unequal lengths, a fresh row,
    a padding row on the trash row; other rows and layers untouched."""
    H, dk, dv = 4, 16, 32
    q, k, v, g, beta, _ = _delta_inputs(3, tokens, H, dk, dv, seed=1)
    counts = jnp.asarray([tokens, max(1, tokens - 58), 0])
    valid = jnp.arange(tokens)[None] < counts[:, None]
    g, beta = (jnp.where(valid[..., None], a, 0.0) for a in (g, beta))
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 5, dk, H * dv))
    rows, fresh = jnp.asarray([2, 4, 0]), jnp.asarray([0, 1, 0])
    got_o, got_pool = gated_delta.gated_delta_pallas(
        q, k, v, g, beta, pool, rows, counts, fresh, layer=1, interpret=True)
    want_o, want_pool = gated_delta.gated_delta_reference(
        q, k, v, g, beta, pool, rows, fresh > 0, layer=1, chunked=False)
    np.testing.assert_allclose(jnp.where(valid[..., None, None], got_o, 0),
                               jnp.where(valid[..., None, None], want_o, 0),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_pool[1, 1:], want_pool[1, 1:], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[1, jnp.asarray([1, 3])],
                                  pool[1, jnp.asarray([1, 3])])


def _through_the_pool(q, k, v, g, beta, pool, rows, counts, fresh, layer=1):
    """(o, pool) of the kernel (interpreted), and what the recurrence gives
    from the rows' states with tokens past a row's count the identity."""
    H = q.shape[2]
    valid = jnp.arange(q.shape[1])[None] < counts[:, None]
    got = gated_delta.gated_delta_pallas(
        q, k, v, g, beta, pool, rows, counts, fresh, layer=layer, interpret=True)
    state = jnp.where((fresh > 0)[:, None, None, None], 0.0,
                      gated_delta.pool_rows(pool, layer, rows, H))
    want_o, want_state = gated_delta.gated_delta_recurrence(
        q, k, v, jnp.where(valid[..., None], g, 0.0),
        jnp.where(valid[..., None], beta, 0.0), state)
    return got, (want_o, gated_delta.flat_rows(want_state)), valid


CHUNKWISE = ("unequal_rows", "strong_decay", "repeated_keys_beta_2",
             "split_prompt", "odd_pairing")


@pytest.mark.parametrize("case", CHUNKWISE)
def test_chunkwise_scalar_body_is_the_recurrence(case):
    """The chunkwise body of the scalar form (whole 64-token chunks at the
    cell's head geometry, 96 x 192, two heads a 384-lane slab; interpreted)
    against the token-by-token definition, at the chunked twin's tolerance.
    ``unequal_rows``: a full row, one ending inside its second chunk, a fresh
    one ending in the first 16 tokens, a padding row on the trash row (tokens
    past a count are the identity whatever g and beta say there); other rows
    and layers untouched. ``strong_decay``: g = -10 a token for a chunk and
    more, a chunk's summed g at -640, where the factored WY form overflows
    float32. ``repeated_keys_beta_2``: three keys in turn with beta 1.95 and
    hardly any decay, the triangular system's worst case. ``split_prompt``:
    512 tokens as one call and as two of 256, the state carried by the pool.
    ``odd_pairing``: fourteen heads (2 mod 4) are seven slabs, five in step
    in the loop's one turn and two after it."""
    tol = dict(atol=2e-5, rtol=2e-5)
    dk, dv = 96, 192
    B, S, H = {"unequal_rows": (4, 128, 2), "split_prompt": (1, 512, 2),
               "odd_pairing": (1, 64, 14)}.get(case, (2, 128, 2))
    assert gated_delta.scalar_chunk_group(S, H, dk, dv) == 2
    q, k, v, g, beta, _ = _delta_inputs(B, S, H, dk, dv, seed=5)
    counts = jnp.full((B,), S)
    rows, fresh = jnp.arange(1, B + 1), jnp.zeros((B,), jnp.int32)
    if case == "unequal_rows":
        counts, rows = jnp.asarray([S, 70, 5, 0]), jnp.asarray([2, 4, 5, 0])
        fresh = jnp.asarray([0, 0, 1, 0])
    elif case == "strong_decay":
        g = jnp.where((jnp.arange(S) < 80)[None, :, None], -10.0, g)
    elif case == "repeated_keys_beta_2":
        k = k[:, jnp.arange(S) % 3]
        g, beta = g * 0.01, jnp.full_like(beta, 1.95)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 7, dk, H * dv))
    (got_o, got_pool), (want_o, want_rows), valid = _through_the_pool(
        q, k, v, g, beta, pool, rows, counts, fresh)
    assert bool(jnp.all(jnp.isfinite(got_o))) and bool(jnp.all(jnp.isfinite(got_pool)))
    np.testing.assert_allclose(jnp.where(valid[..., None, None], got_o, 0),
                               jnp.where(valid[..., None, None], want_o, 0), **tol)
    live = np.flatnonzero(np.asarray(rows))
    np.testing.assert_allclose(got_pool[1, rows[live]], want_rows[live], **tol)
    others = jnp.asarray(sorted(set(range(1, 7)) - set(np.asarray(rows).tolist())))
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[1, others], pool[1, others])
    if case == "split_prompt":
        half, p = S // 2, pool
        outs = []
        for lo in (0, half):
            part = slice(lo, lo + half)
            o, p = gated_delta.gated_delta_pallas(
                q[:, part], k[:, part], v[:, part], g[:, part], beta[:, part], p,
                rows, jnp.full((B,), half), fresh, layer=1, interpret=True)
            outs.append(o)
        np.testing.assert_allclose(jnp.concatenate(outs, axis=1), got_o, **tol)
        np.testing.assert_allclose(p, got_pool, **tol)


@pytest.mark.parametrize("S, H, dk, dv, group", [
    (256, 30, 96, 192, 2), (512, 30, 96, 192, 2),       # the cell's buckets
    (128, 4, 16, 32, 4), (64, 4, 16, 128, 1),
    (1, 30, 96, 192, None), (32, 30, 96, 192, None), (96, 30, 96, 192, None),
    (128, 3, 96, 192, None), (128, 4, 12, 128, None)])
def test_the_scalar_chunkwise_body_is_chosen_by_shape_alone(S, H, dk, dv, group):
    """Whole chunks of heads that have a lane-aligned grouping (and keys on
    whole sublane tiles) take the chunkwise body, all heads a grid step; a
    decode step, a bucket shorter than or not whole chunks and a geometry
    without a grouping stay on the token walk. The cell's four dense prefill
    programs ([1, 256], [1, 512], [2, 512], [4, 512]) are the first two."""
    assert gated_delta.scalar_chunk_group(S, H, dk, dv) == group
    assert gated_delta.chunk_body(S, H, dk, dv, channel=False) == (
        "chunkwise" if group else "walk")


def test_a_chunk_of_the_scalar_form_builds_no_token_tile():
    """The chunkwise body reads q, k, g and beta as the mixer made them (v and
    o as the state lies, as the walk does): its program has no
    [B, S, 2 d_k + 8, H] tile, the walk's still has; the kernel's name is
    the same."""
    H, dk, dv = 2, 96, 192
    q, k, v, g, beta, _ = _delta_inputs(1, 64, H, dk, dv)
    pool, one = jnp.zeros((1, 2, dk, H * dv)), jnp.ones((1,), jnp.int32)
    text = lambda S: str(jax.make_jaxpr(partial(
        gated_delta.gated_delta_pallas, layer=0, interpret=True))(
            q[:, :S], k[:, :S], v[:, :S], g[:, :S], beta[:, :S], pool, one, one, one))
    tile = f"{2 * dk + 8},{H}]"
    assert "gated_delta_chunk" in text(64) and tile not in text(64)
    assert "gated_delta_chunk" in text(32) and tile in text(32)


def test_the_engine_counts_prefills_by_the_body_their_bucket_takes(monkeypatch):
    """``EngineStats.delta_chunkwise_steps`` / ``.delta_walk_steps``: a
    prefill dispatch and a chunk round count by the family's ``delta_body``,
    a rule of the bucket's length; nothing where the ``jax.numpy`` twin runs
    (every CPU engine), so the rule is stood in for here."""
    big = dataclasses.replace(CFG, linear_n_heads=30, linear_key_dim=96,
                              linear_value_dim=192)
    mesh = _engine().mesh
    assert olmo_hybrid.delta_body(big, mesh, 512) is None           # the twin
    monkeypatch.setattr(olmo_hybrid, "on_tpu", lambda mesh: True)
    assert [olmo_hybrid.delta_body(big, mesh, S) for S in (256, 512, 16, 96)] == [
        "chunkwise", "chunkwise", "walk", "walk"]
    monkeypatch.undo()
    monkeypatch.setattr(olmo_hybrid, "delta_body", lambda c, mesh, seq: (
        gated_delta.chunk_body(seq, c.linear_n_heads, c.linear_key_dim,
                               c.linear_value_dim, False)))

    async def run():
        engine = _engine(prefill_buckets=(32, BUCKET))
        await engine.start()
        try:
            await _generate(engine, [1] + prompt_of(20, 20), 3)    # a prefill
            await _generate(engine, [1] + prompt_of(150, 150), 3)  # chunk rounds
            stats = engine.stats
            return (stats.prefill_batches, stats.delta_chunkwise_steps,
                    stats.delta_walk_steps)
        finally:
            await engine.stop()

    # the short prompt's bucket of 32 is no whole chunk (the walk); the long
    # one's rounds of 64 are, but for a last round in the shorter bucket
    batches, chunkwise, walk = asyncio.run(run())
    assert chunkwise + walk == batches and chunkwise >= 2 and walk >= 1


# ------------------------------------------------------------ pools and rows

def test_pools_declare_their_layers_and_page_bytes_are_unchanged():
    """A pool's own layer count: the GQA trunk's and the latent family's pages
    cost what they did, and the hybrid's K/V pages are reckoned over its
    full-attention layers only."""
    mistral = MODEL_CONFIGS["mistral-7b"]
    assert kv_page_bytes(mistral, 128) == 32 * 128 * 2 * 8 * 128 * 2
    assert kv_page_bytes(mistral, 128, quant="int8") == \
        32 * 128 * 2 * 8 * 128 + 2 * 32 * 8 * 2
    latent = MODEL_CONFIGS["deepseek-test"]
    assert kv_page_bytes(latent, 16) == 3 * 16 * (32 + 16 + 16) * 2
    assert state_rows_for(mistral, 32) == state_rows_for(latent, 8) == 0
    assert kv_state_bytes(mistral, 33) == 0
    pools = {p.name: p for p in kv_pools(CFG)}
    assert (pools["k"].layers, pools["state"].layers) == (2, 6)
    assert pools["state"].per == pools["conv_tail"].per == "sequence"
    assert kv_page_bytes(CFG, PAGE) == 2 * PAGE * 2 * 4 * 16 * 2
    assert state_rows_for(CFG, 32) == 33
    assert kv_state_bytes(CFG, 1) == 6 * (16 * 4 * 32 * 4 + 3 * CFG.conv_dim * 2)
    big = dataclasses.replace(
        CFG, dim=3840, n_layers=32, n_heads=30, n_kv_heads=30, head_dim=128,
        linear_n_heads=30, linear_key_dim=96, linear_value_dim=192)
    # a page holds 32 heads: 30 padded to whole sublane tiles (16.78 MB)
    assert big.kv_pool_heads == 32 and CFG.kv_pool_heads == 4
    assert kv_page_bytes(big, 128) == 8 * 128 * 2 * 32 * 128 * 2
    assert kv_state_bytes(big, 1) == 24 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)


def test_allocator_deals_a_state_row_with_the_slot():
    alloc = PageAllocator(64, PAGE, SLOTS, TABLE, state_rows=SLOTS + 1)
    assert alloc.allocate_slot(0, 20) and alloc.allocate_slot(2, 20)
    row0, row2 = alloc.slot_row(0), alloc.slot_row(2)
    assert {row0, row2} <= set(range(1, SLOTS + 1)) and row0 != row2
    assert alloc.state_row_table().tolist() == [row0, 0, row2, 0]
    alloc.free_slot(0)
    assert alloc.rows_in_use == 1
    assert alloc.allocate_slot(3, 20) and alloc.slot_row(3) == row0
    plain_alloc = PageAllocator(64, PAGE, SLOTS, TABLE)
    assert plain_alloc.allocate_slot(0, 20) and plain_alloc.slot_row(0) == 0


# ------------------------------------------------------------------ the engine

def _engine(**over):
    base = dict(model="olmo-hybrid-test", dtype="float32", max_batch=4,
                max_seq_len=256, page_size=PAGE, num_pages=80,
                prefill_buckets=(BUCKET,), prefill_max_batch=2,
                prefix_cache=False, warmup=False)
    return TPUEngine(EngineConfig(**{**base, **over}),
                     devices=jax.devices()[:1])


async def _generate(engine, prompt, n):
    return [t async for t in engine.generate(list(prompt), max_tokens=n)]


def test_engine_serves_the_family_and_compaction_keeps_the_tokens():
    """Short and chunked prompts through admission, chunk rounds, decode and
    compaction give the tokens each gives alone; rows come back."""
    assert family_of(CFG) is olmo_hybrid
    prompts = [[1] + prompt_of(n, n) for n in (20, 100, 150, 33)]

    async def run():
        engine = _engine()
        await engine.start()
        try:
            alone = [await _generate(engine, p, 10) for p in prompts]
            # a short request finishes first: the others are compacted under it
            budgets = (3, 10, 10, 10)
            together = await asyncio.gather(*[
                _generate(engine, p, n) for p, n in zip(prompts, budgets)])
            stats = engine.stats
            return alone, together, budgets, (
                engine.allocator.rows_in_use, stats.state_rows_total,
                stats.state_scanned_tokens)
        finally:
            await engine.stop()

    alone, together, budgets, (rows_left, rows_total, scanned) = asyncio.run(run())
    for a, t, n in zip(alone, together, budgets):
        assert t == a[:n]
    assert rows_left == 0 and rows_total == 4
    assert scanned >= 2 * sum(len(p) for p in prompts)


def test_a_table_sync_uploads_the_row_ids_not_the_state():
    engine = _engine()
    kv = engine.kv
    assert kv.state.shape[:2] == (6, 5) and kv.k_pages.shape[0] == 2
    for slot in (0, 3):
        assert engine.allocator.allocate_slot(slot, 40)
    rows = [engine.allocator.slot_row(slot) for slot in (0, 3)]
    engine._sync_tables()
    assert np.asarray(engine.kv.state_rows).tolist() == [rows[0], 0, 0, rows[1]]
    engine.allocator.free_slot(0)                 # its row goes to the next
    assert engine.allocator.allocate_slot(1, 40)
    engine._sync_tables()
    assert np.asarray(engine.kv.state_rows).tolist() == [0, rows[0], 0, rows[1]]
    assert engine.kv.state is kv.state            # nothing copied


@pytest.mark.parametrize("setting,words", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_decode=True), "spec_decode"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(prefix_cache=True, prefix_tiers=True), "KV tiers"),
], ids=["prefix_cache", "spec_decode", "kv_quant", "tiers"])
def test_unserved_settings_refuse_at_build(setting, words):
    with pytest.raises(NotImplementedError, match=words):
        _engine(**setting)


def test_refusals_name_the_model_axis_and_sequence_parallel():
    class Mesh:
        shape = {"model": 4}
    config = EngineConfig(model="olmo-hybrid-test", prefix_cache=False,
                          sp_impl="ring")
    why = olmo_hybrid.refusals(CFG, config, Mesh(), tiers=False)
    assert len(why) == 2 and "model axis" in why[0] and "sp_impl" in why[1]
    assert olmo_hybrid.refusals(
        CFG, EngineConfig(model="olmo-hybrid-test", prefix_cache=False),
        type("M", (), {"shape": {"model": 1}})(), tiers=False) == []
