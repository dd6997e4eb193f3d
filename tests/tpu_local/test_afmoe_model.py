"""The window / full family (window layers that keep a ring of their newest
keys beside full layers that page, a gated QK-normed attention, dense or
sigmoid-routed expert FFNs) on the CPU in float32: the program's step functions
through the cache against the plain whole-sequence reference, the ring's
discipline, the bounded kernels against the gather reference, and the engine
serving it."""

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
from mcp_context_forge_tpu.tpu_local.kv import (init_kv_state, kv_page_bytes,
                                                kv_pools, kv_state_bytes,
                                                state_rows_for)
from mcp_context_forge_tpu.tpu_local.kv.paged_cache import (ring_tables,
                                                            ring_view)
from mcp_context_forge_tpu.tpu_local.models import afmoe, family_of, llama
from mcp_context_forge_tpu.tpu_local.models.configs import (MODEL_CONFIGS,
                                                            AfmoeConfig)
from mcp_context_forge_tpu.tpu_local.ops import paged_attention as paged

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from benchmark.reference import afmoe_plain as plain  # noqa: E402

CFG = MODEL_CONFIGS["afmoe-test"]
# a window of 4 pages, a chunk of 2, a ring of 7: a prompt of 150 tokens laps
# its ring more than twice
PAGE, SLOTS, TABLE, BUCKET = 8, 4, 32, 16
W, RING = CFG.sliding_window, CFG.ring_tokens // PAGE
TOL = 1e-4
WRONG = [v for v in plain.VARIANTS if v]


@pytest.fixture(scope="module")
def params():
    return afmoe.init_params(CFG, jax.random.PRNGKey(3), jnp.float32)


def fresh_kv(slot_rows=(1, 2, 3, 4)):
    """A pool of SLOTS slots, slot s owning pages [1 + s * TABLE, ...) of the
    full layers and state row ``slot_rows[s]`` of the rings."""
    kv = init_kv_state(CFG, 1 + SLOTS * TABLE, PAGE, SLOTS, TABLE,
                       dtype=jnp.float32)
    tables = 1 + np.arange(SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    return kv._replace(block_tables=jnp.asarray(tables),
                       state_rows=jnp.asarray(slot_rows, jnp.int32))


_hist = jax.jit(partial(afmoe.prefill_with_history, config=CFG),
                static_argnames=("ctx_pages", "paged_impl"))
_dense = jax.jit(partial(afmoe.prefill, config=CFG))
_decode = jax.jit(partial(afmoe.decode_step, config=CFG),
                  static_argnames=("ctx_pages", "paged_impl"))


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


def reference(params, tokens, positions, variant=None):
    return np.asarray(plain.forward(params, CFG, tokens, positions,
                                    variant=variant)[0])


def chunked(params, kv, prompt, slot, ctx_pages=TABLE, **how):
    """``prompt`` through chunk rounds of BUCKET beside an all-padding row.
    -> (the last round's logits of the real row [tokens, V], kv)."""
    for start in range(0, len(prompt), BUCKET):
        end = min(start + BUCKET, len(prompt))
        tokens, positions = pack([(prompt, start, end), (prompt, 0, 0)])
        logits, kv, _ = _hist(params, tokens=tokens, positions=positions,
                              kv=kv, slot_ids=jnp.asarray([slot, 0]),
                              ctx_pages=ctx_pages, **how)
    return np.asarray(logits[0, :end - start]), kv, (start, end)


# ------------------------------------------------ program against reference

def test_dense_prefill_inside_the_window_matches_reference(params):
    prompts = [prompt_of(n, n) for n in (16, 11, 3)]
    tokens, positions = pack([(p, 0, len(p)) for p in prompts])
    logits, kv, aux = _dense(params, tokens=tokens, positions=positions,
                             kv=fresh_kv(), slot_ids=jnp.arange(3))
    for i, p in enumerate(prompts):
        np.testing.assert_allclose(np.asarray(logits[i, :len(p)]),
                                   reference(params, p, list(range(len(p)))),
                                   atol=TOL, rtol=TOL)
    tokens = 16 + 11 + 3
    expert_layers = CFG.n_layers - CFG.n_dense_layers
    # expert tokens, pairs, summed visible share (all inside the window),
    # tokens, live rows, 0, the rows' context and what a window layer sees
    np.testing.assert_allclose(
        np.asarray(aux), [tokens * expert_layers, tokens * expert_layers * 2,
                          tokens, tokens, 3, 0, tokens, tokens])


def test_a_dense_prefill_longer_than_the_window_is_refused(params):
    tokens, positions = pack([(prompt_of(40, 1), 0, 40)], width=W + 8)
    with pytest.raises(ValueError, match="longer than the window"):
        afmoe.prefill(params, CFG, tokens, positions, fresh_kv(),
                      jnp.arange(1))


@pytest.mark.parametrize("length", [40, 150], ids=["crosses_window",
                                                   "laps_ring_twice"])
def test_chunk_rounds_across_a_wrapped_ring_match_reference(params, length):
    prompt = prompt_of(length, length)
    assert length > W and (length <= CFG.ring_tokens or length > 2 * CFG.ring_tokens)
    got, _, (start, end) = chunked(params, fresh_kv(), prompt, slot=2)
    np.testing.assert_allclose(
        got, reference(params, prompt, list(range(start, end))),
        atol=TOL, rtol=TOL)


def test_decode_past_the_wrap_beside_a_row_inside_its_window(params):
    """Three rows of one decode batch: one far past the wrap (150 tokens), one
    just past the window (40) and one still inside it (10, from a dense
    prefill), and an idle row; 12 steps, which carry the long row across a
    page of its ring."""
    prompts = {2: prompt_of(150, 5), 0: prompt_of(40, 6), 3: prompt_of(10, 7)}
    kv = fresh_kv()
    for slot in (2, 0):
        _, kv, _ = chunked(params, kv, prompts[slot], slot)
    tokens, positions = pack([(prompts[3], 0, 10)])
    _, kv, _ = _dense(params, tokens=tokens, positions=positions, kv=kv,
                      slot_ids=jnp.asarray([3]))
    order = [2, 0, 3]
    forced = {slot: prompt_of(12, 20 + slot) for slot in order}
    # the reference is causal: one pass over a row's whole sequence gives the
    # logits of every step's query
    want = {s: reference(params, prompts[s] + forced[s],
                         [len(prompts[s]) + j for j in range(12)])
            for s in order}
    for j in range(12):
        lens = [len(prompts[s]) + j + 1 for s in order]
        step, kv, aux = _decode(
            params, tokens=jnp.asarray([forced[s][j] for s in order] + [0]),
            positions=jnp.asarray([n - 1 for n in lens] + [0]), kv=kv,
            slot_ids=jnp.asarray(order + [1]), seq_lens=jnp.asarray(lens + [0]),
            write_mask=jnp.asarray([True, True, True, False]),
            ctx_pages=TABLE)
        for i, s in enumerate(order):
            np.testing.assert_allclose(np.asarray(step[i]), want[s][j],
                                       atol=TOL, rtol=TOL)
    # the last step's counts: context summed, and min(context, W) a row
    assert np.asarray(aux)[-2:].tolist() == [sum(lens),
                                             sum(min(n, W) for n in lens)]


@pytest.mark.parametrize("variant", WRONG)
def test_each_named_wrong_program_is_told_apart(params, variant):
    """The comparison that passes at 1e-4 fails by orders of magnitude against
    window layers that see everything, rotated full layers, a missing output
    gate and int8 activations: on a chunked prompt past the window."""
    prompt = prompt_of(72, 72)
    got, _, (start, end) = chunked(params, fresh_kv(), prompt, slot=1)
    at = list(range(start, end))
    right = np.abs(got - reference(params, prompt, at)).max()
    wrong = np.abs(got - reference(params, prompt, at, variant)).max()
    assert right < TOL and wrong > 100 * TOL, (variant, right, wrong)


def test_a_slot_reused_by_a_short_sequence_sees_nothing_of_the_last(params):
    """Dead by position: a short sequence in the row (and the pages) a long
    one left reads what a fresh pool gives it, through chunk rounds and
    decode."""
    _, used, _ = chunked(params, fresh_kv(), prompt_of(150, 1), slot=2)
    short, forced = prompt_of(21, 2), prompt_of(3, 3)
    outs = []
    for kv in (used, fresh_kv()):
        got, kv, _ = chunked(params, kv, short, slot=2)
        rows = [got[-1]]
        for j, token in enumerate(forced):
            at = len(short) + j
            step, kv, _ = _decode(
                params, tokens=jnp.asarray([token]),
                positions=jnp.asarray([at]), kv=kv, slot_ids=jnp.asarray([2]),
                seq_lens=jnp.asarray([at + 1]), ctx_pages=TABLE)
            rows.append(np.asarray(step[0]))
        outs.append(np.stack(rows))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_allclose(
        outs[0], reference(params, short + forced,
                           list(range(len(short) - 1, len(short) + 3))),
        atol=TOL, rtol=TOL)


# ----------------------------------------------- the ring and what it costs

def test_a_window_layer_keeps_a_ring_and_a_full_layer_every_page(params):
    """After 150 tokens the full layers' pages hold all 150 and a window
    layer's ring the newest 56 (7 pages), position p in ring page (p // page)
    mod 7 of the row; other rows' rings are untouched."""
    prompt = prompt_of(150, 9)
    _, kv, _ = chunked(params, fresh_kv(), prompt, slot=2)
    assert kv.k_pages.shape[:2] == (2, 1 + SLOTS * TABLE)
    assert kv.state.shape == (6, (SLOTS + 1) * RING + 1, PAGE, 2, 16)
    row = int(kv.state_rows[2])
    mine = np.asarray(kv.state[:, 1 + row * RING:1 + (row + 1) * RING])
    assert np.abs(mine).sum(axis=(0, 3, 4)).all()       # every entry written
    others = np.delete(np.asarray(kv.state), np.s_[1 + row * RING:
                                                   1 + (row + 1) * RING], axis=1)
    assert not others[:, 1:].any()                       # but the trash page
    view = ring_view(kv, RING)
    # the newest page (tokens 144..149) sits where the table sends page 18
    assert int(view.block_tables[2, 18]) == 1 + row * RING + 18 % RING
    newest = np.asarray(kv.state[0, 1 + row * RING + 18 % RING])
    older = np.asarray(kv.state[0, 1 + row * RING + 17 % RING])
    assert newest[:6].any() and older.all()
    # the full layers hold 150 tokens in 19 pages of the slot
    held = np.asarray(kv.k_pages[0, 1 + 2 * TABLE:1 + 3 * TABLE])
    assert np.abs(held).sum(axis=(2, 3)).astype(bool).sum() == 150


def test_ring_tables_send_logical_pages_round_the_row():
    rows, first = jnp.asarray([2, 0]), jnp.asarray([5, 0])
    got = np.asarray(ring_tables(7, rows, first, 8))
    assert got[0].tolist() == [1 + 14 + n % 7 for n in range(5, 13)]
    assert got[1].tolist() == [1 + n % 7 for n in range(8)]      # the trash row


def test_pools_and_their_bytes():
    """Full layers' K/V a page id, window layers' rings a sequence: by the
    layers that hold each; and the benchmark configuration's figures."""
    pools = {pool.name: pool for pool in kv_pools(CFG)}
    assert [pools[n].layers for n in ("k", "v", "window_k", "window_v")] == \
        [2, 2, 6, 6]
    assert pools["k"].per == "token" and pools["window_k"].per == "sequence"
    assert pools["window_k"].shape == (CFG.ring_tokens, 2, 16)
    assert state_rows_for(CFG, SLOTS) == SLOTS + 1
    assert kv_page_bytes(CFG, PAGE, jnp.float32) == 2 * 2 * PAGE * 2 * 16 * 4
    assert kv_state_bytes(CFG, 1, jnp.float32) == 6 * 2 * 56 * 2 * 16 * 4
    cell = AfmoeConfig(name="cell", vocab_size=200192, dim=2048, n_layers=8,
                       n_heads=32, n_kv_heads=4, head_dim=128, ffn_hidden=6144,
                       moe_ffn_hidden=1024, n_experts=128, moe_top_k=8,
                       sliding_window=2048, n_dense_layers=1)
    assert cell.ring_tokens == 25 * 128
    assert kv_page_bytes(cell, 128) == 2 * 262_144
    assert kv_state_bytes(cell, 1) == 6 * 25 * 262_144          # 39.3 MB
    assert afmoe.param_count(cell) == 6_758_929_280
    with pytest.raises(NotImplementedError, match="kv_quant"):
        kv_page_bytes(CFG, PAGE, jnp.float32, "int8")


def test_layer_kinds_name_mixer_and_ffn():
    kinds = [afmoe.layer_kind(CFG, i) for i in range(CFG.n_layers)]
    assert kinds == ["window.dense", "window.experts", "window.experts",
                     "full.experts"] + ["window.experts"] * 3 + ["full.experts"]
    assert family_of(CFG) is afmoe and afmoe.STEP_KIND == "token"
    tree = afmoe.params_logical(CFG)["layers"]
    assert "router" not in tree[0] and tree[1]["router"] == "replicated"
    assert tree[1]["wg"] == "attn_qkv" and tree[1]["shared_w2"] == "ffn_down"


# ------------------------------------------------ the kernels with the bound

def _random_rings(seed, rows=3, kv_heads=2, hd=128):
    key = jax.random.PRNGKey(seed)
    shape = (2, rows * RING + 1, PAGE, kv_heads, hd)
    k, v = (jax.random.normal(k_, shape, jnp.float32)
            for k_ in jax.random.split(key))
    return k, v


@pytest.mark.parametrize("contexts", [(150, 40, 10), (56, 33, 0)],
                         ids=["past_in_inside", "ring_full_edge_idle"])
def test_bounded_decode_kernel_matches_the_gather_reference(contexts):
    """The kernel in interpret mode under ``window`` against the gather
    reference with the same bound, over a ring read as the step program reads
    it (tables from the first page a window touches, relative lengths)."""
    class Geo:
        n_heads, n_kv_heads, head_dim = 8, 2, 128
    k, v = _random_rings(0)
    lens = jnp.asarray(contexts, jnp.int32)
    first = jnp.maximum(lens - W, 0) // PAGE
    tables = ring_tables(RING, jnp.asarray([1, 2, 0]), first, 8)
    relative = jnp.maximum(lens - first * PAGE, 0)
    q = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 4, 128), jnp.float32)
    got = paged.paged_decode_attention_pallas(
        q, k, v, tables, relative, layer=1, interpret=True, window=W)
    keys, values = (pool[1][tables].reshape(3, -1, 2, 128) for pool in (k, v))
    want = llama._paged_decode_attention(q.reshape(3, 8, 128), keys, values,
                                         relative, Geo, window=W)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got).reshape(3, 8, 128)[live],
                               np.asarray(want)[live, 0], atol=2e-5, rtol=2e-5)
    # and it differs from the unbounded walk wherever the first page a window
    # touches still holds entries older than the window
    free = paged.paged_decode_attention_pallas(
        q, k, v, tables, relative, layer=1, interpret=True)
    moved = np.abs(np.asarray(free) - np.asarray(got)).max(axis=(1, 2, 3))
    assert [bool(m > 1e-3) for m in moved] == (np.asarray(relative) > W).tolist()
    assert (np.asarray(relative) > W).any() or max(contexts) <= CFG.ring_tokens


def test_bounded_chunk_kernel_matches_the_gather_reference():
    """A chunk round's queries (16 a row, one row padded short, one all
    padding) over wrapped rings."""
    class Geo:
        n_heads, n_kv_heads, head_dim = 8, 2, 128
    k, v = _random_rings(2)
    starts, counts = [144, 32, 0], [16, 9, 0]
    positions = np.full((3, 16), -1, np.int32)
    for i, (s, n) in enumerate(zip(starts, counts)):
        positions[i, :n] = np.arange(s, s + n)
    valid = positions >= 0
    lowest = np.where(valid.any(1), np.where(valid, positions, 1 << 30).min(1), 0)
    first = np.maximum(lowest - (W - 1), 0) // PAGE
    tables = ring_tables(RING, jnp.asarray([1, 2, 0]), jnp.asarray(first), 8)
    relative = jnp.asarray(np.where(valid, positions - first[:, None] * PAGE, -1))
    q = jax.random.normal(jax.random.PRNGKey(3), (3, 16, 2, 4, 128), jnp.float32)
    got = paged.paged_chunk_attention_pallas(
        q, k, v, tables, relative, layer=0, interpret=True, window=W)
    keys, values = (pool[0][tables].reshape(3, -1, 2, 128) for pool in (k, v))
    want = llama._history_attention(
        q.reshape(3, 16, 8, 128), keys, values, jnp.maximum(relative, 0),
        jnp.asarray(valid), Geo, window=W)
    np.testing.assert_allclose(
        np.asarray(got).reshape(3, 16, 8, 128)[valid], np.asarray(want)[valid],
        atol=2e-5, rtol=2e-5)


def test_the_bounded_kernel_walks_no_page_wholly_behind_the_window():
    """What a slot fetches (``_block_entries``): a decode row at context 16050
    under a window of 2048 reaches 17 pages of its 132, not 126; without the
    bound the walk is the parent's."""
    table = jnp.arange(1, 133, dtype=jnp.int32)[None]
    context = 16_050
    pos = jnp.asarray([[context - 1]], jnp.int32)
    free = np.asarray(paged._block_entries(table, pos, 4, 128))
    bound = np.asarray(paged._block_entries(table, pos, 4, 128, pos, 2048))
    assert len(set(free[0].tolist())) == 126
    # 17 pages hold a key of the window; a dead slot before the first live
    # page keeps what the call's FIRST grid step fetched (one block of 4, once
    # a call: a later row's dead slots keep the row before's last pages)
    first, last = (context - 2048) // 128, (context - 1) // 128
    assert last - first + 1 == 17
    fetched = set(bound[0].tolist())
    assert set(range(1 + first, 2 + last)) <= fetched
    assert fetched - set(range(1 + first, 2 + last)) == {1, 2, 3, 4}


def test_programs_without_a_window_are_the_ones_they_were():
    """``window=None`` (every accepted family's call) traces what a call that
    names no window traces: no lower bound anywhere in the kernel."""
    k, v = _random_rings(4, rows=1)
    q = jnp.zeros((1, 2, 4, 128), jnp.float32)
    tables, lens = jnp.ones((1, 8), jnp.int32), jnp.asarray([40], jnp.int32)
    trace = lambda **kw: str(jax.make_jaxpr(partial(
        paged.paged_decode_attention_pallas, layer=0, **kw))(
            q, k, v, tables, lens))
    assert trace() == trace(window=None) != trace(window=W)
    assert " gt " not in trace() and " gt " in trace(window=W)


# ------------------------------------------------------ the expert formulations

def test_the_sigmoid_router_feeds_both_formulations_alike(params):
    """``routed_experts``: the same ids and weights through the row-block
    plan and through the scan give the same sum; padding gets no row in the
    grouped path."""
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (64, CFG.dim), jnp.float32)
    ids, weights, _ = afmoe.route(layer, CFG, x)
    stacks = {k: layer[k] for k in ("w1", "w3", "w2")}
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    assert llama.expert_path(CFG, mesh, 64, jnp.float32) == "grouped"
    valid = jnp.arange(64) < 50
    grouped = llama.routed_experts(stacks, CFG, x, ids, weights, mesh, valid)
    scan = llama.routed_experts(
        stacks, dataclasses.replace(CFG, moe_impl="dense"), x, ids, weights,
        mesh)
    np.testing.assert_allclose(np.asarray(grouped[:50]), np.asarray(scan[:50]),
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(grouped[50:]).any()
    # the weights are the normalised, scaled sigmoid scores of the chosen
    np.testing.assert_allclose(np.asarray(weights.sum(-1)),
                               CFG.routed_scaling_factor, rtol=1e-5)


def test_the_softmax_router_still_goes_through_the_same_cut():
    """``_ffn_block``'s grouped branch (Mixtral, block diffusion) is
    ``moe_ffn_grouped``'s function, now by way of ``routed_experts``."""
    from mcp_context_forge_tpu.tpu_local.ops.grouped_moe import moe_ffn_grouped
    from mcp_context_forge_tpu.tpu_local.parallel.moe import MoEConfig

    cfg = dataclasses.replace(MODEL_CONFIGS["mixtral-test"], moe_impl="grouped",
                              moe_block=8)
    layer = llama.init_layer(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.dim), jnp.float32)
    valid = jnp.arange(64).reshape(2, 32) % 5 > 0
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    assert llama.expert_path(cfg, mesh, 64, jnp.float32) == "grouped"
    got = llama._ffn_block(layer, cfg, x, mesh, valid)
    want = moe_ffn_grouped(
        {k: layer[k] for k in ("router", "w1", "w3", "w2")}, x,
        MoEConfig(dim=cfg.dim, n_experts=4, expert_hidden=cfg.ffn_hidden,
                  top_k=2), block=llama.expert_block(cfg, 64, jnp.float32),
        valid=valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# the benchmark cell's expert shape (trinity-mini-d8: 128 x top-8, the kernel,
# moe_block 128); the widths do not enter the rule
CELL = dataclasses.replace(CFG, n_experts=128, moe_top_k=8, moe_block=128,
                           moe_impl="grouped_pallas")


def _mesh(model: int = 1):
    return jax.sharding.Mesh(np.array(jax.devices()[:model]).reshape(1, model),
                             ("data", "model"))


@pytest.mark.parametrize("tokens, block", [
    (1, 16),      # the logits check's one-row decode: 8 passes against 128
    (8, 16),
    (16, 16),
    (32, 16),     # the cell's decode step: at most 144 passes against 2 x 128
    (64, 16),
    (512, 32),    # the half-length dense prefill
    (1024, 64),   # a [1, 1024] chunk round
    (2048, 128),  # a [2, 1024] chunk round: wide, the trunk's own answer
])
def test_family_rule_table(tokens, block):
    """``afmoe.expert_path`` over the cell's shape: every step is grouped
    where one device holds the stacks, at the row-block the trunk's
    ``expert_block`` gives it; a ``model`` axis of two, a caller that names
    no mesh and ``moe_impl="dense"`` keep the scan. The trunk's rule scans
    under 86 tokens of this shape (a quarter of the scan's rows), and is the
    family's answer from there up."""
    assert afmoe.expert_block is llama.expert_block
    assert afmoe.expert_block(CELL, tokens) == block
    assert afmoe.expert_path(CELL, _mesh(), tokens) == "grouped"
    assert afmoe.expert_path(CELL, _mesh(), tokens, jnp.float32) == "grouped"
    assert afmoe.expert_path(CELL, _mesh(2), tokens) == "scan"
    assert afmoe.expert_path(CELL, None, tokens) == "scan"
    assert afmoe.expert_path(dataclasses.replace(CELL, moe_impl="dense"),
                             _mesh(), tokens) == "scan"
    assert llama.expert_path(CELL, _mesh(), tokens) == (
        "grouped" if tokens >= 86 else "scan")
    # the plan through XLA runs on any mesh that is named
    xla = dataclasses.replace(CELL, moe_impl="grouped")
    assert afmoe.expert_path(xla, _mesh(2), tokens) == "grouped"


def test_decode_step_of_32_rows_grouped_matches_the_scan(params):
    """``decode_step`` at [32, 1] with 11 idle rows, contexts inside and past
    the window over random pools: the family's rule traces the row-block plan
    (the idle rows get no row of it), ``moe_impl="dense"`` the scan; the live
    rows' logits agree and the pools are written alike."""
    rows, table = 32, 16
    live = np.arange(rows) % 3 != 1                     # 21 live, 11 idle
    assert live.sum() == 21
    rng = np.random.default_rng(11)
    lens = np.where(live, rng.integers(1, table * PAGE, rows), 0).astype(np.int32)
    assert (lens[live] > W).any() and (lens[live] <= W).any()
    kv = init_kv_state(CFG, 1 + rows * table, PAGE, rows, table,
                       dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    kv = kv._replace(
        **{name: jax.random.normal(key, getattr(kv, name).shape, jnp.float32)
           for name, key in zip(("k_pages", "v_pages", "state", "conv_tail"),
                                keys)},
        block_tables=jnp.asarray(
            1 + np.arange(rows * table, dtype=np.int32).reshape(rows, table)),
        state_rows=jnp.arange(1, rows + 1, dtype=jnp.int32))
    # the test configuration with the row-block limit that makes 32 rows a
    # NARROW step (64 pairs under 8 x 16), as the cell's 32 rows are: the
    # trunk's rule scans it, the family's groups it at blocks of 8
    narrow, mesh = dataclasses.replace(CFG, moe_block=16), _mesh()
    assert afmoe.expert_path(narrow, mesh, rows, jnp.float32) == "grouped"
    assert llama.expert_path(narrow, mesh, rows, jnp.float32) == "scan"
    assert llama.expert_block(narrow, rows, jnp.float32) == 8
    call = dict(tokens=jnp.asarray(rng.integers(32, 127, rows), jnp.int32),
                positions=jnp.asarray(np.maximum(lens - 1, 0)), kv=kv,
                slot_ids=jnp.arange(rows), seq_lens=jnp.asarray(lens),
                write_mask=jnp.asarray(live))
    outs = {}
    for impl in ("grouped", "dense"):
        config = dataclasses.replace(narrow, moe_impl=impl)
        step = jax.jit(partial(afmoe.decode_step, config=config, mesh=mesh,
                               ctx_pages=table))
        text = str(jax.make_jaxpr(step)(params, **call))
        # the plan sorts the pairs by expert, the scan sorts nothing
        assert (" sort[" in text) == (impl == "grouped")
        outs[impl] = step(params, **call)
    (got, got_kv, got_aux), (want, want_kv, want_aux) = (outs["grouped"],
                                                         outs["dense"])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=TOL, rtol=TOL)
    for name in ("k_pages", "v_pages", "state", "conv_tail"):
        # but the trash page, where the idle rows' entries land
        np.testing.assert_allclose(np.asarray(getattr(got_kv, name))[:, 1:],
                                   np.asarray(getattr(want_kv, name))[:, 1:],
                                   atol=TOL, rtol=TOL)
        assert not np.array_equal(np.asarray(getattr(got_kv, name)),
                                  np.asarray(getattr(kv, name)))
    np.testing.assert_array_equal(np.asarray(got_aux), np.asarray(want_aux))


# ------------------------------------------------------------------ the engine

def _engine(**over):
    base = dict(model="afmoe-test", dtype="float32", max_batch=4,
                max_seq_len=256, page_size=PAGE, num_pages=128,
                prefill_buckets=(BUCKET,), prefill_max_batch=2,
                prefix_cache=False, warmup=False)
    return TPUEngine(EngineConfig(**{**base, **over}),
                     devices=jax.devices()[:1])


async def _generate(engine, prompt, n):
    return [t async for t in engine.generate(list(prompt), max_tokens=n)]


def test_engine_serves_the_family_end_to_end():
    """Dense and chunked prompts through admission, chunk rounds, decode and
    compaction: alone and together the same greedy tokens, each the plain
    reference's argmax; the counts of keys seen and kept; rows come back."""
    prompts = [prompt_of(n, 30 + n) for n in (150, 10, 70, 33)]

    async def run():
        engine = _engine()
        await engine.start()
        try:
            alone = [await _generate(engine, p, 10) for p in prompts]
            budgets = (3, 10, 10, 10)
            together = await asyncio.gather(*[
                _generate(engine, p, n) for p, n in zip(prompts, budgets)])
            return engine, alone, together, budgets
        finally:
            await engine.stop()

    engine, alone, together, budgets = asyncio.run(run())
    for a, t, n in zip(alone, together, budgets):
        assert t == a[:n]
    # the reference is causal: one pass over prompt and tokens gives the
    # logits each token was the argmax of
    for prompt, tokens in zip(prompts, alone):
        want = reference(engine.params, list(prompt) + tokens[:-1],
                         [len(prompt) - 1 + j for j in range(len(tokens))])
        assert want.argmax(axis=-1).tolist() == tokens
    stats = engine.stats
    assert engine.allocator.rows_in_use == 0 and stats.state_rows_total == 4
    assert 0 < stats.window_keys < stats.context_keys
    # by the family's rule every step on one device is grouped
    assert stats.moe_scan_steps == 0 < stats.moe_grouped_steps
    # the admission unit counts the full layers' pools, the row the rings'
    assert engine._kv_page_bytes == kv_page_bytes(CFG, PAGE, jnp.float32)
    assert engine._state_row_bytes == kv_state_bytes(CFG, 1, jnp.float32)
    assert engine.window_pool_bytes() == 5 * engine._state_row_bytes
    assert engine._window_attrs(100) == {
        "llm.window_layers": 6, "llm.full_layers": 2, "llm.window_tokens": W}
    assert engine._window_attrs(20)["llm.window_tokens"] == 20


def test_decode_dispatches_count_as_grouped_and_give_the_scans_tokens(
        model_variant):
    """A few greedy requests on one device: every decode dispatch counts as a
    grouped step and none as a scan (``engine._count_expert_path`` by the
    family's rule, the rule the step programs trace by); the tokens are those
    of the same engine on the scan (``moe_impl="dense"``)."""
    prompts = [prompt_of(n, 80 + n) for n in (70, 12, 33)]

    async def run(**over):
        engine = _engine(**over)
        await engine.start()
        try:
            tokens = await asyncio.gather(*[_generate(engine, p, 8)
                                            for p in prompts])
            return engine.stats, tokens
        finally:
            await engine.stop()

    stats, tokens = asyncio.run(run())
    scan_stats, scan_tokens = asyncio.run(run(
        model=model_variant("afmoe-test", moe_impl="dense")))
    assert tokens == scan_tokens and all(len(t) == 8 for t in tokens)
    assert stats.decode_steps >= 7
    # dense prefills and chunk rounds are prefill batches: grouped before too
    assert (stats.moe_grouped_steps, stats.moe_scan_steps) == (
        stats.decode_steps + stats.prefill_batches, 0)
    assert scan_stats.moe_grouped_steps == 0
    assert scan_stats.moe_scan_steps >= scan_stats.decode_steps >= 7


def test_an_engine_of_another_family_has_no_window_attributes():
    engine = TPUEngine(EngineConfig(model="llama3-test", dtype="float32",
                                    max_batch=2, max_seq_len=64, page_size=8,
                                    num_pages=32, warmup=False),
                       devices=jax.devices()[:1])
    assert engine._window_attrs(100) == {} and engine.window_pool_bytes() == 0


@pytest.mark.parametrize("setting,words", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_decode=True), "spec_decode"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(prefix_cache=True, prefix_tiers=True), "KV tiers"),
    (dict(prefill_buckets=(64,)), "longer than the window"),
    (dict(page_size=16), "ring_slack"),
], ids=["prefix_cache", "spec_decode", "kv_quant", "tiers", "bucket_past_window",
        "ring_too_small"])
def test_unserved_settings_refuse_at_build(setting, words):
    with pytest.raises(NotImplementedError, match=words):
        _engine(**setting)


def test_refusals_name_the_model_axis_and_sequence_parallel():
    class Mesh:
        shape = {"model": 4}
    config = EngineConfig(model="afmoe-test", prefix_cache=False, page_size=PAGE,
                          prefill_buckets=(BUCKET,), sp_impl="ring")
    why = afmoe.refusals(CFG, config, Mesh(), tiers=False)
    assert len(why) == 2 and "model axis" in why[0] and "sp_impl" in why[1]
    assert afmoe.refusals(
        CFG, EngineConfig(model="afmoe-test", prefix_cache=False,
                          page_size=PAGE, prefill_buckets=(BUCKET,)),
        type("One", (), {"shape": {"model": 1}})(), tiers=False) == []


def test_superstep_and_overlap_twin_serve_the_family():
    """A token step: super-steps and the device-fed overlap twin give the
    tokens the plain engine gives."""
    prompts = [prompt_of(n, 60 + n) for n in (70, 12)]

    async def run(**over):
        engine = _engine(**over)
        await engine.start()
        try:
            return await asyncio.gather(*[_generate(engine, p, 9)
                                          for p in prompts])
        finally:
            await engine.stop()

    plain_tokens = asyncio.run(run(decode_overlap=False))
    assert asyncio.run(run(decode_overlap=True)) == plain_tokens
    assert asyncio.run(run(superstep=4, decode_overlap=False)) == plain_tokens
