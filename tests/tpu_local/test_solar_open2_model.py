"""The KDA / gated-GQA expert family (``solar_open2``) on the CPU in float32:
the program's step functions through the cache against the plain
token-by-token reference, the channel-decay kernel against the recurrence, a
held range of experts against the uncut layer, the engine serving it, and the
other families' step programs held to what they were before it."""

import asyncio
import dataclasses
import hashlib
import math
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
from mcp_context_forge_tpu.tpu_local.models import (family_of, llama,
                                                    solar_open2)
from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
from mcp_context_forge_tpu.tpu_local.ops import gated_delta
from mcp_context_forge_tpu.tpu_local.quantize import quantize_tree
from mcp_context_forge_tpu.tpu_local.sampling import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from benchmark.reference import solar_open2_plain as plain  # noqa: E402

CFG = MODEL_CONFIGS["solar-open2-test"]
PAGE, SLOTS, TABLE, BUCKET = 16, 4, 16, 64
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return solar_open2.init_params(CFG, jax.random.PRNGKey(3), jnp.float32)


def fresh_kv(slot_rows=(1, 2, 3, 4)):
    """A pool of SLOTS slots, slot s owning pages [1 + s * TABLE, ...) and
    state row ``slot_rows[s]``."""
    kv = init_kv_state(CFG, 1 + SLOTS * TABLE, PAGE, SLOTS, TABLE,
                       dtype=jnp.float32)
    tables = 1 + np.arange(SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    return kv._replace(block_tables=jnp.asarray(tables),
                       state_rows=jnp.asarray(slot_rows, jnp.int32))


_hist = jax.jit(partial(solar_open2.prefill_with_history, config=CFG),
                static_argnames=("ctx_pages",))
_dense = jax.jit(partial(solar_open2.prefill, config=CFG))
_decode = jax.jit(partial(solar_open2.decode_step, config=CFG))


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
    return np.asarray(plain.forward(params, CFG, tokens, positions, variant)[0])


# ------------------------------------------------- program against reference

def test_dense_prefill_of_unequal_rows_matches_reference(params):
    prompts = [prompt_of(n, n) for n in (64, 37, 5)]
    tokens, positions = pack([(p, 0, len(p)) for p in prompts])
    logits, kv, aux = _dense(params, tokens=tokens, positions=positions,
                             kv=fresh_kv(), slot_ids=jnp.arange(3))
    for i, p in enumerate(prompts):
        want = reference(params, p, list(range(len(p))))
        np.testing.assert_allclose(np.asarray(logits[i, :len(p)]), want,
                                   atol=TOL, rtol=TOL)
    # tokens through the 8 expert layers, pairs on the 4 of 16 held (a quarter
    # of top-4 under a uniform router), 0, rows, live state rows, tokens scanned
    real = 64 + 37 + 5
    moe_tokens, pairs, zero, rows, live, scanned = np.asarray(aux).tolist()
    assert (moe_tokens, zero, rows, live, scanned) == (8 * real, 0, 3, 3, real)
    assert 0.5 * 8 * real < pairs < 2 * 8 * real


def test_chunk_rounds_carry_the_state_then_decode_reads_it(params):
    """A prompt of three chunk rounds with a padded last chunk, beside a row
    that is all padding; then decode through the pages and the state row."""
    length = 150
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
        step, kv, aux = _decode(
            params, tokens=jnp.asarray([token, 0]),
            positions=jnp.asarray([at, 0]), kv=kv,
            slot_ids=jnp.asarray([2, 1]), seq_lens=jnp.asarray([at + 1, 0]),
            write_mask=jnp.asarray([True, False]))
        rows.append(np.asarray(step[0]))
    want = reference(params, prompt + forced,
                     list(range(length - 1, length + len(forced))))
    np.testing.assert_allclose(np.stack(rows), want, atol=TOL, rtol=TOL)
    assert np.asarray(aux)[[0, 3, 4, 5]].tolist() == [8, 1, 1, 1]
    # slot 0's row (all padding) and slot 1's (idle decode row) were never written
    assert not np.asarray(kv.state[:, 1]).any()
    assert not np.asarray(kv.state[:, 2]).any()
    assert np.asarray(kv.state[:, 3]).any()


def test_a_rows_next_tenant_starts_from_zero(params):
    """A row whose first position is 0 reads nothing of what its last tenant
    left in the state row, the tail or the pages."""
    first, second = prompt_of(40, 1), prompt_of(33, 2)
    kv = fresh_kv()
    for prompt in (first, second):
        tokens, positions = pack([(prompt, 0, len(prompt))])
        logits, kv, _ = _dense(params, tokens=tokens, positions=positions,
                               kv=kv, slot_ids=jnp.asarray([1]))
    np.testing.assert_allclose(
        np.asarray(logits[0, :33]), reference(params, second, list(range(33))),
        atol=TOL, rtol=TOL)


def test_int8_weights_agree_with_their_dequantised_twin(params):
    quant = quantize_tree(params, solar_open2.params_logical(CFG),
                          scale_dtype=jnp.float32)
    kda, gqa = quant["layers"][1], quant["layers"][0]
    for name in ("wq", "wo", "w1", "w2", "shared_w1"):
        assert isinstance(kda[name], dict) and isinstance(gqa[name], dict)
    assert isinstance(gqa["wg"], dict)
    for name in ("wf_down", "wf_up", "wg_down", "wg_up", "wb", "conv",
                 "router", "A_log", "dt_bias"):
        assert not isinstance(kda[name], dict)
    prompt = prompt_of(48, 5)
    tokens, positions = pack([(prompt, 0, 48)])
    logits, _, _ = _dense(quant, tokens=tokens, positions=positions,
                          kv=fresh_kv(), slot_ids=jnp.arange(1))
    np.testing.assert_allclose(np.asarray(logits[0, :48]),
                               reference(quant, prompt, list(range(48))),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("variant", [v for v in plain.VARIANTS if v],
                         ids=lambda v: v)
def test_the_references_named_wrong_programs_are_other_functions(params, variant):
    """Each variant the chip's tolerance is set against moves the float32
    logits far beyond what the program is held to here."""
    prompt = prompt_of(96, 11)
    at = list(range(40, 96))
    moved = np.abs(reference(params, prompt, at, variant)
                   - reference(params, prompt, at)).max()
    assert moved > 50 * TOL
    with pytest.raises(ValueError, match="variant"):
        plain.forward(params, CFG, prompt, at, variant="no_such_program")


# --------------------------------------------------- the channel-decay kernel

def _delta_inputs(B, S, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -4.0 * jax.random.uniform(ks[3], (B, S, H, dk)) ** 3     # a decay a channel
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, S, H)))
    state = jax.random.normal(ks[5], (B, H, dk, dv))
    return q, k, v, g, beta, state


def test_a_channels_decay_is_that_rows_decay():
    """The definition: with every channel of a head at the head's value the
    channel form is the scalar form; with channels apart, row i of the state
    decays by its own alpha_i."""
    q, k, v, g, beta, state = _delta_inputs(2, 9, 3, 8, 16)
    same = jnp.broadcast_to(g[..., :1], g.shape)
    want = gated_delta.gated_delta_recurrence(q, k, v, same[..., 0], beta, state)
    got = gated_delta.gated_delta_recurrence(q, k, v, same, beta, state)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    one = gated_delta.gated_delta_recurrence(
        q[:, :1], k[:, :1], v[:, :1], g[:, :1], jnp.zeros_like(beta[:, :1]), state)[1]
    np.testing.assert_allclose(one, state * jnp.exp(g[:, 0])[..., None],
                               atol=1e-6, rtol=1e-6)


def test_the_chunked_twin_refuses_a_channel_decay_and_the_reference_scans():
    q, k, v, g, beta, state = _delta_inputs(1, 70, 2, 8, 16)
    with pytest.raises(ValueError, match="one decay a head"):
        gated_delta.gated_delta_chunked(q, k, v, g, beta, state)
    pool = jnp.zeros((1, 2, 8, 2 * 16))
    o, _ = gated_delta.gated_delta_reference(
        q, k, v, g, beta, pool, jnp.asarray([1]), jnp.asarray([True]), layer=0)
    want, _ = gated_delta.gated_delta_recurrence(q, k, v, g, beta,
                                                 jnp.zeros_like(state))
    np.testing.assert_allclose(o, want, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize(
    "tokens, dk, dv",
    [(1, 16, 128), (128, 16, 128), (4, 16, 128), (128, 16, 64), (96, 128, 128)],
    ids=["step", "chunk", "short_bucket", "two_heads_a_tile", "walk_at_the_cells_heads"])
def test_channel_kernel_is_the_recurrence(tokens, dk, dv):
    """The Pallas body (interpreted) with a [d_k] decay against the
    token-by-token definition: rows with unequal lengths (one shorter than a
    token tile), a fresh row, a padding row on the trash row; other rows and
    layers untouched. 128-lane heads take the aligned 8-row store, 64-lane
    heads in pairs and a bucket under 8 tokens the single-row one. All of
    these shapes stay on the token walk: 16-wide keys, or a bucket that is
    not whole chunks."""
    H = 4
    assert gated_delta.chunk_heads(tokens, H, dk, dv) is None
    q, k, v, g, beta, _ = _delta_inputs(3, tokens, H, dk, dv, seed=1)
    counts = jnp.asarray([tokens, max(1, tokens - 58), 0])
    valid = jnp.arange(tokens)[None] < counts[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 5, dk, H * dv))
    rows, fresh = jnp.asarray([2, 4, 0]), jnp.asarray([0, 1, 0])
    got_o, got_pool = gated_delta.gated_delta_pallas(
        q, k, v, g, beta, pool, rows, counts, fresh, layer=1, interpret=True)
    state = jnp.where((fresh > 0)[:, None, None, None], 0.0,
                      gated_delta.pool_rows(pool, 1, rows, H))
    want_o, want_state = gated_delta.gated_delta_recurrence(q, k, v, g, beta, state)
    np.testing.assert_allclose(jnp.where(valid[..., None, None], got_o, 0),
                               jnp.where(valid[..., None, None], want_o, 0),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_pool[1, rows[:2]],
                               gated_delta.flat_rows(want_state)[:2],
                               atol=1e-5, rtol=1e-5)
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
        q, k, v, jnp.where(valid[..., None, None], g, 0.0),
        jnp.where(valid[..., None], beta, 0.0), state)
    return got, (want_o, gated_delta.flat_rows(want_state)), valid


CHUNKWISE = ("unequal_rows", "strong_decay", "repeated_keys_beta_2",
             "split_prompt", "two_head_blocks")


@pytest.mark.parametrize("case", CHUNKWISE)
def test_chunkwise_channel_body_is_the_recurrence(case):
    """The chunkwise body (whole 64-token chunks at the cell's head geometry,
    d_k = d_v = 128; interpreted) against the token-by-token definition, at
    the scalar form's chunked twin's tolerance. ``unequal_rows``: a full row,
    one ending inside its second chunk, a fresh one ending inside the first
    sub-block, a padding row on the trash row (tokens past a count are the
    identity whatever g and beta say there); other rows and layers untouched.
    ``strong_decay``: a quarter of the channels at g = -10 a token for a
    whole chunk and more, where the factored WY form overflows float32.
    ``repeated_keys_beta_2``: three keys in turn with beta 1.95 and hardly
    any decay, the triangular system's worst case. ``split_prompt``: 1024
    tokens as one call and as two of 512, the state carried by the pool.
    ``two_head_blocks``: 16 heads, two grid blocks of 8 (three heads: one a
    block)."""
    tol = dict(atol=2e-5, rtol=2e-5)
    B, S, H, d = {"unequal_rows": (4, 128, 2, 128), "split_prompt": (1, 1024, 2, 128),
                  "two_head_blocks": (1, 64, 16, 128),
                  "strong_decay": (2, 128, 2, 128)}.get(case, (2, 128, 3, 128))
    assert gated_delta.chunk_heads(S, H, d, d) == math.gcd(H, 8)
    q, k, v, g, beta, _ = _delta_inputs(B, S, H, d, d, seed=5)
    counts = jnp.full((B,), S)
    rows, fresh = jnp.arange(1, B + 1), jnp.zeros((B,), jnp.int32)
    if case == "unequal_rows":
        counts, rows = jnp.asarray([S, 70, 5, 0]), jnp.asarray([2, 4, 5, 0])
        fresh = jnp.asarray([0, 0, 1, 0])
    elif case == "strong_decay":
        strong = (jnp.arange(d) % 4 == 0) & (jnp.arange(S) < 80)[:, None]
        g = jnp.where(strong[None, :, None, :], -10.0, g)
    elif case == "repeated_keys_beta_2":
        k = k[:, jnp.arange(S) % 3]
        g, beta = g * 0.01, jnp.full_like(beta, 1.95)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 7, d, H * d))
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


@pytest.mark.parametrize("S, H, dk, dv, heads", [
    (1024, 64, 128, 128, 8), (512, 64, 128, 128, 8), (64, 2, 128, 256, 2),
    (64, 12, 128, 128, 4), (1, 64, 128, 128, None), (32, 64, 128, 128, None),
    (96, 64, 128, 128, None), (128, 4, 16, 128, None), (128, 4, 128, 64, None)])
def test_the_chunkwise_body_is_chosen_by_shape_alone(S, H, dk, dv, heads):
    """Whole chunks of heads whose keys and values fill whole 128-lane tiles
    take the chunkwise body, up to 8 heads a grid step; a decode step, a
    bucket shorter than or not whole chunks and every other head geometry
    stay on the token walk."""
    assert gated_delta.chunk_heads(S, H, dk, dv) == heads


def test_a_chunk_of_the_channel_form_builds_no_token_tile():
    """The chunkwise body reads q, k, g, v and beta as the mixer made them:
    its program has no [B, S, 3 d_k + 8, H] tile, the walk's still has."""
    q, k, v, g, beta, _ = _delta_inputs(1, 64, 2, 128, 128)
    pool, one = jnp.zeros((1, 2, 128, 2 * 128)), jnp.ones((1,), jnp.int32)
    text = lambda S: str(jax.make_jaxpr(partial(
        gated_delta.gated_delta_pallas, layer=0, interpret=True))(
            q[:, :S], k[:, :S], v[:, :S], g[:, :S], beta[:, :S], pool, one, one, one))
    tile = f"{3 * 128 + 8},2]"
    assert "kda_chunk" in text(64) and tile not in text(64)
    assert "kda_chunk" in text(32) and tile in text(32)


def test_the_two_forms_are_two_programs_with_their_own_names():
    """A static specialisation: the scalar form's tile and kernel carry
    nothing of the channel form, and a trace tells them apart by name."""
    q, k, v, g, beta, _ = _delta_inputs(1, 64, 4, 16, 128)
    assert gated_delta.pack_token_tiles(q, k, g[..., 0], beta).shape[2] == 2 * 16 + 8
    assert gated_delta.pack_token_tiles(q, k, g, beta).shape[2] == 3 * 16 + 8
    pool, one = jnp.zeros((1, 2, 16, 4 * 128)), jnp.ones((1,), jnp.int32)
    trace = lambda decay, S: str(jax.make_jaxpr(partial(
        gated_delta.gated_delta_pallas, layer=0, interpret=True))(
            q[:, :S], k[:, :S], v[:, :S], decay[:, :S], beta[:, :S], pool, one,
            one, one))
    assert "kda_chunk" in trace(g, 64) and "kda_step" in trace(g, 1)
    assert "gated_delta_chunk" in trace(g[..., 0], 64)
    assert "kda" not in trace(g[..., 0], 64) + trace(g[..., 0], 1)


# ---------------------------------------------------------- a share of experts

def test_the_eight_shares_add_up_to_the_uncut_layer(params):
    """The parts of one layer's FFN that the shares [0, 2), [2, 4), ... of its
    16 experts give, with the shared expert counted once, add up to what the
    uncut layer gives: through the program's ``_expert_ffn`` (the row-block
    plan and the scan) and through the reference."""
    whole = dataclasses.replace(CFG, experts_held=(0, CFG.n_experts))
    layer = solar_open2.init_layer(whole, jax.random.PRNGKey(7), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, CFG.dim))
    valid = jnp.ones((2, 24), bool)

    def share_of(lo, hi):
        cfg = dataclasses.replace(CFG, experts_held=(lo, hi))
        cut = {**layer, **{n: layer[n][lo:hi] for n in ("w1", "w3", "w2")}}
        return cfg, cut

    shared = llama._ffn({"w1": layer["shared_w1"], "w3": layer["shared_w3"],
                         "w2": layer["shared_w2"]}, x)
    for impl in ("grouped", "dense"):
        uncut, all_pairs = solar_open2._expert_ffn(
            layer, dataclasses.replace(whole, moe_impl=impl), x, valid, None)
        parts, pairs = [], 0.0
        for lo in range(0, CFG.n_experts, 2):
            cfg, cut = share_of(lo, lo + 2)
            f, here = solar_open2._expert_ffn(
                cut, dataclasses.replace(cfg, moe_impl=impl), x, valid, None)
            parts.append(f - shared)
            pairs += float(here)
        np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5, rtol=2e-5)
        assert pairs == float(all_pairs) == 2 * 24 * CFG.moe_top_k
    # the reference's shares: x + held part + shared, a share at a time
    flat = x.reshape(-1, CFG.dim)
    kw = dict(eps=CFG.norm_eps, top_k=CFG.moe_top_k, scale=1.0, int8=False)
    with jax.default_matmul_precision("highest"):
        full = plain._experts(flat, layer, lo=0, **kw)[0] - flat
        none = plain._swiglu(plain._rms(flat, layer["ffn_norm"], CFG.norm_eps),
                             *(layer[f"shared_{n}"] for n in ("w1", "w3", "w2")),
                             False)
        held = [plain._experts(flat, share_of(lo, lo + 2)[1], lo=lo, **kw)[0]
                - flat - none for lo in range(0, CFG.n_experts, 2)]
    np.testing.assert_allclose(sum(held) + none, full, atol=2e-5, rtol=2e-5)


def test_the_rule_of_shape_reads_the_published_count_and_the_held_range():
    """An expert's share of the pairs follows the 320 the router scores, the
    passes the 40 held: the benchmark configuration's widths through
    ``expert_block`` and ``expert_path`` (32 decode rows: min(32, 40 + 2)
    passes against the scan's 2 x 40; a 1024-token round: 72 against 80)."""
    class Mesh:
        shape = {"model": 1}
    big = dataclasses.replace(CFG, n_experts=320, experts_held=(0, 40),
                              moe_top_k=8, moe_impl="grouped_pallas",
                              moe_block=128)
    assert [llama.expert_block(big, t) for t in (1, 32, 512, 1024, 2048)] == [
        16, 16, 16, 32, 64]
    for tokens in (1, 32, 512, 1024, 2048):
        assert solar_open2.expert_path(big, Mesh(), tokens) == "grouped"
    # no mesh named (a caller that differentiates) or a model axis: the scan
    assert solar_open2.expert_path(big, None, 32) == "scan"
    assert solar_open2.expert_path(
        big, type("M", (), {"shape": {"model": 4}})(), 32) == "scan"
    assert solar_open2.expert_path(
        dataclasses.replace(big, moe_impl="dense"), Mesh(), 1024) == "scan"
    # a step wide enough for whole row-blocks an expert is grouped outright
    assert solar_open2.expert_path(big, Mesh(), 8192) == "grouped"
    # the rule is the window / full family's, which holds every expert
    from mcp_context_forge_tpu.tpu_local.models import afmoe
    assert solar_open2.expert_path is afmoe.expert_path
    assert MODEL_CONFIGS["afmoe-test"].n_held == MODEL_CONFIGS["afmoe-test"].n_experts


# ------------------------------------------------------------ pools and rows

def test_the_family_declares_the_hybrid_pools():
    assert family_of(CFG) is solar_open2 and solar_open2.STEP_AUX
    assert [solar_open2.layer_kind(CFG, i) for i in range(5)] == [
        "gqa.experts", "kda.experts", "kda.experts", "kda.experts", "gqa.experts"]
    assert CFG.layers_of("full_attention") == (0, 4)
    pools = {p.name: p for p in kv_pools(CFG)}
    assert (pools["k"].layers, pools["state"].layers) == (2, 6)
    assert pools["state"].per == pools["conv_tail"].per == "sequence"
    assert kv_page_bytes(CFG, PAGE) == 2 * PAGE * 2 * 2 * 16 * 2
    assert state_rows_for(CFG, 32) == 33
    assert kv_state_bytes(CFG, 1) == 6 * (16 * 4 * 16 * 4 + 3 * CFG.conv_dim * 2)
    big = dataclasses.replace(
        CFG, dim=4096, n_heads=64, n_kv_heads=8, head_dim=128,
        linear_n_heads=64, linear_key_dim=128, linear_value_dim=128)
    # 4.19 MB of state and a 147 KB tail a KDA layer a sequence: 26.0 MB over 6
    assert kv_state_bytes(big, 1) == 6 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert kv_page_bytes(big, 128) == 2 * 128 * 2 * 8 * 128 * 2 == 1_048_576


# ------------------------------------------------------------------ the engine

def _engine(**over):
    base = dict(model="solar-open2-test", dtype="float32", max_batch=4,
                max_seq_len=256, page_size=PAGE, num_pages=80,
                prefill_buckets=(BUCKET,), prefill_max_batch=2,
                prefix_cache=False, warmup=False)
    return TPUEngine(EngineConfig(**{**base, **over}),
                     devices=jax.devices()[:1])


async def _generate(engine, prompt, n):
    return [t async for t in engine.generate(list(prompt), max_tokens=n)]


def test_engine_serves_the_family():
    """Short and chunked prompts through admission, chunk rounds and decode
    give together the tokens each gives alone; rows come back; the counts
    reach the engine's stats."""
    prompts = [[1] + prompt_of(n, n) for n in (20, 100, 150, 33)]

    async def run():
        engine = _engine()
        await engine.start()
        try:
            alone = [await _generate(engine, p, 10) for p in prompts]
            budgets = (3, 10, 10, 10)
            together = await asyncio.gather(*[
                _generate(engine, p, n) for p, n in zip(prompts, budgets)])
            return alone, together, budgets, engine.allocator.rows_in_use, engine.stats
        finally:
            await engine.stop()

    alone, together, budgets, rows_left, stats = asyncio.run(run())
    for a, t, n in zip(alone, together, budgets):
        assert t == a[:n]
    assert rows_left == 0 and stats.state_rows_total == 4
    assert stats.state_scanned_tokens >= 2 * sum(len(p) for p in prompts)
    assert stats.moe_tokens == 8 * stats.state_scanned_tokens
    assert 0 < stats.moe_local_pairs < 2 * stats.moe_tokens
    assert stats.moe_grouped_steps > 0 and stats.moe_scan_steps == 0


@pytest.mark.parametrize("setting,words", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_decode=True), "spec_decode"),
    (dict(kv_quant="int8"), "kv_quant"),
], ids=["prefix_cache", "spec_decode", "kv_quant"])
def test_unserved_settings_refuse_at_build(setting, words):
    with pytest.raises(NotImplementedError, match=words):
        _engine(**setting)


# ------------------------------------- the other families' programs, as before

# sha256 of ``str(jax.make_jaxpr(...))`` of each step program below, traced at
# the PARENT commit of the PR that brought this family (65b0814, PR 49) by this
# file's own ``_step_programs``: the refactors that PR made in shared code
# (``olmo_hybrid``'s mixer split into ``conv_qkv`` / ``delta_rule`` /
# ``decode_attend``, ``afmoe.gated_output``, ``llama.routed_experts``' held
# range, the kernel body's static channel form) leave every equation of the
# accepted families' programs where it was. A later PR that changes one on
# purpose traces it again and says so.
PARENT_PROGRAMS = {
    "olmo-hybrid-test": {
        "prefill": "124af6090c8c35d79ca8480993ba26b81bfa06d4e5b6819d4904e0e6cb77a03f",
        "chunk": "209df41fbd2f9a2ef2d6f3e8d148eac9107f66423f4f7851c52cb7f20057df05",
        # PR 54 (``conv_with_tail`` at S == 1: four [B, C] terms and the new
        # tail by a select, for every family that calls it): the two decode
        # programs traced again on purpose; prefill and chunk (S > 1) stay as
        # pinned, equation for equation
        "decode": "1254a96b81c83375da92b78ce061316224497f2d2c7117c866fffb5df9c30bb9",
        "kernel_step": "5665504a8d8b629a0c2e11c960efc38147ff5f62b1fbe1e6d2c04b05f2884bae",
        # PR 52: a scalar-form chunk of whole 64-token chunks takes the
        # chunkwise body (``gated_delta.scalar_chunk_group``); the preset's
        # bucket of 16 and every step stay on the walk, as pinned above
        "kernel_chunk": "fb2c80152d0159b0c4c4ce433fcc9cdf6365f78f7eee4fd28268b6844e6efc11"},
    "afmoe-test": {
        "prefill": "67ad0465620984a1cb42a5e6b1ee43fc7aa6a8c4b9df24264e844e35f431d1cf",
        "chunk": "ed47d8b83208e679f14f291d9f03a2c7c7848873a4322049c1b8a3ca1b821d7c",
        "decode": "e89dc8600a256c9f4a3213969d726e814d47c9a761d784b71a2507649936419f"},
    "mixtral-test": {
        "prefill": "33b858de91c4a2748df3675eec15924c3ee8f868cfe6ff3f55765cfb4bf7962e",
        "chunk": "89c51e9ff8ef4261d95c88b0e7e675a5aed36ca8c70a44fdc6a5496d87b89500",
        "decode": "2e8df2c387373d42292974342ec1a6fe93f49a0f635051ccbe3d164599d34968"},
    # PR 52 (the scalar form's chunkwise body beside the channel form's, one
    # `_mm` between them): this family's own programs and its chunkwise
    # kernel, traced at PR 51's commit
    "solar-open2-test": {
        "prefill": "c1a45b31933be23126b274502c6c8552989f0ec993407bd882efa9353b2dea3c",
        "chunk": "a75d48b141579bb353c3e373c73a1531e4142fee972b97e23c6ecda7b7d252e7",
        "decode": "a396c1a057534114b1a66828566a3543e88522d614dbfc9d0b5d1c7a640f9ee2",  # PR 54: above
        "kernel_chunk": "153b3235c94f9f243fca7032537d89347711779606e4952df4d3c51b06ed933e"},
}


def _step_programs(model):
    """The jaxprs of an engine's three step programs at one small shape, and
    for the hybrid family the scalar-decay kernel's at Olmo's head geometry
    (two 192-lane heads a tile)."""
    engine = TPUEngine(EngineConfig(
        model=model, max_batch=2, max_seq_len=128, page_size=8, num_pages=64,
        prefill_buckets=(16,), prefix_cache=False, dtype="float32",
        warmup=False), devices=jax.devices()[:1])
    B, S = 2, 16
    samp = SamplingParams(jnp.zeros(B), jnp.zeros(B, jnp.int32), jnp.ones(B))
    tokens = jnp.zeros((B, S), jnp.int32)
    positions = jnp.tile(jnp.arange(S), (B, 1))
    rows, key = jnp.arange(B), jax.random.PRNGKey(0)
    with engine.mesh:
        out = {
            "prefill": jax.make_jaxpr(engine._prefill_and_sample)(
                engine.params, engine.kv, tokens, positions, rows, rows, samp, key),
            "chunk": jax.make_jaxpr(partial(engine._prefill_hist_and_sample,
                                            ctx_pages=4))(
                engine.params, engine.kv, tokens, positions, rows, rows, samp, key),
            "decode": jax.make_jaxpr(partial(engine._decode_and_sample,
                                             ctx_pages=4, k=2))(
                engine.params, engine.kv, rows, rows, rows, rows + 1, rows,
                jnp.full((B, 4), -1), samp, key)}
    f32 = lambda *s: jnp.zeros(s, jnp.float32)
    if model == "solar-open2-test":     # the channel form's chunkwise body
        i32, H, d, S = jnp.zeros((2,), jnp.int32), 8, 128, 128
        out["kernel_chunk"] = jax.make_jaxpr(partial(
            gated_delta.gated_delta_pallas, layer=1, interpret=True))(
            f32(2, S, H, d), f32(2, S, H, d), f32(2, S, H, d), f32(2, S, H, d),
            f32(2, S, H), f32(2, 5, d, H * d), i32, i32, i32)
    if model != "olmo-hybrid-test":
        return out
    H, dk, dv = 4, 16, 192
    for name, S in (("kernel_step", 1), ("kernel_chunk", 128)):
        i32 = jnp.zeros((3,), jnp.int32)
        out[name] = jax.make_jaxpr(partial(
            gated_delta.gated_delta_pallas, layer=1, interpret=True))(
            f32(3, S, H, dk), f32(3, S, H, dk), f32(3, S, H, dv), f32(3, S, H),
            f32(3, S, H), f32(2, 5, dk, H * dv), i32, i32, i32)
    return out


@pytest.mark.parametrize("model", sorted(PARENT_PROGRAMS))
def test_accepted_families_step_programs_are_the_parents(model):
    now = {name: hashlib.sha256(str(jaxpr).encode()).hexdigest()
           for name, jaxpr in _step_programs(model).items()}
    assert now == PARENT_PROGRAMS[model]
