"""The Mamba-2 / un-rotated GQA family (``granite_hybrid``) on the CPU in
float32: the program's step functions through the cache against the plain
token-by-token reference, both scan kernels (interpreted) and their twins
against the recurrence, the reference's named wrong programs as controls, and
the engine serving it at a decode width of 64."""

import asyncio
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
from mcp_context_forge_tpu.tpu_local.models import (family_of, granite_hybrid,
                                                    olmo_hybrid)
from mcp_context_forge_tpu.tpu_local.models.configs import (
    MODEL_CONFIGS, GraniteHybridConfig)
from mcp_context_forge_tpu.tpu_local.ops import ssd
from mcp_context_forge_tpu.tpu_local.quantize import quantize_tree

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from benchmark.reference import granite_hybrid_plain as plain  # noqa: E402

CFG = MODEL_CONFIGS["granite-hybrid-test"]
PAGE, SLOTS, TABLE, BUCKET = 16, 4, 16, 64
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    tree = granite_hybrid.init_params(CFG, jax.random.PRNGKey(3), jnp.float32)
    # D is drawn as ones: give it values of its own, so that a skip applied to
    # the wrong head would show
    for i, layer in enumerate(tree["layers"]):
        if "D" in layer:
            layer["D"] = 0.5 + jax.random.uniform(jax.random.PRNGKey(100 + i),
                                                  layer["D"].shape)
    return tree


def fresh_kv(slot_rows=(1, 2, 3, 4)):
    """A pool of SLOTS slots, slot s owning pages [1 + s * TABLE, ...) and
    state row ``slot_rows[s]``."""
    kv = init_kv_state(CFG, 1 + SLOTS * TABLE, PAGE, SLOTS, TABLE,
                       dtype=jnp.float32)
    tables = 1 + np.arange(SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    return kv._replace(block_tables=jnp.asarray(tables),
                       state_rows=jnp.asarray(slot_rows, jnp.int32))


_hist = jax.jit(partial(granite_hybrid.prefill_with_history, config=CFG),
                static_argnames=("ctx_pages",))
_dense = jax.jit(partial(granite_hybrid.prefill, config=CFG))
_decode = jax.jit(partial(granite_hybrid.decode_step, config=CFG))


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

def test_the_preset_keeps_the_published_ratios():
    """One group, heads x head_dim = 2 x dim, attention at an interior
    position of the period, a softmax at 1 / head_dim."""
    assert CFG.mamba_inner == 2 * CFG.dim
    assert CFG.conv_dim == CFG.mamba_inner + 2 * CFG.mamba_d_state
    assert CFG.layers_of("full_attention") == (2, 6)
    assert CFG.attention_multiplier == 1 / CFG.head_dim
    assert (CFG.embedding_multiplier, CFG.residual_multiplier,
            CFG.logits_scaling) == (12.0, 0.22, 8.0)
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig(**{**CFG.__dict__, "layer_types": ("mamba",) * 7})
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig(**{**CFG.__dict__,
                               "layer_types": ("mamba",) * 7 + ("moe",)})


def test_dense_prefill_of_unequal_rows_matches_reference(params):
    prompts = [prompt_of(n, n) for n in (64, 37, 5)]
    tokens, positions = pack([(p, 0, len(p)) for p in prompts])
    logits, kv, aux = _dense(params, tokens=tokens, positions=positions,
                             kv=fresh_kv(), slot_ids=jnp.arange(3))
    for i, p in enumerate(prompts):
        want = reference(params, p, list(range(len(p))))
        np.testing.assert_allclose(np.asarray(logits[i, :len(p)]), want,
                                   atol=TOL, rtol=TOL)
    # 0, 0, 0, rows, live state rows, real tokens scanned
    assert np.asarray(aux).tolist() == [0, 0, 0, 3, 3, 64 + 37 + 5]


def test_chunk_rounds_carry_state_and_tail_then_decode_reads_them(params):
    """A prompt of three chunk rounds with a padded last chunk, beside a row
    that is all padding, equals the dense forward; then decode through the
    pages and the state row."""
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
    assert np.asarray(aux)[3:].tolist() == [1, 1, 1]
    # slot 0's row (all padding) and slot 1's (idle decode row) were never written
    assert not np.asarray(kv.state[:, 1]).any()
    assert not np.asarray(kv.state[:, 2]).any()
    assert np.asarray(kv.state[:, 3]).any()
    assert not np.asarray(kv.conv_tail[:, 1]).any()
    assert np.asarray(kv.conv_tail[:, 3]).any()


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


def test_a_batch_neighbour_changes_nothing(params):
    prompt, other = prompt_of(50, 4), prompt_of(64, 5)
    alone_t, alone_p = pack([(prompt, 0, 50)])
    both_t, both_p = pack([(other, 0, 64), (prompt, 0, 50)])
    alone, _, _ = _dense(params, tokens=alone_t, positions=alone_p,
                         kv=fresh_kv(), slot_ids=jnp.asarray([3]))
    both, _, _ = _dense(params, tokens=both_t, positions=both_p,
                        kv=fresh_kv(), slot_ids=jnp.asarray([0, 3]))
    np.testing.assert_allclose(np.asarray(both[1, :50]),
                               np.asarray(alone[0, :50]), atol=1e-5, rtol=1e-5)


def test_int8_weights_and_the_tied_head_agree_with_their_dequantised_twin(params):
    """One embedding matrix, quantised a row, used both ways; the dt columns,
    the convolution, A_log, D, dt_bias and the norms stay full precision."""
    logical = granite_hybrid.params_logical(CFG)
    quant = quantize_tree(params, logical, scale_dtype=jnp.float32)
    assert "lm_head" not in quant and isinstance(quant["embed"], dict)
    assert quant["embed"]["s"].shape == (CFG.vocab_size,)
    mamba, attention = quant["layers"][0], quant["layers"][2]
    for name in ("wz", "wxbc", "wo", "w1", "w2", "w3"):
        assert isinstance(mamba[name], dict)
    for name in ("wq", "wk", "wv", "wo"):
        assert isinstance(attention[name], dict)
    for name in ("wdt", "conv", "conv_bias", "A_log", "D", "dt_bias", "o_norm",
                 "mixer_norm"):
        assert not isinstance(mamba[name], dict)
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
    """Each of the four multipliers, the gate-before-norm order, the
    convolution's bias and the skip moves the float32 logits far beyond what
    the program is held to here; a bfloat16 state (a decayed SUM, which no
    correction term feeds a rounding error back into, under a residual
    multiplier of 0.22 and logits divided by 8) moves them by twice that
    limit, which is still not held."""
    prompt = prompt_of(96, 11)
    at = list(range(40, 96))
    moved = np.abs(reference(params, prompt, at, variant)
                   - reference(params, prompt, at)).max()
    assert moved > (1.5 if variant == "bf16_state" else 50) * TOL
    with pytest.raises(ValueError, match="variant"):
        plain.forward(params, CFG, prompt, at, variant="no_such_program")


def test_a_scan_over_the_buckets_padding_is_another_function(params, monkeypatch):
    """The control the chip's tolerance is also set against: padding tokens
    that are NOT made identity steps leave another state behind."""
    prompt = prompt_of(37, 6)
    tokens, positions = pack([(prompt, 0, 37)])
    everything = jnp.ones_like(positions, dtype=bool)
    real = granite_hybrid.scan

    def scan_all(x, dt, layer, b, c, valid, *rest):
        return real(x, dt, layer, b, c, everything, *rest)

    good = granite_hybrid.prefill(params, CFG, tokens, positions, fresh_kv(),
                                  jnp.asarray([1]))[1]
    monkeypatch.setattr(granite_hybrid, "scan", scan_all)
    bad = granite_hybrid.prefill(params, CFG, tokens, positions, fresh_kv(),
                                 jnp.asarray([1]))[1]
    assert np.abs(np.asarray(bad.state[0, 2]) - np.asarray(good.state[0, 2])).max() > 1e-2


# ------------------------------------------------------- the scan kernels

def _scan_inputs(B, S, H, P, N, seed=0, scale=3.0):
    """Strong decay: dt A reaches below -30 a token."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, S, H * P))
    dt = scale * jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    a_log = jnp.log(jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0))
    b, c = jax.random.normal(k[3], (B, S, N)), jax.random.normal(k[4], (B, S, N))
    pool = jax.random.normal(k[5], (3, B + 2, N, H * P))
    return x, dt, a_log, b, c, pool


SCAN_CASES = {
    # S, rows, counts, fresh
    "step": (1, [2, 0, 1, 4], [1, 0, 1, 1], [False, False, True, False]),
    "step_all_idle": (1, [0, 0, 0, 0], [0, 0, 0, 0], [False] * 4),
    "step_first_rows_idle": (1, [0, 0, 3, 0], [0, 0, 1, 0], [False] * 4),
    "chunks": (128, [2, 0, 1, 4], [128, 0, 98, 128], [False, False, True, False]),
    "chunks_short_of_the_bucket": (128, [0, 5, 3, 1], [0, 7, 64, 65],
                                   [False, True, False, False]),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scan_kernels_and_twins_are_the_recurrence(case):
    """``ssd_chunk`` / ``ssd_step`` (interpreted) and the chunkwise twin
    against the token recurrence: strong decay, padding, counts short of the
    bucket, a fresh row, the trash row, rows that are idle."""
    S, rows, counts, fresh = SCAN_CASES[case]
    B, H, P, N = 4, 4, 64, 16
    x, dt, a_log, b, c, pool = _scan_inputs(B, S, H, P, N, seed=S)
    assert float((dt * -jnp.exp(a_log)).min()) < -30
    rows, counts, fresh = (jnp.asarray(rows), jnp.asarray(counts),
                           jnp.asarray(fresh))
    valid = jnp.arange(S)[None, :] < counts[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    want_y, want_pool = ssd.ssd_reference(x, dt, a_log, b, c, pool, rows, fresh,
                                          layer=1, chunked=False)
    twin_y, twin_pool = ssd.ssd_reference(x, dt, a_log, b, c, pool, rows, fresh,
                                          layer=1)
    got_y, got_pool = ssd.ssd_pallas(x, dt, a_log, b, c, pool, rows, counts,
                                     fresh, layer=1, interpret=True)
    scale = float(jnp.abs(want_y).max())
    for y, out in ((twin_y, twin_pool), (got_y, got_pool)):
        np.testing.assert_allclose(np.where(valid[..., None], y, 0),
                                   np.where(valid[..., None], want_y, 0),
                                   atol=2e-6 * scale, rtol=1e-5)
        np.testing.assert_allclose(out[:, 1:], want_pool[:, 1:], atol=1e-5,
                                   rtol=1e-5)
    # the kernel moves no state for a row on the trash row, writes zeros for
    # it, and touches no other layer
    np.testing.assert_array_equal(got_pool[:, 0], pool[:, 0])
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[2], pool[2])
    idle = np.asarray(counts) == 0
    assert not np.asarray(got_y)[idle].any()
    untouched = sorted(set(range(1, B + 2)) - set(np.asarray(rows).tolist()))
    np.testing.assert_array_equal(got_pool[1, untouched], pool[1, untouched])


def test_a_reused_row_continues_where_the_last_call_left_it():
    """Two calls of 64 tokens through the pool are one of 128; the step
    kernel after them is token 129."""
    B, H, P, N = 2, 4, 64, 16
    x, dt, a_log, b, c, pool = _scan_inputs(B, 129, H, P, N, seed=5, scale=0.3)
    rows, fresh, no = jnp.asarray([2, 1]), jnp.asarray([True, True]), jnp.asarray([False, False])
    want_y, want_pool = ssd.ssd_reference(x, dt, a_log, b, c, pool, rows, fresh,
                                          layer=0, chunked=False)
    ys, state = [], pool
    for lo, hi, first in ((0, 64, fresh), (64, 128, no), (128, 129, no)):
        part = [v[:, lo:hi] for v in (x, dt)] + [a_log] + [v[:, lo:hi] for v in (b, c)]
        y, state = ssd.ssd_pallas(*part, state, rows,
                                  jnp.full((B,), hi - lo), first, layer=0,
                                  interpret=True)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), want_y, atol=2e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(state[0, 1:3], want_pool[0, 1:3], atol=2e-4,
                               rtol=1e-4)


def test_the_kernels_take_the_published_geometry_and_say_so():
    assert ssd.takes(64, 64, 128)
    assert not ssd.takes(8, 16, 16) and not ssd.takes(3, 64, 16)
    with pytest.raises(ValueError, match="heads"):
        ssd.ssd_pallas(*_scan_inputs(1, 64, 8, 16, 16)[:5],
                       jnp.zeros((1, 2, 16, 128)), jnp.ones((1,), jnp.int32),
                       jnp.full((1,), 64), jnp.zeros((1,), bool), layer=0,
                       interpret=True)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_pallas(*_scan_inputs(1, 96, 4, 64, 16)[:5],
                       jnp.zeros((1, 2, 16, 256)), jnp.ones((1,), jnp.int32),
                       jnp.full((1,), 96), jnp.zeros((1,), bool), layer=0,
                       interpret=True)
    named, live = ssd.live_rows(jnp.asarray([0, 0, 5, 0, 7, 0]))
    assert named.tolist() == [5, 5, 5, 5, 7, 7] and live.tolist() == [0, 0, 1, 0, 1, 0]
    named, live = ssd.live_rows(jnp.zeros((3,), jnp.int32))
    assert named.tolist() == [0, 0, 0] and not live.any()


def test_the_kernels_carry_their_names_in_a_program():
    """What a device trace shows, and what the benchmark's readers look for."""
    x, dt, a_log, b, c, pool = _scan_inputs(2, 64, 4, 64, 16)
    args = (pool, jnp.asarray([1, 2]), jnp.full((2,), 64), jnp.zeros((2,), bool))
    text = lambda S: str(jax.make_jaxpr(partial(ssd.ssd_pallas, layer=0))(
        x[:, :S], dt[:, :S], a_log, b[:, :S], c[:, :S], *args))
    assert "ssd_chunk" in text(64) and "ssd_step" not in text(64)
    assert "ssd_step" in text(1) and "ssd_chunk" not in text(1)


# ------------------------------------------- the one-token path of the mixer

# heads of 64, which the kernels take; the other widths stay tiny
KCFG = GraniteHybridConfig(**{**CFG.__dict__, "name": "granite-kernel-test",
                              "mamba_n_heads": 4, "mamba_head_dim": 64,
                              "dim": 128})

# the decode widths the engine is built with here and in the cell (max_batch:
# 64 there, 1 to 8 in the tests) and one that is not whole blocks of 8 rows
TOKEN_CASES = {
    # rows (0: idle, on the trash row), fresh
    "one_block_live_idle_fresh": ([3, 0, 5, 0, 0, 7, 2, 9],
                                  [False, True, True, False, False, False, True, False]),
    "all_idle": ([0] * 8, [False] * 8),
    "first_block_idle": ([0] * 8 + [4, 0, 11, 0, 0, 6, 0, 1], [False] * 16),
    "width_64": 64,         # drawn: six rows in ten live, three in ten fresh
    "width_12": 12,
    "width_4": ([2, 0, 4, 1], [False, False, True, False]),
    "width_2": ([0, 2], [True, True]),
    "width_1": ([1], [False]),
}


def _token_case(case):
    if isinstance(TOKEN_CASES[case], int):
        B = TOKEN_CASES[case]
        rng = np.random.default_rng(B)
        rows = np.where(rng.random(B) < 0.6, 1 + rng.permutation(B), 0)
        fresh = rng.random(B) < 0.3
    else:
        rows, fresh = TOKEN_CASES[case]
    return np.asarray(rows, np.int32), np.asarray(fresh, bool)


def _plain_token_mixer(layer, cfg, a, tail, state):
    """One row's decode token through the Mamba mixer, written plainly in
    float32: a [D] the normed stream; tail [taps, C] (zeros for a fresh row);
    state [N, H, P]. -> (mixed [D], the new tail, the new state)."""
    H, P, N, inner = (cfg.mamba_n_heads, cfg.mamba_head_dim, cfg.mamba_d_state,
                      cfg.mamba_inner)
    hi = jax.lax.Precision.HIGHEST
    dot = partial(jnp.dot, precision=hi)
    window = jnp.concatenate([tail, dot(a, layer["wxbc"])[None]])       # [4, C]
    conv = jax.nn.silu(jnp.sum(layer["conv"] * window, axis=0)
                       + layer["conv_bias"])
    x, b, c = conv[:inner], conv[inner:inner + N], conv[inner + N:]
    dt = jax.nn.softplus(dot(a, layer["wdt"]) + layer["dt_bias"])       # [H]
    y, state = ssd.ssd_recurrence(x.reshape(1, 1, H, P), dt[None, None],
                                  layer["A_log"], b[None, None], c[None, None],
                                  state[None])
    y = y.reshape(inner) + jnp.repeat(layer["D"], P) * x                # the skip
    gated = y * jax.nn.silu(dot(a, layer["wz"]))                        # the gate
    normed = (gated * jax.lax.rsqrt(jnp.mean(gated * gated) + cfg.norm_eps)
              * layer["o_norm"])                           # the norm, all channels
    return dot(normed, layer["wo"]), window[1:], state[0]


@pytest.mark.parametrize("case", sorted(TOKEN_CASES))
def test_the_one_token_path_is_the_recurrence_and_its_plain_epilogue(
        case, monkeypatch):
    """A decode token through ``_mamba_mixer`` with ``ssd_step`` (interpreted)
    against ``ssd_recurrence`` with the convolution, the skip, the gate and
    the norm written plainly, at 1e-5 of the largest entry: live, idle and
    fresh rows inside one block of 8 rows, a call with no live row, a first
    block that is all idle, the cell's width of 64 and narrower ones, 12
    among them (not whole blocks: idle steps fill the last one)."""
    cfg = KCFG
    rows, fresh = _token_case(case)
    B = len(rows)
    H, P, N = cfg.mamba_n_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    layer = granite_hybrid.init_layer(cfg, jax.random.PRNGKey(7), jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(B), 6)
    layer["D"] = 0.5 + jax.random.uniform(k[0], (H,))
    layer["o_norm"] = 0.5 + jax.random.uniform(k[1], (cfg.mamba_inner,))
    n_rows = max(int(rows.max()), B) + 1
    kv = init_kv_state(cfg, 3, PAGE, B, 1, dtype=jnp.float32)
    kv = kv._replace(
        state=jax.random.normal(k[2], (2, n_rows, N, H * P)),
        conv_tail=jax.random.normal(k[3], (2, n_rows, 3, cfg.conv_dim)))
    stream = jax.random.normal(k[4], (B, 1, cfg.dim))
    live = rows > 0
    counts = jnp.asarray(live.astype(np.int32))
    monkeypatch.setattr(ssd, "ssd_pallas", partial(ssd.ssd_pallas, interpret=True))
    mixed, out, tails = granite_hybrid._mamba_mixer(
        layer, cfg, 1, stream, stream, jnp.asarray(live)[:, None],
        jnp.asarray(rows), counts, jnp.asarray(fresh), kv, "pallas")
    assert mixed.shape == (B, 1, cfg.dim) and tails.shape == kv.conv_tail.shape[1:]
    want_state, want_tails = np.array(kv.state[1]), np.array(kv.conv_tail[1])
    for i in np.flatnonzero(live):
        zero = 0.0 if fresh[i] else 1.0
        want, tail, state = _plain_token_mixer(
            layer, cfg, stream[i, 0], zero * kv.conv_tail[1, rows[i]],
            (zero * kv.state[1, rows[i]]).reshape(N, H, P))
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(mixed[i, 0], want, atol=1e-5 * scale, rtol=0)
        want_state[rows[i]], want_tails[rows[i]] = state.reshape(N, H * P), tail
    np.testing.assert_allclose(out.state[1], want_state,
                               atol=1e-5 * float(np.abs(want_state).max()), rtol=0)
    np.testing.assert_allclose(tails[1:], want_tails[1:],
                               atol=1e-5 * float(np.abs(want_tails).max()), rtol=0)
    # nothing else moved: the other layer, the trash row, the rows no live
    # step owns (bit for bit)
    np.testing.assert_array_equal(out.state[0], kv.state[0])
    idle_rows = sorted(set(range(n_rows)) - set(rows[live].tolist()))
    np.testing.assert_array_equal(np.asarray(out.state[1])[idle_rows],
                                  np.asarray(kv.state[1])[idle_rows])
    np.testing.assert_array_equal(np.asarray(tails)[idle_rows[1:]],
                                  np.asarray(kv.conv_tail[1])[idle_rows[1:]])
    assert np.isfinite(np.asarray(mixed)).all()


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_one_token_convolution_is_the_many_token_one_bit_for_bit(bias, dtype):
    """``conv_with_tail`` at S == 1 (four [B, C] terms, the new tail by a
    select) against its S > 1 code on the same inputs (the token and one
    padding token after it): the convolution and the new tails bit for bit,
    for rows with a real token (counts 1), without (counts 0) and fresh ones;
    with Granite's bias and without, as Olmo and Solar call it."""
    cfg = KCFG
    B, C = 8, cfg.conv_dim
    k = jax.random.split(jax.random.PRNGKey(11), 5)
    kv = init_kv_state(cfg, 3, PAGE, B, 1, dtype=dtype)
    kv = kv._replace(conv_tail=jax.random.normal(
        k[0], (2, 12, 3, C)).astype(dtype))
    raw = jax.random.normal(k[1], (B, 2, C)).astype(dtype)
    weight = jax.random.normal(k[2], (4, C)).astype(dtype)
    b = jax.random.normal(k[3], (C,)) if bias else None
    rows = jnp.asarray([3, 0, 1, 5, 0, 2, 8, 4])
    counts = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1])
    fresh = jnp.asarray([False, False, True, False, True, False, False, True])
    call = lambda raw: olmo_hybrid.conv_with_tail(raw, weight, b, 4, 1, rows,
                                                  counts, fresh, kv)
    # compiled too where the taps' products are exact in float32 (bfloat16
    # operands), so that no fused multiply-add can round them differently
    runs = [lambda f, x: f(x)] + ([lambda f, x: jax.jit(f)(x)]
                                  if dtype == jnp.bfloat16 else [])
    for run in runs:
        one, kv_one = run(call, raw[:, :1])
        many, kv_many = run(call, raw)
        assert one.shape == (B, 1, C) and one.dtype == jnp.float32
        np.testing.assert_array_equal(one[:, 0], many[:, 0])
        np.testing.assert_array_equal(kv_one.conv_tail.astype(jnp.float32),
                                      kv_many.conv_tail.astype(jnp.float32))
    # a row without a token keeps its tail, a row with one moves up by it
    np.testing.assert_array_equal(kv_one.conv_tail[1, 3, 2].astype(jnp.float32),
                                  raw[0, 0].astype(jnp.float32))
    np.testing.assert_array_equal(kv_one.conv_tail[1, 3, :2], kv.conv_tail[1, 3, 1:])
    np.testing.assert_array_equal(kv_one.conv_tail[1, 9], kv.conv_tail[1, 9])
    np.testing.assert_array_equal(kv_one.conv_tail[0], kv.conv_tail[0])


# ------------------------------------------------------------ pools and rows

def test_the_family_declares_the_hybrid_pools():
    assert family_of(CFG) is granite_hybrid and granite_hybrid.STEP_AUX
    assert granite_hybrid.STEP_KIND == "token"
    assert [granite_hybrid.layer_kind(CFG, i) for i in range(4)] == [
        "mamba", "mamba", "attention", "mamba"]
    pools = {p.name: p for p in kv_pools(CFG)}
    assert (pools["k"].layers, pools["state"].layers) == (2, 6)
    assert pools["state"].shape == (16, 8 * 16) and pools["state"].dtype == jnp.float32
    assert pools["conv_tail"].shape == (3, 160)
    assert pools["state"].per == pools["conv_tail"].per == "sequence"
    # a head of 16 is stored in a whole lane tile (kv_head_dim)
    assert CFG.kv_head_dim == 128 and pools["k"].shape == (2, 128)
    assert kv_page_bytes(CFG, PAGE) == 2 * PAGE * 2 * 2 * 128 * 2
    assert state_rows_for(CFG, 64) == 65
    assert kv_state_bytes(CFG, 1) == 6 * (16 * 128 * 4 + 3 * 160 * 2)
    # the shared plumbing is used, not copied
    assert granite_hybrid.state_rows is olmo_hybrid.state_rows
    assert granite_hybrid.conv_with_tail is olmo_hybrid.conv_with_tail
    assert granite_hybrid.decode_attend is olmo_hybrid.decode_attend
    n = sum(leaf.size for leaf in jax.tree.leaves(
        jax.eval_shape(lambda: granite_hybrid.init_params(
            CFG, jax.random.PRNGKey(0)))))
    assert n == granite_hybrid.param_count(CFG)


# ------------------------------------------------------------------ the engine

def _engine(**over):
    base = dict(model="granite-hybrid-test", dtype="float32", max_batch=64,
                max_seq_len=256, page_size=PAGE, num_pages=64 * 4 + 1,
                prefill_buckets=(BUCKET,), prefill_max_batch=4,
                prefix_cache=False, warmup=False, decode_overlap=False)
    return TPUEngine(EngineConfig(**{**base, **over}),
                     devices=jax.devices()[:1])


async def _generate(engine, prompt, n):
    return [t async for t in engine.generate(list(prompt), max_tokens=n)]


def test_engine_serves_the_family_at_a_decode_width_of_64():
    """Short and chunked prompts through admission, chunk rounds and decode at
    width 64 give together the tokens each gives alone, twice; the books are
    exact; the live rows and the scanned tokens equal hand counts."""
    lengths = (20, 100, 150, 33, 7, 64)
    prompts = [[1] + prompt_of(n, n) for n in lengths]
    budget = 6

    async def run():
        engine = _engine()
        await engine.start()
        try:
            alone = [await _generate(engine, p, budget) for p in prompts[:3]]
            again = await _generate(engine, prompts[0], budget)
            before = engine.stats.state_scanned_tokens
            together = await asyncio.gather(*[
                _generate(engine, p, budget) for p in prompts])
            steps = engine.timeline.snapshot()["step"]
            return (alone, again, together, engine.allocator.rows_in_use,
                    engine.stats, before, steps, engine.config.max_batch)
        finally:
            await engine.stop()

    alone, again, together, rows_left, stats, before, steps, width = asyncio.run(run())
    assert again == alone[0]                                 # greedy repeats
    for a, t in zip(alone, together):
        assert t == a
    assert rows_left == 0 and stats.state_rows_total == 64 and width == 64
    # exact accounting: 4 generations alone, 6 together
    assert stats.requests == 10
    assert stats.completion_tokens == 10 * budget
    assert stats.prompt_tokens == (sum(len(p) for p in prompts)
                                   + sum(len(p) for p in prompts[:3]) + len(prompts[0]))
    # every prompt token is scanned once by prefill, every generated token but
    # the last of a request once by a decode step
    scanned = stats.state_scanned_tokens - before
    assert scanned == sum(len(p) for p in prompts) + 6 * (budget - 1)
    assert sum(s.counts.scanned_tokens for s in steps) == stats.state_scanned_tokens
    # a decode dispatch is 64 wide; its live rows are the rows that decode in
    # it (a chunked prompt joins later), each once a token it generates after
    # the first, over all ten requests
    decodes = [s for s in steps if s.kind == "decode"]
    assert all(s.width == 64 for s in decodes)
    live = [s.counts.state_rows_live for s in decodes]
    assert max(live) == 6 and min(live) >= 1
    assert sum(live) == 10 * (budget - 1)
    assert stats.delta_chunkwise_steps == 0 and stats.delta_walk_steps == 0


@pytest.mark.parametrize("setting,words", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_decode=True), "spec_decode"),
    (dict(kv_quant="int8"), "kv_quant"),
], ids=["prefix_cache", "spec_decode", "kv_quant"])
def test_unserved_settings_refuse_at_build(setting, words):
    with pytest.raises(NotImplementedError, match=words):
        _engine(**setting)


def test_delta_body_answers_for_the_chunk_kernel():
    """Off the TPU the twin runs and nothing is counted; where the kernel
    runs every prefill is chunkwise."""
    import unittest.mock as mock
    assert granite_hybrid.delta_body(CFG, None, 64) is None
    big = MODEL_CONFIGS["granite-hybrid-test"].__class__(
        **{**CFG.__dict__, "mamba_n_heads": 4, "mamba_head_dim": 64,
           "dim": 128})
    with mock.patch.object(granite_hybrid, "on_tpu", lambda mesh: True):
        assert granite_hybrid.delta_impl(None, big) == "pallas"
        assert granite_hybrid.delta_body(big, None, 512) == "chunkwise"
        assert granite_hybrid.delta_impl(None, CFG) == "jnp"
        assert granite_hybrid.prefill_unit(None, big) % ssd.CHUNK == 0
