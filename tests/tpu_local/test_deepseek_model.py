"""The latent-attention / sparse-selector / shared + routed expert family
(``models/deepseek.py``) at a CPU size: held to the plain reference
(``benchmark/reference/deepseek_v32_plain.py``: non-absorbed attention, full
``[T, S]`` index matrix, a full sort, a loop over experts) in float32, through
dense prefill, chunked history prefill and decode over the latent cache, by
the gather path and by the kernels (interpreted), with the pools' zero tails
kept; the kernels against their jnp references; the expert shares adding up
to the uncut layer; the family seam leaving the GQA trunk's programs alone; and
what the family refuses."""

import asyncio
import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import deepseek_v32_plain as plain  # noqa: E402
from mcp_context_forge_tpu.tpu_local import kv as kv_mod  # noqa: E402
from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine  # noqa: E402
from mcp_context_forge_tpu.tpu_local.models import (MODEL_CONFIGS, deepseek,  # noqa: E402
                                                    family_of, llama)
from mcp_context_forge_tpu.tpu_local.ops import mla_attention as mla  # noqa: E402
from mcp_context_forge_tpu.tpu_local.ops.grouped_moe import (  # noqa: E402
    experts_grouped, plan_sorted_blocks)

CFG = MODEL_CONFIGS["deepseek-test"]
PAGE, TABLE, T = 16, 8, 70
SLOT = jnp.zeros((1,), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return deepseek.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (T + 4,), 0,
                                         CFG.vocab_size))


@pytest.fixture(scope="module")
def reference(params, tokens):
    return plain.trace(params, CFG, tokens.tolist())


def fresh_cache(cfg=CFG):
    kv = deepseek.init_kv_state(cfg, 1 + TABLE, PAGE, 1, TABLE, dtype=jnp.float32)
    return kv._replace(block_tables=jnp.arange(1, 1 + TABLE, dtype=jnp.int32)[None])


def block(tokens, start, end, width):
    tok = np.zeros((1, width), np.int32)
    tok[0, :end - start] = tokens[start:end]
    pos = np.full((1, width), -1, np.int32)
    pos[0, :end - start] = np.arange(start, end)
    return jnp.asarray(tok), jnp.asarray(pos)


def chunked(params, tokens, chunk, cfg=CFG, **kw):
    """The prompt in chunks through the history path: logits of every
    position, and the cache it leaves."""
    kv, rows = fresh_cache(cfg), []
    for start in range(0, T, chunk):
        end = min(start + chunk, T)
        tok, pos = block(tokens, start, end, chunk)
        logits, kv, _ = deepseek.prefill_with_history(
            params, cfg, tok, pos, kv, SLOT, ctx_pages=TABLE, **kw)
        rows.append(np.asarray(logits)[0, :end - start])
    return np.concatenate(rows), kv


def interpreted_kernels(monkeypatch):
    """``paged_impl="pallas"`` off the chip: the family's three kernels run
    interpreted."""
    for name in ("mla_paged_attention_pallas", "sparse_index_scores_pallas",
                 "sparse_select_pallas"):
        monkeypatch.setattr(mla, name, partial(getattr(mla, name), interpret=True))


def assert_zero_tails(kv, cfg, written: int):
    """The pools hold ``written`` tokens' declared vectors a layer and zeros
    in the lanes past them (``kv.stored_width``), trash page apart."""
    for pages, width in ((kv.latent_pages, cfg.latent_dim),
                         (kv.index_pages, cfg.index_head_dim)):
        if pages is None:
            continue
        pages = np.asarray(pages)[:, 1:]
        assert pages.shape[-1] == kv_mod.stored_width(width) > width
        assert not pages[..., width:].any()
        tokens_held = pages[..., :width].any(axis=-1).sum(axis=(1, 2))
        assert (tokens_held == written).all(), tokens_held


@pytest.mark.parametrize("impl", ["gather", "pallas"])
@pytest.mark.parametrize("path", ["prefill", "chunked_history", "decode"])
def test_program_equals_the_plain_reference_in_float32(params, tokens, reference,
                                                       path, impl, monkeypatch):
    want = np.asarray(reference["logits"])
    if impl == "pallas":
        interpreted_kernels(monkeypatch)
    with jax.default_matmul_precision("highest"):
        if path == "prefill":
            tok, pos = block(tokens, 0, T, 128)
            logits, kv, _ = deepseek.prefill(params, CFG, tok, pos, fresh_cache(),
                                             SLOT, attn_impl=impl)
            got = np.asarray(logits)[0, :T]
        else:
            got, kv = chunked(params, tokens, 32, paged_impl=impl)
            if path == "decode":
                rows = []
                for j in range(4):
                    logits, kv, _ = deepseek.decode_step(
                        params, CFG, jnp.asarray(tokens[T + j:T + j + 1]),
                        jnp.asarray([T + j]), kv, SLOT, jnp.asarray([T + j + 1]),
                        ctx_pages=TABLE, paged_impl=impl)
                    rows.append(np.asarray(logits)[0])
                got, want = np.stack(rows), want[T:T + 4]
            else:
                want = want[:T]
    # the selection is a strict subset here (index_topk 8 of up to 74 tokens)
    assert T > 4 * CFG.index_topk
    np.testing.assert_allclose(got, want[:len(got)], atol=1e-4, rtol=1e-4)
    assert_zero_tails(kv, CFG, T + 4 if path == "decode" else T)


def test_selecting_everything_is_dense_latent_attention(params, tokens):
    """``index_topk >= context``: the selector keeps every visible token, and
    the result is the dense (selector-free) absorbed attention's."""
    wide = dataclasses.replace(CFG, index_topk=512)
    with jax.default_matmul_precision("highest"):
        selected, _ = chunked(params, tokens, 32, cfg=wide)
        dense, _ = chunked(params, tokens, 32, cfg=wide, dense_attention=True)
        sparse, _ = chunked(params, tokens, 32)
    np.testing.assert_allclose(selected, dense, atol=1e-5, rtol=1e-5)
    assert np.abs(sparse - dense).max() > 1e-2     # and the selector matters


@pytest.mark.parametrize("fault", ["router_bf16", "selector_dropped",
                                   "selection_by_wrong_scores"])
def test_each_fault_fails_the_float32_check(params, tokens, reference, fault,
                                            monkeypatch):
    """What the check must catch (the chip's tolerance tells the last two
    apart, PERF.md; a bfloat16 router only this float32 comparison does): each
    moves the chunked-history logits far past 1e-4."""
    kw = {}
    if fault == "router_bf16":
        real = deepseek.route

        def route(layer, config, flat):
            rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
            return real(dict(layer, router=rounded(layer["router"])), config,
                        rounded(flat))
        monkeypatch.setattr(deepseek, "route", route)
    elif fault == "selector_dropped":
        kw["dense_attention"] = True
    else:
        real = deepseek._select_bias
        monkeypatch.setattr(
            deepseek, "_select_bias", lambda scores, k, use_pallas: real(
                jnp.where(scores > 0.5 * mla.NEG_INF, -scores, mla.NEG_INF), k,
                use_pallas))
    with jax.default_matmul_precision("highest"):
        got, _ = chunked(params, tokens, 32, **kw)
    err = np.abs(got - np.asarray(reference["logits"])[:T]).max()
    assert err > 1e-2, err


def test_selected_set_is_the_references_where_margins_are_clear(params, tokens,
                                                                reference):
    """Layer 0's selection bias against the reference's sorted top-k."""
    with jax.default_matmul_precision("highest"):
        tok, pos = block(tokens, 0, T, 128)
        layer = params["layers"][0]
        x = params["embed"][tok]
        h = llama.rms_norm(x, layer["attn_norm"], CFG.norm_eps)
        q_abs, latent, k_idx, q_idx, w_idx = deepseek._project(
            layer, CFG, h, jnp.maximum(pos, 0))
        kv = kv_mod.write_latent_kv(fresh_cache(), 0, latent, k_idx, SLOT,
                                    jnp.maximum(pos, 0), pos >= 0)
        _, bias = deepseek._attention(0, CFG, q_abs, q_idx, w_idx, kv,
                                      kv.block_tables, pos, use_pallas=False)
    got = np.asarray(bias)[0, :T, :T] > -1e29
    want = np.asarray(reference["selected"][0])[:T, :T]
    assert float(reference["index_margin"].min()) > 0      # no ties at this seed
    np.testing.assert_array_equal(got, want)
    assert got.sum(axis=1).tolist() == [min(t + 1, CFG.index_topk) for t in range(T)]


def test_router_is_sigmoid_bias_corrected_and_group_limited(params):
    layer = params["layers"][1]
    flat = jax.random.normal(jax.random.PRNGKey(5), (33, CFG.dim), jnp.float32)
    ids, weights, biased = (np.asarray(a) for a in deepseek.route(layer, CFG, flat))
    scores = 1 / (1 + np.exp(-(np.asarray(flat) @ np.asarray(layer["router"]))))
    per_group = CFG.n_routed_experts // CFG.n_group
    for t in range(len(flat)):
        b = scores[t] + np.asarray(layer["router_bias"])
        np.testing.assert_allclose(biased[t], b, atol=1e-5)
        groups = b.reshape(CFG.n_group, per_group)
        kept = np.argsort(-np.sort(groups, axis=1)[:, -2:].sum(1))[:CFG.topk_group]
        allowed = [g * per_group + e for g in kept for e in range(per_group)]
        want = sorted(allowed, key=lambda e: -b[e])[:CFG.moe_top_k]
        assert sorted(ids[t].tolist()) == sorted(want)
        picked = scores[t][ids[t]]         # weights from the UNBIASED scores
        np.testing.assert_allclose(
            weights[t], picked / picked.sum() * CFG.routed_scaling_factor, rtol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test: the held-experts parts over all 4 shares of the
    test model (4 of 16 experts each), the shared expert counted once, add up
    to what the uncut layer gives, and to the plain reference's uncut layer."""
    whole = dataclasses.replace(CFG, experts_held=(0, CFG.n_routed_experts))
    layer = deepseek.init_layer(whole, jax.random.PRNGKey(3), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, CFG.dim), jnp.float32)
    valid = jnp.ones((2, 24), bool)
    with jax.default_matmul_precision("highest"):
        uncut, pairs = deepseek._expert_ffn(layer, whole, x, valid)
        assert float(pairs) == 2 * 24 * CFG.moe_top_k      # every pair is local
        shared = llama._ffn({"w1": layer["shared_w1"], "w3": layer["shared_w3"],
                             "w2": layer["shared_w2"]}, x)
        total, held_pairs = jnp.zeros_like(uncut), 0.0
        for lo in range(0, CFG.n_routed_experts, 4):
            share = dataclasses.replace(CFG, experts_held=(lo, lo + 4))
            part = dict(layer, **{w: layer[w][lo:lo + 4] for w in ("w1", "w3", "w2")})
            out, n = deepseek._expert_ffn(part, share, x, valid)
            total, held_pairs = total + out - shared, held_pairs + float(n)
        want, _ = plain._experts(layer, whole, x.reshape(-1, CFG.dim))
    assert held_pairs == 2 * 24 * CFG.moe_top_k
    np.testing.assert_allclose(total + shared, uncut, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(uncut.reshape(-1, CFG.dim), want, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["grouped", "grouped_pallas", "dense"])
def test_expert_formulations_agree(params, impl):
    """Grouped (XLA), the grouped kernel (interpreted) and the expert scan
    compute one function of the routed pairs that land on held experts."""
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 40, CFG.dim), jnp.float32)
    valid = jnp.ones((1, 40), bool)
    with jax.default_matmul_precision("highest"):
        want, pairs = deepseek._expert_ffn(
            layer, dataclasses.replace(CFG, moe_impl="dense"), x, valid)
        got, got_pairs = deepseek._expert_ffn(
            layer, dataclasses.replace(CFG, moe_impl=impl), x, valid)
    assert float(pairs) == float(got_pairs) > 0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_plan_drops_pairs_held_elsewhere_and_counts_live_blocks():
    ids = jnp.asarray([[0, 5], [7, 1], [9, 0], [2, -3]], jnp.int32)    # E = 3 here
    gates = jnp.arange(8, dtype=jnp.float32).reshape(4, 2) + 1
    plan = plan_sorted_blocks(ids, gates, 3, block=2)
    live = np.asarray(plan["row_valid"]) > 0
    assert int(live.sum()) == 4                      # (0,0) (1,1) (2,0) (3,2)
    assert int(plan["live_blocks"][0]) == 3          # expert 0: 2 rows, 1: 1, 2: 1
    assert np.asarray(plan["block_expert"])[:3].tolist() == [0, 1, 2]
    stacks = {"w1": jnp.ones((3, 4, 6)), "w3": jnp.ones((3, 4, 6)),
              "w2": jnp.ones((3, 6, 4))}
    x = jnp.ones((4, 4))
    xla = experts_grouped(stacks, x, plan, impl="xla", block=2)
    kernel = experts_grouped(stacks, x, plan, impl="pallas", block=2, interpret=True)
    np.testing.assert_allclose(kernel, xla, rtol=1e-6)
    assert np.isfinite(np.asarray(kernel)).all()     # padding blocks wrote zeros


# ------------------------------------------------------------------ kernels

def _paged(key, batch, dim, contexts, table=TABLE):
    pages = jax.random.normal(key, (2, 1 + batch * table, PAGE, dim), jnp.float32)
    tables = np.zeros((batch, table), np.int32)
    for b, n in enumerate(contexts):
        used = -(-n // PAGE)
        tables[b, :used] = 1 + b * table + np.arange(used)
    return pages, jnp.asarray(tables)


def test_index_kernel_and_selection_kernel_match_their_references():
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    B, S, Hi, Di = 2, 128, 16, 128      # 16 heads: no two scores tie
    hist = [0, 70 - 32]
    pos = np.stack([np.arange(h, h + S) for h in hist]).astype(np.int32) % (TABLE * PAGE)
    pos = np.sort(pos, axis=1)
    pos[1, 100:] = -1                                    # a padded tail
    pos = jnp.asarray(np.minimum(pos, TABLE * PAGE - 1))
    pages, tables = _paged(ks[0], B, Di, [TABLE * PAGE] * B)
    q = jax.random.normal(ks[1], (B, S, Hi, Di), jnp.float32)
    w = jax.random.normal(ks[2], (B, S, Hi), jnp.float32)
    got = mla.sparse_index_scores_pallas(q, w, pages, tables, pos, layer=1,
                                         interpret=True)
    want = mla.index_scores_reference(
        q, w, kv_mod.gather_pool(pages, 1, tables), pos)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-4)
    k = 16
    flat = want.reshape(B * S, -1)
    thr = mla.sparse_select_pallas(flat, k, interpret=True)
    np.testing.assert_array_equal(thr, mla.topk_threshold_reference(flat, k))
    np.testing.assert_array_equal(thr[:, 0], jax.lax.top_k(flat, k)[0][:, -1])
    chosen = (flat >= thr) & (flat > 0.5 * mla.NEG_INF)
    visible = np.asarray((flat > 0.5 * mla.NEG_INF).sum(axis=1))
    assert chosen.sum(axis=1).tolist() == np.minimum(visible, k).tolist()


# The attention kernel holds a KV block of N table pages a grid step
# (mla._kv_block_pages: 8 for these 128-row blocks, or the largest divisor of
# the table width under it): (table width, the two rows' contexts). A context
# that ends inside a block leaves its tail dead (slots fall back to pages they
# held), one that ends before a block leaves the block dead.
LATENT_WALKS = {
    "w8": (TABLE, [TABLE * PAGE, 40]),
    "w4": (4, [4 * PAGE, 23]),
    "w128-dead-trailing-blocks": (128, [128 * PAGE, 8 * PAGE * 5 + 3]),
    "w6-blocks-of-6": (6, [6 * PAGE, PAGE + 1]),
    "w12-blocks-of-6": (12, [12 * PAGE, 7 * PAGE + 2]),
    "w7-one-block-of-7": (7, [7 * PAGE, 50]),
    "w11-a-page-a-step": (11, [11 * PAGE, 50]),
}


@pytest.mark.parametrize("walk", sorted(LATENT_WALKS))
@pytest.mark.parametrize("decode", [False, True])
def test_latent_attention_kernel_matches_its_reference(decode, walk):
    table, contexts = LATENT_WALKS[walk]
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    B, G, R, Dk, Dv = 2, 2, 128, 48, 32
    pages, tables = _paged(ks[0], B, Dk, contexts, table)
    C = table * PAGE
    if decode:      # one bias row for all of a query's heads
        q = jax.random.normal(ks[1], (B, 1, R, Dk), jnp.float32) * 0.3
        last = jnp.asarray([[c - 1] for c in contexts], jnp.int32)
        visible = jnp.arange(C)[None, None, :] <= last[:, :, None]
    else:           # the last R positions of each context
        q = jax.random.normal(ks[1], (B, G, R, Dk), jnp.float32) * 0.3
        pos = jnp.asarray(np.stack([np.maximum(np.arange(c - R, c), 0)
                                    for c in contexts]), jnp.int32)
        last = jnp.max(pos, axis=1, keepdims=True)
        visible = jnp.arange(C)[None, None, :] <= pos[:, :, None]
    keep = jax.random.bernoulli(ks[2], 0.3, visible.shape) & visible
    keep = keep.at[..., 0].set(True)                 # every row selects something
    bias = jnp.where(keep, 0.0, mla.NEG_INF).astype(jnp.float32)
    got = mla.mla_paged_attention_pallas(q, bias, pages, tables, last, layer=1,
                                         value_dim=Dv, interpret=True)
    want = mla.mla_attention_reference(q, bias, kv_mod.gather_pool(pages, 1, tables), Dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("decode", [False, True])
def test_a_block_a_row_selects_nothing_of_counts_nothing(decode):
    """Rows that select nothing of the first KV block (``m`` stays NEG_INF
    there: exp(0) must not count) and something later; rows that select
    nothing at all read zeros, never NaN."""
    table, Dk, Dv, B, R = 16, 48, 32, 2, 128
    block = mla._kv_block_pages(table, R, PAGE) * PAGE
    assert block < table * PAGE                      # more than one block
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    pages, tables = _paged(ks[0], B, Dk, [table * PAGE] * B, table)
    C = table * PAGE
    rows = 1 if decode else R
    keep = jax.random.bernoulli(ks[2], 0.2, (B, rows, C))
    keep = keep.at[0, :, :block].set(False)          # nothing of block 0
    keep = keep.at[1, rows // 2:].set(False)         # nothing at all
    keep = keep.at[0, :, block + 3].set(True)
    bias = jnp.where(keep, 0.0, mla.NEG_INF).astype(jnp.float32)
    q = jax.random.normal(ks[1], (B, 1 if decode else 2, R, Dk), jnp.float32) * 0.3
    last = jnp.full((B, 1), C - 1, jnp.int32)
    got = np.asarray(mla.mla_paged_attention_pallas(
        q, bias, pages, tables, last, layer=1, value_dim=Dv, interpret=True))
    want = mla.mla_attention_reference(q, bias, kv_mod.gather_pool(pages, 1, tables), Dv)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    nothing = got[1] if decode else got[1, :, rows // 2:]
    assert not nothing.any()
    assert got[0].any()


def test_latent_kv_block_is_a_divisor_of_any_table_width():
    for width in range(1, 130):
        for rows in (128, 256):
            n = mla._kv_block_pages(width, rows, 128)
            assert 1 <= n <= mla._ATTN_BLOCK_PAGES and width % n == 0
    # the cell's tables: chunk blocks of 256 rows, decode blocks of 128
    assert [mla._kv_block_pages(w, 256, 128) for w in (8, 128)] == [4, 4]
    assert [mla._kv_block_pages(w, 128, 128)
            for w in (4, 8, 16, 32, 64, 128)] == [4, 8, 8, 8, 8, 8]


# ------------------------------------------------------------ cache and seam

def test_the_cache_counts_the_pools_a_family_declares():
    assert [(p.name, p.shape) for p in kv_mod.kv_pools(CFG)] == [
        ("latent", (CFG.latent_dim,)), ("index_key", (CFG.index_head_dim,))]
    assert kv_mod.kv_page_bytes(CFG, PAGE, jnp.float32) == (
        CFG.n_layers * PAGE * (CFG.latent_dim + CFG.index_head_dim) * 4)
    mistral = MODEL_CONFIGS["mistral-7b"]
    assert [p.name for p in kv_mod.kv_pools(mistral)] == ["k", "v"]
    assert kv_mod.kv_page_bytes(mistral, 128) == 2 * 32 * 128 * 8 * 128 * 2
    assert kv_mod.kv_page_bytes(mistral, 128, quant="int8") == (
        2 * 32 * 128 * 8 * 128 + 2 * 32 * 8 * 2)
    assert isinstance(deepseek.init_kv_state(CFG, 4, PAGE, 1, 2), kv_mod.LatentKVState)
    assert isinstance(llama.init_kv_state(MODEL_CONFIGS["llama3-test"], 4, PAGE, 1, 2),
                      kv_mod.PagedKVState)
    with pytest.raises(NotImplementedError, match="full precision"):
        kv_mod.kv_page_bytes(CFG, PAGE, quant="int8")
    assert deepseek.param_count(CFG) == sum(
        a.size for a in jax.tree.leaves(deepseek.init_params(CFG, jax.random.PRNGKey(0))))


CELL = dataclasses.replace(      # deepseek-v3.2-d5-ep16's cache widths
    CFG, name="cell-widths", n_layers=5, kv_lora_rank=512, qk_rope_head_dim=64,
    index_head_dim=128)


@pytest.mark.parametrize("config, declared, stored", [
    # the latent family: declared 576 (1152 B a token a layer in bfloat16),
    # stored 640; the selector's 128 comes out of the same rule unchanged
    (CELL, 5 * (576 + 128) * 2,
     {"latent_pages": (5, 6, 128, 640), "index_pages": (5, 6, 128, 128)}),
    (CFG, 3 * (48 + 16) * 2,
     {"latent_pages": (3, 6, 128, 128), "index_pages": (3, 6, 128, 128)}),
    (MODEL_CONFIGS["deepseek-mtp-test"], 4 * 48 * 2,
     {"latent_pages": (4, 6, 128, 128)}),
    # the three other families' pools have the shapes they had
    (MODEL_CONFIGS["mistral-7b"], 2 * 32 * 8 * 128 * 2,
     {"k_pages": (32, 6, 128, 8, 128), "v_pages": (32, 6, 128, 8, 128)}),
    (MODEL_CONFIGS["llama3-test"], None, None),
    (MODEL_CONFIGS["olmo-hybrid-test"], None, None),
    (MODEL_CONFIGS["sdar-test"], None, None),
], ids=lambda v: getattr(v, "name", None))
def test_what_a_pool_declares_and_what_it_stores(config, declared, stored):
    """``kv_pools`` / ``kv_page_bytes`` count the vector a family declares;
    ``init_kv_state`` stores the latent family's padded to whole lanes and
    every other pool as declared (None: the declared shapes themselves)."""
    pools = [p for p in kv_mod.kv_pools(config) if p.per == "token"]
    state = jax.eval_shape(lambda: family_of(config).init_kv_state(
        config, 6, 128, 2, 4, dtype=jnp.bfloat16))
    if stored is None:
        layers = lambda p: p.layers or config.n_layers
        stored = {f"{p.name}_pages": (layers(p), 6, 128, *p.shape) for p in pools}
        declared = sum(layers(p) * int(np.prod(p.shape)) for p in pools) * 2
    assert kv_mod.kv_page_bytes(config, 1, jnp.bfloat16) == declared
    got = {name: a.shape for name, a in state._asdict().items()
           if a is not None and name.endswith("_pages")}
    assert got == stored
    resident = sum(2 * int(np.prod(shape)) for shape in stored.values())
    assert kv_mod.kv_resident_bytes(state) == resident >= 6 * 128 * declared
    assert [kv_mod.stored_width(d) for d in (1, 48, 128, 576, 640, 641)] == [
        128, 128, 128, 640, 640, 768]


def _engine(model, devices=1, **kw):
    return TPUEngine(EngineConfig(
        model=model, max_seq_len=512, page_size=PAGE, num_pages=96,
        prefill_buckets=(32,), prefill_max_batch=2, max_batch=4, dtype="float32",
        **kw), devices=jax.devices()[:devices])


def test_the_family_comes_from_the_model_configs_class_alone():
    assert family_of(MODEL_CONFIGS["mistral-7b"]) is llama
    assert family_of(CFG) is deepseek
    assert not llama.STEP_AUX and deepseek.STEP_AUX
    with pytest.raises(TypeError, match="no model family"):
        family_of(object())


@pytest.mark.parametrize("step", ["prefill", "prefill_hist", "decode"])
def test_the_seam_leaves_the_gqa_trunks_step_programs_alone(step):
    """A ``LlamaConfig`` engine's step functions trace to exactly what a direct
    call of ``models/llama.py`` (the engine before the seam) traces to: same
    jaxpr, no extra output."""
    from mcp_context_forge_tpu.tpu_local.sampling import SamplingParams, sample_tokens

    engine = _engine("llama3-test")
    cfg, B, S = engine.model_config, 2, 32
    samp = SamplingParams(jnp.zeros((B,)), jnp.zeros((B,), jnp.int32), jnp.ones((B,)))
    key = jax.random.PRNGKey(0)
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)
    if step == "decode":
        args = (engine.params, engine.kv, ints(B), ints(B), ints(B), ints(B) + 1,
                ints(B) + 1, ints(B, 4) - 1, samp, key)

        def direct(params, kv, tok, pos, slots, lens, budgets, stops, samp, key):
            logits, kv = llama.decode_step(
                params, cfg, tok, pos, kv, slots, lens, ctx_pages=4,
                write_mask=(lens > 0), paged_impl="gather", mesh=engine.mesh)
            return logits, kv

        ours = jax.make_jaxpr(lambda *a: engine._decode_and_sample(
            *a, ctx_pages=4, k=1))(*args)
        assert len(ours.out_avals) == 3 + len(jax.tree.leaves(engine.kv))
        # the model call inside the scan is the direct call's
        inner = jax.make_jaxpr(direct)(*args)
        text = str(ours)
        for eqn in inner.eqns:
            assert eqn.primitive.name in text
        return
    args = (engine.params, engine.kv, ints(B, S), ints(B, S) - 1, ints(B), ints(B),
            samp, key)
    if step == "prefill":
        def direct(params, kv, tok, pos, slots, last, samp, key):
            logits, kv = llama.prefill(params, cfg, tok, pos, kv, slots,
                                       attn_impl="reference", mesh=engine.mesh,
                                       last_idx=last)
            return sample_tokens(logits, samp, key), kv
        ours = jax.make_jaxpr(engine._prefill_and_sample)(*args)
    else:
        def direct(params, kv, tok, pos, slots, last, samp, key):
            logits, kv = llama.prefill_with_history(
                params, cfg, tok, pos, kv, slots, ctx_pages=4, last_idx=last,
                paged_impl="gather", mesh=engine.mesh)
            return sample_tokens(logits, samp, key), kv
        ours = jax.make_jaxpr(lambda *a: engine._prefill_hist_and_sample(
            *a, ctx_pages=4))(*args)
    assert str(ours) == str(jax.make_jaxpr(direct)(*args))


@pytest.mark.parametrize("setting, reason", [
    ({"mesh_shape": "1x2"}, "more than one device on the model axis"),
    ({"spec_decode": True}, "spec_decode"),
    ({"sp_impl": "ring"}, "sequence-parallel"),
    ({"kv_quant": "int8"}, "latent pools are full precision"),
    ({"quant": "int8"}, "no int8 path"),
    ({"prefix_tiers": True}, "KV tiers"),
])
def test_what_the_family_cannot_serve_is_refused_with_the_reason(setting, reason):
    with pytest.raises(NotImplementedError, match=reason):
        _engine("deepseek-test", devices=2 if "mesh_shape" in setting else 1,
                **setting)


def test_the_engine_serves_the_family_and_reads_its_counts_back():
    engine = _engine("deepseek-test")
    prompt = [1] + list(range(40, 140))

    async def run():
        await engine.start()
        try:
            gen = lambda p: _collect(engine, p)
            first = await asyncio.gather(gen(prompt), gen(prompt[:20]), gen(prompt[:75]))
            again = await gen(prompt)
            return first, again
        finally:
            await engine.stop()

    first, again = asyncio.run(run())
    assert again == first[0] and all(len(t) == 6 for t in first)
    # the repeated prompt took its full pages from the prefix cache, and what
    # every step wrote (chunks, suffix, decode) left the pools' tails zero
    assert engine.allocator.prefix_hits > 0
    for pages, width in ((engine.kv.latent_pages, CFG.latent_dim),
                         (engine.kv.index_pages, CFG.index_head_dim)):
        pages = np.asarray(pages)
        assert pages[..., :width].any() and not pages[..., width:].any()
    assert engine.kv_bytes_capacity() == 96 * PAGE * 3 * (48 + 16) * 4
    assert engine.kv_bytes_resident() == 96 * PAGE * 3 * (128 + 128) * 4
    stats = engine.stats
    assert stats.prompt_tokens == 101 + 20 + 75 + 101 and stats.completion_tokens == 24
    # 2 expert layers: every prefilled prompt token and every decode input token
    # passes both (the repeated prompt's full pages come from the prefix cache)
    assert stats.moe_tokens >= 2 * (101 + 20 + 75)
    assert 0 < stats.moe_local_pairs < 4 * stats.moe_tokens
    steps = engine.timeline.snapshot()["step"]
    assert {s.kind for s in steps} >= {"chunk", "prefill", "decode"}
    assert all(s.counts is not None and 0 < s.counts.selected_share <= 1 for s in steps)
    # a 101-token prompt in chunks of 32: the last chunk's rows see 97-101
    # tokens and keep 8 of them
    assert min(s.counts.selected_share for s in steps if s.kind == "chunk") < 0.2
    assert sum(s.counts.moe_tokens for s in steps) == stats.moe_tokens


async def _collect(engine, prompt):
    return [t async for t in engine.generate(list(prompt), max_tokens=6)]


def test_llama_engines_steps_carry_no_counts():
    engine = _engine("llama3-test")

    async def run():
        await engine.start()
        try:
            return await _collect(engine, [1] + list(range(40, 100)))
        finally:
            await engine.stop()

    assert len(asyncio.run(run())) == 6
    assert all(s.counts is None for s in engine.timeline.snapshot()["step"])
    assert engine.stats.moe_tokens == 0
