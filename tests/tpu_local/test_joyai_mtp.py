"""The latent family WITHOUT a selector and WITH its multi-token-prediction
block (``models/deepseek.py``: ``index_topk`` 0, ``n_mtp_blocks`` 1) at a CPU
size: the model's and the block's logits held to the plain reference
(``benchmark/reference/joyai_flash_plain.py``) in float32 through chunked
prefill and verify-width steps over the latent cache, a rejected draft in
between; the latent kernel (interpreted) under a bias row a query position;
speculation with drafts made on the device lossless through the engine, with
accepted and rejected drafts; the expert shares of a layer and of the block's
layer adding up to the uncut reference; the cache's one pool; the selector
model's tree untouched; and what is still refused."""

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

from benchmark.reference import joyai_flash_plain as plain  # noqa: E402
from mcp_context_forge_tpu.tpu_local import kv as kv_mod  # noqa: E402
from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine  # noqa: E402
from mcp_context_forge_tpu.tpu_local.models import (MODEL_CONFIGS, deepseek,  # noqa: E402
                                                    llama)
from mcp_context_forge_tpu.tpu_local.models.configs import DeepseekConfig  # noqa: E402
from mcp_context_forge_tpu.tpu_local.ops import mla_attention as mla  # noqa: E402

CFG = MODEL_CONFIGS["deepseek-mtp-test"]
PAGE, TABLE, T, CHUNK, STEPS = 16, 8, 70, 32, 6
SLOT = jnp.zeros((1,), jnp.int32)


@pytest.fixture(scope="module")
def params():
    """The program's random tree with the norms outside the layers drawn
    from [0.5, 1.5]: at their initial ones a normed hidden state and a raw one
    are the same thing to the block's own norm, and a wrong order of the
    three norms could not show."""
    tree = deepseek.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    draw = lambda i: jax.random.uniform(jax.random.PRNGKey(20 + i), (CFG.dim,),
                                        jnp.float32, 0.5, 1.5)
    tree["final_norm"] = draw(0)
    tree["mtp"] = dict(tree["mtp"], enorm=draw(1), hnorm=draw(2), norm=draw(3))
    return tree


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (T + STEPS + 1,),
                                         0, CFG.vocab_size))


@pytest.fixture(scope="module")
def reference(params, tokens):
    return plain.trace(params, CFG, tokens.tolist())


def fresh_cache():
    kv = deepseek.init_kv_state(CFG, 1 + TABLE, PAGE, 1, TABLE, dtype=jnp.float32)
    return kv._replace(block_tables=jnp.arange(1, 1 + TABLE, dtype=jnp.int32)[None])


def both(params, kv, start, toks, follows, width, hidden_of=lambda h: h,
         impl="gather"):
    """The model's pass and the block's over one [1, width] block from
    ``start``: (main logits, draft logits) of its real rows, and the cache."""
    n = len(toks)
    tok, nxt = np.zeros((1, width), np.int32), np.zeros((1, width), np.int32)
    pos = np.full((1, width), -1, np.int32)
    tok[0, :n], nxt[0, :n], pos[0, :n] = toks, follows, np.arange(start, start + n)
    logits, kv, aux, hidden = deepseek.prefill_with_history(
        params, CFG, jnp.asarray(tok), jnp.asarray(pos), kv, SLOT,
        ctx_pages=TABLE, hidden=True, paged_impl=impl)
    drafts, kv, aux = deepseek.draft_step(
        params, CFG, hidden_of(hidden), jnp.asarray(nxt), jnp.asarray(pos), kv,
        SLOT, aux, ctx_pages=TABLE, paged_impl=impl)
    return np.asarray(logits)[0, :n], np.asarray(drafts)[0, :n], kv, aux


def served(params, tokens, overwrite=True, hidden_of=lambda h: h,
           impl="gather"):
    """The prompt in chunks with the block's pass beside each, then verify
    steps of the true token and a WRONG draft (whose entries the next step
    overwrites): main and draft logits of positions 0 .. T + STEPS - 1."""
    kv, main, draft = fresh_cache(), [], []
    for start in range(0, T, CHUNK):
        end = min(start + CHUNK, T)
        m, d, kv, _ = both(params, kv, start, tokens[start:end],
                           tokens[start + 1:end + 1], CHUNK, hidden_of, impl)
        main.append(m), draft.append(d)
    for p in range(T, T + STEPS):
        wrong = (int(tokens[p + 1]) + 1) % CFG.vocab_size
        m, d, kv, aux = both(params, kv, p, [tokens[p], wrong],
                             [tokens[p + 1], tokens[p + 1]], 2, hidden_of, impl)
        main.append(m[:1]), draft.append(d[:1])
        if not overwrite:       # the fault: the rejected position keeps its entries
            break
    return np.concatenate(main), np.concatenate(draft), kv, aux


# ------------------------------------------------------ against the reference

@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_model_and_block_equal_the_plain_reference_in_float32(
        params, tokens, reference, impl, monkeypatch):
    """(a) chunked prefill, then the verify-width path through the cache with
    a rejected draft before every step: the model's logits at every position
    and the block's (row i guesses token i + 2) are the reference's, by the
    gather path and by the latent kernel (interpreted); and the pool, stored
    wider than its vector, keeps zeros in the lanes past it."""
    if impl == "pallas":
        monkeypatch.setattr(mla, "mla_paged_attention_pallas", partial(
            mla.mla_paged_attention_pallas, interpret=True))
    with jax.default_matmul_precision("highest"):
        main, draft, kv, aux = served(params, tokens, impl=impl)
    pool = np.asarray(kv.latent_pages)
    assert pool.shape[-1] == kv_mod.stored_width(CFG.latent_dim) > CFG.latent_dim
    assert not pool[..., CFG.latent_dim:].any()
    # every layer and the block hold positions 0 .. T + STEPS: the last
    # rejected draft's entry is still there, dead by position
    held = pool[:, 1:, :, :CFG.latent_dim].any(axis=-1).sum(axis=(1, 2))
    assert (held == T + STEPS + 1).all(), held
    np.testing.assert_allclose(main, np.asarray(reference["logits"])[:T + STEPS],
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(draft,
                               np.asarray(reference["draft_logits"])[:T + STEPS],
                               atol=1e-4, rtol=1e-4)
    # the last verify step's counts: 2 positions through 2 expert layers and
    # the block's, every row attends to all it may see
    moe_tokens, pairs, share, rows = (float(v) for v in aux)
    assert (moe_tokens, share, rows) == (6.0, 2.0, 2.0) and 0 < pairs < 6 * CFG.moe_top_k


@pytest.mark.parametrize("fault", ["block_left_out", "normed_hidden",
                                   "rejected_entry_kept", "bfloat16_router"])
def test_each_fault_fails_the_float32_check(params, tokens, reference, fault,
                                            monkeypatch):
    """What the cell's tolerance must tell apart on the chip is told apart
    here at 1e-4: the block left out (the draft half = the main logits of the
    position before), the block fed the NORMED hidden state, a rejected
    position's entries never overwritten, a router that scores in bfloat16."""
    want_main = np.asarray(reference["logits"])[:T + STEPS]
    want_draft = np.asarray(reference["draft_logits"])[:T + STEPS]
    kwargs = {}
    if fault == "normed_hidden":
        kwargs["hidden_of"] = lambda h: llama.rms_norm(
            h, params["final_norm"], CFG.norm_eps)
    if fault == "bfloat16_router":
        real = deepseek.route

        def route(layer, config, flat):
            return real(dict(layer, router=layer["router"].astype(jnp.bfloat16)
                             .astype(jnp.float32)),
                        config, flat.astype(jnp.bfloat16))
        monkeypatch.setattr(deepseek, "route", route)
    with jax.default_matmul_precision("highest"):
        if fault == "rejected_entry_kept":
            # after a rejection at T + 1 the next step reads T + 1 WITHOUT
            # writing it again: a width-1 step at T + 2 over the stale entry
            _m, _d, kv, _ = served(params, tokens, overwrite=False)
            main, draft, _kv, _ = both(params, kv, T + 2, [tokens[T + 2]],
                                       [tokens[T + 3]], 2)
            want_main, want_draft = want_main[T + 2:T + 3], want_draft[T + 2:T + 3]
        else:
            main, draft, _kv, _ = served(params, tokens, **kwargs)
    if fault == "block_left_out":
        draft = np.concatenate([main[:1], main[:-1]])
    worst = max(np.abs(main - want_main).max(), np.abs(draft - want_draft).max())
    assert worst > 1e-2, fault


def test_the_latent_kernel_under_a_bias_row_a_position():
    """The paged latent kernel at verify width (``group_bias``): K query
    positions a row, each position's heads a group of rows under the
    position's own visibility, against the jnp reference; a padding position
    (sees nothing) reads zeros."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    B, K, H, Dk, Dv, table = 3, 2, 128, 48, 32, 8
    contexts = [100, 33, 17]
    pages = jax.random.normal(ks[0], (2, 1 + B * table, PAGE, Dk), jnp.float32)
    tables = np.zeros((B, table), np.int32)
    for b, n in enumerate(contexts):
        used = -(-n // PAGE)
        tables[b, :used] = 1 + b * table + np.arange(used)
    tables = jnp.asarray(tables)
    q = jax.random.normal(ks[1], (B, K, H, Dk), jnp.float32) * 0.3
    pos = np.asarray([[c - 2, c - 1] for c in contexts], np.int32)
    pos[2, 1] = -1                                   # a row without a draft
    bias = deepseek._visible_bias(jnp.asarray(pos), table * PAGE)
    got = np.asarray(mla.mla_paged_attention_pallas(
        q, bias, pages, tables, jnp.max(jnp.asarray(pos), axis=1, keepdims=True),
        layer=1, value_dim=Dv, interpret=True, group_bias=True))
    want = np.asarray(mla.mla_attention_reference(
        q.transpose(0, 2, 1, 3), bias, kv_mod.gather_pool(pages, 1, tables),
        Dv)).transpose(0, 2, 1, 3)
    live = pos >= 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=1e-4)
    assert not got[2, 1].any() and np.isfinite(got).all()
    with pytest.raises(ValueError, match="a bias row a group"):
        mla.mla_paged_attention_pallas(q, bias[:, :1], pages, tables,
                                       jnp.zeros((B, 1), jnp.int32), layer=1,
                                       value_dim=Dv, interpret=True, group_bias=True)


# ------------------------------------------------------------------- lossless

def _engine(model, **kw):
    return TPUEngine(EngineConfig(
        model=model, max_seq_len=512, page_size=PAGE, num_pages=96,
        prefill_buckets=(32,), prefill_max_batch=2, max_batch=4, dtype="float32",
        prefix_cache=False, **kw), devices=jax.devices()[:1])


def test_speculation_with_device_drafts_is_lossless(monkeypatch):
    """(b) Greedy generation with ``spec_decode`` on equals generation with it
    off, token for token, at a vocabulary of 16 (drafts agree by chance) over
    prompts that take the dense prefill and chunk rounds; accepted and
    rejected drafts both occur; sampled rows ride along at width 1; the
    step records carry the counts."""
    monkeypatch.setitem(MODEL_CONFIGS, "mtp-v16", dataclasses.replace(
        CFG, name="mtp-v16", vocab_size=16))
    spec, base = _engine("mtp-v16", spec_decode=True, spec_k=2), _engine("mtp-v16")
    assert spec._drafts and not base._drafts
    prompts = [[1, 2, 3, 4, 5], list(range(3, 15)) * 7, [7, 7, 2, 9] * 10]

    async def gen(engine, prompt, n=48, **kw):
        return [t async for t in engine.generate(list(prompt), max_tokens=n, **kw)]

    async def run():
        for engine in (spec, base):
            await engine.start()
        try:
            got = await asyncio.gather(*[gen(spec, p) for p in prompts])
            want = await asyncio.gather(*[gen(base, p) for p in prompts])
            mixed = await asyncio.gather(
                gen(spec, prompts[0], 12),
                gen(spec, prompts[2], 12, temperature=0.8, top_k=8))
            return got, want, mixed
        finally:
            for engine in (spec, base):
                await engine.stop()

    got, want, mixed = asyncio.run(run())
    assert got == want and all(len(t) == 48 for t in got)
    assert mixed[0] == want[0][:12] and len(mixed[1]) == 12
    stats = spec.stats
    accepted, rejected = stats.spec_accepted, stats.spec_drafted - stats.spec_accepted
    assert accepted > 0 and rejected > 0, (accepted, rejected)
    # an accepted draft is one more token out of its dispatch
    assert stats.spec_tokens == accepted
    assert stats.spec_steps < stats.completion_tokens - len(prompts) - 2
    steps = [s for s in spec.timeline.snapshot()["step"] if s.kind == "spec"]
    assert len(steps) == stats.spec_steps
    assert sum(s.counts.draft_rows for s in steps) == stats.spec_drafted
    assert sum(s.counts.drafts_accepted for s in steps) == accepted
    assert all(s.counts.draft_rows <= s.counts.draft_wanted <= s.rows for s in steps)
    # every token after a request's first came out of a verify step, but for
    # the plain steps that ran when no row could take a draft
    plain_steps = [s for s in spec.timeline.snapshot()["step"] if s.kind == "decode"]
    assert all(s.counts.draft_wanted == 0 for s in plain_steps)
    assert sum(s.counts.spec_tokens for s in steps) + sum(
        s.rows for s in plain_steps) == stats.completion_tokens - (len(prompts) + 2)
    # the step program is named as the family's decode step (a trace counts
    # it as one), the GQA trunk's verify keeps its own name
    assert all(fn.__wrapped__.__name__ == "_decode_and_sample_draft"
               for fn in spec._verify_fns.values())


# --------------------------------------------------------------------- shares

@pytest.mark.parametrize("which", ["layer", "block"])
def test_the_four_ep4_shares_add_up_to_the_uncut_reference(params, which):
    """(c) The guide's share test for an expert layer of the model and for
    the block's own layer: the held-experts parts over the 4 shares (4 of 16
    experts each), the shared expert counted once, add up to what the plain
    reference gives for the uncut layer."""
    whole = dataclasses.replace(CFG, experts_held=(0, CFG.n_routed_experts))
    layer = deepseek.init_layer(whole, jax.random.PRNGKey(3), jnp.float32)
    if which == "block":
        layer = deepseek.init_trunk(whole, jax.random.PRNGKey(3),
                                    jax.random.PRNGKey(5), jnp.float32)["mtp"]["layer"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, CFG.dim), jnp.float32)
    valid = jnp.ones((2, 24), bool)
    with jax.default_matmul_precision("highest"):
        shared = llama._ffn({"w1": layer["shared_w1"], "w3": layer["shared_w3"],
                             "w2": layer["shared_w2"]}, x)
        total, held_pairs = jnp.zeros_like(x), 0.0
        for lo in range(0, CFG.n_routed_experts, 4):
            share = dataclasses.replace(CFG, experts_held=(lo, lo + 4))
            part = dict(layer, **{w: layer[w][lo:lo + 4] for w in ("w1", "w3", "w2")})
            out, n = deepseek._expert_ffn(part, share, x, valid)
            total, held_pairs = total + out - shared, held_pairs + float(n)
        want, _ = plain._experts(layer, whole, x.reshape(-1, CFG.dim))
    assert held_pairs == 2 * 24 * CFG.moe_top_k
    np.testing.assert_allclose((total + shared).reshape(-1, CFG.dim), want,
                               atol=2e-5, rtol=1e-5)


# ---------------------------------------------------------- cache, tree, seam

JOYAI = DeepseekConfig(     # the published widths (benchmark/configs/joyai-...)
    name="joyai-widths", vocab_size=32320, dim=2048, n_layers=5, n_heads=32,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, ffn_hidden=7168, moe_ffn_hidden=768, n_routed_experts=256,
    experts_held=(0, 64), n_mtp_blocks=1, n_group=1, topk_group=1,
    rope_theta=32e6, rope_original_max=131072, max_seq_len=131072)


def test_the_selector_less_cache_is_one_pool_of_1152_bytes_a_token_a_layer():
    """(d) No index pool: ``c || k_rope`` alone, in the model's layers and the
    block's."""
    assert not JOYAI.has_selector and JOYAI.n_cache_layers == 6
    pools = kv_mod.kv_pools(JOYAI)
    assert [(p.name, p.shape, p.layers) for p in pools] == [("latent", (576,), 6)]
    assert kv_mod.kv_page_bytes(JOYAI, 1, jnp.bfloat16) == 6 * 1152
    assert kv_mod.kv_page_bytes(JOYAI, 128, jnp.bfloat16) == 884_736
    state = jax.eval_shape(lambda: deepseek.init_kv_state(
        JOYAI, 2304, 128, 32, 64, dtype=jnp.bfloat16))
    assert state.index_pages is None
    # stored padded to whole lanes: 2.04 GB declared, 2.26 GB resident
    assert state.latent_pages.shape == (6, 2304, 128, 640)
    assert kv_mod.kv_resident_bytes(state) == 2304 * 128 * 6 * 1280
    assert kv_mod.kv_logical("", JOYAI).index_pages is None
    # rope without scaling: plain frequencies, no YaRN factor on the scale
    assert deepseek.softmax_scale(JOYAI) == 192 ** -0.5
    np.testing.assert_allclose(deepseek.yarn_inv_freq(JOYAI)[:2],
                               [1.0, 32e6 ** (-2 / 64)], rtol=1e-6)


def test_the_tree_counts_the_block_and_no_selector():
    shapes = jax.eval_shape(lambda: deepseek.init_params(
        CFG, jax.random.PRNGKey(0), jnp.float32))
    assert sorted(shapes["mtp"]) == ["eh_proj", "enorm", "hnorm", "layer", "norm"]
    assert shapes["mtp"]["eh_proj"].shape == (2 * CFG.dim, CFG.dim)
    assert "router" in shapes["mtp"]["layer"]
    assert not any(k.startswith("idx_") for layer in shapes["layers"] for k in layer)
    assert deepseek.param_count(CFG) == sum(a.size for a in jax.tree.leaves(shapes))
    logical = deepseek.params_logical(CFG)
    assert jax.tree.structure(logical) == jax.tree.structure(shapes)


def test_the_selector_models_tree_and_count_are_what_they_were():
    """(e) ``deepseek-v3.2-d5-ep16`` (the benchmark's configuration file
    through its family): the same 108 leaves, the same keys a layer, the same
    count as before this family had optional parts, and two pools."""
    from benchmark.families import deepseek_v32
    from benchmark.harness import manifest

    cfg = deepseek_v32.model_config("ep16", manifest.read_json(os.path.join(
        REPO, "benchmark", "configs", "deepseek-v3.2-d5-ep16.json")))
    assert cfg.has_selector and cfg.n_mtp_blocks == 0 and cfg.n_cache_layers == 5
    shapes = jax.eval_shape(lambda: deepseek.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    assert "mtp" not in shapes and len(jax.tree.leaves(shapes)) == 108
    assert sorted(shapes["layers"][1]) == [
        "attn_norm", "ffn_norm", "idx_k_bias", "idx_k_norm", "idx_w", "idx_wk",
        "idx_wq_b", "kv_norm", "q_norm", "router", "router_bias", "shared_w1",
        "shared_w2", "shared_w3", "w1", "w2", "w3", "wkv_a", "wkv_b", "wo",
        "wq_a", "wq_b"]
    assert deepseek.param_count(cfg) == 4_635_518_208 == sum(
        a.size for a in jax.tree.leaves(shapes))
    assert [p.name for p in kv_mod.kv_pools(cfg)] == ["latent", "index_key"]
    assert kv_mod.kv_page_bytes(cfg, 1, jnp.bfloat16) == 5 * 1408
    assert not deepseek.drafts_on_device(cfg) and deepseek.drafts_on_device(CFG)


@pytest.mark.parametrize("model, setting, reason", [
    ("deepseek-mtp-test", {"spec_decode": True, "spec_k": 4},
     "spec_k=4: a verify step is the last token and one draft a block"),
    ("deepseek-mtp-test", {"mesh_shape": "1x2"}, "more than one device on the model axis"),
    ("deepseek-mtp-test", {"sp_impl": "ring"}, "sequence-parallel"),
    ("deepseek-mtp-test", {"kv_quant": "int8"}, "latent pools are full precision"),
    ("deepseek-mtp-test", {"quant": "int8"}, "no int8 path"),
    ("deepseek-mtp-test", {"prefix_tiers": True}, "KV tiers"),
    ("deepseek-test", {"spec_decode": True, "spec_k": 2},
     "the selector has no verify-width path"),
    ("deepseek-test", {"spec_decode": True, "spec_k": 2},
     "no multi-token-prediction block to draft with"),
])
def test_what_is_still_refused_and_why(model, setting, reason):
    """(f) ``spec_decode`` with ``spec_k`` 2 is served where the model has the
    block and no selector; everything else the family refused stays refused,
    each with its reason."""
    devices = jax.devices()[:2 if "mesh_shape" in setting else 1]
    with pytest.raises(NotImplementedError, match=reason):
        TPUEngine(EngineConfig(model=model, max_seq_len=512, page_size=PAGE,
                               num_pages=96, prefill_buckets=(32,), max_batch=4,
                               dtype="float32", **setting), devices=devices)
