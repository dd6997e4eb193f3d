"""Model correctness: prefill/decode over the paged cache must agree with a
single full-sequence forward (the classic incremental-decoding invariant)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local.kv import PageAllocator, init_kv_state
from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS
from mcp_context_forge_tpu.tpu_local.models.llama import (
    decode_step,
    init_params,
    param_count,
    params_logical,
    prefill,
)

CFG = MODEL_CONFIGS["llama3-test"]


def _setup(batch=2, max_slots=4, num_pages=32, page_size=16, pages_per_slot=8):
    params = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    kv = init_kv_state(CFG, num_pages, page_size, max_slots, pages_per_slot,
                       dtype=jnp.float32)
    alloc = PageAllocator(num_pages, page_size, max_slots, pages_per_slot)
    return params, kv, alloc


def test_param_count_matches_tree():
    params = init_params(CFG, jax.random.PRNGKey(0))
    total = sum(np.prod(x.shape) for x in jax.tree.leaves(params))
    assert total == param_count(CFG)


def test_logical_tree_matches_params():
    params = init_params(CFG, jax.random.PRNGKey(0))
    logical = params_logical(CFG)
    assert jax.tree.structure(params) == jax.tree.structure(logical)


def test_prefill_then_decode_matches_full_forward():
    params, kv, alloc = _setup()
    S, extra = 13, 5
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (1, S + extra), 0, CFG.vocab_size)

    # ground truth: prefill over the whole sequence, take per-position logits
    kv_full = init_kv_state(CFG, 32, 16, 4, 8, dtype=jnp.float32)
    alloc_full = PageAllocator(32, 16, 4, 8)
    assert alloc_full.allocate_slot(0, S + extra)
    kv_full = kv_full._replace(block_tables=alloc_full.tables())
    positions = jnp.arange(S + extra)[None, :]
    full_logits, _ = prefill(params, CFG, tokens, positions, kv_full,
                             jnp.array([0]), attn_impl="reference")

    # incremental: prefill first S, then decode the rest one token at a time
    assert alloc.allocate_slot(0, S + extra)
    kv = kv._replace(block_tables=alloc.tables())
    logits, kv = prefill(params, CFG, tokens[:, :S], positions[:, :S], kv,
                         jnp.array([0]), attn_impl="reference")
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(full_logits[0, S - 1]),
                               rtol=2e-4, atol=2e-4)
    for i in range(extra):
        pos = S + i
        step_logits, kv = decode_step(
            params, CFG, tokens[:, pos], jnp.array([pos]), kv,
            jnp.array([0]), jnp.array([pos + 1]))
        np.testing.assert_allclose(np.asarray(step_logits[0]),
                                   np.asarray(full_logits[0, pos]),
                                   rtol=2e-4, atol=2e-4)


def test_padding_does_not_leak_between_slots():
    """Two sequences in one prefill batch with different lengths: the padded
    tail of the short one must not change its logits."""
    params, kv, alloc = _setup()
    t1 = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, CFG.vocab_size)
    # alone
    assert alloc.allocate_slot(0, 16)
    kv0 = kv._replace(block_tables=alloc.tables())
    solo, _ = prefill(params, CFG, t1, jnp.arange(8)[None], kv0,
                      jnp.array([0]), attn_impl="reference")
    # batched with a longer sequence, padded to 16 with position -1
    assert alloc.allocate_slot(1, 16)
    kv1 = kv._replace(block_tables=alloc.tables())
    t2 = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0, CFG.vocab_size)
    tokens = jnp.concatenate([jnp.pad(t1, ((0, 0), (0, 8))), t2], axis=0)
    positions = jnp.stack([
        jnp.concatenate([jnp.arange(8), -jnp.ones(8, dtype=jnp.int32)]),
        jnp.arange(16),
    ])
    batched, _ = prefill(params, CFG, tokens, positions, kv1,
                         jnp.array([0, 1]), attn_impl="reference")
    np.testing.assert_allclose(np.asarray(batched[0, 7]), np.asarray(solo[0, 7]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("KV", [4, 2])  # MHA, and GQA through the index map
def test_flash_attention_matches_reference(KV):
    from mcp_context_forge_tpu.tpu_local.ops.attention import (
        attention_reference, flash_attention_pallas)
    B, S, H, hd = 2, 64, 4, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, hd), dtype=jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd), dtype=jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd), dtype=jnp.float32)
    valid = jnp.ones((B, S), dtype=bool).at[1, 50:].set(False)
    ref = attention_reference(q, k, v, valid)
    out = flash_attention_pallas(q, k, v, valid, block_q=32, block_k=32,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_page_allocator():
    alloc = PageAllocator(num_pages=8, page_size=4, max_slots=2, max_pages_per_slot=4)
    assert alloc.free_pages == 7  # page 0 reserved
    assert alloc.allocate_slot(0, 10)  # 3 pages
    assert alloc.pages_in_use == 3
    assert alloc.grow_slot(0, 13) >= 13    # 4 pages
    assert alloc.grow_slot(0, 17) < 17  # exceeds max_pages_per_slot
    assert alloc.allocate_slot(1, 12)  # 3 more
    assert alloc.free_pages == 0
    assert not alloc.can_allocate(1)
    alloc.free_slot(0)
    assert alloc.free_pages == 4
    table = np.asarray(alloc.tables())
    assert table.shape == (2, 4)
    assert (table[1][:3] > 0).all()


# ----------------------------------------------- model family: qwen2 knobs

def test_qwen2_family_param_tree_and_count():
    """attn_bias adds q/k/v bias vectors; tie_embeddings drops lm_head —
    param_count and the logical sharding tree must track both."""
    cfg = MODEL_CONFIGS["qwen2-tiny"]
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert "lm_head" not in params
    assert {"bq", "bk", "bv"} <= set(params["layers"][0])
    total = sum(np.prod(x.shape) for x in jax.tree.leaves(params))
    assert total == param_count(cfg)
    logical = params_logical(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(logical)


def test_tied_embeddings_head_is_embed_transpose():
    from mcp_context_forge_tpu.tpu_local.models.llama import lm_logits

    cfg = MODEL_CONFIGS["qwen2-tiny"]
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, cfg.dim), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(lm_logits(params, x)),
        np.asarray((x @ params["embed"].T).astype(jnp.float32)),
        rtol=1e-6)


def test_qwen2_prefill_decode_consistency():
    """The incremental-decoding invariant holds with biases + tied head."""
    cfg = MODEL_CONFIGS["qwen2-tiny"]
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # nonzero biases so the bias path actually participates
    for layer in params["layers"]:
        layer["bq"] = layer["bq"] + 0.03
        layer["bk"] = layer["bk"] - 0.02
        layer["bv"] = layer["bv"] + 0.01
    kv = init_kv_state(cfg, 32, 16, 4, 8, dtype=jnp.float32)
    alloc = PageAllocator(32, 16, 4, 8)
    S = 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, S), 0,
                                cfg.vocab_size)
    positions = jnp.arange(S)[None, :]
    assert alloc.allocate_slot(0, S + 1)
    kv = kv._replace(block_tables=alloc.tables())
    logits_full, kv = prefill(params, cfg, tokens, positions, kv,
                              jnp.array([0]), attn_impl="reference")

    next_token = jnp.argmax(logits_full[:, -1], axis=-1)
    logits_step, kv = decode_step(params, cfg, next_token,
                                  jnp.array([S]), kv, jnp.array([0]),
                                  jnp.array([S + 1]))
    # re-run prefill over the extended sequence: last-position logits agree
    kv2 = init_kv_state(cfg, 32, 16, 4, 8, dtype=jnp.float32)
    alloc2 = PageAllocator(32, 16, 4, 8)
    assert alloc2.allocate_slot(0, S + 1)
    kv2 = kv2._replace(block_tables=alloc2.tables())
    ext_tokens = jnp.concatenate([tokens, next_token[:, None]], axis=1)
    ext_positions = jnp.arange(S + 1)[None, :]
    logits_ext, _ = prefill(params, cfg, ext_tokens, ext_positions, kv2,
                            jnp.array([0]), attn_impl="reference")
    np.testing.assert_allclose(np.asarray(logits_step),
                               np.asarray(logits_ext[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_gemma_family_knobs_and_consistency():
    """Gemma knobs all at once — MQA, decoupled head_dim, GeGLU, scaled
    embeddings, (1+w) norms, tied head — preserve the incremental-decode
    invariant and actually change the forward (each knob is live)."""
    import dataclasses

    cfg = MODEL_CONFIGS["gemma-test"]
    assert cfg.head_dim == 32 and cfg.dim // cfg.n_heads == 16
    assert cfg.n_kv_heads == 1                       # MQA
    assert abs(cfg.embed_multiplier - 8.0) < 1e-9    # sqrt(64)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert "lm_head" not in params                   # tied
    assert params["layers"][0]["wq"].shape == (64, 4 * 32)
    assert params["layers"][0]["wk"].shape == (64, 1 * 32)

    kv = init_kv_state(cfg, 32, 16, 4, 8, dtype=jnp.float32)
    alloc = PageAllocator(32, 16, 4, 8)
    S = 11
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S + 1), 0,
                                cfg.vocab_size)
    positions = jnp.arange(S + 1)[None, :]
    assert alloc.allocate_slot(0, S + 1)
    kv = kv._replace(block_tables=alloc.tables())
    full_logits, _ = prefill(params, cfg, tokens, positions, kv,
                             jnp.array([0]), attn_impl="reference")

    kv2 = init_kv_state(cfg, 32, 16, 4, 8, dtype=jnp.float32)
    alloc2 = PageAllocator(32, 16, 4, 8)
    assert alloc2.allocate_slot(0, S + 1)
    kv2 = kv2._replace(block_tables=alloc2.tables())
    logits, kv2 = prefill(params, cfg, tokens[:, :S], positions[:, :S], kv2,
                          jnp.array([0]), attn_impl="reference")
    step_logits, kv2 = decode_step(params, cfg, tokens[:, S],
                                   jnp.array([S]), kv2, jnp.array([0]),
                                   jnp.array([S + 1]))
    np.testing.assert_allclose(np.asarray(step_logits[0]),
                               np.asarray(full_logits[0, S]),
                               rtol=2e-4, atol=2e-4)

    # every knob is LIVE: flipping it moves the logits
    base = np.asarray(full_logits[0, -1])
    for flip in ({"hidden_act": "silu"}, {"embed_scale": False},
                 {"norm_plus_one": False}):
        other = dataclasses.replace(cfg, **flip)
        alt_logits, _ = prefill(params, other, tokens, positions,
                                kv._replace(block_tables=alloc.tables()),
                                jnp.array([0]), attn_impl="reference")
        assert not np.allclose(base, np.asarray(alt_logits[0, -1])), flip


def test_gemma_train_and_pipeline_forwards_match_prefill():
    """Train-loop and pipeline forwards honor EVERY gemma knob — their
    logits must match the serving prefill exactly (review r4 caught the
    embedding scale missing from both)."""
    from mcp_context_forge_tpu.tpu_local.train import forward_logits

    cfg = MODEL_CONFIGS["gemma-test"]
    params = init_params(cfg, jax.random.PRNGKey(9), dtype=jnp.float32)
    S = 9
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, S), 0,
                                cfg.vocab_size)
    kv = init_kv_state(cfg, 32, 16, 4, 8, dtype=jnp.float32)
    alloc = PageAllocator(32, 16, 4, 8)
    assert alloc.allocate_slot(0, S)
    kv = kv._replace(block_tables=alloc.tables())
    ref_logits, _ = prefill(params, cfg, tokens,
                            jnp.arange(S)[None, :], kv, jnp.array([0]),
                            attn_impl="reference")
    train_logits = forward_logits(params, cfg, tokens)
    np.testing.assert_allclose(np.asarray(train_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)


def test_mixtral_moe_trunk_consistency():
    """MoE layers in the serving trunk: incremental decode matches the
    full prefill, and the routed FFN matches the dense per-token oracle
    (no capacity drops at this scale)."""
    from mcp_context_forge_tpu.tpu_local.parallel.moe import (
        MoEConfig, moe_ffn, moe_ffn_reference)

    cfg = MODEL_CONFIGS["mixtral-test"]
    params = init_params(cfg, jax.random.PRNGKey(17), dtype=jnp.float32)
    assert "router" in params["layers"][0]
    assert params["layers"][0]["w1"].shape == (4, 64, 96)

    # the layer's MoE output matches the reference per-token oracle with
    # drop-free capacity
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(19), (1, 6, cfg.dim),
                          dtype=jnp.float32)
    moe_cfg = MoEConfig(dim=cfg.dim, n_experts=cfg.n_experts,
                        expert_hidden=cfg.ffn_hidden, top_k=cfg.moe_top_k,
                        capacity_factor=8.0)  # no drops: exact match
    sub = {k: layer[k] for k in ("router", "w1", "w3", "w2")}
    np.testing.assert_allclose(
        np.asarray(moe_ffn(sub, x, moe_cfg)),
        np.asarray(moe_ffn_reference(sub, x, moe_cfg)),
        rtol=2e-4, atol=2e-4)

    # incremental-decode invariant through the full MoE trunk
    kv = init_kv_state(cfg, 32, 16, 4, 8, dtype=jnp.float32)
    alloc = PageAllocator(32, 16, 4, 8)
    S = 10
    tokens = jax.random.randint(jax.random.PRNGKey(23), (1, S + 1), 0,
                                cfg.vocab_size)
    positions = jnp.arange(S + 1)[None, :]
    assert alloc.allocate_slot(0, S + 1)
    kv = kv._replace(block_tables=alloc.tables())
    full_logits, _ = prefill(params, cfg, tokens, positions, kv,
                             jnp.array([0]), attn_impl="reference")
    kv2 = init_kv_state(cfg, 32, 16, 4, 8, dtype=jnp.float32)
    alloc2 = PageAllocator(32, 16, 4, 8)
    assert alloc2.allocate_slot(0, S + 1)
    kv2 = kv2._replace(block_tables=alloc2.tables())
    _, kv2 = prefill(params, cfg, tokens[:, :S], positions[:, :S], kv2,
                     jnp.array([0]), attn_impl="reference")
    step_logits, _ = decode_step(params, cfg, tokens[:, S], jnp.array([S]),
                                 kv2, jnp.array([0]), jnp.array([S + 1]))
    # NOTE: routing depends only on each token's own hidden state, so
    # decode-time routing matches prefill routing exactly (same capacity
    # caveat: B=1 decode never drops)
    np.testing.assert_allclose(np.asarray(step_logits[0]),
                               np.asarray(full_logits[0, S]),
                               rtol=2e-3, atol=2e-3)


def test_moe_aux_loss_trains_against_collapse():
    """The router load-balancing aux loss is live: a collapsed router
    (all tokens to one expert) scores ~E, a balanced one ~1, and
    train_step carries it into the gradient."""
    from mcp_context_forge_tpu.tpu_local.train import forward_logits, loss_fn

    cfg = MODEL_CONFIGS["mixtral-test"]
    params = init_params(cfg, jax.random.PRNGKey(37), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(41), (2, 8), 0,
                                cfg.vocab_size)
    _, aux = forward_logits(params, cfg, tokens, return_aux=True)
    assert 0.9 < float(aux) < float(cfg.n_experts) + 0.1

    # collapse the routers: aux approaches E (the penalty maximum)
    collapsed = jax.tree.map(lambda x: x, params)
    for layer in collapsed["layers"]:
        router = np.zeros(np.asarray(layer["router"]).shape, np.float32)
        router[:, 0] = 10.0
        layer["router"] = jnp.asarray(router)
    _, aux_collapsed = forward_logits(collapsed, cfg, tokens,
                                      return_aux=True)
    # skew (even partial: the shared direction can't dominate every
    # token's hidden state) must score WORSE than the balanced router
    assert float(aux_collapsed) > float(aux)

    # the aux term is IN the objective: zero vs nonzero weight changes
    # the loss by exactly weight * aux (CE gradients alone also reach the
    # router through the routing weights, so "router moved" would be a
    # vacuous check)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, dtype=jnp.float32)
    loss_off = loss_fn(params, cfg, tokens, targets, mask,
                       moe_aux_weight=0.0)
    loss_on = loss_fn(params, cfg, tokens, targets, mask,
                      moe_aux_weight=0.5)
    np.testing.assert_allclose(float(loss_on - loss_off),
                               0.5 * float(aux), rtol=1e-4)
