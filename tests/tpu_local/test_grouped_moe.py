"""Dropless grouped-GEMM MoE (round-4 VERDICT next #4).

The block-sparse formulation must compute EXACTLY the dense-mask
formulation's per-token function (the continuous-batching invariant
rides on it) at ~top_k/n_experts of the dense FLOPs, with the Pallas
kernel (interpreter mode on CPU) agreeing with the XLA reference path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local.ops.grouped_moe import (
    _expert_blocks_pallas, _expert_blocks_xla, experts_grouped, grouped_flops,
    moe_ffn_grouped,
    plan_sorted_blocks, top_k_gates)
from mcp_context_forge_tpu.tpu_local.parallel.moe import (
    MoEConfig, init_moe_params, moe_ffn_dense_mask, router_probs)

CFG = MoEConfig(dim=32, n_experts=8, expert_hidden=64, top_k=2)


def _params(seed=0, dtype=jnp.float32):
    return init_moe_params(CFG, jax.random.PRNGKey(seed), dtype=dtype)


def _x(shape=(2, 24), seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (*shape, CFG.dim), dtype=jnp.float32)


def test_routing_plan_invariants():
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(3), (50, CFG.n_experts)),
        axis=-1)
    plan = plan_sorted_blocks(*top_k_gates(probs, CFG.top_k), CFG.n_experts,
                              block=16)
    NB = plan["block_expert"].shape[0]
    assert NB == -(-50 * CFG.top_k // 16) + CFG.n_experts
    valid = np.asarray(plan["row_valid"])
    assert valid.sum() == 50 * CFG.top_k          # dropless: every pair
    # every live row's block belongs to the expert that row routed to
    block_expert = np.asarray(plan["block_expert"])
    tokens = np.asarray(plan["sorted_token"])
    gates = np.asarray(plan["gates"])
    _, top_idx = jax.lax.top_k(probs, CFG.top_k)
    routed = {(int(t), int(e))
              for t, row in enumerate(np.asarray(top_idx)) for e in row}
    for row in np.nonzero(valid)[0]:
        expert = block_expert[row // 16]
        assert (tokens[row], expert) in routed
        assert gates[row] > 0
    # gates of each token sum to 1 (renormalized top-k)
    sums = np.zeros(50)
    for row in np.nonzero(valid)[0]:
        sums[tokens[row]] += gates[row]
    np.testing.assert_allclose(sums, 1.0, rtol=1e-5)


def test_grouped_xla_matches_dense_mask_oracle():
    params = _params()
    x = _x()
    dense = moe_ffn_dense_mask(params, x, CFG)
    grouped = moe_ffn_grouped(params, x, CFG, impl="xla", block=16)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_grouped_pallas_interpret_matches_xla():
    params = _params()
    x = _x()
    xla = moe_ffn_grouped(params, x, CFG, impl="xla", block=16)
    pallas = moe_ffn_grouped(params, x, CFG, impl="pallas", block=16,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(xla),
                               rtol=2e-5, atol=2e-6)


def test_batch_shape_invariance():
    """The dropless property that matters for serving: prefill+decode
    must equal one long prefill — per-token outputs are independent of
    how tokens are batched."""
    params = _params()
    x = _x((1, 48), seed=7)
    together = moe_ffn_grouped(params, x, CFG, impl="xla", block=16)
    first = moe_ffn_grouped(params, x[:, :31], CFG, impl="xla", block=16)
    rest = moe_ffn_grouped(params, x[:, 31:], CFG, impl="xla", block=16)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([first, rest], axis=1)),
        np.asarray(together), rtol=2e-5, atol=2e-6)


def test_extreme_skew_is_dropless():
    """All tokens routed to ONE expert (the capacity formulation's worst
    case): the grouped path must still match the oracle exactly."""
    params = _params()
    # a router that sends everything to expert 3 with top-2 = {3, then 0}
    router = np.zeros((CFG.dim, CFG.n_experts), np.float32)
    router[:, 3] = 1.0
    params["router"] = jnp.asarray(router)
    x = jnp.abs(_x((1, 40), seed=9)) + 0.1   # positive => logits skew to 3
    dense = moe_ffn_dense_mask(params, x, CFG)
    grouped = moe_ffn_grouped(params, x, CFG, impl="xla", block=16)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)
    pallas = moe_ffn_grouped(params, x, CFG, impl="pallas", block=16,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_gelu_activation_parity():
    params = _params()
    x = _x()
    dense = moe_ffn_dense_mask(params, x, CFG, act="gelu")
    grouped = moe_ffn_grouped(params, x, CFG, act="gelu", impl="xla",
                              block=16)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_quantized_experts_route_through_xla_path():
    params = _params()
    # the serving trunk's logical names (models/llama.py moe layer): the
    # _QUANT_RULES table covers moe_up/moe_down — NOT the EP-training
    # "expert_stack" name, which would silently skip quantization
    qparams = _quantized(params)
    from mcp_context_forge_tpu.tpu_local.quantize import is_quant
    assert is_quant(qparams["w1"]) and is_quant(qparams["w2"])
    x = _x()
    dense = moe_ffn_dense_mask(qparams, x, CFG)
    grouped = moe_ffn_grouped(qparams, x, CFG, impl="xla", block=16)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=1e-3, atol=1e-4)
    # the int8 Pallas kernel (interpret mode) matches both
    kernel = moe_ffn_grouped(qparams, x, CFG, impl="pallas", block=16,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(grouped),
                               rtol=2e-5, atol=2e-6)


def _quantized(params):
    from mcp_context_forge_tpu.tpu_local.quantize import quantize_tree

    return quantize_tree(dict(params), {"router": "replicated", "w1": "moe_up",
                                        "w3": "moe_up", "w2": "moe_down"})


@pytest.mark.parametrize("stacks", ["full", "int8"])
def test_kernel_skips_dead_blocks_and_writes_zeros(stacks):
    """One skip rule in both kernel variants: a block at or past
    ``live_blocks`` computes nothing and writes zeros, whatever its rows
    hold; the live blocks equal the XLA path's. Dead blocks carry NaN rows
    here, so a block that WAS computed would show."""
    params = _params() if stacks == "full" else _quantized(_params())
    NB, block, live = 7, 16, 4
    x_pad = jax.random.normal(jax.random.PRNGKey(5), (NB, block, CFG.dim))
    x_pad = x_pad.at[live:].set(jnp.nan)
    owner = jnp.asarray([0, 2, 2, 5, 7, 7, 7], jnp.int32)
    weights = (params["w1"], params["w3"], params["w2"])
    out = _expert_blocks_pallas(x_pad, *weights, owner,
                                jnp.asarray([live], jnp.int32),
                                block=block, interpret=True)
    want = _expert_blocks_xla(x_pad[:live], *weights, owner[:live], "silu")
    np.testing.assert_allclose(np.asarray(out[:live]), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    assert not np.asarray(out[live:]).any()
    # no count given: every block is live (and the NaN rows come through)
    every = _expert_blocks_pallas(x_pad, *weights, owner, block=block,
                                  interpret=True)
    assert np.isnan(np.asarray(every[live:])).all()


# a block step of the block-diffusion family in small widths: 32 rows x 4
# positions over 128 experts top-8
STEP = MoEConfig(dim=32, n_experts=128, expert_hidden=64, top_k=8)
EVERY = [("xla", "full"), ("xla", "int8"), ("pallas", "full"), ("pallas", "int8")]
PADDING_CASES = [
    # a prefill bucket: two rows of 48 positions, 21 and 9 of them live
    ("bucket", "xla", "full"), ("bucket", "pallas", "full"),
    ("bucket", "pallas", "int8"),
    # the narrow plan of a decode-width step: 22 of 32 rows live (an idle row
    # has no valid position), at the row-block a bfloat16 step of this width
    # takes (16) and a float32 one (8), one expert holding more than a block
    *[(f"block_step_b{block}", impl, stacks) for block in (16, 8)
      for impl, stacks in EVERY],
]


def _padding_case(shape: str, stacks: str):
    """(config, params, block, x [B, S, D], live positions a row)."""
    if shape == "bucket":
        cfg, block, dims, live = CFG, 16, (2, 48), [21, 9]
    else:
        cfg, block, dims = STEP, int(shape.rsplit("b", 1)[1]), (32, 4)
        live = [4] * 22 + [0] * 10
    params = init_moe_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(13), (*dims, cfg.dim), jnp.float32)
    if cfg is STEP:
        # every token's first choice is expert 5: 88 pairs in one group, more
        # than five blocks of 16, while its other seven follow the router
        x = x.at[..., 0].set(3.0)
        params["router"] = params["router"].at[0, 5].set(10.0)
    return (cfg, params if stacks == "full" else _quantized(params), block, x,
            live)


@pytest.mark.parametrize("shape,impl,stacks", PADDING_CASES)
def test_padding_tokens_get_no_row(shape, impl, stacks):
    """The step's validity mask reaches the plan: a padding token's pairs
    (a bucket's tail, a decode-width step's idle rows) get no row, so the
    live blocks follow the live pairs and not the bucket; valid tokens equal
    the scan's; padding tokens read zero; and the same tokens under another
    padding give the same rows."""
    cfg, params, block, x, live = _padding_case(shape, stacks)
    tol = dict(rtol=2e-5, atol=2e-6) if stacks == "full" else dict(
        rtol=1e-3, atol=1e-4)
    B, S = x.shape[:2]
    valid = jnp.arange(S)[None, :] < jnp.asarray(live)[:, None]

    flat = x.reshape(-1, cfg.dim)
    ids, gates = top_k_gates(router_probs(params["router"], flat), cfg.top_k)
    masked = jnp.where(valid.reshape(-1, 1), ids, cfg.n_experts)
    plan = plan_sorted_blocks(masked, gates, cfg.n_experts, block)
    rows = np.asarray(plan["row_valid"])
    assert rows.sum() == sum(live) * cfg.top_k
    counts = np.bincount(np.asarray(masked).ravel(),
                         minlength=cfg.n_experts + 1)[:cfg.n_experts]
    assert int(plan["live_blocks"][0]) == int(np.ceil(counts / block).sum())
    full = plan_sorted_blocks(ids, gates, cfg.n_experts, block)
    assert int(plan["live_blocks"][0]) < int(full["live_blocks"][0])
    if cfg is STEP:
        assert counts[5] == sum(live) > 5 * block
        # an idle row's pairs own no block: every live block holds a live row
        assert rows.reshape(-1, block)[:int(plan["live_blocks"][0])].any(1).all()
    # the inverse: a live pair's row feeds from its token, a dropped pair
    # points past the buffer
    pair_row = np.asarray(plan["pair_row"])
    assert (pair_row[~np.asarray(valid).ravel()] == rows.size).all()
    token_of = np.asarray(plan["sorted_token"])
    for t in np.nonzero(np.asarray(valid).ravel())[0]:
        assert (token_of[pair_row[t]] == t).all()

    kw = dict(impl=impl, block=block, interpret=impl == "pallas")
    out = moe_ffn_grouped(params, x, cfg, valid=valid, **kw)
    scan = moe_ffn_dense_mask(params, x, cfg)
    m = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(out)[m], np.asarray(scan)[m], **tol)
    assert not np.asarray(out)[~m].any()
    # the first row's live tokens alone, padded to another bucket
    alone = moe_ffn_grouped(
        params, jnp.pad(x[:1, :live[0]], ((0, 0), (0, 11), (0, 0))), cfg,
        valid=jnp.arange(live[0] + 11)[None, :] < live[0], **kw)
    np.testing.assert_allclose(np.asarray(alone[0, :live[0]]),
                               np.asarray(out[0, :live[0]]), rtol=2e-5, atol=2e-6)


def test_flops_accounting_near_topk_over_e():
    """The whole point: ~top_k/E of dense cost, padding vanishing with T."""
    acct = grouped_flops(T=2048, top_k=2, n_experts=8, dim=512,
                         hidden=1024, block=128)
    assert acct["ideal"] / acct["dense_mask"] == pytest.approx(0.25)
    ratio = acct["grouped"] / acct["dense_mask"]
    assert ratio < 0.33                       # ~4x fewer FLOPs than dense
    big = grouped_flops(T=65536, top_k=2, n_experts=8, dim=512,
                        hidden=1024, block=128)
    assert big["grouped"] / big["ideal"] < 1.01   # padding term vanishes


def _greedy_tokens(model: str, devices: int = 1) -> tuple[list[int], object]:
    """Eight greedy tokens of an engine of ``model`` (a ``model_variant`` of
    mixtral-test) over ``devices`` of the conftest's virtual devices (its
    mesh's model axis), and its stats."""
    import asyncio

    from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig,
                                                        TPUEngine)

    config = EngineConfig(model=model, max_batch=2,
                          max_seq_len=128, page_size=16, num_pages=32,
                          prefill_buckets=(32,), dtype="float32",
                          attn_impl="reference")
    engine = TPUEngine(config, devices=jax.devices()[:devices])

    async def run():
        await engine.start()
        try:
            ids = engine.tokenizer.encode("route me through experts")
            return [t async for t in engine.generate(ids, max_tokens=8)]
        finally:
            await engine.stop()

    return asyncio.run(run()), engine.stats


def _mixtral(model_variant, **changes) -> str:
    """mixtral-test with moe_block shrunk so the CI-scale prefill clears the
    T·k >= E·block gate (at the default 128 the tiny prompt would take the
    scan)."""
    return model_variant("mixtral-test", moe_block=8, **changes)


@pytest.fixture(scope="module")
def scan_tokens(model_variant):
    tokens, stats = _greedy_tokens(_mixtral(model_variant, moe_impl="dense"))
    assert len(tokens) == 8
    # the scan always: no step took row-blocks
    assert stats.moe_grouped_steps == 0 and stats.moe_scan_steps >= 8
    return tokens


@pytest.mark.parametrize("moe_impl", ["", "grouped", "grouped_pallas"])
def test_mixtral_trunk_parity_across_impls(scan_tokens, moe_impl,
                                           model_variant):
    """The serving trunk end-to-end: a mixtral-test engine generates the
    SAME greedy tokens on its DEFAULT path ("": the row-block kernel for
    the prefill, interpreted off-TPU, the scan for decode steps), under the
    XLA grouped path and under the scan alone — the MoE formulation is a
    perf choice, never a numerics one. The counters say which steps took
    which: the one prefill grouped, every decode step the scan."""
    tokens, stats = _greedy_tokens(_mixtral(
        model_variant, **({"moe_impl": moe_impl} if moe_impl else {})))
    assert tokens == scan_tokens
    assert stats.moe_grouped_steps == stats.prefill_batches == 1
    assert stats.moe_scan_steps == stats.decode_steps >= 7


def test_default_takes_the_scan_on_a_model_axis_wider_than_one_device(
        scan_tokens, model_variant):
    """A mesh whose model axis holds two devices (the conftest's virtual
    ones) builds the family with its default — it used to be refused on a
    TPU mesh — and every step takes the scan: the kernel is not wrapped in
    shard_map. Same tokens as on one device."""
    tokens, stats = _greedy_tokens(_mixtral(model_variant), devices=2)
    assert tokens == scan_tokens
    assert stats.moe_grouped_steps == 0
    assert stats.moe_scan_steps == stats.prefill_batches + stats.decode_steps


def test_decode_shapes_fall_back_to_dense():
    """The rule at decode width (``expert_path``): a Mixtral-shape [B, 1] call
    (8 experts top-2: T·k alone is the quarter of the scan's E·T rows the
    rule allows) routes through the dense scan, without changing outputs; a
    prefill-shape call takes the grouped path at ``moe_block``; and a
    block-step-shape call over many small experts (128 x top-8, 32 rows x 4
    positions) takes the kernel at the narrow block its width gives it."""
    from unittest import mock

    params = _params()
    x = _x((4, 1), seed=11)  # decode shape: T=4, k=2 -> 8 < E*block

    class _Cfg:
        dim = CFG.dim
        n_experts = CFG.n_experts
        ffn_hidden = CFG.expert_hidden
        moe_top_k = CFG.top_k
        hidden_act = "silu"
        moe_impl = "grouped"
        moe_block = 16

    from mcp_context_forge_tpu.tpu_local.models.llama import _ffn_block
    layer = dict(params)
    # the grouped formulation every router feeds (``llama.routed_experts``)
    grouped_fn = ("mcp_context_forge_tpu.tpu_local.ops.grouped_moe."
                  "experts_grouped")
    with mock.patch(grouped_fn) as spy:
        out = _ffn_block(layer, _Cfg(), x)
        # as wide as a Mixtral decode batch gets, and a verify step's width
        _ffn_block(layer, _Cfg(), _x((32, 1), seed=11))
        _ffn_block(layer, _Cfg(), _x((12, 4), seed=11))
        spy.assert_not_called()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(moe_ffn_dense_mask(params, x, CFG)),
        rtol=2e-5, atol=2e-6)
    # a prefill-shaped call with the same config DOES take the grouped path
    big = _x((4, 32), seed=12)  # T=128, k=2 -> 256 >= E*block=128
    with mock.patch(grouped_fn, wraps=experts_grouped) as spy:
        grouped = _ffn_block(layer, _Cfg(), big)
        assert spy.call_args.kwargs["block"] == 16
    np.testing.assert_allclose(
        np.asarray(grouped),
        np.asarray(moe_ffn_dense_mask(params, big, CFG)),
        rtol=2e-5, atol=2e-6)

    # the block step: 1024 pairs + 128 blocks of 8 rows (float32 activations)
    # against the scan's 16384 rows, far under moe_block's width (4096 pairs)
    class _Step(_Cfg):
        dim, n_experts, ffn_hidden, moe_top_k = (
            STEP.dim, STEP.n_experts, STEP.expert_hidden, STEP.top_k)
        moe_block = 32

    _, step_params, _, step_x, live = _padding_case("block_step_b8", "full")
    valid = jnp.arange(4)[None, :] < jnp.asarray(live)[:, None]
    with mock.patch(grouped_fn, wraps=experts_grouped) as spy:
        narrow = _ffn_block(dict(step_params), _Step(), step_x, valid=valid)
        assert spy.call_args.kwargs["block"] == 8
    m = np.asarray(valid)
    np.testing.assert_allclose(
        np.asarray(narrow)[m],
        np.asarray(moe_ffn_dense_mask(step_params, step_x, STEP))[m],
        rtol=2e-5, atol=2e-6)
    assert not np.asarray(narrow)[~m].any()


def _family_config(name: str, **fields):
    import dataclasses

    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS

    return dataclasses.replace(MODEL_CONFIGS[name], moe_impl="grouped_pallas",
                               **fields)


ONE_DEVICE = type("Mesh", (), {"shape": {"data": 1, "model": 1}})()
RULE_CONFIGS = {
    "mixtral": lambda: _family_config("mixtral-test", n_experts=8, moe_top_k=2,
                                      moe_block=128),
    "sdar_block32": lambda: _family_config("sdar-test", n_experts=128,
                                           moe_top_k=8, moe_block=32),
    "sdar_block128": lambda: _family_config("sdar-test", n_experts=128,
                                            moe_top_k=8, moe_block=128),
}
# (configuration, tokens of the step, activations) -> (path, row-block): the
# block matters where the path is grouped, and is what the rule weighed else
RULE_TABLE = [
    # 8 x top-2: decode and verify widths scan (T·k alone is E·T / 4),
    # prefills and history suffixes take the kernel at moe_block, as before
    ("mixtral", 8, "bfloat16", "scan", 16),
    ("mixtral", 16, "bfloat16", "scan", 16),
    ("mixtral", 32, "bfloat16", "scan", 16),
    ("mixtral", 128, "bfloat16", "scan", 32),
    ("mixtral", 512, "bfloat16", "grouped", 128),
    ("mixtral", 2048, "bfloat16", "grouped", 128),
    # 128 x top-8: a block step (32 rows x 4) takes the kernel at the
    # sublane tile of its activations; narrower dispatches keep the scan;
    # every step that cleared T·k >= E·moe_block keeps moe_block
    ("sdar_block32", 32, "bfloat16", "scan", 16),
    ("sdar_block32", 64, "bfloat16", "scan", 16),
    ("sdar_block32", 128, "bfloat16", "grouped", 16),
    ("sdar_block32", 128, "float32", "grouped", 8),
    ("sdar_block32", 256, "bfloat16", "grouped", 16),
    ("sdar_block32", 512, "bfloat16", "grouped", 32),
    ("sdar_block32", 2048, "bfloat16", "grouped", 32),
    ("sdar_block128", 128, "bfloat16", "grouped", 16),
    ("sdar_block128", 512, "bfloat16", "grouped", 32),
    ("sdar_block128", 1024, "bfloat16", "grouped", 64),
    ("sdar_block128", 2048, "bfloat16", "grouped", 128),
]


@pytest.mark.parametrize("name,tokens,dtype,path,block", RULE_TABLE)
def test_expert_rule_table(name, tokens, dtype, path, block):
    """``expert_path`` / ``expert_block`` over (configuration, step width): a
    pure function of the step's shape, the same for the trunk and for the
    family that imports it, and the scan wherever one device does not hold
    the stacks or no mesh is named."""
    from mcp_context_forge_tpu.tpu_local.models import afmoe, llama, sdar

    config = RULE_CONFIGS[name]()
    assert llama.expert_path(config, ONE_DEVICE, tokens, dtype) == path
    assert llama.expert_block(config, tokens, dtype) == block
    assert sdar.expert_path is llama.expert_path
    # the window / full family weighs a narrow step by a rule of its own
    assert afmoe.expert_path is not llama.expert_path
    two = type("Mesh", (), {"shape": {"data": 1, "model": 2}})()
    assert llama.expert_path(config, two, tokens, dtype) == "scan"
    assert llama.expert_path(config, None, tokens, dtype) == "scan"


def test_grouped_matches_dense_on_virtual_expert_mesh():
    """The distributed claim: grouped routing under an 8-device mesh with
    the expert stacks SHARDED over the mesh (each device owns E/n
    experts) computes the same per-token function as the single-device
    dense oracle — XLA inserts the gather collectives."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    assert len(devices) == 8, "conftest provides the 8-device CPU mesh"
    mesh = Mesh(np.array(devices), ("expert",))

    params = _params()
    x = _x((2, 32), seed=21)
    dense = moe_ffn_dense_mask(params, x, CFG)

    expert_sharded = NamedSharding(mesh, P("expert", None, None))
    replicated = NamedSharding(mesh, P())
    placed = {
        "router": jax.device_put(params["router"], replicated),
        "w1": jax.device_put(params["w1"], expert_sharded),
        "w3": jax.device_put(params["w3"], expert_sharded),
        "w2": jax.device_put(params["w2"], expert_sharded),
    }

    @jax.jit
    def run(p, inp):
        return moe_ffn_grouped(p, inp, CFG, impl="xla", block=16)

    with mesh:
        out = run(placed, jax.device_put(x, replicated))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)
