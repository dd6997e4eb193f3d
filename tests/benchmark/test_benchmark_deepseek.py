"""What PR 28 added to the benchmark for ``deepseek-v3.2-d5-ep16``: the family
file's contract names, the configuration file against the published keys, the
check's lengths against the mix, the cost functions at hand-computed sizes, and
each new per-layer reader on a small synthetic trace and ring."""

import pytest

from benchmark import families
from benchmark.harness import (correct, kernel_cost, layers, manifest, mla_cost, stats,
                               trace_reduce)
from mcp_context_forge_tpu.observability.timeline import StepCounts, StepTimeline

T0, NS0 = 100.0, 5e9    # the ring's seconds and the trace's nanoseconds: one second


def reduced(ops=(), modules=()):
    ns = lambda t: NS0 + (t - T0) * 1e9
    device = trace_reduce.DeviceTrace(
        modules=[(ns(a), ns(b), name, kind) for a, b, name, kind in modules],
        ops=[(ns(a), ns(b), name) for a, b, name in ops])
    return trace_reduce.Reduced({"/device:TPU:0": device}, (NS0, NS0 + 1e9))


def read(name, ctx):
    return layers.load_reader(name)(ctx)

CELL = "deepseek-v3.2-d5-ep16.longctx-closed"
PUBLISHED = {   # https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json
    "hidden_size": 7168, "num_attention_heads": 128, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "index_n_heads": 64, "index_head_dim": 128,
    "index_topk": 2048, "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "n_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 163840, "num_nextn_predict_layers": 1,
    "num_hidden_layers": 61, "first_k_dense_replace": 3, "n_routed_experts": 256,
    "vocab_size": 129280}


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


@pytest.fixture(scope="module")
def config(cell):
    return manifest.read_json(cell.config_file)


@pytest.fixture(scope="module")
def model(config):
    return families.of(config).model_config("deepseek-v3.2-d5-ep16", config)


def test_the_cell_and_what_it_reports(cell):
    assert (cell.config, cell.traffic, cell.chips) == (
        "deepseek-v3.2-d5-ep16", "longctx-closed", 1)
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "mla_prefill_attention_roofline", "mla_decode_attention_roofline",
        "sparse_index_roofline", "sparse_select.device_share",
        "sparse.selected_share_mean", "moe.local_pairs_per_token",
        "decode.device_ms_per_step", "device.idle_share.serve",
        # the five readers without a list report in every cell
        "gateway.pre_engine_ms_p50", "prefill.batch_width_mean",
        "prefill.step_ms_mean", "queue.wait_behind_prefill_share",
        "prefill.device_ms_per_step"}
    assert not names & {"paged_attention_roofline", "prefill_attention_roofline",
                        "device.idle_share.sat"}
    for name in names:
        layers.load_reader(name)            # every reader file is there


def test_family_file_keeps_the_contract():
    family = families.load("deepseek_v32")
    assert all(hasattr(family, name) for name in families.CONTRACT)
    assert family.reference == "deepseek_v32_plain"
    assert callable(families.reference_of(family).forward)


def test_configuration_file_is_the_published_one_but_for_its_cut(config, model):
    reduced_keys = set(config["reduced"])
    assert reduced_keys == {"num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced_keys:
            assert config["published"][key] == value and config[key] != value
        else:
            assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"}
    # the floors: a period and four layers after the leading dense one, at
    # least 8 routed experts, an eighth of the vocabulary; no width cut
    assert (model.n_layers, model.n_dense_layers) == (5, 1)
    assert model.n_held == 16 >= 8 and model.experts_held == (0, 16)
    assert model.n_routed_experts == 256 and model.moe_top_k == 8   # router as published
    assert model.vocab_size * 8 == PUBLISHED["vocab_size"]
    assert (model.dim, model.n_heads, model.latent_dim, model.index_topk) == (
        7168, 128, 576, 2048)
    assert config["engine"] == {"quant": "", "kv_quant": "", "dtype": "bfloat16",
                                "page_size": 128, "num_pages": 1152}
    for key in ("deployment", "assumed", "guarantees", "check_seed", "logits_tolerance"):
        assert key in config
    assert "16 chips share each layer" in config["deployment"]
    assert "NOT loaded" in config["assumed"]["num_nextn_predict_layers"]


def test_held_weights_and_pool_are_what_the_issue_reckoned(model):
    from mcp_context_forge_tpu.tpu_local.kv import kv_page_bytes
    from mcp_context_forge_tpu.tpu_local.models import deepseek

    assert deepseek.param_count(model) * 2 == pytest.approx(9.27e9, rel=2e-3)
    page = kv_page_bytes(model, 128)
    assert page == 5 * 128 * (512 + 64 + 128) * 2           # 1408 B a token a layer
    assert 1152 * page == pytest.approx(1.04e9, rel=5e-3)


def test_check_lengths_fit_the_mix_and_straddle_the_selector(cell, config):
    mix = manifest.read_json(cell.traffic_file)
    check = correct.check_of(config, mix)
    assert check.prompt_lengths == (4608, 2304, 640) and check.decode_positions == 8
    assert check.tokens <= mix["engine"]["max_seq_len"] == 16384
    topk, bucket = config["index_topk"], mix["engine"]["prefill_buckets"][0]
    assert check.prompt_lengths[0] > 2 * topk and check.prompt_lengths[0] % bucket
    assert topk < check.prompt_lengths[1] and check.prompt_lengths[2] < topk
    with pytest.raises(ValueError, match="exceed the mix's max_seq_len"):
        correct.check_of(config, {"engine": {"max_seq_len": 4096}})


def test_traffic_is_the_issues_letter_for_letter(cell):
    mix = manifest.read_json(cell.traffic_file)
    assert mix["kind"] == "closed_loop"
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "low": 4096, "high": 16000}
    assert mix["max_tokens"] == {"dist": "uniform", "low": 128, "high": 256}
    assert mix["engine"] == {"max_seq_len": 16384, "prefill_buckets": [1024],
                             "prefill_max_batch": 2, "max_batch": 8}
    assert (mix["cycle"], mix["schedule_seed"], mix["drain_seconds"],
            mix["trace_seconds"], mix["temperature"]) == (64, 23, 40, 5.0, 0.0)
    assert manifest.read_json(cell.cell_file) == {"clients": 8}
    # every prompt is 2-8 x index_topk, and the longest request fits its row
    assert mix["prompt_tokens"]["low"] >= 2 * 2048
    assert mix["prompt_tokens"]["high"] + mix["max_tokens"]["high"] <= 16384


def test_cost_functions_at_hand_computed_sizes():
    # 3 queries after 2 cached tokens, top-4: contexts 3, 4, 5 -> 3 + 4 + 4 pairs
    assert mla_cost.selected_pairs(3, 2, 4) == 11
    assert mla_cost.selected_pairs(3, 0, 4) == 1 + 2 + 3
    assert mla_cost.selected_pairs(2, 10, 4) == 8
    assert mla_cost.causal_pairs(3, 2) == 3 + 4 + 5
    ops, nbytes = mla_cost.mla_attention(3, 2, n_heads=2, latent_dim=6, value_dim=4,
                                         topk=4)
    assert ops == 2 * 11 * 2 * (6 + 4)
    assert nbytes == 5 * 6 * 2 + 3 * 2 * (6 + 4) * 2
    ops, nbytes = mla_cost.mla_attention(1, 9, n_heads=2, latent_dim=6, value_dim=4,
                                         topk=4)          # a decode token: reads 4
    assert ops == 2 * 4 * 2 * 10 and nbytes == 4 * 6 * 2 + 2 * 10 * 2
    ops, nbytes = mla_cost.index_scores(3, 2, n_heads=2, head_dim=8, query_block=2)
    assert ops == 2 * 12 * 2 * 8
    assert nbytes == 2 * 5 * 8 * 2 + 3 * 2 * 8 * 2 + 4 * 12
    # the published sizes: a selected pair costs 128 heads x (576 + 512) x 2
    ops, _ = mla_cost.mla_attention(1, 16000, 128, 576, 512, 2048)
    assert ops == 2 * 2048 * 128 * 1088


class _Model:
    n_layers, n_heads, latent_dim, kv_lora_rank = 5, 128, 576, 512
    index_topk, index_n_heads, index_head_dim = 2048, 64, 128


def _record(index, sent, prompt, token_times):
    record = stats.Record(index, sent, prompt, len(token_times))
    record.sent = sent
    record.token_times = list(token_times)
    return record


def _context(trace, records=(), model=_Model):
    return layers.LayerContext(
        records=list(records), window=(T0, T0 + 1.0), stats={}, model=model,
        peak=kernel_cost.peaks("TPU v5 lite"), trace=trace,
        trace_span=(T0, T0 + 1.0))


def test_roofline_readers_count_the_selected_set_against_the_kernels_time():
    trace = reduced(
        modules=[(T0 + 0.0, T0 + 0.4, "jit__prefill_hist_and_sample", "prefill_hist"),
                 (T0 + 0.5, T0 + 0.6, "jit__decode_and_sample", "decode")],
        ops=[(T0 + 0.0, T0 + 0.2, "mla_paged_attention"),
             (T0 + 0.2, T0 + 0.3, "sparse_index_scores"),
             (T0 + 0.3, T0 + 0.32, "sparse_select"),
             (T0 + 0.5, T0 + 0.51, "mla_paged_attention"),
             (T0 + 0.51, T0 + 0.512, "sparse_select"),
             (T0 + 0.52, T0 + 0.53, "sort")])
    # one prompt of 8192 tokens whose prefill lies wholly inside the span, and
    # one decode token (its second) inside it
    record = _record(0, T0 + 0.0, 8192, [T0 + 0.5, T0 + 0.55])
    ctx = _context(trace, [record])
    peak = ctx.peak
    ops, nbytes = mla_cost.mla_attention(8192, 0, 128, 576, 512, 2048)
    want = max(5 * ops / peak["bf16_flops_per_s"], 5 * nbytes / peak["hbm_bytes_per_s"])
    assert read("mla_prefill_attention_roofline", ctx) == pytest.approx(
        100 * want / 0.2, rel=1e-6)
    assert ctx.notes["mla_prefill_attention_roofline"]["bound"] == "compute"
    ops, nbytes = mla_cost.mla_attention(1, 8192, 128, 576, 512, 2048)
    want = max(5 * ops / peak["bf16_flops_per_s"], 5 * nbytes / peak["hbm_bytes_per_s"])
    assert read("mla_decode_attention_roofline", ctx) == pytest.approx(
        100 * want / 0.01, rel=1e-6)
    ops, nbytes = mla_cost.index_scores(8192, 0, 64, 128)
    want = max(5 * ops / peak["bf16_flops_per_s"], 5 * nbytes / peak["hbm_bytes_per_s"])
    assert read("sparse_index_roofline", ctx) == pytest.approx(100 * want / 0.1, rel=1e-6)
    # the selection's share: its kernel by name, sampling's sort not counted
    assert read("sparse_select.device_share", ctx) == pytest.approx(
        100 * 0.022 / 0.5, rel=1e-6)
    for name in ("mla_prefill_attention_roofline", "mla_decode_attention_roofline",
                 "sparse_index_roofline"):
        assert 0 < read(name, ctx) <= 100


@pytest.mark.parametrize("name", [
    "mla_prefill_attention_roofline", "mla_decode_attention_roofline",
    "sparse_index_roofline", "sparse_select.device_share"])
def test_kernel_readers_report_nothing_where_the_program_lacks_the_kernels(name):
    """The parent's program (no such kernel, a GQA model config) under this
    PR's benchmark files: nothing, and no error."""
    trace = reduced(
        modules=[(T0, T0 + 0.5, "jit__decode_and_sample", "decode")],
        ops=[(T0, T0 + 0.2, "paged_attention"), (T0 + 0.2, T0 + 0.3, "sort")])
    record = _record(0, T0, 100, [T0 + 0.1, T0 + 0.2])
    assert read(name, _context(trace, [record], model=object())) is None
    assert read(name, _context(None, [record])) is None


def test_counter_readers_read_the_step_records():
    ring = StepTimeline("0")
    ring.step(1, "chunk", 2, 2, 1024, T0 + 0.0, T0 + 0.3,
              StepCounts(0.4, 8192.0, 4000.0))
    ring.step(2, "decode", 8, 8, 64, T0 + 0.3, T0 + 0.4, StepCounts(0.25, 32.0, 20.0))
    ring.step(3, "decode_fb", 8, 8, 64, T0 + 0.4, T0 + 0.5, StepCounts(0.35, 32.0, 12.0))
    ring.step(4, "decode", 8, 8, 64, T0 + 1.4, T0 + 1.5, StepCounts(0.9, 32.0, 32.0))
    ctx = _context(None)
    # decode steps of the window only: (0.25 + 0.35) / 2
    assert read("sparse.selected_share_mean", ctx) == pytest.approx(30.0)
    assert read("moe.local_pairs_per_token", ctx) == pytest.approx(
        (4000 + 20 + 12) / (8192 + 32 + 32))


def test_counter_readers_report_nothing_without_counts():
    ring = StepTimeline("0")
    ring.step(1, "decode", 8, 8, 4, T0 + 0.3, T0 + 0.4)      # a GQA engine's step
    ctx = _context(None)
    assert read("sparse.selected_share_mean", ctx) is None
    assert read("moe.local_pairs_per_token", ctx) is None
