"""What PR 37 added to the benchmark for ``sdar-30b-a3b-d12``: the manifest's
new entries, the configuration file against the catalog's published keys, the
family file's contract and its block path, the block cost at hand-counted
sizes, both new per-layer readers on a small synthetic trace and ring, and a
CPU rehearsal of the cell at a tiny size."""

import asyncio
import json
import os

import pytest

from benchmark import families
from benchmark.harness import (block_cost, correct, kernel_cost, layers, manifest,
                               stats, trace_reduce)
from mcp_context_forge_tpu.observability.timeline import StepCounts, StepTimeline

T0, NS0 = 100.0, 5e9
CELL, CONFIG = "sdar-30b-a3b-d12.chat", "sdar-30b-a3b-d12"
NEW_READERS = ("diffusion.tokens_per_pass", "block_attention_roofline")
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
PUBLISHED = {   # the catalog row's ``config``, key by key
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}


def reduced(ops=(), modules=()):
    ns = lambda t: NS0 + (t - T0) * 1e9
    device = trace_reduce.DeviceTrace(
        modules=[(ns(a), ns(b), name, kind) for a, b, name, kind in modules],
        ops=[(ns(a), ns(b), name) for a, b, name in ops])
    return trace_reduce.Reduced({"/device:TPU:0": device}, (NS0, NS0 + 1e9))


def read(name, ctx):
    return layers.load_reader(name)(ctx)


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


@pytest.fixture(scope="module")
def cell(doc):
    return manifest.cell(doc, CELL)


@pytest.fixture(scope="module")
def config(cell):
    return manifest.read_json(cell.config_file)


@pytest.fixture(scope="module")
def model(config):
    return families.of(config).model_config(CONFIG, config)


def test_the_cell_and_what_it_reports(doc, cell):
    assert (cell.config, cell.traffic, cell.chips) == (CONFIG, "chat", 1)
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    listed = set(NEW_READERS) | {
        "decode.device_ms_per_step", "decode.host_gap_ms_mean",
        "device.idle_share.serve", "device.idle_share.host.serve",
        "decode.retire_interval_ms_p95", "decode.prefill_stall_share",
        "queue.wait_ms_p95.no_tail", "ttft_tail_p95_ms"}
    # the cell is on exactly these lists; beside them it reports every reader
    # that has no list, however many later PRs append
    assert {m["name"] for m in cell.per_layer if "workloads" in m} == listed
    assert names - listed == {m["name"] for m in doc["per_layer"]
                              if "workloads" not in m}
    # the first counts one context read a TOKEN a layer, which a block step
    # undercuts; the second is held to the GQA trunk's family file by
    # test_benchmark_reference.py, which this PR may not edit
    assert not names & {"paged_attention_roofline", "prefill_attention_roofline"}
    for name in names:
        layers.load_reader(name)
    # held by name, never by position: later PRs append after these entries
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE
    assert entry["reduced"] == ["num_hidden_layers"]
    assert [w["name"] for w in doc["workloads"]].count(CELL) == 1
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW_READERS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "tpot_p95_ms"
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"].count(CELL) == 1
    # the traffic is the mix that was there, at this cell's own rate
    assert manifest.read_json(cell.traffic_file)["engine"] == {
        "max_seq_len": 1024, "prefill_buckets": [512], "prefill_max_batch": 4,
        "max_batch": 32}
    params = manifest.read_json(cell.cell_file)
    assert set(params) == {"rate_rps"} and params["rate_rps"] * 2 % 1 == 0


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_holds_the_published_key(config, key):
    if key == "num_hidden_layers":
        assert config["reduced"] == [key] and config[key] == 12
        assert config["published"] == {key: PUBLISHED[key]}
    else:
        assert config[key] == PUBLISHED[key]


def test_configuration_states_its_cut_and_what_it_assumed(config, model):
    assert config["source"] == SOURCE and config["family"] == "sdar"
    assert config["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking_strategy": "low_confidence_dynamic",
        "confidence_threshold": 0.9, "mask_token_id": 151669}
    for key in ("qk_norm", "logits_position", "attention_mask", "block_length",
                "denoising_steps", "remasking_strategy", "confidence_threshold",
                "mask_token_id", "noise_schedule", "commit", "router", "moe_block",
                "weights", "tokenizer"):
        assert key in config["assumed"], key
    assert "a quarter of the 48-layer model" in config["deployment"]
    assert config["engine"] == {
        "quant": "int8", "kv_quant": "", "dtype": "bfloat16", "page_size": 128,
        "num_pages": 512, "prefix_cache": False, "decode_overlap": False}
    for key in ("guarantees", "check_seed", "logits_tolerance"):
        assert key in config
    assert "reason" in config["logits_tolerance"]
    assert (model.n_layers, model.dim, model.ffn_hidden, model.vocab_size) == (
        12, 2048, 768, 151936)
    assert (model.n_heads, model.n_kv_heads, model.head_dim) == (32, 4, 128)
    assert (model.n_experts, model.moe_top_k, model.moe_block) == (
        128, 8, config["moe_block"])
    assert (model.block_length, model.denoising_steps, model.confidence_threshold,
            model.mask_token_id) == (4, 4, 0.9, 151669)
    assert model.rope_theta == 1e6 and model.norm_eps == 1e-6
    family = families.of(config)
    for key, value in (("norm_topk_prob", False), ("attention_bias", True),
                       ("use_sliding_window", True), ("decoder_sparse_step", 2)):
        with pytest.raises(ValueError, match=key):
            family.model_config(CONFIG, {**config, key: value})
    with pytest.raises(ValueError, match="low_confidence_dynamic"):
        family.model_config(CONFIG, {**config, "generation": {
            **config["generation"], "remasking_strategy": "random"}})


def test_weights_and_cache_are_what_the_issue_reckoned(config, model):
    from mcp_context_forge_tpu.tpu_local.kv import kv_page_bytes
    from mcp_context_forge_tpu.tpu_local.models import family_of, sdar

    assert family_of(model) is sdar
    layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048       # wq, wk, wv, wo
             + 2048 * 128 + 128 * 3 * 2048 * 768)             # router, experts
    assert layer == pytest.approx(623.1e6, rel=1e-3)
    assert sdar.param_count(model) == pytest.approx(12 * layer + 622.3e6, rel=1e-3)
    assert sdar.param_count(model) == pytest.approx(8.10e9, rel=1e-3)
    page = kv_page_bytes(model, 128)
    assert page == 12 * 128 * 2 * 4 * 128 * 2               # 24.6 KB a token
    assert config["engine"]["num_pages"] * page == pytest.approx(1.61e9, rel=5e-3)
    # the rule's two sides at these shapes (moe_block 32, measured against the
    # program's default 128). It is one of rows, T.k + E.b(T) <= E.T / 4: the
    # cell's block step (32 rows x 4 positions = 128 tokens) and every prefill
    # are grouped, the check's forced block (one row, 4 tokens) scans
    one = type("M", (), {"shape": {"model": 1}})()
    assert model.moe_block == 32
    assert [sdar.expert_path(model, one, t) for t in (4, 64, 128, 256, 512, 2048)] == [
        "scan", "scan", "grouped", "grouped", "grouped", "grouped"]


def test_family_file_keeps_the_contract():
    family = families.load("sdar")
    assert all(hasattr(family, name) for name in families.CONTRACT)
    assert family.reference == "sdar_plain"
    reference = families.reference_of(family)
    assert callable(reference.forward) and callable(reference.generate)
    source = open(reference.__file__, encoding="utf-8").read()
    assert "mcp_context_forge_tpu" not in source.replace(
        "nothing imported from ``mcp_context_forge_tpu``", "")


def test_check_lengths_fit_the_mix_and_leave_a_remainder(cell, config):
    mix = manifest.read_json(cell.traffic_file)
    check = correct.check_of(config, mix)
    assert check.prompt_lengths == (700, 386, 96) and check.decode_positions == 16
    bucket = mix["engine"]["prefill_buckets"][0]
    assert check.tokens <= mix["engine"]["max_seq_len"]
    assert bucket < check.prompt_lengths[0] < 2 * bucket      # the chunk path
    assert check.prompt_lengths[1] % 4 == 2                   # a prompt remainder
    assert (check.prompt_lengths[1] + check.decode_positions) % 4 == 2   # a cut block
    # ids 32..126 and the bos: the mask token's row is never read by the check
    assert config["generation"]["mask_token_id"] > 255


def test_block_cost_at_hand_counted_sizes():
    # 4 positions after 10 cached, 2 heads over 1 kv head of 8
    ops, nbytes = block_cost.block_attention(4, 10, 2, 1, 8)
    assert ops == 4 * 4 * 14 * 2 * 8
    assert nbytes == 2 * 14 * 1 * 8 * 2 + 2 * 4 * 2 * 8 * 2
    # the published sizes: a block after 500 tokens reads 2 KB a token of K and V
    ops, nbytes = block_cost.block_attention(4, 500, 32, 4, 128)
    assert ops == 4 * 4 * 504 * 32 * 128
    assert nbytes == pytest.approx(504 * 2048 + 65536)
    # a prompt of 10: its first block starts at 8 with 2 known positions
    times = [1.0, 1.0, 2.0, 2.0, 2.0, 2.0, 3.0]
    assert list(block_cost.blocks_of(10, times, 4)) == [(8, 1.0), (12, 2.0), (16, 3.0)]
    assert list(block_cost.blocks_of(8, times[:5], 4)) == [(8, 1.0), (12, 2.0)]
    assert block_cost.block_length(object()) is None


def _record(index, sent, prompt, token_times):
    record = stats.Record(index, sent, prompt, len(token_times))
    record.sent = sent
    record.token_times = list(token_times)
    return record


def _context(trace, records=(), model=None):
    return layers.LayerContext(
        records=list(records), window=(T0, T0 + 1.0), stats={}, model=model,
        peak=kernel_cost.peaks("TPU v5 lite"), trace=trace,
        trace_span=(T0, T0 + 1.0))


def _block_ring():
    ring = StepTimeline("0")
    ring.step(1, "prefill", 1, 1, 512, T0 + 0.0, T0 + 0.1)
    ring.step(2, "decode", 32, 2, 8, T0 + 0.1, T0 + 0.3,
              StepCounts(0.0, 0.0, 0.0, denoise_passes=4.0, block_tokens=6.0))
    ring.step(3, "decode", 32, 2, 8, T0 + 0.3, T0 + 0.5,
              StepCounts(0.0, 0.0, 0.0, denoise_passes=2.0, block_tokens=8.0,
                         filled_by_threshold=4.0))
    ring.step(4, "decode", 32, 2, 8, T0 + 1.3, T0 + 1.5,
              StepCounts(0.0, 0.0, 0.0, denoise_passes=4.0, block_tokens=8.0))
    return ring


def test_tokens_per_pass_reads_the_step_records():
    ring = _block_ring()
    ctx = _context(None)
    # two steps of two blocks each: 14 tokens over 2 * (4 + 1) + 2 * (2 + 1) passes
    assert read("diffusion.tokens_per_pass", ctx) == pytest.approx(14 / 16)
    assert ctx.notes["diffusion.tokens_per_pass"] == {
        "steps": 2, "blocks": 4, "tokens": 14.0, "denoise_passes": 6.0,
        "filled_by_threshold": 4.0}
    del ring


def test_block_attention_roofline_on_a_synthetic_trace(model):
    ring = _block_ring()
    trace = reduced(
        modules=[(T0 + 0.0, T0 + 0.1, "jit__prefill_and_sample", "prefill"),
                 (T0 + 0.1, T0 + 0.3, "jit__decode_and_sample_block", "decode"),
                 (T0 + 0.3, T0 + 0.5, "jit__decode_and_sample_block", "decode")],
        ops=[(T0 + 0.05, T0 + 0.06, "flash_attention"),
             (T0 + 0.1, T0 + 0.1002, "paged_attention"),
             (T0 + 0.3, T0 + 0.3002, "paged_attention")])
    # a prompt of 402: its first block (2 known + 2 new) after 400 cached, seen
    # after the 4-pass step; the next (404 cached) after the 2-pass step
    record = _record(0, T0, 402, [T0 + 0.31, T0 + 0.31] + [T0 + 0.51] * 4)
    ctx = _context(trace, [record], model)
    ops = nbytes = 0.0
    for cached, passes in ((400, 4.0), (404, 2.0)):
        o, b = block_cost.block_attention(4, cached, 32, 4, 128)
        ops, nbytes = ops + 12 * passes * o, nbytes + 12 * passes * b
    peak = ctx.peak
    least = max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    value = read("block_attention_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.0004, rel=1e-6)
    assert 0 < value <= 100          # a reading over 100 % is a failure
    assert ctx.notes["block_attention_roofline"]["bound"] == "memory"
    assert ctx.notes["block_attention_roofline"]["blocks"] == 2
    del ring


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_report_nothing_from_a_program_without_block_steps(name, model):
    """The parent's program (no block step, a GQA model config, step records
    without the counts) under this PR's benchmark files: nothing, no error."""
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS

    ring = StepTimeline("0")
    ring.step(1, "decode", 8, 8, 4, T0 + 0.3, T0 + 0.4)       # a GQA engine's step
    ring.step(2, "decode", 8, 8, 4, T0 + 0.4, T0 + 0.5,
              StepCounts(0.3, 32.0, 20.0))                    # the latent family's
    trace = reduced(
        modules=[(T0, T0 + 0.5, "jit__decode_and_sample", "decode")],
        ops=[(T0, T0 + 0.2, "paged_attention")])
    record = _record(0, T0, 100, [T0 + 0.1, T0 + 0.2])
    for other in (MODEL_CONFIGS["mistral-7b"], object(), model):
        assert read(name, _context(trace, [record], model=other)) is None
    assert read(name, _context(None, [record], model)) is None
    del ring


# ------------------------------------------------------- the cell, rehearsed

TINY = {   # sdar-test's geometry, as a config.json
    "model_type": "sdar_moe", "vocab_size": 512, "hidden_size": 64, "head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "hidden_act": "silu",
    "max_position_embeddings": 512, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "generation": {"block_length": 4, "denoising_steps": 4,
                   "remasking_strategy": "low_confidence_dynamic",
                   "confidence_threshold": 0.9, "mask_token_id": 511},
    "moe_block": 8, "family": "sdar",
    "check": {"prompt_lengths": [100, 42, 20], "decode_positions": 6},
    "engine": {"quant": "int8", "kv_quant": "", "dtype": "float32", "page_size": 32,
               "num_pages": 48, "prefix_cache": False, "decode_overlap": False,
               "moe_impl": "grouped",
               # the suite's 8 CPU devices as replicas of the data axis
               "mesh_shape": "8x1", "embedding_model": "encoder-tiny"},
    "logits_tolerance": {"atol": 2e-3, "rtol": 2e-3}, "check_seed": 5,
}
MIX = {"kind": "open_loop", "arrivals": "poisson", "schedule_seed": 1,
       "prompt_tokens": {"dist": "log_uniform", "low": 32, "high": 60},
       "max_tokens": {"dist": "log_uniform", "low": 3, "high": 9},
       "drain_seconds": 30, "trace_seconds": 1.0,
       "engine": {"max_seq_len": 128, "prefill_buckets": [64],
                  "prefill_max_batch": 2, "max_batch": 4}}


def test_rehearsal_of_the_cell_traced(cell, capsys, tmp_path, monkeypatch):
    """``run.measure`` at a tiny size on the CPU: the check's 100-token prompt
    is above the 64 bucket and takes the engine's chunk path, 42 leaves a
    remainder of 2 and ends inside a block; every token comes from a block
    step and the accounting is exact; the counter reader reads the records."""
    from benchmark import run
    from mcp_context_forge_tpu.config import reset_settings_cache

    # a trace directory of its own: the other files' traced rehearsals share
    # the checkout's, and clear it, while this one runs beside them
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))

    tiny_cell = manifest.Cell(**{**cell.__dict__, "config": "bench-tiny-sdar"})
    saved = dict(os.environ)
    try:
        result = asyncio.run(run.measure(tiny_cell, TINY, MIX, {"rate_rps": 6.0},
                                         seed=3_000_000_019, seconds=2.0, trace=True))
    finally:
        os.environ.clear()
        os.environ.update(saved)
        reset_settings_cache()
    notes = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            fact = json.loads(line)
            notes[fact.pop("note")] = fact
    assert result["correct"] is True, notes
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] == 12 and result["failed"] == 0
    logits = notes["logits_vs_reference"]
    assert logits["ok"] and len(logits["position_max_abs_err"]) == 3 * 7
    assert logits["attn"]["prefill"] == "reference"
    assert logits["attn"]["chunk"] == logits["attn"]["block"] == "gather"
    assert logits["attn"]["experts"] == {"4 tokens": "scan", "64 tokens": "grouped"}
    assert len(notes["greedy_repeats"]["tokens"]) == 8
    assert notes["accounting"]["held"] and notes["accounting"]["ok"]
    assert notes["requests"]["serving_compiles"] == 0
    # no plain decode program exists for this family
    assert notes["build"]["step_programs"] == 2 * (1 + 1)      # prefills only
    per_pass = result["metrics"]["diffusion.tokens_per_pass"]
    assert per_pass["unit"] == "tokens/pass" and 0.2 <= per_pass["value"] <= 0.8
    assert result["metrics"]["decode.retire_interval_ms_p95"]["value"] > 0
    # no device plane on the CPU: the kernel reader is left out
    assert "block_attention_roofline" not in result["metrics"]
    json.dumps(result)


def test_engine_logits_takes_the_chunk_path_only_above_the_bucket():
    family = families.load("sdar")
    logits = family.EngineLogits.__new__(family.EngineLogits)
    logits.chunk = 512
    assert [logits.chunked(n) for n in (700, 513, 512, 386, 96)] == [
        True, True, False, False, False]
