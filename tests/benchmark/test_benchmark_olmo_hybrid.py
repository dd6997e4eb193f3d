"""What PR 33 added to the benchmark for ``olmo-hybrid-7b``: the manifest's new
entries, the configuration file against the catalog's published keys, the
family file's contract and its chunk path, the cost functions at hand-counted
sizes, each new per-layer reader on a small synthetic trace and ring, and a CPU
rehearsal of the cell at a tiny size."""

import asyncio
import json
import os

import pytest

from benchmark import families
from benchmark.harness import (correct, gdn_cost, kernel_cost, layers, manifest, stats,
                               trace_reduce)
from mcp_context_forge_tpu.observability.timeline import StepCounts, StepTimeline

T0, NS0 = 100.0, 5e9
CELL, CONFIG = "olmo-hybrid-7b.chat", "olmo-hybrid-7b"
NEW_READERS = ("gdn_step_roofline", "gdn_chunk_roofline", "linear_mixer.device_share",
               "hybrid_paged_attention_roofline", "state.rows_live_mean")
PUBLISHED = {   # https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8}


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
    assert names >= set(NEW_READERS) | {
        "decode.device_ms_per_step", "decode.host_gap_ms_mean",
        "device.idle_share.serve", "device.idle_share.host.serve",
        "decode.retire_interval_ms_p95", "decode.prefill_stall_share",
        "queue.wait_ms_p95.no_tail", "ttft_tail_p95_ms",
        # the five readers without a list report in every cell
        "gateway.pre_engine_ms_p50", "prefill.batch_width_mean",
        "prefill.step_ms_mean", "queue.wait_behind_prefill_share",
        "prefill.device_ms_per_step"}
    # they multiply by n_layers, 32 where 8 layers attend
    assert not names & {"paged_attention_roofline", "prefill_attention_roofline",
                        "queue.wait_ms_p95", "device.idle_share.sat"}
    for name in names:
        layers.load_reader(name)
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    for metric in doc["per_layer"]:
        if metric["name"] in NEW_READERS:
            assert metric["workloads"] == [CELL]
    # the traffic is the mix that was there, at this cell's own rate
    assert manifest.read_json(cell.traffic_file)["engine"] == {
        "max_seq_len": 1024, "prefill_buckets": [512], "prefill_max_batch": 4,
        "max_batch": 32}
    assert set(manifest.read_json(cell.cell_file)) == {"rate_rps"}


def test_configuration_file_is_the_published_one_uncut(config, model):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == [] and config["family"] == "olmo_hybrid"
    assert (model.n_layers, model.dim, model.ffn_hidden, model.vocab_size) == (
        32, 3840, 11008, 100352)
    assert (model.n_heads, model.n_kv_heads, model.head_dim) == (30, 30, 128)
    assert (model.linear_n_heads, model.linear_key_dim, model.linear_value_dim,
            model.conv_kernel, model.allow_neg_eigval) == (30, 96, 192, 4, True)
    assert len(model.layers_of("linear_attention")) == 24
    assert model.layers_of("full_attention") == tuple(range(3, 32, 4))
    assert config["engine"] == {"quant": "int8", "kv_quant": "", "dtype": "bfloat16",
                                "page_size": 128, "num_pages": 256,
                                "prefix_cache": False}
    for key in ("deployment", "assumed", "guarantees", "check_seed", "logits_tolerance"):
        assert key in config
    for key in ("norm_placement", "qk_norm", "rotary_embedding", "state_precision",
                "A_log_dt_bias", "weights", "tokenizer"):
        assert key in config["assumed"]
    assert "reason" in config["logits_tolerance"]
    with pytest.raises(ValueError, match="layer_types"):
        families.of(config).model_config(CONFIG, {
            **config, "layer_types": ["linear_attention"] * 32})


def test_weights_cache_and_state_are_what_the_issue_reckoned(config, model):
    from mcp_context_forge_tpu.tpu_local.kv import (kv_page_bytes, kv_state_bytes,
                                                    state_rows_for)
    from mcp_context_forge_tpu.tpu_local.models import olmo_hybrid

    assert olmo_hybrid.param_count(model) == pytest.approx(7.43e9, rel=1e-3)
    # K/V in the 8 attending layers only, 32 heads a page (30 + 2 of padding)
    page = kv_page_bytes(model, 128)
    assert page == 8 * 128 * 2 * 32 * 128 * 2
    assert config["engine"]["num_pages"] * page == pytest.approx(4.29e9, rel=5e-3)
    assert state_rows_for(model, 32) == 33
    row = kv_state_bytes(model, 1)
    assert row == 24 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)      # 54.7 MB a sequence
    assert 33 * row == pytest.approx(1.81e9, rel=5e-3)


def test_family_file_keeps_the_contract():
    family = families.load("olmo_hybrid")
    assert all(hasattr(family, name) for name in families.CONTRACT)
    assert family.reference == "olmo_hybrid_plain"
    assert callable(families.reference_of(family).forward)


def test_check_lengths_fit_the_mix_and_straddle_the_bucket(cell, config):
    mix = manifest.read_json(cell.traffic_file)
    check = correct.check_of(config, mix)
    assert check.prompt_lengths == (700, 384, 96) and check.decode_positions == 8
    bucket = mix["engine"]["prefill_buckets"][0]
    assert check.tokens <= mix["engine"]["max_seq_len"]
    # 700 is above the bucket and its second chunk (188) is padded; the others
    # take the dense program with padding behind them
    assert bucket < check.prompt_lengths[0] < 2 * bucket
    assert all(n < bucket for n in check.prompt_lengths[1:])


def test_cost_functions_at_hand_counted_sizes():
    # 2 heads of a 3 x 5 state: 30 entries, 7 operations an entry
    assert gdn_cost.state_bytes(2, 3, 5) == 2 * 3 * 5 * 4
    assert gdn_cost.token_bytes(2, 3, 5) == 2 * ((6 + 10) * 2 + 8)
    ops, nbytes = gdn_cost.delta_step(2, 3, 5)
    assert ops == 7 * 30 and nbytes == 2 * 120 + 80
    ops, nbytes = gdn_cost.delta_chunk(10, 2, 3, 5)
    assert ops == 7 * 30 * 10 and nbytes == 10 * 80 + 2 * 120
    # the published sizes: a decode token of one layer moves its 2.21 MB state twice
    ops, nbytes = gdn_cost.delta_step(30, 96, 192)
    assert ops == 7 * 30 * 96 * 192
    assert nbytes == pytest.approx(2 * 2.21e6, rel=1e-2)
    assert gdn_cost.linear_layers(object()) is None


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


def test_trace_readers_on_a_synthetic_trace(model):
    trace = reduced(
        modules=[(T0 + 0.0, T0 + 0.3, "jit__prefill_and_sample", "prefill"),
                 (T0 + 0.5, T0 + 0.6, "jit__decode_and_sample", "decode"),
                 (T0 + 0.6, T0 + 0.7, "jit__decode_and_sample_fb", "decode")],
        ops=[(T0 + 0.0, T0 + 0.1, "gated_delta_chunk"),
             (T0 + 0.5, T0 + 0.52, "gated_delta_step"),
             (T0 + 0.52, T0 + 0.53, "paged_attention"),
             (T0 + 0.6, T0 + 0.62, "gated_delta_step"),
             (T0 + 0.62, T0 + 0.63, "paged_attention"),
             (T0 + 0.64, T0 + 0.65, "sort")])
    # one prompt of 400 tokens prefilled wholly inside the span, then two
    # decode tokens (its second and third) inside it
    record = _record(0, T0 + 0.0, 400, [T0 + 0.3, T0 + 0.58, T0 + 0.68])
    ctx = _context(trace, [record], model)
    peak = ctx.peak
    least = lambda ops, nbytes: max(ops / peak["bf16_flops_per_s"],
                                    nbytes / peak["hbm_bytes_per_s"])
    ops, nbytes = gdn_cost.delta_step(30, 96, 192)
    assert read("gdn_step_roofline", ctx) == pytest.approx(
        100 * least(2 * 24 * ops, 2 * 24 * nbytes) / 0.04, rel=1e-6)
    assert ctx.notes["gdn_step_roofline"]["bound"] == "memory"
    ops, nbytes = gdn_cost.delta_chunk(400, 30, 96, 192)
    assert read("gdn_chunk_roofline", ctx) == pytest.approx(
        100 * least(24 * ops, 24 * nbytes) / 0.1, rel=1e-6)
    assert read("linear_mixer.device_share", ctx) == pytest.approx(
        100 * 0.14 / 0.5, rel=1e-6)
    ops = nbytes = 0.0
    for context in (401, 402):
        o, b = kernel_cost.decode_attention(context, 30, 30, 128)
        ops, nbytes = ops + 8 * o, nbytes + 8 * b       # 8 layers attend, not 32
    assert read("hybrid_paged_attention_roofline", ctx) == pytest.approx(
        100 * least(ops, nbytes) / 0.02, rel=1e-6)
    for name in NEW_READERS[:4]:
        assert 0 < read(name, ctx) <= 100


@pytest.mark.parametrize("name", NEW_READERS[:4])
def test_trace_readers_report_nothing_where_the_program_lacks_the_kernels(name, model):
    """The parent's program (no such kernel, a GQA model config) under this
    PR's benchmark files: nothing, and no error."""
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS

    trace = reduced(
        modules=[(T0, T0 + 0.5, "jit__decode_and_sample", "decode")],
        ops=[(T0, T0 + 0.2, "paged_attention"), (T0 + 0.2, T0 + 0.3, "sort")])
    record = _record(0, T0, 100, [T0 + 0.1, T0 + 0.2])
    for other in (MODEL_CONFIGS["mistral-7b"], object()):
        assert read(name, _context(trace, [record], model=other)) is None
    assert read(name, _context(None, [record], model)) is None
    # this family's model on a trace without the kernels (a CPU rehearsal)
    empty = reduced(modules=[(T0, T0 + 0.5, "jit__decode_and_sample", "decode")])
    assert read(name, _context(empty, [record], model)) is None


def test_state_rows_reader_reads_the_step_records():
    ring = StepTimeline("0")
    ring.step(1, "prefill", 4, 3, 512, T0 + 0.0, T0 + 0.3,
              StepCounts(0.0, 0.0, 0.0, 3.0, 900.0))
    ring.step(2, "decode", 32, 20, 8, T0 + 0.3, T0 + 0.4,
              StepCounts(0.0, 0.0, 0.0, 20.0, 20.0))
    ring.step(3, "decode_fb", 32, 24, 8, T0 + 0.4, T0 + 0.5,
              StepCounts(0.0, 0.0, 0.0, 24.0, 24.0))
    ring.step(4, "decode", 32, 31, 8, T0 + 1.4, T0 + 1.5,
              StepCounts(0.0, 0.0, 0.0, 31.0, 31.0))          # outside the window
    ctx = _context(None)
    assert read("state.rows_live_mean", ctx) == pytest.approx(22.0)
    assert ctx.notes["state.rows_live"] == {"steps": 2, "min": 20.0, "max": 24.0}


def test_state_rows_reader_reports_nothing_without_the_count():
    ring = StepTimeline("0")
    ring.step(1, "decode", 8, 8, 4, T0 + 0.3, T0 + 0.4)      # a GQA engine's step
    assert read("state.rows_live_mean", _context(None)) is None
    ring = StepTimeline("0")                                  # the latent family's
    ring.step(1, "decode", 8, 8, 4, T0 + 0.3, T0 + 0.4, StepCounts(0.3, 32.0, 20.0))
    assert read("state.rows_live_mean", _context(None)) is None


# ------------------------------------------------------- the cell, rehearsed

TINY = {   # olmo-hybrid-test's geometry, as a config.json
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 512, "rms_norm_eps": 1e-06,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "family": "olmo_hybrid",
    "check": {"prompt_lengths": [100, 40, 20], "decode_positions": 4},
    "engine": {"quant": "int8", "kv_quant": "", "dtype": "float32", "page_size": 32,
               "num_pages": 48, "prefix_cache": False,
               # the suite's 8 CPU devices as replicas of the data axis: the
               # family refuses a model axis wider than one device
               "mesh_shape": "8x1", "embedding_model": "encoder-tiny"},
    "logits_tolerance": {"atol": 2e-3, "rtol": 2e-3}, "check_seed": 5,
}
MIX = {"kind": "open_loop", "arrivals": "poisson", "schedule_seed": 1,
       "prompt_tokens": {"dist": "log_uniform", "low": 32, "high": 60},
       "max_tokens": {"dist": "log_uniform", "low": 3, "high": 8},
       "drain_seconds": 30, "trace_seconds": 1.0,
       "engine": {"max_seq_len": 128, "prefill_buckets": [64],
                  "prefill_max_batch": 2, "max_batch": 4}}


def test_rehearsal_of_the_cell_traced(cell, capsys):
    """``run.measure`` at a tiny size on the CPU: the check's 100-token prompt
    is above the 64 bucket and takes the engine's chunk path, the others the
    dense program; the counter reader reads the step records."""
    from benchmark import run
    from mcp_context_forge_tpu.config import reset_settings_cache

    tiny_cell = manifest.Cell(**{**cell.__dict__, "config": "bench-tiny-hybrid"})
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
    assert logits["ok"] and len(logits["position_max_abs_err"]) == 3 * 5
    assert logits["attn"] == {"prefill": "reference", "chunk": "gather",
                              "decode": "gather", "delta": "jnp"}
    assert notes["requests"]["serving_compiles"] == 0
    live = result["metrics"]["state.rows_live_mean"]
    assert live["unit"] == "rows" and 1.0 <= live["value"] <= 4.0
    # no device plane on the CPU: the kernel readers are left out
    assert not set(NEW_READERS[:4]) & set(result["metrics"])
    json.dumps(result)


def test_engine_logits_takes_the_chunk_path_only_above_the_bucket():
    family = families.load("olmo_hybrid")
    logits = family.EngineLogits.__new__(family.EngineLogits)
    logits.chunk = 512
    assert [logits.chunked(n) for n in (700, 513, 512, 384, 96)] == [
        True, True, False, False, False]
