"""What PR 43 added to the benchmark for ``trinity-mini-d8`` (its cell an open
loop at a fixed rate since PR 47, ``mixedctx-open`` in place of
``mixedctx-closed``): the manifest's new
entries (held by name, never by position), the configuration file against the
catalog's published keys, the bytes the issue reckoned from those keys, the mix
and the cell letter for letter, the family file's contract, the window cost at
hand-counted sizes, the four new per-layer readers on a small synthetic trace
and ring (and reporting nothing where the program lacks what they read), and a
CPU rehearsal of the cell at a tiny size."""

import asyncio
import json
import os
import sys

import pytest

from benchmark import families
from benchmark.harness import (correct, kernel_cost, layers, manifest, stats,
                               trace_reduce, window_cost)
from mcp_context_forge_tpu.observability.timeline import StepCounts, StepTimeline

T0, NS0 = 100.0, 5e9
CELL, CONFIG = "trinity-mini-d8.mixedctx-open", "trinity-mini-d8"
NEW_READERS = ("window_paged_attention_roofline", "window_chunk_attention_roofline",
               "window.visible_share_mean", "paged_attention.device_share")
APPENDED_TO = ("decode.device_ms_per_step", "decode.host_gap_ms_mean",
               "device.idle_share.serve", "device.idle_share.host.serve",
               "decode.retire_interval_ms_p95", "decode.prefill_stall_share")
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {   # the catalog row's ``config``, key by key
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "layer_types": PERIOD * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
CUT = {"num_hidden_layers": 8, "num_dense_layers": 1}
# the list of a kind a layer follows the depth: a changed group is named too
REDUCED = [*CUT, "layer_types"]


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


# ------------------------------------------------------------ the manifest

def test_the_cell_and_what_it_reports(doc, cell):
    assert (cell.config, cell.traffic, cell.chips) == (CONFIG, "mixedctx-open", 1)
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= set(NEW_READERS) | set(APPENDED_TO)
    # readers of other families' kernels and counters, and the GQA trunk's two
    # rooflines (which count every layer at the whole context): not this cell's
    assert not names & {
        "paged_attention_roofline", "prefill_attention_roofline",
        "hybrid_paged_attention_roofline", "sparse.selected_share_mean",
        "mla_decode_attention_roofline", "diffusion.tokens_per_pass",
        "state.rows_live_mean", "spec.tokens_per_step"}
    for name in names:
        layers.load_reader(name)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    moves = {"window_paged_attention_roofline": "tpot_p95_ms",
             "window_chunk_attention_roofline": "ttft_p50_ms",
             "window.visible_share_mean": "tpot_p95_ms",
             "paged_attention.device_share": "tpot_p95_ms"}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == moves[name] and by_name[name]["unit"] == "%"
    assert by_name["window.visible_share_mean"]["source"] == "program_counter"
    assert by_name["window.visible_share_mean"]["layer"] == "decode step"
    assert by_name["window.visible_share_mean"]["better"] == "lower"
    assert by_name["paged_attention.device_share"]["better"] == "lower"
    for name in set(NEW_READERS) - {"window.visible_share_mean"}:
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["layer"] == "kernels"
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"].count(CELL) == 1   # by name: later cells follow it
        assert CELL.replace("-open", "-closed") not in metric.get("workloads", ())
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/trinity-mini-d8.json"
    assert len(doc["workloads"]) >= 8
    why = next(w["why"] for w in doc["workloads"] if w["name"] == CELL)
    rate = manifest.read_json(cell.cell_file)["rate_rps"]
    for words in ("open loop", f"{rate} req/s", "0.7 x", "1024-16000", "256-512",
                  "window", "GB"):
        assert words in why, words


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_holds_the_published_key(config, key):
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key]
        assert config["published"][key] == PUBLISHED[key]
    elif key == "layer_types":      # a list a layer: the two periods kept
        assert config[key] == PERIOD * 2 and "layer_types" in config["published"]
        assert key in config["reduced"]
    else:
        assert config[key] == PUBLISHED[key]


def test_configuration_states_its_cut_and_what_it_assumed(config, model):
    assert config["source"] == SOURCE and config["family"] == "afmoe"
    assert config["reduced"] == REDUCED
    assert set(config["published"]) == set(CUT) | {"layer_types"}
    for key in ("qk_norm", "output_gate", "full_layers_unrotated", "four_norms",
                "router_bias", "window_mask", "rotary_pairing", "depth_scaled_norms",
                "load_balance_coeff", "moe_block", "weights", "tokenizer", "ring"):
        assert len(config["assumed"][key]) > 40, key
    assert "quarter" in config["deployment"] and "pipeline" in config["deployment"]
    assert config["engine"] == {
        "quant": "int8", "kv_quant": "", "dtype": "bfloat16", "page_size": 128,
        "num_pages": 4352, "prefix_cache": False}
    assert config["guarantees"]["serving_compiles"] == 0
    assert "greedy" in config["guarantees"]["decoding"]
    tolerance = config["logits_tolerance"]
    assert set(tolerance) == {"atol", "rtol", "positions_within", "atol_any", "reason"}
    for words in ("no_window", "rotate_full", "no_gate", "int8"):
        assert words in tolerance["reason"]
    assert type(config["check_seed"]) is int and config["check_seed"] > 2 ** 31
    # what the program makes of the keys
    assert (model.n_layers, model.n_dense_layers, model.sliding_window) == (8, 1, 2048)
    assert [model.mixer_kind(i) for i in range(8)] == (["window"] * 3 + ["full"]) * 2
    assert [model.ffn_kind(i) for i in range(8)] == ["dense"] + ["experts"] * 7
    assert (model.n_experts, model.moe_top_k, model.routed_scaling_factor) == \
        (128, 8, 2.826)
    assert model.moe_block == config["moe_block"]


def test_weights_and_cache_are_the_bytes_the_issue_reckoned(config, model):
    """Section 3 of the issue, reckoned again from the file's keys."""
    c = config
    D, hd = c["hidden_size"], c["head_dim"]
    Q, KV = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    attention = 3 * D * Q + 2 * D * KV                      # W_q W_g W_o, W_k W_v
    assert attention == 27_262_976
    dense = 3 * D * c["intermediate_size"]
    assert dense == 37_748_736
    experts = c["num_experts"] * 3 * D * c["moe_intermediate_size"]
    shared = c["num_shared_experts"] * 3 * D * c["moe_intermediate_size"]
    router = D * c["num_experts"]
    assert (experts, shared, router) == (805_306_368, 6_291_456, 262_144)
    layers_, dense_layers = c["num_hidden_layers"], c["num_dense_layers"]
    matrices = (layers_ * attention + dense_layers * dense
                + (layers_ - dense_layers) * (experts + shared + router)
                + 2 * c["vocab_size"] * D)
    assert matrices == 6_758_858_752                         # 6.76 GB in int8
    from mcp_context_forge_tpu.tpu_local.models import afmoe
    small = afmoe.param_count(model) - matrices              # norms and biases
    assert 0 < small < 2e5
    # the cache: full layers a page id, window layers a ring a sequence
    from mcp_context_forge_tpu.tpu_local.kv import (kv_page_bytes, kv_state_bytes,
                                                    state_rows_for)
    page = c["engine"]["page_size"]
    one = page * c["num_key_value_heads"] * hd * 2 * 2       # K + V of a layer's page
    assert one == 262_144
    mix = manifest.read_json(os.path.join(os.path.dirname(os.path.dirname(
        manifest.cell(manifest.load(), CELL).config_file)), "traffic",
        "mixedctx-open.json"))
    rows = state_rows_for(model, mix["engine"]["max_batch"])
    assert rows == 33
    assert kv_page_bytes(model, page) == 2 * one             # 2 full layers
    assert kv_state_bytes(model, 1) == 6 * 25 * one          # 6 window layers' rings
    full = c["engine"]["num_pages"] * kv_page_bytes(model, page)
    window = kv_state_bytes(model, rows)
    assert round(full / 1e9, 2) == 2.28 and round(window / 1e9, 2) == 1.30
    # every row at max_seq_len fits the pool, with pages to spare
    per_row = mix["engine"]["max_seq_len"] // page
    assert per_row == 132
    assert 32 * per_row + 128 == c["engine"]["num_pages"]
    # stored like full layers the same rows would not fit beside the weights
    assert 8 * one * c["engine"]["num_pages"] > 9.1e9
    assert (matrices + full + window) / 16e9 < 0.66


def test_the_mix_and_the_cell_are_the_issues(doc, cell):
    mix = manifest.read_json(cell.traffic_file)
    what = mix.pop("what")
    assert "summarizer" in what and "moderation" in what and "/v1" in what
    assert "do not wait" in what
    # the mix letter for letter: test_benchmark_open_cells.py
    assert mix["kind"] == "open_loop"
    params = manifest.read_json(cell.cell_file)
    assert set(params) == {"rate_rps"} and 1.0 <= params["rate_rps"] <= 2.8
    assert 16000 + 512 <= mix["engine"]["max_seq_len"]
    # a quarter of the window's prompts inside the model's window of 2048,
    # three quarters 1-8 x past it (the grid of the requests a run offers)
    from benchmark.harness.draw import grid
    offered = round(params["rate_rps"] * doc["run_seconds"])
    lengths = grid(mix["prompt_tokens"], offered)
    inside = sum(length <= 2048 for length in lengths)
    assert abs(inside - offered / 4) <= 1
    assert min(lengths) >= 1024 and max(lengths) <= 16000
    assert sum(length > 4096 for length in lengths) >= 0.48 * offered


def test_family_file_keeps_the_contract(config, monkeypatch):
    family = families.load("afmoe")
    assert all(hasattr(family, name) for name in families.CONTRACT)
    assert family.reference == "afmoe_plain"
    reference = families.reference_of(family)
    assert callable(reference.forward)
    assert reference.VARIANTS == (None, "no_window", "rotate_full", "no_gate",
                                  "int8_activations")
    source = open(reference.__file__, encoding="utf-8").read()
    assert "mcp_context_forge_tpu" not in source.replace(
        "nothing imported from\n``mcp_context_forge_tpu``", "")
    mix = manifest.read_json(manifest.cell(manifest.load(), CELL).traffic_file)
    check = correct.check_of(config, mix)
    assert check.prompt_lengths == (4608, 2304, 640) and check.decode_positions == 8
    bucket = mix["engine"]["prefill_buckets"][0]
    window = config["sliding_window"]
    # past W + a chunk (the ring has wrapped), just past W, inside W and dense
    assert check.prompt_lengths[0] > window + bucket + 128
    assert window < check.prompt_lengths[1] < window + bucket
    assert check.prompt_lengths[2] < bucket <= window
    # a configuration the program computes otherwise is refused by its key
    with pytest.raises(ValueError, match="score_func"):
        family.model_config("x", {**config, "score_func": "softmax"})
    with pytest.raises(ValueError, match="layer_types"):
        family.model_config("x", {**config, "layer_types": ["full_attention"] * 8})


def test_family_refuses_a_program_without_the_model_family(monkeypatch):
    """On a program whose ``models/configs.py`` has no ``AfmoeConfig`` (the
    parent commit) the family file fails at import, where ``run.main`` looks it
    up: before any device work."""
    from mcp_context_forge_tpu.tpu_local.models import configs

    monkeypatch.delattr(configs, "AfmoeConfig")
    monkeypatch.delitem(sys.modules, "benchmark.families.afmoe", raising=False)
    with pytest.raises(ImportError, match="AfmoeConfig"):
        families.load("afmoe")
    monkeypatch.undo()
    assert families.load("afmoe").reference == "afmoe_plain"


# ------------------------------------------------------------------ the cost

GEOMETRY = (32, 4, 128)     # query heads, kv heads, head_dim


@pytest.mark.parametrize("context,seen", [(1500, 1500), (2048, 2048),
                                          (16000, 2048)],
                         ids=["under_W", "at_W", "far_past_W"])
def test_decode_cost_at_hand_counted_sizes(context, seen):
    """6 window layers see min(context, 2048) keys, 2 full layers the context:
    4 operations a key a head dimension; K and V read once, q in, out out."""
    ops, nbytes = window_cost.decode_token(context, 6, 2, 2048, *GEOMETRY)
    keys = 6 * seen + 2 * context
    assert ops == 4.0 * keys * 32 * 128
    assert nbytes == 2.0 * keys * 4 * 128 * 2 + 8 * 2.0 * 32 * 128 * 2
    # a model of full layers alone is kernel_cost's
    assert window_cost.decode_token(context, 0, 8, 2048, *GEOMETRY) == tuple(
        8 * x for x in kernel_cost.decode_attention(context, *GEOMETRY))


@pytest.mark.parametrize("new,history,pairs,keys", [
    (1000, 0, 1000 * 1001 // 2, 1000),               # under W: the causal triangle
    (1024, 1024, 1024 * 1024 + 1024 * 1025 // 2, 2048),   # ends exactly at W
    (1024, 1536, 512 * 1536 + 512 * 513 // 2 + 512 * 2048, 2560),  # fills inside
    (1024, 14976, 1024 * 2048, 2048 + 1023),         # far past it: W a query
], ids=["under_W", "at_W", "crossing_W", "far_past_W"])
def test_chunk_cost_at_hand_counted_sizes(new, history, pairs, keys):
    assert window_cost.window_pairs(new, history, 2048) == pairs
    assert window_cost.window_keys(new, history, 2048) == keys
    brute = sum(min(history + i, 2048) for i in range(1, new + 1))
    assert brute == pairs
    ops, nbytes = window_cost.chunk(new, history, 6, 2, 2048, *GEOMETRY)
    full_ops, full_bytes = kernel_cost.prefill_attention(new, history, *GEOMETRY)
    assert ops == 2 * full_ops + 6 * 4.0 * pairs * 32 * 128
    assert nbytes == 2 * full_bytes + 6 * (2.0 * keys * 4 * 128 * 2
                                           + 2.0 * new * 32 * 128 * 2)


def test_layers_of_reads_the_model_or_nothing(model):
    assert window_cost.layers_of(model) == (6, 2, 2048)
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
    assert window_cost.layers_of(MODEL_CONFIGS["llama3-test"]) is None
    assert window_cost.layers_of(MODEL_CONFIGS["olmo-hybrid-test"]) is None


# --------------------------------------------------------------- the readers

def _record(index, sent, prompt, token_times, max_tokens=64):
    record = stats.Record(index, sent, prompt, max_tokens)
    record.sent = sent
    record.token_times = list(token_times)
    return record


def _context(trace, records=(), model=None):
    return layers.LayerContext(
        records=list(records), window=(T0, T0 + 1.0), stats={}, model=model,
        peak=kernel_cost.peaks("TPU v5 lite"), trace=trace,
        trace_span=(T0, T0 + 1.0))


def _keys(context, window):
    return StepCounts(window / context, 7.0, 56.0, context_keys=context,
                      window_keys=window)


def test_visible_share_reads_the_step_records():
    ring = StepTimeline("0")
    ring.step(1, "chunk", 2, 2, 1024, T0 + 0.0, T0 + 0.1, _keys(9000.0, 4096.0))
    ring.step(2, "decode", 32, 3, 128, T0 + 0.1, T0 + 0.2, _keys(20000.0, 5000.0))
    ring.step(3, "decode_fb", 32, 3, 128, T0 + 0.2, T0 + 0.3, _keys(1000.0, 1000.0))
    ring.step(4, "decode", 32, 3, 128, T0 + 1.3, T0 + 1.4, _keys(100.0, 1.0))
    ctx = _context(None)
    # the window's two decode steps: a quarter and the whole
    assert read("window.visible_share_mean", ctx) == pytest.approx(100 * (0.25 + 1) / 2)
    assert ctx.notes["window.visible_share"] == {"steps": 2, "min": 0.25, "max": 1.0}
    del ring
    # step records without the counts (another family's): nothing
    other = StepTimeline("0")
    other.step(1, "decode", 32, 3, 8, T0 + 0.1, T0 + 0.2, StepCounts(1.0, 4.0, 1.0))
    other.step(2, "decode", 32, 3, 8, T0 + 0.2, T0 + 0.3, None)
    assert read("window.visible_share_mean", _context(None)) is None
    del other


def test_kernel_readers_on_a_synthetic_trace(model):
    trace = reduced(
        modules=[(T0 + 0.0, T0 + 0.1, "jit__prefill_hist_and_sample", "prefill_hist"),
                 (T0 + 0.11, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.21, T0 + 0.32, "jit__decode_and_sample", "decode"),
                 (T0 + 0.4, T0 + 0.5, "jit__prefill_and_sample", "prefill")],
        ops=[(T0 + 0.05, T0 + 0.07, "paged_attention"),     # a chunk round's
             (T0 + 0.12, T0 + 0.121, "paged_attention"),
             (T0 + 0.22, T0 + 0.221, "paged_attention"),
             (T0 + 0.45, T0 + 0.46, "flash_attention")])
    # a prompt of 9000 sent at T0, first token at 0.09, then two decode tokens
    record = _record(0, T0, 9000, [T0 + 0.09, T0 + 0.21, T0 + 0.31])
    ctx = _context(trace, [record], model)
    peak = ctx.peak
    ops = nbytes = 0.0
    for context in (9001, 9002):
        o, b = window_cost.decode_token(context, 6, 2, 2048, 32, 4, 128)
        ops, nbytes = ops + o, nbytes + b
    least = max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    value = read("window_paged_attention_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.002, rel=1e-6)
    assert 0 < value <= 100          # a reading over 100 % is a failure
    note = ctx.notes["window_paged_attention_roofline"]
    assert note["bound"] == "memory" and note["calls"] == 2
    assert (note["window_layers"], note["full_layers"], note["window"]) == (6, 2, 2048)
    # the GQA trunk's reader would count 8 layers at the whole context
    trunk = read("paged_attention_roofline", _context(trace, [record], model))
    assert trunk == pytest.approx(
        value * 8 * 9001.5 / (6 * 2048 + 2 * 9001.5), rel=5e-3)
    # the prefill: the whole prompt's attention, its span wholly in the trace
    o, b = window_cost.chunk(9000, 0, 6, 2, 2048, 32, 4, 128)
    least = max(o / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
    value = read("window_chunk_attention_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.03, rel=1e-6)
    note = ctx.notes["window_chunk_attention_roofline"]
    assert note["bound"] == "compute"
    assert (note["flash_calls"], note["paged_calls"]) == (1, 1)
    # the kernel's share of the decode programs
    assert read("paged_attention.device_share", ctx) == pytest.approx(100 * 0.002 / 0.2)


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_report_nothing_where_there_is_nothing_to_read(name, model):
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
    empty = reduced()
    record = _record(0, T0, 300, [T0 + 0.09, T0 + 0.21])
    assert read(name, _context(None, [record], model)) is None       # no trace
    assert read(name, _context(empty, [record], model)) is None      # no kernel
    busy = reduced(
        modules=[(T0 + 0.1, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.0, T0 + 0.1, "jit__prefill_and_sample", "prefill")],
        ops=[(T0 + 0.1, T0 + 0.101, "paged_attention"),
             (T0 + 0.05, T0 + 0.06, "flash_attention")])
    trunk = _context(busy, [record], MODEL_CONFIGS["llama3-test"])
    if name.endswith("_roofline"):      # a model without window layers
        assert read(name, trunk) is None


# ------------------------------------------------------- the cell, rehearsed

TINY = {   # afmoe-test's geometry, as a config.json
    "model_type": "afmoe", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 1,
    "num_shared_experts": 1, "route_scale": 2.826, "score_func": "sigmoid",
    "route_norm": True, "n_group": 1, "topk_group": 1, "mup_enabled": True,
    "sliding_window": 64, "global_attn_every_n_layers": 4,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "max_position_embeddings": 512, "hidden_act": "silu",
    "tie_word_embeddings": False, "moe_block": 8,
    "family": "afmoe",
    "check": {"prompt_lengths": [150, 70, 20], "decode_positions": 6},
    "engine": {"quant": "int8", "kv_quant": "", "dtype": "float32", "page_size": 32,
               "num_pages": 64, "prefix_cache": False, "moe_impl": "grouped",
               # the suite's 8 CPU devices as replicas of the data axis
               "mesh_shape": "8x1", "embedding_model": "encoder-tiny"},
    "logits_tolerance": {"atol": 2e-3, "rtol": 2e-3}, "check_seed": 5,
}
MIX = {"kind": "open_loop", "arrivals": "poisson", "schedule_seed": 1,
       "prompt_tokens": {"dist": "log_uniform", "low": 40, "high": 200},
       "max_tokens": {"dist": "uniform", "low": 6, "high": 12},
       "temperature": 0.0, "shared_prefix_tokens": 0,
       "drain_seconds": 30, "trace_seconds": 1.0,
       "engine": {"max_seq_len": 256, "prefill_buckets": [32],
                  "prefill_max_batch": 2, "max_batch": 4}}


def test_rehearsal_of_the_cell_traced(cell, capsys, tmp_path, monkeypatch):
    """``run.measure`` at a tiny size on the CPU (a window of 2 pages, a ring
    of 38 with the default slack: the check's 150-token prompt crosses the
    window in chunk rounds): correct, exact accounting, and the counter reader
    reads the records."""
    from benchmark import run
    from mcp_context_forge_tpu.config import reset_settings_cache

    # a trace directory of its own: the other files' traced rehearsals share
    # the checkout's, and clear it, while this one runs beside them
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))

    tiny_cell = manifest.Cell(**{**cell.__dict__, "config": "bench-tiny-afmoe"})
    saved = dict(os.environ)
    try:
        result = asyncio.run(run.measure(tiny_cell, TINY, MIX, {"rate_rps": 3.0},
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
    assert result["attempted"] >= 6 and result["failed"] == 0
    logits = notes["logits_vs_reference"]
    assert logits["ok"] and len(logits["position_max_abs_err"]) == 3 * 7
    assert logits["attn"]["chunk"] == "gather" and logits["attn"]["decode"] == "gather"
    assert len(notes["greedy_repeats"]["tokens"]) == 8
    assert notes["accounting"]["held"] and notes["accounting"]["ok"]
    assert notes["requests"]["serving_compiles"] == 0
    metrics = result["metrics"]
    share = metrics["window.visible_share_mean"]
    assert share["unit"] == "%" and 0 < share["value"] < 100
    assert metrics["decode.retire_interval_ms_p95"]["value"] > 0
    # no device plane on the CPU: the kernel readers are left out
    assert not {"window_paged_attention_roofline", "window_chunk_attention_roofline",
                "paged_attention.device_share"} & set(metrics)
    json.dumps(result)
