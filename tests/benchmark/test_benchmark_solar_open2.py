"""What PR 50 added to the benchmark for ``solar-open2-d8-ep8``: the
manifest's new entries (held by name, never by position), the configuration
file against the catalog's published keys, the bytes the issue reckoned from
those keys, the cell and the mix it reuses, the family file's contract, the
KDA cost at hand-counted sizes, the three new per-layer readers on a small
synthetic trace (and reporting nothing where the program lacks what they
read), and a CPU rehearsal of the cell at a tiny size."""

import asyncio
import json
import os
import sys

import pytest

from benchmark import families
from benchmark.harness import (correct, gdn_cost, kda_cost, kernel_cost, layers,
                               manifest, stats, trace_reduce)

T0, NS0 = 100.0, 5e9
CELL, CONFIG = "solar-open2-d8-ep8.mixedctx-open", "solar-open2-d8-ep8"
NEW_READERS = ("kda_step_roofline", "kda_chunk_roofline", "kda_mixer.device_share")
APPENDED_TO = ("decode.device_ms_per_step", "decode.host_gap_ms_mean",
               "decode.retire_interval_ms_p95", "decode.prefill_stall_share",
               "device.idle_share.serve", "device.idle_share.host.serve",
               "moe.local_pairs_per_token")
SOURCE = "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
PUBLISHED = {   # the catalog row's ``config``, key by key
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": list(range(0, 48, 4)),
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}
CUT = {"num_hidden_layers": 8, "n_routed_experts": 40, "vocab_size": 24576}
# the list of the GQA layers follows the depth: a changed group is named too
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]


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
    # readers of other families' kernels and counters, the scalar-decay
    # kernels' among them, and the GQA trunk's two rooflines (which count
    # every layer as an attending one): not this cell's. Nor, though they
    # would read it, ``hybrid_paged_attention_roofline`` and
    # ``state.rows_live_mean``: an accepted test holds their lists to Olmo's
    # cell alone (tests/benchmark/test_benchmark_olmo_hybrid.py; PERF.md 7)
    assert not names & {
        "hybrid_paged_attention_roofline", "state.rows_live_mean",
        "paged_attention_roofline", "prefill_attention_roofline",
        "gdn_step_roofline", "gdn_chunk_roofline", "linear_mixer.device_share",
        "window_paged_attention_roofline", "sparse.selected_share_mean",
        "mla_decode_attention_roofline", "diffusion.tokens_per_pass",
        "spec.tokens_per_step"}
    for name in names:
        layers.load_reader(name)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    moves = {"kda_step_roofline": ("tpot_p95_ms", "higher"),
             "kda_chunk_roofline": ("ttft_p50_ms", "higher"),
             "kda_mixer.device_share": ("ttft_p50_ms", "lower")}
    for name in NEW_READERS:
        entry = by_name[name]
        assert CELL in entry["workloads"]      # by name: later cells may follow it
        assert (entry["moves"], entry["better"]) == moves[name]
        assert (entry["unit"], entry["source"], entry["layer"]) == (
            "%", "device_trace", "kernels")
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"].count(CELL) == 1   # by name: later cells follow it
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/solar-open2-d8-ep8.json"
    assert len(doc["workloads"]) >= 9 and len(doc["configs"]) >= 8
    why = next(w["why"] for w in doc["workloads"] if w["name"] == CELL)
    rate = manifest.read_json(cell.cell_file)["rate_rps"]
    for words in ("open loop", f"{rate} req/s", "1024-16000", "256-512", "KDA",
                  "1/8", "GB"):
        assert words in why, words


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_holds_the_published_key(config, key):
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key]
        assert config["published"][key] == PUBLISHED[key]
    elif key == "gqa_layers":       # a list of layers: the two periods kept
        assert config[key] == [0, 4] and key in config["reduced"]
        assert "gqa_layers" in config["published"]
    else:
        assert config[key] == PUBLISHED[key]


def test_configuration_states_its_cut_and_what_it_assumed(config, model):
    assert config["source"] == SOURCE and config["family"] == "solar_open2"
    assert config["reduced"] == REDUCED
    assert set(config["published"]) == set(REDUCED)
    assert config["experts_held"] == [0, 40]
    # the guide's floors: a period + 4 layers, 8 experts, an eighth
    assert config["num_hidden_layers"] >= 4 + 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key in ("kda_layer", "A_log_dt_bias", "norm_placement", "qk_norm",
                "gqa_gate", "rotary_embedding", "router", "intermediate_size",
                "precision", "n_routed_experts", "vocab_size", "moe_block",
                "weights", "tokenizer"):
        assert len(config["assumed"][key]) > 40, key
    for words in ("48 chips", "EP8", "pipeline", "8 x their share", "1/8"):
        assert words in config["deployment"], words
    assert config["engine"] == {
        "quant": "int8", "kv_quant": "", "dtype": "bfloat16", "page_size": 128,
        "num_pages": 4352, "prefix_cache": False}
    assert config["guarantees"]["serving_compiles"] == 0
    assert "float32" in config["guarantees"]["state"]
    tolerance = config["logits_tolerance"]
    assert set(tolerance) == {"atol", "rtol", "positions_within", "atol_any", "reason"}
    for words in ("bf16_state", "scalar_decay", "no_gate", "int8"):
        assert words in tolerance["reason"]
    assert type(config["check_seed"]) is int and config["check_seed"] > 2 ** 31
    # what the program makes of the keys
    assert (model.n_layers, model.gqa_layers, model.vocab_size) == (8, (0, 4), 24576)
    assert [model.mixer_kind(i) for i in range(8)] == (
        ["full_attention"] + ["linear_attention"] * 3) * 2
    assert model.layers_of("full_attention") == (0, 4)
    assert (model.n_experts, model.experts_held, model.n_held, model.moe_top_k,
            model.routed_scaling_factor) == (320, (0, 40), 40, 8, 1)
    assert (model.n_heads, model.n_kv_heads, model.head_dim) == (64, 8, 128)
    assert (model.linear_n_heads, model.linear_key_dim, model.linear_value_dim,
            model.gate_rank, model.conv_kernel) == (64, 128, 128, 128, 4)
    assert model.allow_neg_eigval and model.moe_block == config["moe_block"]


def test_weights_and_cache_are_the_bytes_the_issue_reckoned(config, model):
    """The issue's Tentpole 2, reckoned again from the file's keys."""
    c = config
    D, hd, F = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    lin = c["linear_attn_config"]
    wide = D * lin["num_heads"] * lin["head_dim"]
    pair = D * lin["head_dim"] + lin["head_dim"] * lin["num_heads"] * lin["head_dim"]
    conv = lin["short_conv_kernel_size"] * 3 * lin["num_heads"] * lin["head_dim"]
    kda = 4 * wide + 2 * pair + D * lin["num_heads"] + conv
    assert (wide, pair, conv) == (33_554_432, 1_572_864, 98_304)
    assert round(kda / 1e6, 1) == 137.7
    Q, KV = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    gqa = 3 * D * Q + 2 * D * KV                            # W_q W_g W_o, W_k W_v
    assert round(gqa / 1e6, 1) == 109.1
    expert = 3 * D * F
    ffn = D * c["published"]["n_routed_experts"] + (
        c["n_shared_experts"] + c["n_routed_experts"]) * expert
    assert (expert, round(ffn / 1e6, 1)) == (15_728_640, 646.2)
    matrices = (6 * (kda + ffn) + 2 * (gqa + ffn) + 2 * c["vocab_size"] * D)
    assert round(matrices / 1e9, 2) == 6.42
    from mcp_context_forge_tpu.tpu_local.models import solar_open2
    small = solar_open2.param_count(model) - matrices       # norms, biases, A, dt
    assert 0 < small < 2e5
    # the published model from the same layout: "250B-A15B" (the issue's 250.5
    # and 14.7 round a layer's 0.155 / 0.126 B outside the experts to 0.15 / 0.13)
    whole = lambda experts: 48 * (D * 320 + (1 + experts) * expert) + 36 * kda \
        + 12 * gqa + 2 * PUBLISHED["vocab_size"] * D
    assert round(whole(320) / 1e9, 1) == 250.3 and round(whole(8) / 1e9, 1) == 14.7
    # the cache: GQA layers a page id, KDA layers a state row a sequence
    from mcp_context_forge_tpu.tpu_local.kv import (kv_page_bytes, kv_state_bytes,
                                                    state_rows_for)
    page = c["engine"]["page_size"]
    mix = manifest.read_json(manifest.cell(manifest.load(), CELL).traffic_file)
    rows = state_rows_for(model, mix["engine"]["max_batch"])
    assert rows == 33
    assert kv_page_bytes(model, page) == 2 * page * 2 * 8 * 128 * 2 == 1_048_576
    a_row = kv_state_bytes(model, 1)
    assert a_row == 6 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert a_row == 26_050_560                               # the issue's 26.0 MB
    pages = c["engine"]["num_pages"] * kv_page_bytes(model, page)
    state = kv_state_bytes(model, rows)
    assert round(pages / 1e9, 2) == 4.56 and round(state / 1e9, 2) == 0.86
    # every row at max_seq_len fits the pool, with pages to spare
    per_row = mix["engine"]["max_seq_len"] // page
    assert 32 * per_row + 128 == c["engine"]["num_pages"]
    assert 0.70 < (matrices + pages + state) / 16.9e9 < 0.75


def test_the_cell_reuses_the_mix_and_its_check_fits(doc, cell, config):
    mix = manifest.read_json(cell.traffic_file)
    assert mix["kind"] == "open_loop" and mix["schedule_seed"] == 23
    assert mix["engine"] == {"max_seq_len": 16896, "prefill_buckets": [1024],
                             "prefill_max_batch": 2, "max_batch": 32}
    params = manifest.read_json(cell.cell_file)
    assert set(params) == {"rate_rps"} and 0.2 <= params["rate_rps"] <= 3.0
    check = correct.check_of(config, mix)
    assert check.prompt_lengths == (2304, 640, 96) and check.decode_positions == 8
    bucket = mix["engine"]["prefill_buckets"][0]
    # three chunk rounds with the state carried, one dense prefill whose
    # half-length program the engine would also take, the shortest prefill
    assert 2 * bucket < check.prompt_lengths[0] <= 3 * bucket
    assert bucket // 2 < check.prompt_lengths[1] <= bucket
    assert check.prompt_lengths[2] < bucket // 2
    assert check.tokens <= mix["engine"]["max_seq_len"]
    # the trinity cell's requests: the same mix file, another rate
    other = manifest.cell(doc, "trinity-mini-d8.mixedctx-open")
    assert other.traffic_file == cell.traffic_file


def test_family_file_keeps_the_contract(config):
    family = families.load("solar_open2")
    assert all(hasattr(family, name) for name in families.CONTRACT)
    assert family.reference == "solar_open2_plain"
    reference = families.reference_of(family)
    assert callable(reference.forward)
    assert reference.VARIANTS == (None, "bf16_state", "scalar_decay", "no_gate",
                                  "int8_activations")
    source = open(reference.__file__, encoding="utf-8").read()
    assert "mcp_context_forge_tpu" not in source.replace(
        "nothing imported from ``mcp_context_forge_tpu``", "")
    assert "import" in source and "lax.scan" in source
    # a configuration the program computes otherwise is refused by its key
    with pytest.raises(ValueError, match="use_rope"):
        family.model_config("x", {**config, "use_rope": True})
    with pytest.raises(ValueError, match="kda_use_full_proj"):
        family.model_config("x", {**config, "kda_use_full_proj": True})
    with pytest.raises(ValueError, match="gqa_layers"):
        family.model_config("x", {**config, "gqa_layers": [3, 7]})
    with pytest.raises(ValueError, match="experts_held"):
        family.model_config("x", {**config, "experts_held": [0, 8]})


def test_family_refuses_a_program_without_the_model_family(monkeypatch):
    """On a program whose ``models/configs.py`` has no ``SolarOpen2Config``
    (the parent commit) the family file fails at import, where ``run.main``
    looks it up: before any device work."""
    from mcp_context_forge_tpu.tpu_local.models import configs

    monkeypatch.delattr(configs, "SolarOpen2Config")
    monkeypatch.delitem(sys.modules, "benchmark.families.solar_open2", raising=False)
    with pytest.raises(ImportError, match="SolarOpen2Config"):
        families.load("solar_open2")
    monkeypatch.undo()
    assert families.load("solar_open2").reference == "solar_open2_plain"


# ------------------------------------------------------------------ the cost

GEOMETRY = (64, 128, 128)       # KDA heads, key dim, value dim


def test_kda_cost_at_hand_counted_sizes():
    """Seven operations an entry of the 64 x 128 x 128 state a token; a token
    brings q, k (128 each), v and takes o (128 each) in bfloat16 and 128
    decays + 1 beta a head in float32; the float32 state in and out once."""
    entries = 64 * 128 * 128
    assert gdn_cost.state_bytes(*GEOMETRY) == 4 * entries == 4_194_304
    token = 64 * (4 * 128 * 2 + 129 * 4)
    assert kda_cost.token_bytes(*GEOMETRY) == token == 98_560
    # the scalar form's token brings 127 decay floats a head fewer
    assert token - gdn_cost.token_bytes(*GEOMETRY) == 64 * 127 * 4
    assert kda_cost.kda_step(*GEOMETRY) == (7.0 * entries, 2 * 4 * entries + token)
    ops, nbytes = kda_cost.kda_chunk(1000, *GEOMETRY)
    assert ops == 7.0 * 1000 * entries
    assert nbytes == 1000 * token + 2 * 4 * entries
    # a decode token is bound by its state's bytes, a prompt by the VPU's work
    peak = kernel_cost.peaks("TPU v5 lite")
    assert kernel_cost.least_seconds(*kda_cost.kda_step(*GEOMETRY), peak)[1] == "memory"


def test_kda_layers_reads_the_model_or_nothing(model):
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
    assert kda_cost.kda_layers(model) == 6
    assert kda_cost.kda_layers(MODEL_CONFIGS["solar-open2-test"]) == 6
    assert kda_cost.kda_layers(MODEL_CONFIGS["olmo-hybrid-test"]) is None
    assert kda_cost.kda_layers(MODEL_CONFIGS["llama3-test"]) is None


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


def test_kernel_readers_on_a_synthetic_trace(model):
    trace = reduced(
        modules=[(T0 + 0.0, T0 + 0.1, "jit__prefill_hist_and_sample", "prefill_hist"),
                 (T0 + 0.11, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.21, T0 + 0.32, "jit__decode_and_sample", "decode")],
        ops=[(T0 + 0.01, T0 + 0.05, "kda_chunk"),
             (T0 + 0.12, T0 + 0.121, "kda_step"),
             (T0 + 0.22, T0 + 0.221, "kda_step"),
             (T0 + 0.23, T0 + 0.24, "paged_attention"),
             (T0 + 0.06, T0 + 0.07, "gated_delta_chunk")])    # another family's
    # a prompt of 9000 sent at T0, first token at 0.09, then two decode tokens
    record = _record(0, T0, 9000, [T0 + 0.09, T0 + 0.21, T0 + 0.31])
    ctx = _context(trace, [record], model)
    peak = ctx.peak
    ops, nbytes = kda_cost.kda_step(*GEOMETRY)
    least = max(2 * 6 * ops / peak["bf16_flops_per_s"],
                2 * 6 * nbytes / peak["hbm_bytes_per_s"])
    value = read("kda_step_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.002, rel=1e-6)
    assert 0 < value <= 100          # a reading over 100 % is a failure
    note = ctx.notes["kda_step_roofline"]
    assert (note["bound"], note["calls"], note["decode_tokens"]) == ("memory", 2, 2)
    ops, nbytes = kda_cost.kda_chunk(9000, *GEOMETRY)
    least = max(6 * ops / peak["bf16_flops_per_s"],
                6 * nbytes / peak["hbm_bytes_per_s"])
    value = read("kda_chunk_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.04, rel=1e-6)
    assert 0 < value <= 100
    assert ctx.notes["kda_chunk_roofline"]["prompt_tokens"] == pytest.approx(9000)
    # the two kernels' share of the three step programs
    assert read("kda_mixer.device_share", ctx) == pytest.approx(
        100 * 0.042 / (0.1 + 0.09 + 0.11))
    # the scalar-decay family's readers find nothing of theirs to read here
    assert read("gdn_step_roofline", _context(trace, [record], model)) is None
    only_kda = reduced(modules=[(T0, T0 + 0.1, "jit__decode_and_sample", "decode")],
                       ops=[(T0 + 0.01, T0 + 0.02, "kda_step")])
    assert read("linear_mixer.device_share",
                _context(only_kda, [record], model)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_report_nothing_where_there_is_nothing_to_read(name, model):
    """No trace, no kernel of this name (the parent's program in any cell),
    another family's model."""
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
    record = _record(0, T0, 300, [T0 + 0.09, T0 + 0.21])
    assert read(name, _context(None, [record], model)) is None
    assert read(name, _context(reduced(), [record], model)) is None
    olmo = reduced(
        modules=[(T0 + 0.1, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.0, T0 + 0.1, "jit__prefill_and_sample", "prefill")],
        ops=[(T0 + 0.1, T0 + 0.101, "gated_delta_step"),
             (T0 + 0.05, T0 + 0.06, "gated_delta_chunk")])
    assert read(name, _context(olmo, [record], MODEL_CONFIGS["olmo-hybrid-test"])) is None
    named = reduced(
        modules=[(T0 + 0.1, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.0, T0 + 0.1, "jit__prefill_and_sample", "prefill")],
        ops=[(T0 + 0.1, T0 + 0.101, "kda_step"), (T0 + 0.05, T0 + 0.06, "kda_chunk")])
    if name.endswith("_roofline"):      # a model without KDA layers
        assert read(name, _context(named, [record],
                                   MODEL_CONFIGS["olmo-hybrid-test"])) is None


# ------------------------------------------------------- the cell, rehearsed

TINY = {   # solar-open2-test's geometry, as a config.json
    "model_type": "solar_open2", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "published": {"n_routed_experts": 16}, "experts_held": [4, 8],
    "num_experts_per_tok": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "first_k_dense_replace": 0, "use_rope": False,
    "use_gqa_gate": True, "gqa_interval": 3, "gqa_layers": [0, 4],
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "rms_norm_eps": 1e-05, "max_position_embeddings": 512,
    "tie_word_embeddings": False, "moe_block": 8,
    "family": "solar_open2",
    "check": {"prompt_lengths": [80, 40, 12], "decode_positions": 6},
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
    """``run.measure`` at a tiny size on the CPU (the check's 80-token prompt
    carries its state over three chunk rounds of 32): correct, exact
    accounting, and the counter readers read the step records."""
    from benchmark import run
    from mcp_context_forge_tpu.config import reset_settings_cache

    # a trace directory of its own: the other files' traced rehearsals share
    # the checkout's, and clear it, while this one runs beside them
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))

    tiny_cell = manifest.Cell(**{**cell.__dict__, "config": "bench-tiny-solar"})
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
    assert len(logits["position_routing_margin"]) == 3 * 7
    assert logits["attn"]["delta"] == "jnp" and logits["attn"]["chunk"] == "gather"
    assert set(logits["attn"]["experts"]) == {"1 tokens", "32 tokens"}
    assert len(notes["greedy_repeats"]["tokens"]) == 8
    assert notes["accounting"]["held"] and notes["accounting"]["ok"]
    assert notes["requests"]["serving_compiles"] == 0
    metrics = result["metrics"]
    # top-4 of 16 with 4 held: a pair a token under a uniform router
    assert 0.3 < metrics["moe.local_pairs_per_token"]["value"] < 3
    assert metrics["decode.retire_interval_ms_p95"]["value"] > 0
    # no device plane on the CPU: the kernel readers are left out
    assert not set(NEW_READERS) & set(metrics)
    json.dumps(result)
