"""What PR 53 added to the benchmark for ``granite-4.0-h-micro``: the
manifest's new entries (held by name, never by position), the configuration
file against the catalog's published keys, the bytes the issue reckoned from
those keys and the program's own tree, the new mix and the cell, the family
file's contract and refusals, the state-space cost at hand-counted sizes, the
four new per-layer readers on a small synthetic trace (and reporting nothing
where the program lacks what they read), and a CPU rehearsal of the cell at a
tiny size."""

import asyncio
import json
import os
import sys

import pytest

from benchmark import families
from benchmark.harness import (correct, gdn_cost, kernel_cost, layers, manifest,
                               ssd_cost, stats, trace_reduce)

T0, NS0 = 100.0, 5e9
CELL, CONFIG = "granite-4.0-h-micro.longgen-open", "granite-4.0-h-micro"
NEW_READERS = ("ssd_step_roofline", "ssd_chunk_roofline",
               "ssd_mixer.device_share", "ssd.rows_live_mean")
APPENDED_TO = ("decode.device_ms_per_step", "decode.host_gap_ms_mean",
               "decode.retire_interval_ms_p95", "decode.prefill_stall_share",
               "device.idle_share.serve", "device.idle_share.host.serve")
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
LAYER_TYPES = ["attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40)]
PUBLISHED = {   # the catalog row's ``config``, key by key
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": LAYER_TYPES, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


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
    assert (cell.config, cell.traffic, cell.chips) == (CONFIG, "longgen-open", 1)
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= set(NEW_READERS) | set(APPENDED_TO)
    # every reader without a list of cells is this cell's too
    assert names >= {m["name"] for m in doc["per_layer"] if "workloads" not in m}
    # readers of other families' kernels and counters, the delta rule's among
    # them (an accepted test holds their lists to Olmo's cell), and the GQA
    # trunk's rooflines (which count every layer as an attending one)
    assert not names & {
        "hybrid_paged_attention_roofline", "state.rows_live_mean",
        "paged_attention_roofline", "prefill_attention_roofline",
        "gdn_step_roofline", "gdn_chunk_roofline", "linear_mixer.device_share",
        "kda_step_roofline", "kda_mixer.device_share",
        "window_paged_attention_roofline", "sparse.selected_share_mean",
        "mla_decode_attention_roofline", "diffusion.tokens_per_pass",
        "spec.tokens_per_step", "moe.local_pairs_per_token"}
    for name in names:
        layers.load_reader(name)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    want = {"ssd_step_roofline": ("tpot_p95_ms", "higher", "%", "device_trace", "kernels"),
            "ssd_chunk_roofline": ("ttft_p50_ms", "higher", "%", "device_trace", "kernels"),
            "ssd_mixer.device_share": ("tpot_p95_ms", "lower", "%", "device_trace", "kernels"),
            "ssd.rows_live_mean": ("tpot_p95_ms", "higher", "rows", "program_counter",
                                   "decode step")}
    for name in NEW_READERS:
        entry = by_name[name]
        assert CELL in entry["workloads"]      # by name: later cells may follow it
        assert (entry["moves"], entry["better"], entry["unit"], entry["source"],
                entry["layer"]) == want[name]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"].count(CELL) == 1
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/granite-4.0-h-micro.json"
    assert len(doc["workloads"]) >= 10 and len(doc["configs"]) >= 9
    why = next(w["why"] for w in doc["workloads"] if w["name"] == CELL)
    rate = manifest.read_json(cell.cell_file)["rate_rps"]
    for words in ("open loop", f"{rate} req/s", "64-512", "512-1024", "rows",
                  "state", "nothing cut", "GB"):
        assert words in why, words
    assert len(why) <= 200


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_holds_the_published_key(config, key):
    assert config[key] == PUBLISHED[key]
    assert key not in config["reduced"]


def test_configuration_states_what_it_assumed_and_cuts_nothing(config, model):
    assert config["source"] == SOURCE and config["family"] == "granite_hybrid"
    assert config["reduced"] == [] and "published" not in config
    assert config["head_dim"] == 64 == config["hidden_size"] // config["num_attention_heads"]
    for key in ("head_dim", "mamba_layer", "mamba_init", "mamba_chunk_size",
                "block", "attention", "state_precision", "weights", "tokenizer"):
        assert len(config["assumed"][key]) > 40, key
    for words in ("z | xBC | dt", "BEFORE the norm", "time_step"):
        assert words in config["assumed"]["mamba_layer"], words
    assert "Way (b)" in config["assumed"]["attention"]
    assert "held once" in config["assumed"]["weights"]
    for words in ("one TPU v5e chip", "nothing cut", "65 rows", "1025 pages"):
        assert words in config["deployment"], words
    assert config["engine"] == {
        "quant": "int8", "kv_quant": "", "dtype": "bfloat16", "page_size": 128,
        "num_pages": 1025, "prefix_cache": False}
    assert config["guarantees"]["serving_compiles"] == 0
    assert "float32" in config["guarantees"]["state"]
    tolerance = config["logits_tolerance"]
    assert set(tolerance) == {"atol", "rtol", "positions_within", "atol_any", "reason"}
    for words in ("bf16_state", "padding", "no_skip", "divided by 8"):
        assert words in tolerance["reason"], words
    assert type(config["check_seed"]) is int and config["check_seed"] > 2 ** 31
    # what the program makes of the keys
    assert (model.n_layers, model.vocab_size, model.dim) == (40, 100352, 2048)
    assert model.layers_of("full_attention") == (5, 15, 25, 35)
    assert len(model.layers_of("linear_attention")) == 36
    assert (model.n_heads, model.n_kv_heads, model.head_dim) == (32, 8, 64)
    assert (model.mamba_n_heads, model.mamba_head_dim, model.mamba_d_state,
            model.conv_kernel, model.conv_dim, model.mamba_inner) == (
        64, 64, 128, 4, 4352, 4096)
    assert (model.embedding_multiplier, model.residual_multiplier,
            model.attention_multiplier, model.logits_scaling) == (12, 0.22, 1 / 64, 8)
    assert model.attention_multiplier * model.head_dim ** 0.5 == 0.125
    assert model.ffn_hidden == 8192 and model.norm_eps == 1e-5


def test_weights_and_cache_are_the_bytes_the_issue_reckoned(config, model):
    """The issue's count, reckoned again from the file's keys, and the
    program's own tree beside it."""
    import jax

    from mcp_context_forge_tpu.tpu_local.kv import (kv_page_bytes, kv_state_bytes,
                                                    state_rows_for)
    from mcp_context_forge_tpu.tpu_local.models import granite_hybrid

    c = config
    D, F = c["hidden_size"], c["shared_intermediate_size"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    xbc = inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    in_proj = D * (inner + xbc + c["mamba_n_heads"])
    assert (inner, xbc, in_proj) == (4096, 4352, 2048 * 8512)
    mlp = D * 2 * F + F * D
    mamba = (in_proj + inner * D + (c["mamba_d_conv"] + 1) * xbc
             + 3 * c["mamba_n_heads"] + inner + mlp)
    Q, KV = c["num_attention_heads"] * 64, c["num_key_value_heads"] * 64
    attention = D * (Q + 2 * KV) + Q * D + mlp
    assert (round(mamba / 1e6, 1), round(attention / 1e6, 1)) == (76.2, 60.8)
    embedding = c["vocab_size"] * D                         # ONE matrix, tied
    matrices = 36 * mamba + 4 * attention + embedding
    assert round(embedding / 1e6, 1) == 205.5 and round(matrices / 1e9, 2) == 3.19
    small = granite_hybrid.param_count(model) - matrices    # the 81 norms
    assert small == 40 * 2 * D + D
    tree = jax.eval_shape(lambda: granite_hybrid.init_params(
        model, jax.random.PRNGKey(0)))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) \
        == granite_hybrid.param_count(model)
    assert "lm_head" not in tree
    # the cache: attention layers a page id, Mamba layers a state row
    page = c["engine"]["page_size"]
    mix = manifest.read_json(manifest.cell(manifest.load(), CELL).traffic_file)
    rows = state_rows_for(model, mix["engine"]["max_batch"])
    assert rows == 65
    # way (b): a head of 64 stored in a whole 128-lane tile, 16 KB a token
    assert model.kv_head_dim == 128
    assert kv_page_bytes(model, page) == 4 * 2 * page * 8 * 128 * 2 == 2_097_152
    a_row = kv_state_bytes(model, 1)
    assert a_row == 36 * (128 * 4096 * 4 + 3 * 4352 * 2) == 76_437_504
    pages = c["engine"]["num_pages"] * kv_page_bytes(model, page)
    state = kv_state_bytes(model, rows)
    assert round(pages / 1e9, 2) == 2.15 and round(state / 1e9, 2) == 4.97
    # every slot at max_seq_len fits the pool beside the trash page
    per_row = mix["engine"]["max_seq_len"] // page
    assert mix["engine"]["max_batch"] * per_row + 1 == c["engine"]["num_pages"]
    # ~10.3 GB of arguments (the issue's 9.2 + way (b)'s second GB of K/V),
    # 61 % of the chip before temporaries (floor: 25 %)
    held = matrices + pages + state
    assert round(held / 1e9, 1) == 10.3 and 0.59 < held / 16.9e9 < 0.63


def test_the_mix_is_long_answers_and_the_check_fits(cell, config):
    mix = manifest.read_json(cell.traffic_file)
    assert mix["kind"] == "open_loop" and mix["arrivals"] == "poisson"
    assert mix["schedule_seed"] == 23 and mix["trace_seconds"] == 5.0
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "low": 64, "high": 512}
    assert mix["max_tokens"] == {"dist": "uniform", "low": 512, "high": 1024}
    assert (mix["temperature"], mix["shared_prefix_tokens"],
            mix["drain_seconds"]) == (0.0, 0, 40)
    assert mix["engine"] == {"max_seq_len": 2048, "prefill_buckets": [512],
                             "prefill_max_batch": 4, "max_batch": 64}
    assert len(mix["what"]) > 80
    # the longest request fits a slot
    assert mix["prompt_tokens"]["high"] + mix["max_tokens"]["high"] \
        <= mix["engine"]["max_seq_len"]
    params = manifest.read_json(cell.cell_file)
    assert set(params) == {"rate_rps"} and 1.0 <= params["rate_rps"] <= 6.0
    check = correct.check_of(config, mix)
    assert check.prompt_lengths == (700, 384, 96) and check.decode_positions == 8
    bucket = mix["engine"]["prefill_buckets"][0]
    # two chunk rounds with state and tail carried, a dense prefill, and one
    # the half-length program would take
    assert bucket < check.prompt_lengths[0] <= 2 * bucket
    assert bucket // 2 < check.prompt_lengths[1] <= bucket
    assert check.prompt_lengths[2] <= bucket // 2
    assert check.tokens <= mix["engine"]["max_seq_len"]


def test_family_file_keeps_the_contract_and_refuses_what_it_cannot(config):
    family = families.load("granite_hybrid")
    assert all(hasattr(family, name) for name in families.CONTRACT)
    assert family.reference == "granite_hybrid_plain"
    assert family.engine_logits is families.load("olmo_hybrid").EngineLogits
    reference = families.reference_of(family)
    assert callable(reference.forward)
    assert set(reference.VARIANTS) >= {None, "bf16_state", "no_skip",
                                       "no_conv_bias", "norm_before_gate"}
    source = open(reference.__file__, encoding="utf-8").read()
    assert "mcp_context_forge_tpu" not in source.replace(
        "nothing imported from ``mcp_context_forge_tpu``", "")
    assert "lax.scan" in source and "highest" in source
    # a configuration the program computes otherwise is refused by its key
    for key, value in (("num_local_experts", 8), ("num_experts_per_tok", 2),
                       ("position_embedding_type", "rope"),
                       ("attention_bias", True), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False), ("mamba_n_groups", 8),
                       ("tie_word_embeddings", False), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            family.model_config("x", {**config, key: value})
    with pytest.raises(ValueError, match="mamba_expand"):
        family.model_config("x", {**config, "mamba_n_heads": 48})
    with pytest.raises(ValueError, match="layer_types"):
        family.model_config("x", {**config, "layer_types": ["mamba"] * 40})
    with pytest.raises(ValueError, match="layer_types"):
        family.model_config("x", {**config, "layer_types": LAYER_TYPES[:39]})


def test_family_refuses_a_program_without_the_model_family(monkeypatch):
    """On a program whose ``models/configs.py`` has no ``GraniteHybridConfig``
    (the parent commit) the family file fails at import, where ``run.main``
    looks it up: before any device work."""
    from mcp_context_forge_tpu.tpu_local.models import configs

    monkeypatch.delattr(configs, "GraniteHybridConfig")
    monkeypatch.delitem(sys.modules, "benchmark.families.granite_hybrid",
                        raising=False)
    with pytest.raises(ImportError, match="GraniteHybridConfig"):
        families.load("granite_hybrid")
    monkeypatch.undo()
    assert families.load("granite_hybrid").reference == "granite_hybrid_plain"


# ------------------------------------------------------------------ the cost

GEOMETRY = (64, 64, 128)       # heads, head_dim, d_state


def test_ssd_cost_at_hand_counted_sizes():
    """Five operations an entry of the 128 x 4096 state a token; a token
    brings x (4096) and the group's B and C (128 each) and takes y (4096) in
    bfloat16, and 64 steps in float32; the float32 state in and out once."""
    entries = 128 * 64 * 64
    assert ssd_cost.state_bytes(*GEOMETRY) == 4 * entries == 2_097_152
    token = (2 * 4096 + 2 * 128) * 2 + 64 * 4
    assert ssd_cost.token_bytes(*GEOMETRY) == token == 17_152
    ops, nbytes = ssd_cost.ssd_step(*GEOMETRY)
    assert (ops, nbytes) == (5.0 * entries, 2 * 4 * entries + token)
    assert round(ops / 1e6, 1) == 2.6 and round(nbytes / 1e6, 2) == 4.21
    ops, nbytes = ssd_cost.ssd_chunk(500, *GEOMETRY)
    assert ops == 5.0 * 500 * entries
    assert nbytes == 500 * token + 2 * 4 * entries
    # no delta correction: two operations an entry fewer than the delta rule's
    assert gdn_cost.OPS_PER_STATE_ENTRY - ssd_cost.OPS_PER_STATE_ENTRY == 2
    # a decode token is bound by its state's bytes: 5.1 us a row a layer
    peak = kernel_cost.peaks("TPU v5 lite")
    least, bound = kernel_cost.least_seconds(*ssd_cost.ssd_step(*GEOMETRY), peak)
    assert bound == "memory" and 5.0e-6 < least < 5.3e-6


def test_mamba_layers_reads_the_model_or_nothing(model):
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
    assert ssd_cost.mamba_layers(model) == 36
    assert ssd_cost.mamba_layers(MODEL_CONFIGS["granite-hybrid-test"]) == 6
    assert ssd_cost.mamba_layers(MODEL_CONFIGS["olmo-hybrid-test"]) is None
    assert ssd_cost.mamba_layers(MODEL_CONFIGS["llama3-test"]) is None
    assert gdn_cost.linear_layers(MODEL_CONFIGS["llama3-test"]) is None


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
        modules=[(T0 + 0.0, T0 + 0.1, "jit__prefill_and_sample", "prefill"),
                 (T0 + 0.11, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.21, T0 + 0.32, "jit__decode_and_sample", "decode")],
        ops=[(T0 + 0.01, T0 + 0.05, "ssd_chunk"),
             (T0 + 0.12, T0 + 0.121, "ssd_step"),
             (T0 + 0.22, T0 + 0.221, "ssd_step"),
             (T0 + 0.23, T0 + 0.24, "fusion.7"),
             (T0 + 0.06, T0 + 0.07, "gated_delta_chunk")])    # another family's
    # a prompt of 500 sent at T0, first token at 0.09, then two decode tokens
    record = _record(0, T0, 500, [T0 + 0.09, T0 + 0.21, T0 + 0.31])
    ctx = _context(trace, [record], model)
    peak = ctx.peak
    ops, nbytes = ssd_cost.ssd_step(*GEOMETRY)
    least = max(2 * 36 * ops / peak["bf16_flops_per_s"],
                2 * 36 * nbytes / peak["hbm_bytes_per_s"])
    value = read("ssd_step_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.002, rel=1e-6)
    assert 0 < value <= 100          # a reading over 100 % is a failure
    note = ctx.notes["ssd_step_roofline"]
    assert (note["bound"], note["calls"], note["decode_tokens"]) == ("memory", 2, 2)
    ops, nbytes = ssd_cost.ssd_chunk(500, *GEOMETRY)
    least = max(36 * ops / peak["bf16_flops_per_s"],
                36 * nbytes / peak["hbm_bytes_per_s"])
    value = read("ssd_chunk_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.04, rel=1e-6)
    assert 0 < value <= 100
    assert ctx.notes["ssd_chunk_roofline"]["prompt_tokens"] == pytest.approx(500)
    # the two kernels' share of the device's busy time (the union of its ops)
    assert read("ssd_mixer.device_share", ctx) == pytest.approx(
        100 * 0.042 / (0.04 + 0.001 + 0.001 + 0.01 + 0.01))
    # the delta-rule families' readers find nothing of theirs to read here
    assert read("gdn_step_roofline", _context(trace, [record], model)) is None
    assert read("kda_step_roofline", _context(trace, [record], model)) is None
    only = reduced(modules=[(T0, T0 + 0.1, "jit__decode_and_sample", "decode")],
                   ops=[(T0 + 0.01, T0 + 0.02, "ssd_step")])
    assert read("linear_mixer.device_share", _context(only, [record], model)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_report_nothing_where_there_is_nothing_to_read(name, model):
    """No trace, no kernel of this name (the parent's program in any cell),
    another family's model, no timeline."""
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
    record = _record(0, T0, 300, [T0 + 0.09, T0 + 0.21])
    assert read(name, _context(None, [record], model)) is None
    olmo = reduced(
        modules=[(T0 + 0.1, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.0, T0 + 0.1, "jit__prefill_and_sample", "prefill")],
        ops=[(T0 + 0.1, T0 + 0.101, "gated_delta_step"),
             (T0 + 0.05, T0 + 0.06, "gated_delta_chunk")])
    assert read(name, _context(olmo, [record], MODEL_CONFIGS["olmo-hybrid-test"])) is None
    named = reduced(
        modules=[(T0 + 0.1, T0 + 0.2, "jit__decode_and_sample", "decode"),
                 (T0 + 0.0, T0 + 0.1, "jit__prefill_and_sample", "prefill")],
        ops=[(T0 + 0.1, T0 + 0.101, "ssd_step"), (T0 + 0.05, T0 + 0.06, "ssd_chunk")])
    if name != "ssd_mixer.device_share":    # a model without Mamba-2 layers
        assert read(name, _context(named, [record],
                                   MODEL_CONFIGS["olmo-hybrid-test"])) is None
    if name != "ssd.rows_live_mean":        # an empty trace
        assert read(name, _context(reduced(), [record], model)) is None


# ------------------------------------------------------- the cell, rehearsed

TINY = {   # granite-hybrid-test's geometry, as a config.json
    "model_type": "granitemoehybrid", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 128, "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0625, "logits_scaling": 8,
    "position_embedding_type": "nope", "num_local_experts": 0,
    "num_experts_per_tok": 0, "tie_word_embeddings": True, "hidden_act": "silu",
    "rms_norm_eps": 1e-05, "max_position_embeddings": 512,
    "family": "granite_hybrid",
    "check": {"prompt_lengths": [80, 40, 12], "decode_positions": 6},
    "engine": {"quant": "int8", "kv_quant": "", "dtype": "float32", "page_size": 32,
               "num_pages": 16 * 8 + 1, "prefix_cache": False,
               # the suite's 8 CPU devices as replicas of the data axis
               "mesh_shape": "8x1", "embedding_model": "encoder-tiny"},
    "logits_tolerance": {"atol": 2e-3, "rtol": 2e-3}, "check_seed": 5,
}
MIX = {"kind": "open_loop", "arrivals": "poisson", "schedule_seed": 1,
       "prompt_tokens": {"dist": "log_uniform", "low": 40, "high": 200},
       "max_tokens": {"dist": "uniform", "low": 12, "high": 24},
       "temperature": 0.0, "shared_prefix_tokens": 0,
       "drain_seconds": 30, "trace_seconds": 1.0,
       "engine": {"max_seq_len": 256, "prefill_buckets": [32],
                  "prefill_max_batch": 2, "max_batch": 16}}


def test_the_tiny_configuration_is_the_programs_preset():
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS
    import dataclasses
    got = families.load("granite_hybrid").model_config("granite-hybrid-test", TINY)
    assert got == dataclasses.replace(MODEL_CONFIGS["granite-hybrid-test"])


def test_rehearsal_of_the_cell_traced(cell, capsys, tmp_path, monkeypatch):
    """``run.measure`` at a tiny size on the CPU (the check's 80-token prompt
    carries its state and tail over three chunk rounds of 32): correct, exact
    accounting, and the counter reader reads the step records."""
    from benchmark import run
    from mcp_context_forge_tpu.config import reset_settings_cache

    # a trace directory of its own: the other files' traced rehearsals share
    # the checkout's, and clear it, while this one runs beside them
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))

    tiny_cell = manifest.Cell(**{**cell.__dict__, "config": "bench-tiny-granite"})
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
    assert logits["position_routing_margin"] == []
    assert logits["attn"] == {"prefill": "reference", "chunk": "gather",
                              "decode": "gather", "delta": "jnp"}
    assert len(notes["greedy_repeats"]["tokens"]) == 8
    assert notes["accounting"]["held"] and notes["accounting"]["ok"]
    assert notes["requests"]["serving_compiles"] == 0
    assert notes["build"]["max_batch"] == 16
    metrics = result["metrics"]
    # 6 requests of 12-24 tokens over two seconds: a few live rows of 16
    assert 0 < metrics["ssd.rows_live_mean"]["value"] <= 16
    assert metrics["ssd.rows_live_mean"]["unit"] == "rows"
    assert metrics["decode.retire_interval_ms_p95"]["value"] > 0
    # no device plane on the CPU: the kernel readers are left out
    assert not {"ssd_step_roofline", "ssd_chunk_roofline",
                "ssd_mixer.device_share"} & set(metrics)
    json.dumps(result)
