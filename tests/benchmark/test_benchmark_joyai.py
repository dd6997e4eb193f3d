"""What PR 41 added to the benchmark for ``joyai-llm-flash-d5-ep4`` (its cell
an open loop at a fixed rate since PR 47, ``reason-open`` in place of
``reason-closed``): the
manifest's new entries (held by name, never by position), the configuration
file against the catalog's published keys, the bytes the issue reckoned, the
mix and the cell letter for letter, the family file's contract, the verify
cost at hand-counted sizes, the three new per-layer readers on a small
synthetic trace and ring (and reporting nothing where the program lacks what
they read), and a CPU rehearsal of the cell at a tiny size."""

import asyncio
import json
import os

import pytest

from benchmark import families
from benchmark.harness import (correct, kernel_cost, layers, manifest,
                               mla_verify_cost, stats, trace_reduce)
from mcp_context_forge_tpu.observability.timeline import StepCounts, StepTimeline

T0, NS0 = 100.0, 5e9
CELL, CONFIG = "joyai-llm-flash-d5-ep4.reason-open", "joyai-llm-flash-d5-ep4"
NEW_READERS = ("spec.tokens_per_step", "spec.verify_share",
               "mla_verify_attention_roofline")
APPENDED_TO = ("decode.device_ms_per_step", "decode.host_gap_ms_mean",
               "device.idle_share.serve", "device.idle_share.host.serve",
               "decode.retire_interval_ms_p95", "decode.prefill_stall_share",
               "moe.local_pairs_per_token")
SOURCE = "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json"
PUBLISHED = {   # the catalog row's ``config``, key by key
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}
CUT = {"num_hidden_layers": 5, "n_routed_experts": 64, "vocab_size": 32320}


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


# ------------------------------------------------------------- the manifest

def test_the_cell_and_what_it_reports(doc, cell):
    assert (cell.config, cell.traffic, cell.chips) == (CONFIG, "reason-open", 1)
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= set(NEW_READERS) | set(APPENDED_TO)
    # readers of a selector (this model has none) and of other families'
    # kernels, and the two latent readers whose cost takes index_topk for the
    # context a query sees (0 here): not this cell's
    assert not names & {
        "sparse_index_roofline", "sparse_select.device_share",
        "sparse.selected_share_mean", "mla_decode_attention_roofline",
        "mla_prefill_attention_roofline", "paged_attention_roofline",
        "prefill_attention_roofline", "diffusion.tokens_per_pass"}
    for name in names:
        layers.load_reader(name)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_p95_ms"
    assert by_name["mla_verify_attention_roofline"]["unit"] == "%"
    assert by_name["mla_verify_attention_roofline"]["source"] == "device_trace"
    assert by_name["spec.verify_share"]["source"] == "program_counter"
    # by name, once: later cells follow it on these lists; the closed loop it
    # took the place of is on none
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"].count(CELL) == 1
        assert CELL.replace("-open", "-closed") not in metric.get("workloads", ())
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and entry["reduced"] == list(CUT)
    assert len(doc["workloads"]) >= 7 and all(w["chips"] == 1 for w in doc["workloads"])
    why = next(w["why"] for w in doc["workloads"] if w["name"] == CELL)
    rate = manifest.read_json(cell.cell_file)["rate_rps"]
    for words in ("open loop", f"{rate} req/s", "0.8 x", "2048-7000", "512-1024",
                  "verif", "GB"):
        assert words in why, words


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_file_holds_the_published_key(config, key):
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key]
        assert config["published"][key] == PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key]


def test_configuration_states_its_cut_and_what_it_assumed(config, model):
    assert config["source"] == SOURCE and config["family"] == "joyai_flash"
    assert config["reduced"] == list(CUT) and set(config["published"]) == set(CUT)
    assert config["experts_held"] == [0, 64]
    for key in ("weights", "mtp_layout", "mtp_concatenation", "mtp_rotary_index",
                "rotary_layout", "num_hidden_layers", "n_routed_experts",
                "vocab_size", "rope_scaling", "tokenizer", "moe_impl"):
        assert key in config["assumed"], key
    assert "once in 32320" in config["assumed"]["weights"]
    for words in ("EP4", "1/4 of its deployed load", "1 of 41 deployed",
                  "No code stands in"):
        assert words in config["deployment"]
    assert config["engine"] == {
        "quant": "", "kv_quant": "", "dtype": "bfloat16", "page_size": 128,
        "num_pages": 2304, "spec_decode": True, "spec_k": 2}
    assert "lossless" in config["guarantees"]["speculation"]
    assert config["guarantees"]["serving_compiles"] == 0
    assert "reason" in config["logits_tolerance"] and config["check_seed"] > 2 ** 31
    # no width differs, the cut is of depth, experts held and vocabulary rows
    assert (model.n_layers, model.dim, model.ffn_hidden, model.moe_ffn_hidden,
            model.vocab_size) == (5, 2048, 7168, 768, 32320)
    assert (model.n_heads, model.q_lora_rank, model.kv_lora_rank,
            model.qk_nope_head_dim, model.qk_rope_head_dim, model.v_head_dim) == (
                32, 1536, 512, 128, 64, 128)
    assert (model.n_routed_experts, model.experts_held, model.moe_top_k,
            model.n_group, model.topk_group, model.n_shared_experts,
            model.n_dense_layers) == (256, (0, 64), 8, 1, 1, 1, 1)
    assert (model.n_mtp_blocks, model.has_selector, model.n_cache_layers) == (
        1, False, 6)
    assert model.rope_theta == 32e6 and model.norm_eps == 1e-6
    assert model.routed_scaling_factor == 2.5
    assert model.max_seq_len <= model.rope_original_max          # no YaRN
    family = families.of(config)
    for key, value in (("norm_topk_prob", False), ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"}),
                       ("num_nextn_predict_layers", 0), ("index_topk", 2048)):
        with pytest.raises(ValueError, match=key):
            family.model_config(CONFIG, {**config, key: value})
    with pytest.raises(ValueError, match="experts_held"):
        family.model_config(CONFIG, {**config, "experts_held": [0, 32]})


def test_weights_and_pool_are_the_bytes_the_issue_reckoned(config, model):
    import jax
    import jax.numpy as jnp

    from mcp_context_forge_tpu.tpu_local.kv import kv_page_bytes
    from mcp_context_forge_tpu.tpu_local.models import deepseek, family_of

    assert family_of(model) is deepseek and deepseek.drafts_on_device(model)
    attention = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert attention == pytest.approx(26.35e6, rel=1e-3)
    dense, experts, shared = 3 * 2048 * 7168, 64 * 3 * 2048 * 768, 3 * 2048 * 768
    assert (dense, experts, shared) == (44_040_192, 301_989_888, 4_718_592)
    tree = jax.eval_shape(lambda: deepseek.init_params(
        model, jax.random.PRNGKey(0), jnp.bfloat16))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert nbytes == pytest.approx(3.76e9, rel=2e-3)
    # the router's gate and bias float32, the rest bfloat16, the norms apart
    reckoned = (6 * attention * 2 + dense * 2
                + 5 * ((experts + shared) * 2 + 2048 * 256 * 4)
                + 2 * 32320 * 2048 * 2 + 4096 * 2048 * 2)
    assert nbytes == pytest.approx(reckoned, rel=1e-3)
    assert deepseek.param_count(model) == sum(a.size for a in jax.tree.leaves(tree))
    page = kv_page_bytes(model, 128)
    assert page == 6 * 147_456 == 884_736                   # 1152 B a token a layer
    assert config["engine"]["num_pages"] * page == pytest.approx(2.04e9, rel=2e-3)
    assert config["engine"]["num_pages"] == 32 * 64 + 256
    # chunk rounds take the grouped kernel, a verify step of 64 tokens the scan
    one = type("M", (), {"shape": {"model": 1}})()
    assert [deepseek.expert_path(model, one, t) for t in (64, 1024, 2048)] == [
        "scan", "grouped", "grouped"]


def test_the_mix_and_the_cell_are_the_issues(cell):
    mix = manifest.read_json(cell.traffic_file)
    what = mix.pop("what")
    assert "reasoning" in what and "A2A" in what and "do not wait" in what
    # the mix letter for letter: test_benchmark_open_cells.py
    assert mix["kind"] == "open_loop"
    params = manifest.read_json(cell.cell_file)
    assert set(params) == {"rate_rps"} and 1.0 <= params["rate_rps"] <= 2.5
    # the longest prompt and output fit a row, every row's pages fit the pool
    assert 7000 + 1024 <= mix["engine"]["max_seq_len"]


def test_family_file_keeps_the_contract(config):
    family = families.load("joyai_flash")
    assert all(hasattr(family, name) for name in families.CONTRACT)
    assert family.reference == "joyai_flash_plain"
    reference = families.reference_of(family)
    assert callable(reference.forward) and callable(reference.trace)
    source = open(reference.__file__, encoding="utf-8").read()
    assert "mcp_context_forge_tpu" not in source.replace(
        "nothing imported from ``mcp_context_forge_tpu``", "")
    # a rejected draft is never the token itself, and stays a printable byte
    assert all(family.rejected_draft(t) != t and 32 <= family.rejected_draft(t) < 127
               for t in range(32, 127))
    mix = manifest.read_json(manifest.cell(manifest.load(), CELL).traffic_file)
    check = correct.check_of(config, mix)
    assert check.prompt_lengths == (4608, 2304, 640) and check.decode_positions == 8
    bucket = mix["engine"]["prefill_buckets"][0]
    assert check.prompt_lengths[0] > 4 * bucket and check.prompt_lengths[2] < bucket
    assert check.tokens + 1 <= mix["engine"]["max_seq_len"]


# ------------------------------------------------------------------ the cost

def test_verify_cost_at_hand_counted_sizes():
    # 2 query positions of which the last sees 12 vectors: 11 + 12 pairs
    ops, nbytes = mla_verify_cost.verify_attention(2, 12, 2, 6, 4)
    assert ops == 2 * 23 * 2 * (6 + 4)
    assert nbytes == 12 * 6 * 2 + 2 * 2 * (6 + 4) * 2
    # one position (no draft) is a decode step over its whole context
    ops, nbytes = mla_verify_cost.verify_attention(1, 12, 2, 6, 4)
    assert ops == 2 * 12 * 2 * 10 and nbytes == 12 * 6 * 2 + 2 * 10 * 2
    # the published sizes: 4400 visible vectors are 5.07 MB a row a layer
    ops, nbytes = mla_verify_cost.verify_attention(2, 4400, 32, 576, 512)
    assert nbytes == pytest.approx(4400 * 1152 + 2 * 32 * 1088 * 2)
    assert ops == 2 * (4399 + 4400) * 32 * 1088
    # a request of 100 prompt tokens, max_tokens 6: its first token is the
    # prefill's; an accepted draft brings two tokens at once (one step); the
    # step that emits the last token carries no draft
    record = stats.Record(0, 0.0, 100, 6)
    record.token_times = [1.0, 1.02, 1.04, 1.0401, 1.06, 1.08]
    assert list(mla_verify_cost.verify_steps(record, (0.0, 2.0))) == [
        (2, 102), (2, 103), (2, 105), (1, 105)]
    assert list(mla_verify_cost.verify_steps(record, (1.03, 1.07))) == [
        (2, 103), (2, 105)]


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


def _spec(wanted, drafted, accepted, emitted):
    return StepCounts(1.0, 12.0, 3.0, draft_wanted=wanted, draft_rows=drafted,
                      drafts_accepted=accepted, spec_tokens=emitted)


def _verify_ring():
    ring = StepTimeline("0")
    ring.step(1, "chunk", 2, 2, 1024, T0 + 0.0, T0 + 0.1, StepCounts(1.0, 8.0, 2.0))
    ring.step(2, "spec", 32, 3, 8, T0 + 0.1, T0 + 0.2, _spec(3.0, 3.0, 1.0, 4.0))
    ring.step(3, "spec", 32, 3, 8, T0 + 0.2, T0 + 0.3, _spec(2.0, 2.0, 0.0, 3.0))
    # a row that wanted a draft rode without one
    ring.step(4, "spec", 32, 2, 8, T0 + 0.3, T0 + 0.4, _spec(2.0, 1.0, 1.0, 3.0))
    # a plain step that left a greedy row without one, and one that left none
    ring.step(5, "decode", 32, 2, 8, T0 + 0.4, T0 + 0.5,
              StepCounts(1.0, 4.0, 1.0, draft_wanted=1.0))
    ring.step(6, "decode", 32, 1, 8, T0 + 0.5, T0 + 0.6, StepCounts(1.0, 2.0, 1.0))
    ring.step(7, "spec", 32, 3, 8, T0 + 1.3, T0 + 1.4, _spec(3.0, 3.0, 3.0, 6.0))
    return ring


def test_the_two_counter_readers_read_the_step_records():
    ring = _verify_ring()
    ctx = _context(None)
    # three verify steps with drafts in the window: 10 tokens over 8 live rows
    assert read("spec.tokens_per_step", ctx) == pytest.approx(10 / 8)
    assert ctx.notes["spec.tokens_per_step"] == {
        "steps": 3, "rows": 8, "tokens": 10.0, "draft_rows": 6.0,
        "drafts_accepted": 2.0}
    # four dispatches had a row to draft for; two verify steps served all
    assert read("spec.verify_share", ctx) == pytest.approx(100 * 2 / 4)
    assert ctx.notes["spec.verify_share"] == {
        "decode_steps": 5, "with_a_row_to_draft_for": 4,
        "verify_steps_of_them": 3, "every_such_row_drafted": 2}
    del ring


def test_verify_attention_roofline_on_a_synthetic_trace(model):
    ring = _verify_ring()
    trace = reduced(
        modules=[(T0 + 0.0, T0 + 0.1, "jit__prefill_hist_and_sample", "prefill_hist"),
                 (T0 + 0.1, T0 + 0.2, "jit__decode_and_sample_draft", "decode"),
                 (T0 + 0.2, T0 + 0.3, "jit__decode_and_sample_draft", "decode")],
        ops=[(T0 + 0.05, T0 + 0.06, "mla_paged_attention"),        # a chunk's: not counted
             (T0 + 0.1, T0 + 0.1004, "mla_paged_attention"),
             (T0 + 0.2, T0 + 0.2004, "mla_paged_attention")])
    assert trace_reduce.program_kind("jit__decode_and_sample_draft", set()) == "decode"
    # a prompt of 3000: its second and third tokens came from two verify steps
    record = _record(0, T0, 3000, [T0 + 0.09, T0 + 0.21, T0 + 0.31])
    ctx = _context(trace, [record], model)
    ops = nbytes = 0.0
    for context in (3002, 3003):
        o, b = mla_verify_cost.verify_attention(2, context, 32, 576, 512)
        ops, nbytes = ops + 6 * o, nbytes + 6 * b           # 5 layers and the block
    peak = ctx.peak
    least = max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    value = read("mla_verify_attention_roofline", ctx)
    assert value == pytest.approx(100 * least / 0.0008, rel=1e-6)
    assert 0 < value <= 100          # a reading over 100 % is a failure
    note = ctx.notes["mla_verify_attention_roofline"]
    assert note["bound"] == "memory" and note["row_steps"] == 2 and note["calls"] == 2
    del ring


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_report_nothing_from_a_program_without_device_drafts(name, model):
    """The parent's program (no block, no verify step that drafts, step records
    without the counts) under this PR's benchmark files: nothing, no error."""
    from mcp_context_forge_tpu.tpu_local.models.configs import MODEL_CONFIGS

    ring = StepTimeline("0")
    ring.step(1, "decode", 8, 8, 4, T0 + 0.3, T0 + 0.4)       # a GQA engine's step
    ring.step(2, "spec", 8, 8, 4, T0 + 0.4, T0 + 0.5)         # its prompt-lookup verify
    ring.step(3, "decode", 8, 8, 4, T0 + 0.5, T0 + 0.6,
              StepCounts(0.3, 32.0, 20.0))                    # the selector model's
    trace = reduced(
        modules=[(T0, T0 + 0.5, "jit__decode_and_sample", "decode")],
        ops=[(T0, T0 + 0.2, "paged_attention")])
    record = _record(0, T0, 100, [T0 + 0.1, T0 + 0.2])
    others = (MODEL_CONFIGS["mistral-7b"], MODEL_CONFIGS["deepseek-test"], object())
    for other in others:
        assert read(name, _context(trace, [record], model=other)) is None
    assert read(name, _context(None, [record], model)) is None
    if name == "mla_verify_attention_roofline":
        # the model's own trace without the kernel in a decode program
        assert read(name, _context(trace, [record], model)) is None
    del ring


# ------------------------------------------------------- the cell, rehearsed

TINY = {   # deepseek-mtp-test's geometry, as a config.json
    "model_type": "joyai_llm_flash", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 10000, "rope_scaling": None,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 512,
    "num_nextn_predict_layers": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "hidden_act": "silu",
    "published": {"n_routed_experts": 16}, "experts_held": [0, 4],
    "family": "joyai_flash",
    "check": {"prompt_lengths": [100, 42, 20], "decode_positions": 6},
    "engine": {"quant": "", "kv_quant": "", "dtype": "float32", "page_size": 32,
               "num_pages": 64, "spec_decode": True, "spec_k": 2,
               "moe_impl": "grouped", "moe_block": 8,
               # the suite's 8 CPU devices as replicas of the data axis
               "mesh_shape": "8x1", "embedding_model": "encoder-tiny"},
    "logits_tolerance": {"atol": 2e-3, "rtol": 2e-3}, "check_seed": 5,
}
MIX = {"kind": "open_loop", "arrivals": "poisson", "schedule_seed": 1,
       "prompt_tokens": {"dist": "log_uniform", "low": 40, "high": 100},
       "max_tokens": {"dist": "uniform", "low": 6, "high": 12},
       "temperature": 0.0, "shared_prefix_tokens": 0,
       "drain_seconds": 30, "trace_seconds": 1.0,
       "engine": {"max_seq_len": 128, "prefill_buckets": [32],
                  "prefill_max_batch": 2, "max_batch": 4}}


def test_rehearsal_of_the_cell_traced(cell, capsys, tmp_path, monkeypatch):
    """``run.measure`` at a tiny size on the CPU: the check's 100-token prompt
    takes the chunk path with the block's pass beside every chunk, its verify
    steps carry a wrong draft; every decode dispatch of the window is a verify
    step that drafts, the accounting is exact, and the two counter readers
    read the records."""
    from benchmark import run
    from mcp_context_forge_tpu.config import reset_settings_cache

    # a trace directory of its own: the other files' traced rehearsals share
    # the checkout's, and clear it, while this one runs beside them
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))

    tiny_cell = manifest.Cell(**{**cell.__dict__, "config": "bench-tiny-joyai"})
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
    assert logits["attn"] == {"chunk": "gather", "verify": "gather", "moe": "grouped"}
    assert len(notes["greedy_repeats"]["tokens"]) == 8
    assert notes["accounting"]["held"] and notes["accounting"]["ok"]
    assert notes["requests"]["serving_compiles"] == 0
    assert notes["build"]["attn_traced"]["draft"] == "gather"
    metrics = result["metrics"]
    assert metrics["spec.verify_share"] == {"value": 100.0, "unit": "%"}
    per_step = metrics["spec.tokens_per_step"]
    assert per_step["unit"] == "tokens/step" and 1.0 <= per_step["value"] <= 2.0
    assert metrics["moe.local_pairs_per_token"]["value"] > 0
    assert metrics["decode.retire_interval_ms_p95"]["value"] > 0
    # no device plane on the CPU: the kernel reader is left out
    assert "mla_verify_attention_roofline" not in metrics
    json.dumps(result)
