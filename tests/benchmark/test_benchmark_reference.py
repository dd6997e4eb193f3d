"""The plain reference (``benchmark/reference/decoder.py``) against the
engine's prefill + decode logits at CI sizes — the same comparison the chip
run makes at published widths (``harness/correct.logits_check``).

Tolerance, with its reason: both sides compute in float32 here (the engine is
built with ``dtype="float32"``), on the same int8-dequantised weights, so they
differ only in summation order — measured 3e-6 on logits of magnitude 4. At
1e-4 a bf16 activation anywhere (relative step 4e-3) fails, as does a wrong
mask, head mapping, RoPE convention, scale axis or expert choice.

Below them, in this file so that the suite keeps its files (and its workers
their schedule): the family seam (``benchmark/families/__init__.py``). A
configuration names its family file, its reference and its check lengths; the
harness finds them by name and names no model family itself."""


import copy
import os
import re
import types

import numpy as np
import pytest

from benchmark import families
from benchmark.harness import correct, gateway, manifest

TOLERANCE = {"atol": 1e-4, "rtol": 1e-4}


def engine_of(model, quant):
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
    import jax

    return TPUEngine(EngineConfig(
        model=model, quant=quant, dtype="float32", max_batch=2, max_seq_len=256,
        page_size=32, num_pages=24, prefill_buckets=(128,), prefill_max_batch=1,
        cost_analysis=False), devices=jax.devices()[:1])


@pytest.mark.parametrize("model,quant", [("llama3-test", "int8"),
                                         ("llama3-test", ""),
                                         ("mixtral-test", "int8")])
def test_engine_logits_agree_with_the_reference(model, quant):
    engine = engine_of(model, quant)
    facts = correct.logits_check(engine, seed=2 ** 31 + 5, tolerance=TOLERANCE)
    assert facts["ok"], facts
    assert facts["max_abs_err"] < 1e-4 and facts["positions_within"] == 1.0
    for prompt in facts["per_prompt"]:
        assert prompt["ref_abs_max"] > 0.5          # logits are not all zero
        assert prompt["argmax_agree"] == 1.0


def test_a_lower_precision_fails_the_tolerance():
    """An engine computing in bfloat16 is off by ~1e-2: the float32 tolerance
    catches a precision lower than the configuration states."""
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
    import jax

    engine = TPUEngine(EngineConfig(
        model="llama3-test", quant="int8", dtype="bfloat16", max_batch=2,
        max_seq_len=256, page_size=32, num_pages=24, prefill_buckets=(128,),
        prefill_max_batch=1, cost_analysis=False), devices=jax.devices()[:1])
    facts = correct.logits_check(engine, seed=9, tolerance=TOLERANCE)
    assert not facts["ok"] and facts["max_abs_err"] > 1e-3


# ======== the family seam ========

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(manifest.BENCH_DIR, "traffic")
CHAT = manifest.read_json(os.path.join(TRAFFIC, "chat.json"))
# The two configurations accepted before the seam: they state neither a family
# nor check lengths. Pinned by name: a later PR's configuration states what it needs.
ACCEPTED = ("mistral-7b", "mixtral-8x7b-d8")
LLAMA_PY = os.path.realpath(os.path.join(manifest.BENCH_DIR, "families", "llama.py"))
TOY, TOY_CELL = "toy-state-1", "toy-state-1.chat"


@pytest.fixture()
def test_only_families(monkeypatch):
    """Families and references are looked for under ``tests/benchmark/``."""
    monkeypatch.setattr(families, "FAMILY_DIR", os.path.join(HERE, "families"))
    monkeypatch.setattr(families, "REFERENCE_DIR", os.path.join(HERE, "reference"))


def a_family_appended(monkeypatch, tmp_path):
    """What a ``model_config`` PR does, with the test-only family: a family
    file and a reference file laid beside those that are there (links in one
    directory), a configuration file, a configuration and a cell appended to
    the manifest, the cell's name appended to ``tpot_p95_ms``'s list. Nothing
    under ``benchmark/`` is edited."""
    for kind, attr in (("families", "FAMILY_DIR"), ("reference", "REFERENCE_DIR")):
        together = tmp_path / kind
        together.mkdir()
        for directory in (os.path.join(manifest.BENCH_DIR, kind), os.path.join(HERE, kind)):
            for name in os.listdir(directory):
                if name.endswith(".py") and name != "__init__.py":
                    os.symlink(os.path.join(directory, name), together / name)
        monkeypatch.setattr(families, attr, str(together))
    doc = copy.deepcopy(manifest.load())
    file = f"tests/benchmark/configs/{TOY}.json"
    config = manifest.read_json(os.path.join(manifest.ROOT, file))
    doc["configs"].append({"name": TOY, "source": config["source"], "file": file,
                           "reduced": config["reduced"],
                           "why": "one recurrent state a sequence: no K or V, no pages"})
    doc["workloads"].append({"name": TOY_CELL, "config": TOY, "traffic": "chat", "chips": 1,
                             "why": "the chat mix through a family outside the GQA trunk"})
    next(m for m in doc["end_to_end"] if m["name"] == "tpot_p95_ms")["workloads"].append(TOY_CELL)
    manifest.validate(doc)
    return doc


@pytest.fixture(params=["as-committed", "a-family-appended"])
def doc(request, monkeypatch, tmp_path):
    """The manifest as it is, and as a later PR's appended family leaves it:
    every test of the seam that reads the manifest holds on both."""
    if request.param == "as-committed":
        return manifest.load()
    return a_family_appended(monkeypatch, tmp_path)


def config_of(doc, name):
    entry = next(c for c in doc["configs"] if c["name"] == name)
    return manifest.read_json(os.path.join(manifest.ROOT, entry["file"]))


# ---- defaults: what the accepted configurations run ----

@pytest.mark.parametrize("name", ACCEPTED)
def test_accepted_configurations_take_the_defaults(doc, name):
    config = config_of(doc, name)
    assert "family" not in config and "check" not in config
    family = families.of(config)
    assert os.path.realpath(family.__file__) == LLAMA_PY
    assert family.reference == "decoder"
    assert callable(families.reference_of(family).forward)
    for mix in ("chat", "docs-closed"):
        check = correct.check_of(config, manifest.read_json(os.path.join(TRAFFIC, mix + ".json")))
        assert check == correct.Check() == correct.Check((96, 40), 8)
        assert check.tokens == 104


def test_every_configuration_finds_its_family_its_reference_and_its_check(doc):
    """Whatever family a configuration names: what ``run.py: main`` looks up
    before it looks for a chip is there, for every cell of the manifest."""
    for cell in doc["workloads"]:
        config = config_of(doc, cell["config"])
        family = families.of(config)
        assert os.path.basename(family.__file__) == config.get("family", "llama") + ".py"
        assert callable(family.model_config) and callable(family.engine_logits)
        assert callable(families.reference_of(family).forward)
        mix = manifest.read_json(os.path.join(TRAFFIC, cell["traffic"] + ".json"))
        assert correct.check_of(config, mix).tokens <= mix["engine"]["max_seq_len"]


def test_the_contract_is_one_docstring_and_every_family_file_holds_it(doc):
    assert families.CONTRACT == ("model_config", "engine_logits", "reference")
    for name in families.CONTRACT:
        assert f"``{name}" in families.__doc__
    found = sorted(f[:-3] for f in os.listdir(families.FAMILY_DIR)
                   if f.endswith(".py") and f != "__init__.py")
    assert "llama" in found
    for name in found:
        family = families.load(name)
        assert callable(family.model_config) and callable(family.engine_logits)
        assert callable(families.reference_of(family).forward)


@pytest.mark.parametrize("name", ACCEPTED)
def test_register_model_goes_through_the_family(doc, name):
    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS
    from mcp_context_forge_tpu.tpu_local.models.configs import LlamaConfig

    config = config_of(doc, name)
    registered = f"families-test-{name}"
    try:
        model = gateway.register_model(registered, config)
        assert MODEL_CONFIGS[registered] is model and isinstance(model, LlamaConfig)
        assert (model.dim, model.n_layers, model.n_heads, model.n_kv_heads) == (
            config["hidden_size"], config["num_hidden_layers"],
            config["num_attention_heads"], config["num_key_value_heads"])
        assert model.head_dim == config.get(
            "head_dim", config["hidden_size"] // config["num_attention_heads"])
        assert model.n_experts == config.get("num_local_experts", 0)
        wide = gateway.register_model(registered, {**config, "head_dim": 256})
        assert wide.head_dim == 256 and wide.head_dim_override == 256
    finally:
        MODEL_CONFIGS.pop(registered, None)


# ---- refusals, before any device work ----

def test_an_unknown_family_refuses_with_the_path_it_looked_at():
    with pytest.raises(FileNotFoundError) as refusal:
        families.of({"family": "latent-moe"})
    assert os.path.join(manifest.BENCH_DIR, "families", "latent-moe.py") in str(refusal.value)
    with pytest.raises(manifest.ManifestError, match="not a name"):
        families.of({"family": "../harness/gateway"})


@pytest.mark.parametrize("lacks", families.CONTRACT)
def test_a_family_file_missing_a_contract_name_refuses(lacks, tmp_path, monkeypatch):
    body = {"model_config": "def model_config(name, config): return config\n",
            "engine_logits": "def engine_logits(engine, check): return None\n",
            "reference": "reference = 'decoder'\n"}
    path = tmp_path / f"no-{lacks}.py"
    path.write_text("".join(text for name, text in body.items() if name != lacks))
    monkeypatch.setattr(families, "FAMILY_DIR", str(tmp_path))
    with pytest.raises(manifest.ManifestError) as refusal:
        families.load(f"no-{lacks}")
    assert str(path) in str(refusal.value) and lacks in str(refusal.value)


def test_a_reference_that_is_not_there_refuses_with_the_path():
    family = types.SimpleNamespace(reference="not-written-yet")
    with pytest.raises(FileNotFoundError) as refusal:
        families.reference_of(family)
    assert os.path.join(manifest.BENCH_DIR, "reference", "not-written-yet.py") \
        in str(refusal.value)


def test_readers_families_and_references_are_found_by_one_loader():
    from benchmark.harness import layers

    read = layers.load_reader("prefill_attention_roofline")
    assert read is layers.load_reader("prefill_attention_roofline")     # loaded once a path
    assert families.load() is families.load("llama")
    decoder = families.reference_of(families.load())
    assert decoder is manifest.load_by_name(families.REFERENCE_DIR, "decoder", "reference",
                                            ("forward",))
    with pytest.raises(manifest.ManifestError, match=r"lacks \['plan'\]"):
        manifest.load_by_name(families.REFERENCE_DIR, "decoder", "reference", ("plan",))


@pytest.mark.parametrize("group,why", [
    ({"prompt_lengths": [2048, 40]}, "max_seq_len 1024"),      # the chat mix holds 1024
    ({"prompt_lengths": [1020], "decode_positions": 8}, "max_seq_len 1024"),
    ({"prompt_lengths": []}, "whole numbers"),
    ({"prompt_lengths": [96.5]}, "whole numbers"),
    ({"prompt_lengths": [1]}, "whole numbers"),
    ({"decode_positions": 0}, "whole numbers"),
    ({"decode_steps": 8}, "unknown keys"),
])
def test_check_lengths_the_mix_cannot_hold_or_malformed_refuse(group, why):
    with pytest.raises(ValueError, match=why):
        correct.check_of({"check": group}, CHAT)


def test_stated_check_lengths_are_taken():
    docs = manifest.read_json(os.path.join(TRAFFIC, "docs-closed.json"))
    check = correct.check_of({"check": {"prompt_lengths": [2304, 40],
                                        "decode_positions": 4}}, docs)
    assert check == correct.Check((2304, 40), 4) and check.tokens == 2308
    assert correct.check_of({"check": {"decode_positions": 3}}) == correct.Check((96, 40), 3)


@pytest.mark.parametrize("inject,said", [
    ({"family": "latent-moe"}, "families/latent-moe.py"),
    ({"check": {"prompt_lengths": [4096]}}, "max_seq_len"),
])
def test_the_command_refuses_before_it_looks_for_a_chip(inject, said, monkeypatch, capsys):
    from benchmark import run

    read = manifest.read_json

    def patched(path):
        loaded = read(path)
        return {**loaded, **inject} if os.sep + "configs" + os.sep in path else loaded

    monkeypatch.setattr(manifest, "read_json", patched)
    monkeypatch.setattr(run, "require_tpu", lambda chips: pytest.fail("looked for a chip"))
    with pytest.raises(run.Refused) as refusal:
        run.main(["--workload", "mistral-7b.chat", "--seed", "1", "--seconds", "1"])
    assert refusal.value.code == 2
    assert said in capsys.readouterr().err


def test_the_command_gets_as_far_as_the_chip_in_every_cell(doc, monkeypatch):
    """Every cell of the manifest, an appended family's too, passes what
    ``main`` looks up by name and stops only where it looks for a chip."""
    from benchmark import run

    class LookedForAChip(Exception):
        pass

    def no_chip(chips):
        raise LookedForAChip

    read = manifest.read_json

    def patched(path):      # the cell file a later PR adds under benchmark/cells/
        if path.endswith(os.path.join("cells", TOY_CELL + ".json")):
            path = path.replace(TOY_CELL, "mistral-7b.chat")
        return read(path)

    monkeypatch.setattr(manifest, "load", lambda: doc)
    monkeypatch.setattr(manifest, "read_json", patched)
    monkeypatch.setattr(run, "require_tpu", no_chip)
    for cell in doc["workloads"]:
        with pytest.raises(LookedForAChip):
            run.main(["--workload", cell["name"], "--seed", str(2 ** 31 + 3), "--seconds", "1"])


# ---- a family outside the trunk reaches a verdict with no edit under benchmark/ ----

class StubEngine:
    """What ``logits_check`` and the toy family ask of an engine."""

    def __init__(self, model, step_returns_state_unchanged=False):
        rng = np.random.default_rng(3)
        self.model_config = model
        self.params = {
            "embed": rng.standard_normal((model.vocab_size, model.state_size)).astype(np.float32),
            "head": rng.standard_normal((model.state_size, model.vocab_size)).astype(np.float32)}
        self.tokenizer = types.SimpleNamespace(bos_id=1, pad_id=0)
        self.step_returns_state_unchanged = step_returns_state_unchanged


def toy_verdict(engine_kw):
    """From the configuration file to a verdict, as ``run.py: ready`` goes."""
    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS

    config = manifest.read_json(os.path.join(HERE, "configs", TOY + ".json"))
    assert config["family"] == "toy-state" and "check" in config
    try:
        model = gateway.register_model(TOY, config)
        assert MODEL_CONFIGS[TOY] is model
        assert type(model).__name__ == "ToyStateConfig" and model.decay == 0.9
        return correct.logits_check(
            StubEngine(model, **engine_kw), int(config["check_seed"]),
            config["logits_tolerance"], correct.check_of(config, CHAT), config.get("family"))
    finally:
        MODEL_CONFIGS.pop(TOY, None)


def test_a_test_only_family_reaches_a_verdict(test_only_families):
    facts = toy_verdict({})
    assert facts["ok"] is True and facts["positions_within"] == 1.0
    assert [p["tokens"] for p in facts["per_prompt"]] == [300, 17]
    assert len(facts["position_max_abs_err"]) == 2 * (1 + 3)     # the stated lengths
    assert facts["max_abs_err"] < 1e-3 and facts["attn"] is None
    assert all(p["ref_abs_max"] > 1.0 for p in facts["per_prompt"])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(test_only_families):
    facts = toy_verdict({"step_returns_state_unchanged": True})
    assert facts["ok"] is False and facts["max_abs_err"] > 1.0


def test_an_appended_family_reaches_a_verdict_beside_the_trunk(monkeypatch, tmp_path):
    """With the family appended as a later PR would, the toy cell reaches its
    verdict and the trunk's configurations still resolve as before."""
    doc = a_family_appended(monkeypatch, tmp_path)
    assert [c["name"] for c in doc["configs"]][-1] == TOY
    assert toy_verdict({})["ok"] is True
    assert toy_verdict({"step_returns_state_unchanged": True})["ok"] is False
    for name in ACCEPTED:
        assert os.path.realpath(families.of(config_of(doc, name)).__file__) == LLAMA_PY


def test_the_test_only_family_is_not_under_benchmark(test_only_families):
    assert families.load("toy-state").__file__.startswith(HERE + os.sep)
    with pytest.raises(FileNotFoundError):          # and the trunk is not under tests/
        families.load("llama")
    assert not os.path.exists(os.path.join(manifest.BENCH_DIR, "families", "toy-state.py"))
    assert not os.path.exists(os.path.join(manifest.BENCH_DIR, "configs", TOY + ".json"))


# ---- the llama family at stated lengths ----

def small_engine(buckets):
    import jax

    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine

    return TPUEngine(EngineConfig(
        model="llama3-test", quant="int8", dtype="float32", max_batch=2, max_seq_len=512,
        page_size=32, num_pages=24, prefill_buckets=buckets, prefill_max_batch=1,
        cost_analysis=False), devices=jax.devices()[:1])


def test_llama_check_at_stated_lengths_follows_from_them():
    engine = small_engine((256,))
    check = correct.check_of({"check": {"prompt_lengths": [20, 200], "decode_positions": 4}},
                             {"engine": {"max_seq_len": 512}})
    producer = families.load("llama").engine_logits(engine, check)
    assert (producer.seq, producer.per_slot) == (256, 9)        # 256 + 4 tokens in pages of 32
    facts = correct.logits_check(engine, seed=17, tolerance=TOLERANCE, check=check)
    assert facts["ok"], facts
    assert [p["tokens"] for p in facts["per_prompt"]] == [20, 200]
    assert len(facts["position_max_abs_err"]) == 2 * (1 + 4)
    assert all(p["argmax_agree"] == 1.0 for p in facts["per_prompt"])


def test_llama_check_above_the_prefill_bucket_is_refused():
    """The engine would chunk such a prompt through the history path, which the
    family does not drive: refused, not compared on a program the cells never time."""
    engine = small_engine((128,))
    at_default = families.load("llama").engine_logits(engine, correct.Check())
    assert (at_default.seq, at_default.per_slot) == (128, 5)    # one page of 128 + 8 tokens
    check = correct.check_of({"check": {"prompt_lengths": [300, 40]}},
                             {"engine": {"max_seq_len": 512}})
    with pytest.raises(ValueError, match="above the engine's prefill bucket 128"):
        correct.logits_check(engine, seed=17, tolerance=TOLERANCE, check=check)


# ---- the harness names no model family ----

def test_the_harness_names_no_model_family():
    harness = os.path.join(manifest.BENCH_DIR, "harness")
    files = [os.path.join(harness, f) for f in sorted(os.listdir(harness))
             if f.endswith(".py")] + [os.path.join(manifest.BENCH_DIR, "run.py")]
    assert len(files) > 10
    named = re.compile(r"models\.llama|LlamaConfig|init_kv_state|select_paged_attention|"
                       r"select_prefill_attention")
    for path in files:
        with open(path, encoding="utf-8") as handle:
            hits = [line.strip() for line in handle if named.search(line)]
        assert not hits, (path, hits)
    with open(LLAMA_PY) as handle:
        assert named.search(handle.read())          # the guard finds what it looks for


def test_prefill_attention_roofline_lists_cells_of_the_gqa_trunk_only(doc):
    """It reckons GQA bytes: the three accepted cells are on its list, and no
    cell of another family is (that one brings a reader for its own kernels)."""
    metric = next(m for m in doc["per_layer"] if m["name"] == "prefill_attention_roofline")
    assert {"mistral-7b.chat", "mixtral-8x7b-d8.chat",
            "mistral-7b.docs-closed"} <= set(metric["workloads"])
    cells = {w["name"]: w for w in doc["workloads"]}
    for name in metric["workloads"]:
        assert config_of(doc, cells[name]["config"]).get("family", "llama") == "llama"


def test_every_number_compared_is_printed_beside_its_limit():
    logits = {"ok": False, "positions_within": 0.5, "positions_within_needed": 0.6,
              "atol": 0.4, "rtol": 0.05, "max_abs_err": 1.5967, "atol_any": 2.5}
    books = {"ok": True, "held": True, "engine": {"requests": 3}, "client": {"requests": 3}}
    greedy = {"ok": False, "tokens": [5, 6, 7], "differing": 2}
    lines = correct.compared(logits, greedy, books, 2)
    assert len(lines) == 4 and all(line.startswith("correct: ") for line in lines)
    assert "0.5000 >= 0.6" in lines[0] and "1.5967 <= atol_any 2.5" in lines[0]
    assert "differing between two runs 2 <= 0" in lines[1] and "a run 3 >= 1" in lines[1]
    assert [line.rsplit(": ", 1)[1] for line in lines] == [
        "NOT CORRECT", "NOT CORRECT", "ok", "NOT CORRECT"]
    assert "serving_compiles 2 <= 0" in lines[3]


def test_greedy_repeats_counts_the_tokens_that_differ():
    import asyncio

    class Engine:
        tokenizer = types.SimpleNamespace(bos_id=1)
        runs = iter([[4, 5, 6, 7], [4, 9, 6]])

        async def generate(self, prompt, max_tokens):
            for token in next(self.runs):
                yield token

    facts = asyncio.run(correct.greedy_repeats(Engine(), seed=2 ** 31 + 9))
    assert facts == {"ok": False, "tokens": [4, 5, 6, 7], "differing": 2}
