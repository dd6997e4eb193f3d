"""The plain reference (``benchmark/reference/decoder.py``) against the
engine's prefill + decode logits at CI sizes — the same comparison the chip
run makes at published widths (``harness/correct.logits_check``).

Tolerance, with its reason: both sides compute in float32 here (the engine is
built with ``dtype="float32"``), on the same int8-dequantised weights, so they
differ only in summation order — measured 3e-6 on logits of magnitude 4. At
1e-4 a bf16 activation anywhere (relative step 4e-3) fails, as does a wrong
mask, head mapping, RoPE convention, scale axis or expert choice."""


import pytest

from benchmark.harness import correct

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
