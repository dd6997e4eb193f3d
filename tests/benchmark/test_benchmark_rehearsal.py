"""CPU rehearsal of ``benchmark/run.py`` end to end at llama3-test size.

The command refuses to run without a TPU; this calls ``run.measure`` — the
test-only entry — with a tiny configuration, so every phase (build as
``cli serve`` does, socket, warm-up, reference check, open and closed loop,
accounting, the result line's shape) is exercised where no chip is. Nothing
it prints is a speed: the result says ``"platform": "cpu"``.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest

REPO = manifest.ROOT

TINY = {   # llama3-test's geometry, as a config.json
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "vocab_size": 512,
    "rope_theta": 500000.0, "rms_norm_eps": 1e-05, "max_position_embeddings": 512,
    "engine": {"quant": "int8", "kv_quant": "", "dtype": "float32",
               "page_size": 32, "num_pages": 48,
               "embedding_model": "encoder-tiny"},
    "logits_tolerance": {"atol": 2e-3, "rtol": 2e-3}, "check_seed": 5,
}
TINY_ENGINE = {"max_seq_len": 128, "prefill_buckets": [64],
               "prefill_max_batch": 1, "max_batch": 4}
MIXES = {
    "open": ({"kind": "open_loop", "arrivals": "poisson", "schedule_seed": 1,
              "prompt_tokens": {"dist": "log_uniform", "low": 32, "high": 60},
              "max_tokens": {"dist": "log_uniform", "low": 3, "high": 8},
              "drain_seconds": 30, "trace_seconds": 1.0, "engine": TINY_ENGINE},
             {"rate_rps": 6.0}),
    "closed": ({"kind": "closed_loop", "cycle": 8, "schedule_seed": 1,
                "prompt_tokens": {"dist": "log_uniform", "low": 70, "high": 110},
                "max_tokens": {"dist": "uniform", "low": 3, "high": 6},
                "drain_seconds": 30, "trace_seconds": 1.0, "engine": TINY_ENGINE},
               {"clients": 2}),
}


def _measure(cell_name: str, mix_name: str, trace: bool, capsys):
    from benchmark import run
    from mcp_context_forge_tpu.config import reset_settings_cache

    doc = manifest.load()
    cell = manifest.cell(doc, cell_name)
    cell = manifest.Cell(**{**cell.__dict__, "config": "bench-tiny"})
    mix, params = MIXES[mix_name]
    saved = dict(os.environ)
    try:
        result = asyncio.run(run.measure(cell, TINY, mix, params, seed=3_000_000_019,
                                         seconds=2.0, trace=trace))
    finally:
        os.environ.clear()
        os.environ.update(saved)
        reset_settings_cache()
    notes = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            fact = json.loads(line)
            notes[fact.pop("note")] = fact
    return result, notes


def test_rehearsal_open_loop_end_to_end(capsys):
    result, notes = _measure("mistral-7b.chat", "open", False, capsys)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu"     # and says so
    assert result["correct"] is True, notes
    assert result["attempted"] == 12 and result["failed"] == 0
    assert set(result["metrics"]) == {"ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert notes["accounting"]["held"] and notes["accounting"]["ok"]
    assert notes["accounting"]["engine"] == notes["accounting"]["client"]
    assert notes["requests"]["serving_compiles"] == 0
    assert notes["logits_vs_reference"]["ok"]
    assert notes["generator_lateness_ms"]["n"] == 12
    json.dumps(result)      # finite numbers only


def test_rehearsal_closed_loop_traced(capsys):
    """Closed loop through the chunked history path, with the traced run's
    shape: per-layer metrics, ``busy_s``/``window_s``, ``breakdown``."""
    result, notes = _measure("mistral-7b.docs-closed", "closed", True, capsys)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device",
                           "breakdown"}
    assert result["correct"] is True, notes
    assert result["attempted"] >= 2 and result["failed"] == 0
    # the CPU has no device plane in its trace: trace readers return nothing
    # and are left out; the counters and spans are there
    assert {"gateway.pre_engine_ms_p50", "prefill.batch_width_mean",
            "prefill.step_ms_mean"} <= set(result["metrics"])
    assert "device.idle_share.sat" not in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in result["device"] and "busy_s" in result["device"]
    # prompts above the 64-token bucket were chunked: more dispatches than requests
    assert notes["requests"]["window_stats"]["prefill_batches"] > result["attempted"]


def test_command_refuses_without_a_tpu():
    """The command itself: non-zero exit and no result line on the CPU."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
         "mistral-7b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith('{"correct"') for line in proc.stdout.splitlines())
