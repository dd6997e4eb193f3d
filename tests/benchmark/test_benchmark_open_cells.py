"""The two cells PR 47 turned from closed loops of 32 callers into open loops
at a fixed rate: the same configuration, lengths and engine block as the cell
each took the place of, a plan that is a function of the seed alone, and a
manifest whose lists name only cells that exist."""

import os

import pytest

from benchmark import traffic
from benchmark.harness import manifest

BIG = 2 ** 31 + 4747           # the driver's seeds pass 32 signed bits
OVERHEAD = 21


def open_loop(prompts, outputs, trace_seconds, max_seq_len):
    """A mix file of the two cells, all but its ``what``."""
    return {"kind": "open_loop", "arrivals": "poisson",
            "prompt_tokens": {"dist": "log_uniform", "low": prompts[0], "high": prompts[1]},
            "max_tokens": {"dist": "uniform", "low": outputs[0], "high": outputs[1]},
            "temperature": 0.0, "shared_prefix_tokens": 0, "drain_seconds": 40,
            "schedule_seed": 23, "trace_seconds": trace_seconds,
            "engine": {"max_seq_len": max_seq_len, "prefill_buckets": [1024],
                       "prefill_max_batch": 2, "max_batch": 32}}


CELLS = {
    "trinity-mini-d8.mixedctx-open": {
        "config": "trinity-mini-d8", "traffic": "mixedctx-open",
        "took_the_place_of": "trinity-mini-d8.mixedctx-closed",
        # traced for 3 s, not the closed loop's 5: a traced run has to end
        # within 360 s, and reducing 5 s of this model's trace (896 expert
        # iterations a decode step) alone took 190 (PERF.md section 6, PR 47)
        "mix": open_loop((1024, 16000), (256, 512), 3.0, 16896)},
    "joyai-llm-flash-d5-ep4.reason-open": {
        "config": "joyai-llm-flash-d5-ep4", "traffic": "reason-open",
        "took_the_place_of": "joyai-llm-flash-d5-ep4.reason-closed",
        "mix": open_loop((2048, 7000), (512, 1024), 5.0, 8192)},
}


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


@pytest.mark.parametrize("name", sorted(CELLS))
def test_an_open_loop_over_the_same_lengths_and_engine(doc, name):
    want = CELLS[name]
    cell = manifest.cell(doc, name)
    assert (cell.config, cell.traffic, cell.chips) == (
        want["config"], want["traffic"], 1)
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p50_ms", "tpot_p95_ms",
                                                    "setup_s"]
    mix = manifest.read_json(cell.traffic_file)
    assert mix.pop("what")
    assert mix == want["mix"]
    params = manifest.read_json(cell.cell_file)
    assert set(params) == {"rate_rps"} and params["rate_rps"] > 0
    # the longest prompt with the longest answer fits a row
    assert (mix["prompt_tokens"]["high"] + mix["max_tokens"]["high"]
            <= mix["engine"]["max_seq_len"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_plan_for_a_seed_is_the_same_twice(doc, name):
    cell = manifest.cell(doc, name)
    mix, params = manifest.read_json(cell.traffic_file), manifest.read_json(cell.cell_file)
    seconds = float(doc["run_seconds"])
    once = traffic.plan(mix, params, seconds, BIG, OVERHEAD)
    twice = traffic.plan(mix, params, seconds, BIG, OVERHEAD)
    other = traffic.plan(mix, params, seconds, BIG + 1, OVERHEAD)
    assert once["mode"] == "open" and once == twice
    requests = once["requests"]
    assert len(requests) == round(params["rate_rps"] * seconds) >= 80
    # arrivals do not wait for answers: every request has its due time, in
    # order, inside the window, whatever the system does
    assert all(0 < a.due_s < b.due_s < seconds for a, b in zip(requests, requests[1:]))
    # another seed: the same schedule of the same sizes, other text
    shape = lambda rs: [(r.due_s, r.prompt_tokens, r.max_tokens) for r in rs]
    assert shape(requests) == shape(other["requests"])
    assert {r.content for r in requests}.isdisjoint(r.content for r in other["requests"])
    for r in requests:
        assert mix["prompt_tokens"]["low"] <= r.prompt_tokens <= mix["prompt_tokens"]["high"]
        assert mix["max_tokens"]["low"] <= r.max_tokens <= mix["max_tokens"]["high"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_every_list_names_only_cells_that_exist(doc, name):
    cells = {w["name"] for w in doc["workloads"]}
    gone = CELLS[name]["took_the_place_of"]
    assert name in cells and gone not in cells
    for metric in doc["end_to_end"] + doc["per_layer"]:
        listed = metric.get("workloads", ())
        assert set(listed) <= cells, metric["name"]
        assert len(set(listed)) == len(listed), metric["name"]
    # in place: eight cells before, at least eight after
    assert len(doc["workloads"]) >= 8


def test_every_cell_and_traffic_file_belongs_to_a_cell(doc):
    """No file of a cell that is gone (the two closed loops' cell files and
    mixes) stays behind under ``benchmark/``."""
    bench = manifest.BENCH_DIR
    cells = {w["name"] for w in doc["workloads"]}
    mixes = {w["traffic"] for w in doc["workloads"]}
    assert {f[:-5] for f in os.listdir(os.path.join(bench, "cells"))} == cells
    assert {f[:-5] for f in os.listdir(os.path.join(bench, "traffic"))
            if f.endswith(".json")} == mixes
