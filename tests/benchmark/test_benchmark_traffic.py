"""The generators: determinism under --seed, the same work under every seed,
length ranges, exact token lengths."""

import os
from collections import Counter

import pytest

from benchmark import traffic
from benchmark.harness import manifest
from benchmark.harness.draw import grid, nonce_index

BIG = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits
TRAFFIC = os.path.join(manifest.BENCH_DIR, "traffic")
OVERHEAD = 21


def mix(name):
    return manifest.read_json(os.path.join(TRAFFIC, name + ".json"))


def test_open_loop_is_a_function_of_the_seed():
    a = traffic.plan(mix("chat"), {"rate_rps": 5.0}, 40.0, BIG, OVERHEAD)["requests"]
    b = traffic.plan(mix("chat"), {"rate_rps": 5.0}, 40.0, BIG, OVERHEAD)["requests"]
    c = traffic.plan(mix("chat"), {"rate_rps": 5.0}, 40.0, BIG + 1, OVERHEAD)["requests"]
    assert a == b and a != c
    assert len(a) == 200
    # every seed: the same schedule of the same sizes (the mix's own order), other text
    shape = lambda rs: [(r.due_s, r.prompt_tokens, r.max_tokens) for r in rs]
    assert shape(a) == shape(c)
    assert Counter(r.prompt_tokens for r in a) == Counter(grid(mix("chat")["prompt_tokens"], 200))
    assert [r.prompt_tokens for r in a] != sorted(r.prompt_tokens for r in a)
    assert a[-1].due_s == pytest.approx(40.0 - 0.1)
    assert all(0 < x.due_s < y.due_s < 40.0 for x, y in zip(a, a[1:]))
    assert {r.content for r in a}.isdisjoint({r.content for r in c})
    other = dict(mix("chat"), schedule_seed=24)
    assert shape(traffic.plan(other, {"rate_rps": 5.0}, 40.0, BIG, OVERHEAD)["requests"]) \
        != shape(a)


@pytest.mark.parametrize("name,cell", [("chat", {"rate_rps": 4.0}),
                                       ("docs-closed", {"clients": 8})])
def test_lengths_stay_in_the_mix_range_and_render_exactly(name, cell):
    from mcp_context_forge_tpu.tpu_local.tokenizer import ByteTokenizer, render_chat

    spec = mix(name)
    plan = traffic.plan(spec, cell, 20.0, 7, OVERHEAD)
    requests = (plan["requests"] if plan["mode"] == "open"
                else [plan["request"](i) for i in range(80)])
    tokenizer = ByteTokenizer(vocab_size=32768)
    assert OVERHEAD == len(tokenizer.encode(render_chat([{"role": "user", "content": ""}])))
    for r in requests:
        assert spec["prompt_tokens"]["low"] <= r.prompt_tokens <= spec["prompt_tokens"]["high"]
        assert spec["max_tokens"]["low"] <= r.max_tokens <= spec["max_tokens"]["high"]
        ids = tokenizer.encode(render_chat([{"role": "user", "content": r.content}]))
        assert len(ids) == r.prompt_tokens
        assert nonce_index(bytes(t for t in ids[:24] if t < 256)) == r.index
        # prompt and answer fit the mix's context without truncation
        assert r.prompt_tokens + r.max_tokens <= spec["engine"]["max_seq_len"]
    firsts = {r.content[:128] for r in requests}
    assert len(firsts) == len(requests)        # no shared first page


def test_closed_loop_cycles_one_fixed_set():
    plan = traffic.plan(mix("docs-closed"), {"clients": 8}, 20.0, BIG, OVERHEAD)
    assert plan["mode"] == "closed" and plan["clients"] == 8
    cycle = mix("docs-closed")["cycle"]
    first = [plan["request"](i) for i in range(cycle)]
    again = [plan["request"](i + cycle) for i in range(cycle)]
    assert [r.prompt_tokens for r in first] == [r.prompt_tokens for r in again]
    assert sorted(r.prompt_tokens for r in first) \
        == grid(mix("docs-closed")["prompt_tokens"], cycle)
    assert plan["request"](5) == plan["request"](5)


def test_unknown_kind_or_distribution_is_an_error():
    with pytest.raises(ModuleNotFoundError):
        traffic.plan({"kind": "sessions"}, {}, 1.0, 1, OVERHEAD)
    with pytest.raises(ValueError):
        grid({"dist": "zipf", "low": 1, "high": 2}, 4)
