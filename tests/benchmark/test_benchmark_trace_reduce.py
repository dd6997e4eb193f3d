"""The trace reduction against a small trace recorded on a TPU v5e
(``data/v5e_probe.xplane.pb``, 95 kB): five executions each of a two-layer
``jit__decode_and_sample`` (two ``paged_attention`` calls) and a
``jit__prefill_and_sample`` (one ``flash_attention`` call), with sleeps between.
The expected numbers were read off the raw events when the trace was taken."""

import os

import pytest

from benchmark.harness import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "v5e_probe.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_names():
    assert trace_reduce.op_name(
        "%paged_attention.2 = bf16[4,8,4,128]{3,2,1,0} custom-call(s32[4,2] %x)"
    ) == "paged_attention"
    assert trace_reduce.op_name("%fusion = bf16[4,1024] fusion(bf16[1])") == "fusion"
    assert trace_reduce.module_name("jit__decode_and_sample(14792519038681711279)") \
        == "jit__decode_and_sample"
    assert trace_reduce.op_dims(
        "%paged_attention.2 = bf16[4,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call("
    ) == [4, 8, 4, 128]


def test_program_kind_by_name_else_by_kernel_shape():
    kind = trace_reduce.program_kind
    assert kind("jit__prefill_and_sample", set()) == "prefill"
    assert kind("jit__prefill_hist_and_sample", {4096}) == "prefill_hist"
    assert kind("jit__decode_and_sample_fb", {4}) == "decode"
    # the engine jits these through functools.partial: no name survives. The
    # paged kernel's result is [B, KV, rows, hd]: rows is the query group (4)
    # in a decode step, chunk length x group (1024 x 4) in a chunk round
    assert kind("jit__unknown", {4}) == "decode"
    assert kind("jit__unknown", {4096}) == "prefill_hist"
    assert kind("jit__unknown", {16}) == "decode"        # a spec-verify of 4 x 4
    assert kind("jit__threefry_split", set()) == "other"


def test_union():
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert trace_reduce.union_ns([]) == 0


def test_programs_and_kernels(reduced):
    assert list(reduced.devices) == ["/device:TPU:0"]
    seconds, runs = reduced.module_time(("decode",))
    assert runs == 5 and seconds == pytest.approx(5 * 31.7e-6, rel=0.02)
    seconds, runs = reduced.module_time(("prefill",))
    assert runs == 5 and seconds == pytest.approx(5 * 106.8e-6, rel=0.02)
    paged, calls = reduced.op_time("paged_attention")
    assert calls == 10 and paged == pytest.approx(145162e-9, rel=1e-3)
    # every paged call ran inside a decode program, none inside a prefill one
    assert reduced.op_time("paged_attention", ("decode",)) \
        == (paged, calls)
    assert reduced.op_time("paged_attention", ("prefill",)) == (0.0, 0)
    assert set(reduced.programs()) == {"decode:jit__decode_and_sample",
                                       "prefill:jit__prefill_and_sample"}
    flash, calls = reduced.op_time("flash_attention", ("prefill",))
    assert calls == 5 and flash == pytest.approx(425340e-9, rel=1e-3)


def test_busy_union_and_gaps(reduced):
    busy = reduced.busy_s()
    programs = reduced.module_time()[0]
    # operations run inside programs and cover nearly all of them
    assert 0.9 * programs < busy <= programs * 1.001
    assert reduced.window_s == pytest.approx(0.141888331, rel=1e-6)  # first to last device event
    assert busy / reduced.window_s < 0.01          # the probe slept between calls
    gaps = reduced.idle_gaps(5)
    assert len(gaps) == 5 and gaps == sorted(gaps, key=lambda g: -g[1])
    # the sleep of 20 ms followed each prefill, the one of 10 ms each decode
    assert gaps[0][0] == "prefill->decode"
    assert 0.02 < gaps[0][1] < 0.03
    top = reduced.top_ops(3)
    assert top[0][0] == "flash_attention" and top[1][0] == "paged_attention"
    assert sum(g[1] for g in reduced.idle_gaps(100)) + programs \
        == pytest.approx(reduced.window_s, rel=1e-6)
