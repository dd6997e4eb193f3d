"""What the host hands the device for one dispatch is one array and one jitted
call (``tpu_local/call_layout.py``, ``engine._of_call``).

Falsifiable form, for every kind of dispatch (dense prefill, half-length
prefill, chunk round, host-fed and device-fed decode step, verify step with
prompt-lookup drafts and with the family's own, block step):

- exactly one host-to-device transfer carries the call, beside a table sync
  where rows were dirty: counted round ``jax.device_put`` and by
  ``EngineStats.host_uploads`` / the step record's ``host_uploads``;
- between ``build.t0`` and ``dispatch.t1`` the only jitted call is the step
  program (no key split, no ``iota``), read off a profiler capture;
- a call packs and unpacks every field bit for bit, the float32 ones too;
- the step key is folded from the base key and the dispatch's number inside
  the program: a seeded engine repeats itself, two dispatches never share a
  key, two rows of one dispatch draw apart.
"""

import asyncio
import dataclasses
import itertools
import os
import re
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local import engine as engine_mod
from mcp_context_forge_tpu.tpu_local.call_layout import (CALL_TAIL, CallLayout,
                                                         Field)
from mcp_context_forge_tpu.tpu_local.engine import (EngineConfig, GenRequest,
                                                    TPUEngine)
from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS
from mcp_context_forge_tpu.tpu_local.sampling import sample_tokens

_IDS = itertools.count()


def _serve(engine, prompts, max_tokens=6, **kwargs):
    async def main():
        await engine.start()
        try:
            requests = [GenRequest(request_id=f"p{next(_IDS)}",
                                   prompt_ids=list(p), max_tokens=max_tokens,
                                   **kwargs) for p in prompts]

            async def one(request):
                await engine.submit(request)
                while await request.stream.get() is not None:
                    pass
                return request.generated

            return await asyncio.wait_for(
                asyncio.gather(*[one(r) for r in requests]), 300)
        finally:
            await engine.stop()
    return asyncio.run(main())


class Recorded(NamedTuple):
    engine: TPUEngine
    ring: dict                  # the timeline of the recorded serve alone
    rows: list                  # its step-log rows
    puts: list                  # (perf_counter, shape, dtype) a device_put
    uploads: int                # EngineStats.host_uploads over the serve
    trace: list     # (start_ns, end_ns, name, step) of the host plane's events


def _record(engine, prompts, tmp_path, **kwargs) -> Recorded:
    """Serve ``prompts`` three times: twice to compile every program the
    traffic takes (a prefill over the pool a decode step returned compiles
    once more), then once under a counting wrapper round ``jax.device_put``
    and a profiler capture that shows every jitted call."""
    from jax.profiler import ProfileData

    for _ in range(2):
        _serve(engine, prompts, **kwargs)
    marks = (len(engine.timeline.snapshot()["span"]),
             len(engine.timeline.snapshot()["step"]), len(engine.step_log),
             engine.stats.host_uploads)
    real, puts = jax.device_put, []

    def counted(value, *args, **kw):
        puts.append((time.perf_counter(), np.shape(value),
                     getattr(value, "dtype", None)))
        return real(value, *args, **kw)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2       # PjitFunction(...) a jitted call
    engine_mod.jax.device_put = counted
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _serve(engine, prompts, **kwargs)
    finally:
        jax.profiler.stop_trace()
        engine_mod.jax.device_put = real
    ring = engine.timeline.snapshot()
    paths = [os.path.join(root, f) for root, _d, files in os.walk(tmp_path)
             for f in files if f.endswith(".xplane.pb")]
    trace = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
              dict(ev.stats).get("step"))
             for plane in ProfileData.from_file(paths[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    return Recorded(engine, {"span": ring["span"][marks[0]:],
                             "step": ring["step"][marks[1]:]},
                    list(engine.step_log)[marks[2]:], puts,
                    engine.stats.host_uploads - marks[3], trace)


def _config(**overrides):
    kwargs = dict(model="llama3-test", max_batch=4, max_seq_len=128,
                  page_size=16, num_pages=64, prefill_buckets=(16, 64),
                  dtype="float32", attn_impl="reference", prefix_cache=False)
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


@pytest.fixture(scope="module")
def overlapped(tmp_path_factory):
    """Dense prefills at both buckets, the upper bucket's half program (a lone
    20-token prompt), chunk rounds (100 > 64), one host-fed decode step after
    every barrier and device-fed ones between."""
    engine = TPUEngine(_config(decode_overlap=True))
    assert engine.half_lengths == {64: 32}
    return _record(engine, [list(range(300, 400)), list(range(10, 20)),
                            list(range(40, 80)), list(range(20, 40))],
                   tmp_path_factory.mktemp("overlapped"), max_tokens=8)


@pytest.fixture(scope="module")
def lookup(tmp_path_factory):
    """Verify steps whose drafts are looked up in the prompt."""
    engine = TPUEngine(_config(spec_decode=True, spec_k=4))
    return _record(engine, [[7, 8, 9, 7, 8, 9, 7, 8]],
                   tmp_path_factory.mktemp("lookup"), max_tokens=8)


@pytest.fixture(scope="module")
def drafting(tmp_path_factory):
    """Verify steps of a family that drafts on the device (every prefill and
    chunk round carries ``follow``), at a vocabulary where drafts agree."""
    name = "mtp-v16-packed"
    MODEL_CONFIGS[name] = dataclasses.replace(
        MODEL_CONFIGS["deepseek-mtp-test"], name=name, vocab_size=16)
    try:
        engine = TPUEngine(EngineConfig(
            model=name, max_seq_len=256, page_size=16, num_pages=96,
            prefill_buckets=(32,), prefill_max_batch=2, max_batch=4,
            dtype="float32", prefix_cache=False, spec_decode=True, spec_k=2),
            devices=jax.devices()[:1])
    finally:
        del MODEL_CONFIGS[name]
    assert engine._drafts
    return _record(engine, [[1, 2, 3, 4, 5], list(range(3, 15)) * 4],
                   tmp_path_factory.mktemp("drafting"), max_tokens=8)


@pytest.fixture(scope="module")
def blocks(tmp_path_factory):
    """Block steps (a family whose state rows sync beside its block table
    does not exist among the toys: this one syncs the table alone)."""
    engine = TPUEngine(EngineConfig(
        model="sdar-test", max_batch=4, max_seq_len=256, page_size=16,
        num_pages=64, prefill_buckets=(32,), prefill_max_batch=2,
        prefix_cache=False, decode_overlap=False, dtype="float32"),
        devices=jax.devices()[:1])
    return _record(engine, [list(range(5, 25)), list(range(30, 36))],
                   tmp_path_factory.mktemp("blocks"), max_tokens=8)


# case -> (fixture, the timeline's kind, the padded length or None, the name
# of the one program its dispatch calls)
CASES = {
    "dense prefill": ("overlapped", "prefill", 64, "_prefill_and_sample"),
    "half prefill": ("overlapped", "prefill", 32, "_prefill_and_sample"),
    "chunk round": ("overlapped", "chunk", None, "_prefill_hist_and_sample"),
    "first decode step": ("overlapped", "decode", None, "_decode_and_sample"),
    "device-fed decode step": ("overlapped", "decode_fb", None,
                               "_decode_and_sample_fb"),
    "verify step, looked-up drafts": ("lookup", "spec", None,
                                      "_verify_and_sample"),
    "verify step, device drafts": ("drafting", "spec", None,
                                   "_decode_and_sample_draft"),
    "chunk round with follow": ("drafting", "chunk", None,
                                "_prefill_hist_and_sample"),
    "block step": ("blocks", "decode", None, "_decode_and_sample_block"),
}


def _steps_of(case, request):
    fixture, kind, length, program = CASES[case]
    recorded = request.getfixturevalue(fixture)
    steps = [s for s in recorded.ring["step"] if s.kind == kind
             and (length is None or s.shape == length)]
    assert steps, f"the recorded serve made no {case}"
    return recorded, steps, program


def _spans_of(recorded, seq):
    return {s.name.split(".", 1)[1]: s for s in recorded.ring["span"]
            if s.step == seq and s.name.split(".")[0] in ("prefill", "decode")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_transfer_carries_the_call(case, request):
    recorded, steps, _program = _steps_of(case, request)
    rows = {row["seq"]: row for row in recorded.rows}
    for step in steps:
        spans = _spans_of(recorded, step.seq)
        upload = spans["dispatch.upload"]
        inside = [p for p in recorded.puts if upload.t0 <= p[0] <= upload.t1]
        assert len(inside) == 1, (case, inside)
        _t, shape, dtype = inside[0]
        assert len(shape) == 2 and shape[0] == step.width and dtype == np.int32
        # build.t0 -> dispatch.t1: that one, and what the table sync sent
        held = [p for p in recorded.puts
                if spans["build"].t0 <= p[0] <= spans["dispatch"].t1]
        sync = spans.get("table_sync")
        synced = [p for p in held if sync and sync.t0 <= p[0] <= sync.t1]
        assert len(held) - len(synced) == 1, (case, held)
        # the record's own count: its call, and the syncs that led to it
        assert 1 <= rows[step.seq]["host_uploads"] <= 2
        assert rows[step.seq]["host_uploads"] >= 1 + len(synced)


@pytest.mark.parametrize("fixture", ["overlapped", "lookup", "drafting",
                                     "blocks"])
def test_the_counter_counts_every_transfer_and_the_records_share_them_out(
        fixture, request):
    recorded = request.getfixturevalue(fixture)
    assert recorded.uploads == len(recorded.puts)
    assert sum(row["host_uploads"] for row in recorded.rows) == recorded.uploads
    dispatches = len(recorded.ring["step"])
    assert dispatches == len(recorded.rows)
    # one a dispatch and a table sync where rows were dirty: under two
    assert dispatches <= recorded.uploads < 2 * dispatches
    assert "host_uploads" not in (recorded.rows[0]["phases"] or {})


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_step_program_is_the_only_jitted_call_of_a_dispatch(case, request):
    recorded, steps, program = _steps_of(case, request)
    window: dict[int, list[int]] = {}
    for start, end, name, seq in recorded.trace:
        if re.fullmatch(r"(prefill|decode)\.(build|dispatch)", name):
            window.setdefault(int(seq), []).extend((start, end))
    # the capture shows a call's entry and, nested in it, its fast path:
    # the outermost events are the calls
    calls, busy_until = [], 0
    for start, end, name, _seq in sorted(recorded.trace):
        if name.startswith("PjitFunction(") and start >= busy_until:
            calls.append((start, name))
            busy_until = end
    assert calls, "the capture shows no jitted call"
    for step in steps:
        lo, hi = min(window[step.seq]), max(window[step.seq])
        inside = [name for start, name in calls if lo <= start <= hi]
        assert inside == [f"PjitFunction({program})"], (case, step.seq)


# ------------------------------------------------------------ pack -> unpack

VALUES = {
    "plain": [0.0, 0.8, 1.0, 0.95],
    "negative": [-0.0, -1.5, -3.0e38, -1e-3],
    "denormal": [1e-45, -1e-45, 1.1754942e-38, 5e-41],
    "extremes": [3.4028235e38, float("inf"), 1.1920929e-07, 1e-30],
}


@pytest.mark.parametrize("values", sorted(VALUES))
def test_a_call_unpacks_every_field_bit_for_bit(values):
    rng = np.random.default_rng(3)
    layout = CallLayout(Field("tokens", 5, np.int32, 7),
                        Field("masked", 5, np.bool_),
                        Field("last_idx", 0, np.int32), *CALL_TAIL)
    call, fields = layout.host(4)
    # an idle call holds the fills
    assert (fields["tokens"] == 7).all() and (fields["top_p"] == 1.0).all()
    assert not fields["masked"].any() and call.shape == (4, layout.width)
    floats = np.asarray(VALUES[values], np.float32)
    want = {
        "tokens": rng.integers(-2**31, 2**31 - 1, (4, 5)).astype(np.int32),
        "masked": rng.integers(0, 2, (4, 5)).astype(bool),
        "last_idx": np.asarray([0, -1, 2**31 - 1, -2**31], np.int32),
        "temperature": floats, "top_p": floats[::-1].copy(),
        "top_k": np.asarray([0, 1, 50, 2**31 - 1], np.int32),
        "counter": np.full((4,), 2**31 - 1, np.int32),
    }
    for name, value in want.items():
        fields[name][:] = value
    got = jax.jit(layout.unpack)(jax.device_put(call))
    assert set(got) == set(want)
    for name, value in want.items():
        out = np.asarray(got[name])
        assert out.dtype == value.dtype and out.shape == value.shape
        bits = np.uint8 if value.dtype == bool else np.int32
        np.testing.assert_array_equal(out.view(bits), value.view(bits), name)


def test_a_layout_refuses_what_does_not_ride_in_32_bits_and_a_foreign_call():
    with pytest.raises(TypeError, match="int32 column"):
        CallLayout(Field("wide", 0, np.float64))
    layout = CallLayout(Field("tokens", 3, np.int32), *CALL_TAIL)
    with pytest.raises(ValueError, match="against a layout"):
        layout.unpack(jnp.zeros((2, layout.width + 1), jnp.int32))


@pytest.mark.parametrize("which", ["prefill", "prefill with follow", "decode",
                                   "decode_fb", "verify", "block"])
def test_each_programs_layout_carries_its_step_functions_parameters(
        which, request):
    """The fields of an engine's layouts are its step functions' own
    parameter names (``_of_call`` hands them over by keyword), the sampling
    parameters and the counter last."""
    import inspect

    fixture, layout, step = {
        "prefill": ("overlapped", lambda e: e._prefill_call(16),
                    "_prefill_and_sample"),
        "prefill with follow": ("drafting", lambda e: e._prefill_call(32),
                                "_prefill_hist_and_sample"),
        "decode": ("overlapped", lambda e: e._decode_call,
                   "_decode_and_sample"),
        "decode_fb": ("overlapped", lambda e: e._decode_fb_call,
                      "_decode_and_sample_fb"),
        "verify": ("lookup", lambda e: e._verify_call, "_verify_and_sample"),
        "block": ("blocks", lambda e: e._block_call,
                  "_decode_and_sample_block"),
    }[which]
    engine = request.getfixturevalue(fixture).engine
    layout = layout(engine)
    names = [f.name for f in layout.fields]
    assert tuple(layout.fields[-4:]) == CALL_TAIL
    accepted = set(inspect.signature(getattr(engine, step)).parameters)
    assert set(names[:-4]) <= accepted, (names, accepted)
    assert ("follow" in names) == (which == "prefill with follow")
    assert ("tokens" in names) == (which != "decode_fb")
    if which.startswith("prefill"):     # found again from the call's width
        assert engine._prefill_call_of_width(layout.width) is layout


# ------------------------------------------------------------------ the key

def _flat_logits_program(engine):
    """The decode program's wrapper round a step that samples every row from
    the SAME flat logits: what differs between draws is the key alone."""
    def step(params, kv, *, sampling, key, **_fields):
        return sample_tokens(jnp.zeros((4, 4096), jnp.float32), sampling,
                             key), kv
    return jax.jit(engine._of_call(step, engine._decode_call))


def _sampled_call(engine, counter: int):
    call, fields = engine._decode_call.host(4)
    fields["temperature"][:] = 0.8
    fields["counter"][:] = counter
    return jax.device_put(call, engine._call_sharding)


def test_rows_and_dispatches_draw_apart_and_a_number_repeats_itself(overlapped):
    engine = overlapped.engine
    program = _flat_logits_program(engine)
    key = jax.random.PRNGKey(1234)
    draw = lambda counter: np.asarray(
        program(None, None, _sampled_call(engine, counter), key)[0]).tolist()
    first, again, second = draw(1), draw(1), draw(2)
    assert first == again                       # the same number, the same key
    assert len(set(first)) == 4                 # four rows, four draws
    assert all(a != b for a, b in zip(first, second))   # the next dispatch
    other = np.asarray(program(None, None, _sampled_call(engine, 1),
                               jax.random.PRNGKey(1235))[0]).tolist()
    assert other != first                       # another base key


def test_a_seeded_engine_samples_the_same_tokens_twice(overlapped):
    engine = overlapped.engine
    prompts = [list(range(10, 20)), list(range(40, 80))]
    runs = []
    for _ in range(2):
        engine._rng = jax.device_put(jax.random.PRNGKey(7),
                                     engine._call_sharding)
        engine._dispatch_no = 0
        runs.append(_serve(engine, prompts, max_tokens=10, temperature=0.8,
                           top_k=20))
    assert runs[0] == runs[1]
    assert engine._dispatch_no > 10             # a number a dispatch
    assert engine.stats.sample_filtered_steps > 0
    engine._dispatch_no = 0
    engine._rng = jax.device_put(jax.random.PRNGKey(8), engine._call_sharding)
    assert _serve(engine, prompts, max_tokens=10, temperature=0.8,
                  top_k=20) != runs[0]


def test_the_dispatch_number_never_repeats_under_one_base_key(overlapped):
    """Past the int32 the number rides in, the base key moves on and the
    count restarts."""
    engine = overlapped.engine
    engine._dispatch_no = 0x7FFFFFFF - 1
    before = np.asarray(engine._rng).tolist()
    assert engine._next_dispatch() == 0x7FFFFFFF
    assert np.asarray(engine._rng).tolist() == before
    assert engine._next_dispatch() == 1
    assert np.asarray(engine._rng).tolist() != before
