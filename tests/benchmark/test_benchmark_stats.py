"""Percentile and due-time arithmetic on hand-made records."""

import math

import pytest

from benchmark.harness.stats import (MISSED_MS, Record, end_to_end,
                                     lateness_ms, percentile)


def rec(index, due, sent, times, done, ok=True, prompt=100):
    return Record(index, due, prompt, len(times), sent=sent, token_times=list(times),
                  done=done, ok=ok, finish="length" if ok else None)


def test_percentile_interpolates_like_numpy():
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile([10], 95) == 10
    assert percentile(list(range(101)), 95) == 95
    assert math.isinf(percentile([1, 2, math.inf], 95))
    with pytest.raises(ValueError):
        percentile([], 50)


def test_ttft_runs_from_due_not_from_sent():
    # due at 10.0, the generator sent 0.3 s late, first token at 10.5
    r = rec(0, 10.0, 10.3, [10.5, 10.6, 10.7], 10.8)
    assert r.ttft_ms == pytest.approx(500.0)
    assert lateness_ms([r])["max"] == pytest.approx(300.0)


def test_tpot_is_a_per_request_mean():
    # a super-step delivered three tokens at once: no zero gaps are counted
    r = rec(0, 0.0, 0.0, [1.0, 1.3, 1.3, 1.3], 1.4)
    assert r.tpot_ms == pytest.approx(100.0)
    assert rec(1, 0.0, 0.0, [1.0], 1.0).tpot_ms is None


@pytest.mark.parametrize("name", ["ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms"])
def test_a_failed_request_misses_every_limit(name):
    good = [rec(i, 0.0, 0.0, [0.1, 0.2], 0.3) for i in range(9)]
    failed = rec(9, 0.0, 0.0, [0.1], math.nan, ok=False)
    assert math.isinf(failed.ttft_ms) and math.isinf(failed.tpot_ms)
    value = end_to_end(name, good + [failed], (0.0, 1.0), 1.0)
    # one failure in ten reaches the 95th percentile, not the median
    assert (value == MISSED_MS) == name.endswith("p95_ms")


def test_tokens_per_s_counts_what_completed_inside_the_window():
    inside = rec(0, 0.0, 0.0, [1.0] * 10, 5.0, prompt=90)
    late = rec(1, 9.0, 9.0, [9.5] * 10, 11.0, prompt=90)      # done after the end
    failed = rec(2, 1.0, 1.0, [2.0] * 5, 3.0, ok=False, prompt=90)
    assert end_to_end("tokens_per_s", [inside, late, failed], (0.0, 10.0), 0.0) \
        == pytest.approx(10.0)
    assert end_to_end("setup_s", [], (0.0, 1.0), 42.5) == 42.5
    with pytest.raises(KeyError):
        end_to_end("goodput", [inside], (0.0, 1.0), 0.0)


def test_sweep_row_counts_limits_and_backlog():
    """The knee sweep's judgement of one window: a failed or slow request
    misses the limits, and the backlog is what was due and not yet done."""
    from benchmark.sweep import judge

    fast = [rec(i, i * 1.0, i * 1.0, [i + 0.2, i + 0.25, i + 0.3], i + 0.5)
            for i in range(8)]
    slow = rec(8, 8.0, 8.0, [9.5, 9.6], 9.7)                 # first token after 1.5 s
    failed = rec(9, 9.0, 9.0, [], math.nan, ok=False)
    row = judge(fast + [slow, failed], (0.0, 10.0), ttft_ms=1000.0, tpot_ms=100.0)
    assert row["attempted"] == 10 and row["failed"] == 1
    assert row["met_both_share"] == pytest.approx(0.8)
    assert row["backlog_middle"] == 1          # due by 5.0: requests 0..5; 5 is done at 5.5
    assert row["backlog_end"] == 1             # only the failed one never finished
    assert row["passes"] is False              # under nine tenths
    assert judge(fast, (0.0, 10.0), 1000.0, 100.0)["passes"] is True


def test_per_layer_tail_readers_match_the_end_to_end_arithmetic():
    """The TTFT tail is reported per layer (no cell's window holds enough
    requests for it to repeat): it is the end-to-end arithmetic, failures
    included; the queue's reader beside it reads the engine's own waits."""
    from types import SimpleNamespace

    from benchmark.harness import layers

    records = [rec(i, 0.0, 0.0, [0.1 + 0.01 * i, 0.5], 0.6) for i in range(40)]
    submits = {i: (0.0, SimpleNamespace(queue_ms=float(i), queue_observed=True))
               for i in range(40)}
    ctx = layers.LayerContext(records=records, window=(0.0, 1.0), stats={},
                              model=None, submits=submits)
    assert layers.load_reader("ttft_tail_p95_ms")(ctx) \
        == end_to_end("ttft_p95_ms", records, (0.0, 1.0), 0.0)
    assert layers.load_reader("queue.wait_ms_p95.no_tail")(ctx) == pytest.approx(37.05)
    ctx.records = records + [rec(40 + i, 0.0, 0.0, [], math.nan, ok=False)
                             for i in range(5)]
    assert layers.load_reader("ttft_tail_p95_ms")(ctx) == MISSED_MS
