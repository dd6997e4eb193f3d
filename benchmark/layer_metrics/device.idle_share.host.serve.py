"""Device: share of the traced window in which no operation ran on the device
AND the engine's dispatch thread was inside a working span (any span but
``loop.wait``): the idle time the host caused, in %. ``per_layer_notes`` gets
the rest of the split (``no_work``: the thread had nothing to do, head-room;
``unattributed``: the thread was under no span), idle seconds by span, the
five longest gaps with the span that covers most of each, how far the
profiler put the device's events from the host's clock (bounded from
causality, and taken out before anything is attributed), and the lag from the
end of a ``*.dispatch`` span that found the device drained to the start of its
program."""
import bisect

from benchmark.harness import timeline_view
from benchmark.harness.stats import percentile


def read(ctx):
    view = timeline_view.load() if timeline_view.traced_on_device(ctx) else None
    if view is None:
        return None
    matched = timeline_view.match_programs(ctx, view)
    skew = timeline_view.clock_skew(matched)
    skew_s = skew["applied"] if skew else 0.0
    idle = timeline_view.device_idle(ctx, skew_s)
    clock = timeline_view.ring_clock(ctx, skew_s)
    window_s, n = ctx.trace.window_s, len(idle)
    by_span: dict[str, float] = {}
    inside = 0.0
    for device, gaps in zip(ctx.trace.devices.values(), idle):
        programs = sorted((clock(e[0]), clock(e[1])) for e in device.modules)
        for lo, hi in gaps:
            for name, seconds in view.cover(lo, hi).items():
                by_span[name] = by_span.get(name, 0.0) + seconds / n
            inside += _inside(programs, lo, hi) / n
    no_work = by_span.get(timeline_view.WAIT, 0.0)
    unattributed = by_span.get(timeline_view.UNLABELLED, 0.0)
    total = sum(by_span.values())
    host = total - no_work - unattributed
    share = 100.0 / window_s
    longest = sorted(idle[0], key=lambda g: g[0] - g[1])[:5]
    ctx.notes["device.idle_share.host"] = {
        "idle_share": total * share, "host": host * share,
        "no_work": no_work * share, "unattributed": unattributed * share,
        # gaps between the operations of one running program: the device's own
        "of_it_inside_programs": inside * share,
        "idle_s_by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "longest_gaps": [_gap_row(view, ctx, lo, hi) for lo, hi in longest],
        "clock_skew_ms": skew and {
            k: v * 1e3 if isinstance(v, float) else v for k, v in skew.items()},
        **_dispatch_lag(matched, view, skew_s)}
    return host * share


def _inside(programs, lo, hi):
    """Seconds of [lo, hi] inside a program execution (sorted, disjoint)."""
    i = max(0, bisect.bisect_right(programs, (lo, float("inf"))) - 1)
    seconds = 0.0
    while i < len(programs) and programs[i][0] < hi:
        seconds += max(0.0, min(programs[i][1], hi) - max(programs[i][0], lo))
        i += 1
    return seconds


def _gap_row(view, ctx, lo, hi):
    cover = view.cover(lo, hi)
    name = max(cover, key=cover.get)
    return {"ms": (hi - lo) * 1e3, "at_s": lo - ctx.trace_span[0],
            "span": name, "span_share": cover[name] / (hi - lo),
            "spans_ms": {k: v * 1e3 for k, v in cover.items()}}


def _dispatch_lag(matched, view, skew_s):
    """A host-fed decode dispatch (and a prefill) finds the device drained,
    so its program starts as soon as it is enqueued: start of the program
    minus end of its ``*.dispatch`` span, median, ms — as the bridge gives it
    (``raw``) and after the skew is taken out."""
    ends = {s.step: s.t1 for s in view.spans if s.name.endswith(".dispatch")}
    lags = [(start - ends[step.seq]) * 1e3 for step, start, _end in matched
            if step.kind != "decode_fb" and step.seq in ends]
    if not lags:
        return {"dispatch_to_device_lag_ms_p50": None, "lag_samples": 0}
    raw = percentile(lags, 50)
    return {"dispatch_to_device_lag_ms_p50": raw + skew_s * 1e3,
            "dispatch_to_device_lag_ms_p50_raw": raw, "lag_samples": len(lags)}
