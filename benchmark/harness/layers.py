"""Per-layer metrics: one small reader per metric, found by the metric's name
(``layer_metrics/<name>.py`` with ``read(ctx) -> float | None``). A reader
that finds nothing to read returns None and the metric is left out."""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any

from .manifest import BENCH_DIR, load_by_name
from .stats import Record
from .trace_reduce import Reduced

DECODE_PROGRAMS = ("decode",)                      # trace_reduce.program_kind
PREFILL_PROGRAMS = ("prefill", "prefill_hist")


@dataclass
class LayerContext:
    """What a reader may look at. Times of ``records``, ``window``,
    ``trace_span`` and ``submits`` are seconds on the client's clock."""
    records: list[Record]
    window: tuple[float, float]
    stats: dict[str, float]                  # EngineStats, end minus start of window
    model: Any                               # the engine's model config
    peak: dict[str, Any] | None = None       # peaks.json row of this device
    trace: Reduced | None = None
    trace_span: tuple[float, float] | None = None
    # request index -> (time engine.submit was entered, the GenRequest)
    submits: dict[int, tuple[float, Any]] = field(default_factory=dict)
    notes: dict[str, Any] = field(default_factory=dict)   # readers' earlier lines


def load_reader(name: str):
    return load_by_name(os.path.join(BENCH_DIR, "layer_metrics"), name,
                        "per-layer reader", ("read",)).read


def read_all(metrics: tuple[dict, ...], ctx: LayerContext) -> dict[str, dict]:
    out = {}
    for metric in metrics:
        value = load_reader(metric["name"])(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def idle_share(ctx: LayerContext) -> float | None:
    """1 - union of device-operation intervals over the traced window, in %."""
    if ctx.trace is None or not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)


def overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))
