"""XLA cost-model registry + roofline math for the live MFU / HBM gauges.

MFU and ``hbm_roofline_frac`` computed after the fact from analytic byte
counts are invisible in production. This module makes the same numbers
ALWAYS-ON: at warmup the engine lowers each compiled executable once
more through the AOT path and records XLA's
own ``cost_analysis()`` (FLOPs, bytes accessed) into a per-engine
:class:`CostRegistry`; every decode retire then divides the dispatched
executable's cost by its measured wall to feed the
``mcpforge_llm_mfu`` / ``mcpforge_llm_hbm_roofline_frac`` gauges.

The peaks are per-chip and configurable (``EngineConfig.peak_tflops_per_
chip`` / ``hbm_gbps_per_chip``); defaults are TPU v5e, whatever device
runs — so the live gauges mean something only on a v5e (ROADMAP Queue 3
item 1 replaces the two settings with a table keyed by ``device_kind``).
Anything that PRINTS a fraction of a peak asks :func:`v5e_peaks` first and
prints ``null`` for any other device.

Pure stdlib on purpose: importable before the jax platform is pinned, so
it must not import jax at module scope.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

# TPU v5e, per chip (Google Cloud documentation, "TPU v5e")
V5E_PEAK_BF16_TFLOPS = 197.0
V5E_HBM_GBPS = 819.0
V5E_DEVICE_KIND = "TPU v5 lite"   # what jax reports as device_kind


def v5e_peaks(device_kind: str) -> tuple[float, float] | None:
    """(peak bf16 TFLOP/s, HBM GB/s) per chip where ``device_kind`` is a
    v5e, None for every other device — a caller that gets None prints no
    fraction of a peak."""
    if device_kind == V5E_DEVICE_KIND:
        return V5E_PEAK_BF16_TFLOPS, V5E_HBM_GBPS
    return None


@dataclass(frozen=True)
class CostEntry:
    """One executable's XLA cost model: total FLOPs and HBM bytes touched
    per dispatch (the whole batch, not per row), plus how many Pallas
    kernel calls (``tpu_custom_call``) its compiled text holds — 0 says
    the step runs the XLA reference paths."""

    flops: float
    bytes_accessed: float
    kernel_calls: int = 0


def normalize_cost_analysis(analysis: dict[str, Any],
                            kernel_calls: int = 0) -> CostEntry | None:
    """The numbers the roofline needs out of ``Compiled.cost_analysis()``,
    or None when XLA priced the executable at nothing."""
    flops = float(analysis.get("flops", 0.0) or 0.0)
    byts = float(analysis.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and byts <= 0.0:
        return None
    return CostEntry(flops=flops, bytes_accessed=byts,
                     kernel_calls=kernel_calls)


def roofline_fractions(flops: float, bytes_accessed: float, dur_s: float,
                       n_chips: int, peak_tflops_per_chip: float,
                       hbm_gbps_per_chip: float) -> tuple[float, float]:
    """(mfu, hbm_roofline_frac) for one dispatch of known cost and wall."""
    if dur_s <= 0.0:
        return 0.0, 0.0
    chips = max(1, n_chips)
    mfu = flops / dur_s / (peak_tflops_per_chip * 1e12 * chips)
    frac = bytes_accessed / dur_s / (hbm_gbps_per_chip * 1e9 * chips)
    return mfu, frac


class CostRegistry:
    """Per-engine map of (kind, batch width, ctx bucket) -> CostEntry.

    Kinds mirror the engine's executable families: ``prefill`` (dense,
    keyed by token bucket at B=1), ``decode`` / ``decode_fb`` (keyed by
    batch width x context-page bucket), ``spec_verify``. Populated only
    at warmup — capture lowers+compiles through the AOT path, which is a
    real XLA compile, so it must never run on the serving path.
    """

    def __init__(self) -> None:
        self._entries: dict[str, dict[tuple[int, int], CostEntry]] = {}

    def capture(self, kind: str, width: int, ctx: int, fn: Any,
                *args: Any) -> CostEntry | None:
        """Record ``fn``'s XLA cost at this shape (``fn`` is a jitted
        callable; ``args`` the exact example arguments warmup dispatches).
        A shape the compiler refuses raises here, as the warming call
        right after it would."""
        compiled = fn.lower(*args).compile()
        entry = normalize_cost_analysis(
            compiled.cost_analysis(),
            kernel_calls=compiled.as_text().count("tpu_custom_call"))
        if entry is not None:
            self._entries.setdefault(kind, {})[(width, ctx)] = entry
        return entry

    def lookup(self, kind: str, width: int, ctx: int) -> CostEntry | None:
        """Exact (width, ctx) hit, else the same ctx at any width (batch
        rows are cheap next to the shared param read decode streams, so a
        width-mismatched entry is still the right order of magnitude)."""
        table = self._entries.get(kind)
        if not table:
            return None
        entry = table.get((width, ctx))
        if entry is not None:
            return entry
        for (_w, c), candidate in sorted(table.items()):
            if c == ctx:
                return candidate
        return None

    def counts(self) -> dict[str, int]:
        return {kind: len(table) for kind, table in sorted(
            self._entries.items())}

    def snapshot(self) -> dict[str, Any]:
        """Serializable registry view for /admin/engine/steps + bench."""
        return {
            kind: {f"{w}x{c}": {"flops": entry.flops,
                                "bytes_accessed": entry.bytes_accessed,
                                "kernel_calls": entry.kernel_calls}
                   for (w, c), entry in sorted(table.items())}
            for kind, table in sorted(self._entries.items())
        }
