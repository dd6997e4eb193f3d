"""From a profiler trace (``.xplane.pb``) to what the metrics read: device
busy time (union of operation intervals), time by operation name, time by
step program, operations inside a kind of program, and the longest gaps.

Read with ``jax.profiler.ProfileData`` alone. On a TPU the device planes are
``/device:TPU:<n>``; their line ``XLA Modules`` holds one event per program
execution (``jit__decode_and_sample(<hash>)``), ``XLA Ops`` one per operation
(``%paged_attention.2 = bf16[...] custom-call(...)``). Times are nanoseconds
on the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_START, WINDOW_END = "bench.window.start", "bench.window.end"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%paged_attention.2 = bf16[..] custom-call(..)`` -> ``paged_attention``."""
    return _SUFFIX.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


def op_dims(event_name: str) -> list[int]:
    """An operation's (first) result shape: ``%x = bf16[4,8,4,128]{..} ...`` ->
    [4, 8, 4, 128]."""
    match = re.search(r" = \(?\w+\[([\d,]*)\]", event_name)
    return [int(d) for d in match.group(1).split(",") if d] if match else []


DECODE_ROWS_BELOW = 64


def program_kind(name: str, paged_rows: set[int]) -> str:
    """What a program execution was, by its name where the jitted function
    has one and else by its attention kernel: the engine jits its decode and
    history-prefill steps through ``functools.partial``, which leaves them all
    one module name (``jit__unknown``). The paged kernel returns
    [B, KV, rows, hd] with rows = the query heads per kv head in a decode step
    (4 here, never above 16) and rows = chunk length x that group in a
    history prefill (at least 128 x 1)."""
    for kind in ("prefill_hist", "prefill", "decode"):
        if name.startswith(f"jit__{kind}_and_sample"):
            return kind
    if any(rows >= DECODE_ROWS_BELOW for rows in paged_rows):
        return "prefill_hist"
    if paged_rows:
        return "decode"
    return "other"


def module_name(event_name: str) -> str:
    """``jit__decode_and_sample(1479..)`` -> ``jit__decode_and_sample``."""
    return event_name.split("(", 1)[0]


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


@dataclass
class DeviceTrace:
    # (start_ns, end_ns, module name, kind): one per program execution
    modules: list[tuple[float, float, str, str]] = field(default_factory=list)
    # (start_ns, end_ns, operation name): one per operation
    ops: list[tuple[float, float, str]] = field(default_factory=list)


def _classify(modules: list[tuple[float, float, str]],
              paged: list[tuple[float, int]]) -> list[tuple[float, float, str, str]]:
    """Give every program execution its kind; ``paged`` holds (start, rows)
    of the paged-attention calls."""
    paged = sorted(paged)
    out, i = [], 0
    for start, end, name in sorted(modules):
        while i < len(paged) and paged[i][0] < start:
            i += 1
        rows, j = set(), i
        while j < len(paged) and paged[j][0] < end:
            rows.add(paged[j][1])
            j += 1
        out.append((start, end, name, program_kind(name, rows)))
    return out


@dataclass
class Reduced:
    """One trace, reduced: events are clipped to the window. Seconds unless
    named otherwise. A program's kind is ``prefill``, ``prefill_hist``,
    ``decode`` or ``other``."""
    devices: dict[str, DeviceTrace]
    window_ns: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def _mean(self, total: float, count: int) -> tuple[float, int]:
        n = max(1, len(self.devices))
        return total / n / 1e9, count // n

    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, averaged over the
        devices (operations where the trace has them, else programs)."""
        total = sum(union_ns([e[:2] for e in (d.ops or d.modules)])
                    for d in self.devices.values())
        return self._mean(total, 0)[0]

    def module_time(self, kinds: tuple[str, ...] = ()) -> tuple[float, int]:
        """(seconds, executions) of programs of these kinds (all: ``()``),
        mean over devices."""
        spans = [e for d in self.devices.values() for e in d.modules
                 if not kinds or e[3] in kinds]
        return self._mean(sum(e[1] - e[0] for e in spans), len(spans))

    def programs(self) -> dict[str, list]:
        """``{"<kind>:<module name>": [seconds, executions]}`` for an earlier line."""
        table: dict[str, list] = {}
        for device in self.devices.values():
            for start, end, name, kind in device.modules:
                row = table.setdefault(f"{kind}:{name}", [0.0, 0])
                row[0] += (end - start) / 1e9
                row[1] += 1
        return table

    def op_time(self, op: str, kinds: tuple[str, ...] = ()) -> tuple[float, int]:
        """(seconds, calls) of operation ``op``; with ``kinds`` only calls that
        ran inside a program of such a kind. Mean over devices."""
        seconds, count = 0.0, 0
        for device in self.devices.values():
            spans = sorted(e[:2] for e in device.modules
                           if not kinds or e[3] in kinds)
            i = 0
            for start, end, name in sorted(device.ops):
                if name != op:
                    continue
                while i < len(spans) and spans[i][1] < start:
                    i += 1
                if not kinds or (i < len(spans) and spans[i][0] <= start):
                    seconds += end - start
                    count += 1
        return self._mean(seconds, count)

    def top_ops(self, limit: int = 10) -> list[list]:
        totals: dict[str, float] = {}
        for device in self.devices.values():
            for e in (device.ops or device.modules):
                totals[e[2]] = totals.get(e[2], 0.0) + (e[1] - e[0])
        n = max(1, len(self.devices))
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / n / 1e9] for name, ns in ranked]

    def idle_gaps(self, limit: int = 5) -> list[list]:
        """The longest spans of the first device in which no program ran,
        named by the kinds of program before and after: all the harness can
        say today of what the host was doing between them."""
        if not self.devices:
            return []
        device = next(iter(self.devices.values()))
        gaps, reach, last = [], self.window_ns[0], "window.start"
        for start, end, name, kind in sorted(device.modules):
            label = kind if kind != "other" else name
            if start > reach:
                gaps.append((start - reach, f"{last}->{label}"))
            if end > reach:
                reach, last = end, label
        if self.window_ns[1] > reach:
            gaps.append((self.window_ns[1] - reach, f"{last}->window.end"))
        gaps.sort(reverse=True)
        return [[name, ns / 1e9] for ns, name in gaps[:limit]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(path: str) -> Reduced:
    """Read one ``.xplane.pb``. The window is what the harness marked
    (``bench.window.start`` .. ``bench.window.end`` host annotations), else
    the span of all device events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, DeviceTrace] = {}
    marks: dict[str, float] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            trace, modules, paged = DeviceTrace(), [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                module_name(ev.name)) for ev in line.events]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name = op_name(ev.name)
                        trace.ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                          name))
                        if name == "paged_attention":
                            paged.append((ev.start_ns, (op_dims(ev.name) + [0, 0, 0])[2]))
            trace.modules = _classify(modules, paged)
            if trace.modules or trace.ops:
                devices[plane.name] = trace
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_START:
                        marks.setdefault("start", ev.start_ns)
                    elif ev.name == WINDOW_END:
                        marks["end"] = ev.start_ns + ev.duration_ns
    if "start" in marks and "end" in marks and marks["end"] > marks["start"]:
        window = (marks["start"], marks["end"])
    else:
        spans = [e[:2] for d in devices.values() for e in d.ops + d.modules]
        window = (min(s for s, _ in spans), max(e for _, e in spans)) if spans \
            else (0.0, 0.0)
    lo, hi = window
    for trace in devices.values():
        for events in (trace.modules, trace.ops):
            events[:] = [(max(e[0], lo), min(e[1], hi), *e[2:]) for e in events
                         if e[1] > lo and e[0] < hi]
    return Reduced(devices, window)
