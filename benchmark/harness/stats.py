"""From request records to the end-to-end metrics. Pure arithmetic, no clock."""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any

PERCENTILE = re.compile(r"^(ttft|tpot)_p(\d{1,2})_ms$")
MISSED_MS = 1e9   # what a tail reads when the failed requests reach it


@dataclass
class Record:
    """One request as the client saw it; times on one monotonic clock, seconds."""
    index: int
    due: float
    prompt_tokens: int
    max_tokens: int
    sent: float = math.nan
    token_times: list[float] = field(default_factory=list)
    done: float = math.nan
    ok: bool = False
    finish: str | None = None
    error: str | None = None

    @property
    def tokens(self) -> int:
        return len(self.token_times)

    @property
    def ttft_ms(self) -> float:
        """Due to first token; a failed request misses every latency."""
        if not self.ok or not self.token_times:
            return math.inf
        return (self.token_times[0] - self.due) * 1e3

    @property
    def tpot_ms(self) -> float | None:
        """Mean gap of this request: (last - first) / (tokens - 1); None for a
        good request of one token, which has no gap."""
        if not self.ok:
            return math.inf
        if self.tokens < 2:
            return None
        return (self.token_times[-1] - self.token_times[0]) * 1e3 / (self.tokens - 1)


def percentile(values: list[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default); an
    infinite value (a failed request) that the rank reaches gives infinity."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * p / 100.0
    low, high = math.floor(rank), math.ceil(rank)
    if math.isinf(ordered[high]):
        return math.inf
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def end_to_end(name: str, records: list[Record], window: tuple[float, float],
               setup_s: float) -> float | None:
    """The value of end-to-end metric ``name`` over ALL requests that fell due
    in the window; None where the metric has no sample."""
    if name == "setup_s":
        return setup_s
    if name == "tokens_per_s":
        start, end = window
        done = sum(r.prompt_tokens + r.tokens for r in records
                   if r.ok and r.done <= end)
        return done / (end - start)
    match = PERCENTILE.match(name)
    if match is None:
        raise KeyError(f"no arithmetic for end-to-end metric {name!r}")
    which, p = match.group(1), float(match.group(2))
    if which == "ttft":
        values = [r.ttft_ms for r in records]
    else:
        values = [v for v in (r.tpot_ms for r in records) if v is not None]
    if not values:
        return None
    value = percentile(values, p)
    return MISSED_MS if math.isinf(value) else value


def lateness_ms(records: list[Record]) -> dict[str, Any]:
    """How late the generator sent (sent - due): a starved generator must not
    read as a fast server."""
    late = [(r.sent - r.due) * 1e3 for r in records if not math.isnan(r.sent)]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50": percentile(late, 50), "p99": percentile(late, 99),
            "max": max(late)}
