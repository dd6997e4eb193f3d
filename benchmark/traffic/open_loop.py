"""Open loop: requests fall due on a schedule whether or not earlier ones finished."""

from __future__ import annotations

from typing import Any

from ..harness.draw import Planned, exponential_gaps, grid, shuffled, text


def plan(mix: dict[str, Any], cell: dict[str, Any], seconds: float, seed: int,
         overhead: int) -> dict[str, Any]:
    rate = float(cell["rate_rps"])
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"open_loop knows poisson arrivals, not {mix['arrivals']!r}")
    n = max(1, round(rate * seconds))
    order = int(mix["schedule_seed"])      # the schedule is the mix's, see draw.py
    gaps = shuffled(exponential_gaps(n, rate), order, "gaps")
    # the fixed set of gaps sums to a little under n/rate; stretch it so the
    # last request falls due half a mean gap before the window closes
    scale = (seconds - 0.5 / rate) / sum(gaps) if n > 1 else 0.0
    prompts = shuffled(grid(mix["prompt_tokens"], n), order, "prompts")
    outputs = shuffled(grid(mix["max_tokens"], n), order, "outputs")
    requests, due = [], 0.0
    for i in range(n):
        due += gaps[i] * scale
        requests.append(Planned(i, due, prompts[i], outputs[i],
                                text(seed, i, prompts[i], overhead)))
    return {"mode": "open", "requests": requests}
