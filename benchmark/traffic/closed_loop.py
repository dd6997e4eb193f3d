"""Closed loop: ``clients`` callers, each sending its next request when the last returned."""

from __future__ import annotations

from typing import Any

from ..harness.draw import Planned, grid, shuffled, text


def plan(mix: dict[str, Any], cell: dict[str, Any], seconds: float, seed: int,
         overhead: int) -> dict[str, Any]:
    cycle = int(mix.get("cycle", 64))
    order = int(mix["schedule_seed"])      # the schedule is the mix's, see draw.py
    prompts = shuffled(grid(mix["prompt_tokens"], cycle), order, "prompts")
    outputs = shuffled(grid(mix["max_tokens"], cycle), order, "outputs")

    def request(index: int) -> Planned:
        k = index % cycle
        return Planned(index, None, prompts[k], outputs[k],
                       text(seed, index, prompts[k], overhead))

    return {"mode": "closed", "clients": int(cell["clients"]), "request": request}
