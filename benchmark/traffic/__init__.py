"""Traffic: a mix is ``<mix>.json``; its ``kind`` names the generator module here."""

from __future__ import annotations

import importlib
from typing import Any


def plan(mix: dict[str, Any], cell: dict[str, Any], seconds: float, seed: int,
         overhead: int) -> dict[str, Any]:
    """The requests of one run: ``{"mode": "open", "requests": [Planned]}`` or
    ``{"mode": "closed", "clients": n, "request": index -> Planned}``."""
    module = importlib.import_module(f"{__name__}.{mix['kind']}")
    return module.plan(mix, cell, seconds, seed, overhead)
