"""Seeded draws shared by the traffic generators.

Lengths and gaps are the quantile grid of the stated distribution, in an order
drawn once from the mix's ``schedule_seed``; ``--seed`` draws the text. So every
seed offers the same schedule of the same sizes, with other content. Measured
on the chip (PERF.md, PR 23): with the order drawn from ``--seed`` two runs of
one seed agreed to 2-5 % on ``ttft_p95_ms`` while six seeds spread by 29 % —
a tail is set by which long prompts meet which bursts, so another order is
another workload. No decision of the system depends on the text.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass

import numpy as np

ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789    ", dtype=np.uint8)
NONCE = "r{:06d} "


@dataclass(frozen=True)
class Planned:
    """One request as the generator hands it to the client."""
    index: int
    due_s: float | None       # seconds after the window opens; None: closed loop
    prompt_tokens: int        # tokens of the rendered chat template
    max_tokens: int
    content: str


def grid(spec: dict, n: int) -> list[int]:
    """``n`` whole numbers at the mid-quantiles of ``spec``'s distribution."""
    low, high = float(spec["low"]), float(spec["high"])
    if not (0 < low <= high):
        raise ValueError(f"bad range in {spec}")
    quantiles = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "log_uniform":
        values = [low * (high / low) ** q for q in quantiles]
    elif spec["dist"] == "uniform":
        values = [low + (high - low) * q for q in quantiles]
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return [int(round(v)) for v in values]


def exponential_gaps(n: int, rate: float) -> list[float]:
    """``n`` inter-arrival gaps at the mid-quantiles of Exp(rate)."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def shuffled(values: list, seed: int, stream: str) -> list:
    out = list(values)
    random.Random(f"{seed}/{stream}").shuffle(out)
    return out


def text(seed: int, index: int, prompt_tokens: int, overhead: int) -> str:
    """Printable ASCII (one byte = one token of the byte-level tokenizer) that
    renders to ``prompt_tokens`` tokens; a per-request nonce leads, so no two
    prompts share a first page and the prefix cache is bypassed."""
    nonce = NONCE.format(index)
    body = prompt_tokens - overhead - len(nonce)
    if body < 0:
        raise ValueError(f"{prompt_tokens} prompt tokens cannot hold the chat "
                         f"template ({overhead}) and the nonce")
    rng = np.random.default_rng([seed % (2 ** 63), index])
    return nonce + ALPHABET[rng.integers(0, len(ALPHABET), body)].tobytes().decode()


def nonce_index(prompt: bytes) -> int | None:
    """The request index a prompt's nonce carries (None: not a planned one)."""
    at = prompt.find(b"\nr")
    head = prompt[at + 2:at + 8]
    return int(head) if at >= 0 and head.isdigit() and len(head) == 6 else None
