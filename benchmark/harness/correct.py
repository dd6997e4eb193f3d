"""What decides ``correct``, outside the timed window: the engine's prefill
and decode logits against the plain reference, greedy repeatability, and
(after the window) exact token accounting and zero serving-stage compiles.

The yardstick's part of the logits check is here: the prompts drawn from the
configuration's ``check_seed``, the check's lengths (``Check``: the
configuration's ``"check"`` group, or the defaults below, the one place they
exist), the tolerance rule, the verdict and the facts printed. How logits are
produced on each side is the configuration's family's
(``benchmark/families/<family>.py``: the program's own prefill and decode
through its own cache, and the name of the plain reference); this module names
no model family.

Tolerance (the configuration file's ``logits_tolerance``; reason): the engine
computes in bfloat16 (8 bits of mantissa, a relative step of 2**-8 = 0.4 %)
through ``n_layers`` residual blocks and rounds attention weights to bf16
before P.V; the reference is float32 throughout on the same dequantised
weights. Logits of random weights have magnitude 1 to 4, and the
accumulated rounding was measured at a few hundredths (see PERF.md). A wrong
head mapping, mask, page, scale, RoPE convention or expert choice moves
logits by O(1); int8 or fp8 activations would move them by several tenths.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from .. import families


@dataclasses.dataclass(frozen=True)
class Check:
    """The lengths the logits check runs at. A configuration whose mechanism
    starts at some context length states lengths above it."""
    prompt_lengths: tuple[int, ...] = (96, 40)
    decode_positions: int = 8

    @property
    def tokens(self) -> int:
        """The longest sequence the check holds in the cache."""
        return max(self.prompt_lengths) + self.decode_positions


def check_of(config: dict[str, Any], mix: dict[str, Any] | None = None) -> Check:
    """The configuration file's optional ``"check": {"prompt_lengths": [...],
    "decode_positions": n}`` (absent keys: the defaults), refused where it is
    malformed or longer than the mix's ``max_seq_len``."""
    group = config.get("check", {})
    unknown = set(group) - {"prompt_lengths", "decode_positions"}
    if unknown:
        raise ValueError(f"check: unknown keys {sorted(unknown)}")
    check = Check(tuple(group.get("prompt_lengths", Check.prompt_lengths)),
                  group.get("decode_positions", Check.decode_positions))
    if not (check.prompt_lengths
            and all(type(n) is int and n >= 2 for n in check.prompt_lengths)
            and type(check.decode_positions) is int and check.decode_positions >= 1):
        raise ValueError(f"check: prompt_lengths are whole numbers from 2, "
                         f"decode_positions one from 1; got {group}")
    limit = (mix or {}).get("engine", {}).get("max_seq_len")
    if limit is not None and check.tokens > int(limit):
        raise ValueError(
            f"check: {max(check.prompt_lengths)} prompt + {check.decode_positions} "
            f"decode tokens exceed the mix's max_seq_len {limit}")
    return check


def logits_check(engine, seed: int, tolerance: dict[str, float],
                 check: Check = Check(), family: str | None = None) -> dict[str, Any]:
    """One prompt per ``check.prompt_lengths`` from ``seed`` (the
    configuration's ``check_seed``, not the run's: weights and check prompts
    are then the same in every run, so the tolerance was validated on exactly
    the comparison each run makes): the family's engine logits (prefill's last
    position and ``check.decode_positions`` decode steps through the cache)
    against its reference's full forward over the same tokens.

    A position passes where every logit is within ``atol + rtol * |ref|``.
    ``positions_within`` (default 1: all of them) is the share of positions
    that must pass, and ``atol_any`` bounds every logit of every position. A
    routed model needs the looser pair: with random weights the router's second
    and third choices are often closer than bf16 resolves, the engine then
    picks another expert than the float32 reference for that token, and that
    one position (and, diluted through attention, those after it) moves by
    several tenths. A wrong mask, head, scale or precision moves all of them.
    """
    import jax

    family_module = families.load(family)
    reference = families.reference_of(family_module)
    rng = np.random.default_rng([seed % (2 ** 63), 7])
    atol, rtol = float(tolerance["atol"]), float(tolerance["rtol"])
    need = float(tolerance.get("positions_within", 1.0))
    atol_any = float(tolerance.get("atol_any", np.inf))
    started = time.monotonic()
    engine_logits = family_module.engine_logits(engine, check)
    position_err: list[float] = []
    position_ok: list[bool] = []
    position_margin: list[float] = []
    per_prompt = []
    for length in check.prompt_lengths:
        prompt = [engine.tokenizer.bos_id] + rng.integers(
            32, 127, length - 1).tolist()
        forced = rng.integers(32, 127, check.decode_positions).tolist()
        got = engine_logits(prompt, forced)
        ref, margins = jax.device_get(reference.forward(
            engine.params, engine.model_config, prompt + forced,
            list(range(length - 1, length + check.decode_positions))))
        ref = np.asarray(ref, np.float32)
        if margins is not None:
            position_margin += [round(float(m), 4) for m in margins]
        if got.shape != ref.shape or not np.isfinite(got).all():
            raise AssertionError(f"engine logits {got.shape} vs reference "
                                 f"{ref.shape}, or not finite")
        err = np.abs(got - ref)
        position_err += [round(float(e), 4) for e in err.max(axis=-1)]
        position_ok += (err <= atol + rtol * np.abs(ref)).all(axis=-1).tolist()
        per_prompt.append({
            "tokens": length, "p99_abs_err": float(np.quantile(err, 0.99)),
            "ref_abs_max": float(np.abs(ref).max()),
            "argmax_agree": float((got.argmax(-1) == ref.argmax(-1)).mean())})
    within = float(np.mean(position_ok))
    return {"ok": bool(within >= need and max(position_err) <= atol_any),
            "atol": atol, "rtol": rtol, "positions_within": within,
            "positions_within_needed": need, "atol_any": atol_any,
            "max_abs_err": max(position_err),
            "position_max_abs_err": position_err,
            "position_routing_margin": position_margin, "per_prompt": per_prompt,
            "attn": getattr(engine_logits, "impl", None),
            "wall_s": round(time.monotonic() - started, 2)}


async def greedy_repeats(engine, seed: int, new_tokens: int = 8) -> dict[str, Any]:
    """The same prompt through the serving path twice gives the same tokens."""
    rng = np.random.default_rng([seed % (2 ** 63), 11])
    prompt = [engine.tokenizer.bos_id] + rng.integers(32, 127, 47).tolist()
    runs = [[t async for t in engine.generate(list(prompt), max_tokens=new_tokens)]
            for _ in range(2)]
    differing = sum(a != b for a, b in zip(*runs)) + abs(len(runs[0]) - len(runs[1]))
    return {"ok": len(runs[0]) >= 1 and differing == 0, "tokens": runs[0],
            "differing": differing}


def accounting(before: dict[str, int], after: dict[str, int], records,
               ) -> dict[str, Any]:
    """EngineStats against the client's counts, exactly. Only a window in
    which every request came back whole can be held to it."""
    sent = [r for r in records if not np.isnan(r.sent)]
    want = {"requests": len(sent),
            "prompt_tokens": sum(r.prompt_tokens for r in sent),
            "completion_tokens": sum(r.tokens for r in sent)}
    got = {k: after[k] - before[k] for k in want}
    whole = all(r.ok for r in records)
    return {"ok": got == want or not whole, "held": whole, "engine": got,
            "client": want}


def compared(logits: dict[str, Any], greedy: dict[str, Any], books: dict[str, Any],
             serving_compiles: int) -> list[str]:
    """Each number ``correct`` compares, beside its limit, one a line: the last
    lines a run leaves on standard error."""
    say = lambda ok: "ok" if ok else "NOT CORRECT"
    return [
        f"correct: logits_vs_reference positions_within {logits['positions_within']:.4f}"
        f" >= {logits['positions_within_needed']} (a position: every logit within "
        f"{logits['atol']} + {logits['rtol']} * |ref|), max_abs_err "
        f"{logits['max_abs_err']} <= atol_any {logits['atol_any']}: {say(logits['ok'])}",
        f"correct: greedy_repeats tokens differing between two runs "
        f"{greedy['differing']} <= 0, tokens a run {len(greedy['tokens'])} >= 1: "
        f"{say(greedy['ok'])}",
        f"correct: accounting engine {books['engine']} == client {books['client']}"
        f" (held to it: {books['held']}): {say(books['ok'])}",
        f"correct: serving_compiles {serving_compiles} <= 0: {say(serving_compiles == 0)}"]


def stats_snapshot(engine) -> dict[str, Any]:
    s = engine.stats
    return {k: getattr(s, k) for k in (
        "requests", "prompt_tokens", "completion_tokens", "decode_steps",
        "decode_dispatches", "prefill_batches", "prefill_requests",
        "prefill_ms_total", "dispatch_gap_ms_total", "overlap_steps",
        "pipeline_drains")}
