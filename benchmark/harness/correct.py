"""What decides ``correct``, outside the timed window: the engine's prefill
and decode logits against the plain reference, greedy repeatability, and
(after the window) exact token accounting and zero serving-stage compiles.

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

import time
from typing import Any

import numpy as np

CHECK_SEQ = 128          # the check's prefill length (one page, flash-able)
DECODE_POSITIONS = 8


class EngineLogits:
    """Last-position prefill logits, then one decode step per forced token
    through the paged cache — ``models.llama.prefill`` and ``decode_step``
    with the engine's own params, mesh and attention choice, on a scratch pool
    laid out like the engine's. Two programs, traced once for every prompt."""

    def __init__(self, engine) -> None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        from mcp_context_forge_tpu.tpu_local.kv import init_kv_state
        from mcp_context_forge_tpu.tpu_local.models.llama import decode_step, prefill
        from mcp_context_forge_tpu.tpu_local.ops.attention import (
            select_paged_attention, select_prefill_attention)

        cfg, econf, mesh = engine.model_config, engine.config, engine.mesh
        self.engine, self.page = engine, econf.page_size
        self.per_slot = (CHECK_SEQ + DECODE_POSITIONS + self.page - 1) // self.page
        self.impl = {
            "prefill": select_prefill_attention(
                econf.attn_impl, mesh, CHECK_SEQ, cfg.head_dim, cfg.n_kv_heads),
            "decode": select_paged_attention(
                mesh, cfg.head_dim, self.page, cfg.n_kv_heads, bool(econf.kv_quant))}
        slot = jnp.zeros((1,), jnp.int32)
        self._scratch = jax.jit(
            partial(init_kv_state, cfg, 1 + self.per_slot, self.page, 1,
                    self.per_slot, dtype=engine._kv_dtype, quant=econf.kv_quant),
            out_shardings=jax.tree.map(lambda a: a.sharding, engine.kv))
        self._prefill = jax.jit(lambda params, kv, tok, pos, last: prefill(
            params, cfg, tok, pos, kv, slot, attn_impl=self.impl["prefill"],
            mesh=mesh, last_idx=last))
        self._decode = jax.jit(lambda params, kv, tok, pos: decode_step(
            params, cfg, tok, pos, kv, slot, pos + 1, ctx_pages=self.per_slot,
            paged_impl=self.impl["decode"], mesh=mesh))

    def __call__(self, prompt: list[int], forced: list[int]) -> np.ndarray:
        """[1 + len(forced), V] float32."""
        import jax
        import jax.numpy as jnp

        engine, n = self.engine, len(prompt)
        if n > CHECK_SEQ:
            raise ValueError(f"check prompt of {n} tokens exceeds {CHECK_SEQ}")
        tokens = np.full((1, CHECK_SEQ), engine.tokenizer.pad_id, np.int32)
        tokens[0, :n] = prompt
        positions = np.full((1, CHECK_SEQ), -1, np.int32)
        positions[0, :n] = np.arange(n)
        with engine.mesh:
            scratch = self._scratch()
            scratch = scratch._replace(block_tables=jax.device_put(
                1 + np.arange(self.per_slot, dtype=np.int32)[None, :],
                scratch.block_tables.sharding))
            logits, scratch = self._prefill(
                engine.params, scratch, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray([n - 1], jnp.int32))
            rows = [np.asarray(logits, np.float32)[0]]
            for j, token in enumerate(forced):
                logits, scratch = self._decode(
                    engine.params, scratch, jnp.asarray([token], jnp.int32),
                    jnp.asarray([n + j], jnp.int32))
                rows.append(np.asarray(logits, np.float32)[0])
        for leaf in jax.tree.leaves(scratch):
            leaf.delete()
        return np.stack(rows)


def logits_check(engine, seed: int, tolerance: dict[str, float],
                 prompt_lengths: tuple[int, ...] = (96, 40)) -> dict[str, Any]:
    """Two prompts from ``seed`` (the configuration's ``check_seed``, not the
    run's: weights and check prompts are then the same in every run, so the
    tolerance was validated on exactly the comparison each run makes): engine
    logits (prefill's last position and
    ``DECODE_POSITIONS`` decode steps through the cache) against the
    reference's full forward over the same tokens.

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

    from ..reference import decoder

    rng = np.random.default_rng([seed % (2 ** 63), 7])
    atol, rtol = float(tolerance["atol"]), float(tolerance["rtol"])
    need = float(tolerance.get("positions_within", 1.0))
    atol_any = float(tolerance.get("atol_any", np.inf))
    started = time.monotonic()
    engine_logits = EngineLogits(engine)
    position_err: list[float] = []
    position_ok: list[bool] = []
    position_margin: list[float] = []
    per_prompt = []
    for length in prompt_lengths:
        prompt = [engine.tokenizer.bos_id] + rng.integers(
            32, 127, length - 1).tolist()
        forced = rng.integers(32, 127, DECODE_POSITIONS).tolist()
        got = engine_logits(prompt, forced)
        ref, margins = jax.device_get(decoder.forward(
            engine.params, engine.model_config, prompt + forced,
            list(range(length - 1, length + DECODE_POSITIONS))))
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
            "attn": engine_logits.impl,
            "wall_s": round(time.monotonic() - started, 2)}


async def greedy_repeats(engine, seed: int, new_tokens: int = 8) -> dict[str, Any]:
    """The same prompt through the serving path twice gives the same tokens."""
    rng = np.random.default_rng([seed % (2 ** 63), 11])
    prompt = [engine.tokenizer.bos_id] + rng.integers(32, 127, 47).tolist()
    runs = [[t async for t in engine.generate(list(prompt), max_tokens=new_tokens)]
            for _ in range(2)]
    return {"ok": len(runs[0]) >= 1 and runs[0] == runs[1], "tokens": runs[0]}


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


def stats_snapshot(engine) -> dict[str, Any]:
    s = engine.stats
    return {k: getattr(s, k) for k in (
        "requests", "prompt_tokens", "completion_tokens", "decode_steps",
        "decode_dispatches", "prefill_batches", "prefill_requests",
        "prefill_ms_total", "dispatch_gap_ms_total", "overlap_steps",
        "pipeline_drains")}
