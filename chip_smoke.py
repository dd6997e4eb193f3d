#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             # one TPU chip: gateway -> engine, end to end
    python3 chip_smoke.py --chips 4   # four chips: TP engine + replica pool only

One process (a chip belongs to one process). It fails at once, before
building anything, unless ``jax.devices()[0].platform`` is ``tpu`` — no
probe child, no watchdog that turns into a CPU run. Then, with no arguments:

1. on-chip parity of every Pallas kernel on the serving path (paged decode
   bf16 and int8, paged chunk, flash prefill) against its ``jax.numpy``
   reference at the model's head geometry, and the paged kernel's time
   alone at the benchmark cells' three shapes (``timing``: microseconds a
   call and a live page);
2. the gateway app built in-process exactly as ``cli serve`` builds it
   (``get_settings`` -> ``install_event_loop`` -> ``build_app``), bound to
   a real socket on 127.0.0.1 and driven by an HTTP client: ``/health``,
   chat (non-streamed, streamed, a concurrent burst on a shared page-aligned
   prefix), ``/v1/embeddings``, ``/v1/moderations``;
3. checks by the repo's own means: token accounting from ``EngineStats``,
   greedy determinism, zero serving-stage compiles on the warmed engine
   (``compile_events``), the kernel present in every compiled step
   (``tpu_custom_call`` in the compiled text, via the engine's cost
   registry), finite logits.

Every phase prints one JSON object on its own line — facts of a smoke run
(wall and compile seconds, which attention implementation each step traced,
peak device bytes, where the compile cache lives and whether it was warm),
NOT benchmark numbers. Any failed check raises; nothing is caught and
carried past. The LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with ``"ok": false`` and a non-zero exit code on any failure.

Model and sizes (``ENGINE_ENV`` below): ``mistral-7b`` at its published
widths and all 32 layers, int8 weights (~7.2 GB), bf16 KV, random weights
from the engine's fixed seed, the in-tree byte-level tokenizer; encoder
``encoder-mini``. Nothing is read that git would not commit, and nothing
off the machine.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import re
import sys
import time
import traceback
from typing import Any

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODEL = "mistral-7b"
ENCODER = "encoder-mini"
NEW_TOKENS = 32

# The engine, sized for ONE v5e chip (16 GB): int8 weights ~7.2 GB + a
# 384-page bf16 KV pool (384 x 16.8 MB = 6.4 GB, 49k tokens) + <0.3 GB of
# step workspace (compiled for a described v5e: 12.8-13.0 GB per step
# program). 16 decode slots x 1024 tokens. One prefill bucket keeps the
# cold compile set small and stated — 13 step programs: prefill@128 and
# history-prefill@128 x ctx{4,8} pages, each at B in {1,2,4}; decode and
# its feedback twin x ctx{4,8} pages at B=16.
ENGINE_ENV = {
    "MCPFORGE_TPU_LOCAL_ENABLED": "true",
    "MCPFORGE_TPU_LOCAL_MODEL": MODEL,
    "MCPFORGE_TPU_LOCAL_QUANT": "int8",
    "MCPFORGE_TPU_LOCAL_KV_QUANT": "",
    "MCPFORGE_TPU_LOCAL_DTYPE": "bfloat16",
    "MCPFORGE_TPU_LOCAL_MAX_BATCH": "16",
    "MCPFORGE_TPU_LOCAL_MAX_SEQ_LEN": "1024",
    "MCPFORGE_TPU_LOCAL_PAGE_SIZE": "128",
    "MCPFORGE_TPU_LOCAL_NUM_PAGES": "384",
    "MCPFORGE_TPU_LOCAL_PREFILL_BUCKETS": "128",
    "MCPFORGE_TPU_LOCAL_PREFILL_MAX_BATCH": "4",
    "MCPFORGE_TPU_LOCAL_WARMUP": "true",
    "MCPFORGE_TPU_LOCAL_WARMUP_MODE": "full",
    "MCPFORGE_TPU_LOCAL_EMBEDDING_MODEL": ENCODER,
    "MCPFORGE_TPU_LOCAL_ENCODER_MAX_BATCH": "4",
}
GATEWAY_ENV = {
    "MCPFORGE_DATABASE_URL": "sqlite:///:memory:",
    "MCPFORGE_BUS_BACKEND": "memory",
    "MCPFORGE_OTEL_EXPORTER": "none",
    "MCPFORGE_LOG_LEVEL": "WARNING",
    "MCPFORGE_GATEWAY_HEALTH_INTERVAL": "3600",
}

# kernel vs reference, both with bf16 outputs: bf16 keeps 8 bits of
# mantissa (relative step 2^-8 = 0.4%), the kernels round the softmax
# weights to bf16 before P.V and the result once more; |out| <= ~3 on unit
# normal V. A wrong head, mask, page or scale is off by O(1).
PARITY_ATOL = 2e-2
PARITY_RTOL = 2e-2
# a 1x4 TP engine sums each row-parallel matmul in four bf16 partials where
# one chip sums once, so 64 residual adds each carry a different last bf16
# bit (2^-8 relative): a few percent of logits of magnitude ~1-4. A wrong
# shard-to-head mapping gives unrelated logits, off by several units.
TP_LOGITS_ATOL = 0.25

SHARED_SYSTEM = (
    "You are the gateway's tool-routing assistant. Answer briefly, name the "
    "MCP tool you would call and its arguments as JSON, and never invent a "
    "tool that the catalog below does not list. Catalog: search_documents("
    "query, top_k), get_weather(city, units), create_ticket(title, body, "
    "priority), summarize(text, max_words), translate(text, target_lang).")


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def emit(phase: str, **facts: Any) -> None:
    print(json.dumps({"phase": phase, **facts}, default=str), flush=True)


# ------------------------------------------------------------------ bookkeeping

class CompileMeter:
    """Process-wide XLA compile count/seconds and persistent-cache hits
    (``jax.monitoring``), read as deltas around a phase."""

    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += float(duration)

    def _on_event(self, event: str, **_kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits

    def since(self, before: tuple[int, float, int]) -> dict[str, Any]:
        return {"compiles": self.count - before[0],
                "compile_s": round(self.seconds - before[1], 3),
                "cache_hits": self.cache_hits - before[2]}


def device_facts() -> dict[str, Any]:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(devices=None) -> list[int | None]:
    """``memory_stats()["peak_bytes_in_use"]`` per device (None where the
    backend keeps no stats — the CPU)."""
    import jax

    out = []
    for device in devices or jax.devices():
        stats = device.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def cache_facts() -> dict[str, Any]:
    """Where the persistent compile cache lives (engine.apply_compile_cache's
    rule) and whether it held entries when this process started."""
    from mcp_context_forge_tpu.tpu_local.engine import (COMPILE_CACHE_ENV,
                                                        apply_compile_cache)

    path = apply_compile_cache()
    entries = len(os.listdir(path)) if path and os.path.isdir(path) else 0
    return {"cache_dir": path,
            "cache_dir_from": (COMPILE_CACHE_ENV if os.environ.get(
                COMPILE_CACHE_ENV) else "checkout"),
            "cache_entries_at_start": entries, "cache_warm": entries > 0}


# --------------------------------------------------------------- kernel parity

def phase_kernel_parity(model: str = MODEL, page_size: int = 128,
                        interpret: bool = False) -> dict[str, Any]:
    """Each Pallas kernel on the serving path against its jax.numpy
    reference (models/llama.py, ops/attention.py) at ``model``'s head
    geometry, on seeded random inputs in bf16. ``interpret`` is for the CPU
    rehearsal only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcp_context_forge_tpu.tpu_local.kv import PagedKVState, gather_kv
    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS
    from mcp_context_forge_tpu.tpu_local.models.llama import (
        _history_attention, _paged_decode_attention)
    from mcp_context_forge_tpu.tpu_local.ops.attention import (
        attention_reference, flash_attention_pallas)
    from mcp_context_forge_tpu.tpu_local.ops.paged_attention import (
        paged_chunk_attention_pallas, paged_decode_attention_pallas)

    cfg = MODEL_CONFIGS[model]
    KV, H, hd = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    G = H // KV
    L, B, per_slot = 2, 3, 4
    layer = 1                       # the layer index rides the index map
    n_pages = 1 + B * per_slot      # page 0 is the trash page
    S = page_size                   # chunk / flash query length
    dt = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(21), 16))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dt)

    tables = jnp.asarray(
        1 + np.arange(B * per_slot, dtype=np.int32).reshape(B, per_slot))
    pool_shape = (L, n_pages, page_size, KV, hd)
    kv = PagedKVState(normal(pool_shape), normal(pool_shape), tables)
    k8 = jax.random.randint(next(keys), pool_shape, -127, 128, jnp.int8)
    v8 = jax.random.randint(next(keys), pool_shape, -127, 128, jnp.int8)
    scales = [(0.002 + 0.01 * jax.random.uniform(
        next(keys), (L, n_pages, KV))).astype(dt) for _ in range(2)]
    kv8 = PagedKVState(k8, v8, tables, *scales)
    slots = jnp.arange(B)
    ctx = per_slot * page_size
    # a full context, one that ends mid-page, and a one-token context
    seq_lens = jnp.asarray([ctx, 2 * page_size + page_size // 3 + 1, 1],
                           jnp.int32)
    q_dec = normal((B, KV, G, hd))
    # chunk rows: a row deep in its history, a row from position 0, and a
    # row that is half padding
    starts = np.asarray([ctx - S, 0, page_size + 5])
    lens = np.asarray([S, S, S // 2])
    positions = np.where(np.arange(S)[None, :] < lens[:, None],
                         starts[:, None] + np.arange(S)[None, :], -1)
    positions = jnp.asarray(positions, jnp.int32)
    q_chunk = normal((B, S, H, hd))
    S_flash = 2 * S
    q_f, k_f, v_f = (normal((2, S_flash, H, hd)), normal((2, S_flash, KV, hd)),
                     normal((2, S_flash, KV, hd)))
    valid_f = jnp.ones((2, S_flash), bool).at[1, S_flash - S // 2:].set(False)

    # (kernel, reference) thunks. Only the REFERENCES run under "highest"
    # matmul precision (true f32 on the MXU): inside a kernel it would ask
    # Mosaic for an fp32-precision matmul of bf16 operands, which it refuses.
    def decode_pair(state):
        def kernel():
            return paged_decode_attention_pallas(
                q_dec, state.k_pages, state.v_pages, tables, seq_lens,
                layer=layer, interpret=interpret, k_scales=state.k_scales,
                v_scales=state.v_scales).reshape(B, H, hd)

        def reference():
            keys_g, values_g = gather_kv(state, layer, slots)
            return _paged_decode_attention(
                q_dec.reshape(B, H, hd), keys_g, values_g, seq_lens,
                cfg).reshape(B, H, hd)
        return kernel, reference

    live = (positions >= 0)[:, :, None, None]   # padding rows: garbage

    def chunk_kernel():
        out = paged_chunk_attention_pallas(
            q_chunk.reshape(B, S, KV, G, hd), kv.k_pages, kv.v_pages, tables,
            positions, layer=layer, interpret=interpret)
        return jnp.where(live, out.reshape(B, S, H, hd), 0)

    def chunk_reference():
        keys_g, values_g = gather_kv(kv, layer, slots)
        return jnp.where(live, _history_attention(
            q_chunk, keys_g, values_g, jnp.maximum(positions, 0),
            positions >= 0, cfg), 0)

    pairs = {
        "paged_decode_bf16": decode_pair(kv),
        "paged_decode_int8": decode_pair(kv8),
        "paged_chunk_bf16": (chunk_kernel, chunk_reference),
        "flash_prefill": (
            lambda: flash_attention_pallas(q_f, k_f, v_f, valid_f,
                                           interpret=interpret),
            lambda: attention_reference(q_f, k_f, v_f, valid_f)),
    }
    results = {}
    for name, (kernel, reference) in pairs.items():
        out = np.asarray(kernel(), np.float32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(reference(), np.float32)
        check(out.shape == ref.shape and np.isfinite(out).all(),
              f"{name}: kernel output not finite / wrong shape")
        excess = np.abs(out - ref) - (PARITY_ATOL + PARITY_RTOL * np.abs(ref))
        results[name] = {"max_abs_err": float(np.abs(out - ref).max()),
                         "ref_abs_max": float(np.abs(ref).max())}
        check(float(excess.max()) <= 0.0,
              f"{name}: kernel differs from its reference by "
              f"{results[name]['max_abs_err']:.4f} "
              f"(atol {PARITY_ATOL}, rtol {PARITY_RTOL})")
    return {"geometry": {"kv_heads": KV, "group": G, "head_dim": hd,
                         "page_size": page_size},
            "atol": PARITY_ATOL, "rtol": PARITY_RTOL, "kernels": results,
            "timing": time_paged_kernel(KV, G, hd, page_size, interpret)}


# the benchmark cells' kernel shapes: (slots, block-table width, queries per
# row or None for decode, live rows, live context range in tokens)
TIMED_SHAPES = {
    "decode_32x8": (32, 8, None, 12, (64, 712)),       # mistral-7b.chat
    "decode_8x32": (8, 32, None, 6, (1024, 4048)),     # docs-closed decode
    "chunk_2x512x32": (2, 32, 512, 2, (1024, 4000)),   # docs-closed chunk tile
}


def time_paged_kernel(KV: int, G: int, hd: int, page_size: int,
                      interpret: bool = False) -> dict[str, Any]:
    """The paged kernel ALONE at ``TIMED_SHAPES``, bf16 pages: microseconds
    a call and a live page. 32 kernel calls (a layer each) run in series
    in one jitted program, as in a step; the host clock around it ends in
    ``block_until_ready``, the fastest of 10 counts. Contexts are drawn
    log-uniform; idle slots and block-table entries past a row's pages are
    0 like the engine's, so their grid steps fetch nothing; a grid step
    holds ``block_pages`` table pages. Under
    ``interpret`` (the CPU rehearsal) the shapes shrink and the numbers say
    nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcp_context_forge_tpu.tpu_local.ops.paged_attention import (
        _ROW_BLOCK, _kv_block_pages, paged_chunk_attention_pallas,
        paged_decode_attention_pallas)

    L = 4
    calls, reps = (2, 1) if interpret else (32, 10)
    rng = np.random.default_rng(25)
    key = jax.random.PRNGKey(25)
    out = {}
    for name, (B, table, chunk, live_rows, (lo, hi)) in TIMED_SHAPES.items():
        if interpret:
            B, table, chunk = min(B, 2), 4, chunk and 2 * page_size
            live_rows, lo, hi = min(live_rows, B), page_size, table * page_size
        n_pages = 1 + B * table
        pool = (L, n_pages, page_size, KV, hd)
        k_pages, v_pages = (jax.random.normal(
            jax.random.fold_in(key, i), pool, jnp.float32).astype(jnp.bfloat16)
            for i in range(2))
        lens = np.zeros(B, int)
        lens[rng.choice(B, live_rows, replace=False)] = np.minimum(
            np.exp(rng.uniform(np.log(lo), np.log(hi), live_rows)).astype(int),
            table * page_size)
        held = -(-lens // page_size)                    # pages a row holds
        tables = 1 + np.arange(B * table).reshape(B, table)
        tables = jnp.asarray(
            np.where(np.arange(table)[None] < held[:, None], tables, 0),
            jnp.int32)
        if chunk is None:
            q = jax.random.normal(key, (B, KV, G, hd), jnp.float32)
            extra = jnp.asarray(lens, jnp.int32)
            rows = G
            kernel, live_pages, row_blocks = (
                paged_decode_attention_pallas, int(held.sum()), 1)
        else:
            # a tile of ``chunk`` queries somewhere inside each prompt
            start = np.array([rng.integers(0, max(1, n // chunk)) * chunk
                              for n in lens])
            pos = start[:, None] + np.arange(chunk)[None]
            pos = np.where(pos < lens[:, None], pos, -1)
            q = jax.random.normal(key, (B, chunk, KV, G, hd), jnp.float32)
            extra = jnp.asarray(pos, jnp.int32)
            rows = min(chunk * G, _ROW_BLOCK)
            top = np.repeat(pos, G, axis=1).reshape(B, -1, rows).max(axis=2)
            kernel, live_pages, row_blocks = (
                paged_chunk_attention_pallas,
                int(np.where(top >= 0, top // page_size + 1, 0).sum()),
                top.shape[1])
        q = q.astype(jnp.bfloat16)

        @jax.jit
        def series(q, k_pages, v_pages):
            for i in range(calls):
                res = kernel(q, k_pages, v_pages, tables, extra,
                             layer=i % L, interpret=interpret)
                q = q + (res * 1e-3).astype(q.dtype)    # in series
            return q

        series(q, k_pages, v_pages).block_until_ready()
        seconds = []
        for _ in range(reps):
            t0 = time.perf_counter()
            series(q, k_pages, v_pages).block_until_ready()
            seconds.append(time.perf_counter() - t0)
        us = min(seconds) / calls * 1e6
        block_pages = _kv_block_pages(table, rows, page_size)
        out[name] = {"us_per_call": round(us, 2),
                     "us_per_live_page": round(us / max(1, live_pages), 3),
                     "live_pages": live_pages,
                     "block_pages": block_pages,
                     "grid_steps": B * row_blocks * table // block_pages}
    return out


# --------------------------------------------------------------------- helpers

def chat_ids(engine, messages: list[dict]) -> list[int]:
    from mcp_context_forge_tpu.tpu_local.tokenizer import render_chat

    return engine.tokenizer.encode(render_chat(messages))


async def greedy_tokens(engine, prompt_ids: list[int], n: int) -> list[int]:
    return [t async for t in engine.generate(list(prompt_ids), max_tokens=n)]


def first_step_logits(engine, prompt_ids: list[int]):
    """Next-token logits after ``prompt_ids`` through models.llama.prefill
    with the engine's own params, mesh and attention choice, on a scratch
    KV pool of two pages laid out like the engine's -> np.ndarray [V]."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcp_context_forge_tpu.tpu_local.kv import init_kv_state
    from mcp_context_forge_tpu.tpu_local.models.llama import prefill
    from mcp_context_forge_tpu.tpu_local.ops.attention import (
        select_prefill_attention)

    cfg, econf = engine.model_config, engine.config
    page = econf.page_size
    S = min(econf.prefill_buckets)
    n = len(prompt_ids)
    check(n <= S, f"logits prompt of {n} tokens exceeds the {S} bucket")
    impl = select_prefill_attention(
        econf.attn_impl, engine.mesh, S, cfg.head_dim, cfg.n_kv_heads)
    tokens = np.full((1, S), engine.tokenizer.pad_id, np.int32)
    tokens[0, :n] = prompt_ids
    positions = np.full((1, S), -1, np.int32)
    positions[0, :n] = np.arange(n)
    per_slot = S // page
    with engine.mesh:
        scratch = jax.jit(
            partial(init_kv_state, cfg, 1 + per_slot, page, 1, per_slot,
                    dtype=engine._kv_dtype, quant=econf.kv_quant),
            out_shardings=jax.tree.map(lambda a: a.sharding, engine.kv))()
        scratch = scratch._replace(block_tables=jax.device_put(
            1 + np.arange(per_slot, dtype=np.int32)[None, :],
            scratch.block_tables.sharding))
        step = jax.jit(lambda params, kv, tok, pos: prefill(
            params, cfg, tok, pos, kv, jnp.zeros((1,), jnp.int32),
            attn_impl=impl, mesh=engine.mesh,
            last_idx=jnp.asarray([n - 1]))[0])
        logits = step(engine.params, scratch, jnp.asarray(tokens),
                      jnp.asarray(positions))
    return np.asarray(logits, np.float32)[0]


def engine_step_facts(engine, expect_kernels: bool) -> dict[str, Any]:
    """What each compiled step family of a WARMED engine runs: the
    attention implementation its trace chose, and how many Pallas kernel
    calls its compiled text holds (cost registry). With ``expect_kernels``
    a step on the XLA path — a silent fallback — fails the run."""
    kernel_calls = {
        kind: min(entry["kernel_calls"] for entry in table.values())
        for kind, table in engine.cost_registry.snapshot().items()}
    traced = dict(engine.attn_traced)
    if expect_kernels:
        for kind in ("prefill", "prefill_hist", "decode", "decode_fb"):
            check(kernel_calls.get(kind, 0) >= engine.model_config.n_layers,
                  f"compiled {kind} step holds {kernel_calls.get(kind, 0)} "
                  f"tpu_custom_call(s): the Pallas kernel is missing")
        for step, impl in traced.items():
            check(impl == "pallas", f"step {step!r} traced {impl!r}, "
                                    "not the Pallas kernel")
    return {"attn_traced": traced, "kernel_calls": kernel_calls}


# --------------------------------------------------------------- gateway phase

async def phase_gateway(env: dict[str, str], meter: CompileMeter,
                        expect_kernels: bool,
                        new_tokens: int = NEW_TOKENS) -> None:
    """Build the gateway as ``cli serve`` does, bind 127.0.0.1, drive it
    over HTTP, check, tear down. Emits one JSON line per sub-phase."""
    import aiohttp
    from aiohttp import web

    from mcp_context_forge_tpu.config import (get_settings,
                                              reset_settings_cache)
    from mcp_context_forge_tpu.gateway.app import (build_app,
                                                   install_event_loop)
    from mcp_context_forge_tpu.utils import jwt

    os.environ.update(env)
    reset_settings_cache()
    settings = get_settings()
    install_event_loop(settings.gw_event_loop)

    before, started = meter.snapshot(), time.monotonic()
    app = await build_app(settings)
    engine = app["tpu_engine"]
    tracker = engine.compile_tracker.snapshot()
    emit("build", wall_s=round(time.monotonic() - started, 1),
         **meter.since(before), model=engine.config.model,
         layers=engine.model_config.n_layers, quant=engine.config.quant,
         kv_pages=engine.num_kv_pages, max_batch=engine.config.max_batch,
         max_seq_len=engine.config.max_seq_len,
         prefill_buckets=list(engine.config.prefill_buckets),
         engine_warmup_compiles=tracker["warmup"]["count"],
         engine_warmup_compile_s=round(tracker["warmup"]["ms_total"] / 1e3, 1),
         **engine_step_facts(engine, expect_kernels),
         peak_bytes=peak_bytes())

    runner = web.AppRunner(app)
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        host, port = runner.addresses[0][:2]
        token = jwt.create_token(
            {"sub": settings.platform_admin_email}, settings.jwt_secret_key,
            settings.jwt_algorithm, expires_minutes=30,
            audience=settings.jwt_audience, issuer=settings.jwt_issuer)
        async with aiohttp.ClientSession(
                base_url=f"http://{host}:{port}",
                headers={"Authorization": f"Bearer {token}"},
                timeout=aiohttp.ClientTimeout(total=600)) as http:
            await _drive(http, app, meter, new_tokens)
        serving = engine.compile_tracker.serving_compiles()
        check(serving == 0, f"{serving} serving-stage XLA compile(s) on a "
                            "warmed engine")
        emit("serving_compiles", count=serving, peak_bytes=peak_bytes())
    finally:
        await runner.cleanup()      # stops the engine's dispatch thread


async def _drive(http, app, meter: CompileMeter, new_tokens: int) -> None:
    import numpy as np

    engine = app["tpu_engine"]
    stats = engine.stats
    model = engine.config.model

    async def chat(messages, stream=False):
        body = {"model": model, "messages": messages,
                "max_tokens": new_tokens, "temperature": 0.0,
                "stream": stream}
        async with http.post("/v1/chat/completions", json=body) as resp:
            if resp.status != 200:
                raise SmokeFailure(f"chat answered {resp.status}: "
                                   f"{(await resp.text())[:300]}")
            if not stream:
                return await resp.json()
            events = [line[len(b"data: "):].strip()
                      async for line in resp.content
                      if line.startswith(b"data: ")]
            check(bool(events) and events[-1] == b"[DONE]",
                  f"stream did not end with [DONE]: {events[-2:]}")
            return [json.loads(e) for e in events[:-1]]

    def took(name: str, started: float, before, tokens0: int, asked: int,
             **facts: Any) -> None:
        made = stats.completion_tokens - tokens0
        check(made == asked, f"{name}: engine emitted {made} tokens, "
                             f"{asked} were asked for")
        emit(name, wall_s=round(time.monotonic() - started, 3),
             **meter.since(before), new_tokens=made, **facts)

    # ---- /health
    started = time.monotonic()
    async with http.get("/health") as resp:
        check(resp.status == 200, f"/health answered {resp.status}")
    emit("health", wall_s=round(time.monotonic() - started, 3), status=200)

    # ---- non-streamed chat, twice: same greedy prompt, same answer
    question = [{"role": "user", "content":
                 "Which MCP tool lists the files of a repository?"}]
    started, before, tokens0 = (time.monotonic(), meter.snapshot(),
                                stats.completion_tokens)
    answers = [await chat(question) for _ in range(2)]
    for answer in answers:
        check(answer["usage"]["completion_tokens"] == new_tokens
              and answer["choices"][0]["finish_reason"] == "length",
              f"chat returned {answer['usage']} / "
              f"{answer['choices'][0]['finish_reason']}, asked {new_tokens}")
    check(answers[0]["choices"][0]["message"]
          == answers[1]["choices"][0]["message"],
          "the same greedy prompt gave two different answers over HTTP")
    took("chat", started, before, tokens0, 2 * new_tokens, requests=2)

    # token ids do not cross the HTTP surface (and random weights mostly
    # sample ids the byte tokenizer cannot render), so the determinism
    # check proper reads them from the engine's own generate()
    started, before, tokens0 = (time.monotonic(), meter.snapshot(),
                                stats.completion_tokens)
    ids = chat_ids(engine, question)
    runs = [await greedy_tokens(engine, ids, new_tokens) for _ in range(2)]
    check(len(runs[0]) == new_tokens and runs[0] == runs[1],
          f"greedy decoding is not deterministic: {runs}")
    took("greedy_determinism", started, before, tokens0, 2 * new_tokens,
         tokens_head=runs[0][:8])

    # ---- streamed chat
    started, before, tokens0 = (time.monotonic(), meter.snapshot(),
                                stats.completion_tokens)
    chunks = await chat([{"role": "user", "content":
                          "Stream me a haiku about paged attention."}],
                        stream=True)
    check(not any("error" in c for c in chunks),
          f"stream carried an error event: {chunks[-1]}")
    finish = chunks[-1]["choices"][0]["finish_reason"]
    check(finish == "length", f"stream finished {finish!r}, not 'length'")
    took("chat_stream", started, before, tokens0, new_tokens,
         chunks=len(chunks))

    # ---- a burst on a shared, page-aligned prefix. The primer's prompt
    # exceeds the one prefill bucket, so it prefills in chunks through the
    # history path and registers the shared pages; the burst's eight
    # sharers then hit them and prefill only their suffix
    # (_prefill_hist_and_sample), while four short unshared chats take
    # the dense prefill — all twelve decode in one batch.
    def shared(i: int) -> list[dict]:
        return [{"role": "system", "content": SHARED_SYSTEM},
                {"role": "user", "content":
                 f"Request {i}: route 'weather in city number {i}'."}]

    page = engine.config.page_size
    shared_ids = [chat_ids(engine, shared(i)) for i in range(9)]
    common = os.path.commonprefix(shared_ids)
    check(len(common) >= 2 * page, f"shared prefix of {len(common)} tokens "
                                   f"does not cover two {page}-token pages")
    started, before, tokens0 = (time.monotonic(), meter.snapshot(),
                                stats.completion_tokens)
    await chat(shared(0))
    took("prefix_primer", started, before, tokens0, new_tokens,
         prompt_tokens=len(shared_ids[0]))

    started, before, tokens0 = (time.monotonic(), meter.snapshot(),
                                stats.completion_tokens)
    hits0 = engine.allocator.prefix_hit_tokens
    seq0 = max((s["seq"] for s in engine.recent_steps()), default=0)
    burst = [shared(i) for i in range(1, 9)] + [
        [{"role": "user", "content": f"Unshared short chat number {i}."}]
        for i in range(4)]
    answers = await asyncio.gather(*[chat(m) for m in burst])
    for answer in answers:
        check(answer["usage"]["completion_tokens"] == new_tokens,
              f"burst chat returned {answer['usage']}, asked {new_tokens}")
    steps = [s for s in engine.recent_steps() if s["seq"] > seq0]
    prefill_widths = [s["batch"] for s in steps if "prefill" in s["kind"]]
    decode_widths = [s["batch"] for s in steps if s["kind"] == "decode"]
    hit_tokens = engine.allocator.prefix_hit_tokens - hits0
    check(hit_tokens >= 8 * 2 * page,
          f"prefix cache served {hit_tokens} tokens to eight sharers of a "
          f"{2 * page}-token prefix")
    check(max(prefill_widths, default=0) >= 2,
          f"no fused prefill batch in the burst: {prefill_widths}")
    check(max(decode_widths, default=0) >= 8,
          f"decode never batched the burst: max {max(decode_widths, default=0)}")
    took("burst", started, before, tokens0, len(burst) * new_tokens,
         requests=len(burst), prefix_hit_tokens=hit_tokens,
         max_prefill_batch=max(prefill_widths),
         max_decode_batch=max(decode_widths),
         attn_traced=dict(engine.attn_traced))

    # ---- logits of the first step: finite, the vocabulary's width, and
    # (reported, not required: a near-tie may round either way) the same
    # argmax the serving path sampled
    started, before = time.monotonic(), meter.snapshot()
    logits = first_step_logits(engine, ids)
    check(logits.shape == (engine.model_config.vocab_size,)
          and np.isfinite(logits).all(), "first-step logits are not finite")
    emit("logits", wall_s=round(time.monotonic() - started, 3),
         **meter.since(before), vocab=int(logits.shape[0]),
         abs_max=float(np.abs(logits).max()),
         argmax_is_served_token=bool(int(logits.argmax()) == runs[0][0]))

    # ---- encoder path
    enc = app["tpu_provider"].encoder_config
    started, before = time.monotonic(), meter.snapshot()
    texts = ["paged attention on a tensor processing unit",
             "a gateway that federates model context protocol servers"]
    async with http.post("/v1/embeddings",
                         json={"model": model, "input": texts}) as resp:
        check(resp.status == 200, f"/v1/embeddings answered {resp.status}")
        vectors = np.asarray([row["embedding"]
                              for row in (await resp.json())["data"]])
    check(vectors.shape == (len(texts), enc.dim)
          and np.isfinite(vectors).all()
          and not np.allclose(vectors[0], vectors[1]),
          f"embeddings wrong: shape {vectors.shape}")
    emit("embeddings", wall_s=round(time.monotonic() - started, 3),
         **meter.since(before), encoder=enc.name, dim=int(vectors.shape[1]))

    started, before = time.monotonic(), meter.snapshot()
    async with http.post("/v1/moderations", json={
            "input": "please summarize this harmless tool output"}) as resp:
        check(resp.status == 200, f"/v1/moderations answered {resp.status}")
        score = (await resp.json())["results"][0]["category_scores"]["harmful"]
    check(0.0 <= score <= 1.0, f"moderation score {score} is no probability")
    emit("moderations", wall_s=round(time.monotonic() - started, 3),
         **meter.since(before), encoder=enc.name)


# ---------------------------------------------------------------- four chips

def _engine_config(**overrides: Any):
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig

    # as ENGINE_ENV, cut to what these phases run: a 128-page pool (2.1 GB),
    # one 128-token prefill bucket, contexts of 512, no warmup grid — each
    # engine compiles prefill@128 B=1 and decode (+feedback) at ctx 4 pages
    base = dict(model=MODEL, quant="int8", dtype="bfloat16", max_batch=8,
                max_seq_len=512, page_size=128, num_pages=128,
                prefill_buckets=(128,), prefill_max_batch=1,
                cost_analysis=False)
    return EngineConfig(**{**base, **overrides})


async def phase_tp_engine(meter: CompileMeter, model: str = MODEL,
                          expect_kernels: bool = True, new_tokens: int = 16,
                          devices: list | None = None) -> None:
    """A 1xN TP engine over every device against a one-device engine of the
    same config on ``devices[:1]``: same seed, same prompts."""
    import jax
    import numpy as np

    from mcp_context_forge_tpu.tpu_local.engine import TPUEngine
    from mcp_context_forge_tpu.tpu_local.quantize import param_bytes

    devices = devices or jax.devices()
    prompts = [[{"role": "user", "content": text}] for text in (
        "Which MCP tool lists the files of a repository?",
        "Name three uses of a KV cache.")]

    async def run(engine):
        await engine.start()
        try:
            ids = [chat_ids(engine, p) for p in prompts]
            logits = [first_step_logits(engine, i) for i in ids]
            tokens = [await greedy_tokens(engine, i, new_tokens) for i in ids]
        finally:
            await engine.stop()
        for row in logits:
            check(np.isfinite(row).all(), "TP logits are not finite")
        return logits, tokens

    # ---- the sharded engine: placement first, while it is alone on the host
    started, before = time.monotonic(), meter.snapshot()
    tp = TPUEngine(_engine_config(model=model), devices=devices)
    expected = param_bytes(tp.params) + param_bytes(tp.kv)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if all(b is not None for b in in_use):      # the CPU keeps no stats
        share = expected / len(devices)
        check(min(in_use) > 0.7 * share and max(in_use) < 1.3 * share,
              f"params + KV ({expected} bytes) are not spread a quarter "
              f"each: {in_use}")
    tp_logits, tp_tokens = await run(tp)
    text = tp._decode_fn(tp._ctx_buckets()[0]).lower(
        *_decode_example_args(tp)).compile().as_text()
    kernels = text.count("tpu_custom_call")
    layer_pool = int(np.prod(tp.kv.k_pages.shape[1:]))   # one layer's K
    gathered = [int(np.prod([int(x) for x in dims.split(",")]))
                for dims in _all_gather_shapes(text)]
    check(max(gathered, default=0) < layer_pool,
          f"the compiled decode step all-gathers {max(gathered, default=0)} "
          f"elements (one layer's K pool is {layer_pool}): the KV pool is "
          "being gathered")
    if expect_kernels:
        check(kernels >= tp.model_config.n_layers,
              f"sharded decode step holds {kernels} kernel call(s)")
        check(tp.attn_traced.get("decode") == "pallas",
              f"sharded decode traced {tp.attn_traced}")
    emit("tp_engine", mesh=dict(tp.mesh.shape), devices=len(devices),
         wall_s=round(time.monotonic() - started, 1), **meter.since(before),
         bytes_in_use=in_use, expected_bytes=expected,
         kernel_calls_per_shard=kernels, all_gather_elems=gathered,
         attn_traced=dict(tp.attn_traced), peak_bytes=peak_bytes(devices))
    _release(tp)

    # ---- the same engine on one device
    started, before = time.monotonic(), meter.snapshot()
    one = TPUEngine(_engine_config(model=model), devices=devices[:1])
    one_logits, one_tokens = await run(one)
    _release(one)
    diffs = [float(np.abs(a - b).max()) for a, b in zip(tp_logits, one_logits)]
    check(max(diffs) <= TP_LOGITS_ATOL,
          f"first-step logits differ by {max(diffs)} between the sharded "
          f"and the one-device engine (atol {TP_LOGITS_ATOL})")
    agree = [sum(a == b for a, b in zip(x, y)) / new_tokens
             for x, y in zip(tp_tokens, one_tokens)]
    emit("tp_vs_one_device", wall_s=round(time.monotonic() - started, 1),
         **meter.since(before), logits_max_abs_diff=diffs,
         atol=TP_LOGITS_ATOL, greedy_agreement_first_tokens=agree,
         tokens=new_tokens)


def _release(engine) -> None:
    """Free a stopped engine's params and KV pool on the device now: the
    next phase's engines need the room, and waiting for the collector to
    find every cycle through a jitted bound method is a hope, not a plan."""
    import jax

    for array in jax.tree.leaves((engine.params, engine.kv)):
        array.delete()
    gc.collect()


def _decode_example_args(engine):
    """Arguments of the shapes (and shardings) the engine's decode step
    was compiled for, to look its executable up again: params, kv, one packed
    call of idle rows and the base key."""
    return (engine.params, engine.kv,
            engine._idle_call(engine._decode_call, engine.config.max_batch),
            engine._rng)


def _all_gather_shapes(hlo_text: str) -> list[str]:
    return re.findall(r"= \w+\[([\d,]+)\][^ ]* all-gather\(", hlo_text)


async def phase_replica_pool(meter: CompileMeter, model: str = MODEL,
                             new_tokens: int = 8,
                             devices: list | None = None) -> None:
    """One one-chip replica per device behind the pool router: every
    replica's mesh on a device of its own, one request answered by each."""
    import jax

    from mcp_context_forge_tpu.tpu_local.pool import EnginePool

    devices = devices or jax.devices()
    started, before = time.monotonic(), meter.snapshot()
    pool = EnginePool(
        _engine_config(model=model, prefix_cache=False, warmup=True,
                       prefill_buckets=(512,), decode_overlap=False),
        replicas=len(devices), devices=devices, affinity_routing=False,
        heartbeat_timeout_s=600.0)
    await pool.start()
    try:
        placed = [[d.id for d in r.engine.mesh.devices.flat]
                  for r in pool.replicas]
        check(sorted(placed) == [[d.id] for d in devices],
              f"replicas are not one per device: {placed}")
        engine0 = pool.replicas[0].engine
        prompts = [chat_ids(engine0, [{"role": "user", "content":
                                       f"Replica check number {i}."}])
                   for i in range(len(devices))]
        tokens = await asyncio.gather(*[
            greedy_tokens(pool, ids, new_tokens) for ids in prompts])
        check(all(len(t) == new_tokens for t in tokens),
              f"pool answers: {[len(t) for t in tokens]} tokens, "
              f"asked {new_tokens}")
        served = [r.engine.stats.requests for r in pool.replicas]
        check(all(n >= 1 for n in served),
              f"a replica answered nothing: {served}")
    finally:
        await pool.stop()
    emit("replica_pool", replicas=len(devices), replica_devices=placed,
         requests_per_replica=served,
         wall_s=round(time.monotonic() - started, 1), **meter.since(before),
         peak_bytes=peak_bytes(devices))


# ------------------------------------------------------------------------ main

async def run_one_chip(meter: CompileMeter) -> None:
    started, before = time.monotonic(), meter.snapshot()
    emit("kernel_parity", **phase_kernel_parity(),
         wall_s=round(time.monotonic() - started, 1), **meter.since(before))
    await phase_gateway({**GATEWAY_ENV, **ENGINE_ENV}, meter,
                        expect_kernels=True)


async def run_four_chips(meter: CompileMeter) -> None:
    await phase_tp_engine(meter)
    await phase_replica_pool(meter)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the TP-engine and replica-pool phases")
    args = parser.parse_args(argv)

    ok = False
    device = {"platform": "none", "kind": "none", "count": 0}
    try:
        device = device_facts()
        check(device["platform"] == "tpu",
              f"no TPU: jax found {device} — this run needs the chip and "
              "does not continue on another platform")
        check(device["count"] == args.chips,
              f"--chips {args.chips} but jax reports {device['count']} "
              "device(s)")
        emit("device", **device, **cache_facts())
        meter = CompileMeter()
        asyncio.run(run_four_chips(meter) if args.chips == 4
                    else run_one_chip(meter))
        ok = True
    except BaseException:
        traceback.print_exc()
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
