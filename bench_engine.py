"""tpu_local engine micro-benchmark: continuous-batching decode throughput.

Separate from bench.py (the driver's headline gateway metric). Prints one
JSON line: {"metric": "tpu_local_decode_tokens_per_s", ...}. On TPU it
reports BOTH utilization views (round-2 VERDICT #1 asked for a stated,
justified roofline):

- ``mfu``: achieved FLOPs / peak bf16 FLOPs (2 * n_params FLOPs per token;
  v5e peak 197 TFLOP/s/chip). Decode is NOT FLOPs-bound, so mfu is
  structurally tiny at small batch — reported for continuity only.
- ``hbm_roofline_frac``: the honest ceiling for decode. Every decode step
  must stream all resident params once from HBM (plus KV pages), so the
  per-chip bound is steps/s <= HBM_BW / bytes_resident. We report
  achieved_bytes/s = (param_bytes + kv_bytes_touched) * steps/s divided
  by the v5e HBM bandwidth (819 GB/s). 1.0 = perfectly bandwidth-bound.

Also reported: per-token latency percentiles (intervals between
consecutive tokens on each stream, post-warmup) and the A/B knobs in
effect (superstep/decode_block, spec_decode) so captures are
self-describing.

BENCH_SUPERSTEP=K runs the K-step fused decode super-step
(tpu_local_superstep: one jitted on-device token loop per dispatch, one
host sync per K tokens). A comma list (``BENCH_SUPERSTEP=1,4,8,16``)
runs an arm per K and reports ``superstep_ab``: per-arm tok/s,
host-syncs-per-token, live roofline, and greedy token parity against
the first arm — the ROADMAP-item-1 A/B that shows the host-dispatch
bound dissolving as K rises.

Model/geometry via env: BENCH_MODEL (default llama3-1b on tpu /
llama3-tiny on cpu), BENCH_CLIENTS, BENCH_TOKENS, BENCH_DECODE_BLOCK,
BENCH_SPEC (=1 enables prompt-lookup speculative decoding),
BENCH_PROMPT_MODE (repetitive|chat — repetitive favors spec drafting).

BENCH_REPLICAS=N (default 1) serves the same client load through an
EnginePool of N replicas (device-subset meshes on TPU, shared-device
replicas on CPU) and reports aggregate tok/s plus per-replica routing/
occupancy so the pool's scheduling overhead and balance are visible.

BENCH_KV_QUANT=1 runs an A/B pair at the SAME KV byte budget — baseline
KV dtype vs int8 paged KV (tpu_local_kv_quant) — and reports both arms'
tok/s, each arm's page capacity + peak resident pages, and the int8
arm's greedy token-parity rate against the baseline arm.

BENCH_PREFIX_TIERS=1 runs the tiered-prefix-cache A/B
(tpu_local_prefix_tiers, docs/kv_tiering.md): a shared-prefix workload
— more distinct long templates than the FIXED small HBM page budget
can keep resident, revisited round-robin so each template is evicted
between uses — served with tiers off (eviction drops pages) vs on
(eviction spills to host/disk; matches restore). Reports per-arm
prefix_hit_tokens, the tier hit mix, spill/restore counts + restore
p95, tok/s, and greedy token parity across arms. The acceptance bar:
the tiers-on arm's prefix_hit_tokens >= 2x the off arm's at the same
page budget.

BENCH_PREFIX_FABRIC=1 runs the cross-host prefix-cache fabric A/B
(docs/cache_fabric.md): a "prefill host" engine first pushes the
shared templates through the write-behind worker into a file:// object
store and emits its fabric advert; then the SAME cold-start workload
is served by a fresh engine twice — without the fabric (every template
re-prefills) vs with the object store + the merged advert (revisits
restore from T3 as cross-host hits). Reports per-arm
prefix_hit_tokens, the tier hit mix (the fabric arm's "object" column
is the cross-host win), object store read/write counters, tok/s, and
greedy token parity across arms (must be 1.0 — lossless spill mode).
The capture self-describes with "fabric": true so bench_trend judges
it only against fabric history.

BENCH_DISAGG=1 runs the disaggregated prefill/decode A/B
(docs/disaggregation.md): the same mixed long-prefill + chat load
served by a pool of 2 replicas, uniform (both "any") vs role-split
(prefill+decode with live KV-page migration through the shared tiers).
Reports per-arm TTFT p95 / TPOT p95 / tok/s, the migration counters
(ok/degraded + page conservation), tier restore p95, and greedy token
parity across arms (must be 1.0 — the migration hop is the requeue
continuation contract).

BENCH_CONTROLLER=1 runs the closed-loop serving-controller A/B
(docs/controller.md): the same phase-shifting greedy load (interactive
-> batch -> burst) served with a frozen config vs with the
ServingController steering superstep K over a warmed ladder. Reports
per-arm tok/s + TTFT p95, the decision counts, and greedy token parity
(must be 1.0 — K only moves at drain barriers) with zero serving-stage
XLA compiles.

Platform: what this process finds, or BENCH_PLATFORM (bench.pin_platform
— a run asked for tpu without one fails). ``mfu`` / ``hbm_roofline_frac``
are printed only where ``device_kind`` is a v5e, ``null`` elsewhere.
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import pin_platform  # noqa: E402

# roofline peaks: single source of truth shared with the engine's live
# gauges (tpu_local/roofline.py is jax-free, so importing it here cannot
# pin the platform before pin_platform runs)
from mcp_context_forge_tpu.tpu_local.roofline import v5e_peaks  # noqa: E402


def count_params(config) -> int:
    """Parameter count (single source of truth: models.llama.param_count —
    handles tied embeddings and Qwen2 attention biases)."""
    from mcp_context_forge_tpu.tpu_local.models.llama import param_count

    return param_count(config)


async def run(platform: str, kv_quant: str = "", superstep: int = 0) -> dict:
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS

    model = os.environ.get(
        "BENCH_MODEL", "llama3-1b" if platform == "tpu" else "llama3-tiny")
    clients = int(os.environ.get("BENCH_CLIENTS", "8"))
    max_tokens = int(os.environ.get("BENCH_TOKENS", "32"))
    # multi-step decode dispatch pays off where the per-token host sync is
    # the bottleneck (TPU): default 4 there, 1 on CPU (compute-bound)
    decode_block = int(os.environ.get("BENCH_DECODE_BLOCK",
                                      "4" if platform == "tpu" else "1"))
    # super-step arm: the K-step fused token loop supersedes the legacy
    # decode_block knob (a single BENCH_SUPERSTEP value flows through
    # main(); sweep lists fan out to one run() per K)
    if superstep == 0:
        env_ss = os.environ.get("BENCH_SUPERSTEP", "")
        if env_ss and "," not in env_ss:
            superstep = int(env_ss)
    if superstep > 0:
        decode_block = 1
    spec = os.environ.get("BENCH_SPEC", "0") == "1"
    if spec:
        decode_block = 1  # mutually exclusive with multi-step dispatch
        superstep = 0
    # A/B arm for the overlapped decode pipeline: BENCH_OVERLAP=0 runs the
    # serial dispatch->device_get->bookkeeping loop, =1 (default) overlaps
    # host work behind device execution
    overlap = os.environ.get("BENCH_OVERLAP", "1") == "1"
    # BENCH_SAMPLE_EVERY=N: decode-step phase attribution every Nth step
    # (the bench then reports the sampled phase rows alongside tok/s)
    sample_every = int(os.environ.get("BENCH_SAMPLE_EVERY", "0"))
    quant = os.environ.get("BENCH_QUANT", "")
    buckets = os.environ.get("BENCH_BATCH_BUCKETS", "0") == "1"
    moe_impl = os.environ.get("BENCH_MOE_IMPL", "")
    moe_block = int(os.environ.get("BENCH_MOE_BLOCK", "0"))
    # page size: the int8 Pallas gate needs page_size % 32 == 0 — under
    # BENCH_KV_QUANT both arms run 32 so the A/B compares KV storage
    # dtype on the SAME kernel path (16-page baseline would keep the
    # fused kernel while the int8 arm fell back to the dequant gather,
    # attributing the gather's extra HBM traffic to quantization)
    page_size = int(os.environ.get(
        "BENCH_PAGE_SIZE",
        "32" if os.environ.get("BENCH_KV_QUANT", "0") == "1" else "16"))
    replicas = int(os.environ.get("BENCH_REPLICAS", "1"))
    config = EngineConfig(model=model, max_batch=min(clients, 16),
                          max_seq_len=512, page_size=page_size,
                          num_pages=1024,
                          prefill_buckets=(64,),
                          dtype="bfloat16" if platform == "tpu" else "float32",
                          attn_impl="auto", decode_block=decode_block,
                          superstep=max(1, superstep),
                          decode_overlap=overlap,
                          step_sample_every=sample_every,
                          spec_decode=spec, quant=quant, kv_quant=kv_quant,
                          batch_buckets=buckets, moe_impl=moe_impl,
                          moe_block=moe_block)
    if replicas > 1:
        from mcp_context_forge_tpu.tpu_local.pool import EnginePool

        engine = EnginePool(config, replicas=replicas)
    else:
        engine = TPUEngine(config)
    await engine.start()
    try:
        prompt_mode = os.environ.get("BENCH_PROMPT_MODE", "chat")
        if prompt_mode == "repetitive":
            # summaries/extraction-shaped context: n-gram lookup can draft
            text = ("the metric value is 42; the metric value is 42; "
                    "report: the metric value is 42 and rising; ") * 3
        else:
            text = "benchmark prompt for decode throughput"
        prompt = engine.tokenizer.encode(text)

        async def one() -> tuple[list[int], list[float]]:
            tokens, intervals = [], []
            last = time.monotonic()
            async for tok in engine.generate(prompt, max_tokens=max_tokens):
                nownow = time.monotonic()
                intervals.append((nownow - last) * 1000)
                last = nownow
                tokens.append(tok)
            return tokens, intervals

        # warmup so the timed region below measures steady state, not XLA
        # compiles; the fast subset on TPU keeps cold-cache boot in minutes
        # (BENCH_WARMUP overrides — the CI smoke uses "fast" everywhere)
        await asyncio.to_thread(
            engine.warmup,
            os.environ.get("BENCH_WARMUP",
                           "fast" if platform == "tpu" else "full"))
        await one()  # primes the dispatch loop end-to-end (already compiled)
        steps0 = engine.stats.decode_steps
        dispatches0 = engine.stats.decode_dispatches
        spec0 = engine.stats.spec_tokens
        overlap0 = engine.stats.overlap_steps
        drains0 = engine.stats.pipeline_drains
        prefills0 = engine.stats.prefill_batches
        started = time.monotonic()
        results = await asyncio.gather(*[one() for _ in range(clients)])
        wall = time.monotonic() - started
        total = sum(len(r[0]) for r in results)
        intervals = sorted(i for _, iv in results for i in iv[1:])  # drop TTFT
        tokens_per_s = total / wall
        steps = engine.stats.decode_steps - steps0
        dispatches = engine.stats.decode_dispatches - dispatches0
        out = {
            "metric": "tpu_local_decode_tokens_per_s",
            "value": round(tokens_per_s, 2),
            "unit": "tokens/s",
            "vs_baseline": None,  # reference has no in-process engine
            "platform": platform,
            "model": model,
            "clients": clients,
            "tokens": total,
            "wall_s": round(wall, 3),
            "decode_block": decode_block, "batch_buckets": buckets,
            # K-step fused token loop: each decode dispatch retires up to
            # superstep tokens/slot in ONE host sync — syncs/token is the
            # number token-loop fusion exists to drive toward 1/K
            "superstep": config.fused_steps,
            "decode_dispatches": dispatches,
            "host_syncs_per_token": round(dispatches / max(1, total), 4),
            "spec_decode": spec,
            "decode_overlap": overlap,
            "overlap_steps": engine.stats.overlap_steps - overlap0,
            "pipeline_drains": engine.stats.pipeline_drains - drains0,
            # the number overlap exists to drive to ~0: fraction of decode
            # wall the device spent waiting on host bookkeeping
            "device_idle_frac": round(engine.device_idle_fraction(), 4),
            "quant": quant,
            # KV storage arm: page capacity is the dtype-aware pool size
            # at the FIXED byte budget num_pages denominates (int8 ~2x),
            # peak is the allocator's monotonic high-water resident mark
            # (the step ring is bounded and would under-report long runs)
            "kv_quant": kv_quant,
            "kv_pages_capacity": (
                sum(r.engine.num_kv_pages for r in engine.replicas)
                if replicas > 1 else engine.num_kv_pages),
            "kv_pages_peak": (
                sum(r.engine.allocator.peak_pages_in_use
                    for r in engine.replicas)
                if replicas > 1 else engine.allocator.peak_pages_in_use),
            "token_streams": [r[0] for r in results],
            "decode_steps": steps,
            "prefill_batches": engine.stats.prefill_batches - prefills0,
            "spec_tokens": engine.stats.spec_tokens - spec0,
            "token_latency_p50_ms": (round(statistics.median(intervals), 2)
                                     if intervals else None),
            "token_latency_p95_ms": (round(intervals[int(len(intervals) * 0.95)], 2)
                                     if intervals else None),
        }
        out["replicas"] = replicas
        # live-observability twins of the post-hoc numbers below: the
        # warmup-captured cost-model roofline over the run's decode
        # window, XLA compile attribution (serving count must be 0 on a
        # warmed engine), and — under BENCH_SAMPLE_EVERY — the last few
        # sampled phase-attribution rows
        eng0 = engine.replicas[0].engine if replicas > 1 else engine
        import jax

        device = jax.devices()[0]
        out["device_kind"] = device.device_kind
        peaks = v5e_peaks(device.device_kind)
        out["live_roofline"] = eng0.roofline_snapshot()
        if peaks is None:
            # the engine's gauges divide by v5e peaks whatever runs them
            # (roofline.py): off a v5e the fractions mean nothing
            for key in ("mfu", "hbm_roofline_frac"):
                if key in out["live_roofline"]:
                    out["live_roofline"][key] = None
        out["xla_compiles"] = {k: v for k, v in eng0.compile_stats().items()
                               if k != "recent"}
        if sample_every:
            out["sample_every"] = sample_every
            out["phase_rows"] = [s["phases"] for s in eng0.recent_steps()
                                 if s.get("phases")][-8:]
        if replicas > 1:
            # pool arm: aggregate tok/s is `value` above (the clients'
            # wall covers the whole pool); per-replica occupancy shows
            # how the router balanced the load
            stats_total = max(1, sum(r.engine.stats.completion_tokens
                                     for r in engine.replicas))
            out["pool"] = {
                "router": engine.router.counters(),
                "requeues": engine.requeues,
                "per_replica": [{
                    "id": r.id,
                    "routed": r.routed,
                    "completion_tokens": r.engine.stats.completion_tokens,
                    "occupancy_share": round(
                        r.engine.stats.completion_tokens / stats_total, 3),
                    "decode_steps": r.engine.stats.decode_steps,
                    "kv_pages_peak": r.engine.allocator.peak_pages_in_use,
                } for r in engine.replicas],
            }
        if platform == "tpu":
            n_chips = len(jax.devices())  # engine meshes over every chip
            model_config = MODEL_CONFIGS[model]
            n_params = count_params(model_config)
            achieved_tflops = 2 * n_params * tokens_per_s / 1e12
            out["n_params"] = n_params
            out["n_chips"] = n_chips
            out["mfu"] = (round(achieved_tflops / (peaks[0] * n_chips), 5)
                          if peaks else None)
            # HBM roofline: params stream once per STEP (all slots share the
            # read); KV pages touched scale with resident context
            param_bytes = (1 if quant == "int8" else 2) * n_params
            kv_bytes = (2 * 2 * model_config.n_layers * model_config.n_kv_heads
                        * model_config.head_dim
                        * min(clients, 16) * (len(prompt) + max_tokens // 2))
            steps_per_s = steps / wall if wall else 0.0
            achieved_gbps = (param_bytes + kv_bytes) * steps_per_s / 1e9
            out["achieved_hbm_gbps"] = round(achieved_gbps, 1)
            out["hbm_roofline_frac"] = (
                round(achieved_gbps / (peaks[1] * n_chips), 4)
                if peaks else None)
        return out
    finally:
        await engine.stop()


async def _run_prefix_tiers_arm(platform: str, tiers: bool) -> dict:
    """One arm of the tiered-prefix-cache A/B: G distinct long templates
    over a page budget sized to hold only a couple of them, revisited in
    rotation so every reuse finds its pages evicted (dropped with tiers
    off, spilled with tiers on)."""
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine

    model = os.environ.get(
        "BENCH_MODEL", "llama3-1b" if platform == "tpu" else "llama3-tiny")
    page_size = int(os.environ.get("BENCH_PAGE_SIZE", "16"))
    groups = int(os.environ.get("BENCH_TIER_GROUPS", "6"))
    rounds = int(os.environ.get("BENCH_TIER_ROUNDS", "3"))
    max_tokens = int(os.environ.get("BENCH_TOKENS", "8"))
    tmpl_pages = 3                       # full pages per shared template
    # the FIXED HBM page budget both arms serve under: room for one
    # active request (template + suffix + generation) plus ~1.5 cached
    # templates — far below the groups x tmpl_pages working set
    slot_pages = tmpl_pages + 2
    target_pages = 1 + slot_pages + int(tmpl_pages * 1.5)
    kv_quant = os.environ.get("BENCH_KV_QUANT_TIERS", "")
    num_pages = target_pages
    if kv_quant:
        # EngineConfig.num_pages is a byte budget denominated in
        # ENGINE-DTYPE pages; re-denominate so the RESIDENT pool still
        # holds ~target_pages and the eviction pressure the A/B depends
        # on survives the int8 conversion
        import jax.numpy as jnp

        from mcp_context_forge_tpu.tpu_local.kv import kv_page_bytes
        from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS

        dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
        mc = MODEL_CONFIGS[model]
        budget = target_pages * kv_page_bytes(mc, page_size, dtype, kv_quant)
        num_pages = max(2, -(-budget // kv_page_bytes(mc, page_size, dtype)))
    config = EngineConfig(
        model=model, max_batch=2, max_seq_len=page_size * 8,
        page_size=page_size, num_pages=num_pages,
        prefill_buckets=(page_size, page_size * 4),
        dtype="bfloat16" if platform == "tpu" else "float32",
        attn_impl="auto", prefix_cache=True, prefix_tiers=tiers,
        tier_host_bytes=64 * 1024 * 1024, tier_disk_bytes=64 * 1024 * 1024,
        kv_quant=kv_quant)
    engine = TPUEngine(config)
    await engine.start()
    try:
        templates = [[7 + g * 101 + i for i in range(tmpl_pages * page_size)]
                     for g in range(groups)]
        streams: list[list[int]] = []
        prompt_tokens = 0
        started = time.monotonic()
        total = 0
        for r in range(rounds):
            for g, template in enumerate(templates):
                prompt = template + [900 + r * groups + g]
                prompt_tokens += len(prompt)
                tokens = [t async for t in engine.generate(
                    prompt, max_tokens=max_tokens)]
                streams.append(tokens)
                total += len(tokens)
        wall = time.monotonic() - started
        alloc = engine.allocator
        arm = {
            "prefix_tiers": tiers,
            "value": round(total / wall, 2) if wall else 0.0,
            "tokens": total,
            "kv_pages_capacity": engine.num_kv_pages,
            "prompt_tokens": prompt_tokens,
            "prefix_hits": alloc.prefix_hits,
            "prefix_hit_tokens": alloc.prefix_hit_tokens,
            "tier_hit_mix": dict(alloc.tier_hit_tokens),
            "token_streams": streams,
        }
        stats = engine.tier_stats()
        if stats is not None:
            arm["spills"] = stats["spills"]
            arm["restores"] = stats["restores"]
            arm["restore_p95_ms"] = stats["restore_p95_ms"]
            arm["store"] = stats.get("store")
        return arm
    finally:
        await engine.stop()


def run_prefix_tiers(platform: str) -> dict:
    """The BENCH_PREFIX_TIERS A/B block: tiers off vs on at the same
    page budget + workload; parity is greedy and must be 1.0."""
    off = asyncio.run(_run_prefix_tiers_arm(platform, tiers=False))
    on = asyncio.run(_run_prefix_tiers_arm(platform, tiers=True))
    base_streams = off.pop("token_streams")
    on_streams = on.pop("token_streams")
    return {
        "baseline": off,
        "tiered": on,
        "hit_tokens_ratio": round(
            on["prefix_hit_tokens"] / max(1, off["prefix_hit_tokens"]), 3),
        "token_parity_rate": _parity_rate(base_streams, on_streams),
    }


def _parity_rate(base_streams, arm_streams) -> float:
    """Per-position greedy token agreement across paired streams (1.0 =
    byte-identical)."""
    matched = positions = 0
    for a, b in zip(base_streams, arm_streams):
        positions += max(len(a), len(b))
        matched += sum(1 for x, y in zip(a, b) if x == y)
    return round(matched / max(1, positions), 4)


def _fabric_workload(page_size: int, groups: int, rounds: int):
    """The shared-template rotation the fabric A/B serves — same shape
    as the tiers A/B so captures are comparable."""
    tmpl_pages = 3
    templates = [[7 + g * 101 + i for i in range(tmpl_pages * page_size)]
                 for g in range(groups)]
    prompts = [template + [900 + r * groups + g]
               for r in range(rounds)
               for g, template in enumerate(templates)]
    return templates, prompts, tmpl_pages


def _fabric_engine_config(platform: str, page_size: int, tmpl_pages: int,
                          object_url: str, host_bytes: int):
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig

    model = os.environ.get(
        "BENCH_MODEL", "llama3-1b" if platform == "tpu" else "llama3-tiny")
    slot_pages = tmpl_pages + 2
    target_pages = 1 + slot_pages + int(tmpl_pages * 1.5)
    # tier_spill_quant="" (lossless spill) so the fabric arm's greedy
    # parity vs the cold arm is a HARD 1.0 gate, not a drift tolerance
    return EngineConfig(
        model=model, max_batch=2, max_seq_len=page_size * 8,
        page_size=page_size, num_pages=target_pages,
        prefill_buckets=(page_size, page_size * 4),
        dtype="bfloat16" if platform == "tpu" else "float32",
        attn_impl="auto", prefix_cache=True, prefix_tiers=True,
        tier_host_bytes=host_bytes, tier_disk_bytes=0,
        tier_spill_quant="", tier_object_url=object_url)


async def _fabric_prefill_host(platform: str, object_url: str,
                               page_size: int, groups: int,
                               max_tokens: int):
    """Host A of the fabric A/B: serve each template once over a T1
    budget too small to keep it, so displaced pages flow through the
    write-behind worker into the shared object store; return the
    advert a real deployment would gossip (docs/cache_fabric.md)."""
    from mcp_context_forge_tpu.tpu_local.engine import TPUEngine
    from mcp_context_forge_tpu.tpu_local.kv.fabric import FabricAdvert

    templates, prompts, tmpl_pages = _fabric_workload(page_size, groups,
                                                      rounds=1)
    config = _fabric_engine_config(platform, page_size, tmpl_pages,
                                   object_url, host_bytes=4096)
    engine = TPUEngine(config)
    await engine.start()
    try:
        for prompt in prompts:
            async for _ in engine.generate(prompt, max_tokens=max_tokens):
                pass
        store = engine._tier_client.store
        # push the still-resident chains through the REAL spill path so
        # the store holds every template, then drain the writer
        engine.allocator.spill_resident_prefix()
        deadline = time.monotonic() + 30
        while ((not store._writeq.empty() or store._pending)
               and time.monotonic() < deadline):
            await asyncio.sleep(0.02)
        hashes = store.object_hashes()
        return FabricAdvert(tenant=store.object_namespace,
                            host="bench-prefill", hashes=hashes), {
            "object_pages": store.stats().get("object_pages", 0),
            "object_writes": store.stats().get("object_writes", 0),
        }
    finally:
        await engine.stop()


async def _run_prefix_fabric_arm(platform: str, page_size: int,
                                 groups: int, rounds: int,
                                 max_tokens: int, object_url: str = "",
                                 advert=None) -> dict:
    """One serving arm: a FRESH engine (cold local cache) over the same
    rotation workload. With an object_url + peer advert merged, every
    template's first visit is a cross-host T3 restore instead of a full
    prefill."""
    from mcp_context_forge_tpu.tpu_local.engine import TPUEngine

    _templates, prompts, tmpl_pages = _fabric_workload(page_size, groups,
                                                       rounds)
    config = _fabric_engine_config(platform, page_size, tmpl_pages,
                                   object_url,
                                   host_bytes=64 * 1024 * 1024)
    engine = TPUEngine(config)
    await engine.start()
    try:
        if advert is not None:
            engine._tier_client.store.fabric.merge(advert)
        streams: list[list[int]] = []
        prompt_tokens = 0
        started = time.monotonic()
        total = 0
        for prompt in prompts:
            prompt_tokens += len(prompt)
            tokens = [t async for t in engine.generate(
                prompt, max_tokens=max_tokens)]
            streams.append(tokens)
            total += len(tokens)
        wall = time.monotonic() - started
        alloc = engine.allocator
        arm = {
            "fabric": bool(object_url),
            "value": round(total / wall, 2) if wall else 0.0,
            "tokens": total,
            "prompt_tokens": prompt_tokens,
            "prefix_hits": alloc.prefix_hits,
            "prefix_hit_tokens": alloc.prefix_hit_tokens,
            "tier_hit_mix": dict(alloc.tier_hit_tokens),
            "token_streams": streams,
        }
        stats = engine.tier_stats()
        if stats is not None and stats.get("store"):
            store = stats["store"]
            for key in ("object_reads", "object_writes",
                        "object_write_drops", "object_pages"):
                if key in store:
                    arm[key] = store[key]
        return arm
    finally:
        await engine.stop()


def run_prefix_fabric(platform: str) -> dict:
    """The BENCH_PREFIX_FABRIC A/B block: cold serving vs serving over
    a fabric another host already populated (docs/cache_fabric.md)."""
    import shutil
    import tempfile

    page_size = int(os.environ.get("BENCH_PAGE_SIZE", "16"))
    groups = int(os.environ.get("BENCH_TIER_GROUPS", "6"))
    rounds = int(os.environ.get("BENCH_TIER_ROUNDS", "3"))
    max_tokens = int(os.environ.get("BENCH_TOKENS", "8"))
    tmp = tempfile.mkdtemp(prefix="bench-fabric-")
    try:
        url = f"file://{tmp}"
        advert, prefill = asyncio.run(_fabric_prefill_host(
            platform, url, page_size, groups, max_tokens))
        cold = asyncio.run(_run_prefix_fabric_arm(
            platform, page_size, groups, rounds, max_tokens))
        fab = asyncio.run(_run_prefix_fabric_arm(
            platform, page_size, groups, rounds, max_tokens,
            object_url=url, advert=advert))
        cold_streams = cold.pop("token_streams")
        fab_streams = fab.pop("token_streams")
        return {
            "prefill_host": prefill,
            "baseline": cold,
            "fabric": fab,
            "advert_hashes": len(advert.hashes),
            "object_hit_tokens": fab["tier_hit_mix"].get("object", 0),
            "hit_tokens_ratio": round(
                fab["prefix_hit_tokens"]
                / max(1, cold["prefix_hit_tokens"]), 3),
            "token_parity_rate": _parity_rate(cold_streams, fab_streams),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


async def _run_controller_arm(platform: str, controlled: bool) -> dict:
    """One arm of the BENCH_CONTROLLER A/B: identical greedy phase-
    shifting load (interactive-heavy -> batch-heavy -> interactive
    burst), served either by a frozen config (controlled=False) or with
    the closed-loop ServingController steering superstep K over a
    warmed ladder (docs/controller.md). Parity must be 1.0 — K moves
    only at drain barriers — and serving-stage XLA compiles must stay 0
    because every ladder rung was warmed up front."""
    from mcp_context_forge_tpu.observability.signals import SignalBus
    from mcp_context_forge_tpu.tpu_local.controller import ServingController
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine

    model = os.environ.get(
        "BENCH_MODEL", "llama3-1b" if platform == "tpu" else "llama3-tiny")
    clients = int(os.environ.get("BENCH_CLIENTS", "8"))
    max_tokens = int(os.environ.get("BENCH_TOKENS", "32"))
    raw_k = os.environ.get("BENCH_SUPERSTEP", "8").split(",")[0]
    base_k = max(1, int(raw_k or "8"))
    ladder = tuple(sorted({1, max(1, base_k // 2), base_k}))
    config = EngineConfig(
        model=model, max_batch=min(clients, 16), max_seq_len=512,
        page_size=16, num_pages=1024, prefill_buckets=(64,),
        dtype="bfloat16" if platform == "tpu" else "float32",
        attn_impl="auto", superstep=base_k,
        k_ladder=ladder if controlled else ())
    bus = SignalBus()
    engine = TPUEngine(config, signals=bus if controlled else None)
    await engine.start()
    controller = None
    try:
        await asyncio.to_thread(
            engine.warmup,
            os.environ.get("BENCH_WARMUP",
                           "fast" if platform == "tpu" else "full"))
        prompt = engine.tokenizer.encode(
            "benchmark prompt for decode throughput")
        async for _ in engine.generate(prompt, max_tokens=4):
            pass  # primes the dispatch loop end-to-end (already compiled)
        if controlled:
            # bench-cadence control loop: same ladders as production,
            # compressed timing so decisions can land inside the run
            controller = ServingController(
                bus, lambda: [engine],
                tick_s=0.05, cooldown_s=0.25, eval_window_s=0.25,
                queue_wait_high_ms=25.0, queue_wait_low_ms=2.0,
                idle_frac_high=0.05)
            await controller.start()

        async def stream(n_tokens: int) -> tuple[list[int], float | None]:
            toks: list[int] = []
            first = None
            t0 = time.monotonic()
            async for tok in engine.generate(prompt, max_tokens=n_tokens):
                if first is None:
                    first = (time.monotonic() - t0) * 1000
                toks.append(tok)
            return toks, first

        streams: list[list[int]] = []
        ttfts: list[float] = []

        async def phase(reqs: int, n_tokens: int) -> None:
            res = await asyncio.gather(*[stream(n_tokens)
                                         for _ in range(reqs)])
            for toks, first in res:
                streams.append(toks)
                if first is not None:
                    ttfts.append(first)

        started = time.monotonic()
        await phase(clients, 8)            # interactive-heavy
        await phase(clients, 8)
        await phase(clients, max_tokens)   # batch-heavy
        await phase(clients, 8)            # interactive burst again
        wall = time.monotonic() - started
        total = sum(len(s) for s in streams)
        ttfts.sort()
        arm = {
            "controlled": controlled,
            "value": round(total / wall, 2) if wall else 0.0,
            "tokens": total,
            "wall_s": round(wall, 3),
            "superstep_base": base_k,
            "ttft_p95_ms": (round(ttfts[int(len(ttfts) * 0.95)], 2)
                            if ttfts else None),
            "xla_compiles": {k: v for k, v in engine.compile_stats().items()
                             if k != "recent"},
            "token_streams": streams,
        }
        if controlled:
            arm["k_ladder"] = list(ladder)
            arm["knob_state"] = engine.knob_state()
            decisions = controller.decisions(limit=256)
            arm["decisions"] = len(decisions)
            arm["decisions_by_knob"] = {}
            for d in decisions:
                key = f"{d['knob']}:{d['direction']}"
                arm["decisions_by_knob"][key] = (
                    arm["decisions_by_knob"].get(key, 0) + 1)
        return arm
    finally:
        if controller is not None:
            await controller.stop()
        await engine.stop()


def run_controller_ab(platform: str) -> dict:
    """The BENCH_CONTROLLER A/B block: frozen config vs closed-loop
    controller on the SAME phase-shifting greedy load. Parity is greedy
    and must be 1.0 (K changes land only at drain barriers)."""
    off = asyncio.run(_run_controller_arm(platform, controlled=False))
    on = asyncio.run(_run_controller_arm(platform, controlled=True))
    base_streams = off.pop("token_streams")
    on_streams = on.pop("token_streams")
    return {
        "off": off,
        "on": on,
        "token_parity_rate": _parity_rate(base_streams, on_streams),
    }


async def _run_disagg_arm(platform: str, roles: str) -> dict:
    """One arm of the BENCH_DISAGG A/B: a pool of 2 replicas serving a
    mixed load — long-prefill requests (several full pages, the class
    disaggregation exists for) interleaved with short chat turns — with
    either a uniform pool (roles="", both generalists) or the
    prefill+decode split (long admissions prefill on replica 0, migrate
    their KV chain through the shared tiers, and decode on replica 1).
    Greedy end to end, so the arms' streams must be byte-identical."""
    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig
    from mcp_context_forge_tpu.tpu_local.pool import EnginePool

    model = os.environ.get(
        "BENCH_MODEL", "llama3-1b" if platform == "tpu" else "llama3-tiny")
    page_size = int(os.environ.get("BENCH_PAGE_SIZE", "16"))
    long_reqs = int(os.environ.get("BENCH_DISAGG_LONG", "4"))
    chat_reqs = int(os.environ.get("BENCH_DISAGG_CHAT", "4"))
    max_tokens = int(os.environ.get("BENCH_TOKENS", "16"))
    long_pages = 5                       # full pages per long prompt
    config = EngineConfig(
        model=model, max_batch=4,
        max_seq_len=max(256, page_size * (long_pages + 2) + 2 * max_tokens),
        page_size=page_size, num_pages=256,
        prefill_buckets=(page_size, page_size * 8),
        dtype="bfloat16" if platform == "tpu" else "float32",
        attn_impl="auto", prefix_cache=True, prefix_tiers=True,
        tier_host_bytes=64 * 1024 * 1024, tier_disk_bytes=0)
    pool = EnginePool(config, replicas=2, roles=roles,
                      disagg_prompt_tokens=page_size * 2)
    await pool.start()
    try:
        await asyncio.to_thread(
            pool.warmup,
            os.environ.get("BENCH_WARMUP",
                           "fast" if platform == "tpu" else "full"))
        # deterministic synthetic prompts: the long class spans
        # long_pages FULL pages (each distinct — no cross-request prefix
        # reuse muddying the migration accounting); the chat class stays
        # under the disagg threshold
        long_prompts = [[11 + i * 97 + j
                         for j in range(long_pages * page_size)]
                        for i in range(long_reqs)]
        # must stay under the disagg threshold (2 pages) even with the
        # char-level test tokenizer, so the chat class routes to decode
        chat_prompt = pool.tokenizer.encode("short chat turn")
        async for _ in pool.generate(list(chat_prompt), max_tokens=2):
            pass  # primes both dispatch loops end-to-end

        async def stream(prompt: list[int], n_tokens: int
                         ) -> tuple[list[int], float | None, list[float]]:
            toks: list[int] = []
            gaps: list[float] = []
            first = None
            t0 = time.monotonic()
            last = t0
            async for tok in pool.generate(list(prompt),
                                           max_tokens=n_tokens):
                now = time.monotonic()
                if first is None:
                    first = (now - t0) * 1000
                else:
                    gaps.append((now - last) * 1000)
                last = now
                toks.append(tok)
            return toks, first, gaps

        started = time.monotonic()
        results = await asyncio.gather(
            *[stream(p, max_tokens) for p in long_prompts],
            *[stream(list(chat_prompt) + [1000 + i], max_tokens)
              for i in range(chat_reqs)])
        wall = time.monotonic() - started
        streams = [r[0] for r in results]
        ttfts = sorted(r[1] for r in results if r[1] is not None)
        long_ttfts = sorted(r[1] for r in results[:long_reqs]
                            if r[1] is not None)
        gaps = sorted(g for r in results for g in r[2])
        total = sum(len(s) for s in streams)
        restore_p95 = max((r.engine.tier_stats() or {}).get(
            "restore_p95_ms") or 0.0 for r in pool.replicas)
        return {
            "roles": ([p.strip() for p in roles.split(",") if p.strip()]
                      if roles else []),
            "value": round(total / wall, 2) if wall else 0.0,
            "tokens": total,
            "wall_s": round(wall, 3),
            "ttft_p95_ms": (round(ttfts[int(len(ttfts) * 0.95)], 2)
                            if ttfts else None),
            "ttft_long_p95_ms": (
                round(long_ttfts[int(len(long_ttfts) * 0.95)], 2)
                if long_ttfts else None),
            "tpot_p95_ms": (round(gaps[int(len(gaps) * 0.95)], 2)
                            if gaps else None),
            "migrations": dict(pool.migrations),
            "migration_pages": dict(pool.migration_pages),
            "restore_p95_ms": restore_p95,
            "router": pool.router.counters(),
            "requeues": pool.requeues,
            "token_streams": streams,
        }
    finally:
        await pool.stop()


def run_disagg_ab(platform: str) -> dict:
    """The BENCH_DISAGG A/B block: uniform pool vs prefill/decode split
    on the SAME mixed load. Parity is greedy and must be 1.0 (the
    migration hop is the requeue continuation contract); migration page
    counters must conserve (spilled == restored + degraded)."""
    uniform = asyncio.run(_run_disagg_arm(platform, roles=""))
    disagg = asyncio.run(_run_disagg_arm(platform, roles="prefill,decode"))
    base_streams = uniform.pop("token_streams")
    arm_streams = disagg.pop("token_streams")
    pages = disagg["migration_pages"]
    return {
        "uniform": uniform,
        "disagg": disagg,
        "ttft_p95_delta_ms": (
            round(uniform["ttft_p95_ms"] - disagg["ttft_p95_ms"], 2)
            if uniform["ttft_p95_ms"] is not None
            and disagg["ttft_p95_ms"] is not None else None),
        "pages_conserved": (
            pages["spilled"] == pages["restored"] + pages["degraded"]),
        "token_parity_rate": _parity_rate(base_streams, arm_streams),
    }


def _superstep_sweep() -> list[int]:
    """K values of a BENCH_SUPERSTEP sweep ('1,4,8,16'); empty for a
    single/unset value (which run() consumes directly)."""
    raw = os.environ.get("BENCH_SUPERSTEP", "")
    if "," not in raw:
        return []
    return [int(v) for v in raw.split(",") if v.strip()]


def main() -> dict:
    platform = pin_platform()
    sweep = _superstep_sweep()
    out = asyncio.run(run(platform, superstep=sweep[0] if sweep else 0))
    base_streams = out.pop("token_streams")
    if sweep:
        # superstep A/B: one arm per K, all greedy on identical prompts —
        # host syncs per token must fall ~1/K while streams stay
        # byte-identical to the first arm (exact fused-decode parity)
        arm_keys = ("superstep", "value", "decode_steps",
                    "decode_dispatches", "host_syncs_per_token",
                    "device_idle_frac", "live_roofline")
        arms = [{**{k: out[k] for k in arm_keys}, "token_parity_rate": 1.0}]
        if "hbm_roofline_frac" in out:
            arms[0]["hbm_roofline_frac"] = out["hbm_roofline_frac"]
        for k_steps in sweep[1:]:
            arm = asyncio.run(run(platform, superstep=k_steps))
            arm_streams = arm.pop("token_streams")
            summary = {**{k: arm[k] for k in arm_keys},
                       "token_parity_rate": _parity_rate(base_streams,
                                                         arm_streams)}
            if "hbm_roofline_frac" in arm:
                summary["hbm_roofline_frac"] = arm["hbm_roofline_frac"]
            arms.append(summary)
        out["superstep_ab"] = {"arms": arms}
    if os.environ.get("BENCH_KV_QUANT", "0") == "1":
        # A/B arm: same byte budget, int8 paged KV. Prompts are greedy and
        # identical across arms, so per-position token agreement measures
        # quantization drift directly (1.0 = byte-identical streams).
        # the int8 arm must run at the SAME fused K as the baseline it is
        # compared against (under a sweep, run() sees the comma value and
        # would fall back to BENCH_DECODE_BLOCK — conflating the fusion
        # win with the quantization win)
        arm = asyncio.run(run(platform, kv_quant="int8",
                              superstep=sweep[0] if sweep else 0))
        arm_streams = arm.pop("token_streams")
        keys = ("value", "kv_pages_capacity", "kv_pages_peak",
                "decode_steps", "device_idle_frac")
        out["kv_quant_ab"] = {
            "baseline": {k: out[k] for k in keys},
            "int8": {k: arm[k] for k in keys},
            "page_capacity_ratio": round(
                arm["kv_pages_capacity"] / max(1, out["kv_pages_capacity"]),
                3),
            "token_parity_rate": _parity_rate(base_streams, arm_streams),
        }
    if os.environ.get("BENCH_CONTROLLER", "0") == "1":
        # closed-loop controller A/B (docs/controller.md): frozen config
        # vs adaptive-K under a phase-shifting load. The capture self-
        # describes as a controller arm so bench_trend partitions it
        # away from static-K history.
        out["controller"] = True
        out["controller_ab"] = run_controller_ab(platform)
    if os.environ.get("BENCH_DISAGG", "0") == "1":
        # disaggregated prefill/decode A/B (docs/disaggregation.md):
        # uniform pool vs role-split pool with live KV-page migration.
        # The capture self-describes its role split so bench_trend
        # partitions it away from uniform-pool history.
        out["roles"] = ["prefill", "decode"]
        out["disagg_ab"] = run_disagg_ab(platform)
    if os.environ.get("BENCH_PREFIX_TIERS", "0") == "1":
        # tiered prefix cache A/B: shared-prefix workload at a FIXED
        # small HBM page budget — tiers off drops evicted templates,
        # tiers on spills + restores them. The capture self-describes as
        # a tiers arm so bench_trend judges it only against tier history.
        out["prefix_tiers"] = True
        out["prefix_tiers_ab"] = run_prefix_tiers(platform)
    if os.environ.get("BENCH_PREFIX_FABRIC", "0") == "1":
        # cross-host prefix-cache fabric A/B (docs/cache_fabric.md):
        # cold serving vs serving over an object store another "host"
        # populated. The capture self-describes as a fabric arm so
        # bench_trend judges it only against fabric history.
        out["fabric"] = True
        out["prefix_fabric_ab"] = run_prefix_fabric(platform)
    return out


if __name__ == "__main__":
    print(json.dumps(main()))
