"""Continuous-batching inference engine.

The crux component (SURVEY.md §7.2 #1): an asyncio front (request queue,
tokenizer, per-request token streams) bridged to a **dispatch thread** that
owns every device sync, so decode steps never stall the gateway's event
loop (SURVEY.md §7.2 #3 — "one process cannot block the event loop on
jax.device_get"). XLA's static-shape discipline is respected everywhere:

- prefill compiles once per (prefill_batch, bucket) shape — admissions are
  batched up to ``prefill_max_batch`` requests sharing a bucket, so bursts
  amortize the forward pass instead of serializing behind each other;
- decode compiles once for the full [max_batch] slot array — inactive slots
  ride along masked (position 0 into the trash page);
- sampling params are per-row columns of the one packed call a dispatch
  uploads (call_layout.py), so mixed greedy/temperature requests share one
  compiled step, and the FIRST token is sampled on device with the same
  kernel + engine PRNG as every later token (one sampler; each step's key
  folded inside its program from one base key and the dispatch's number).

The engine is a single-owner of its mesh/slice: gateway workers reach it
in-process (single worker) or over the /v1 HTTP surface (multi-worker),
mirroring the reference's session-affinity routing (SURVEY.md §7.1 phase 4).
"""

from __future__ import annotations

import gc
import os
import asyncio
import logging
import math
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field, fields, replace
from functools import partial
from typing import Any, AsyncIterator

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.faults import fault_point
from ..observability.logging import trace_extra
from ..observability.timeline import (STALL_S, StepCounts, StepTimeline,
                                      gc_watch)
from .call_layout import CALL_TAIL, CallLayout, Field
from .compile_events import (CompileTracker, install_listener,
                             restore_thread, track_thread)
from .kv import PageAllocator, kv_resident_bytes
from .models import MODEL_CONFIGS, LlamaConfig
from .models import family_of
from .parallel import make_mesh, param_specs
from .roofline import (V5E_HBM_GBPS, V5E_PEAK_BF16_TFLOPS, CostRegistry,
                       roofline_fractions)
from .sampling import SamplingParams, sample_tokens
from .tokenizer import load_tokenizer

logger = logging.getLogger(__name__)
_HEAP_FROZEN = False    # TPUEngine.warmup freezes the build's heap once

# smoothing factor for the tokens-per-dispatch EWMA gauge twin (and the
# signal-bus copy): ~last 10 dispatches dominate, long enough to ride out
# batch-occupancy whipsaw, short enough to track a real load shift
_TPD_EWMA_ALPHA = 0.2


@dataclass
class EngineConfig:
    model: str = "llama3-tiny"
    checkpoint: str = ""
    # identity within an EnginePool (pool/): labels the replica's metrics
    # (TTFT/TPOT/dispatch-gap/KV-bytes) and spans so per-replica SLOs are
    # separable on one dashboard. "0" for a standalone engine.
    replica_id: str = "0"
    max_batch: int = 8              # decode slots
    max_seq_len: int = 2048
    page_size: int = 128
    num_pages: int = 512
    prefill_buckets: tuple[int, ...] = (128, 512, 2048)  # padded dense prefill lengths, at every width; each also gets a width-1 program at half its length for lone short prompts
    prefill_max_batch: int = 4      # admissions fused into one prefill call
    mesh_shape: str = ""
    dtype: str = "bfloat16"
    max_queue: int = 1024
    attn_impl: str = "auto"
    # sequence-parallel long prefill: prompts > sp_threshold tokens route
    # through ring/ulysses attention over the mesh (SURVEY.md §5.7)
    sp_impl: str = "none"      # none|ring|ulysses
    sp_threshold: int = 1024
    # K-step decode SUPER-STEPS (token-loop fusion, ROADMAP item 1 /
    # SnapStream-style dataflow decoding): one jitted lax.scan runs
    # ``superstep`` decode iterations entirely on device — fused
    # sampling, in-loop paged-KV page append over pre-granted pages, and
    # per-slot budget/EOS/stop masking so finished rows FREEZE on device
    # (no post-EOS KV writes, positions stop advancing) — and the host
    # syncs once per K tokens instead of once per token. Composes with
    # decode_overlap (depth-2 pipeline at super-step granularity) and
    # int8 KV; mutually exclusive with spec_decode.
    superstep: int = 1
    # depth-2 overlapped decode pipeline: dispatch step N+1 fed by step
    # N's device-resident sampled tokens while step N's results transfer
    # and emit one step behind, so host bookkeeping (emission, EOS
    # checks, page extension) hides behind device execution instead of
    # serializing with it. Drain barriers (admission, chunk completion,
    # stop/crash) keep token streams identical to the serial path.
    # Ignored when spec_decode is on (the verify step has its own host
    # feedback loop).
    decode_overlap: bool = True
    # seconds to wait for jax backend init before failing fast (0 = forever)
    init_timeout_s: float = 120.0
    # precompile the shape grid at construction (see TPUEngine.warmup)
    warmup: bool = False
    warmup_mode: str = "full"  # full | fast (cold-TPU-friendly subset)
    # prefix cache: reuse resident KV pages for shared full-page prompt
    # prefixes; only each request's suffix pays prefill (vLLM APC analog)
    prefix_cache: bool = True
    # tiered prefix/KV cache (kv/tiers.py, docs/kv_tiering.md): evicted
    # prefix pages SPILL to a bounded host-RAM store (int8 bytes +
    # per-(layer, kv-head) scales; quantize-on-spill under a bf16 pool)
    # with a disk write-behind tier below it, and admission restores
    # tier-resident chain pages into HBM on match (fetch-on-miss). Under
    # an EnginePool the store + prefix index are POOL-SHARED, so a
    # prefix prefilled on any replica serves a hit on every replica.
    # Requires prefix_cache.
    prefix_tiers: bool = False
    tier_host_bytes: int = 256 * 1024 * 1024   # T1 (host RAM) byte budget
    tier_disk_bytes: int = 1024 * 1024 * 1024  # T2 (disk) byte budget; 0 = off
    tier_disk_dir: str = ""                    # "" = private tempdir
    # spill storage mode for FULL-PRECISION pools: "int8" (default)
    # quantizes on spill — 2-4x cheaper tiers, restored pages carry the
    # same small greedy drift as resident int8 KV — or "" to spill in
    # resident precision (lossless round trip, byte-identical
    # continuations guaranteed). An int8-resident pool always spills its
    # bytes verbatim (bit-exact) regardless of this knob.
    tier_spill_quant: str = "int8"
    # spill-tier disk IO hardening (docs/resilience.md): transient
    # read/writeback errors retry this many times with jittered backoff,
    # then the entry quarantines to a clean MISS
    tier_io_retry_max: int = 2
    tier_io_retry_backoff_ms: float = 10.0
    # cross-host prefix-cache fabric (kv/fabric/, docs/cache_fabric.md):
    # T3 object-store hop below disk — "" = no fabric; the namespace
    # qualifies every blob key (tenant isolation by construction)
    tier_object_url: str = ""
    fabric_namespace: str = "shared"
    # speculative decoding via prompt-lookup (n-gram) drafting: decode is
    # HBM-bandwidth-bound (one full param read per step), so verifying
    # spec_k drafted tokens in ONE step multiplies tokens/step by the
    # accept rate for free bandwidth-wise. Greedy rows only; sampled rows
    # ride the same verify step one token at a time. Mutually exclusive
    # with superstep > 1. On TPU the verify runs the Pallas paged
    # CHUNK kernel (same enabling conditions as decode); the remaining
    # trade is K x the attention/MLP compute per dispatch, so low accept
    # rates (non-repetitive output) can still lose — enable for
    # repetitive workloads (summaries, extraction, code edits) and watch
    # stats.spec_tokens.
    spec_decode: bool = False
    spec_k: int = 4          # chunk width: 1 input token + spec_k-1 drafts
    spec_ngram: int = 2      # context n-gram length used for lookup
    # weight-only quantization: "" (full precision) or "int8" — halves the
    # resident param footprint AND the per-step HBM traffic (quantize.py;
    # how Llama-3-8B fits a single 16 GB v5e chip)
    quant: str = ""
    # KV-cache quantization: "" (pages in the engine dtype) or "int8" —
    # pages store int8 with per-page, per-kv-head scales
    # (kv/paged_cache.py), halving decode-attention HBM traffic; the
    # Pallas decode kernel dequantizes in VMEM. ``num_pages`` stays
    # denominated in ENGINE-DTYPE pages (a byte budget): at the same HBM
    # bytes an int8 pool holds ~2x the pages, so _init_kv converts.
    kv_quant: str = ""
    # the model config's ``moe_impl`` for this engine ("" = as registered;
    # models/configs.py): dense | grouped | grouped_pallas. Kept for the
    # CPU rehearsals of tests/benchmark/, whose ``engine`` blocks set it
    # (ROADMAP Queue 3 ``unmeasured-options``); every cell leaves it ""
    moe_impl: str = ""
    # device-fault recovery (SURVEY §5.3): a crashed dispatch thread
    # rebuilds the KV pool, re-queues PENDING requests (mid-stream ones
    # fail — silent retry would duplicate emitted tokens) and restarts
    # itself, at most auto_restart_max times. Off by default: tests and
    # benches prefer fail-fast; production serving turns it on.
    auto_restart: bool = False
    auto_restart_max: int = 3
    # step-introspection ring: per-dispatch summaries (kind, batch shape,
    # duration, tokens) kept for the diagnostics endpoint / admin UI
    step_log_size: int = 256
    # capture XLA cost_analysis() (FLOPs, bytes accessed) per compiled
    # executable at warmup into the engine's CostRegistry — what feeds
    # the live mcpforge_llm_mfu / mcpforge_llm_hbm_roofline_frac gauges.
    # Capture lowers each shape once more through the AOT path (a real
    # compile, amortized by the persistent cache); disable on cold TPUs
    # where warmup time is the binding constraint.
    cost_analysis: bool = True
    # per-chip roofline peaks the live gauges divide by (defaults: v5e)
    peak_tflops_per_chip: float = V5E_PEAK_BF16_TFLOPS
    hbm_gbps_per_chip: float = V5E_HBM_GBPS
    # extra superstep rungs warmed ALONGSIDE superstep so the serving
    # controller (tpu_local/controller.py) can retune K at drain
    # barriers onto pre-compiled executables — a knob move can never
    # trigger a mid-traffic XLA compile. () = no extra rungs: the
    # decode grid is exactly the static-K grid (controller-off builds
    # compile nothing new and behave bit-identically).
    k_ladder: tuple[int, ...] = ()

    def k_rungs(self) -> tuple[int, ...]:
        """Superstep values the warmup decode grid compiles: the static
        superstep plus every configured ladder rung, deduped and
        ascending. Adaptive K only ever moves along this set."""
        rungs = {self.superstep}
        rungs.update(int(k) for k in self.k_ladder if int(k) >= 1)
        return tuple(sorted(rungs))

    @classmethod
    def from_settings(cls, settings) -> "EngineConfig":
        """Every field from its ``tpu_local_<field>`` setting, but for the
        few in ``_SETTING_OF``. A setting that is missing is an
        ``AttributeError`` here, at start-up: a default lives in ``Settings``
        and on the field, nowhere else."""
        values = {}
        for spec in fields(cls):
            source = _SETTING_OF.get(spec.name, "tpu_local_" + spec.name)
            if source is None:
                continue
            value = (source(settings) if callable(source)
                     else getattr(settings, source))
            values[spec.name] = (tuple(value) if isinstance(spec.default, tuple)
                                 else value)
        return cls(**values)


# EngineConfig fields that are not read from ``tpu_local_<field>``: another
# setting's name, a function of the settings, or None for a field that no
# setting reaches (a pool names its replicas; a test or a bench picks the
# attention implementation)
_SETTING_OF: dict[str, Any] = {
    "replica_id": None,
    "attn_impl": None,
    "tier_io_retry_max": "tier_io_retry_max",
    "tier_io_retry_backoff_ms": "tier_io_retry_backoff_ms",
    # extra K rungs only when the controller is on: off keeps the warmup
    # grid — and therefore compile count and serving behavior —
    # bit-identical to a pre-controller build
    "k_ladder": lambda settings: (settings.controller_k_ladder
                                  if settings.controller_enabled else ()),
}


@dataclass
class GenRequest:
    request_id: str
    prompt_ids: list[int]
    max_tokens: int = 128
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_ids: tuple[int, ...] = ()
    # admission class (SURVEY §7.2 #2 latency budget): 0 = interactive
    # (chat turns, agent hops), 1 = background (summaries, batch work).
    # Lower admits first when slots are contended; decode itself is shared
    # continuous batching, so a class never starves once admitted.
    priority: int = 0
    # unbounded: tokens are ints bounded by max_tokens, and a bounded queue
    # could drop the end-of-stream sentinel and hang the consumer
    stream: asyncio.Queue = field(default_factory=asyncio.Queue)
    created: float = field(default_factory=time.time)
    # the same instant on the step timeline's clock (perf_counter): the
    # twin of ``created``, so pool shadows inherit both and a failover
    # continuation's queue wait and TTFT still span the failed attempt.
    # The engine stamps the others: slot won, first token, that token
    # handed to the loop by the dispatch thread's flush, put into ``stream``
    # on the loop's thread, retired.
    # queue_ms / prefill_ms and the llm.* span durations derive from these
    t_submit: float = field(default_factory=time.perf_counter)
    t_admit: float = 0.0
    t_first: float = 0.0
    t_emit: float = 0.0
    t_deliver: float = 0.0
    t_done: float = 0.0
    # filled by the engine
    slot: int = -1
    generated: list[int] = field(default_factory=list)
    # a family that drafts on the device (models/__init__.py:
    # drafts_on_device): (the position the draft is a guess for, the token).
    # Kept by position, so a draft that a plain decode step has passed is
    # simply not taken
    draft: tuple[int, int] | None = None
    finish_reason: str | None = None
    prefill_ms: float = 0.0
    queue_ms: float = 0.0
    # prefix-cache admission state: probed cached-history length and the
    # (suffix) bucket; bucket -1 means not yet probed. The probe takes no
    # page references — the real match happens at admission. ``chunked``
    # marks prompts whose (suffix) length exceeds every bucket: they
    # prefill in multiple bucket-sized chunks through the history path.
    hist: int = 0
    bucket: int = -1
    chunked: bool = False
    chunk_pos: int = 0   # tokens prefilled so far (chunk-round scheduler)
    # the length its prefill dispatch was padded to: its bucket, or half of
    # it where it went alone through the half-length program (0: not yet)
    prefill_len: int = 0
    # billing identity (observability/tenant.py resolution order:
    # team → API key → user; "" = unattributed internal work). Rides
    # into the engine so retire-time accounting lands in the tenant
    # ledger, survives pool failover (shadows copy it), and labels the
    # TTFT/TPOT/queue-wait histograms (clamped)
    tenant: str = ""
    # telemetry: (trace_id, span_id) of the submitter's llm.request span —
    # the dispatch thread parents llm.queue/prefill/decode spans to it
    trace_ctx: tuple[str, str] | None = None
    # routing class for role-specialized pools (docs/disaggregation.md):
    # "" = classify by shape (prompt length) at the pool router; a
    # non-empty value pins the request to replicas holding that role
    # ("prefill"/"decode" for the phase split, or any fleet class such
    # as a tenant SLO tier / model size behind the same field)
    route_class: str = ""
    # once-only guard: crash-recovery requeues pass admission twice, and
    # the queue span/histogram must not double-observe the request
    queue_observed: bool = False
    # same pattern for the first-token surfaces: a pool-failover
    # continuation whose original attempt already emitted tokens must not
    # observe a second TTFT sample (it would span the failed attempt +
    # failover) or re-emit llm.prefill for the same logical request
    ttft_observed: bool = False


class EngineStats:
    def __init__(self) -> None:
        self.requests = 0
        self.prompt_tokens = 0
        self.completion_tokens = 0
        self.decode_steps = 0
        self.decode_dispatches = 0    # device dispatches (= host syncs);
        #                               decode_steps / decode_dispatches ≈ K
        self.prefill_batches = 0
        self.prefill_requests = 0
        # host-to-device transfers made for dispatches: the one packed call
        # of each (call_layout.py), and a table sync where rows were dirty
        self.host_uploads = 0
        # what dense prefills (no history, no chunk round) carried and what
        # they ran: prompt tokens, positions dispatched (padded rows x padded
        # length; 1 - tokens / positions is the padding share), and the
        # dispatches that took a bucket's half-length program
        self.dense_prefill_tokens = 0
        self.dense_prefill_positions = 0
        self.half_prefill_batches = 0
        self.queue_depth = 0
        self.spec_steps = 0      # speculative verify dispatches
        self.spec_tokens = 0     # extra tokens emitted beyond 1/step
        # a family that drafts on the device counts there: rows of verify
        # steps that carried a draft, and drafts the step's samples bore out
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.prefill_ms_total = 0.0   # host wall of prefill dispatches (build -> first tokens on host)
        self.decode_ms_total = 0.0    # per-step decode wall: retire-to-retire under overlap
        self.engine_restarts = 0      # crash-recovery restarts (auto_restart)
        self.chunking = 0             # long prompts mid-chunk-prefill
        self.overlap_steps = 0        # decode dispatches fed from device tokens
        self.pipeline_drains = 0      # overlap barriers that forced a drain
        # flushes made EARLY, for first tokens alone: after a prefill or a
        # chunk round, ahead of the iteration's decode or verify dispatch
        self.first_flushes = 0
        self.dispatch_gap_ms_total = 0.0  # host-side stall between dispatches
        # host-fed dispatches whose build.t0 -> dispatch.t1 passed
        # timeline.STALL_S (each also logged, with the part that held it)
        self.dispatch_stalls = 0
        # counted on the device by families whose step programs do
        # (models/deepseek.py), read back with each step's tokens
        self.moe_tokens = 0           # tokens through expert layers
        self.moe_local_pairs = 0      # token-expert pairs on held experts
        # steps whose expert FFN took each formulation (the family's
        # ``expert_path``: static a program, so counted at dispatch)
        self.moe_grouped_steps = 0    # chosen experts only, row-blocks
        self.moe_scan_steps = 0       # every held expert, gate-masked
        # prefill dispatches and chunk rounds of a family with per-sequence
        # state by the body their delta-rule kernel took (the family's
        # ``delta_body``: a rule of the bucket's shape, static a program, so
        # counted at dispatch as the two above)
        self.delta_chunkwise_steps = 0  # whole chunks on the MXU
        self.delta_walk_steps = 0       # token by token
        # sampled steps by the work their rows' parameters asked of
        # ``sample_tokens`` (counted on the host from the same rows)
        self.sample_argmax_steps = 0    # no row samples: the argmax alone
        self.sample_plain_steps = 0     # some row samples, none filters
        self.sample_filtered_steps = 0  # some sampled row has a top-k / top-p
        # per-sequence state rows (a family with "sequence" cache pools):
        # rows live now, rows the pool holds beside the trash row, and the
        # real tokens the recurrence scanned (counted on the device)
        self.state_rows_in_use = 0
        self.state_rows_total = 0
        self.state_scanned_tokens = 0
        # a family with window layers (counted on the device, decode steps
        # and chunk rounds): the live rows' summed context, and what of it
        # their window layers see (min(context, window) a row)
        self.context_keys = 0
        self.window_keys = 0
        # a family whose decode dispatch is a BLOCK step (models/sdar.py):
        # dispatches, the forward passes they made that sampled (the commit
        # pass of each dispatch is not one of them), the tokens they emitted,
        # and the positions filled because their confidence passed the
        # threshold (the rest were filled by rank)
        self.block_steps = 0
        self.denoise_passes = 0
        self.block_tokens = 0
        self.block_positions_filled_by_threshold = 0


def _named(jitted, name: str):
    """Name the ``functools.partial`` a step function was jitted through
    after the step function itself, before its first trace: the compiled
    module is then ``jit_<name>`` in a profiler trace, not ``jit__unknown``
    (bucket and width are not in the name). The jit call keeps the
    ``jax.jit(partial(f, ...))`` shape the linter's jit rules read."""
    jitted.__wrapped__.__name__ = name
    return jitted


def _beside(tokens, counts):
    """What a prefill step program returns beside ``kv``: the sampled tokens
    alone (the GQA family: the program it always was), or ``(tokens,
    counts)`` for a family whose step functions count on the device
    (``STEP_AUX``); :func:`_apart` splits the host's copy again."""
    return (tokens, *counts) if counts else tokens


def _apart(host_out):
    return host_out if isinstance(host_out, tuple) else (host_out,)


def _sample_every_position(logits, sampling: SamplingParams, key):
    """A verify step's samples: logits [B, K, V] -> [B, K] tokens, each
    position of a row under the row's sampling parameters."""
    B, K, V = logits.shape
    samp = SamplingParams(jnp.repeat(sampling.temperature, K),
                          jnp.repeat(sampling.top_k, K),
                          jnp.repeat(sampling.top_p, K))
    return sample_tokens(logits.reshape(B * K, V), samp, key).reshape(B, K)


class EngineInitTimeout(RuntimeError):
    """jax backend init exceeded the watchdog budget (dead TPU runtime)."""


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path — a cache directory that moves (per
# host, per pid, per boot) is never found again. Listed in .gitignore.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def apply_compile_cache() -> str | None:
    """Place JAX's persistent compilation cache; engine construction calls
    this. One rule: where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is
    placed from outside and this code sets no directory at all; where it
    is not, the cache lives at ``<checkout>/.jax_cache``. Returns the
    directory in use, or None when the cache is switched off
    (``jax_enable_compilation_cache=False`` — the test suite's setting, so
    a checkout does not fill with CPU executables)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    if os.environ.get(COMPILE_CACHE_ENV):
        return os.environ[COMPILE_CACHE_ENV]
    if jax.config.jax_compilation_cache_dir != _CHECKOUT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR


def probe_devices(timeout_s: float) -> list:
    """``jax.devices()`` under a watchdog that can only FAIL.

    A wedged accelerator runtime (for one, a chip another process holds)
    can block backend init indefinitely inside the PJRT client
    constructor; run it on a daemon thread so a hang becomes a
    diagnosable exception instead of a gateway that never binds its port.
    There is no other outcome: the devices the backend reports, or an
    error — never a quiet move to another platform. On success the
    backend is cached process-wide, so every later jax call returns
    instantly.
    """
    if timeout_s <= 0:
        return jax.devices()
    result: dict[str, Any] = {}

    def _probe() -> None:
        try:
            result["devices"] = jax.devices()
        except Exception as exc:  # surfaced on the caller thread
            result["error"] = exc

    t = threading.Thread(target=_probe, name="tpu-init-probe", daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise EngineInitTimeout(
            f"jax backend init did not complete within {timeout_s:.0f}s — "
            "is another process holding the chip? (one process per chip; "
            "raise MCPFORGE_TPU_LOCAL_INIT_TIMEOUT_S for a slow runtime, or "
            "set MCPFORGE_TPU_LOCAL_ENABLED=false to serve without the "
            "engine)")
    if "error" in result:
        raise result["error"]
    return result["devices"]


class TPUEngine:
    """Owns params + KV pool on the mesh; device syncs run on the dispatch
    thread, token emission hops back to the asyncio loop."""

    # static stop-id columns the super-step's on-device freeze checks:
    # column 0 is always EOS, the rest carry a request's first stop_ids.
    # STATIC so one compiled super-step serves every request mix; rows
    # with more stop ids stay host-detected (the device merely fails to
    # freeze early — streams are unaffected, see _decode_and_sample)
    _STOP_TBL_WIDTH = 4

    def __init__(self, config: EngineConfig, tracer=None, metrics=None,
                 devices: list | None = None, ledger=None,
                 tier_store=None, prefix_index=None, signals=None):
        # telemetry handles are optional: None means zero-cost no-ops, so
        # unit tests and benches constructing engines directly pay nothing
        self.tracer = tracer
        self.metrics = metrics
        # live signal bus (observability/signals.py): retire-site pushes
        # feed the serving controller; None = every publish site is a
        # single attribute check. Assignable post-construction too (the
        # gateway wires the bus after the pool builds its replicas).
        self.signals = signals
        # per-tenant usage ledger (observability/metering.py): fed at the
        # SAME sites as the untagged stats counters so per-tenant sums
        # conserve exactly against stats.prompt_tokens /
        # completion_tokens / allocator.prefix_hit_tokens
        self.ledger = ledger
        self.step_log: deque[dict[str, Any]] = deque(
            maxlen=max(1, config.step_log_size))
        # the one source of step time on the dispatch thread: phase spans,
        # one record per device dispatch, request stamps — all on the
        # profiler's clock (observability/timeline.py)
        self.timeline = StepTimeline(config.replica_id)
        if metrics is not None:
            metrics.watch_gc(gc_watch)      # mcpforge_gc_pause_seconds
        if config.superstep < 1:
            raise ValueError(
                f"superstep must be >= 1, got {config.superstep}")
        if config.spec_decode and config.superstep > 1:
            raise ValueError("spec_decode and superstep>1 are mutually "
                             "exclusive (both widen the per-dispatch step)")
        if config.spec_decode and any(int(k) > 1 for k in config.k_ladder):
            raise ValueError("k_ladder rungs > 1 are mutually exclusive "
                             "with spec_decode (same exclusivity as "
                             "superstep > 1)")
        if config.spec_decode and config.spec_k < 2:
            raise ValueError(f"spec_k must be >= 2, got {config.spec_k}")
        if config.spec_decode and config.spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {config.spec_ngram}")
        if config.prefix_tiers and not config.prefix_cache:
            raise ValueError("prefix_tiers requires prefix_cache (the tiers "
                             "spill and restore prefix-cache pages)")
        if config.tier_spill_quant not in ("", "int8"):
            raise ValueError(f"unsupported tier_spill_quant mode "
                             f"{config.tier_spill_quant!r}")
        self.config = config
        # tiered prefix cache (kv/tiers.py): bind to the POOL-SHARED
        # store/index when an EnginePool passed them, else own a private
        # store (standalone engine). A client with only an index still
        # publishes HBM residency so the pool router can score affinity
        # across replicas even with the spill tiers off.
        self._owned_tier_store = None
        self._tier_client = None
        if config.prefix_cache and (config.prefix_tiers
                                    or prefix_index is not None
                                    or tier_store is not None):
            from .kv.tiers import TierClient, TieredPageStore
            store = tier_store
            if store is None and config.prefix_tiers:
                from .kv.fabric.object_store import object_store_or_none
                store = TieredPageStore(
                    host_bytes=config.tier_host_bytes,
                    disk_bytes=config.tier_disk_bytes,
                    disk_dir=config.tier_disk_dir,
                    index=prefix_index, metrics=metrics,
                    io_retry_max=config.tier_io_retry_max,
                    io_retry_backoff_ms=config.tier_io_retry_backoff_ms,
                    object_store=object_store_or_none(
                        config.tier_object_url),
                    object_namespace=config.fabric_namespace)
                self._owned_tier_store = store
            self._tier_client = TierClient(config.replica_id, store=store,
                                           index=prefix_index,
                                           metrics=metrics, tracer=tracer)
        # dispatch-side export snapshot for the per-tier hit counters
        self._tier_hits_exported: dict[str, int] = {}  # lint: thread[dispatch]
        # the fused super-step width every decode dispatch scans over
        # (1 = the classic one-token step); resolved once — the compiled
        # grid is keyed on it
        self._k = config.superstep
        self.compile_cache_dir = apply_compile_cache()
        self.model_config: LlamaConfig = MODEL_CONFIGS[config.model]
        if config.moe_impl:
            self.model_config = replace(self.model_config,
                                        moe_impl=config.moe_impl)
        # the model family (models/__init__.py): the module whose step
        # functions, weight tree and cache pools serve this config's class
        self._family = family_of(self.model_config)
        # positions a decode dispatch fills a row where the family's step
        # is a block step (models/__init__.py: STEP_KIND), else 0: what the
        # scheduler asks the family, once
        self._block = (self.model_config.block_length
                       if self._family.STEP_KIND == "block" else 0)
        # ... whose prefill programs run no head (nothing is sampled there)
        self._prefill_head = {"head": False} if self._block else {}
        # where a speculative draft comes from (models/__init__.py:
        # drafts_on_device), asked once: True, the family drafts beside every
        # prefill, chunk round and verify step; False, prompt lookup
        self._drafts = bool(config.spec_decode and getattr(
            self._family, "drafts_on_device", lambda _config: False)(
                self.model_config))
        if self._drafts:    # ... and its prefill programs hand it their hiddens
            self._prefill_head = {"hidden": True}
        self.tokenizer = load_tokenizer(config.checkpoint,
                                        vocab_size=self.model_config.vocab_size)
        self.stats = EngineStats()
        self._work: queue.Queue[GenRequest] = queue.Queue(maxsize=config.max_queue)
        self._pending: deque[GenRequest] = deque()   # lint: thread[dispatch]
        self._running: dict[int, GenRequest] = {}    # slot -> request  # lint: thread[dispatch]
        self._chunking: dict[int, GenRequest] = {}   # mid-chunk-prefill  # lint: thread[dispatch]
        self._thread: threading.Thread | None = None
        self._stop_event = threading.Event()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._started = False
        self._killed = False
        # overlapped decode pipeline state (dispatch thread only): the
        # dispatched-but-not-yet-emitted decode step, if any
        self._inflight: dict[str, Any] | None = None  # lint: thread[dispatch]
        # submit-side wakeup: the dispatch thread blocks here when idle
        # instead of polling with time.sleep (satellite: idle wakeup
        # latency and idle CPU both drop)
        self._wake = threading.Event()
        # step emission buffer: tokens accumulate here during a step and
        # flush to the asyncio loop in ONE call_soon_threadsafe per step
        self._emit_buf: list[list[Any]] = []  # lint: thread[dispatch]
        # the buffer holds a request's first token (an entry with tokens
        # whose request nothing has been flushed of yet)
        self._emit_first = False  # lint: thread[dispatch]
        # dispatch-gap telemetry: (gap_s, step_wall_s) per decode step
        self._gap_window: deque[tuple[float, float]] = deque(maxlen=256)  # lint: thread[dispatch]
        # liveness heartbeat: bumped once per dispatch-loop iteration (the
        # idle wait is bounded at 50 ms, so a healthy engine beats at
        # >=20 Hz even with no traffic). The pool's health monitor reads
        # its AGE to tell a wedged device call from an idle engine.
        self._heartbeat_ts = time.monotonic()  # lint: thread[dispatch]
        # cancellation handoff: request ids the loop side asked to
        # terminate; the dispatch thread consumes them at the top of each
        # iteration (request_cancel is the only other writer, lock-guarded)
        self._cancels: set[str] = set()  # lint: thread[dispatch]
        self._cancel_lock = threading.Lock()  # lint: lock[dispatch]
        # serving-knob handoff (tpu_local/controller.py): loop-side
        # callers stage validated knob moves under the lock; the dispatch
        # thread consumes them at the top of its iteration, DRAINING the
        # overlap pipeline first when K changes — knob moves only ever
        # land at drain barriers, so greedy token streams match a run
        # that used the new posture from that barrier on
        self._pending_knobs: dict[str, Any] = {}  # lint: thread[dispatch]
        self._knob_lock = threading.Lock()  # lint: lock[dispatch]
        # chain-export handoff (pool KV migration, docs/disaggregation.md):
        # the pool stages (prompt_ids, future) pairs; the dispatch thread
        # consumes them at its drain barrier — device page reads are
        # dispatch-thread-only, and exporting at the barrier guarantees
        # the prefill leg's pages are fully retired before they spill
        self._pending_exports: list[tuple[tuple[int, ...],
                                          "Future"]] = []  # lint: thread[dispatch]
        self._export_lock = threading.Lock()  # lint: lock[dispatch]
        # runtime spec-decode gate (the controller's on/off knob): plain
        # decode is always warmed as the fallback path, so flipping this
        # never compiles; engines built without spec_decode ignore it
        self._spec_enabled = True  # lint: thread[dispatch]
        # superstep rungs the warmup grid compiled; adaptive K may only
        # select these (request_knobs rejects anything else)
        self._warmed_k: set[int] = set()  # lint: thread[dispatch]
        # EWMA twin of the tokens-per-dispatch gauge (the instantaneous
        # value whipsaws with batch occupancy; smoothed form is what the
        # signal bus and alerts act on)
        self._tpd_ewma: float | None = None  # lint: thread[dispatch]
        # last publish of O(window) signals (idle fraction): bounded tick
        self._signals_slow_ts = 0.0  # lint: thread[dispatch]
        self._phase_observers: dict[str, Any] = {}  # lint: thread[dispatch]
        # live roofline state: the window backs roofline_snapshot(), and
        # the cost registry holds warmup-captured XLA cost_analysis()
        self._roofline_window: deque[tuple[float, float, float]] = \
            deque(maxlen=256)  # lint: thread[dispatch]
        self.cost_registry = CostRegistry()
        # step family -> attention implementation its trace chose
        # ("pallas" | "gather" | "reference" | ring/ulysses), written at
        # trace time by the device fns
        self.attn_traced: dict[str, str] = {}
        # XLA compile tracking: every backend compile on a registered
        # thread (dispatch = "serving", warmup callers = "warmup") counts
        # + times itself; a serving-stage compile on a warmed engine is
        # the PR-5 mid-traffic-compile catastrophe resurfacing
        self.compile_tracker = CompileTracker(self._on_xla_compile)
        install_listener()
        # the build window compiles for real (param init, KV-state
        # placement, config.warmup's grid): attribute it all to the
        # "warmup" stage so the every-engine-compile-is-attributed
        # contract holds from construction on
        ctor_token = track_thread(self.compile_tracker, "warmup")
        try:
            self._build_device_state(devices)
        finally:
            restore_thread(ctor_token)

    def _build_device_state(self, devices) -> None:
        """Mesh + params + KV pool + jitted-step tables (the compile-heavy
        tail of construction; runs under the constructor's warmup-stage
        compile attribution)."""
        config = self.config
        dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        # an EnginePool passes each replica its device subset; a standalone
        # engine owns every device the (watchdogged) backend reports
        if devices is None:
            devices = probe_devices(config.init_timeout_s)
        self.mesh = make_mesh(config.mesh_shape, devices=devices)
        logger.info("tpu_local: mesh %s, model %s", self.mesh.shape, config.model)
        refused = self._family.refusals(self.model_config, config, self.mesh,
                                        tiers=self._tier_client is not None)
        if refused:
            raise NotImplementedError(
                f"model {config.model!r} ({type(self.model_config).__name__}) "
                f"cannot be served with: " + "; ".join(refused))
        if config.sp_impl != "none":
            # SP shard_map requires the sequence (bucket) to divide the axis;
            # reject at construction instead of killing the dispatch thread
            # on the first long prefill
            axis = self.mesh.shape.get("model", 1)
            bad = [b for b in config.prefill_buckets
                   if b > config.sp_threshold and b % axis != 0]
            if bad:
                raise ValueError(
                    f"sp_impl={config.sp_impl!r}: prefill buckets {bad} not"
                    f" divisible by mesh model axis {axis}")

        if config.quant not in ("", "int8"):
            raise ValueError(f"unsupported quant mode {config.quant!r}")
        if config.kv_quant not in ("", "int8"):
            raise ValueError(
                f"unsupported kv_quant mode {config.kv_quant!r}")
        if config.moe_impl not in ("", "dense", "grouped", "grouped_pallas"):
            # a typo must not silently serve the dense path (and make a
            # hardware A/B compare dense against dense)
            raise ValueError(
                f"moe_impl must be dense|grouped|grouped_pallas, "
                f"got {config.moe_impl!r}")
        # params: load checkpoint or random-init, placed with TP shardings;
        # quant="int8" swaps in the {"q","s"} tree (quantize.py)
        with self.mesh:
            logical = self._family.params_logical(self.model_config)
            if config.quant == "int8":
                from .quantize import quantize_logical
                shardings = param_specs(quantize_logical(logical), self.mesh)
            else:
                shardings = param_specs(logical, self.mesh)
            if config.checkpoint:
                from .checkpoint import load_params
                self.params = load_params(config.checkpoint, self.model_config,
                                          shardings, dtype, quant=config.quant)
            else:
                self.params = self._init_params(logical, shardings, dtype)

            self._kv_dtype = dtype
            self._init_kv()

        # what a dispatch hands its step program beside params and kv: ONE
        # packed call (call_layout.py), replicated over the mesh as the
        # block table is, and the base key, which stays on the device; the
        # program folds the dispatch's number (a column of the call) into
        # it, so no two dispatches share a key and none costs a key-split
        # program
        self._call_sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        self._rng = jax.device_put(
            jax.random.PRNGKey(int(time.time()) & 0x7FFFFFFF),
            self._call_sharding)
        self._dispatch_no = 0  # lint: thread[dispatch]
        self._uploads_claimed = 0  # lint: thread[dispatch]
        i32 = np.int32
        decode_rows = (Field("positions", 0, i32), Field("seq_lens", 0, i32),
                       Field("budgets", 0, i32),
                       Field("stop_tbl", self._STOP_TBL_WIDTH, i32, -1))
        self._decode_call = CallLayout(Field("tokens", 0, i32), *decode_rows,
                                       *CALL_TAIL)
        # a device-fed step takes its tokens from the step in flight
        self._decode_fb_call = CallLayout(*decode_rows, *CALL_TAIL)
        self._block_call = CallLayout(
            Field("tokens", self._block, i32, self.model_config.mask_token_id),
            Field("positions", self._block, i32, -1),
            Field("masked", self._block, np.bool_),
            *CALL_TAIL) if self._block else None
        self._verify_call = CallLayout(
            Field("tokens", config.spec_k, i32),
            Field("positions", config.spec_k, i32, -1),
            *CALL_TAIL) if config.spec_decode else None
        self._prefill_calls: dict[int, CallLayout] = {}

        # compiled steps
        self._prefill_sample = _named(
            jax.jit(self._of_call(self._prefill_and_sample,
                                  self._prefill_call_of_width),
                    donate_argnames=("kv",)), "_prefill_and_sample")
        self._prefill_sample_sp = (
            _named(jax.jit(partial(self._of_call(self._prefill_and_sample,
                                                 self._prefill_call_of_width),
                                   sp=True),
                           donate_argnames=("kv",)), "_prefill_and_sample")
            if config.sp_impl != "none" else None)
        self.half_lengths = self._find_half_lengths()
        # decode compiles per (superstep K, context-width bucket) pair:
        # attention reads only the table columns the longest active row
        # needs — the full-width gather wastes ~max_context/actual_context
        # x HBM bandwidth on short conversations, and decode is
        # bandwidth-bound
        self._decode_fns: dict[tuple[int, int], Any] = {}
        # device-token-feedback decode (overlapped pipeline steady state):
        # same grid as _decode_fns, but the input token comes from the
        # PREVIOUS dispatch's on-device sampled block instead of the host
        self._decode_fb_fns: dict[tuple[int, int], Any] = {}
        # a block family's decode grid: context-width bucket -> its block
        # step (and then neither dict above ever fills)
        self._block_fns: dict[int, Any] = {}
        # the chunk/history prefill is a core primitive (prefix-cache hits
        # AND chunked prefill of prompts longer than the largest bucket);
        # compiled per context-width bucket like decode (a hit with 40
        # resident tokens must not pay full-table-width attention)
        self._prefill_hist_fns: dict[int, Any] = {}
        self._verify_fns: dict[int, Any] | None = (
            {} if config.spec_decode else None)
        # spill-tier device I/O (one compiled scatter/gather per direction;
        # the page index rides as a traced scalar so every page shares it)
        self._tier_read_fn = None
        self._tier_write_fn = None
        if self._tier_client is not None and self._tier_client.store is not None:
            self._build_tier_fns()
            self._tier_client.read_fn = self._read_page_payload
            self._tier_client.write_fn = self._upload_page
        if config.warmup:
            self.warmup()

    def _init_params(self, logical, shardings, dtype) -> dict[str, Any]:
        """Random weights from the fixed seed, built LAYER BY LAYER: each
        jitted call makes one layer in ``dtype`` and (under quant) hands
        back its int8 twin, so the peak on the device is the tree being
        kept plus one layer of scratch — never the whole full-precision
        tree, which for a 7B model is the chip's entire HBM. Every layer
        shares one executable (same shapes), so a 32-layer init compiles
        two small programs."""
        cfg = self.model_config
        quant = self.config.quant == "int8"

        def finish(tree, tree_logical):
            if not quant:
                return tree
            from .quantize import quantize_tree
            return quantize_tree(tree, tree_logical, scale_dtype=dtype)

        trunk_logical = {k: v for k, v in logical.items() if k != "layers"}
        trunk_shardings = {k: v for k, v in shardings.items()
                           if k != "layers"}
        family = self._family
        # one executable per KIND of layer (a family whose layers differ —
        # leading dense layers before expert layers — names them)
        layer_fns: dict[str, Any] = {}

        def layer_fn(i: int):
            kind = family.layer_kind(cfg, i)
            if kind not in layer_fns:
                layer_fns[kind] = jax.jit(
                    lambda key: finish(
                        family.init_layer(cfg, key, dtype, kind=kind),
                        logical["layers"][i]),
                    out_shardings=shardings["layers"][i])
            return layer_fns[kind]

        trunk_fn = jax.jit(
            lambda ek, hk: finish(family.init_trunk(cfg, ek, hk, dtype),
                                  trunk_logical),
            out_shardings=trunk_shardings)
        keys = family.init_keys(cfg, jax.random.PRNGKey(0))
        params = trunk_fn(keys[-2], keys[-1])
        params["layers"] = [layer_fn(i)(keys[i]) for i in range(cfg.n_layers)]
        return params

    def _build_tier_fns(self) -> None:
        """Jitted device I/O for the spill tiers: a one-page device->host
        read (quantize-on-spill under a bf16/f32 pool — the same int8 +
        per-(layer, kv-head) running-max scheme the resident int8 mode
        uses; an int8 pool spills its resident bytes + scales verbatim,
        so its T1/T2 round trip is bit-exact) and the inverse host->device
        upload (dequantize-on-restore for full-precision pools). Warmup
        exercises both so a first spill/restore mid-traffic never
        compiles on the serving path."""
        from .quantize import kv_dequantize, kv_int8_scale, kv_quantize

        if self.config.kv_quant == "int8":
            def read(kv, idx):
                return (kv.k_pages[:, idx], kv.v_pages[:, idx],
                        kv.k_scales[:, idx].astype(jnp.float32),
                        kv.v_scales[:, idx].astype(jnp.float32))

            def write(kv, idx, k, v, ks, vs):
                return kv._replace(
                    k_pages=kv.k_pages.at[:, idx].set(k),
                    v_pages=kv.v_pages.at[:, idx].set(v),
                    k_scales=kv.k_scales.at[:, idx].set(
                        ks.astype(kv.k_scales.dtype)),
                    v_scales=kv.v_scales.at[:, idx].set(
                        vs.astype(kv.v_scales.dtype)))
        elif self.config.tier_spill_quant == "":
            # resident-precision spill (tier_spill_quant=""): payloads
            # carry the page values as float32 (a lossless container for
            # bf16/f32 residents), so the round trip is byte-identical
            # at 2-4x the tier footprint of int8
            def read(kv, idx):
                scales = jnp.ones(
                    (kv.k_pages.shape[0], kv.k_pages.shape[3]), jnp.float32)
                return (kv.k_pages[:, idx].astype(jnp.float32),
                        kv.v_pages[:, idx].astype(jnp.float32),
                        scales, scales)

            def write(kv, idx, k, v, ks, vs):
                dt = kv.k_pages.dtype
                return kv._replace(
                    k_pages=kv.k_pages.at[:, idx].set(k.astype(dt)),
                    v_pages=kv.v_pages.at[:, idx].set(v.astype(dt)))
        else:
            def _quant(page):  # [L, page, KV, hd] -> (int8, [L, KV] scales)
                amax = jnp.max(jnp.abs(page.astype(jnp.float32)),
                               axis=(1, 3))
                scales = kv_int8_scale(amax)
                return (kv_quantize(page, scales[:, None, :, None]),
                        scales.astype(jnp.float32))

            def read(kv, idx):
                kq, ks = _quant(kv.k_pages[:, idx])
                vq, vs = _quant(kv.v_pages[:, idx])
                return kq, vq, ks, vs

            def write(kv, idx, k, v, ks, vs):
                dt = kv.k_pages.dtype
                return kv._replace(
                    k_pages=kv.k_pages.at[:, idx].set(
                        kv_dequantize(k, ks[:, None, :, None], dt)),
                    v_pages=kv.v_pages.at[:, idx].set(
                        kv_dequantize(v, vs[:, None, :, None], dt)))

        self._tier_read_fn = jax.jit(read)
        self._tier_write_fn = jax.jit(write, donate_argnames=("kv",))

    def _read_page_payload(self, page: int):
        """Device->host read of one prefix page for spilling. Dispatch
        thread only; runs at eviction time (admission/grow under page
        pressure), and the payload must leave HBM before the page's new
        tenant overwrites it."""
        from .kv.tiers import SpilledPage
        out = self._tier_read_fn(self.kv, jnp.asarray(page, jnp.int32))
        k, v, ks, vs = jax.device_get(out)  # lint: allow[host-sync-in-hot-path] spill-on-evict: the evicted page's bytes must be read before its new tenant overwrites them
        return SpilledPage(chunk=(), parent=b"", k=np.asarray(k),
                           v=np.asarray(v), k_scales=np.asarray(ks),
                           v_scales=np.asarray(vs))

    def _upload_page(self, page: int, payload) -> None:
        """Host->device upload of a restored page into this replica's
        pool (fetch-on-miss inside the admission allocate path; dispatch
        thread, pipeline already drained by the admission barrier)."""
        # np.asarray normalizes pinned-host payloads too: every call sees
        # the same (shape, dtype, uncommitted-numpy) signature, so the
        # warmup-compiled executable serves all of them (zero mid-traffic
        # compiles — the pool wedge monitor depends on that invariant)
        self.kv = self._tier_write_fn(
            self.kv, jnp.asarray(page, jnp.int32),
            np.asarray(payload.k), np.asarray(payload.v),
            np.asarray(payload.k_scales), np.asarray(payload.v_scales))

    def _init_kv(self) -> None:
        """(Re)build the KV pool + allocator on the mesh — used at
        construction and by crash recovery (a fault inside a jitted call
        may have consumed the donated kv buffers).

        ``config.num_pages`` is a BYTE budget denominated in engine-dtype
        pages: under ``kv_quant="int8"`` the same bytes hold ~2x the
        pages (1 byte/element + a per-page scale sliver), so the pool and
        allocator are sized by the converted, dtype-aware page count."""
        config = self.config
        max_pages_per_slot = config.max_seq_len // config.page_size
        from .kv import (kv_pools, kv_state_bytes, num_pages_for_budget,
                         state_rows_for)
        from .parallel.sharding import (kv_pages_sharding, kv_scales_sharding,
                                        logical_to_sharding)
        # bytes one page costs under the ACTIVE storage mode (gauge unit)
        kv_page_bytes = self._family.kv_page_bytes
        self._kv_page_bytes = kv_page_bytes(
            self.model_config, config.page_size, self._kv_dtype,
            config.kv_quant)
        if config.kv_quant:
            budget = config.num_pages * kv_page_bytes(
                self.model_config, config.page_size, self._kv_dtype)
            self.num_kv_pages = num_pages_for_budget(
                self.model_config, config.page_size, budget,
                self._kv_dtype, config.kv_quant)
        else:
            self.num_kv_pages = config.num_pages
        with self.mesh:
            # kv_logical is the single source of the state's structure;
            # the page/scale rules route through the divisibility-aware
            # helpers (kv heads that don't divide the TP degree replicate)
            def to_sharding(name: str):
                if name == "kv_pages":
                    return kv_pages_sharding(self.mesh,
                                             self.model_config.n_kv_heads)
                if name == "kv_scales":
                    return kv_scales_sharding(self.mesh,
                                              self.model_config.n_kv_heads)
                return logical_to_sharding(name, self.mesh)

            kv_shardings = jax.tree.map(
                to_sharding, self._family.kv_logical(
                    config.kv_quant, config=self.model_config))
            kv_init = jax.jit(partial(
                self._family.init_kv_state, self.model_config,
                self.num_kv_pages,
                config.page_size, config.max_batch, max_pages_per_slot,
                dtype=self._kv_dtype, quant=config.kv_quant),
                out_shardings=kv_shardings)
            self.kv = kv_init()
        logger.info("tpu_local: kv pool of %d pages: %d bytes declared, %d "
                    "resident", self.num_kv_pages, self.kv_bytes_capacity(),
                    self.kv_bytes_resident())
        if self._tier_client is not None:
            # a rebuilt pool (crash restart, reload) invalidates every
            # resident page — stale HBM locations in the pool index would
            # mis-route until they aged out
            self._tier_client.drop_replica()
        # the fresh allocator's tier counters restart at zero: the delta
        # snapshot must too, or post-rebuild hits are swallowed until the
        # new totals pass the old ones (counters would silently flatline)
        self._tier_hits_exported.clear()
        state_rows = state_rows_for(self.model_config, config.max_batch)
        self._state_row_bytes = kv_state_bytes(self.model_config, 1,
                                               self._kv_dtype)
        self.stats.state_rows_total = max(0, state_rows - 1)
        # window layers that keep a ring a sequence (pools named window_*)
        # beside full layers that page: how many of each, for the spans
        pools = {pool.name: pool for pool in kv_pools(self.model_config)}
        self._window_layers = getattr(pools.get("window_k"), "layers", 0)
        self._full_layers = pools["k"].layers if self._window_layers else 0
        self.allocator = PageAllocator(self.num_kv_pages, config.page_size,
                                       config.max_batch, max_pages_per_slot,
                                       tiers=self._tier_client,
                                       state_rows=state_rows)

    def _ctx_buckets(self) -> list[int]:
        """The page-width buckets decode compiles for: powers of two from
        4 pages up to (and always including) the full table width."""
        max_pages = self.config.max_seq_len // self.config.page_size
        buckets = []
        pages = 4
        while pages < max_pages:
            buckets.append(pages)
            pages *= 2
        buckets.append(max_pages)
        return buckets

    def _ctx_bucket_for(self, max_tokens_needed: int) -> int:
        pages_needed = (max_tokens_needed + self.config.page_size - 1) \
            // self.config.page_size
        for bucket in self._ctx_buckets():
            if bucket >= pages_needed:
                return bucket
        return self._ctx_buckets()[-1]

    def _decode_fn(self, ctx_pages: int, k: int | None = None):
        # K is part of the executable identity (the scan length is baked
        # into the trace), so the cache keys on it: adaptive K switches
        # between PRE-COMPILED entries and can never compile mid-traffic
        k = self._k if k is None else int(k)
        key = (k, ctx_pages)
        fn = self._decode_fns.get(key)
        if fn is None:
            fn = _named(jax.jit(partial(self._of_call(self._decode_and_sample,
                                                      self._decode_call),
                                        ctx_pages=ctx_pages, k=k),
                                donate_argnames=("kv",)),
                        "_decode_and_sample")
            self._decode_fns[key] = fn
        return fn

    def _decode_fb_fn(self, ctx_pages: int, k: int | None = None):
        k = self._k if k is None else int(k)
        key = (k, ctx_pages)
        fn = self._decode_fb_fns.get(key)
        if fn is None:
            fn = _named(jax.jit(partial(
                self._of_call(self._decode_and_sample_fb,
                              self._decode_fb_call),
                ctx_pages=ctx_pages, k=k), donate_argnames=("kv",)),
                        "_decode_and_sample_fb")
            self._decode_fb_fns[key] = fn
        return fn

    def _block_fn(self, ctx_pages: int):
        fn = self._block_fns.get(ctx_pages)
        if fn is None:
            fn = _named(jax.jit(partial(
                self._of_call(self._decode_and_sample_block,
                              self._block_call),
                ctx_pages=ctx_pages), donate_argnames=("kv",)),
                        "_decode_and_sample_block")
            self._block_fns[ctx_pages] = fn
        return fn

    def _find_half_lengths(self) -> dict[int, int]:
        """Dense prefill bucket -> the length of its half program: the same
        jitted function at ``[1, bucket / 2]``, which a lone short prompt
        takes in place of ``[1, bucket]`` (``_lone_length``). A bucket has
        one where its half is a whole number of KV pages and of the unit the
        family's prefill kernels need on this mesh (``prefill_unit``), where
        a step of half the tokens keeps the bucket's expert formulation
        (``expert_path``: one that falls from the chosen experts' row-blocks
        to the scan over every expert computes as many expert rows as the
        bucket's step, and is no shorter), where some prompt of the bucket
        fits it (it lies above the next bucket down), and where the bucket
        is not a sequence-parallel one."""
        config = self.config
        unit = math.lcm(config.page_size, self._family.prefill_unit(
            self.mesh, self.model_config))

        def experts(tokens: int) -> str | None:
            return self._family.expert_path(self.model_config, self.mesh,
                                            tokens, self._kv_dtype)

        buckets = sorted(config.prefill_buckets)
        halves = {}
        for below, bucket in zip([0] + buckets, buckets):
            sp = (self._prefill_sample_sp is not None
                  and bucket > config.sp_threshold)
            half = bucket // 2
            if not (bucket % 2 or half % unit or half <= below or sp
                    or experts(half) != experts(bucket)):
                halves[bucket] = half
        return halves

    def _lone_length(self, request: GenRequest) -> int | None:
        """The half program's length where ``request`` (its bucket assigned)
        takes it: no cached history, not chunked, and what its prefill runs
        fits half its bucket. Such a request is dispatched alone
        (``_admit_slots``); None for every other."""
        half = self.half_lengths.get(request.bucket)
        if (half is None or request.hist or request.chunked
                or self._prefill_end(request) > half):
            return None
        return half

    def _hist_ctx_buckets(self) -> list[int]:
        """Context-width buckets for the history/chunk prefill: one per
        prefill bucket (covers hist≈0 hits) plus the full table width —
        a small set so warmup can precompile it."""
        page = self.config.page_size
        max_pages = self.config.max_seq_len // page

        def ceil_pow2(n: int) -> int:
            p = 1
            while p < n:
                p *= 2
            return p

        buckets = {min(max_pages, max(4, ceil_pow2(b // page)))
                   for b in self.config.prefill_buckets}
        buckets.add(max_pages)
        return sorted(buckets)

    def _hist_ctx_for(self, max_tokens_needed: int) -> int:
        pages_needed = (max_tokens_needed + self.config.page_size - 1) \
            // self.config.page_size
        for bucket in self._hist_ctx_buckets():
            if bucket >= pages_needed:
                return bucket
        return self._hist_ctx_buckets()[-1]

    def _hist_fn(self, ctx_pages: int):
        fn = self._prefill_hist_fns.get(ctx_pages)
        if fn is None:
            fn = _named(jax.jit(partial(
                self._of_call(self._prefill_hist_and_sample,
                              self._prefill_call_of_width),
                ctx_pages=ctx_pages), donate_argnames=("kv",)),
                        "_prefill_hist_and_sample")
            self._prefill_hist_fns[ctx_pages] = fn
        return fn

    def warmup(self, mode: str | None = None) -> None:
        """Precompile the shape grid before traffic. Safe pre-traffic:
        warmup rows use positions=-1, so KV writes land on the reserved
        trash page (page 0) and the allocator is untouched. Also what
        benches call so their timed region measures steady state, not XLA
        compile latency. Compiles here (and the cost-registry AOT
        captures) attribute to the tracker's "warmup" stage — only
        compiles on the dispatch thread count as the mid-traffic kind.

        ``mode`` (default config.warmup_mode):
        - "full": every prefill bucket x power-of-2 admission batch x
          history context bucket + the decode grid — zero mid-traffic
          compiles, but on a cold TPU cache the grid is ~dozens of shapes
          at 20-40 s each;
        - "fast": per bucket only B=1 and the admission cap, history only
          at the smallest + largest context bucket — boots in minutes on
          a cold chip; a cache miss mid-traffic costs one compile (which
          the persistent cache then keeps).
        Either mode also compiles each dense bucket's half-length program
        (``half_lengths``), one shape a bucket that has one.
        """
        token = track_thread(self.compile_tracker, "warmup")
        try:
            self._warmup_impl(mode)
        finally:
            restore_thread(token)
        # what the build left on the heap (every program's jaxpr and
        # executable, the weights' wrappers) lives as long as the engine:
        # out of the collector's reach, so that a full collection in traffic
        # walks what traffic made and not the build. With fifteen step
        # programs of 40 layers on the heap one full collection stopped the
        # whole process for 4.2 s in mid-window (PERF.md section 6, PR 53)
        global _HEAP_FROZEN
        if not _HEAP_FROZEN:        # once a process: the first warm engine's
            _HEAP_FROZEN = True
            gc.collect()
            gc.freeze()

    def _warmup_impl(self, mode: str | None = None) -> None:
        mode = mode or self.config.warmup_mode
        if mode not in ("full", "fast"):
            raise ValueError(f"warmup mode must be full|fast, got {mode!r}")
        started = time.monotonic()
        shapes = 0
        # cost-registry capture (roofline.py): AOT-lower each executable
        # once and record XLA's FLOPs / bytes-accessed so live step timing
        # can feed the mcpforge_llm_mfu / hbm_roofline_frac gauges. Always
        # BEFORE the warming call at the same shape: the call donates
        # self.kv, and lower() must see live buffers
        capture = self.config.cost_analysis
        hist_ctx = self._hist_ctx_buckets()
        if mode == "fast" and len(hist_ctx) > 2:
            hist_ctx = [hist_ctx[0], hist_ctx[-1]]
        with self.mesh:
            # sharding-settle call: the first jitted call canonicalizes
            # the kv pytree's output shardings (P(...,'model',...) from
            # kv_init becomes the executables' inferred placement), and
            # the pjit cache keys on input shardings — compiling the grid
            # against the PRE-transition kv would bake the init placement
            # into the first shape and recompile it at first traffic hit
            b0 = min(self.config.prefill_buckets)
            first, self.kv = self._prefill_sample(
                self.params, self.kv,
                self._idle_call(self._prefill_call(b0), 1), self._rng)
            jax.block_until_ready(first)
            if self._tier_read_fn is not None:
                # spill/restore executables: compile both directions now
                # (against the trash page — contents are zeros either
                # way) so eviction-under-pressure and fetch-on-miss never
                # compile on the serving path
                idx = jnp.asarray(0, jnp.int32)
                spilled = jax.device_get(self._tier_read_fn(self.kv, idx))
                self.kv = self._tier_write_fn(self.kv, idx, *spilled)
                shapes += 1
            for bucket in self.config.prefill_buckets:
                use_sp = (self._prefill_sample_sp is not None
                          and bucket > self.config.sp_threshold)
                # _admit_batch pads to the pow-2 CEILING of the group size,
                # so compile through ceil_pow2(prefill_max_batch), not just
                # the powers of two at or below it
                cap = 1
                while cap < max(1, self.config.prefill_max_batch):
                    cap *= 2
                B = 1
                while B <= cap:
                    if mode == "fast" and B not in (1, cap):
                        B *= 2
                        continue
                    # the history fn serves prefix-cache hits AND chunk
                    # rounds (both batch to any B now) — compile it for
                    # every B whenever either path is reachable; one
                    # compile per context-width bucket (see _hist_fn)
                    hist_reachable = (
                        self.config.prefix_cache
                        or self.config.max_seq_len
                        > max(self.config.prefill_buckets))
                    if use_sp:
                        fns = [self._prefill_sample_sp]
                    else:
                        fns = [self._prefill_sample]
                        if hist_reachable:
                            fns.extend(self._hist_fn(cp) for cp in hist_ctx)
                    for fn in fns:
                        args = (self.params, self.kv, self._idle_call(
                            self._prefill_call(bucket), B), self._rng)
                        # cost entries: the dense prefill, and the history
                        # prefill at its narrowest context bucket
                        kind = ("prefill" if fn is self._prefill_sample
                                else "prefill_hist" if not use_sp
                                and fn is fns[1] else "")
                        if capture and B == 1 and kind:
                            self.cost_registry.capture(kind, B, bucket,
                                                       fn, *args)
                        first, self.kv = fn(*args)
                        jax.block_until_ready(first)
                        shapes += 1
                    B *= 2
                half = self.half_lengths.get(bucket)
                if half is not None:
                    # the bucket's half program (_find_half_lengths): the
                    # dense function alone, at width 1 alone
                    args = (self.params, self.kv, self._idle_call(
                        self._prefill_call(half), 1), self._rng)
                    if capture:
                        self.cost_registry.capture(
                            "prefill", 1, half, self._prefill_sample, *args)
                    first, self.kv = self._prefill_sample(*args)
                    jax.block_until_ready(first)
                    shapes += 1
            B = self.config.max_batch
            if self._verify_fns is not None:
                for ctx_pages in self._ctx_buckets():
                    args = (self.params, self.kv,
                            self._idle_call(self._verify_call, B), self._rng)
                    if capture:
                        self.cost_registry.capture(
                            "spec_verify", B, ctx_pages,
                            self._verify_fn(ctx_pages), *args)
                    block, self.kv = self._verify_fn(ctx_pages)(*args)
                    jax.block_until_ready(block)
                    shapes += 1
            # plain decode is always live: spec engines fall back to it on
            # steps where no greedy row would draft (width-K verify would be
            # pure compute waste — round-2 ADVICE low). One compile per
            # (context-width bucket, K rung) pair. An idle call's
            # seq_lens are 0: every slot is "inactive", writes masked to
            # trash, budgets zero, the stop table empty.
            # The K ladder multiplies the grid: every (ctx, K rung) pair
            # compiles here so the controller's adaptive K only ever lands
            # on pre-warmed executables. With no ladder configured this is
            # exactly the static-K grid (one rung). A block family has no
            # one-token decode program: its grid is the block step
            k_rungs = self.config.k_rungs()
            ctx_buckets = self._ctx_buckets()
            if self._block:
                shapes += self._warmup_block_steps()
                ctx_buckets = []
            for ctx_pages in ctx_buckets:
                for k_rung in k_rungs:
                    # cost entries for non-default rungs carry the
                    # rung in the kind (FLOPs/bytes scale with K, so
                    # MFU after a K switch must divide by the right
                    # cost); the static rung keeps the bare kind the
                    # existing roofline consumers look up
                    suffix = "" if k_rung == self._k else f"@k{k_rung}"
                    args = (self.params, self.kv, self._idle_call(
                        self._decode_call, B), self._rng)
                    if capture:
                        self.cost_registry.capture(
                            "decode" + suffix, B, ctx_pages,
                            self._decode_fn(ctx_pages, k_rung), *args)
                    (block, *_), self.kv = \
                        self._decode_fn(ctx_pages, k_rung)(*args)
                    block.block_until_ready()
                    shapes += 1
                    if (self.config.decode_overlap
                            and self._verify_fns is None):
                        # the pipelined steady state runs the feedback
                        # variant; warm it alongside so overlap never
                        # compiles mid-traffic. Feed it the plain
                        # decode's OUTPUT block — at runtime the feed
                        # is always the previous step's on-device jit
                        # output, and the pjit cache keys on that
                        # committed sharding (a fresh jnp.zeros here
                        # would warm a cache entry traffic never hits)
                        fb_args = (self.params, self.kv, self._idle_call(
                            self._decode_fb_call, B), self._rng, block)
                        if capture:
                            self.cost_registry.capture(
                                "decode_fb" + suffix, B, ctx_pages,
                                self._decode_fb_fn(ctx_pages, k_rung),
                                *fb_args)
                        (block, *_), self.kv = self._decode_fb_fn(
                            ctx_pages, k_rung)(*fb_args)
                        block.block_until_ready()
                        shapes += 1
            self._warmed_k.update(k_rungs)
        logger.info("tpu_local warmup: %d shapes compiled in %.1fs",
                    shapes, time.monotonic() - started)

    def _warmup_block_steps(self) -> int:
        """Compile the block step for every context bucket: rows with
        positions -1 are idle, write the trash page and mask nothing, so
        the loop makes no pass and the commit pass runs once."""
        for ctx_pages in self._ctx_buckets():
            (block, *_), self.kv = self._block_fn(ctx_pages)(
                self.params, self.kv,
                self._idle_call(self._block_call, self.config.max_batch),
                self._rng)
            block.block_until_ready()
        return len(self._ctx_buckets())

    # ------------------------------------------------------------- device fns

    def _paged_impl(self, step: str, kv) -> str:
        """Which paged-attention implementation ``step`` traces — decided
        from THIS engine's mesh (ops/attention.py), and recorded so
        ``attn_traced`` can say what every compiled step runs."""
        impl = self._family.paged_impl(self.mesh, self.model_config, kv)
        self.attn_traced[step] = impl
        return impl

    def _of_call(self, step, layout):
        """``step`` (a step function below) as the program of one packed
        call: ``(params, kv, packed, base_key, *fed, **static)``. The program
        slices ``packed`` apart by ``layout`` (a :class:`CallLayout`, or a
        function of the call's width that gives one), whose fields carry the
        step function's own parameter names; ``slot_ids`` is the rows' own
        numbers where the call has none (a decode width's rows ARE its
        slots); the step's key is the base key with the call's dispatch
        number folded in. ``fed`` goes on before them as it came (the block
        of the step in flight)."""
        layout_of = layout if callable(layout) else (lambda _width: layout)

        def program(params, kv, packed, base_key, *fed, **static):
            fields = layout_of(packed.shape[1]).unpack(packed)
            key = jax.random.fold_in(base_key, fields.pop("counter")[0])
            sampling = SamplingParams(
                *(fields.pop(name) for name in SamplingParams._fields))
            fields.setdefault("slot_ids", jnp.arange(packed.shape[0],
                                                     dtype=jnp.int32))
            return step(params, kv, *fed, sampling=sampling, key=key,
                        **fields, **static)
        # a capture names a jitted call after the function: the step's own
        program.__name__ = program.__qualname__ = step.__name__
        return program

    def _prefill_call(self, length: int) -> CallLayout:
        """The call of a prefill program (dense, history, chunk round) over
        rows padded to ``length``; under a family that drafts on the device
        with ``follow`` (:meth:`_draft_beside`)."""
        layout = self._prefill_calls.get(length)
        if layout is None:
            i32 = np.int32
            follow = (Field("follow", 0, i32, -1),) if self._drafts else ()
            layout = self._prefill_calls[length] = CallLayout(
                Field("tokens", length, i32, self.tokenizer.pad_id),
                Field("positions", length, i32, -1),
                Field("last_idx", 0, i32), Field("slot_ids", 0, i32),
                *follow, *CALL_TAIL)
        return layout

    def _prefill_call_of_width(self, width: int) -> CallLayout:
        """... found again inside the program, from the call's width: two
        fields of ``length`` columns and one column each of the rest."""
        return self._prefill_call(
            (width - 2 - bool(self._drafts) - len(CALL_TAIL)) // 2)

    def _idle_call(self, layout: CallLayout, rows: int):
        """A call of ``rows`` idle rows on the device, placed as a dispatch
        places its own: what warm-up runs a program on (positions -1 or
        lengths 0: every write lands on the trash page)."""
        return jax.device_put(layout.host(rows)[0], self._call_sharding)

    def _prefill_and_sample(self, params, kv, tokens, positions, slot_ids,
                            last_idx, sampling: SamplingParams, key,
                            follow=None, sp: bool = False):
        """Batched prefill + on-device first-token sampling (same sampler and
        PRNG stream as decode — round-1 VERDICT weak #5). ``sp=True`` runs
        the sequence-parallel attention path for long prompts. Returns
        ``(first tokens, kv)``, the tokens with the step's counts beside
        them for a family that counts on the device (:func:`_beside`), and
        with each row's first draft after those for one that drafts there
        (``follow`` [B]: :meth:`_draft_beside`)."""
        cfg = self.model_config
        impl = self.config.sp_impl if sp else self._family.prefill_impl(
            self.config.attn_impl, self.mesh, tokens.shape[1], cfg,
            jnp.dtype(self._kv_dtype).itemsize)
        self.attn_traced["prefill"] = impl
        # last_idx inside the forward: only those rows go through the lm
        # head — [B,S,V] f32 logits would be gigabytes at real vocab sizes
        logits, kv, *aux = self._family.prefill(
            params, cfg, tokens, positions, kv, slot_ids, attn_impl=impl,
            mesh=self.mesh, last_idx=last_idx, **self._prefill_head)
        first = self._first_tokens(logits, last_idx, sampling, key)
        if self._drafts:
            return self._draft_beside(params, kv, tokens, positions, slot_ids,
                                      last_idx, first, follow, aux, None)
        return _beside(first, aux), kv

    def _draft_beside(self, params, kv, tokens, positions, slot_ids, last_idx,
                      first, follow, aux, ctx_pages: int | None):
        """The family's draft pass beside a prefill or a chunk round (a
        family that drafts on the device): over the same positions, each with
        the token that FOLLOWS it: the next one of the row, and at a row's
        last position ``follow`` [B], the prompt's next token where the
        prompt goes on in a later chunk, or (-1) the token just sampled. Its
        cache entries are then built as far as the main model's, and a row
        whose prompt ends here has its first draft: the guess for the token
        after ``first``. Returns ``((first, counts, drafts [B]), kv)``."""
        counts, hidden = aux
        at_last = (jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
                   == last_idx[:, None])
        next_tokens = jnp.where(
            at_last, jnp.where(follow >= 0, follow, first)[:, None],
            jnp.roll(tokens, -1, axis=1))
        logits, kv, counts = self._family.draft_step(
            params, self.model_config, hidden, next_tokens, positions, kv,
            slot_ids, counts, ctx_pages=ctx_pages, pick=last_idx,
            paged_impl=self._paged_impl("draft", kv), mesh=self.mesh)
        return (first, counts, jnp.argmax(logits, axis=-1).astype(jnp.int32)), kv

    def _first_tokens(self, logits, last_idx, sampling, key):
        """What a prefill program samples: each row's next token, or under a
        block family nothing (every position it ran is known, the head was
        skipped, and the request's first tokens come from its first block)."""
        if self._block:
            return jnp.zeros_like(last_idx)
        return sample_tokens(logits, sampling, key)

    def _prefill_hist_and_sample(self, params, kv, tokens, positions, slot_ids,
                                 last_idx, sampling: SamplingParams, key,
                                 follow=None, ctx_pages: int | None = None):
        """Suffix prefill over cached prefix pages (prefix-cache hit path):
        same surface as _prefill_and_sample, but attention spans the slot's
        paged context up to the static ``ctx_pages`` bucket, so rows start
        at their history offset."""
        logits, kv, *aux = self._family.prefill_with_history(
            params, self.model_config, tokens, positions, kv, slot_ids,
            ctx_pages=ctx_pages, last_idx=last_idx,
            paged_impl=self._paged_impl("prefill_hist", kv), mesh=self.mesh,
            **self._prefill_head)
        first = self._first_tokens(logits, last_idx, sampling, key)
        if self._drafts:
            return self._draft_beside(params, kv, tokens, positions, slot_ids,
                                      last_idx, first, follow, aux, ctx_pages)
        return _beside(first, aux), kv

    def _verify_fn(self, ctx_pages: int):
        fn = self._verify_fns.get(ctx_pages)
        if fn is None:
            # a family that drafts on the device: the verify step IS its
            # decode step, and is named as one (a trace counts it as decode)
            step, name = ((self._decode_and_sample_draft,
                           "_decode_and_sample_draft") if self._drafts
                          else (self._verify_and_sample, "_verify_and_sample"))
            fn = _named(jax.jit(partial(
                self._of_call(step, self._verify_call), ctx_pages=ctx_pages),
                donate_argnames=("kv",)), name)
            self._verify_fns[ctx_pages] = fn
        return fn

    def _verify_and_sample(self, params, kv, tokens, positions, slot_ids,
                           sampling: SamplingParams, key,
                           ctx_pages: int | None = None):
        """Speculative verify: a [B, K] chunk (1 real token + K-1 drafts per
        row) through the gathered-history path, sampling at EVERY position.
        Position j's sample is the model's true next token given the chunk
        prefix up to j — the host accepts drafts while they agree. Returns
        ([B, K] sampled tokens, kv)."""
        logits, kv, *_ = self._family.prefill_with_history(
            params, self.model_config, tokens, positions, kv, slot_ids,
            ctx_pages=ctx_pages,
            paged_impl=self._paged_impl("spec_verify", kv), mesh=self.mesh)
        return _sample_every_position(logits, sampling, key), kv

    def _decode_and_sample_draft(self, params, kv, tokens, positions,
                                 slot_ids, sampling: SamplingParams, key,
                                 ctx_pages: int | None = None):
        """A decode dispatch of a family that drafts on the device: a verify
        step that also drafts. The model over the [B, K] chunk (the last
        emitted token and the draft after it; positions -1 where a row has
        none) samples at every position as :meth:`_verify_and_sample` does;
        the family's draft pass (``draft_step``) over the same positions
        takes those samples as the tokens that follow and guesses the one
        after each; ``accepted`` [B] counts a row's leading drafts that the
        samples bear out, and the row's next draft is the guess of position
        ``accepted`` (the last position whose inputs were all true). No
        branch on acceptance: a rejected position's cache entries, the
        model's and the draft pass's, are dead by position and overwritten
        by the next step. Returns ``((samples [B, K], the step's counts,
        [rows that carried a draft, drafts accepted], next drafts [B]), kv)``:
        the drafts last, as a prefill program returns them."""
        impl = self._paged_impl("spec_verify", kv)
        logits, kv, counts, hidden = self._family.prefill_with_history(
            params, self.model_config, tokens, positions, kv, slot_ids,
            ctx_pages=ctx_pages, paged_impl=impl, mesh=self.mesh, hidden=True)
        samples = _sample_every_position(logits, sampling, key)
        drafted = positions[:, 1:] >= 0
        agree = drafted & (tokens[:, 1:] == samples[:, :-1])
        accepted = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1),
                           axis=1)
        logits, kv, counts = self._family.draft_step(
            params, self.model_config, hidden, samples, positions, kv,
            slot_ids, counts, ctx_pages=ctx_pages, pick=accepted,
            paged_impl=impl, mesh=self.mesh)
        drafts = jnp.stack([jnp.sum(drafted), jnp.sum(accepted)])
        return (samples, counts, drafts.astype(jnp.float32),
                jnp.argmax(logits, axis=-1).astype(jnp.int32)), kv

    def _decode_and_sample(self, params, kv, tokens, positions, slot_ids,
                           seq_lens, budgets, stop_tbl,
                           sampling: SamplingParams, key,
                           ctx_pages: int | None = None,
                           k: int | None = None):
        """One decode SUPER-STEP: k = config.superstep decode iterations
        as a single jitted lax.scan — fused sampling, in-loop paged-KV
        append over pre-granted pages, and per-slot budget/EOS/stop
        masking so finished rows FREEZE on device instead of burning a
        host round-trip per token (the SnapStream-style token-loop
        fusion of ROADMAP item 1).

        ``budgets`` [B] int32 caps how many of the k sampled tokens are
        real per row (max_tokens remainder ∧ granted page capacity);
        ``stop_tbl`` [B, _STOP_TBL_WIDTH] int32 carries each row's EOS +
        stop ids (-1 padding, never a real token). A frozen row (EOS/stop
        sampled, or budget exhausted) stops writing KV and stops
        advancing positions/lens — so int8 page scales never creep on
        post-EOS garbage — while the fixed-shape compute rides along
        masked. The host stays authoritative at retire (_emit re-checks
        every finish condition), so a stop id beyond the static table
        width costs only wasted lookahead compute, never a wrong stream.

        Returns ((tokens [k, B], valid [k, B] bool, done [B] bool), kv):
        valid[j, b] marks a token the host should emit; done[b] is the
        device's end-of-stream verdict, retired in ONE readback. A family
        whose step functions count on the device (``STEP_AUX``) appends
        those counts, summed over the k sub-steps, as a fourth element of
        the same readback."""
        # k is partial-bound by _decode_fn so the scan length is part of
        # the executable identity (adaptive K); the self._k fallback
        # serves direct (unjitted) callers in tests
        k = self._k if k is None else k
        # rows with work this dispatch (inactive slots — empty or
        # mid-chunk-prefill — never write; the mask below derives from
        # the INITIAL lens, not the in-scan incremented ones)
        active = seq_lens > 0
        paged_impl = self._paged_impl("decode", kv)

        def step(carry, xs):
            (step_tokens, step_positions, step_lens, done, prev_valid,
             step_kv) = carry
            j, step_key = xs
            # sub-step j writes the KV of its INPUT token — sampled at
            # j-1, or host/feedback-fed at j=0, always a real emitted
            # token — so the write mask trails validity by one sub-step,
            # and a done row never writes its terminal token's KV
            # (exactly the serial engine, which never re-dispatches a
            # finished request)
            logits, step_kv, *aux = self._family.decode_step(
                params, self.model_config, step_tokens, step_positions,
                step_kv, slot_ids, step_lens, ctx_pages=ctx_pages,
                write_mask=(active & prev_valid & ~done),
                paged_impl=paged_impl, mesh=self.mesh)
            sampled = sample_tokens(logits, sampling, step_key)
            valid = active & ~done & (j < budgets)
            hit_stop = jnp.any(sampled[:, None] == stop_tbl, axis=1)
            done = done | (valid & hit_stop)
            next_positions = jnp.where(valid, step_positions + 1,
                                       step_positions)
            next_lens = jnp.where(valid, step_lens + 1, step_lens)
            return ((sampled, next_positions, next_lens, done, valid,
                     step_kv), (sampled, valid, *aux))

        B = tokens.shape[0]
        keys = jax.random.split(key, k)
        carry0 = (tokens, positions, seq_lens,
                  jnp.zeros((B,), dtype=bool), active, kv)
        (_, _, _, done, _, kv), (all_tokens, all_valid, *aux) = jax.lax.scan(
            step, carry0, (jnp.arange(k), keys))
        return (all_tokens, all_valid, done,
                *(a.sum(axis=0) for a in aux)), kv

    def _decode_and_sample_block(self, params, kv, tokens, positions, masked,
                                 slot_ids, sampling: SamplingParams, key,
                                 ctx_pages: int | None = None):
        """One BLOCK step (a family whose ``STEP_KIND`` is ``"block"``): the
        family fills and commits ``block_length`` positions a row on the
        device, passes and fill rule included (``models/sdar.py:
        block_step``), and the host reads one block back. tokens, positions,
        masked: [B, Bl]. Returns what :meth:`_decode_and_sample` returns, so
        that one retire serves both: ``((tokens [Bl, B], valid [Bl, B]: the
        position was generated here, done [B]: never (the host finds stops),
        [denoise passes, positions filled by the threshold]), kv)``."""
        (block, passes, by_threshold), kv = self._family.block_step(
            params, self.model_config, tokens, positions, masked, kv,
            slot_ids, sampling, key, ctx_pages=ctx_pages,
            paged_impl=self._paged_impl("decode", kv), mesh=self.mesh)
        counts = jnp.stack([passes, by_threshold]).astype(jnp.float32)
        return (block.T, masked.T, jnp.zeros((tokens.shape[0],), bool),
                counts), kv

    def _decode_and_sample_fb(self, params, kv, prev_block, positions,
                              slot_ids, seq_lens, budgets, stop_tbl,
                              sampling: SamplingParams, key,
                              ctx_pages: int | None = None,
                              k: int | None = None):
        """Device-token-feedback decode (overlapped pipeline steady state):
        the input token is the PREVIOUS dispatch's last sampled token —
        row k-1 of its [k, B] block — which never left the device, so the
        host feeds no tokens at all between barriers. prev_block is NOT
        donated: the retire path still reads it back for emission while
        this step executes."""
        return self._decode_and_sample(params, kv, prev_block[-1], positions,
                                       slot_ids, seq_lens, budgets, stop_tbl,
                                       sampling, key, ctx_pages=ctx_pages,
                                       k=k)

    # --------------------------------------------------------------- lifecycle

    async def start(self) -> None:
        if self._started:
            return
        if self._thread is not None and self._thread.is_alive():
            # a wedged thread from a failed stop() still owns kv/_running;
            # a second dispatch thread would corrupt both
            raise RuntimeError("previous dispatch thread still running")
        self._started = True
        self._killed = False
        self._loop = asyncio.get_running_loop()
        # fresh events per thread: a wedged old thread keeps seeing its own
        # (set) events and can never be revived by a later start()
        self._stop_event = threading.Event()
        self._wake = threading.Event()
        self._thread = threading.Thread(target=self._device_loop,
                                        name="tpu-engine-dispatch", daemon=True)
        self._thread.start()

    async def stop(self) -> None:
        if not self._started:
            self._close_owned_tiers()
            return
        self._started = False
        self._stop_event.set()
        self._wake.set()  # unblock an idle dispatch thread immediately
        thread = self._thread
        if thread is not None:
            await asyncio.to_thread(thread.join, 30.0)
            if thread.is_alive():
                logger.error("dispatch thread failed to stop within 30s; "
                             "engine restart refused until it exits")
                return  # keep self._thread so start() refuses a double-start
        self._thread = None
        self._close_owned_tiers()

    def spill_prefix_pages(self) -> int:
        """Spill-on-drain: copy every ref==0 resident prefix page into
        the (pool-shared) spill store so a rebuilt engine fetches the
        corpus on miss instead of losing it with the HBM pool
        (docs/resilience.md; ROADMAP item 3's remaining half). Caller
        contract: the dispatch thread must be QUIESCED (stop() joined —
        the pool's reload path) — this reads device pages from the
        calling thread, which is only legal with no concurrent device
        mutation. Runs under the engine mesh so the warmup-compiled
        tier-read executable serves every page (no fresh compiles)."""
        client = self._tier_client
        if client is None or not client.active:
            return 0
        with self.mesh:
            spilled = self.allocator.spill_resident_prefix()
        if spilled:
            logger.info("tpu_local: spilled %d resident prefix page(s) "
                        "to the tier store before teardown", spilled)
        return spilled

    def _close_owned_tiers(self) -> None:
        """Shut down a standalone engine's private spill store (its
        write-behind worker + tempdir). Pool-shared stores are closed by
        the pool, which outlives every replica engine."""
        if self._owned_tier_store is not None:
            self._owned_tier_store.close()
            self._owned_tier_store = None
            if self._tier_client is not None:
                self._tier_client.store = None

    def kill(self) -> None:
        """Signal the dispatch thread to stop WITHOUT joining it.

        Pool failover path: a wedged device call can hold the thread for
        minutes, and the pool must not wait on it before requeueing the
        replica's in-flight requests onto healthy replicas. After kill()
        the engine refuses new submissions (_check_alive) and a revived
        zombie thread exits at its next loop check; any tokens it emits
        land in streams the pool has already abandoned."""
        self._killed = True
        self._started = False
        self._stop_event.set()
        self._wake.set()

    def heartbeat_age(self) -> float:
        """Seconds since the dispatch loop last started an iteration —
        the pool health monitor's wedge signal (a healthy loop beats at
        >=20 Hz; a thread stuck inside a device call stops beating)."""
        return max(0.0, time.monotonic() - self._heartbeat_ts)

    def last_step_age(self) -> float | None:
        """Seconds since the last device dispatch retired (step-ring
        staleness); None before the first step."""
        return self.timeline.since_last_retired()

    def dispatch_alive(self) -> bool:
        """True while the dispatch thread is running (started and the
        thread object is live) — the crash half of the health check."""
        return bool(self._started and self._thread is not None
                    and self._thread.is_alive())

    @property
    def warmed(self) -> bool:
        """True once warmup compiled the decode grid. A warmed
        engine has no first-dispatch compile left, so the pool health
        monitor may read a stale heartbeat as a wedge even before the
        first traffic step retires."""
        return bool(self._warmed_k)

    def request_cancel(self, request_id: str) -> bool:
        """Thread-safe: ask the dispatch thread to terminate a generation.

        Returns True when the id matches a request the engine currently
        holds (pending, chunk-prefilling, or decoding); the stream then
        receives its terminal like any other finish, with
        ``finish_reason="cancelled"``. A request still in the submit
        handoff queue is not yet visible here (the window is one
        dispatch-loop iteration) — callers get False and may retry.
        The id set is consumed by ``_apply_cancels`` on the dispatch
        thread; this side only reads the request tables (snapshots under
        the GIL) and mutates under the cancel lock."""
        for _ in range(8):
            try:
                known = any(
                    r.request_id == request_id
                    for bucket in (list(self._pending),
                                   list(self._chunking.values()),
                                   list(self._running.values()))
                    for r in bucket)
                break
            except RuntimeError:
                # the dispatch thread mutated a table mid-snapshot; the
                # tables are small and mutate once per step — retry
                continue
        else:
            known = True  # can't prove absence: mark anyway (an unmatched
            #               id is dropped at the next _apply_cancels sweep)
        if not known:
            return False
        with self._cancel_lock:
            self._cancels.add(request_id)
        self._wake.set()
        return True

    # ------------------------------------------------------------- submission

    async def submit(self, request: GenRequest) -> GenRequest:
        self._check_alive()
        self.timeline.stamp("submit", request.request_id, -1,
                            request.t_submit)
        self.stats.requests += 1
        self.stats.prompt_tokens += len(request.prompt_ids)
        if self.ledger is not None:
            # same site as stats.prompt_tokens — the per-tenant slices
            # must sum to the untagged total (conservation gate)
            self.ledger.add(request.tenant, requests=1,
                            prompt_tokens=len(request.prompt_ids))
        while True:
            try:
                self._work.put_nowait(request)
                self._wake.set()  # wake an idle dispatch thread
                break
            except queue.Full:  # backpressure without blocking the loop
                await asyncio.sleep(0.005)
                # recheck AFTER the await, with no further await before
                # the retry put: the pool's health sweep can kill this
                # engine during the sleep (kill + _fail_outstanding drain
                # the queue), and a put that then succeeds would register
                # work on a dead replica no sweep will ever requeue
                self._check_alive()
        self.stats.queue_depth = self._work.qsize() + len(self._pending)
        if self.metrics is not None:
            self.metrics.llm_queue_depth.labels(
                replica=self.config.replica_id).set(self.stats.queue_depth)
        return request

    def _check_alive(self) -> None:
        """Fail fast instead of queueing work no consumer will ever drain
        (a crashed dispatch thread must not hang every later request).
        A kill()ed engine refuses outright: kill clears _started without
        joining, so the liveness clause alone would wave submissions into
        a queue nothing drains — exactly the pool race where a submit
        awaiting backpressure resumes after the health sweep killed the
        replica."""
        if self._killed:
            raise RuntimeError("tpu_local engine was killed (failover)")
        if self._started and (self._thread is None
                              or not self._thread.is_alive()):
            raise RuntimeError("tpu_local engine dispatch thread is not running")

    async def generate(self, prompt_ids: list[int], **kwargs) -> AsyncIterator[int]:
        """Submit and yield token ids as they decode."""
        from ..utils.ids import new_id
        request = GenRequest(request_id=new_id(), prompt_ids=prompt_ids, **kwargs)
        await self.submit(request)
        while True:
            token = await request.stream.get()
            if token is None:
                break
            yield token

    # --------------------------------------------------------- dispatch thread

    def _device_loop(self) -> None:  # lint: runs-on[dispatch]  # lint: hot-path
        """Owns every jax call + device sync. Never touched by the asyncio
        loop; results hop back via loop.call_soon_threadsafe: one flush per
        iteration, not one wakeup per token, and one more, early, where a
        prefill or a chunk round made a request's first token — it leaves
        before the iteration's decode or verify dispatch is built.

        With ``decode_overlap`` the decode phase runs a depth-2 pipeline:
        one decode step is always in flight, fed by the previous step's
        on-device tokens, and results emit one step behind. Everything
        that re-homes slots or pages (admission, chunk completion, width
        changes, stop/crash) first drains the pipeline so token streams
        stay byte-identical to the serial path."""
        crashed = False
        overlap = self.config.decode_overlap and self._verify_fns is None
        # every XLA compile on this thread is a mid-traffic ("serving")
        # compile — the thing warmup exists to prevent; count + time it
        compile_token = track_thread(self.compile_tracker, "serving")
        try:
            # the pjit dispatch cache keys on the AMBIENT mesh context, not
            # just input shardings: warmup() compiles under ``with
            # self.mesh`` so dispatch must run under it too, or every
            # warmed shape recompiles on its first traffic hit (observed:
            # seconds-long "mid-traffic" compiles on shapes warmup had
            # already built, which reads as a wedge to the pool's
            # heartbeat monitor)
            with self.mesh:
                while not self._stop_event.is_set():
                    self._heartbeat_ts = time.monotonic()
                    # fault point engine.dispatch (docs/resilience.md),
                    # scope = replica id: latency = a slow replica (the
                    # chaos matrix's slow-replica arm — heartbeat still
                    # beats, work just drags), error = a dispatch-thread
                    # crash through the REAL crash/failover path below.
                    # Unarmed (the default): one dict miss per iteration.
                    fault = fault_point("engine.dispatch",
                                        scope=self.config.replica_id)
                    if fault is not None:
                        fault.apply()
                    did_work = False
                    # drain the bounded handoff queue EVERY iteration (as the
                    # old unconditional _admit_batch did): the backlog lives
                    # in the unbounded _pending, where the priority sort and
                    # within-class FIFO apply — even while all slots are busy
                    self._drain_work()
                    if self._cancels:
                        self._apply_cancels()
                        did_work = True
                    if self._pending_knobs:
                        # controller knob moves land HERE — before
                        # admission/decode, draining the overlap pipeline
                        # when K changes, so every move is a clean drain
                        # barrier (greedy parity holds)
                        self._apply_knobs()
                        did_work = True
                    if self._pending_exports:
                        # pool KV-migration exports land at the same
                        # barrier: the pipeline drains first so every
                        # exported page holds fully retired prefill state
                        self._apply_exports()
                        did_work = True
                    incoming = bool(self._pending)
                    occupied = len(self._running) + len(self._chunking)
                    can_admit = incoming and occupied < self.config.max_batch
                    if self._inflight is not None and (
                            can_admit or self._chunking or not self._running):
                        # drain barriers: admission and chunk completion move
                        # requests into slots/pages the in-flight lookahead
                        # indexes; an empty running set means the lookahead
                        # holds only rows that already finished
                        self._drain_pipeline()
                        did_work = True
                    if can_admit:
                        did_work = self._admit_batch() or did_work
                        self._flush_emits(first_only=True)
                    if self._chunking:
                        self._chunk_round()
                        self._flush_emits(first_only=True)
                        did_work = True
                    if self._running:
                        if (self._verify_fns is not None
                                and self._spec_enabled
                                and self._any_would_draft()):
                            self._spec_step_all()
                        elif overlap:
                            self._decode_step_overlapped()
                        else:
                            self._decode_step_all()
                        did_work = True
                    self.stats.queue_depth = self._work.qsize() + len(self._pending)
                    self.stats.chunking = len(self._chunking)
                    self._flush_emits()
                    if not did_work:
                        self._wait_for_work()
                # clean stop: already-sampled in-flight tokens reach their
                # streams before the cancel sweep below
                self._drain_pipeline()
        except Exception:
            crashed = True
            # device state (and the in-flight block) is suspect after a
            # fault inside a jitted call; never try to read it back
            self._inflight = None
            logger.exception("tpu_local dispatch thread crashed")
        finally:
            self._flush_emits()
            try:
                if (crashed and self.config.auto_restart
                        and not self._stop_event.is_set()
                        and self.stats.engine_restarts
                        < self.config.auto_restart_max):
                    # still registered: crash-recovery compiles (fresh
                    # _init_kv jit wrappers) are mid-traffic "serving"
                    # compiles and must not escape attribution
                    self._restart_after_crash()
                else:
                    # a dead thread must not strand consumers on
                    # stream.get()
                    self._fail_outstanding(
                        "cancelled" if self._stop_event.is_set()
                        else "error")
            finally:
                restore_thread(compile_token)

    def _restart_after_crash(self) -> None:
        """Device-fault recovery (SURVEY §5.3: "TPU driver errors → engine
        restart + request re-queue"). Runs on the DYING dispatch thread:

        - mid-stream requests fail (tokens already emitted; a silent retry
          would duplicate output) — the gateway's retry layer owns those;
        - PENDING requests (no tokens yet) re-queue and survive;
        - the KV pool + allocator are REBUILT: a crash inside a jitted call
          may have consumed the donated kv buffers, so resident state is
          untrustworthy (params are never donated and stay);
        - a fresh dispatch thread takes over. Bounded by auto_restart_max.
        """
        self.stats.engine_restarts += 1
        logger.warning("tpu_local: restarting engine after crash (%d/%d)",
                       self.stats.engine_restarts, self.config.auto_restart_max)
        self._inflight = None  # sampled-but-unfetched tokens die with the kv
        self._drain_work()
        requeue = list(self._pending)
        self._pending.clear()
        # mid-chunk requests have emitted NOTHING — they re-queue safely
        # (their pages die with the KV rebuild below)
        requeue.extend(self._chunking.values())
        self._chunking.clear()
        for request in list(self._running.values()):
            if request.finish_reason is None:
                request.finish_reason = "error"
            # crash-killed requests are the ones an operator hunts for in
            # traces — emit their ERROR llm.decode span like every other
            # termination path does
            self._observe_finish(request)
            self._running.pop(request.slot, None)
            self._post_tokens(request, [], done=True)
        # flush BEFORE the replacement thread can exist: two dispatch
        # threads must never race on the unlocked emit buffer
        self._flush_emits()
        try:
            self._init_kv()
            for request in requeue:  # fresh admission state
                request.slot = -1
                request.bucket = -1
                request.hist = 0
                request.chunked = False
                request.chunk_pos = 0
                self._pending.append(request)
            requeue = []
            replacement = threading.Thread(target=self._device_loop,
                                           name="tpu-engine-dispatch",
                                           daemon=True)
            # start BEFORE publishing: a concurrent stop() must never join
            # a not-yet-started thread (the dying thread keeps
            # _check_alive() true until this method returns)
            replacement.start()
            self._thread = replacement
        except Exception:
            logger.exception("tpu_local: crash recovery failed; engine down")
            # fail EVERYTHING reachable — the requeue list, _pending, and
            # anything submitted into _work while the rebuild ran — so no
            # consumer is stranded on stream.get()
            self._pending.extendleft(reversed(requeue))
            self._fail_outstanding("error")

    def _fail_outstanding(self, reason: str) -> None:
        self._inflight = None
        self._drain_work()
        with self._export_lock:
            exports, self._pending_exports = self._pending_exports, []
        for _ids, fut in exports:
            # a migration awaiting this export degrades to decode-in-
            # place (or a plain requeue) instead of hanging forever
            if not fut.done():
                fut.set_exception(RuntimeError(
                    f"engine dispatch thread died ({reason}) before the "
                    f"chain export ran"))
        for request in list(self._running.values()):
            if request.finish_reason is None:
                request.finish_reason = reason
            # trace correlation (observability/logging.py): the incident
            # line for a generation killed mid-decode joins to the OTel
            # trace of the request it truncated
            logger.warning(
                "tpu_local: failing in-flight request %s (%s) after %d "
                "generated token(s)", request.request_id,
                request.finish_reason, len(request.generated),
                extra=trace_extra(request.trace_ctx))
            self._finish(request)
        for request in list(self._chunking.values()):
            self._chunking.pop(request.slot, None)
            self.allocator.free_slot(request.slot)
            if request.finish_reason is None:
                request.finish_reason = reason
            self._post_tokens(request, [], done=True)
        while self._pending:
            request = self._pending.popleft()
            if request.finish_reason is None:
                request.finish_reason = reason
            self._post_tokens(request, [], done=True)
        self._flush_emits()

    def _apply_cancels(self) -> None:  # lint: runs-on[dispatch]
        """Terminate the generations request_cancel() marked. Runs at the
        top of the dispatch iteration; a cancelled RUNNING slot re-homes
        pages, so the overlap pipeline drains first (same barrier as
        admission/stop). Ids that matched nothing (the request finished
        between the mark and this sweep) are dropped — cancelling a
        completed request is a no-op by contract."""
        with self._cancel_lock:
            ids, self._cancels = self._cancels, set()
        if not ids:
            return
        if self._inflight is not None and any(
                r.request_id in ids for r in self._running.values()):
            self._drain_pipeline()
        for request in list(self._running.values()):
            if request.request_id in ids and request.finish_reason is None:
                request.finish_reason = "cancelled"
                self._finish(request)
        for request in list(self._chunking.values()):
            if request.request_id in ids and request.finish_reason is None:
                self._chunking.pop(request.slot, None)
                self.allocator.free_slot(request.slot)
                request.finish_reason = "cancelled"
                self._post_tokens(request, [], done=True)
        kept: deque[GenRequest] = deque()
        for request in self._pending:
            if request.request_id in ids and request.finish_reason is None:
                request.finish_reason = "cancelled"
                self._post_tokens(request, [], done=True)
            else:
                kept.append(request)
        self._pending = kept

    def request_knobs(self, *, superstep: int | None = None,
                      spec_enabled: bool | None = None) -> dict[str, bool]:
        """Stage serving-knob changes for the dispatch thread to land at
        its next drain barrier (the controller's actuation surface —
        same handoff pattern as request_cancel). Validation happens HERE,
        against the warmed grid, so a rejected value never reaches the
        loop: adaptive K may only select warmed ladder rungs (zero
        mid-traffic XLA compiles by construction) and toggling spec needs
        a spec-built engine. Returns {knob: accepted} so the caller can
        audit refusals.
        Thread-safe; callable from any thread."""
        accepted: dict[str, bool] = {}
        staged: dict[str, Any] = {}
        if superstep is not None:
            k = int(superstep)
            ok = k >= 1 and (k in self._warmed_k or any(
                key[0] == k for key in self._decode_fns))
            if self._verify_fns is not None and k > 1:
                ok = False  # spec engines can't take K>1 (ctor exclusivity)
            accepted["superstep"] = ok
            if ok:
                staged["superstep"] = k
        if spec_enabled is not None:
            ok = self._verify_fns is not None
            accepted["spec_enabled"] = ok
            if ok:
                staged["spec_enabled"] = bool(spec_enabled)
        if staged:
            with self._knob_lock:
                self._pending_knobs.update(staged)
            self._wake.set()
        return accepted

    def _apply_knobs(self) -> None:  # lint: runs-on[dispatch]
        """Land staged knob moves on the dispatch thread. A superstep
        change drains the overlap pipeline first: the in-flight lookahead
        was dispatched at the OLD K and its retire accounting carries its
        own ``k``; after the drain the switch is a clean barrier and the
        next dispatch picks the pre-warmed executable for the new K.
        A spec move is a pure host-side posture flip."""
        with self._knob_lock:
            knobs, self._pending_knobs = self._pending_knobs, {}
        if not knobs:
            return
        new_k = knobs.get("superstep")
        if new_k is not None and new_k != self._k:
            if self._inflight is not None:
                self._drain_pipeline()
            self._k = int(new_k)
        if "spec_enabled" in knobs:
            self._spec_enabled = bool(knobs["spec_enabled"])

    def request_chain_export(self, prompt_ids: list[int]) -> "Future[int]":
        """Stage a KV chain export for the dispatch thread (the pool's
        prefill->decode migration seam, docs/disaggregation.md): the
        prompt's registered full-page chain spills — as a COPY — into
        the pool-shared tier store at the next drain barrier. Same
        handoff pattern as request_knobs: stage under the lock, wake the
        loop, let the only thread allowed to touch device state do the
        reads. Returns a future resolving to the number of pages now
        present in the store; it fails if the engine dies first.
        Thread-safe; callable from any thread."""
        self._check_alive()
        fut: "Future[int]" = Future()
        with self._export_lock:
            self._pending_exports.append((tuple(prompt_ids), fut))
        self._wake.set()
        return fut

    def _apply_exports(self) -> None:  # lint: runs-on[dispatch]
        """Land staged chain exports on the dispatch thread, draining the
        overlap pipeline first — the prefill leg's retire must be fully
        applied to the pages before their bytes are read off the device."""
        with self._export_lock:
            exports, self._pending_exports = self._pending_exports, []
        if not exports:
            return
        if self._inflight is not None:
            self._drain_pipeline()
        for prompt_ids, fut in exports:
            if fut.cancelled():
                continue
            try:
                fut.set_result(self.allocator.spill_chain(list(prompt_ids)))
            except Exception as exc:  # device read failed: the POOL
                fut.set_exception(exc)  # degrades; the engine lives on

    def knob_state(self) -> dict[str, Any]:
        """Live serving-knob posture (the /admin/controller "now" row and
        the bench harness's zero-compile assertion read this)."""
        return {
            "superstep": self._k,
            "spec_built": self._verify_fns is not None,
            "spec_enabled": bool(self._verify_fns is not None
                                 and self._spec_enabled),
            "warmed_k": sorted(self._warmed_k),
        }

    def _wait_for_work(self) -> None:
        """Idle path: block on the submit-side wake event instead of a
        1 ms sleep poll — submit latency drops to the event signal and
        idle CPU to ~zero. clear-then-check closes the race where a
        request lands between the caller's emptiness check and the wait;
        the timeout is a safety net for states the event cannot signal
        (e.g. page-bound pending work that must periodically re-probe)."""
        with self.timeline.span("loop.wait"):
            self._wake.clear()
            if self._work.qsize() or self._stop_event.is_set():
                return
            self._wake.wait(0.05)

    def _drain_work(self) -> None:
        while True:
            try:
                self._pending.append(self._work.get_nowait())
            except queue.Empty:
                return

    def _bucket_for(self, length: int) -> int | None:
        for bucket in sorted(self.config.prefill_buckets):
            if length <= bucket:
                return bucket
        return None

    def _assign_bucket(self, request: GenRequest) -> int:
        """Request's prefill bucket (0 = fits no bucket). A prefix-cache
        hit buckets by SUFFIX length, so a 2048-token prompt with a cached
        1920-token template prefix prefills in the smallest bucket. The
        probe is READ-ONLY — no page references are taken here, so pending
        requests never pin cache pages (a pinned-pages cycle between two
        queued requests would deadlock admission); the real match happens
        at admission and is re-verified against this probe. SP buckets
        never run the history path (the shard_map prefill has no
        paged-history support) — those fall back to a dense full prefill."""
        if request.bucket != -1:
            return request.bucket
        request.chunked = False  # recomputed below on every (re-)probe
        ids = request.prompt_ids
        if len(ids) + 1 > self.config.max_seq_len:
            # the prompt plus >=1 generated token must fit the block table;
            # past it, page indices clamp and silently overwrite (and, with
            # the prefix cache, publish) the slot's last page
            request.bucket = 0
            return 0
        if self.config.prefix_cache:
            hist = self.allocator.probe_prefix(ids)
            # a hit only pays when the suffix lands a STRICTLY smaller
            # bucket than dense prefill of the whole prompt: the history
            # path costs more per padded token (gathered context
            # attention), so "saving" 16 cached tokens of a 90-token
            # prompt while still padding to the same bucket is a net loss
            # on every backend
            if hist:
                dense_bucket = self._bucket_for(len(ids))
                bucket = self._bucket_for(len(ids) - hist)
                if (dense_bucket is not None and bucket is not None
                        and bucket >= dense_bucket):
                    hist = 0
            if hist:
                bucket = self._bucket_for(len(ids) - hist)
                sp_bucket = (self._prefill_sample_sp is not None
                             and bucket is not None
                             and bucket > self.config.sp_threshold)
                if bucket is not None and not sp_bucket:
                    request.hist = hist
                    request.bucket = bucket
                    return bucket
                if bucket is None:
                    # the suffix alone exceeds every bucket: chunk it, but
                    # FROM the cached prefix — the chunk loop starts at hist
                    request.hist = hist
                    request.chunked = True
                    request.bucket = max(self.config.prefill_buckets)
                    return request.bucket
        request.hist = 0
        bucket = self._bucket_for(len(ids))
        if bucket is None:
            # longer than every bucket but fits the block table: chunked
            # prefill — bucket-sized chunks through the history path, each
            # attending to the previous chunks' KV. (Also the safety net
            # for a prefix-cache hit whose pages were evicted between
            # probe and admission: the request stays servable.)
            request.chunked = True
            request.bucket = max(self.config.prefill_buckets)
            return request.bucket
        request.bucket = bucket
        return request.bucket

    def _admit_batch(self) -> bool:
        """Admit up to prefill_max_batch same-bucket requests in ONE prefill
        call (round-1 VERDICT weak #4: serial batch=1 admission serialized
        bursts behind each other and behind decode)."""
        with self.timeline.span("admit"):
            admitted, bucket = self._admit_slots()
        if not admitted:
            return False
        if not admitted[0].chunked:  # chunked: device work is _chunk_round's
            self._prefill_admitted(admitted, bucket)
        return True

    def _admit_slots(self) -> tuple[list[GenRequest], int]:
        """The host half of admission, up to the table sync: pick the
        group, match prefixes, win slots and pages. Returns the admitted
        requests and their shared prefill bucket ([] when none)."""
        config = self.config
        none: tuple[list[GenRequest], int] = ([], 0)
        self._drain_work()
        if not self._pending:
            return none
        # priority classes: interactive requests admit before queued
        # background work (summaries must not make a chat turn wait for a
        # free slot — and the sort is stable, so FIFO holds within each
        # class and no class reorders internally)
        if len({r.priority for r in self._pending}) > 1:
            self._pending = deque(sorted(self._pending,
                                         key=lambda r: r.priority))

        # (oversized prompts reject inside the head-selection scan below)
        free_slots = [s for s in range(config.max_batch)
                      if s not in self._running and s not in self._chunking]
        if not self._pending or not free_slots:
            return none

        # chunk rounds advance at most prefill_max_batch rows: admitting
        # more chunkers would pin full-prompt page allocations that sit
        # idle for rounds — but a chunked HEAD at capacity must not block
        # the short requests behind it either, so capacity-blocked
        # chunkers step aside (keeping FIFO among themselves) and the
        # next admissible request leads the group
        deferred: list[GenRequest] = []
        head: GenRequest | None = None
        while self._pending:
            candidate = self._pending[0]
            if self._assign_bucket(candidate) == 0:
                # oversized requests behind deferred chunkers reject here —
                # promoting one to head would admit it with bucket 0
                self._pending.popleft()
                candidate.finish_reason = "length"
                self._post_tokens(candidate, [], done=True)
                continue
            if (candidate.chunked
                    and len(self._chunking) >= config.prefill_max_batch):
                deferred.append(self._pending.popleft())
                continue
            head = candidate
            break
        if head is None:
            for request in reversed(deferred):
                self._pending.appendleft(request)
            return none
        bucket = self._assign_bucket(head)
        # history rows run the gathered-context attention path, which costs
        # O(S * max_context) regardless of hist — don't drag dense rows of
        # the same bucket through it (they'd pay for a hit they didn't get)
        with_hist = head.hist > 0
        # a prompt that fits its bucket's half program goes through it
        # ALONE: a short head takes nobody along, and a long head passes
        # over the short ones behind it (they lead a later admission, in
        # their order) — a wider batch is no cheaper a token, and a short
        # prompt's first token should not wait for a long one's positions
        alone = self._lone_length(head) is not None
        group: list[GenRequest] = []
        skipped: list[GenRequest] = []
        limit = 1 if alone else min(len(free_slots), config.prefill_max_batch)
        if head.chunked:
            limit = min(limit,
                        config.prefill_max_batch - len(self._chunking))
        while self._pending and len(group) < limit:
            request = self._pending.popleft()
            if head.chunked:
                # chunked requests group with each other regardless of hist
                # — chunk ROUNDS batch them (per-row absolute positions)
                ok = (self._assign_bucket(request) != 0 and request.chunked)
            else:
                ok = (self._assign_bucket(request) == bucket
                      and (request.hist > 0) == with_hist
                      and not request.chunked
                      and (self._lone_length(request) is not None) == alone)
            if ok:
                group.append(request)
            else:
                skipped.append(request)
        for request in reversed(skipped):  # preserve FIFO for other buckets
            self._pending.appendleft(request)
        for request in reversed(deferred):  # capacity-blocked chunkers first
            self._pending.appendleft(request)
        if not group:
            return none

        admitted: list[GenRequest] = []
        for request in group:
            total = min(len(request.prompt_ids) + request.max_tokens,
                        config.max_seq_len)
            slot = free_slots[len(admitted)]
            shared: list[int] = []
            # trace attribution for tier IO: spills/restores the match +
            # allocate below trigger emit tier.spill/tier.restore spans
            # into THIS request's trace (cleared after — spills forced by
            # later decode-time page growth stay unattributed)
            if self._tier_client is not None:
                self._tier_client.trace_ctx = request.trace_ctx
            try:
                if request.hist:
                    hist, shared = self.allocator.match_prefix(
                        request.prompt_ids)
                    if hist != request.hist:
                        # the cache moved between probe and admission
                        # (eviction or a longer registration): re-probe
                        # for a new bucket
                        self.allocator.release_prefix(shared)
                        request.bucket = -1
                        self._pending.appendleft(request)
                        continue
                if not self.allocator.allocate_slot(slot, total,
                                                    prefix_pages=shared):
                    # page pressure: release the match (references held
                    # past this point would pin pages and could deadlock
                    # admission) and retry later with a fresh probe
                    if self.metrics is not None:
                        self.metrics.llm_kv_alloc_failures.inc()
                    self.allocator.release_prefix(shared)
                    request.bucket = -1
                    self._pending.appendleft(request)
                    continue
            finally:
                if self._tier_client is not None:
                    self._tier_client.trace_ctx = None
            if shared and self.ledger is not None:
                # discounted prefill: these tokens were served from shared
                # prefix-cache pages. Same site semantics as the
                # allocator's prefix_hit_tokens (counted when the match is
                # CONSUMED by a successful allocate), so the per-tenant
                # slices conserve against it exactly
                self.ledger.add(request.tenant, cache_hit_tokens=(
                    len(shared) * self.allocator.page_size))
            request.slot = slot
            request.t_admit = self.timeline.stamp(
                "admit", request.request_id, slot)
            request.queue_ms = (request.t_admit - request.t_submit) * 1000
            self._observe_admitted(request)
            if request.chunked:
                # chunk-round scheduler owns it until the prompt is fully
                # prefilled; slots/pages are held, decode ignores it
                request.chunk_pos = request.hist
                self._chunking[slot] = request
            else:
                self._running[slot] = request
            admitted.append(request)
        if not admitted:
            return none
        self._sync_tables()
        return admitted, bucket

    def _prefill_end(self, request: GenRequest) -> int:
        """Where a request's prefill ends: its prompt's length, or under a
        block family the whole blocks of it (the tokens left open the first
        generation block as known positions)."""
        n = len(request.prompt_ids)
        return n - n % self._block if self._block else n

    def _prefill_admitted(self, admitted: list[GenRequest],
                          bucket: int) -> None:
        """One prefill dispatch over the just-admitted group, through to
        each request's first token (a block family's prefill yields none)."""
        tl = self.timeline
        any_hist = any(r.hist > 0 for r in admitted)
        kind = "prefill_hist" if any_hist else "prefill"
        # the length the rows are padded to: the bucket, or for a prompt
        # admitted alone because it fits it, the bucket's half program
        half = self._lone_length(admitted[0]) if len(admitted) == 1 else None
        length = half or bucket
        seq = tl.next_seq()
        parts: dict[str, Any] = {}
        with tl.span("prefill.build", seq, kind) as build:
            with tl.span("prefill.build.rows", seq, kind) as parts["rows"]:
                call, fields, per_row = self._pack_rows(
                    [(r, r.hist, self._prefill_end(r)) for r in admitted],
                    length)
            self._seal_call("prefill", seq, kind, fields, per_row, parts)
            # long buckets route through the sequence-parallel attention
            # path (shape-deterministic: SP-ness is a property of the
            # bucket; SP groups never carry history — _assign_bucket
            # guarantees it)
            if (self._prefill_sample_sp is not None
                    and bucket > self.config.sp_threshold):
                prefill_fn = self._prefill_sample_sp
            elif any_hist:
                # context-width bucket: history attention only needs to
                # span the longest admitted prompt (hist + suffix)
                prefill_fn = self._hist_fn(self._hist_ctx_for(
                    max(len(r.prompt_ids) for r in admitted)))
            else:
                prefill_fn = self._prefill_sample
        first, dispatch = self._launch_prefill(prefill_fn, seq, kind, build,
                                               call, parts)
        with tl.span("prefill.sync", seq, kind) as sync:
            first_host, *aux_host = _apart(jax.device_get(first))  # lint: allow[host-sync-in-hot-path] first-token fetch: prefill result feeds host-side admission
        drafts_host = aux_host.pop() if self._drafts else None
        counts = self._step_counts(aux_host)
        elapsed_ms = (sync.t1 - build.t0) * 1000
        self.stats.prefill_ms_total += elapsed_ms
        self.stats.prefill_batches += 1
        self.stats.prefill_requests += len(admitted)
        width = call.shape[0]  # the dispatched pad
        if not any_hist:
            real = sum(self._prefill_end(r) for r in admitted)
            self.stats.dense_prefill_tokens += real
            self.stats.dense_prefill_positions += width * length
            self.stats.half_prefill_batches += int(half is not None)
            if self.metrics is not None:
                rid = self.config.replica_id
                self.metrics.llm_dense_prefill_positions.labels(
                    replica=rid, kind="prompt").inc(real)
                self.metrics.llm_dense_prefill_positions.labels(
                    replica=rid, kind="padding").inc(width * length - real)
                if half is not None:
                    self.metrics.llm_half_prefill_batches.labels(
                        replica=rid).inc()
        self._count_expert_path(width * length)
        self._count_delta_body(length)
        tl.step(seq, kind, width, len(admitted), length, dispatch.t0, sync.t1,
                counts)
        self._record_step("prefill", seq=seq, batch=len(admitted),
                          width=width, dur_ms=elapsed_ms,
                          tokens=0 if self._block else len(admitted),
                          bucket=length, host_uploads=self._claim_uploads(),
                          phases=self._phase_row(parts, build, sync))
        with tl.span("prefill.emit", seq, kind):
            for i, request in enumerate(admitted):
                request.prefill_ms = elapsed_ms
                request.prefill_len = length
                # the prompt's pages are written: register the full ones
                # so later prompts sharing the prefix skip their KV —
                # BEFORE emitting, as a first token that finishes the
                # request frees the slot's pages
                if self.config.prefix_cache:
                    self.allocator.register_prefix(request.slot,
                                                   request.prompt_ids)
                if not self._block:
                    self._emit(request, int(first_host[i]))
                    self._keep_draft(request, drafts_host, i)

    def _pack_rows(self, rows: list[tuple[GenRequest, int, int]], S: int):
        """Pack [(request, start, end)] prompt spans into the call of a
        prefill program padded to ``S`` (:meth:`_prefill_call`: ``tokens``,
        ``positions`` [B, S], ``last_idx``, ``slot_ids`` [B]). Returns the
        call's buffer, its fields (views of it) and the rows' sampling
        parameters ``(temperature, top_k, top_p)``. B pads to the next power
        of two so XLA compiles at most log2(prefill_max_batch)+1 shapes per
        width; padding rows stay idle (positions -1: no KV write — the same
        masking decode uses for inactive slots) and their samples are
        discarded. Shared by dense/suffix prefill and chunk rounds. Under a
        family that drafts on the device the field ``follow`` [B] says what
        follows each row's span: the prompt's next token where it goes on in
        a later chunk, -1 where it ends here (:meth:`_draft_beside`)."""
        B = 1
        while B < len(rows):
            B *= 2
        call, fields = self._prefill_call(S).host(B)
        temperature = np.zeros((B,), dtype=np.float32)
        top_k = np.zeros((B,), dtype=np.int32)
        top_p = np.ones((B,), dtype=np.float32)
        for i, (request, start, end) in enumerate(rows):
            n = end - start
            fields["tokens"][i, :n] = request.prompt_ids[start:end]
            fields["positions"][i, :n] = np.arange(start, end)
            fields["last_idx"][i] = max(n - 1, 0)
            fields["slot_ids"][i] = request.slot
            temperature[i] = request.temperature
            top_k[i] = request.top_k
            top_p[i] = request.top_p
            if self._drafts and end < len(request.prompt_ids):
                fields["follow"][i] = request.prompt_ids[end]
        return call, fields, (temperature, top_k, top_p)

    def _launch_prefill(self, prefill_fn, seq: int, kind: str, build,
                        call: np.ndarray, parts: dict[str, Any]):
        """The ``prefill.dispatch`` span of a prefill or a chunk round: the
        packed call onto the device, then the jitted call alone. Returns the
        program's result (still on the device) and the span."""
        tl = self.timeline
        with tl.span("prefill.dispatch", seq, kind) as dispatch:
            with tl.span("prefill.dispatch.upload", seq, kind) \
                    as parts["upload"]:
                packed = self._upload(call)
            with tl.span("prefill.dispatch.launch", seq, kind) \
                    as parts["launch"]:
                first, self.kv = prefill_fn(self.params, self.kv, packed,
                                            self._rng)
        self._host_fed(seq, kind, build, dispatch, parts)
        return first, dispatch

    def _chunk_round(self) -> None:
        """Advance every mid-prefill long prompt by ONE chunk, batched.

        Prompts longer than every bucket prefill in bucket-sized chunks
        through the history path — chunk i attends to chunks 0..i-1
        already in the slot's pages (plus any cached prefix). Rows carry
        ABSOLUTE positions, so requests at different chunk offsets batch
        into one dispatch (previously each long prompt chunked alone at
        B=1, serializing summarizer-style concurrent traffic). Mid-chunk
        samples predict known prompt tokens and are discarded; a row
        whose prompt completes this round emits its sampled token and
        moves to decode."""
        config = self.config
        batch = list(self._chunking.values())[:config.prefill_max_batch]
        # the smallest bucket covering the WIDEST remaining span this
        # round — rows all on short final chunks must not pay a
        # max-bucket-wide dispatch (every (B, bucket) pair is warmed)
        max_remaining = max(self._prefill_end(r) - r.chunk_pos for r in batch)
        S = next((b for b in sorted(config.prefill_buckets)
                  if max_remaining <= b), max(config.prefill_buckets))
        tl = self.timeline
        seq = tl.next_seq()
        parts: dict[str, Any] = {}
        with tl.span("prefill.build", seq, "chunk") as build:
            with tl.span("prefill.build.rows", seq, "chunk") as parts["rows"]:
                rows: list[tuple[GenRequest, int, int]] = []
                max_end = 1
                for request in batch:
                    start = request.chunk_pos
                    end = min(start + S, self._prefill_end(request))
                    rows.append((request, start, end))
                    request.chunk_pos = end
                    max_end = max(max_end, end)
                call, fields, per_row = self._pack_rows(rows, S)
            self._seal_call("prefill", seq, "chunk", fields, per_row, parts)
            hist_fn = self._hist_fn(self._hist_ctx_for(max_end))
        first, dispatch = self._launch_prefill(hist_fn, seq, "chunk", build,
                                               call, parts)
        with tl.span("prefill.sync", seq, "chunk") as sync:
            first_host, *aux_host = _apart(jax.device_get(first))  # lint: allow[host-sync-in-hot-path] chunk-round boundary: host decides next chunk from these tokens
        drafts_host = aux_host.pop() if self._drafts else None
        counts = self._step_counts(aux_host)
        elapsed_ms = (sync.t1 - build.t0) * 1000
        self.stats.prefill_batches += 1
        self.stats.prefill_ms_total += elapsed_ms
        width = call.shape[0]
        self._count_expert_path(width * S)
        self._count_delta_body(S)
        tl.step(seq, "chunk", width, len(batch), S, dispatch.t0, sync.t1,
                counts)
        self._record_step(
            "chunk_prefill", seq=seq, batch=len(batch), width=width,
            dur_ms=elapsed_ms,
            tokens=0 if self._block else sum(
                1 for r in batch if r.chunk_pos >= len(r.prompt_ids)),
            bucket=S, host_uploads=self._claim_uploads(),
            phases=self._phase_row(parts, build, sync))
        with tl.span("prefill.emit", seq, "chunk"):
            for i, request in enumerate(batch):
                request.prefill_ms += elapsed_ms
                request.prefill_len = S
                if request.chunk_pos < self._prefill_end(request):
                    continue  # more chunks to go; sample discarded
                del self._chunking[request.slot]
                # register BEFORE emitting: a first token that finishes
                # the request (EOS / max_tokens=1) frees the slot's pages,
                # and a post-emit registration would cache nothing
                if config.prefix_cache:
                    self.allocator.register_prefix(request.slot,
                                                   request.prompt_ids)
                self.stats.prefill_requests += 1
                self._running[request.slot] = request
                if not self._block:
                    self._emit(request, int(first_host[i]))
                    self._keep_draft(request, drafts_host, i)

    # ------------------------------------------------------- speculative step

    def _keep_draft(self, request: GenRequest, drafts, row: int) -> None:
        """Keep with ``request`` the draft its last dispatch made on the
        device (``drafts`` [B] on the host; None where the family does not
        draft there): the guess for the position after its last token."""
        if drafts is not None:
            request.draft = (len(request.prompt_ids) + len(request.generated),
                             int(drafts[row]))

    def _draft_tokens(self, request: GenRequest, k: int) -> list[int]:
        """Prompt-lookup drafting: the most recent earlier occurrence of the
        trailing spec_ngram in (prompt + generated), returning up to k
        tokens that followed it. No draft model — the context itself is the
        proposer (works because summaries/tool outputs echo their inputs,
        and greedy decoding revisits its own phrases)."""
        n = self.config.spec_ngram
        ctx = request.prompt_ids + request.generated
        if len(ctx) <= n:
            return []
        tail = ctx[-n:]
        lo = max(0, len(ctx) - n - 512)  # bounded scan window
        for start in range(len(ctx) - n - 1, lo - 1, -1):
            if ctx[start:start + n] == tail:
                return ctx[start + n:start + n + k]
        return []

    def _any_would_draft(self) -> bool:
        """True iff some active row can take speculative drafts this step.
        Purely-sampled (or one-token-remaining) traffic pays ~spec_k x the
        attention/MLP compute through the [B,K] verify for zero extra
        emitted tokens — those steps run the plain width-1 decode instead
        (round-2 ADVICE low)."""
        return any(self._takes_draft(request)
                   for request in self._running.values())

    @staticmethod
    def _takes_draft(request: GenRequest) -> bool:
        """A greedy row with more than one token to go: the rows a verify
        step carries drafts for."""
        return (request.temperature == 0.0
                and request.max_tokens - len(request.generated) > 1)

    def _spec_step_all(self) -> None:
        """One [B, K] verify step over every active slot: row = last token
        + up to K-1 drafted continuations. Drafts are accepted while the
        model's own (sampled) next token agrees, so each dispatch emits
        1..K tokens per slot for a single param read. Greedy rows only get
        drafts; sampled rows ride along at width 1 (their one token is
        drawn from the true distribution). Rejected-draft KV is dead by
        masking: attention reads at position p only after some later chunk
        rewrites p. Where the family drafts on the device the row's draft
        is the one its last dispatch made (K is 2), the step program
        returns each row's next one beside the samples, and the step's
        record carries the counts of both."""
        B, K = self.config.max_batch, self.config.spec_k
        tl = self.timeline
        seq = tl.next_seq()
        active = list(self._running.items())
        wanted = sum(self._takes_draft(request) for _slot, request in active)
        parts: dict[str, Any] = {}
        with tl.span("decode.build", seq, "spec") as build:
            with tl.span("decode.build.rows", seq, "spec") as parts["rows"]:
                call, fields, per_row, widths, chunks = \
                    self._spec_rows(active, B, K)
            self._seal_call("decode", seq, "spec", fields, per_row, parts)
            max_pos = int(fields["positions"].max()) + 1 if active else K
            spec_ctx_pages = self._ctx_bucket_for(max_pos)
        with tl.span("decode.table_sync", seq, "spec") as parts["table_sync"]:
            self._sync_tables()
        with tl.span("decode.dispatch", seq, "spec") as dispatch:
            verify_fn = self._verify_fn(spec_ctx_pages)
            with tl.span("decode.dispatch.upload", seq, "spec") \
                    as parts["upload"]:
                packed = self._upload(call)
            with tl.span("decode.dispatch.launch", seq, "spec") \
                    as parts["launch"]:
                block, self.kv = verify_fn(self.params, self.kv, packed,
                                           self._rng)
        self._host_fed(seq, "spec", build, dispatch, parts)
        self.stats.decode_steps += 1
        self.stats.decode_dispatches += 1
        self.stats.spec_steps += 1
        self._count_expert_path(B * K)
        with tl.span("decode.readback", seq, "spec") as readback:
            block_host, *aux_host = _apart(jax.device_get(block))  # [B, K]  # lint: allow[host-sync-in-hot-path] spec verify: host must compare drafts to accept
        drafts_host = aux_host.pop() if self._drafts else None
        counts = self._step_counts(aux_host)
        spec_elapsed_ms = (readback.t1 - dispatch.t0) * 1000
        spec_emitted = 0
        with tl.span("decode.emit", seq, "spec"):
            for slot, request in active:
                if (request.finish_reason == "length"
                        and request.slot in self._running):
                    self._finish(request)
                    continue
                chunk = chunks.get(slot, [])
                sampled = block_host[slot]
                emitted = 0
                for j in range(widths[slot]):
                    # chunk[j] (j>0) is a draft: valid iff it matched the
                    # model's sample at the previous position
                    if j > 0 and chunk[j] != sampled[j - 1]:
                        break
                    self._emit(request, int(sampled[j]))
                    emitted += 1
                    if request.slot not in self._running:
                        break  # EOS/stop/max hit inside the chunk
                self._keep_draft(request, drafts_host, slot)
                self.stats.spec_tokens += max(0, emitted - 1)
                spec_emitted += emitted
        if counts is not None:
            # what only the host knows of a verify step: the rows a draft
            # could have served, and the tokens the step emitted
            counts = counts._replace(
                draft_wanted=float(wanted), spec_tokens=float(spec_emitted))
        tl.step(seq, "spec", B, len(active), spec_ctx_pages, dispatch.t0,
                readback.t1, counts)
        mfu, hbm_frac = self._observe_roofline(
            "spec_verify", B, spec_ctx_pages, spec_elapsed_ms)
        if self.signals is not None and active:
            # acceptance = EXTRA tokens per row this dispatch (0..K-1);
            # the controller's spec on/off knob acts on its EWMA
            self.signals.publish(
                "llm.spec_accept",
                max(0.0, spec_emitted / len(active) - 1.0),
                self.config.replica_id)
        self._record_step("spec_decode", seq=seq, batch=len(active), width=B,
                          dur_ms=spec_elapsed_ms, tokens=spec_emitted,
                          ctx_pages=spec_ctx_pages, mfu=mfu,
                          hbm_frac=hbm_frac,
                          host_uploads=self._claim_uploads(),
                          phases=self._phase_row(parts, build, readback))

    def _spec_rows(self, active: list[tuple[int, GenRequest]], B: int,
                   K: int):
        """Pack the verify step's [B, K] rows: each active slot's last
        token plus its drafts, cut to the pages the pool grants. Returns
        (the call's buffer, its fields ``tokens`` and ``positions``, the
        rows' sampling parameters, usable width and chunk by slot)."""
        call, fields = self._verify_call.host(B)
        tokens, positions = fields["tokens"], fields["positions"]
        temperature = np.zeros((B,), dtype=np.float32)
        top_k = np.zeros((B,), dtype=np.int32)
        top_p = np.ones((B,), dtype=np.float32)
        widths: dict[int, int] = {}
        chunks: dict[int, list[int]] = {}
        for slot, request in active:
            n_ctx = len(request.prompt_ids) + len(request.generated)
            p0 = n_ctx - 1
            remaining = max(0, request.max_tokens - len(request.generated))
            chunk = [request.generated[-1]]
            if self._takes_draft(request):
                if not self._drafts:
                    chunk += self._draft_tokens(request, K - 1)
                elif request.draft is not None and request.draft[0] == n_ctx:
                    chunk.append(request.draft[1])
            chunk = chunk[:min(K, remaining)]  # active => remaining >= 1
            # one allocator call per slot (not one per drafted token): the
            # usable width falls out of the granted token capacity
            # (n_ctx = p0 + 1: the verify chunk's first token sits at p0)
            usable = self.allocator.pregrant_block(slot, p0 + 1, len(chunk))
            widths[slot] = usable
            if usable == 0:
                # page pool exhausted mid-stream: the request truncates
                request.finish_reason = "length"
                if self.metrics is not None:
                    self.metrics.llm_kv_alloc_failures.inc()
                continue
            chunk = chunk[:usable]
            chunks[slot] = chunk
            tokens[slot, :usable] = chunk
            positions[slot, :usable] = np.arange(p0, p0 + usable)
            temperature[slot] = request.temperature
            top_k[slot] = request.top_k
            top_p[slot] = request.top_p
        return call, fields, (temperature, top_k, top_p), widths, chunks

    # ------------------------------------------------------------ decode step

    def _decode_step_all(self) -> None:
        """Serial decode: one fixed-shape step over every active slot,
        dispatched and retired back-to-back (the pre-overlap behavior;
        also the first step after any pipeline drain)."""
        inflight = self._decode_dispatch(None)
        self._decode_retire(inflight)

    def _decode_step_overlapped(self) -> None:
        """Depth-2 pipelined decode: dispatch step N+1 fed by step N's
        device-resident sampled tokens, THEN retire step N while the
        device executes N+1. The host's per-step work — device_get,
        emission, EOS checks, page extension — overlaps device compute
        instead of sitting between dispatches. Rows that finish inside
        step N still ride dispatch N+1 (their KV writes land in pages no
        one can reuse before the next drain barrier) and their lookahead
        tokens are discarded at retire, exactly like tokens past EOS
        inside a super-step."""
        k = self._k
        feed = self._inflight
        self._inflight = None
        if feed is not None:
            # barriers that invalidate the lookahead's slot->column map or
            # its token-feedback row:
            # - a row the in-flight dispatch doesn't cover (defensive —
            #   admission/chunk completion drain upstream);
            # - a PARTIAL budget on a row that will survive its retire
            #   (per-slot page cap granted 0 < b < k): the feedback fn
            #   feeds block row k-1, but the row's true last token is at
            #   b-1 — only a host-fed dispatch can resume it correctly
            stale = any(
                feed["reqs"].get(slot) is not request
                or (0 < feed["budgets"].get(slot, 0) < k
                    and len(request.generated) + feed["budgets"][slot]
                    < request.max_tokens)
                for slot, request in self._running.items())
            if stale:
                if not self._drain_feed(feed):
                    return
                feed = None
        if feed is not None and all(
                request.max_tokens - len(request.generated)
                - feed["budgets"].get(slot, 0) <= 0
                for slot, request in self._running.items()):
            # every surviving row's budget is already exhausted by the
            # in-flight tokens (max_tokens tail): a lookahead would sample
            # only discards — retire instead, keeping decode_steps and RNG
            # consumption identical to the serial path on these tails
            self._decode_retire(feed)
            return
        if feed is not None:
            # page-pressure pre-flight: the lookahead's grow_slot calls run
            # BEFORE retire N frees any EOS'd rows' pages, so dispatching
            # into a too-dry pool would truncate rows the serial order
            # (retire, then grow from the freed pages) would have served.
            # If the pool can't cover every surviving row's full want,
            # drain first — the retire may free pages, and the follow-up
            # host-fed dispatch then truncates exactly where serial would.
            deficit = 0
            for slot, request in self._running.items():
                pending = feed["budgets"].get(slot, 0)
                n_ctx = (len(request.prompt_ids) + len(request.generated)
                         + pending)
                want = min(k, max(0, request.max_tokens
                                  - len(request.generated) - pending))
                if want > 0:
                    deficit += max(
                        0, self.allocator.pages_needed(n_ctx + want - 1)
                        - self.allocator.slot_pages(slot))
            if deficit > self.allocator.free_pages:
                if not self._drain_feed(feed):
                    return
                feed = None
        nxt = self._decode_dispatch(feed)
        self._inflight = nxt
        if feed is not None:
            self._decode_retire(feed)

    def _drain_pipeline(self) -> None:
        """Retire the in-flight decode step, if any (pipeline barrier)."""
        inflight = self._inflight
        if inflight is None:
            return
        self._inflight = None
        self.stats.pipeline_drains += 1
        with self.timeline.span("loop.drain", inflight["seq"]):
            self._decode_retire(inflight)

    def _drain_feed(self, feed: dict[str, Any]) -> bool:
        """Barrier inside the overlap step: retire the fed step now and
        report whether any rows survive to dispatch."""
        self.stats.pipeline_drains += 1
        with self.timeline.span("loop.drain", feed["seq"]):
            self._decode_retire(feed)
        return bool(self._running)

    def _decode_dispatch(self, feed: dict[str, Any] | None
                         ) -> dict[str, Any]:  # lint: hot-path
        """Build and submit one decode SUPER-STEP dispatch, ``max_batch``
        rows wide (a row is a slot); returns the in-flight record the
        matching _decode_retire consumes.

        ``feed`` is the previous, still-in-flight step: its [k, B] sampled
        block (device-resident) supplies this step's input token, and host
        state advances OPTIMISTICALLY by the fed step's per-slot budgets.
        The optimism is sound: a row that survives its step always used
        its FULL budget (a short budget means max_tokens or the page pool
        ended it, i.e. the row dies at that step's retire), so surviving
        rows advance by exactly ``budget`` tokens and dead rows' lookahead
        output is discarded wholesale.

        Under a block family (``self._block``) the dispatch is a BLOCK step:
        never device-fed, its rows are blocks (:meth:`_block_rows`) and its
        program the family's block step, which returns the same record."""
        B = self.config.max_batch
        k = self._k
        tl = self.timeline
        seq = tl.next_seq()
        kind = "decode_fb" if feed is not None else "decode"
        first: dict[int, int] = {}
        parts: dict[str, Any] = {}
        with tl.span("decode.build", seq, kind) as build:
            with tl.span("decode.build.rows", seq, kind) as parts["rows"]:
                if self._block:
                    (call, fields, per_row, budgets, first, truncated,
                     reqs) = self._block_rows(B)
                    reach = int(fields["positions"].max()) + 1
                else:
                    call, fields, per_row, budgets, truncated, reqs = \
                        self._decode_rows(B, feed, k)
                    # the longest row this block can reach (seq_lens counts
                    # the incoming token; k-1 more may be written)
                    reach = int(fields["seq_lens"].max()) + k
            self._seal_call("decode", seq, kind, fields, per_row, parts,
                            steps=1 if self._block else k)
            ctx_pages = self._ctx_bucket_for(reach)   # context-width bucket
        with tl.span("decode.table_sync", seq, kind) as parts["table_sync"]:
            self._sync_tables()
        with tl.span("decode.dispatch", seq, kind) as dispatch:
            if self._block:
                step_fn = self._block_fn(ctx_pages)
            elif feed is None:
                step_fn = self._decode_fn(ctx_pages)
            else:
                step_fn = self._decode_fb_fn(ctx_pages)
            with tl.span("decode.dispatch.upload", seq, kind) \
                    as parts["upload"]:
                packed = self._upload(call)
            # a device-fed step's tokens: the block of the step in flight
            fed = () if feed is None else (feed["block"],)
            with tl.span("decode.dispatch.launch", seq, kind) \
                    as parts["launch"]:
                (block_tokens, block_valid, block_done, *block_aux), self.kv = \
                    step_fn(self.params, self.kv, packed, self._rng, *fed)
        if feed is None:
            self._host_fed(seq, kind, build, dispatch, parts)
        # dispatch-gap telemetry: host time since the last step retired,
        # with nothing in flight. A device-fed dispatch by construction
        # overlaps the still-running previous step, so its gap is zero.
        gap_s = 0.0
        if feed is None and tl.last_retired is not None:
            gap_s = max(0.0, dispatch.t0 - tl.last_retired)
        else:
            self.stats.overlap_steps += int(feed is not None)
        self.stats.dispatch_gap_ms_total += gap_s * 1000
        if self.metrics is not None:
            self.metrics.llm_dispatch_gap.labels(
                replica=self.config.replica_id).observe(gap_s)
        try:
            # D2H overlaps device compute (tokens + the super-step's
            # valid/done masks all retire in one readback)
            block_tokens.copy_to_host_async()
            block_valid.copy_to_host_async()
            block_done.copy_to_host_async()
        except AttributeError:
            pass
        self.stats.decode_steps += k
        self.stats.decode_dispatches += 1
        if not self._block:     # a block step's passes are known at retire
            self._count_expert_path(B, steps=k)
        return {"block": block_tokens, "valid": block_valid,
                "done": block_done, "aux": block_aux,
                "budgets": budgets, "reqs": reqs, "first": first,
                "truncated": truncated, "B": B, "k": k,
                "ctx_pages": ctx_pages, "batch": len(reqs), "seq": seq,
                "kind": kind, "t_dispatched": dispatch.t0, "gap_s": gap_s,
                "uploads": self._claim_uploads(),
                # the named host parts of a HOST-FED dispatch: its phase row
                # at retire (a device-fed step's host work overlaps its
                # predecessor on the device and holds nothing up)
                "build": build, "parts": parts if feed is None else None}

    def _decode_rows(self, B: int, feed: dict[str, Any] | None, k: int):
        """Pack one decode dispatch's [B] rows from the running set into its
        call (``_decode_call``; ``_decode_fb_call``, without ``tokens``,
        where ``feed`` supplies them on the device) and pre-grant its pages.
        Returns the call's buffer, its fields, the rows' sampling parameters
        and the per-slot bookkeeping the retire needs."""
        call, fields = (self._decode_call if feed is None
                        else self._decode_fb_call).host(B)
        positions, seq_lens = fields["positions"], fields["seq_lens"]
        temperature = np.zeros((B,), dtype=np.float32)
        top_k = np.zeros((B,), dtype=np.int32)
        top_p = np.ones((B,), dtype=np.float32)
        # device-side freeze inputs: per-slot token budgets (max_tokens
        # remainder ∧ granted pages) and the EOS/stop-id table — what
        # lets a finished row freeze INSIDE the super-step without a
        # host round-trip
        budget_arr, stop_tbl = fields["budgets"], fields["stop_tbl"]
        # per-slot budget within this block: page capacity and max_tokens cap
        # how many of the k decoded tokens are usable
        budgets: dict[int, int] = {}
        truncated: set[int] = set()
        reqs = dict(self._running)
        for slot, request in reqs.items():
            pending = feed["budgets"].get(slot, 0) if feed is not None else 0
            # n_ctx counts every token that exists (prompt + generated +
            # the fed step's budgeted-but-unseen tokens); the input token
            # sits at 0-based position n_ctx-1 and is written to the cache
            # this step, after which the slot's context length is n_ctx.
            n_ctx = len(request.prompt_ids) + len(request.generated) + pending
            if feed is None:
                fields["tokens"][slot] = request.generated[-1]
            positions[slot] = n_ctx - 1
            seq_lens[slot] = n_ctx
            temperature[slot] = request.temperature
            top_k[slot] = request.top_k
            top_p[slot] = request.top_p
            # pre-grant pages for the whole super-step in ONE allocator
            # call; writes beyond the granted range land on the reserved
            # trash page and their tokens are discarded via the budget
            remaining = max(0, request.max_tokens - len(request.generated)
                            - pending)
            want = min(k, remaining)
            usable = 0
            if want > 0:
                usable = self.allocator.pregrant_block(slot, n_ctx, want)
                if usable == 0:
                    # page pool exhausted mid-stream: the request truncates
                    # (finish happens at retire so the PREVIOUS step's
                    # tokens still emit first)
                    truncated.add(slot)
                    if self.metrics is not None:
                        self.metrics.llm_kv_alloc_failures.inc()
            budgets[slot] = usable
            budget_arr[slot] = usable
            stops = (self.tokenizer.eos_id,) + tuple(
                request.stop_ids)[:self._STOP_TBL_WIDTH - 1]
            stop_tbl[slot, :len(stops)] = stops
        return (call, fields, (temperature, top_k, top_p), budgets, truncated,
                reqs)

    def _block_rows(self, B: int):
        """Pack one block step's [B, Bl] rows from the running set and grant
        each block its page. A row's block starts at the last block boundary
        at or below its context: its first ``known`` positions hold the
        prompt's remainder (only a request's first block has one), the rest
        hold the mask token and are flagged masked. All ``Bl`` positions are
        computed and written whatever ``max_tokens`` leaves to emit, so the
        block's whole page must be granted or the request truncates. Returns
        the call's buffer and its fields (``_block_call``: an idle position
        holds the mask token), the rows' sampling parameters, and by slot the
        tokens to emit (``budgets``), where they start in the block
        (``first``), the rows the pool refused (``truncated``) and the
        requests."""
        Bl = self._block
        call, fields = self._block_call.host(B)
        tokens, positions, masked = (fields["tokens"], fields["positions"],
                                     fields["masked"])
        temperature = np.zeros((B,), dtype=np.float32)
        top_k = np.zeros((B,), dtype=np.int32)
        top_p = np.ones((B,), dtype=np.float32)
        budgets: dict[int, int] = {}
        first: dict[int, int] = {}
        truncated: set[int] = set()
        reqs = dict(self._running)
        for slot, request in reqs.items():
            n_ctx = len(request.prompt_ids) + len(request.generated)
            known = n_ctx % Bl
            start = n_ctx - known
            want = min(Bl - known, request.max_tokens - len(request.generated))
            # capacity for the whole block: positions start .. start + Bl - 1
            if self.allocator.pregrant_block(slot, start + 1, Bl) < Bl:
                truncated.add(slot)
                budgets[slot] = 0
                if self.metrics is not None:
                    self.metrics.llm_kv_alloc_failures.inc()
                continue
            if known:
                tokens[slot, :known] = (request.prompt_ids
                                        + request.generated)[start:]
            positions[slot] = np.arange(start, start + Bl)
            masked[slot, known:] = True
            temperature[slot] = request.temperature
            top_k[slot] = request.top_k
            top_p[slot] = request.top_p
            budgets[slot], first[slot] = max(0, want), known
        return (call, fields, (temperature, top_k, top_p), budgets, first,
                truncated, reqs)

    def _decode_retire(self, inflight: dict[str, Any]) -> None:  # lint: hot-path
        """Fetch and emit one dispatched decode SUPER-STEP: the [k, B]
        token block plus the device's valid/done masks come back in ONE
        readback, and up to k tokens per slot emit per sync. Under
        overlap this runs while the NEXT step executes on device, so
        every line here is off the device's critical path."""
        tl = self.timeline
        seq, kind = inflight["seq"], inflight["kind"]
        with tl.span("decode.readback", seq, kind) as readback:
            block_host, valid_host, done_host, *aux_host = jax.device_get(  # lint: allow[host-sync-in-hot-path] retire-side read-back — the ONE host sync per K-token super-step, overlapped by the in-flight dispatch
                (inflight["block"], inflight["valid"], inflight["done"],
                 *inflight.get("aux", ())))
        counts = None if self._block else self._step_counts(aux_host)
        if self._drafts and counts is not None:
            # a plain step of an engine that drafts on the device (no row
            # could take a draft, or speculation is switched off): the rows
            # it left without one
            counts = counts._replace(draft_wanted=float(sum(
                self._takes_draft(r) for r in inflight["reqs"].values())))
        t_dispatched, t_retired = inflight["t_dispatched"], readback.t1
        decode_elapsed_ms = (t_retired - t_dispatched) * 1000
        # the per-step wall: under the depth-2 pipeline this step was
        # dispatched while its PREDECESSOR still executed, so dispatch->
        # done spans ~2 device steps at steady state — the per-step wall
        # is retire-to-retire there, and dispatch->done only when the
        # device was idle at dispatch (serial path / first after drain)
        step_wall_ms = (t_retired - max(t_dispatched,
                                        tl.last_retired or 0.0)) * 1000
        if not self._block:     # a block step's record waits for its tokens
            tl.step(seq, kind, inflight["B"], inflight["batch"],
                    inflight["ctx_pages"], t_dispatched, t_retired, counts)
        self.stats.decode_ms_total += step_wall_ms
        first = inflight["first"]       # by slot; empty for token steps
        decode_emitted = 0
        with tl.span("decode.emit", seq, kind):
            for slot, request in inflight["reqs"].items():
                if self._running.get(slot) is not request:
                    continue  # finished at an earlier retire: lookahead discards
                if slot in inflight["truncated"]:
                    if request.finish_reason is None:
                        request.finish_reason = "length"
                    self._finish(request)
                    continue
                at = first.get(slot, 0)   # a block's known positions lead it
                for step_i in range(at, at + inflight["budgets"][slot]):
                    if not valid_host[step_i][slot]:
                        # the device froze this row mid-super-step
                        # (EOS/stop sampled earlier in the block): nothing
                        # real follows
                        break
                    self._emit(request, int(block_host[step_i][slot]))
                    decode_emitted += 1
                    if self._running.get(slot) is not request:
                        break  # finished (EOS/stop/max): rest discarded
        if self._block:
            passes, by_threshold = (int(v) for v in aux_host[0])
            self.stats.block_steps += 1
            self.stats.denoise_passes += passes
            self.stats.block_tokens += decode_emitted
            self.stats.block_positions_filled_by_threshold += by_threshold
            if self.metrics is not None:
                rid = self.config.replica_id
                self.metrics.llm_block_steps.labels(replica=rid).inc()
                self.metrics.llm_denoise_passes.labels(replica=rid).inc(passes)
                self.metrics.llm_block_tokens.labels(replica=rid).inc(
                    decode_emitted)
                self.metrics.llm_block_threshold_fills.labels(
                    replica=rid).inc(by_threshold)
            # every pass and the commit ran the experts over the whole width
            self._count_expert_path(inflight["B"] * self._block,
                                    steps=passes + 1)
            tl.step(seq, kind, inflight["B"], inflight["batch"],
                    inflight["ctx_pages"], t_dispatched, t_retired,
                    StepCounts(0.0, 0.0, 0.0, denoise_passes=float(passes),
                               block_tokens=float(decode_emitted),
                               filled_by_threshold=float(by_threshold)))
        # a phase row exists only when the host-fed dispatch reached retire
        # intact (crash/drop paths discard the inflight record, so partial
        # rows never surface)
        phases = self._phase_row(inflight["parts"], inflight["build"],
                                 readback)
        mfu, hbm_frac = self._observe_roofline(
            kind, inflight["B"], inflight["ctx_pages"], step_wall_ms,
            k=inflight["k"])
        self._gap_window.append((inflight["gap_s"],
                                 decode_elapsed_ms / 1000))
        self._record_step("decode", seq=seq, batch=inflight["batch"],
                          width=inflight["B"], dur_ms=decode_elapsed_ms,
                          tokens=decode_emitted,
                          ctx_pages=inflight["ctx_pages"],
                          gap_ms=inflight["gap_s"] * 1000,
                          phases=phases, mfu=mfu, hbm_frac=hbm_frac,
                          host_uploads=inflight["uploads"],
                          superstep=inflight["k"],
                          frozen=int(done_host.sum()),
                          wall_ms=step_wall_ms)
        if self.metrics is not None:
            self.metrics.llm_device_idle_frac.labels(
                replica=self.config.replica_id).set(
                self.device_idle_fraction())

    def device_idle_fraction(self) -> float:
        """Host dispatch-gap share of decode wall over the recent window:
        host-clock gaps before host-fed decode dispatches / (gaps +
        dispatch-to-retire wall). A host-side number the overlapped
        pipeline drives to ~0 — NOT the device's idle time, which only a
        profiler trace shows (the benchmark's ``device.idle_share.*``)."""
        gaps = walls = 0.0
        # snapshot first: callers include the asyncio thread (diagnostics,
        # bench) while the dispatch thread appends
        for gap_s, wall_s in list(self._gap_window):
            gaps += gap_s
            walls += wall_s
        total = gaps + walls
        return gaps / total if total > 0 else 0.0

    # --------------------------------------------------------------- telemetry

    def _seal_call(self, family: str, seq: int, kind: str,
                   fields: dict[str, np.ndarray], per_row,
                   parts: dict[str, Any], steps: int = 1) -> None:
        """The two named tails of every build, both on the host: the rows'
        sampling parameters into the call (the tier counted), then the
        dispatch's number, from which the step program folds its key.
        ``family`` is ``prefill`` or ``decode``."""
        tl = self.timeline
        with tl.span(family + ".build.sampling", seq, kind) \
                as parts["sampling"]:
            self._sampling_params(fields, *per_row, steps=steps)
        with tl.span(family + ".build.rng", seq, kind) as parts["rng"]:
            fields["counter"][:] = self._next_dispatch()

    def _next_dispatch(self) -> int:
        """The number the next dispatch's key is folded from: 1, 2, ... in
        dispatch order. It rides as an int32, so past 2**31 - 1 the base key
        itself moves on (once in years of serving) and the count restarts:
        no two dispatches of an engine share a key."""
        self._dispatch_no += 1
        if self._dispatch_no > 0x7FFFFFFF:
            self._rng = jax.random.fold_in(self._rng, 0)
            self._dispatch_no = 1
        return self._dispatch_no

    def _claim_uploads(self) -> int:
        """For a dispatch's step record: the transfers made since the last
        dispatch claimed its own, so its call and the table syncs that led
        to it (an admission's, its own)."""
        made = self.stats.host_uploads - self._uploads_claimed
        self._uploads_claimed = self.stats.host_uploads
        return made

    def _upload(self, call: np.ndarray):
        """A dispatch's ONE host-to-device transfer: its packed call."""
        self.stats.host_uploads += 1
        return jax.device_put(call, self._call_sharding)

    def _host_fed(self, seq: int, kind: str, build, dispatch,
                  parts: dict[str, Any]) -> None:
        """After a HOST-FED dispatch is launched: the device sat drained
        from ``build.t0`` to ``dispatch.t1``, usually ~2 ms. Past
        ``STALL_S`` that is a stall: counted, and logged once with the part
        that held most of it and what paused the process meanwhile."""
        held_s = dispatch.t1 - build.t0
        if held_s <= STALL_S:
            return
        self.stats.dispatch_stalls += 1
        name, span = max(parts.items(), key=lambda kv: kv[1].t1 - kv[1].t0)
        pauses = [
            f"{p.cause}{p.detail} {(p.t1 - p.t0) * 1e3:.1f} ms on {p.thread}"
            for p in self.timeline.pauses_between(build.t0, dispatch.t1)]
        logger.warning(
            "tpu_local dispatch stall: step %d (%s) held the drained device "
            "%.1f ms from build to launch; %s took %.1f ms wall, %.1f ms on "
            "the CPU; pauses in it: %s", seq, kind, held_s * 1e3, name,
            span.ms, span.cpu * 1e3, ", ".join(pauses) or "none")

    def _phase_row(self, parts: dict[str, Any] | None, build,
                   readback) -> dict[str, float] | None:
        """The phase row of one host-fed dispatch, read off the spans it
        left (``rows``, ``sampling``, ``rng``, ``table_sync`` where the
        dispatch syncs one, ``upload``, ``launch``, and ``readback``: the
        wait for its result), ms, with ``total_ms`` = ``build.t0 ->
        readback.t1``; also observed into the per-phase histograms. None for
        a device-fed dispatch. Runs at retire on the dispatch thread."""
        if parts is None:
            return None
        phases = {name + "_ms": span.ms for name, span in parts.items()}
        phases["readback_ms"] = readback.ms
        if self.metrics is not None:
            observers = self._phase_observers   # a histogram child a phase
            for key, dur_ms in phases.items():
                if key not in observers:
                    observers[key] = self.metrics.llm_step_phase.labels(
                        replica=self.config.replica_id, phase=key[:-3])
                observers[key].observe(dur_ms / 1e3)
        phases["total_ms"] = (readback.t1 - build.t0) * 1e3
        return phases

    def _observe_roofline(self, kind: str, width: int, ctx_pages: int,
                          dur_ms: float, k: int | None = None
                          ) -> tuple[float | None, float | None]:
        """Live roofline: the dispatched executable's warmup-captured XLA
        cost over this step's measured wall. Feeds the mcpforge_llm_mfu /
        hbm_roofline_frac gauges and the snapshot window; (None, None)
        when the registry has no entry (unwarmed engine or cost capture
        off). ``k`` selects the rung-suffixed cost entry when adaptive K
        moved off the static rung (FLOPs/bytes scale with K)."""
        entry = None
        if k is not None and k != self.config.superstep:
            entry = self.cost_registry.lookup(f"{kind}@k{k}", width,
                                              ctx_pages)
            if entry is None and kind == "decode_fb":
                entry = self.cost_registry.lookup(f"decode@k{k}", width,
                                                  ctx_pages)
        if entry is None:
            entry = self.cost_registry.lookup(kind, width, ctx_pages)
        if entry is None and kind == "decode_fb":
            entry = self.cost_registry.lookup("decode", width, ctx_pages)
        if entry is None or dur_ms <= 0:
            return None, None
        dur_s = dur_ms / 1e3
        mfu, frac = roofline_fractions(
            entry.flops, entry.bytes_accessed, dur_s, self.mesh.size,
            self.config.peak_tflops_per_chip, self.config.hbm_gbps_per_chip)
        self._roofline_window.append((entry.flops, entry.bytes_accessed,
                                      dur_s))
        if self.metrics is not None:
            rid = self.config.replica_id
            self.metrics.llm_mfu.labels(replica=rid).set(mfu)
            self.metrics.llm_hbm_roofline.labels(replica=rid).set(frac)
        return mfu, frac

    def roofline_snapshot(self) -> dict[str, Any]:
        """Aggregate cost-model roofline over the recent decode window
        (the live gauges' numbers, read at ``/admin/engine/steps``)."""
        flops = byts = dur = 0.0
        window = list(self._roofline_window)
        for f, b, d in window:
            flops += f
            byts += b
            dur += d
        out: dict[str, Any] = {
            "window_steps": len(window),
            "cost_entries": self.cost_registry.counts(),
        }
        if dur > 0:
            mfu, frac = roofline_fractions(
                flops, byts, dur, self.mesh.size,
                self.config.peak_tflops_per_chip,
                self.config.hbm_gbps_per_chip)
            # 12 digits: a CPU-test replica's MFU sits at ~1e-7 — and a
            # load-stalled host can stretch one step's wall enough to
            # push it below 1e-9 — it must never round to a dead 0.0
            out["mfu"] = round(mfu, 12)
            out["hbm_roofline_frac"] = round(frac, 12)
        return out

    def _on_xla_compile(self, stage: str, duration_s: float) -> None:
        """CompileTracker callback — runs on whichever thread compiled.
        Counts every attributed compile; serving-stage compiles (the
        mid-traffic kind PR 5 proved catastrophic) also emit a span so
        they are findable next to the request traces they stalled."""
        rid = self.config.replica_id
        if self.metrics is not None:
            try:
                self.metrics.llm_xla_compiles.labels(
                    replica=rid, stage=stage).inc()
                self.metrics.llm_xla_compile_time.labels(
                    replica=rid).observe(duration_s)
            except Exception:
                pass
        if stage == "serving" and self.tracer is not None:
            try:
                now = time.time()
                self.tracer.emit_span(
                    "llm.xla_compile", now - duration_s, now,
                    attributes={"gen_ai.request.model": self.config.model,
                                "llm.replica_id": rid,
                                "llm.compile_stage": stage})
            except Exception:
                pass  # telemetry must never break the compiling thread

    def compile_stats(self) -> dict[str, Any]:
        """Warmup/serving XLA compile counts + timings (admin surfaces,
        pool status, support bundle)."""
        return self.compile_tracker.snapshot()

    def _count_expert_path(self, tokens: int, steps: int = 1) -> None:
        """Count ``steps`` steps of ``tokens`` tokens each by the expert
        formulation their program traced (nothing for a model without
        routed experts); the engine's dtype is its activations'."""
        path = self._family.expert_path(self.model_config, self.mesh, tokens,
                                        self._kv_dtype)
        if path == "grouped":
            self.stats.moe_grouped_steps += steps
        elif path == "scan":
            self.stats.moe_scan_steps += steps

    def _count_delta_body(self, seq: int) -> None:
        """Count a prefill dispatch or a chunk round of ``seq`` positions a
        row by the body its delta-rule kernel traced (nothing for a family
        without per-sequence state, or where the ``jax.numpy`` twin runs)."""
        rule = getattr(self._family, "delta_body", None)
        body = rule(self.model_config, self.mesh, seq) if rule else None
        if body == "chunkwise":
            self.stats.delta_chunkwise_steps += 1
        elif body == "walk":
            self.stats.delta_walk_steps += 1

    def _sampling_params(self, fields: dict[str, np.ndarray],
                         temperature: np.ndarray, top_k: np.ndarray,
                         top_p: np.ndarray, steps: int = 1) -> None:
        """A dispatch's per-row parameters into its call, its ``steps``
        sampled steps counted by the tier the step program will take."""
        rows = SamplingParams(temperature, top_k, top_p)
        samples, filters = rows.tiers()
        if filters:
            self.stats.sample_filtered_steps += steps
        elif samples:
            self.stats.sample_plain_steps += steps
        else:
            self.stats.sample_argmax_steps += steps
        for name, values in zip(SamplingParams._fields, rows):
            fields[name][:] = values

    def _step_counts(self, aux: list) -> StepCounts | None:
        """What a step program counted on the device, from what it returned
        beside its tokens in the one readback: nothing (``aux`` empty: the
        GQA family), or one vector ``[moe_tokens, moe_local_pairs, summed
        selected / context share, rows]`` (``STEP_AUX``), which a family
        with per-sequence state extends by ``[live state rows, real tokens
        scanned]`` and one with window layers by ``[the live rows' summed
        context, their summed min(context, window)]`` after those; a verify
        step that drafts on the device returns a second
        vector after it, ``[rows that carried a draft, drafts accepted]``.
        The counts go to ``EngineStats`` and onto the step's timeline
        record."""
        if not aux:
            return None
        moe_tokens, pairs, share_sum, rows, *state = (float(v) for v in aux[0])
        self.stats.moe_tokens += int(moe_tokens)
        self.stats.moe_local_pairs += int(pairs)
        live, scanned, context_keys, window_keys = (*state, 0.0, 0.0, 0.0,
                                                    0.0)[:4]
        self.stats.state_scanned_tokens += int(scanned)
        self.stats.context_keys += int(context_keys)
        self.stats.window_keys += int(window_keys)
        drafted, accepted = ((float(v) for v in aux[1]) if len(aux) > 1
                             else (0.0, 0.0))
        self.stats.spec_drafted += int(drafted)
        self.stats.spec_accepted += int(accepted)
        return StepCounts(share_sum / rows if rows else 0.0, moe_tokens, pairs,
                          live, scanned, draft_rows=drafted,
                          drafts_accepted=accepted, context_keys=context_keys,
                          window_keys=window_keys)

    def _record_step(self, kind: str, *, seq: int, batch: int, width: int,
                     dur_ms: float, tokens: int, bucket: int | None = None,
                     ctx_pages: int | None = None,
                     gap_ms: float | None = None,
                     phases: dict[str, float] | None = None,
                     mfu: float | None = None,
                     hbm_frac: float | None = None,
                     superstep: int | None = None,
                     frozen: int | None = None,
                     wall_ms: float | None = None,
                     host_uploads: int = 0) -> None:
        """One ring-buffer entry + gauge refresh per device dispatch.
        Runs on the dispatch thread; deque.append and prometheus_client
        ops are both thread-safe, and the asyncio side only ever copies
        the deque (recent_steps), never mutates it."""
        depth = self._work.qsize() + len(self._pending)
        pages_in_use = self.allocator.pages_in_use
        self.stats.state_rows_in_use = self.allocator.rows_in_use
        self.step_log.append({
            "seq": seq,                         # the timeline's step number
            "ts": time.time(),
            "kind": kind,                       # prefill|chunk_prefill|decode|spec_decode
            "batch": batch,                     # rows carrying real work
            "width": width,                     # padded dispatch width
            "bucket": bucket,                   # length a prefill was padded to (S)
            "ctx_pages": ctx_pages,             # decode context-width bucket
            "duration_ms": round(dur_ms, 3),
            "tokens": tokens,                   # tokens emitted by this step
            # decode iterations fused into this dispatch (None for
            # prefill rows) and rows the device froze mid-super-step —
            # K>1 accounting: tokens ≈ batch × superstep at steady state,
            # and ONE host sync retired them all
            "superstep": superstep,
            "frozen": frozen,
            "queue_depth": depth,
            "kv_pages_in_use": pages_in_use,
            # host-side stall before this dispatch (decode only; 0 when the
            # overlapped pipeline kept the device fed)
            "gap_ms": round(gap_ms, 3) if gap_ms is not None else None,
            # the host phases of a host-fed dispatch, off its timeline
            # spans (None for a device-fed one), and live cost-model roofline
            "phases": ({k: round(v, 3) for k, v in phases.items()}
                       if phases is not None else None),
            # host-to-device transfers made for this dispatch: its packed
            # call, and a table sync where rows were dirty (a count, so
            # beside the phases, whose keys are milliseconds)
            "host_uploads": host_uploads,
            "mfu": round(mfu, 12) if mfu is not None else None,
            "hbm_frac": round(hbm_frac, 12) if hbm_frac is not None else None,
        })
        if (kind in ("decode", "spec_decode") and superstep is not None
                and tokens):
            # smoothed tokens-per-dispatch (satellite): updated before
            # the gauge refresh below so the exported EWMA includes this
            # very step
            self._tpd_ewma = (
                float(tokens) if self._tpd_ewma is None
                else _TPD_EWMA_ALPHA * tokens
                + (1.0 - _TPD_EWMA_ALPHA) * self._tpd_ewma)
        m = self.metrics
        if m is not None:
            rid = self.config.replica_id
            m.llm_batch_occupancy.labels(replica=rid).set(
                len(self._running) + len(self._chunking))
            m.llm_kv_pages_in_use.labels(replica=rid).set(pages_in_use)
            m.llm_kv_page_utilization.labels(replica=rid).set(
                pages_in_use / max(1, self.num_kv_pages - 1))
            # dtype-aware byte view: pages x page bytes under the ACTIVE
            # KV dtype, so int8 and bf16 engines are comparable on one
            # dashboard even though their page counts differ 2x
            m.llm_kv_bytes_in_use.labels(
                replica=self.config.replica_id).set(self.kv_bytes_in_use())
            if self.allocator.state_rows:
                m.llm_state_rows_in_use.labels(replica=rid).set(
                    self.allocator.rows_in_use)
                m.llm_state_bytes.labels(replica=rid).set(
                    self.state_bytes_in_use())
            m.llm_queue_depth.labels(replica=rid).set(depth)
            # tokens/s over the TRUE per-step wall (retire-to-retire under
            # the depth-2 overlap — dur_ms there spans ~2 device steps and
            # would halve the gauge); tokens counts every token this
            # dispatch emitted, so the gauge stays truthful at superstep>1
            rate_ms = wall_ms if wall_ms is not None else dur_ms
            if rate_ms > 0 and tokens:
                m.llm_step_tokens_per_sec.labels(replica=rid).set(
                    tokens / (rate_ms / 1e3))
            if superstep is not None and tokens:
                m.llm_tokens_per_dispatch.labels(replica=rid).set(tokens)
                if self._tpd_ewma is not None:
                    # smoothed twin (satellite): the instantaneous gauge
                    # whipsaws with batch occupancy — alerts and the
                    # controller act on this one
                    m.llm_tokens_per_dispatch_ewma.labels(
                        replica=rid).set(self._tpd_ewma)
            if self._tier_client is not None:
                self._export_tier_metrics(m, rid)
        if kind in ("decode", "spec_decode"):
            self._publish_signals(tokens=tokens, depth=depth, mfu=mfu,
                                  hbm_frac=hbm_frac, gap_ms=gap_ms,
                                  wall_ms=wall_ms if wall_ms is not None
                                  else dur_ms)

    def _publish_signals(self, *, tokens: int, depth: int,
                         mfu: float | None, hbm_frac: float | None,
                         gap_ms: float | None,
                         wall_ms: float | None) -> None:
        """Push this decode dispatch's signals onto the live bus (the
        controller's inputs — docs/controller.md signal catalog). Every
        publish is O(1); the O(window) idle fraction goes out on a
        bounded tick, not per retire. No bus = one attribute check."""
        bus = self.signals
        if bus is None:
            return
        rid = self.config.replica_id
        if tokens:
            bus.publish("llm.tokens_per_dispatch", tokens, rid)
        if mfu is not None:
            bus.publish("llm.mfu", mfu, rid)  # lint: allow[signal-name-conformance] dashboard-only export via the /signals snapshot
        if hbm_frac is not None:
            bus.publish("llm.hbm_roofline_frac", hbm_frac, rid)  # lint: allow[signal-name-conformance] dashboard-only export via the /signals snapshot
        if gap_ms is not None:
            bus.publish("llm.dispatch_gap_ms", gap_ms, rid)  # lint: allow[signal-name-conformance] dashboard-only export via the /signals snapshot
        if wall_ms is not None and wall_ms > 0 and tokens:
            bus.publish("llm.step_tokens_per_sec",
                        tokens / (wall_ms / 1e3), rid)
        bus.publish("llm.saturation",  # lint: allow[signal-name-conformance] dashboard-only export via the /signals snapshot
                    depth / max(1, self.config.max_queue), rid)
        now = self.timeline.last_retired or 0.0  # this step's retire stamp
        if now - self._signals_slow_ts >= 0.25:
            self._signals_slow_ts = now
            bus.publish("llm.idle_frac", self.device_idle_fraction(), rid)

    def _export_tier_metrics(self, m, rid: str) -> None:
        """Per-tier prefix counters/gauges (dispatch thread, piggybacked
        on the per-step gauge refresh): hit counters export as deltas
        from the allocator's consume-site totals; byte gauges report HBM
        residency per replica and the shared store's host/disk footprint
        (pool-shared, so every replica's child reports the same store
        number — read one, don't sum)."""
        alloc = self.allocator
        for tier, count in alloc.tier_hits.items():
            prev = self._tier_hits_exported.get(tier, 0)
            if count > prev:
                m.llm_prefix_tier_hits.labels(replica=rid, tier=tier).inc(
                    count - prev)
                self._tier_hits_exported[tier] = count
        m.llm_prefix_tier_bytes.labels(replica=rid, tier="hbm").set(
            alloc.cached_pages * self._kv_page_bytes)
        store = self._tier_client.store
        if store is not None:
            s = store.stats()
            m.llm_prefix_tier_bytes.labels(replica=rid, tier="host").set(
                s["host_bytes"])
            m.llm_prefix_tier_bytes.labels(replica=rid, tier="disk").set(
                s["disk_bytes"])
            if "object_bytes" in s:
                m.llm_prefix_tier_bytes.labels(
                    replica=rid, tier="object").set(s["object_bytes"])

    def recent_steps(self, limit: int | None = None) -> list[dict[str, Any]]:
        """Last N step summaries, oldest first (diagnostics surface)."""
        steps = list(self.step_log)
        if limit is not None and limit > 0:
            steps = steps[-limit:]
        return steps

    def _span(self, name: str, request: GenRequest, start_ts: float,
              end_ts: float, status: str = "OK",
              events: list[tuple[float, str, dict[str, Any]]] | None = None,
              **attrs: Any) -> None:
        """Emit one per-request engine span parented to the submitter's
        llm.request span (no contextvars on the dispatch thread)."""
        if self.tracer is None or request.trace_ctx is None:
            return
        attributes: dict[str, Any] = {
            "gen_ai.system": "tpu_local",
            "gen_ai.request.model": self.config.model,
            "llm.replica_id": self.config.replica_id,
            "llm.slot": request.slot,
        }
        if request.tenant:
            attributes["llm.tenant"] = request.tenant
        attributes.update(attrs)
        try:
            self.tracer.emit_span(name, start_ts, end_ts,
                                  trace_ctx=request.trace_ctx,
                                  attributes=attributes, status=status,
                                  events=events)
        except Exception:
            pass  # telemetry must never kill the dispatch thread

    def _tenant_label(self, request: GenRequest) -> str:
        """Clamped Prometheus tenant label for a request (the registry's
        shared TenantClamp bounds the exported child set)."""
        return self.metrics.tenant_clamp.label(request.tenant)

    def _exemplar(self, metric: str, value: float, request: GenRequest,
                  labels: tuple = ()) -> dict[str, str] | None:
        """Trace-id exemplar for a latency observe (None when the
        request is unattributed or exemplars are off) — the forensics
        click-through from a histogram bucket to the retained trace.
        ``labels`` must match the ``.labels(...)`` child the observe
        targets (prometheus keeps exemplars per labeled child)."""
        if self.metrics is None or request.trace_ctx is None:
            return None
        return self.metrics.exemplar(metric, value, request.trace_ctx[0],
                                     labels)

    def _observe_admitted(self, request: GenRequest) -> None:
        """Queue-phase telemetry at the moment a request wins a slot."""
        if request.queue_observed:
            return  # re-admission after crash recovery
        request.queue_observed = True
        if self.metrics is not None:
            wait_s = max(0.0, request.queue_ms / 1e3)
            tenant = self._tenant_label(request)
            self.metrics.llm_queue_wait.labels(tenant=tenant).observe(
                wait_s, exemplar=self._exemplar("llm_queue_wait", wait_s,
                                                request, (tenant,)))
        if self.signals is not None:
            self.signals.publish("llm.queue_wait_ms",
                                 max(0.0, request.queue_ms),
                                 self.config.replica_id)
        self._span("llm.queue", request, request.created,
                   request.created + request.queue_ms / 1e3,
                   **{"llm.queue_ms": round(request.queue_ms, 2),
                      "llm.priority": request.priority})

    def _observe_finish(self, request: GenRequest) -> None:
        """Decode-phase telemetry when a request leaves the engine: TPOT
        over the inter-token phase + the llm.decode span."""
        request.t_done = self.timeline.stamp(
            "done", request.request_id, request.slot)
        n = len(request.generated)
        decode_s = max(0.0, request.t_done
                       - (request.t_first or request.t_done))
        # epoch twins of the stamps for the OTel span: wall-clock start,
        # durations from the timeline
        decode_start = request.created + (
            (request.t_first or request.t_done) - request.t_submit)
        if self.metrics is not None and n > 1:
            tpot_s = decode_s / (n - 1)
            tenant = self._tenant_label(request)
            self.metrics.llm_tpot.labels(
                model=self.config.model,
                replica=self.config.replica_id,
                tenant=tenant).observe(
                tpot_s, exemplar=self._exemplar(
                    "llm_tpot", tpot_s, request,
                    (self.config.model, self.config.replica_id, tenant)))
        if self.signals is not None and n > 1:
            self.signals.publish(  # lint: allow[signal-name-conformance] dashboard-only export via the /signals snapshot
                "llm.tpot_ms", decode_s / (n - 1) * 1e3,
                self.config.replica_id)
        if self.ledger is not None and request.slot >= 0:
            # HBM residency: pages this request held x its resident wall
            # (admission -> retire; pages are still held here — the
            # callers free the slot AFTER _observe_finish)
            self.ledger.add(request.tenant, kv_page_seconds=(
                self.allocator.slot_pages(request.slot)
                * max(0.0, request.t_done - request.t_admit)))
        reason = request.finish_reason or "stop"
        phase_events = self._decode_phase_events(request, decode_start)
        self._span("llm.decode", request, decode_start,
                   decode_start + decode_s,
                   status="OK" if reason in ("stop", "length") else "ERROR",
                   events=phase_events or None,
                   **{"gen_ai.usage.completion_tokens": n,
                      "llm.finish_reason": reason,
                      "llm.kv_pages": self.allocator.slot_pages(request.slot),
                      **self._window_attrs(len(request.prompt_ids) + n)})

    def _decode_phase_events(self, request: GenRequest, since_ts: float
                             ) -> list[tuple[float, str, dict[str, Any]]]:
        """The phase rows of the last host-fed decode dispatches since
        ``since_ts`` (at most eight), as events for the request's
        ``llm.decode`` span: the trace-side view of the step ring
        (batch-wide, so shared by the requests decoding together)."""
        events: list[tuple[float, str, dict[str, Any]]] = []
        if self.tracer is None or request.trace_ctx is None:
            return events
        for row in reversed(self.step_log):
            if row["ts"] < since_ts or len(events) == 8:
                break
            if row["phases"] and row["kind"] in ("decode", "spec_decode"):
                events.append((row["ts"], "decode.step.phases", row["phases"]))
        events.reverse()
        return events

    # ---------------------------------------------------------------- plumbing

    def _sync_tables(self) -> None:
        """Refresh the device block table — but only when the allocator
        marked rows dirty since the last sync. Steady-state decode (no
        page growth, no finishes) uploads NOTHING: the previous table
        rides through the donated kv pytree unchanged."""
        if self.allocator.dirty:
            # upload under the table's existing (replicated NamedSharding)
            # placement: the pjit cache keys on input shardings, so a bare
            # jnp.array here — single-device, uncommitted — would recompile
            # every warmup-built executable at its first traffic hit. The
            # host table goes up as it is: one transfer, no program
            fresh = {"block_tables": jax.device_put(
                self.allocator.tables_host(), self.kv.block_tables.sharding)}
            if self.allocator.state_rows:
                # a slot's state row rides beside its block-table row
                fresh["state_rows"] = jax.device_put(
                    self.allocator.state_row_table(),
                    self.kv.state_rows.sharding)
            self.stats.host_uploads += len(fresh)
            self.kv = self.kv._replace(**fresh)

    def _emit(self, request: GenRequest, token: int) -> None:
        request.generated.append(token)
        self.stats.completion_tokens += 1
        if self.ledger is not None:
            # same site as stats.completion_tokens (conservation gate);
            # counting at retire rather than finish means a failover
            # never loses a killed replica's already-emitted tokens
            self.ledger.add(request.tenant, generated_tokens=1)
        if request.t_first == 0.0:
            request.t_first = self.timeline.stamp(
                "first", request.request_id, request.slot)
            if not request.ttft_observed:
                request.ttft_observed = True
                ttft_s = max(0.0, request.t_first - request.t_submit)
                if self.signals is not None:
                    self.signals.publish("llm.ttft_ms", ttft_s * 1e3,
                                         self.config.replica_id)
                if self.metrics is not None:
                    tenant = self._tenant_label(request)
                    self.metrics.llm_ttft.labels(
                        model=self.config.model,
                        replica=self.config.replica_id,
                        tenant=tenant).observe(
                        ttft_s, exemplar=self._exemplar(
                            "llm_ttft", ttft_s, request,
                            (self.config.model, self.config.replica_id,
                             tenant)))
                self._span("llm.prefill", request,
                           request.created + request.queue_ms / 1e3,
                           request.created + ttft_s,
                           **{"gen_ai.usage.prompt_tokens":
                                  len(request.prompt_ids),
                              "llm.prefill_ms": round(request.prefill_ms, 2),
                              "llm.bucket": request.bucket,
                              # what its (last) prefill dispatch was padded
                              # to: under the bucket, the half program
                              "llm.prefill_len": request.prefill_len,
                              "llm.cached_prefix_tokens": request.hist,
                              "llm.chunked": request.chunked,
                              "llm.kv_pages": self.allocator.slot_pages(
                                  request.slot),
                              **self._window_attrs(len(request.prompt_ids))})
        done = (token == self.tokenizer.eos_id or token in request.stop_ids
                or len(request.generated) >= request.max_tokens)
        if done and request.finish_reason is None:
            request.finish_reason = ("stop" if (token == self.tokenizer.eos_id
                                                or token in request.stop_ids)
                                     else "length")
        if done:
            self._observe_finish(request)  # before free_slot: pages still held
            self._running.pop(request.slot, None)
            self.allocator.free_slot(request.slot)
            # no table sync here: free_slot marked the row dirty, and every
            # device dispatch path syncs before submitting
        self._post_tokens(request, [token], done=done)

    def _finish(self, request: GenRequest) -> None:
        self._observe_finish(request)
        self._running.pop(request.slot, None)
        self.allocator.free_slot(request.slot)
        self._post_tokens(request, [], done=True)

    def _post_tokens(self, request: GenRequest, tokens: list[int],
                     done: bool) -> None:
        """Queue tokens for the consumer. Posts accumulate in a step-local
        buffer (merged per request) and hop to the asyncio loop in ONE
        call_soon_threadsafe per flush — one loop wakeup per engine step,
        not one per token (the old per-token wakeups were measurable
        scheduler pressure at superstep/spec widths > 1)."""
        buf = self._emit_buf
        if tokens and request.t_emit == 0.0:
            self._emit_first = True
        if buf and buf[-1][0] is request and not buf[-1][2]:
            buf[-1][1].extend(tokens)
            buf[-1][2] = done
        else:
            buf.append([request, list(tokens), done])

    def _flush_emits(self, first_only: bool = False) -> None:
        """Deliver what _post_tokens buffered in one loop hop, entries that
        hold a request's FIRST token (nothing of the request has been
        flushed yet) ahead of the rest: a stream's order is a property of
        its own request, a first token has nothing of its request ahead of
        it, and its handler then does not queue on the loop behind the live
        streams' wake-ups. The split is stable, so every request's tokens
        keep their order and its ``done`` stays behind them.

        Called once per dispatch-loop iteration and at the end of every
        termination path (fail/crash/stop), so no consumer can strand on an
        unflushed buffer. ``first_only`` is the early flush where a first
        token is made (after a prefill, after a chunk round): those entries
        alone leave, before the iteration's decode or verify dispatch is
        built; the rest keeps its place and leaves with the iteration's
        flush; with no first token in the buffer it does nothing."""
        if not self._emit_buf or (first_only and not self._emit_first):
            return
        batch, self._emit_buf = self._emit_buf, []
        first: list[list[Any]] = []
        if self._emit_first:
            self._emit_first = False
            rest: list[list[Any]] = []
            for entry in batch:
                fresh = entry[1] and entry[0].t_emit == 0.0
                (first if fresh else rest).append(entry)
            if first_only:
                batch, self._emit_buf = first, rest
                self.stats.first_flushes += 1
            else:
                batch = first + rest
        loop = self._loop
        stamp = self.timeline.stamp

        def _put() -> None:
            for request, tokens, done in batch:
                if tokens and request.t_deliver == 0.0:
                    request.t_deliver = stamp("deliver", request.request_id,
                                              request.slot)
                for token in tokens:
                    request.stream.put_nowait(token)
                if done:
                    request.stream.put_nowait(None)

        with self.timeline.span("loop.flush",
                                kind="first" if first_only else ""):
            # the first token's way out, split at the hop: t_first -> t_emit
            # is the dispatch thread between sampling it and this flush,
            # t_emit -> t_deliver the loop's latency for the callback
            for request, _tokens, _done in first:
                if request.t_emit == 0.0:   # once, of two entries too
                    request.t_emit = stamp("emit", request.request_id,
                                           request.slot)
            if loop is not None and not loop.is_closed():
                try:
                    loop.call_soon_threadsafe(_put)
                    return
                except RuntimeError:
                    pass  # loop shut down mid-flight; fall through
            _put()  # no loop (tests driving the thread directly)

    # ------------------------------------------------------------ embeddings

    def kv_pages_in_use(self) -> int:
        return self.allocator.pages_in_use

    def kv_bytes_in_use(self) -> int:
        """HBM bytes the in-use KV pages occupy under the active storage
        dtype (int8 pages cost half their bf16 twin plus a scale sliver)."""
        return self.allocator.pages_in_use * self._kv_page_bytes

    def tier_stats(self) -> dict[str, Any] | None:
        """Tiered-prefix-cache snapshot for the stats/pool/admin
        surfaces: per-tier hit split (consume-site, conserves against
        prefix_hit_tokens), spill/restore counts + restore p95, and the
        shared store's per-tier footprint. None when no tier client is
        wired (prefix_tiers off AND no pool index)."""
        if self._tier_client is None:
            return None
        out = self._tier_client.stats()
        out["enabled"] = self._tier_client.store is not None
        out["hits"] = dict(self.allocator.tier_hits)
        out["hit_tokens"] = dict(self.allocator.tier_hit_tokens)
        return out

    def kv_bytes_capacity(self) -> int:
        """HBM bytes of the whole KV pool by the elements its family DECLARES
        a token (fixed at construction; ``kv_page_bytes``)."""
        return self.num_kv_pages * self._kv_page_bytes

    def kv_bytes_resident(self) -> int:
        """HBM bytes the pool's arrays hold as STORED: more than the declared
        figure where a vector is padded to whole lanes (the latent family's
        576 -> 640, 11 %)."""
        return kv_resident_bytes(self.kv)

    def state_bytes_in_use(self) -> int:
        """HBM bytes of the per-sequence pools the live rows occupy."""
        return self.allocator.rows_in_use * self._state_row_bytes

    def window_pool_bytes(self) -> int:
        """HBM bytes of the window layers' rings, every row's (0 for a family
        without window layers)."""
        if not self._window_layers:
            return 0
        return self.allocator.state_rows * self._state_row_bytes

    def _window_attrs(self, context: int) -> dict[str, int]:
        """Span attributes of a request at ``context`` tokens in a model with
        window layers (none for any other): the layers of each kind and the
        keys a window layer sees of that context."""
        if not self._window_layers:
            return {}
        return {"llm.window_layers": self._window_layers,
                "llm.full_layers": self._full_layers,
                "llm.window_tokens": min(
                    context, self.model_config.sliding_window)}
