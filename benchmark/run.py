#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process finds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process (a chip belongs to one process): it builds the gateway as
``cli serve`` does, binds a real socket on 127.0.0.1, warms every shape the
cell's traffic uses, checks the engine against the plain reference, and only
then opens the measured window, in which its own asyncio client offers the
cell's traffic over HTTP (streamed ``POST /v1/chat/completions``) and times
it on the client's side. Set-up is everything before the window.

It fails at once — non-zero exit, no result line — unless JAX reports a TPU
with exactly the chips the cell asks for. There is no CPU fallback; the
tests' rehearsal calls :func:`measure` directly with a tiny configuration.

Earlier lines of stdout are JSON facts (``{"note": ...}``); the LAST line is
the result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, with ``--trace 1``, ``breakdown``. The last lines of stderr are each
number ``correct`` compared, beside its limit (``correct.compared``).
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families, traffic  # noqa: E402
from benchmark.harness import correct, manifest, stats  # noqa: E402
from benchmark.harness.draw import Planned, nonce_index, text  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")      # git-ignored; traces live here
WARM_INDEX = 900_000                            # nonces of warm-up requests


class Refused(SystemExit):
    """The run cannot measure what it was asked to: exit non-zero, no result."""

    def __init__(self, message: str) -> None:
        print(f"benchmark/run.py: {message}", file=sys.stderr, flush=True)
        super().__init__(2)


def note(what: str, **facts: Any) -> None:
    print(json.dumps({"note": what, **facts}, default=str), flush=True)


def require_tpu(chips: int) -> dict[str, Any]:
    """The device as JAX reports it; anything but ``chips`` TPU chips refuses."""
    try:
        from benchmark.harness.gateway import device_facts
        device = device_facts()
    except ImportError as exc:
        raise Refused(f"the system under test is not in this checkout: {exc}")
    except RuntimeError as exc:          # JAX found no backend it may use
        raise Refused(f"JAX found no accelerator: {exc}")
    if device["platform"] != "tpu":
        raise Refused(f"no TPU: JAX found {device}. A cell is measured on the "
                      "chip or not at all")
    if device["count"] != chips:
        raise Refused(f"the cell needs {chips} chip(s), JAX reports "
                      f"{device['count']}")
    return device


def cache_every_program() -> None:
    """Keep also the small programs in JAX's persistent cache: set-up after
    the first run should compile nothing. (Where the cache lives is the
    program's rule, ``engine.apply_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``.)"""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


def _finite(value: float | None) -> bool:
    return value is not None and math.isfinite(value)


async def _warm_http(http, model: str, mix: dict, overhead: int,
                     seed: int) -> int:
    """Drive the served path once per shape family before the window: one
    request alone, then as many at once as a prefill batch holds plus one, at
    the mix's shortest and longest prompt. Their programs are the warm-up
    grid's; this warms the HTTP path, the allocator and the host caches."""
    from benchmark.harness.client import stream_chat

    low, high = int(mix["prompt_tokens"]["low"]), int(mix["prompt_tokens"]["high"])
    width = int(mix["engine"].get("prefill_max_batch", 1)) + 1
    lengths = [low] + [high if i % 2 else low for i in range(width)]
    records = []

    async def one(i: int, length: int) -> None:
        planned = Planned(WARM_INDEX + i, None, length, 4,
                          text(seed, WARM_INDEX + i, length, overhead))
        record = stats.Record(planned.index, time.perf_counter(), length, 4)
        records.append(record)
        await stream_chat(http, model, planned, record)

    await one(0, lengths[0])
    await asyncio.gather(*[one(i, n) for i, n in enumerate(lengths[1:], 1)])
    bad = [r.error for r in records if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]}")
    return len(records)


async def _trace_part(seconds: float, span_s: float, out_dir: str,
                      marks: dict[str, float]) -> None:
    """Trace ``span_s`` seconds from the middle of the window."""
    import jax

    await asyncio.sleep(max(0.0, (seconds - span_s) / 2.0))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=options)
    from benchmark.harness.trace_reduce import WINDOW_END, WINDOW_START
    with jax.profiler.TraceAnnotation(WINDOW_START):
        marks["start"] = time.perf_counter()
    try:
        await asyncio.sleep(span_s)
    finally:
        with jax.profiler.TraceAnnotation(WINDOW_END):
            marks["end"] = time.perf_counter()
        await asyncio.to_thread(jax.profiler.stop_trace)


@dataclasses.dataclass
class Session:
    """A warmed, checked system under test with a client connected to it."""
    cell: manifest.Cell
    mix: dict[str, Any]
    model: Any
    engine: Any
    http: Any
    overhead: int                    # tokens the chat template adds to a prompt
    checks: dict[str, dict]          # logits / greedy results
    setup_s: float
    submits: dict[int, tuple[float, Any]]


@contextlib.asynccontextmanager
async def ready(cell: manifest.Cell, config: dict[str, Any], mix: dict[str, Any],
                seed: int, trace: bool):
    """Everything before the window: build, warm, check. Yields a Session."""
    import aiohttp

    from benchmark.harness import gateway

    meter = gateway.CompileMeter()
    model = gateway.register_model(cell.config, config)
    env = gateway.engine_env(cell.config, config["engine"], mix["engine"])
    async with gateway.serving(env) as served:
        engine = served["engine"]
        tracker = engine.compile_tracker.snapshot()
        note("build", wall_s=round(served["build_s"], 1), **meter.facts(),
             model=cell.config, layers=getattr(model, "n_layers", None),
             kv_pages=engine.num_kv_pages, max_batch=engine.config.max_batch,
             max_seq_len=engine.config.max_seq_len,
             prefill_buckets=list(engine.config.prefill_buckets),
             step_programs=(len(engine._decode_fns) + len(engine._decode_fb_fns)
                            + _prefill_programs(engine)),
             warmup_compiles=tracker["warmup"]["count"],
             attn_traced=dict(engine.attn_traced),
             cache_dir=engine.compile_cache_dir,
             memory_peak_bytes=gateway.memory_peak_bytes())

        logits = correct.logits_check(
            engine, int(config["check_seed"]), config["logits_tolerance"],
            correct.check_of(config, mix), config.get("family"))
        note("logits_vs_reference", **logits)
        greedy = await correct.greedy_repeats(engine, seed)
        note("greedy_repeats", **greedy)

        from mcp_context_forge_tpu.tpu_local.tokenizer import render_chat
        overhead = len(engine.tokenizer.encode(
            render_chat([{"role": "user", "content": ""}])))
        submits: dict[int, tuple[float, Any]] = {}
        if trace:       # the benchmark's own span: entry of engine.submit
            inner = engine.submit

            async def submit(request):
                index = nonce_index(bytes(
                    t for t in request.prompt_ids[:24] if t < 256))
                if index is not None:
                    submits[index] = (time.perf_counter(), request)
                return await inner(request)

            engine.submit = submit

        timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
        async with aiohttp.ClientSession(
                base_url=served["base_url"], headers=served["headers"],
                timeout=timeout, connector=aiohttp.TCPConnector(limit=0)) as http:
            warmed = await _warm_http(http, cell.config, mix, overhead, seed)
            setup_s = time.monotonic() - PROCESS_START
            note("ready", setup_s=round(setup_s, 2), warm_requests=warmed,
                 template_overhead_tokens=overhead, **meter.facts(),
                 serving_compiles=engine.compile_tracker.serving_compiles())
            yield Session(cell, mix, model, engine, http, overhead,
                          {"logits": logits, "greedy": greedy}, setup_s, submits)


async def window(session: Session, plan: dict[str, Any], seconds: float,
                 trace: bool) -> dict[str, Any]:
    """Offer ``plan`` for ``seconds`` and drain: the records, the window on the
    client's clock, EngineStats at its edges and after the drain, and with
    ``trace`` the reduced trace of a span in its middle."""
    from benchmark.harness.client import run_traffic

    snapshots: dict[str, dict] = {}
    marks: dict[str, float] = {}
    trace_dir = os.path.join(OUT_DIR, "trace")
    tracer = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        span_s = min(float(session.mix.get("trace_seconds", 3.0)), seconds)
        tracer = asyncio.ensure_future(_trace_part(seconds, span_s, trace_dir, marks))
    records, span = await run_traffic(
        session.http, session.cell.config, plan, seconds,
        float(session.mix["drain_seconds"]),
        float(session.mix.get("temperature", 0.0)),
        lambda edge: snapshots.__setitem__(
            edge, correct.stats_snapshot(session.engine)))
    reduced = None
    if tracer is not None:
        await tracer
        from benchmark.harness import trace_reduce
        reduced = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    snapshots["drained"] = correct.stats_snapshot(session.engine)
    return {"records": records, "window": span, "snapshots": snapshots,
            "trace": reduced,
            "trace_span": (marks["start"], marks["end"]) if marks else None}


async def measure(cell: manifest.Cell, config: dict[str, Any], mix: dict[str, Any],
                  cell_params: dict[str, Any], seed: int, seconds: float,
                  trace: bool) -> dict[str, Any]:
    """Set up, check, measure one cell; returns the result object. Does not
    look at the platform: :func:`main` has, and the tests' rehearsal runs
    this on the CPU at a tiny size (its result says ``"platform": "cpu"``)."""
    from benchmark.harness import gateway, kernel_cost, layers

    async with ready(cell, config, mix, seed, trace) as session:
        plan = traffic.plan(mix, cell_params, seconds, seed, session.overhead)
        ran = await window(session, plan, seconds, trace)
        records, snapshots = ran["records"], ran["snapshots"]
        serving_compiles = session.engine.compile_tracker.serving_compiles()
        books = correct.accounting(snapshots["start"], snapshots["drained"], records)
        note("accounting", **books)
        note("generator_lateness_ms", **stats.lateness_ms(records))
        device = gateway.device_facts()
        device["memory_peak_bytes"] = gateway.memory_peak_bytes()
        failed = [r for r in records if not r.ok]
        note("requests", attempted=len(records), failed=len(failed),
             finish={str(k): sum(1 for r in records if r.finish == k)
                     for k in {r.finish for r in records}},
             prompt_tokens=sum(r.prompt_tokens for r in records),
             completion_tokens=sum(r.tokens for r in records),
             first_errors=[r.error for r in failed[:3]],
             serving_compiles=serving_compiles,
             memory_peak_bytes=device["memory_peak_bytes"],
             window_stats={k: snapshots["drained"][k] - snapshots["start"][k]
                           for k in snapshots["drained"]})

        metrics: dict[str, dict[str, Any]] = {}
        checks = session.checks
        verdict = correct.compared(checks["logits"], checks["greedy"], books,
                                   serving_compiles)
        result: dict[str, Any] = {
            "correct": bool(checks["logits"]["ok"] and checks["greedy"]["ok"]
                            and books["ok"] and serving_compiles == 0),
            "attempted": len(records), "failed": len(failed),
            "metrics": metrics, "device": device}
        if not trace:
            for metric in cell.end_to_end:
                value = stats.end_to_end(metric["name"], records, ran["window"],
                                         session.setup_s)
                if _finite(value):
                    metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        else:
            reduced = ran["trace"]
            delta = {k: snapshots["end"][k] - snapshots["start"][k]
                     for k in snapshots["end"]}
            peak = (kernel_cost.peaks(device["kind"])
                    if device["platform"] == "tpu" else None)
            ctx = layers.LayerContext(
                records=records, window=ran["window"], stats=delta,
                model=session.model, peak=peak, trace=reduced,
                trace_span=ran["trace_span"], submits=session.submits)
            metrics.update(layers.read_all(cell.per_layer, ctx))
            note("per_layer_notes", **ctx.notes)
            note("trace_programs", **reduced.programs())
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s
            result["breakdown"] = {"device_ops": reduced.top_ops(10),
                                   "idle_gaps": reduced.idle_gaps(5)}
    # after the tear-down's own lines, so that these end standard error
    print("\n".join(verdict), file=sys.stderr, flush=True)
    return result


def _prefill_programs(engine) -> int:
    """Prefill step programs the warm-up grid holds: per admission batch
    width, the dense prefill and one history prefill per context bucket."""
    cap, widths = 1, 0
    while cap < max(1, engine.config.prefill_max_batch):
        cap *= 2
    width = 1
    while width <= cap:
        widths += 1
        width *= 2
    return widths * len(engine.config.prefill_buckets) * (
        1 + len(engine._prefill_hist_fns))


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cache_every_program()
    try:
        doc = manifest.load()
        cell = manifest.cell(doc, args.workload)
        config = manifest.read_json(cell.config_file)
        mix = manifest.read_json(cell.traffic_file)
        cell_params = manifest.read_json(cell.cell_file)
        families.of(config)                 # the family file and its three names
        correct.check_of(config, mix)       # check lengths the mix can hold
    except (OSError, ValueError) as exc:
        raise Refused(str(exc))
    device = require_tpu(cell.chips)
    note("device", **device, workload=cell.name, seed=args.seed,
         seconds=args.seconds, trace=args.trace)
    os.makedirs(OUT_DIR, exist_ok=True)
    result = asyncio.run(measure(cell, config, mix, cell_params, args.seed,
                                 args.seconds, bool(args.trace)))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
