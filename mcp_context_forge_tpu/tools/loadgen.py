"""Scenario-shaping gateway load generator (SLO-asserting harness core).

Named traffic scenarios — burst, diurnal ramp, mixed workloads,
chaos — driven against an in-process gateway client, with SLO verdicts
pulled from ``GET /admin/slo`` per-consumer delta windows instead of
re-deriving percentiles client-side. ROADMAP item 5 names exactly this:
a load harness that asserts SLOs (TTFT/TPOT p99, error budget), not just
throughput; xLLM's serving-tier report (arXiv:2510.14686) and the LLM
microserving model (arXiv:2412.12488) both treat SLO-gated scenarios as
the precondition for serving-tier scale-out.

The client contract is duck-typed: anything with aiohttp-style
``post(path, json=..., auth=...)`` / ``get(path, ...)`` — an
``aiohttp.test_utils.TestClient`` in tier-1 smoke, the real-socket
``_SocketClient`` of ``bench_gateway_scenarios.py``. Pure asyncio; never
imports jax (the harness builds the gateway, not this module).

Usage shape (see ``bench_gateway_scenarios.py``)::

    window = SloWindow(client, "scenario-burst", auth)
    await window.open()                # resets this consumer's delta
    result = await run_phases(client, auth, kinds, phases)
    result["slo"] = await window.close()   # verdicts over the window
"""

from __future__ import annotations

import asyncio
import statistics
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Sequence

# one request of a given kind: (client, auth, i) -> (ok, error_tag)
RequestFn = Callable[[Any, Any, int], Awaitable[tuple[bool, str]]]


# ---------------------------------------------------------------- tenant mix

def weighted_schedule(items: Sequence[tuple[Any, int]]
                      ) -> Callable[[int], Any]:
    """Deterministic skewed interleave over ``(value, weight)`` pairs:
    returns ``pick(i)`` mapping request index -> value with exact
    weight proportions over each period of ``sum(weights)`` requests.

    Smooth weighted round-robin (the nginx algorithm) precomputed into a
    period schedule, so a tenant-mix scenario gets the same a,a,b,a,c...
    interleave on every run — reproducible per-tenant SLO windows — and
    heavy tenants spread through the period instead of batching up
    front. Weights are integers (give 5:2:1, not 0.5:0.2:0.1)."""
    pairs = [(value, int(weight)) for value, weight in items if weight > 0]
    if not pairs:
        raise ValueError("weighted_schedule needs at least one "
                         "positive-weight item")
    total = sum(weight for _, weight in pairs)
    current = [0] * len(pairs)
    schedule = []
    for _ in range(total):
        for j, (_, weight) in enumerate(pairs):
            current[j] += weight
        best = max(range(len(pairs)), key=lambda j: current[j])
        current[best] -= total
        schedule.append(pairs[best][0])
    return lambda i: schedule[i % total]


# --------------------------------------------------------------- request kinds

def chat_kind(model: str, max_tokens: int = 8,
              prompt: str = "scenario request") -> RequestFn:
    """OpenAI-compatible chat completion against the in-tree engine."""
    async def one(client, auth, i: int) -> tuple[bool, str]:
        resp = await client.post("/v1/chat/completions", auth=auth, json={
            "model": model,
            "messages": [{"role": "user", "content": f"{prompt} {i}"}],
            "max_tokens": max_tokens})
        body = await resp.json()
        ok = resp.status == 200 and bool(body.get("choices"))
        return ok, "" if ok else f"http_{resp.status}"
    return one


def shed_tracking_chat_kind(model: str, shed_log: dict,
                            max_tokens: int = 8,
                            prompt: str = "scenario request") -> RequestFn:
    """Chat kind for overload-shed scenarios: a 429 carrying Retry-After
    is the EXPECTED shed outcome — counted into ``shed_log['shed']``,
    not as a failure — while a 429 MISSING the header is a failure (the
    backpressure-header contract breach the scenario exists to catch).
    Every other status keeps :func:`chat_kind` semantics."""
    async def one(client, auth, i: int) -> tuple[bool, str]:
        resp = await client.post("/v1/chat/completions", auth=auth, json={
            "model": model,
            "messages": [{"role": "user", "content": f"{prompt} {i}"}],
            "max_tokens": max_tokens})
        if resp.status == 429:
            await resp.read()
            if "Retry-After" not in resp.headers:
                return False, "429_without_retry_after"
            shed_log["shed"] = shed_log.get("shed", 0) + 1
            return True, ""
        body = await resp.json()
        ok = resp.status == 200 and bool(body.get("choices"))
        return ok, "" if ok else f"http_{resp.status}"
    return one


def tools_call_kind(tool: str, text: str = "payload") -> RequestFn:
    """MCP tools/call over /mcp (streamable-http stateless)."""
    async def one(client, auth, i: int) -> tuple[bool, str]:
        resp = await client.post("/mcp", auth=auth, json={
            "jsonrpc": "2.0", "id": i, "method": "tools/call",
            "params": {"name": tool,
                       "arguments": {"n": i, "text": f"{text} {i}"}}})
        body = await resp.json()
        ok = (resp.status == 200 and "result" in body
              and not body["result"].get("isError"))
        return ok, "" if ok else f"http_{resp.status}"
    return one


def a2a_kind(agent: str) -> RequestFn:
    """A2A agent invocation (the gateway's agent-to-agent surface)."""
    async def one(client, auth, i: int) -> tuple[bool, str]:
        resp = await client.post(f"/a2a/{agent}/invoke", auth=auth,
                                 json={"q": f"scenario {i}"})
        ok = resp.status == 200
        await resp.read()
        return ok, "" if ok else f"http_{resp.status}"
    return one


# ------------------------------------------------------------------ execution

@dataclass
class PhaseResult:
    """One load phase's client-side numbers."""
    name: str
    concurrency: int
    requests: int = 0
    failures: int = 0
    wall_s: float = 0.0
    latencies_ms: list[float] = field(default_factory=list)
    errors: Counter = field(default_factory=Counter)

    def summary(self) -> dict[str, Any]:
        lat = sorted(self.latencies_ms)
        out: dict[str, Any] = {
            "name": self.name,
            "concurrency": self.concurrency,
            "requests": self.requests,
            "failures": self.failures,
            "wall_s": round(self.wall_s, 3),
            "rps": round(self.requests / self.wall_s, 2)
            if self.wall_s > 0 else 0.0,
        }
        if lat:
            out["p50_ms"] = round(statistics.median(lat), 2)
            out["p95_ms"] = round(lat[min(int(len(lat) * 0.95),
                                          len(lat) - 1)], 2)
            out["p99_ms"] = round(lat[min(int(len(lat) * 0.99),
                                          len(lat) - 1)], 2)
        if self.errors:
            out["errors"] = dict(self.errors)
        return out


async def run_phase(client, auth, kinds: Sequence[RequestFn], *,
                    name: str, concurrency: int, requests: int) -> PhaseResult:
    """Closed-loop phase: ``concurrency`` workers drain ``requests``
    total, each request round-robining across ``kinds`` (deterministic
    mix — a mixed-traffic scenario interleaves chat/tools/A2A instead of
    batching by kind). ``auth`` may be a CALLABLE ``auth_for(i)`` — the
    per-tenant mix hook: pass ``weighted_schedule([(auth_a, 5), ...])``
    to drive N principals with skewed weights through one phase."""
    result = PhaseResult(name=name, concurrency=concurrency)
    # plain iterator, no lock: workers share one event loop and next()
    # has no await point, so draws cannot interleave
    counter = iter(range(requests))
    auth_for = auth if callable(auth) else (lambda _i: auth)

    async def worker() -> None:
        while True:
            i = next(counter, None)
            if i is None:
                return
            kind = kinds[i % len(kinds)]
            started = time.monotonic()
            try:
                ok, tag = await kind(client, auth_for(i), i)
            except Exception as exc:
                ok, tag = False, type(exc).__name__
            result.latencies_ms.append((time.monotonic() - started) * 1e3)
            result.requests += 1
            if not ok:
                result.failures += 1
                result.errors[tag or "error"] += 1

    wall_start = time.monotonic()
    await asyncio.gather(*[worker() for _ in range(max(1, concurrency))])
    result.wall_s = time.monotonic() - wall_start
    return result


async def run_phase_open(client, auth, kinds: Sequence[RequestFn], *,
                         name: str, rate_rps: float, requests: int,
                         max_in_flight: int = 10_000) -> PhaseResult:
    """OPEN-loop phase: arrivals follow a fixed paced schedule (request
    ``i`` is due at ``start + i/rate``) regardless of how slow the
    responses are, and each latency is measured from the request's
    SCHEDULED arrival — not from when a freed-up worker got around to
    sending it. Closed-loop drivers under-report latency at saturation
    (coordinated omission: a stalled server pauses the offered load
    exactly when it is slowest); this is the arm the 10k-concurrent
    burst scenario runs.

    ``max_in_flight`` bounds concurrent sockets (fd safety). When the
    bound is hit, the wait for a slot COUNTS toward the next request's
    latency — a saturated server inflates the tail, as it should.
    ``concurrency`` on the result records the PEAK in-flight depth
    actually reached."""
    rate = max(0.001, float(rate_rps))
    result = PhaseResult(name=name, concurrency=0)
    auth_for = auth if callable(auth) else (lambda _i: auth)
    semaphore = asyncio.Semaphore(max(1, max_in_flight))
    in_flight = 0
    peak = 0

    async def one(i: int, scheduled: float) -> None:
        nonlocal in_flight, peak
        async with semaphore:
            in_flight += 1
            peak = max(peak, in_flight)
            kind = kinds[i % len(kinds)]
            try:
                ok, tag = await kind(client, auth_for(i), i)
            except Exception as exc:
                ok, tag = False, type(exc).__name__
            finally:
                in_flight -= 1
        # latency from the SCHEDULED arrival: queueing the client did on
        # the server's behalf is the server's latency, not omitted time
        result.latencies_ms.append((time.monotonic() - scheduled) * 1e3)
        result.requests += 1
        if not ok:
            result.failures += 1
            result.errors[tag or "error"] += 1

    start = time.monotonic()
    tasks = []
    for i in range(requests):
        scheduled = start + i / rate
        delay = scheduled - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(i, scheduled)))
    await asyncio.gather(*tasks)
    result.wall_s = time.monotonic() - start
    result.concurrency = peak
    return result


async def run_phases(client, auth, kinds: Sequence[RequestFn],
                     phases: Sequence[tuple[str, int, int]]
                     ) -> dict[str, Any]:
    """Run ``(name, concurrency, requests)`` phases back to back (the
    ramp shape is just a phase list) and merge the numbers."""
    results = [await run_phase(client, auth, kinds, name=name,
                               concurrency=conc, requests=n)
               for name, conc, n in phases]
    merged = PhaseResult(name="total",
                         concurrency=max(r.concurrency for r in results))
    for r in results:
        merged.requests += r.requests
        merged.failures += r.failures
        merged.wall_s += r.wall_s
        merged.latencies_ms.extend(r.latencies_ms)
        merged.errors.update(r.errors)
    return {"phases": [r.summary() for r in results], **merged.summary()}


# ----------------------------------------------------------------- SLO window

class SloWindow:
    """One named ``/admin/slo`` delta window bracketing a scenario.

    The evaluator keys delta state per consumer (``?window=<name>``), so
    a scenario's phase-length window cannot be shredded by the admin
    UI's 5 s poll — ``open()`` advances this consumer's snapshot to
    "now", ``close()`` reads the verdicts accumulated since.

    ``tenant`` scopes the window to one tenant's SLO CLASS evaluated
    over that tenant's metric label slice (``?tenant=``); tenant windows
    isolate per (window, tenant), so a mix scenario opens one SloWindow
    per tenant and closes them independently."""

    def __init__(self, client, name: str, auth,
                 tenant: str | None = None,
                 scope: str | None = None) -> None:
        self.client = client
        self.name = name
        self.auth = auth
        self.tenant = tenant
        # scope="fleet": verdicts over the SUMMED cross-worker histogram
        # state (multi-worker arms — docs/scaleout.md); the engine's
        # TTFT samples live in the pool OWNER's registry, so a window
        # opened on any other worker needs the fleet view to see them
        self.scope = scope

    async def _evaluate(self) -> dict[str, Any]:
        url = f"/admin/slo?window={self.name}"
        if self.tenant:
            from urllib.parse import quote
            url += f"&tenant={quote(self.tenant)}"
        if self.scope:
            url += f"&scope={self.scope}"
        resp = await self.client.get(url, auth=self.auth)
        if resp.status != 200:
            raise RuntimeError(
                f"/admin/slo -> {resp.status}: {await resp.text()}")
        return await resp.json()

    async def open(self) -> None:
        await self._evaluate()  # snapshot reset: deltas start here

    async def close(self) -> dict[str, Any]:
        report = await self._evaluate()
        return {
            "ok": report["ok"],
            "window_s": report["window_s"],
            "error_budget": report["error_budget"],
            **({"tenant": report.get("tenant"),
                "slo_class": report.get("slo_class"),
                "tenant_clamped": report.get("tenant_clamped")}
               if self.tenant else {}),
            "objectives": {
                o["name"]: {
                    "ok": o["ok"],
                    "target_ms": o["target_ms"],
                    "window_p_ms": o["window_p_ms"],
                    "window_samples": o["window_samples"],
                    "fraction_over_target": o["fraction_over_target"],
                    "burn_rate": o["burn_rate"],
                } for o in report["objectives"]
            },
        }


async def probe_slowest_trace(client, auth,
                              since_ts: float | None = None
                              ) -> dict[str, Any]:
    """The no-vacuous rule for request forensics (the trace-side twin
    of :func:`assert_slo_measured`): after a scenario, its SLOWEST
    request — the one an operator would chase — must be retrievable at
    ``/admin/trace/{id}`` as a complete stitched waterfall. Returns
    ``{"trace_id", "duration_ms", "spans", "waterfall_complete",
    "problems": [...]}`` — empty problems = forensics held up.

    ``since_ts`` scopes the pick to rows recorded at/after that wall
    time: the flight recorder's rings span the whole gateway lifetime,
    and back-to-back scenarios against one gateway must each probe
    THEIR OWN slowest request, not keep re-validating whichever earlier
    scenario was globally slowest.

    Retention is GLOBAL while the window is per-scenario, so the
    scenario's slowest row can legitimately have been displaced from
    the slowest-per-route tables by an earlier scenario's slower
    requests (and its transient exemplar pin replaced). The probe
    therefore walks the window's rows slowest-first and validates the
    slowest RETAINED one — deterministic across shared-gateway runs —
    recording a displacement note; it hard-fails only when NO in-window
    trace is retained at all (forensics genuinely dark for the
    scenario).

    Checks on the picked trace: the waterfall has spans, the gateway
    phase vector (summing to the row's wall — the existing flight-
    recorder invariant, re-asserted over the stitched surface), and its
    containment invariants hold."""
    problems: list[str] = []
    out: dict[str, Any] = {"trace_id": None, "duration_ms": None,
                           "spans": 0, "waterfall_complete": False,
                           "displaced": 0, "problems": problems}
    resp = await client.get("/admin/gateway/requests?limit=256",
                            auth=auth)
    if resp.status != 200:
        problems.append(f"/admin/gateway/requests -> {resp.status}")
        return out
    snapshot = await resp.json()
    rows = list(snapshot.get("slowest") or []) \
        + list(snapshot.get("recent") or [])
    if since_ts is not None:
        rows = [r for r in rows if r.get("ts", 0.0) >= since_ts]
    if not rows:
        problems.append("flight recorder has no request rows"
                        + (" in the scenario window" if since_ts else ""))
        return out
    rows.sort(key=lambda r: r.get("duration_ms", 0.0), reverse=True)
    if not rows[0].get("trace_id"):
        problems.append("slowest request row carries no trace_id")
        return out
    waterfall = None
    for row in rows:
        trace_id = row.get("trace_id")
        if not trace_id:
            continue
        resp = await client.get(f"/admin/trace/{trace_id}", auth=auth)
        if resp.status == 200:
            out["trace_id"] = trace_id
            out["duration_ms"] = row.get("duration_ms")
            waterfall = await resp.json()
            break
        out["displaced"] += 1
    if waterfall is None:
        problems.append(
            f"none of the window's {len(rows)} request traces is "
            f"retained: /admin/trace has no forensics for this scenario")
        return out
    out["spans"] = waterfall.get("span_count", 0)
    out["waterfall_complete"] = bool(waterfall.get("complete"))
    if not waterfall.get("span_count"):
        problems.append(f"trace {trace_id} stitched to zero spans")
    gateway = waterfall.get("gateway")
    if gateway is None:
        problems.append(f"trace {trace_id} has no gateway flight-"
                        f"recorder join")
    else:
        drift = abs(gateway.get("phase_sum_ms", 0.0)
                    - gateway.get("duration_ms", 0.0))
        if drift > 2.0:
            problems.append(
                f"trace {trace_id} gateway phase sum diverges from "
                f"wall by {drift:.2f} ms")
    inv = waterfall.get("invariants") or {}
    if not inv.get("children_within_parent"):
        problems.append(f"trace {trace_id}: child spans escape their "
                        f"parent window")
    if not inv.get("child_cover_le_wall"):
        problems.append(f"trace {trace_id}: children cover more wall "
                        f"than their parent")
    return out


def assert_slo_measured(slo: dict[str, Any],
                        objectives: Sequence[str]) -> list[str]:
    """The no-vacuous-pass rule for scenario SLOs: each named objective
    must have WINDOW SAMPLES (the scenario actually exercised it) — a
    breach is a verdict, an empty window is a harness bug. Returns the
    list of problems (empty = measured)."""
    problems = []
    for name in objectives:
        obj = slo.get("objectives", {}).get(name)
        if obj is None:
            problems.append(f"objective {name} missing from /admin/slo")
        elif not obj["window_samples"]:
            problems.append(f"objective {name} saw zero window samples "
                            f"(scenario never exercised it)")
    return problems
