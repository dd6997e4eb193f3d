"""Multi-process HTTP load-generator worker (north-star 1k-concurrency).

The driver target is 1,000 *concurrent* MCP tool-calls (BASELINE.json).
One asyncio loop juggling the server AND 1000 client tasks measures its
own scheduling delay, not the gateway — so ``bench.py`` spawns N worker
*processes* of this module, each holding ``concurrency`` real TCP
connections, and merges their reports. The reference drives the same
scale with Locust worker processes (`/root/reference/docs/release/
benchmark.md:21`, `tests/load/locustfile.py`).

Protocol: argv[1] is a JSON spec; the worker prints ONE JSON line:
``{"latencies_ms": [...], "failures": int, "wall_s": float,
"first_ts": float, "last_ts": float, "errors": {reason: count}}``.

Spec fields:
    base          http://host:port
    mode          "tools_call" | "chat"
    tool          tool name (tools_call mode)
    model         model name (chat mode)
    max_tokens    completion budget (chat mode)
    total         requests this worker issues
    concurrency   in-flight cap this worker holds
    worker        worker index (payload uniqueness)
    user/password basic auth
    ramp_s        sleep before first request (stagger process starts)

Workers are pure clients — they never import jax (launchers set
``JAX_PLATFORMS=cpu`` anyway: one process per chip, and it is not this one).
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from collections import Counter


async def run_worker(spec: dict) -> dict:
    import aiohttp

    base = spec["base"]
    mode = spec.get("mode", "tools_call")
    total = int(spec["total"])
    concurrency = int(spec["concurrency"])
    widx = int(spec.get("worker", 0))
    auth = aiohttp.BasicAuth(spec.get("user", "admin"),
                             spec.get("password", "changeme"))
    timeout = aiohttp.ClientTimeout(total=float(spec.get("timeout_s", 300)))

    latencies: list[float] = []
    errors: Counter = Counter()
    semaphore = asyncio.Semaphore(concurrency)
    first_ts = last_ts = 0.0

    async def one(session: aiohttp.ClientSession, i: int) -> None:
        nonlocal first_ts, last_ts
        if mode == "chat":
            path, payload = "/v1/chat/completions", {
                "model": spec.get("model", ""),
                "messages": [{"role": "user",
                              "content": f"w{widx} request {i}: say hi"}],
                "max_tokens": int(spec.get("max_tokens", 16))}
        else:
            path, payload = "/mcp", {
                "jsonrpc": "2.0", "id": f"w{widx}-{i}",
                "method": "tools/call",
                "params": {"name": spec["tool"],
                           "arguments": {"n": i,
                                         "text": f"payload w{widx} {i}"}}}
        async with semaphore:
            started = time.monotonic()
            if not first_ts:
                first_ts = time.time()
            try:
                async with session.post(base + path, json=payload,
                                        auth=auth) as resp:
                    body = await resp.json()
                if mode == "chat":
                    ok = resp.status == 200 and bool(body.get("choices"))
                else:
                    ok = (resp.status == 200 and "result" in body
                          and not body["result"].get("isError"))
                if not ok:
                    errors[f"http_{resp.status}"] += 1
            except Exception as exc:
                errors[type(exc).__name__] += 1
            latencies.append((time.monotonic() - started) * 1000)
            last_ts = time.time()

    await asyncio.sleep(float(spec.get("ramp_s", 0)))
    connector = aiohttp.TCPConnector(limit=concurrency)
    wall_start = time.monotonic()
    async with aiohttp.ClientSession(connector=connector,
                                     timeout=timeout) as session:
        await asyncio.gather(*[one(session, i) for i in range(total)])
    wall = time.monotonic() - wall_start
    return {"latencies_ms": [round(x, 3) for x in latencies],
            "failures": sum(errors.values()), "wall_s": round(wall, 3),
            "first_ts": first_ts, "last_ts": last_ts,
            "errors": dict(errors)}


def main() -> None:
    spec = json.loads(sys.argv[1])
    print(json.dumps(asyncio.run(run_worker(spec))))


if __name__ == "__main__":
    main()
