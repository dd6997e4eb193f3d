"""The benchmark's own client: streamed ``POST /v1/chat/completions`` over the
gateway's socket, timed on the client's side, open or closed loop."""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Callable

from .draw import Planned
from .stats import Record

clock = time.perf_counter


async def stream_chat(http, model: str, planned: Planned, record: Record,
                      temperature: float = 0.0) -> None:
    """Send one request and fill ``record``. Never raises for a failed
    request: it is counted, with the reason."""
    body = {"model": model, "stream": True, "temperature": temperature,
            "max_tokens": planned.max_tokens,
            "messages": [{"role": "user", "content": planned.content}]}
    record.sent = clock()
    try:
        async with http.post("/v1/chat/completions", json=body) as resp:
            if resp.status != 200:
                record.error = f"http {resp.status}: {(await resp.text())[:200]}"
                return
            ended = False
            async for line in resp.content:
                if not line.startswith(b"data: "):
                    continue
                now = clock()
                data = line[6:].strip()
                if data == b"[DONE]":
                    ended = True
                    break
                event = json.loads(data)
                if "error" in event:
                    record.error = f"stream error: {str(event['error'])[:200]}"
                    return
                choice = event["choices"][0]
                content = choice["delta"].get("content")
                if content:     # one character is one token (RenderEveryToken)
                    record.token_times.extend([now] * len(content))
                if choice.get("finish_reason"):
                    record.finish = choice["finish_reason"]
            record.done = clock()
            if not ended or record.finish is None:
                record.error = "stream ended without a finish_reason and [DONE]"
            elif not record.token_times:
                record.error = "no token came"
            else:
                record.ok = True
    except asyncio.CancelledError:
        record.error = record.error or "not finished within the drain time"
        raise
    except Exception as exc:       # a boundary: the request is counted as failed
        record.error = f"{type(exc).__name__}: {exc}"


async def run_traffic(http, model: str, plan: dict[str, Any], seconds: float,
                      drain_s: float, temperature: float = 0.0,
                      on_window: Callable[[str], None] | None = None,
                      ) -> tuple[list[Record], tuple[float, float]]:
    """Offer ``plan`` for ``seconds``, then wait at most ``drain_s`` for what
    is still in flight. Returns every request that fell due in the window and
    the window on the client's clock. ``on_window`` hears "start" and "end"."""
    records: list[Record] = []
    tasks: list[asyncio.Task] = []
    start = clock()
    end = start + seconds
    if on_window:
        on_window("start")

    def launch(planned: Planned, due: float) -> asyncio.Task:
        record = Record(planned.index, due, planned.prompt_tokens, planned.max_tokens)
        records.append(record)
        task = asyncio.ensure_future(
            stream_chat(http, model, planned, record, temperature))
        tasks.append(task)
        return task

    if plan["mode"] == "open":
        for planned in plan["requests"]:
            due = start + planned.due_s
            delay = due - clock()
            if delay > 0:
                await asyncio.sleep(delay)
            launch(planned, due)
        await asyncio.sleep(max(0.0, end - clock()))
    elif plan["mode"] == "closed":
        counter = iter(range(10 ** 9))

        async def one_client() -> None:
            while clock() < end:
                planned = plan["request"](next(counter))
                await launch(planned, clock())

        clients = [asyncio.ensure_future(one_client())
                   for _ in range(plan["clients"])]
        await asyncio.sleep(max(0.0, end - clock()))
        tasks.extend(clients)
    else:
        raise ValueError(f"unknown traffic mode {plan['mode']!r}")
    if on_window:
        on_window("end")
    pending = [t for t in tasks if not t.done()]
    if pending:
        _, late = await asyncio.wait(pending, timeout=drain_s)
        for task in late:
            task.cancel()
        await asyncio.gather(*late, return_exceptions=True)
    for task in tasks:
        if task.done() and not task.cancelled() and task.exception():
            raise task.exception()
    return records, (start, end)
