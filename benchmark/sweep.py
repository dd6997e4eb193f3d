#!/usr/bin/env python3
"""Find a chat cell's knee, once, when the cell is defined. NOT the check's
command: one process builds the engine once and runs short windows at
stepped rates.

    python3 benchmark/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 15 \
        [--seed N] [--engine max_batch=16]

A rate passes where at least 90 % of the requests that fell due saw a first
token within ``--ttft-ms`` and a per-request TPOT within ``--tpot-ms`` (a
failed request misses both), and the backlog (requests due and not done) at
the window's end is no larger than at its middle. The knee is the highest
passing rate. Both limits are the sweep's working definition only; they are
not metrics of the benchmark. One line of JSON per rate, then a summary.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run, traffic  # noqa: E402
from benchmark.harness import manifest, stats  # noqa: E402


def judge(records: list[stats.Record], window: tuple[float, float],
          ttft_ms: float, tpot_ms: float) -> dict:
    """One window's row of the sweep table."""
    start, end = window
    middle = (start + end) / 2.0

    def backlog(at: float) -> int:
        return sum(1 for r in records
                   if r.due <= at and not (r.ok and r.done <= at))

    met = sum(1 for r in records if r.ttft_ms <= ttft_ms
              and (r.tpot_ms is None or r.tpot_ms <= tpot_ms))
    row = {"attempted": len(records),
           "failed": sum(1 for r in records if not r.ok),
           "met_both_share": met / max(1, len(records)),
           "backlog_middle": backlog(middle), "backlog_end": backlog(end)}
    for name in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
                 "tokens_per_s"):
        row[name] = stats.end_to_end(name, records, window, 0.0)
    row["passes"] = bool(row["met_both_share"] >= 0.9
                         and row["backlog_end"] <= max(row["backlog_middle"], 1))
    return row


async def sweep(cell: manifest.Cell, args) -> None:
    config = manifest.read_json(cell.config_file)
    mix = manifest.read_json(cell.traffic_file)
    for pair in args.engine:
        key, value = pair.split("=", 1)
        mix["engine"][key] = json.loads(value)
    rows = []
    async with run.ready(cell, config, mix, args.seed, False) as session:
        for i, rate in enumerate(args.rates):
            plan = traffic.plan(mix, {"rate_rps": rate}, args.seconds,
                                args.seed + i, session.overhead)
            ran = await run.window(session, plan, args.seconds, False)
            row = {"rate_rps": rate,
                   **judge(ran["records"], ran["window"], args.ttft_ms, args.tpot_ms)}
            delta = {k: ran["snapshots"]["end"][k] - ran["snapshots"]["start"][k]
                     for k in ("decode_dispatches", "prefill_batches",
                               "prefill_requests", "completion_tokens")}
            row["tokens_per_decode_step"] = (
                delta["completion_tokens"] / max(1, delta["decode_dispatches"]))
            row["window_stats"] = delta
            rows.append(row)
            run.note("sweep_row", **row)
            await asyncio.sleep(1.0)
        compiles = session.engine.compile_tracker.serving_compiles()
    passing = [r["rate_rps"] for r in rows if r["passes"]]
    run.note("sweep", workload=cell.name, engine=mix["engine"],
             seconds=args.seconds, limits={"ttft_ms": args.ttft_ms,
                                           "tpot_ms": args.tpot_ms},
             knee_rps=max(passing) if passing else None,
             serving_compiles=compiles, setup_s=round(session.setup_s, 1))


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True,
                        type=lambda s: [float(x) for x in s.split(",")])
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--ttft-ms", type=float, default=1000.0)
    parser.add_argument("--tpot-ms", type=float, default=100.0)
    parser.add_argument("--engine", action="append", default=[],
                        help="override one engine size of the mix, e.g. max_batch=16")
    args = parser.parse_args(argv)
    run.cache_every_program()
    cell = manifest.cell(manifest.load(), args.workload)
    run.require_tpu(cell.chips)
    asyncio.run(sweep(cell, args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
