#!/usr/bin/env python3
"""Run one cell several times and print how widely each metric spreads: what
a bound is set from. Not the check's command. It never touches JAX itself (a
parent that has would hold the chip): every run is a child process.

    python3 benchmark/spread.py --workload <cell> [--sets 2] [--runs 6] \
        [--seconds <run_seconds>] [--trace-last 1] [--out chiprun_out/<cell>.jsonl]

Each set uses the same seeds. A spread is the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) over the median; for
each metric the wider of the sets' spreads is what a bound is five times of.
The first run of the call compiles where the cache is cold: its ``setup_s``
is reported apart.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [2_147_483_659, 2_500_000_001, 3_000_000_019, 2_222_222_227,
         2_718_281_829, 3_141_592_661, 2_333_333_341, 2_999_999_929]


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    started = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    lines = [line for line in proc.stdout.splitlines() if line.startswith("{")]
    row = {"seed": seed, "trace": trace, "rc": proc.returncode,
           "wall_s": round(time.monotonic() - started, 1)}
    if proc.returncode == 0 and lines:
        row["result"] = json.loads(lines[-1])
        row["notes"] = [json.loads(line) for line in lines[:-1]]
    else:
        row["stderr_tail"] = proc.stderr[-2000:]
    return row


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace-last", type=int, default=0,
                        help="1: one more run with --trace 1 at the end")
    parser.add_argument("--out", default=None)
    parser.add_argument("--budget-s", type=float, default=3300.0,
                        help="start no further run after this many seconds")
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as handle:
        seconds = args.seconds or json.load(handle)["run_seconds"]
    out = args.out or os.path.join(ROOT, "chiprun_out", args.workload + ".jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    sets: list[list[dict]] = []
    began = time.monotonic()
    with open(out, "a", encoding="utf-8") as log:
        for s in range(args.sets):
            sets.append([])
            for seed in SEEDS[:args.runs]:
                if time.monotonic() - began > args.budget_s:
                    break
                row = one_run(args.workload, seed, seconds, 0)
                row["set"] = s
                sets[-1].append(row)
                log.write(json.dumps(row) + "\n")
                log.flush()
                result = row.get("result", {})
                print(json.dumps({"set": s, "seed": seed, "rc": row["rc"],
                                  "wall_s": row["wall_s"],
                                  "correct": result.get("correct"),
                                  "attempted": result.get("attempted"),
                                  "failed": result.get("failed"),
                                  **{k: v["value"] for k, v in
                                     result.get("metrics", {}).items()}}), flush=True)
        if args.trace_last:
            row = one_run(args.workload, SEEDS[0], seconds, 1)
            log.write(json.dumps(row) + "\n")
            print(json.dumps({"traced": row.get("result"), "rc": row["rc"],
                              "notes": [n for n in row.get("notes", [])
                                        if n.get("note") in ("per_layer_notes",
                                                             "trace_programs")],
                              "stderr": row.get("stderr_tail")}), flush=True)
    names = sorted({k for rows in sets for r in rows
                    for k in r.get("result", {}).get("metrics", {})})
    first = sets[0][0] if sets and sets[0] else None
    for name in names:
        per_set = []
        for rows in sets:
            good = [r for r in rows if "result" in r and name in r["result"]["metrics"]]
            if name == "setup_s" and good and good[0] is first:
                good = good[1:]          # the compiling run is recorded apart
            values = [r["result"]["metrics"][name]["value"] for r in good]
            if len(values) >= 2:
                per_set.append({"n": len(values), "median": statistics.median(values),
                                "spread": spread(values), "min": min(values),
                                "max": max(values)})
        if per_set:
            widest = max(p["spread"] for p in per_set)
            print(json.dumps({"metric": name, "sets": per_set,
                              "widest_spread": widest, "bound_at_5x": 5 * widest}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
