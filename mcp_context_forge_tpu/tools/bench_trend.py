"""Bench-history trend gate: fail the build when a capture regresses.

The repo checks in one bench JSON per round and family
(``BENCH_r03.json``, ``BENCH_LOCAL_r04.json``,
...). Nothing read them back — a tok/s or roofline regression only
surfaced when a human diffed the numbers. This CLI turns the history
into a gate (``make bench-check``, wired into the ``test`` chain and the
Containerfile builder stage):

- files group into series by filename prefix (the ``_r<N>`` round suffix
  orders them); driver wrappers that nest the capture under ``parsed``
  unwrap transparently;
- per series, the NEWEST entry is compared against the MEDIAN of earlier
  entries for each gated metric — throughput (``value``, higher is
  better), ``hbm_roofline_frac`` (higher), and p95 latency
  (``token_latency_p95_ms`` / ``p95_ms``, lower). Median, not best:
  rounds run on different hosts, and one fast outlier round must not
  turn every later capture into a "regression";
- a gated metric breaching the tolerance band (default 25%, sized to the
  round-to-round hardware variance visible in the checked-in history)
  fails the run with exit code 1.

Hardware-variance caveat: rounds run on different hosts/chips, so the
gate catches step-function regressions (an accidental serial decode
path, a dead prefix cache), not single-digit-percent drift — the
tolerance is a tripwire, not a benchmark.

Pure stdlib on purpose: the Containerfile builder stage runs it before
any pip install (same constraint as the lint tool).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import Any

_ROUND_RE = re.compile(r"^(?P<prefix>.+?)_r(?P<round>\d+)\.json$")

# metric -> (json key, higher_is_better) per bench schema, keyed by the
# payload's self-describing "metric" field
_GATES: dict[str, list[tuple[str, bool]]] = {
    "tpu_local_decode_tokens_per_s": [
        ("value", True),
        ("hbm_roofline_frac", True),
        ("token_latency_p95_ms", False),
    ],
    "gateway_mcp_tools_call_rps": [
        ("value", True),
        ("p95_ms", False),
    ],
    # scenario load harness (bench_gateway_scenarios.py): one series per
    # scenario arm by filename prefix (BENCH_SCENARIO_BURST_..., _RAMP_,
    # _MIXED_, _CHAOS_), gated on scenario throughput and tail latency
    "gateway_scenario_slo": [
        ("value", True),
        ("p95_ms", False),
    ],
}


def discover_series(root: str) -> dict[str, list[tuple[int, str]]]:
    """{prefix: [(round, path), ...] sorted by round} for every
    ``*_r<N>.json`` bench capture under ``root`` (top level only)."""
    series: dict[str, list[tuple[int, str]]] = {}
    for path in glob.glob(os.path.join(root, "*_r*.json")):
        match = _ROUND_RE.match(os.path.basename(path))
        if not match:
            continue
        series.setdefault(match.group("prefix"), []).append(
            (int(match.group("round")), path))
    for entries in series.values():
        entries.sort()
    return series


def _load(path: str) -> dict[str, Any] | None:
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(payload, dict):
        return None
    # driver wrapper files ({"n", "cmd", "rc", "tail", "parsed"}) carry
    # the capture under "parsed"
    if "metric" not in payload and isinstance(payload.get("parsed"), dict):
        payload = payload["parsed"]
    return payload if isinstance(payload, dict) else None


def check_series(prefix: str, entries: list[tuple[int, str]],
                 tolerance: float) -> dict[str, Any]:
    """Compare the newest round's gated metrics against the median of
    earlier rounds, per super-step arm (captures carrying the same
    "superstep" K compare only with each other). Files whose "metric"
    field has no gate (MULTICHIP smoke payloads etc.) are skipped, as
    are single-capture series/arms."""
    payloads = [(rnd, path, _load(path)) for rnd, path in entries]
    payloads = [(rnd, path, p) for rnd, path, p in payloads
                if p is not None and p.get("metric") in _GATES]
    result: dict[str, Any] = {"series": prefix, "checks": [],
                              "regressions": []}
    if payloads and entries[-1][0] != payloads[-1][0]:
        # the NEWEST round of an otherwise-gated series didn't parse or
        # lost its gate metric: the one capture the gate exists to judge
        # is unjudgeable — that is a failure, not a silent fallback to
        # the second-newest (the vacuous-pass class again)
        result["regressions"].append(
            f"{prefix} r{entries[-1][0]:02d} "
            f"({os.path.basename(entries[-1][1])}) is unreadable or "
            f"missing its gate metric — the newest capture cannot be "
            f"checked")
        return result
    if len(payloads) < 2:
        result["skipped"] = ("no gated captures"
                             if not payloads else "single capture")
        return result
    result["latest"] = os.path.basename(payloads[-1][1])
    # partition by arm: captures self-describe their fused-K via the
    # "superstep" field (absent/1 = the classic one-token step), their
    # tiered-prefix-cache mode via "prefix_tiers", and their gateway
    # WORKER COUNT via "workers" (absent/1 = single asyncio worker) and
    # their closed-loop CONTROLLER mode via "controller" (absent =
    # frozen knobs) — a K=8 arm's tok/s must only be judged against K=8
    # history, a BENCH_PREFIX_TIERS capture's pressure workload only
    # against tier history, a 4-worker scenario round must never median
    # against 1-worker history (the scale-out win would read every later
    # single-worker capture as a regression, and vice versa), and a
    # controller-on capture's adaptive-K numbers must not gate a
    # frozen-config round, and a disaggregated capture (a non-empty
    # "roles" pool split, e.g. prefill+decode) must only be judged
    # against same-split history (migration hops shift the TTFT/tok_s
    # balance by design), and a REAL-PROCESS capture ("in_process":
    # false — N forked workers under `mcpforge supervise`, real sockets,
    # real GIL isolation) must never median into in-process history
    # (absent = true: all pre-real-process captures ran in-process),
    # and a cross-host fabric capture ("fabric": true — serving over an
    # object store another host populated, docs/cache_fabric.md) must
    # only be judged against fabric history (T3 restores replace
    # prefills, shifting tok/s and hit mix by design)
    groups: dict[tuple[int, bool, int, bool, tuple[str, ...], bool, bool],
                 list[tuple[int, str, dict[str, Any]]]] = {}
    for item in payloads:
        in_process = item[2].get("in_process")
        groups.setdefault((int(item[2].get("superstep") or 1),
                           bool(item[2].get("prefix_tiers")),
                           int(item[2].get("workers") or 1),
                           bool(item[2].get("controller")),
                           tuple(str(r) for r in
                                 (item[2].get("roles") or ())),
                           True if in_process is None else bool(in_process),
                           bool(item[2].get("fabric"))),
                          []).append(item)
    for (k_steps, tiers, workers, controller, roles, in_process,
         fabric), group in sorted(groups.items()):
        if len(group) < 2:
            # a new arm's first capture has no history yet — surface it
            # (a silent zero-check pass would hide the round where the
            # fused path's numbers first land, the vacuous-pass class)
            result.setdefault("new_arms", []).append(
                {"superstep": k_steps, "prefix_tiers": tiers,
                 "workers": workers, "controller": controller,
                 "roles": list(roles), "in_process": in_process,
                 "fabric": fabric,
                 "capture": os.path.basename(group[-1][1])})
            continue
        latest_round, latest_path, latest = group[-1]
        history = group[:-1]
        arm = "" if k_steps == 1 else f"@superstep={k_steps}"
        if tiers:
            arm += "@tiers"
        if workers != 1:
            arm += f"@workers={workers}"
        if controller:
            arm += "@controller"
        if roles:
            arm += f"@roles={','.join(roles)}"
        if not in_process:
            arm += "@real-process"
        if fabric:
            arm += "@fabric"
        for key, higher_better in _GATES[latest.get("metric")]:
            latest_val = latest.get(key)
            prior = [p.get(key) for _rnd, _path, p in history
                     if isinstance(p.get(key), (int, float))]
            if not isinstance(latest_val, (int, float)) or not prior:
                continue  # metric absent in the newest or every prior capture
            baseline = statistics.median(prior)
            if higher_better:
                bound = baseline * (1.0 - tolerance)
                regressed = latest_val < bound
            else:
                bound = baseline * (1.0 + tolerance)
                regressed = latest_val > bound
            check = {
                "metric": key,
                "superstep": k_steps,
                "workers": workers,
                "controller": controller,
                "roles": list(roles),
                "in_process": in_process,
                "fabric": fabric,
                "latest": latest_val,
                "latest_round": latest_round,
                "baseline_median": baseline,
                "prior_rounds": len(prior),
                "bound": round(bound, 4),
                "higher_is_better": higher_better,
                "regressed": regressed,
            }
            result["checks"].append(check)
            if regressed:
                result["regressions"].append(
                    f"{prefix}{arm} r{latest_round:02d} {key}={latest_val} "
                    f"breaches {'>' if not higher_better else '<'} "
                    f"{bound:.4g} (median of {len(prior)} prior round(s) = "
                    f"{baseline}, tolerance {tolerance:.0%})")
    return result


def run_check(root: str, tolerance: float = 0.25) -> dict[str, Any]:
    """The whole gate as a pure function (the smoke test's entry point).
    ``ok`` is False iff any series regressed; ``checks`` counts the
    comparisons actually performed — zero means the gate found nothing
    to look at (wrong root, history not shipped) and callers must treat
    that as its own failure, not a pass."""
    series = discover_series(root)
    results = [check_series(prefix, entries, tolerance)
               for prefix, entries in sorted(series.items())]
    regressions = [line for r in results for line in r["regressions"]]
    return {
        "root": os.path.abspath(root),
        "tolerance": tolerance,
        "series": results,
        "checks": sum(len(r["checks"]) for r in results),
        "regressions": regressions,
        "ok": not regressions,
    }


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="Fail on tolerance-breaking regressions across the "
                    "checked-in BENCH_*.json history (make bench-check).")
    parser.add_argument("--root", default=None,
                        help="directory holding the BENCH history "
                             "(default: $BENCH_TREND_ROOT or the repo "
                             "root containing this package)")
    parser.add_argument("--tolerance", type=float, default=None,
                        help="fractional regression band (default: "
                             "$BENCH_TREND_TOLERANCE or 0.25)")
    parser.add_argument("--json", action="store_true",
                        help="emit the full report as JSON")
    args = parser.parse_args(argv)
    root = args.root or os.environ.get("BENCH_TREND_ROOT") or os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    tolerance = args.tolerance
    if tolerance is None:
        tolerance = float(os.environ.get("BENCH_TREND_TOLERANCE", "0.25"))
    report = run_check(root, tolerance)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        for result in report["series"]:
            if result.get("skipped"):
                print(f"bench-trend: {result['series']}: skipped "
                      f"({result['skipped']})")
                continue
            for arm in result.get("new_arms", ()):
                tiers = "@tiers" if arm.get("prefix_tiers") else ""
                wk = (f"@workers={arm['workers']}"
                      if arm.get("workers", 1) != 1 else "")
                ctl = "@controller" if arm.get("controller") else ""
                rl = (f"@roles={','.join(arm['roles'])}"
                      if arm.get("roles") else "")
                rp = ("@real-process"
                      if arm.get("in_process") is False else "")
                fb = "@fabric" if arm.get("fabric") else ""
                print(f"bench-trend: {result['series']}"
                      f"@superstep={arm['superstep']}{tiers}{wk}{ctl}{rl}"
                      f"{rp}{fb}: first capture ({arm['capture']}) — no "
                      f"history to gate yet")
            for check in result["checks"]:
                arrow = "REGRESSED" if check["regressed"] else "ok"
                print(f"bench-trend: {result['series']} {check['metric']}: "
                      f"{check['latest']} vs prior median "
                      f"{check['baseline_median']} (bound {check['bound']}) "
                      f"[{arrow}]")
        for line in report["regressions"]:
            print(f"bench-trend: FAIL {line}", file=sys.stderr)
        if report["checks"] > 0:
            print(f"bench-trend: {'PASS' if report['ok'] else 'FAIL'} "
                  f"({report['checks']} check(s), "
                  f"{len(report['regressions'])} regression(s), tolerance "
                  f"{tolerance:.0%})")
    if report["checks"] == 0:
        # a gate that compared nothing is not a pass: wrong --root, a
        # BENCH_TREND_ROOT typo, or the history was never shipped next
        # to the package — exit distinctly from a regression (1)
        print(f"bench-trend: FAIL no gated bench captures found under "
              f"{report['root']} (nothing was checked)", file=sys.stderr)
        return 2
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
