"""CPU smoke of bench_gateway_scenarios.py: the SLO-asserting scenario
harness checks the gateway's behaviour (shed, DB outage, tier fault,
chaos stream integrity, workers) against a real-socket pool-of-2
gateway at tiny scale. Each group of scenarios runs ONCE a module (one
gateway build); every scenario's verdict is a case of its own. Mixed —
which builds a second peer gateway — stays in `make bench-scenarios`."""

import asyncio
import glob
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CLASSIC = ("burst", "ramp", "tenant", "chaos")
CHAOS_MATRIX = ("db-outage", "tier-fault", "overload-shed")


def _run_scenarios(only: str, **extra_env) -> dict:
    """One harness run under the smoke environment, restored after."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("BENCH_SCENARIO_SMOKE", "1")
        mp.setenv("BENCH_SCENARIO_MODEL", "llama3-test")
        mp.setenv("BENCH_SCENARIO_ONLY", only)
        mp.setenv("JAX_PLATFORMS", "cpu")
        for key, value in extra_env.items():
            mp.setenv(key, value)
        mp.syspath_prepend(REPO_ROOT)
        import bench_gateway_scenarios as bgs

        return asyncio.run(bgs.run_scenarios("cpu"))


@pytest.fixture(scope="module")
def classic_report():
    return _run_scenarios(",".join(CLASSIC))


@pytest.fixture(scope="module")
def chaos_matrix_report():
    return _run_scenarios(",".join(CHAOS_MATRIX))


def _check_burst(burst):
    assert [p["name"] for p in burst["phases"]] == ["baseline", "burst",
                                                    "cooldown"]


def _check_ramp(ramp):
    assert [p["concurrency"] for p in ramp["phases"]] == [2, 4, 2]


def _check_tenant(tenant):
    # the per-tenant mix ran with skewed weights, each tenant's SLO
    # CLASS window measured over its own label slice, the ledger
    # conserved tokens against the engine totals, the exported label set
    # respected the clamp, and the rollup wrote durable rows
    assert tenant["conservation"]["checked"] is True
    assert (tenant["conservation"]["ledger_prompt"]
            == tenant["conservation"]["engine_prompt"]) and (
        tenant["conservation"]["ledger_generated"]
        == tenant["conservation"]["engine_generated"])
    assert tenant["rollup_rows"] > 0
    # long-shared-prefix arm (docs/kv_tiering.md): the shared template's
    # pages served from the prefix cache (HBM or restored tier pages),
    # cached tokens dominate the arm's prefill, conservation includes
    # the cache_hit column over the tiered path
    prefix = tenant["prefix"]
    assert prefix["requests"] > 0 and prefix["failures"] == 0
    assert prefix["hit_tokens"] > 0
    assert prefix["hit_dominant"] is True, prefix
    assert (tenant["conservation"]["ledger_cache_hit"]
            == tenant["conservation"]["engine_cache_hit"])
    assert sum(prefix["tier_hit_tokens"].values()) > 0
    per_class = {t["slo"]["slo_class"]
                 for t in tenant["tenants"].values()}
    assert {"premium", "default", "batch"} == per_class
    # heavy tenant got ~5x the light tenant's traffic (5:2:1 schedule)
    heavy = tenant["per_tenant_requests"]["user:tenant-a@scenario.local"]
    light = tenant["per_tenant_requests"]["user:tenant-c@scenario.local"]
    assert heavy > light
    for t, block in tenant["tenants"].items():
        assert block["slo"]["objectives"]["ttft_p95"]["window_samples"] > 0, \
            (t, block)


def _check_chaos(chaos):
    # the kill interrupted real in-flight work, the merged failover
    # streams matched the uninterrupted reference token-for-token, and
    # the killed replica reloaded under residual load
    assert chaos["killed_replica"] is not None
    assert chaos["requeues"] >= 1
    assert chaos["token_parity"] is True
    assert chaos["lost_streams"] == 0
    assert chaos["replica_reloaded"] is True


_CLASSIC_CHECKS = {"burst": _check_burst, "ramp": _check_ramp,
                   "tenant": _check_tenant, "chaos": _check_chaos}


@pytest.mark.parametrize("name", CLASSIC)
def test_scenarios_cpu_smoke(classic_report, name):
    report = classic_report
    # problems are prefixed with their scenario's name: a scenario's
    # case fails on its own problems, not on a neighbour's
    assert not [p for p in report["problems"] if p.startswith(name + ":")]
    assert name in report["scenarios"], report["problems"]
    cap = report["scenarios"][name]
    assert cap["requests"] > 0
    assert cap["p95_ms"] > 0
    assert cap["failures"] == 0
    # SLO verdicts came from /admin/slo delta windows, MEASURED: every
    # asserted objective saw window samples (no vacuous pass)
    slo = cap["slo"]
    assert isinstance(slo["ok"], bool)
    for objective in ("http_p95", "ttft_p95", "tpot_p95"):
        assert slo["objectives"][objective]["window_samples"] > 0, \
            (name, objective, slo)
    _CLASSIC_CHECKS[name](cap)


def _check_db_outage(outage):
    assert outage["failures"] == 0            # serving never wavered
    assert outage["failed_flushes"] >= 1
    assert outage["windows_dropped"] >= 1     # loss REPORTED, bounded
    assert max(outage["pending_seen"]) <= 3   # the pending_max bound
    assert outage["breaker_mid"] == "open"
    transitions = outage["breaker_transitions"]
    assert "half_open" in transitions and transitions[-1] == "closed"
    assert outage["degradation_gauge_open_observed"] is True
    cons = outage["conservation"]
    assert cons["checked"] and \
        cons["ledger_prompt"] == cons["engine_prompt"] and \
        cons["ledger_generated"] == cons["engine_generated"]
    assert outage["recovery_rows_written"] >= 1


def _check_tier_fault(tier):
    assert tier["failures"] == 0
    assert tier["spilled"] >= 1
    assert tier["io_errors_mid"]["disk.write"] >= 1
    assert tier["quarantined_mid"] >= 1
    assert tier["breaker_mid"] == "open"
    assert tier["breaker_final"] == "closed"
    assert tier["disk_pages_post_recovery"] >= 1
    assert sum(tier["tier_hit_tokens"].values()) >= 1


def _check_overload_shed(shed):
    assert shed["shed_429s"] >= 1             # batch actually shed
    assert shed["failures"] == 0              # ... cleanly (header present)
    assert shed["premium_failures"] == []     # premium held
    assert shed["slo"]["slo_class"] == "premium" and shed["slo_ok"]
    assert "open" in shed["overload_transitions"]
    assert shed["overload_transitions"][-1] == "closed"


_MATRIX_CHECKS = {"db-outage": _check_db_outage,
                  "tier-fault": _check_tier_fault,
                  "overload-shed": _check_overload_shed}


@pytest.mark.parametrize("name", CHAOS_MATRIX)
def test_chaos_matrix_fault_scenarios_smoke(chaos_matrix_report, name):
    """ISSUE-14 chaos matrix at tiny scale: db-outage (bounded rollup
    buffer + ledger.rollup breaker ladder + conservation), tier-fault
    (disk quarantine + tier.disk breaker recovery, zero failures), and
    overload-shed (batch 429s with Retry-After while premium holds).
    Chaos's slow-replica arm rides the main smoke above."""
    report = chaos_matrix_report
    assert not [p for p in report["problems"] if p.startswith(name + ":")]
    assert name in report["scenarios"], report["problems"]
    _MATRIX_CHECKS[name](report["scenarios"][name])


def test_workers_scenario_cpu_smoke():
    """Multi-worker scale-out arm at workers=2 (docs/scaleout.md): two
    in-process gateway workers over one hub with the SHARED engine plane
    — open-loop single-vs-fleet throughput, byte-identical SSE handoff,
    owner-death mid-stream terminating cleanly with counted loss, and
    leader failover rebuilding the pool on the survivor."""
    report = _run_scenarios("workers", BENCH_GW_WORKERS="2")
    assert report["ok"], report["problems"]
    workers = report["scenarios"]["workers"]
    assert workers["workers"] == 2
    assert workers["failures"] == 0
    assert workers["single_worker"]["rps"] > 0
    assert workers["fleet"]["rps"] > 0
    assert workers["scaleup"] > 0
    handoff = workers["handoff"]
    assert handoff["byte_identical"] is True, handoff
    assert handoff["hang"] is False
    assert handoff["loss_counted"] is True
    assert workers["leader_failover"]["ok"] is True
    # fleet-scope SLO window: TTFT lives in the pool OWNER's registry
    # and must still be MEASURED through /admin/slo?scope=fleet
    assert workers["slo"]["objectives"]["ttft_p95"]["window_samples"] > 0


def test_zero_scenario_run_is_not_a_pass():
    """PR-6's no-vacuous-pass rule: a run that produced no verdicts must
    not report ok (main() exits 2 on an empty scenario set)."""
    report = _run_scenarios("no-such-scenario")
    assert report["ok"] is False
    assert report["scenarios"] == {}
    assert report["problems"]


def test_harness_writes_no_record_file(classic_report):
    """Verdicts go to the report only: four scenarios ran and left no
    record file at the root of the checkout or in the working directory."""
    assert set(classic_report) == {"scenarios", "problems", "platform", "ok"}
    for where in (REPO_ROOT, os.getcwd()):
        assert glob.glob(os.path.join(where, "BENCH_SCENARIO_*")) == []
