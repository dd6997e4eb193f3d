#!/bin/sh
# Entry point (reference: docker-entrypoint.sh): wait for deps, then exec.
set -e

if [ -n "$MCPFORGE_WAIT_FOR" ]; then
  # MCPFORGE_WAIT_FOR="host:port host:port" — wait for each before boot
  for target in $MCPFORGE_WAIT_FOR; do
    host=${target%%:*}; port=${target##*:}
    echo "waiting for $host:$port ..."
    python - "$host" "$port" <<'PY'
import socket, sys, time
host, port = sys.argv[1], int(sys.argv[2])
for _ in range(120):
    try:
        socket.create_connection((host, port), timeout=2).close()
        sys.exit(0)
    except OSError:
        time.sleep(1)
sys.exit(f"timeout waiting for {host}:{port}")
PY
  done
fi

case "$1" in
  lint)
    # in-tree static analysis (docs/static_analysis.md): non-zero exit
    # on unsuppressed findings, same gate the image build already ran
    shift
    exec python -m mcp_context_forge_tpu.tools.lint "$@"
    ;;
  bench-scenarios)
    # SLO-asserting gateway scenario harness (docs/load_harness.md):
    # burst/ramp/mixed/chaos with /admin/slo verdicts; exits non-zero on
    # scenario hard-failures or a zero-verdict (vacuous) run
    shift
    exec python bench_gateway_scenarios.py "$@"
    ;;
  bench-workers-real)
    # real-process fleet arm (docs/load_harness.md "real-process
    # topology"): N forked serve workers on one SO_REUSEPORT socket
    # behind a hub process; gates scaleup against
    # 0.8*min(workers, host_cpus)
    shift
    BENCH_SCENARIO_ONLY=workers-real BENCH_REAL_PROCS=1 \
      BENCH_SCENARIO_ENFORCE_SLO=1 \
      exec python bench_gateway_scenarios.py "$@"
    ;;
  bench-fabric)
    # cross-host prefix-cache fabric arm (docs/cache_fabric.md): two
    # supervisors, disjoint engine pools, one shared file:// object
    # store; gates cross-host hits, byte parity, ledger conservation,
    # and zero failures under a forced tier.object breaker-open
    shift
    BENCH_SCENARIO_ONLY=fabric BENCH_REAL_PROCS=1 \
      exec python bench_gateway_scenarios.py "$@"
    ;;
  bench-chaos)
    # fault-injection matrix only (docs/resilience.md): db-outage /
    # tier-fault / overload-shed / chaos (slow-replica + kill), gated on
    # stream integrity, ledger conservation, and breaker transitions
    shift
    BENCH_SCENARIO_ONLY=db-outage,tier-fault,overload-shed,chaos \
      exec python bench_gateway_scenarios.py "$@"
    ;;
  serve|supervise|hub|token|version)
    cmd="$1"; shift
    if [ "$cmd" = "hub" ]; then
      exec python -m mcp_context_forge_tpu.coordination.hub "$@"
    fi
    exec python -m mcp_context_forge_tpu.cli "$cmd" "$@"
    ;;
  *)
    exec "$@"
    ;;
esac
