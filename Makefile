# mcp-context-forge-tpu (reference: 8.7k-line Makefile; the targets that matter)

.PHONY: serve hub lint test test-py test-fast test-two-process bench-scenarios bench-workers-real bench-fabric bench-chaos wrapper masking clean \
	sanitize sanitize-tsan sanitize-asan

serve:
	python -m mcp_context_forge_tpu.cli serve

hub:
	python -m mcp_context_forge_tpu.coordination.hub --port 7077

# the reference's test-primary-worker-e2e analog: 2 real OS processes + hub
test-two-process:
	python -m pytest tests/integration/test_two_process.py tests/integration/test_supervisor.py -q

supervise:
	python -m mcp_context_forge_tpu.cli supervise --workers 2

compose-config:
	python -c "import yaml; yaml.safe_load(open('docker-compose.yml')); print('ok')"

# in-tree static analysis (docs/static_analysis.md): async-safety, TPU
# host-sync hazards, thread-boundary discipline. Non-zero exit on any
# unsuppressed finding; also enforced in tier-1 via test_lint_clean.py.
lint:
	python -m mcp_context_forge_tpu.tools.lint mcp_context_forge_tpu

# full gate: lint + python suite + the C++ tier under TSAN and ASAN/UBSAN
test: lint test-py sanitize

test-py:
	python -m pytest tests/ -q

test-fast:
	python -m pytest tests/unit tests/fuzz -q

# SLO-asserting gateway scenario harness (docs/load_harness.md): burst /
# diurnal ramp / mixed chat+tools+A2A+federation / tenant (skewed
# per-tenant mix with SLO classes + token-conservation gate) / chaos
# replica-kill under load, each gated through /admin/slo delta windows;
# one JSON report of verdicts on stdout — checks of behaviour, not
# speeds (the benchmark is `python3 benchmark/run.py`, BENCHMARK.json).
# CPU smoke variant runs in tier-1 (tests/unit/test_bench_scenarios_smoke.py).
bench-scenarios:
	python bench_gateway_scenarios.py

# real-process fleet arm only (docs/load_harness.md "real-process
# topology"): forks N `mcpforge serve` workers on one SO_REUSEPORT
# socket behind a hub process — the same path `mcpforge supervise`
# runs in production — and gates scaleup against the honest
# 0.8*min(workers, host_cpus) bar.
bench-workers-real:
	BENCH_SCENARIO_ONLY=workers-real BENCH_REAL_PROCS=1 \
	BENCH_SCENARIO_ENFORCE_SLO=1 \
	python bench_gateway_scenarios.py

# cross-host prefix-cache fabric arm (docs/cache_fabric.md): two real
# supervisors with DISJOINT engine pools sharing only a file:// object
# store — host B must serve the chains host A prefilled (byte-identical
# continuations, exact per-tenant ledger conservation) and a forced
# tier.object breaker-open phase must finish with zero request
# failures.
bench-fabric:
	BENCH_SCENARIO_ONLY=fabric BENCH_REAL_PROCS=1 \
	python bench_gateway_scenarios.py

# chaos matrix only (docs/resilience.md): fault-injection arms —
# db-outage / tier-fault / overload-shed / chaos (slow-replica + kill)
# — against the fault plane; every arm gates on stream integrity,
# ledger conservation, and breaker transitions
bench-chaos:
	BENCH_SCENARIO_ONLY=db-outage,tier-fault,overload-shed,chaos \
	python bench_gateway_scenarios.py

# real HF-format checkpoint built in-tree (BPE tokenizer.json + safetensors;
# the model memorizes its corpus so greedy decode is assertable)
tiny-checkpoint:
	python -m mcp_context_forge_tpu.tools.tiny_checkpoint /tmp/mcpforge-tiny-ckpt

wrapper:
	g++ -O2 -std=c++17 mcp_context_forge_tpu/native/stdio_wrapper.cpp -o mcpforge-wrapper

edge:
	g++ -O2 -std=c++17 -pthread mcp_context_forge_tpu/native/mcp_edge.cpp -o mcpforge-edge

masking:
	g++ -O2 -shared -fPIC -std=c++17 mcp_context_forge_tpu/native/masking.cpp \
	  -o mcp_context_forge_tpu/native/libmasking.so

# --- sanitizer tier for the C++ components (SURVEY.md §5.2: the reference's
# Rust tier gets the borrow checker + deny.toml; the C++ tier gets TSAN +
# ASAN/UBSAN builds run against the same tests) ---
SAN_DIR := /tmp/mcpforge-san

sanitize-tsan:
	mkdir -p $(SAN_DIR)
	g++ -std=c++17 -g -fsanitize=thread tests/native/masking_stress.cpp \
	  -o $(SAN_DIR)/masking_stress_tsan -pthread
	$(SAN_DIR)/masking_stress_tsan
	g++ -std=c++17 -g -O1 -fsanitize=thread -pthread \
	  mcp_context_forge_tpu/native/mcp_edge.cpp -o $(SAN_DIR)/edge_tsan
	MCPFORGE_EDGE_BIN=$(SAN_DIR)/edge_tsan \
	  python -m pytest tests/integration/test_mcp_edge.py -q
	g++ -std=c++17 -g -O1 -fsanitize=thread -pthread \
	  mcp_context_forge_tpu/native/stdio_wrapper.cpp -o $(SAN_DIR)/wrapper_tsan
	MCPFORGE_WRAPPER_BIN=$(SAN_DIR)/wrapper_tsan \
	  python -m pytest tests/integration/test_translate_wrapper.py -q

sanitize-asan:
	mkdir -p $(SAN_DIR)
	g++ -std=c++17 -g -fsanitize=address,undefined \
	  tests/native/masking_stress.cpp -o $(SAN_DIR)/masking_stress_asan -pthread
	$(SAN_DIR)/masking_stress_asan
	g++ -std=c++17 -g -O1 -fsanitize=address,undefined -pthread \
	  mcp_context_forge_tpu/native/mcp_edge.cpp -o $(SAN_DIR)/edge_asan
	g++ -std=c++17 -g -O1 -fsanitize=address,undefined \
	  mcp_context_forge_tpu/native/stdio_wrapper.cpp -o $(SAN_DIR)/wrapper_asan
	MCPFORGE_EDGE_BIN=$(SAN_DIR)/edge_asan \
	  python -m pytest tests/integration/test_mcp_edge.py -q
	MCPFORGE_WRAPPER_BIN=$(SAN_DIR)/wrapper_asan \
	  python -m pytest tests/integration/test_translate_wrapper.py -q

sanitize: sanitize-tsan sanitize-asan

clean:
	rm -rf .pytest_cache mcpforge-wrapper mcp_context_forge_tpu/native/libmasking.so
	find . -name __pycache__ -type d -exec rm -rf {} +
