# Developer/CI entry points. `make verify` wraps the ROADMAP.md tier-1
# command verbatim (lint runs first — fast fail); `make chaos-smoke`
# runs the slow-marked chaos drills (fault-injected matcher + mesh)
# that the default suite skips; `make lint` is the static-analysis
# bundle (brokerlint + mypy-if-installed + the C gate).
SHELL := /bin/bash
PY ?= python

.PHONY: verify chaos-smoke test lint typecheck c-gate san-gate lockgraph loopgraph pipeline-smoke conn-smoke recovery-smoke scrape-cluster scrape-devices scenario-smoke scenario-matrix

# static analysis: the repo-specific concurrency/invariant lint pass
# (tools/brokerlint, README "Static analysis"), the mypy gate over the
# typed core modules (skipped with a notice when mypy is not installed —
# CI always installs it), and the C analysis gate over mqtt_tpu/native/
lint:
	$(PY) -m tools.brokerlint mqtt_tpu
	@if $(PY) -c "import mypy" >/dev/null 2>&1; then \
	  $(PY) -m mypy --config-file mypy.ini; \
	else echo "mypy not installed; skipping typecheck (CI runs it)"; fi
	PY=$(PY) tools/c_gate.sh

# hard-required mypy run (fails when mypy is absent)
typecheck:
	$(PY) -m mypy --config-file mypy.ini

# extract the whole-program lock-acquisition-order graph (brokerlint
# R9) and write exp/artifacts/lockgraph.{dot,json}; render the DOT with
# `dot -Tsvg exp/artifacts/lockgraph.dot` when graphviz is installed
lockgraph:
	$(PY) -m tools.brokerlint mqtt_tpu --lock-graph exp/artifacts

# extract the loop-affinity model (brokerlint R10-R15: loop-owned kinds,
# owner-attach sites, blessed marshal seams) and write
# exp/artifacts/loopgraph.{dot,json}
loopgraph:
	$(PY) -m tools.brokerlint mqtt_tpu --loop-graph exp/artifacts

# gcc -fanalyzer (+ cppcheck when installed) over the native C sources
c-gate:
	PY=$(PY) tools/c_gate.sh

# ASAN/UBSAN leg: sanitized rebuild of both native modules + the
# native-facing test subset run under them (ISSUE 13)
san-gate:
	PY=$(PY) tools/c_gate.sh --san

# the tier-1 gate: full non-slow suite on the CPU backend (ROADMAP.md);
# lint runs first so an invariant break fails in seconds, not minutes
# (tests/test_lint.py also asserts a clean tree from inside the suite)
verify: lint
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

test: verify

# slow-marked chaos smoke: seeded dispatch hang/error/corrupt/flap and
# mesh peer kill under live traffic (tests/test_resilience.py), the
# sustained publish-storm overload drill (tests/test_overload.py), the
# partition-storm mesh drill against a flapping 2-worker broker
# (tests/test_cluster.py + stress.py --partition), the multi-worker
# mesh drills (tests/test_mesh_drill.py: the 32-worker partition
# storm, the shaped-TCP two-machine WAN predicate drill, and the
# root-kill failover leg), and the seeded thread-schedule sweeps
# (tests/test_race.py: the switch-interval fuzz plus the 200-schedule
# graph-guided preemption fuzzer)
chaos-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_resilience.py \
	  tests/test_overload.py tests/test_cluster.py tests/test_race.py \
	  tests/test_federation.py tests/test_tree_mesh.py \
	  tests/test_mesh_drill.py \
	  -q -m slow \
	  -p no:cacheprovider -p no:xdist -p no:randomly

# mesh federation scrape gate (exp/scrape_cluster.py): boot a 3-worker
# tree mesh, drive a cross-worker burst, scrape the root's
# /metrics/cluster + /healthz, validate the federated exposition and
# nonzero remote-path delivery-latency samples
scrape-cluster:
	env JAX_PLATFORMS=cpu $(PY) exp/scrape_cluster.py

# device-observatory scrape gate (exp/scrape_devices.py): boot a broker
# over an 8-way forced host mesh, drive a burst + an 8-way sharded
# matcher, and validate GET /devices + the labeled mqtt_tpu_device_*
# exposition families for all 8 devices (ISSUE 18)
scrape-devices:
	env JAX_PLATFORMS=cpu $(PY) exp/scrape_devices.py

# staged-pipeline smoke: the tiny-size CPU mode of chip_smoke.py (the
# script the chip itself is proven with, ISSUE 21) — served path end to
# end, deliveries checked against the filters, zero breaker/staging
# fallbacks, device-vs-host parity, every kernel vs its host oracle;
# writes pipeline-smoke.json (summary line + verdict line; uploaded as a
# CI artifact)
pipeline-smoke:
	set -o pipefail; env JAX_PLATFORMS=cpu $(PY) chip_smoke.py \
	  --expect-platform cpu --subs 20000 --publishes 6000 \
	  | tee pipeline-smoke.json

# connection-scale smoke (exp/conn_smoke.py): boot the event-loop shard
# fabric (loop_shards>1), ramp thousands of mostly-idle connections +
# a publish burst, assert healthz 200, zero host-trie-oracle delivery
# mismatches, and per-shard connection spread within 2x; writes
# conn-smoke.json (uploaded as a CI artifact)
conn-smoke:
	env JAX_PLATFORMS=cpu $(PY) exp/conn_smoke.py

# scenario lab (exp/scenario_lab.py + mqtt_tpu/scenarios.py, ISSUE 20):
# seeded workload/fault scenarios judged by the delivery oracle AND the
# SLO engine's burn-rate objectives. The smoke tier runs in the CI
# verify job (artifact: exp/artifacts/scenario_lab.json); the full
# matrix — QoS2 kill -9 exactly-once, will storm, 3-worker federation,
# live tenant re-key — rides the nightly chaos leg
scenario-smoke:
	env JAX_PLATFORMS=cpu $(PY) exp/scenario_lab.py --smoke

scenario-matrix:
	env JAX_PLATFORMS=cpu $(PY) exp/scenario_lab.py --all

# crash-recovery smoke (exp/recovery_smoke.py): seed a broker subprocess
# with persistent sessions + retained state over the log-structured
# store, kill -9 it, restart on the same directory, assert the recovery
# budget, the healthz recovering->ready flip, exact restored counts, and
# the post-restart delivery oracle (session resume, live routing,
# retained redelivery through the device matcher with zero oracle
# mismatches); writes recovery-smoke.json (uploaded as a CI artifact)
recovery-smoke:
	env JAX_PLATFORMS=cpu $(PY) exp/recovery_smoke.py
