# Targets mirror the reference's Makefile:15-56 (test/manifests/install/
# deploy/docker-build) for a Python operator.
IMG ?= kubedl-tpu/operator:v0.2.0
PY ?= python
# pipefail below needs bash (tee must not mask a pytest failure)
SHELL := /bin/bash

.PHONY: test
test:
	$(PY) -m pytest tests/ -x -q

# Fleet invariant analyzer (docs/static_analysis.md): AST lint passes
# for the drifted-invariant classes (prom-escape, debug-vars-family,
# shared-validation, payload-dtype, broad-except, bench-lane-merge,
# env-contract, wire-schema, crash-consistency) plus lock-order/
# held-lock-I/O analysis over the concurrent planes.
# Exit 0 = zero unallowlisted findings; every allowlist pragma must
# carry a justification. Also: `kubedl-tpu analyze`.
.PHONY: lint
lint:
	$(PY) -m kubedl_tpu.analysis

# Explicit-state model checker for the admitter/scheduler control plane
# (docs/static_analysis.md "Protocol model"): exhaustively explores
# every interleaving of grant/evict/drain/release/RESIZE/slice-failure
# across 2-3 gangs and proves chip-conservation, exactly-once drain
# release, all-or-nothing admission and the no-eviction-storm shield —
# plus the PINNED restart counterexample (ROADMAP item 5 grant journal).
# Also: `kubedl-tpu analyze --model`.
.PHONY: model-check
model-check:
	$(PY) -m kubedl_tpu.analysis.model

# The FULL suite, slow lane included — run before every snapshot commit
# and quote the tail in the commit message (VERDICT r4 directive 1).
# The fast lane reports its slowest tests and FAILS if any single test
# exceeds 60s (VERDICT Weak #8: presubmit wall-clock creep) — mark such
# tests `slow` instead of letting the fast lane grow silently.
.PHONY: presubmit
presubmit:
	$(PY) -m kubedl_tpu.analysis
	$(PY) -m kubedl_tpu.analysis.model
	set -o pipefail; $(PY) -m pytest tests/ -q -m 'not slow' --durations=0 2>&1 | tee .presubmit-fast.log
	$(PY) hack/check_durations.py .presubmit-fast.log --max-seconds 60 \
	  --total tests/test_gmm_moe.py=100 \
	  --total tests/test_serving_disagg.py=120 \
	  --total tests/test_serving_fleet.py=60 \
	  --total tests/test_reshard.py=45 \
	  --total tests/test_pipeline_1f1b.py=170 \
	  --total tests/test_obs.py=60 \
	  --total tests/test_transport.py=60 \
	  --total tests/test_rl.py=150 \
	  --total tests/test_analysis.py=60 \
	  --total tests/test_protocol_model.py=60 \
	  --total tests/test_journal.py=60 \
	  --total tests/test_journal_chaos.py=60 \
	  --total tests/test_workqueue.py=30 \
	  --total tests/test_manager.py=30 \
	  --total tests/test_capacity_scheduler.py=60 \
	  --total tests/test_runtime_metrics.py=60 \
	  --total tests/test_weights.py=90
	$(PY) -m pytest tests/ -q -m slow

# Host-only timings of the control plane: the launch-delay headline,
# then the four lanes below. No accelerator, no JAX, never a device
# metric: the chip's yardstick is benchmarks/run.py (BENCHMARK.json,
# PERF.md). Records go to .bench_extras.json, KUBEDL_BENCH_SMALL=1
# cuts the lanes to smoke sizes.
.PHONY: bench
bench:
	$(PY) bench.py

# Host-only: the transport_roundtrip record — socket plane vs
# DirChannel msg/s + MB/s at control-sized and boundary-sized (8MB)
# payloads (merges ONLY the transport_roundtrip key into
# .bench_extras.json; span file at .bench_trace/transport.jsonl).
.PHONY: bench-transport
bench-transport:
	$(PY) bench.py --transport-only

# Host-only: the weight_distribution record — serial hub-and-spoke
# dial vs the O(log n) broadcast tree at N in {4,16,64} pods over
# paced loopback planes, per-pod commit p50/p99, relay amplification,
# and the byte-identity/0.25x gates, under the lock witness (merges
# ONLY the weight_distribution key into .bench_extras.json; span file
# at .bench_trace/weights.jsonl).
.PHONY: bench-weights
bench-weights:
	$(PY) bench.py --weights-only

# Host-only: the journal_wal record — grant-path latency with the
# write-ahead journal off vs on, raw fsync'd append throughput, and a
# 1k-gang crash replay (merges ONLY the journal_wal key into
# .bench_extras.json; span file at .bench_trace/journal.jsonl).
.PHONY: bench-journal
bench-journal:
	$(PY) bench.py --journal-only

# Host-only: the fleet_scale record — 10k-job / 100k-pod closed-loop
# launch latency through the real operator, sharded-reconcile
# throughput (1 vs 8 workers), incremental demand-view tick cost, and
# concurrent group-commit grant cost, all under the lock witness
# (merges ONLY the fleet_scale key into .bench_extras.json; span file
# at .bench_trace/fleet.jsonl).
.PHONY: bench-fleet
bench-fleet:
	$(PY) bench.py --fleet-only

.PHONY: manifests
manifests:
	$(PY) hack/gen_manifests.py

.PHONY: install
install: manifests
	kubectl apply -f config/crd/bases/

.PHONY: uninstall
uninstall:
	kubectl delete -f config/crd/bases/

.PHONY: deploy
deploy: install
	kubectl apply -f config/manager/all_in_one.yaml

.PHONY: webhook-certs
webhook-certs:
	bash hack/webhook_certs.sh

.PHONY: deploy-webhook
deploy-webhook:
	kubectl apply -f config/webhook/webhook.yaml

.PHONY: docker-build
docker-build:
	docker build -t $(IMG) .

.PHONY: docker-push
docker-push:
	docker push $(IMG)

.PHONY: dryrun
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"
