# Developer entry points. The image has no sphinx/mkdocs (and no network
# installs), so `docs` runs the vendored zero-dep generator instead.

.PHONY: docs smoke test slow ci ci-lint ci-adapters ci-pools bench-compare

docs:
	python tools/gen_api_docs.py

# Fast tier: excludes tests marked `slow` (heavy e2e/parallel/example runs).
# Budget: ~90 s solo on the 1-core bench host; concurrent load stretches it
# several-fold (measured ~4 min under a parallel bench run).
smoke:
	python -m pytest tests/ -q -m "not slow"

# Heavy tier: multi-process jax.distributed clusters, pool stress,
# end-to-end examples.
slow:
	python -m pytest tests/ -q -m "slow"

test:
	python -m pytest tests/ -q

# ---------------------------------------------------------------------------
# Full gauntlet — the reference runs a four-pass CI matrix (lint+docs, forked
# tests, main suite, torch/tf passes in their own pytest processes:
# reference .github/workflows/unittest.yml:60-88). Same structure here, one
# command, shell timeouts per pass (no pytest-timeout in the image):
#   1. lint (syntax gate via compileall; no flake8 in the image) + docs
#   2. fast tier
#   3. slow tier (process pools, 2-process jax.distributed, examples)
#   4. torch/tf adapter pass, isolated in its own interpreter
#   5. workers-pool/native-ring pass, isolated (process spawn + shm)
# CI (.github/workflows/ci.yml) invokes exactly these targets.
ci: ci-lint docs
	timeout 1800 python -m pytest tests/ -q -m "not slow"
	timeout 2400 python -m pytest tests/ -q -m "slow"
	$(MAKE) ci-adapters
	$(MAKE) ci-pools
	@echo "ci: all passes green"

ci-lint:
	python -m compileall -q petastorm_tpu tests tools examples bench.py chip_smoke.py __graft_entry__.py
	python tools/check_monotonic.py
	python tools/check_backoff.py
	python tools/check_knobs.py
	python tools/check_timeouts.py
	python tools/check_columns.py
	python tools/check_copies.py
	python tools/check_hostlocal.py
	python tools/check_spans.py
	python tools/check_rowloops.py
	python tools/check_pointreads.py
	python tools/check_determinism.py
	python tools/check_listing.py
	python tools/check_metric_docs.py
	python tools/check_operators.py
	python tools/check_lowering.py
	python tools/check_wire.py
	python tools/check_journal.py
	python tools/check_cachekeys.py
	# Shipped SLO rules + anomaly detectors, gated against the committed
	# known-good bench telemetry snapshots (bench.py refreshes them each
	# run): a rule/detector regression fails the BUILD, not just the bench.
	python -m petastorm_tpu.telemetry check bench_snapshots/appending_epoch.json --anomaly
	python -m petastorm_tpu.telemetry check bench_snapshots/deterministic_epoch.json --anomaly
	# Data-quality contract (docs/observability.md "Data quality plane"):
	# the committed quality-on bench snapshot must hold the drift SLO — a
	# shipped profile/scoring regression fails the BUILD.
	python -m petastorm_tpu.telemetry check bench_snapshots/quality_epoch.json --slo "quality.max_drift<=0.2"
	# Telemetry-fabric contract (docs/observability.md "Telemetry fabric"):
	# the committed healthy 3-publisher fleet snapshot must replay clean —
	# a fabric aggregation/federation regression fails the BUILD.
	python -m petastorm_tpu.telemetry check bench_snapshots/fleet_telemetry_epoch.json --anomaly
	# Data-service contract (docs/service.md): the committed dispatcher
	# snapshot from the bench fleet must hold the exactly-once SLO — a
	# lease/coverage regression fails the BUILD.
	python -m petastorm_tpu.telemetry check bench_snapshots/data_service_epoch.json --slo "counter:service.coverage_violations_total<=0"
	# Fleet-survivability contract (docs/service.md "Failure modes &
	# recovery"): the committed chaos snapshot — dispatcher AND one decode
	# server killed mid-epoch — must still hold the exactly-once SLO and
	# show a clean journal; a failover/replay regression fails the BUILD.
	python -m petastorm_tpu.telemetry check bench_snapshots/chaos_service_epoch.json --slo "counter:service.coverage_violations_total<=0" --slo "counter:journal.torn_records_total<=0"
	# Fleet-cache contract (docs/service.md "Fleet cache tier"): the
	# committed two-tenant 80%-overlap snapshot — one decode server killed
	# mid-epoch — must stay exactly-once with bounded peer-fetch fallbacks
	# (a handful of timeouts from the killed server are the designed
	# degradation; unbounded growth is a directory-invalidation bug).
	python -m petastorm_tpu.telemetry check bench_snapshots/fleet_cache_epoch.json --slo "counter:service.coverage_violations_total<=0" --slo "counter:service.cache.peer_fetch_timeouts_total<=8"

# Diff the two newest committed round artifacts — both the CPU-bench
# BENCH_r*.json series and the multi-chip MULTICHIP_r*.json series — and
# fail on a >20% drop in any shared bench phase (tools/bench_compare.py
# for the phase-key rules). Override the pair under comparison with
# `make bench-compare OLD=a.json NEW=b.json`.
bench-compare:
ifdef OLD
ifndef NEW
	$(error bench-compare: OLD is set but NEW is not — pass both, e.g. `make bench-compare OLD=a.json NEW=b.json`)
endif
	python tools/bench_compare.py $(OLD) $(NEW)
else
ifdef NEW
	$(error bench-compare: NEW is set but OLD is not — pass both, e.g. `make bench-compare OLD=a.json NEW=b.json`)
endif
	python tools/bench_compare.py
	python tools/bench_compare.py --prefix MULTICHIP
endif

ci-adapters:
	timeout 1200 python -m pytest tests/test_torch_loader_depth.py \
	    tests/test_torch_tf_depth.py tests/test_tf_depth.py \
	    tests/test_adapters_and_tools.py -q

ci-pools:
	timeout 1200 python -m pytest tests/test_workers_pool.py \
	    tests/test_pool_stress.py tests/test_native_ring.py \
	    tests/test_spawn_and_serializers.py tests/test_ventilator.py -q
