# Developer entry points. The image has no sphinx/mkdocs (and no network
# installs), so `docs` runs the vendored zero-dep generator instead.

.PHONY: docs smoke test slow ci ci-lint ci-adapters ci-pools

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
	python -m compileall -q petastorm_tpu tests tools examples __graft_entry__.py
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

ci-adapters:
	timeout 1200 python -m pytest tests/test_torch_loader_depth.py \
	    tests/test_torch_tf_depth.py tests/test_tf_depth.py \
	    tests/test_adapters_and_tools.py -q

ci-pools:
	timeout 1200 python -m pytest tests/test_workers_pool.py \
	    tests/test_pool_stress.py tests/test_native_ring.py \
	    tests/test_spawn_and_serializers.py tests/test_ventilator.py -q
