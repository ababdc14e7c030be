"""Host-side micro-benchmark of the read path. Prints ONE JSON line:
``{"metric", "value", "unit", "vs_baseline", ...extras}``.

Every phase runs on the host CPU (JAX-touching children are pinned to
``JAX_PLATFORMS=cpu``); nothing here touches an accelerator and no key
it writes is a device metric. The on-chip entry is ``chip_smoke.py``;
the benchmark the driver runs on the chip is ROADMAP.md S0, which
replaces this file.

Phases:

1. **hello_world (headline, ``vs_baseline``)** — the reference's only
   published absolute number: 709.84 samples/sec on the 10-row tutorial
   store with default benchmark args (reference
   docs/benchmarks_tutorial.rst:20-21; 3 thread workers, 200 warmup + 1000
   measured reads, same schema, same store layout).
2. **hello_world_10k** — same schema scaled to 10k rows / 100-row groups so
   the number reflects steady-state decode+IO throughput rather than
   10-row loop overhead (extra key ``hello_world_10k_samples_per_sec``).
3. **best_config** — a sweep of host-pipeline configurations on the 10k
   store (thread pool, dummy+coalescing, process pool over the shm ring +
   native decode + coalescing); the measured winner is reported as
   ``best_config_samples_per_sec``/``best_config`` with the per-config
   breakdown in ``best_config_sweep``.
4. **scalar_batched** — the columnar path (``make_batch_reader`` ->
   ``BatchedDataLoader``) on a plain 20-column numeric Parquet store; extra
   key ``scalar_batched_samples_per_sec`` (the reference only ever made a
   qualitative "significantly higher throughput" claim here, README.rst:242).
4d. **stage_breakdown** — the columnar loader run under the pipeline's
   :mod:`petastorm_tpu.telemetry` registry; the JSON line gains a
   ``stage_breakdown`` block (decode / pool-queue / shuffle / host_wait /
   stage / device_put wait, cumulative seconds) and a
   ``stall_attribution`` verdict (docs/observability.md).

Every multi-rerun phase reports dispersion — ``*_p50`` (median of the
reruns) and ``*_spread_pct`` ((max-min)/median) — alongside the best
value, so a round-over-round delta is attributable to noise vs regression
(round-3 verdict, "weak" item 1).
"""
import json
import os
import statistics
import sys

BASELINE_SAMPLES_PER_SEC = 709.84  # reference docs/benchmarks_tutorial.rst:20


def _ensure(marker_url: str, generate):
    path = marker_url.replace("file://", "") + "/_common_metadata"
    if not os.path.exists(path):
        generate()


def _prior_round_artifact() -> tuple[str, dict] | tuple[None, None]:
    """Newest committed BENCH_r*.json — the previous round's numbers."""
    import glob
    import re
    best_n, best_path = -1, None
    for path in glob.glob(os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m and int(m.group(1)) > best_n:
            best_n, best_path = int(m.group(1)), path
    if best_path is None:
        return None, None
    try:
        with open(best_path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None, None
    # The driver wraps bench.py's JSON line: {"n": .., "cmd": .., "rc": ..,
    # "parsed": {...}, "tail": "<stderr+stdout tail>"} — prefer the
    # pre-parsed dict; fall back to parsing the last JSON line in the tail.
    if isinstance(data.get("parsed"), dict) and "value" in data["parsed"]:
        return os.path.basename(best_path), data["parsed"]
    if "tail" in data and "value" not in data:
        for line in reversed(data["tail"].splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return os.path.basename(best_path), json.loads(line)
                except ValueError:
                    continue
    return os.path.basename(best_path), data


# Phases compared round-over-round: (current-artifact p50 key | best key).
_REGRESSION_PHASES = ("value", "hello_world_10k_samples_per_sec",
                      "best_config_samples_per_sec",
                      "scalar_batched_samples_per_sec",
                      "scalar_batched_process_samples_per_sec")


def _regression_guard(out: dict) -> None:
    """Compare this round's p50s against the previous round artifact and
    flag drops that exceed the phase's own measured noise (round-4 verdict
    "weak" item 1: a real 20% regression must not look identical to host
    jitter). Noise bound = the larger of the two rounds' spread_pct, floored
    at 10% — the single-core bench host shares its core with the driver, and
    sub-10% deltas have never been reproducible here."""
    prior_name, prior = _prior_round_artifact()
    if not prior:
        return
    comparison: dict = {"against": prior_name}
    regressions = []
    for phase in _REGRESSION_PHASES:
        cur = out.get(f"{phase}_p50", out.get(phase))
        old = prior.get(f"{phase}_p50", prior.get(phase))
        if not (isinstance(cur, (int, float)) and isinstance(old, (int, float))
                and old > 0):
            continue
        delta_pct = round(100.0 * (cur - old) / old, 1)
        noise_pct = max(out.get(f"{phase}_spread_pct", 0.0),
                        prior.get(f"{phase}_spread_pct", 0.0), 10.0)
        comparison[phase] = {"prior_p50": old, "p50": cur,
                             "delta_pct": delta_pct,
                             "noise_bound_pct": round(noise_pct, 1)}
        if delta_pct < -noise_pct:
            regressions.append(phase)
    if len(comparison) == 1:  # only "against": nothing actually compared —
        return                # an empty-but-present guard would read as green
    out["vs_prior_round"] = comparison
    out["regressions"] = regressions


def _dispersion(out: dict, prefix: str, samples) -> float:
    """Record best/median/spread for one phase's reruns; returns the best.

    ``{prefix}_p50`` and ``{prefix}_spread_pct`` land next to the headline
    best-of-N so noise (large spread) is distinguishable from regression
    (shifted median) across rounds."""
    samples = [float(s) for s in samples]
    best = max(samples)
    if len(samples) > 1:
        p50 = statistics.median(samples)
        out[f"{prefix}_p50"] = round(p50, 2)
        out[f"{prefix}_spread_pct"] = round(
            100.0 * (best - min(samples)) / p50, 1) if p50 else 0.0
    return best


def main():
    data_dir = os.environ.get("BENCH_DATA_DIR", "/tmp/pt_bench")
    from petastorm_tpu.benchmark.hello_world import generate_hello_world_dataset
    from petastorm_tpu.benchmark.throughput import reader_throughput

    out = {}

    # ---- 1. headline: the reference's exact tutorial config ------------
    url = f"file://{data_dir}/hello_world"
    _ensure(url, lambda: generate_hello_world_dataset(url))
    # best-of-5 warm reruns: single-core host load is spiky, so one clean
    # sample needs several tries (same spirit as the tutorial's warm rerun).
    hello_samples = [
        reader_throughput(url, warmup_cycles=200, measure_cycles=1000,
                          pool_type="thread", loaders_count=3).samples_per_second
        for _ in range(5)]
    best = _dispersion(out, "value", hello_samples)

    # ---- 2. steady-state: 10k rows, 100-row groups ---------------------
    url_10k = f"file://{data_dir}/hello_world_10k"
    _ensure(url_10k, lambda: generate_hello_world_dataset(
        url_10k, rows_count=10_000, rows_per_row_group=100))
    # NOTE: deliberately no rowgroup_coalescing here — with coalesced items
    # the default results queue can buffer the whole 10k-row epoch during
    # warmup and the measurement would drain memory, not the pipeline.
    steady_samples = [
        reader_throughput(url_10k, warmup_cycles=200, measure_cycles=2000,
                          pool_type="thread", loaders_count=3).samples_per_second
        for _ in range(3)]  # 3 reruns: enough for a median on a spiky host
    steady_sps = _dispersion(out, "hello_world_10k_samples_per_sec",
                             steady_samples)

    # ---- 3. best measured config on the same 10k store: a small sweep,
    # reporting whichever pipeline configuration actually wins on THIS
    # host. (Measured 2026-07-30 on the 1-core bench host: process pool +
    # shm ring loses 4x to threads here — IPC serialization swamps the GIL
    # win with no spare core — and all thread/dummy/coalescing variants
    # land within ~10% of the decode-bound ceiling. Hosts with real core
    # counts will pick differently, which is the point of sweeping.)
    # Small results queue so the measurement drains the pipeline, not a
    # warmup backlog of coalesced 800-row items. In a CPU-pinned subprocess
    # for the same reason as the scalar phase.
    best_child = (
        "import json, os\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.benchmark.throughput import reader_throughput\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'hello_world_10k')\n"
        "coal = {'rowgroup_coalescing': 8, 'results_queue_size': 4}\n"
        "sweep = {\n"
        "    'thread_pool+workers=3': dict(pool_type='thread', loaders_count=3),\n"
        "    'dummy_pool+native_decode+rowgroup_coalescing=8':\n"
        "        dict(pool_type='dummy', reader_extra_kwargs=dict(coal)),\n"
        "    'process_pool+shm_ring+native_decode+rowgroup_coalescing=8+workers=2':\n"
        "        dict(pool_type='process', loaders_count=2,\n"
        "             reader_extra_kwargs=dict(coal)),\n"
        "}\n"
        # 2 reruns per config: single-core load spikes exceed the ~10%
        # margins between configs, so one lone run could crown the wrong
        # winner. All samples are returned so the parent reports dispersion.
        "results = {name: [reader_throughput(url, warmup_cycles=800,\n"
        "                                    measure_cycles=8000,\n"
        "                                    **kw).samples_per_second\n"
        "                  for _ in range(2)]\n"
        "           for name, kw in sweep.items()}\n"
        "best = max(results, key=lambda n: max(results[n]))\n"
        "print('BENCHJSON:' + json.dumps({'config': best,\n"
        "                                 'samples': results}))\n")
    try:
        best_cfg_result = _cpu_subprocess(best_child, data_dir,
                                          timeout_s=900.0)
        best_cfg = best_cfg_result["config"]
        best_cfg_sps = _dispersion(out, "best_config_samples_per_sec",
                                   best_cfg_result["samples"][best_cfg])
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        best_cfg_sps = None
        best_cfg = None
        print(f"best_config failed: {e!r}", file=sys.stderr)

    # ---- 4. scalar columnar path: make_batch_reader -> BatchedDataLoader.
    # Always in a JAX_PLATFORMS=cpu subprocess: the metric is host-side
    # pipeline throughput ("no device in the loop", scalar_bench.py), so
    # staging must hit the CPU backend whatever device the host has.
    from petastorm_tpu.benchmark.scalar_bench import generate_scalar_dataset
    url_scalar = f"file://{data_dir}/scalar_100k"
    if not os.path.exists(f"{data_dir}/scalar_100k/part0.parquet"):
        generate_scalar_dataset(url_scalar)
    scalar_child = (
        "import json, os\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.benchmark.scalar_bench import batched_loader_throughput\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "samples = [batched_loader_throughput(url) for _ in range(2)]\n"
        "print('BENCHJSON:' + json.dumps({'samples': samples}))\n")
    try:
        scalar_sps = _dispersion(out, "scalar_batched_samples_per_sec",
                                 _cpu_subprocess(scalar_child, data_dir,
                                                 timeout_s=600.0)["samples"])
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        scalar_sps = None
        # (recorded below only when measured)
        print(f"scalar_batched failed: {e!r}", file=sys.stderr)

    # ---- 4a2. process_pool_decode_epoch (docs/zero_copy.md): the columnar
    # decode pipeline (make_batch_reader -> BatchedDataLoader) over
    # identical thread and process pools — the head-to-head ROADMAP item 3
    # is judged on. Round 8 gave the process pool a zero-copy shm Arrow
    # plane (no pickle round-trip for batch readers, S/P/D preallocated
    # chunk reassembly, segment claims, dlpack staging), so the backend
    # that scales past the GIL no longer pays 3.4x in serialization. Two
    # stores: the 20-column scalar store (the decode plane's headline) and
    # a heavier one with 64-dim embedding columns (~5x bytes/row) where the
    # transport still moves real volume — on starved hosts threads may win
    # the heavy store, which is exactly why placement is an autotune
    # actuator and not an assumption.
    decode_epoch_child = (
        "import json, os\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "import pyarrow.parquet as pq\n"
        "from petastorm_tpu.benchmark.scalar_bench import batched_loader_throughput\n"
        "scalar_url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'tensor_50k')\n"
        "if not os.path.exists(os.path.join(store, 'part0.parquet')):\n"
        "    os.makedirs(store, exist_ok=True)\n"
        "    n, rng = 50_000, np.random.default_rng(0)\n"
        "    cols = {'id': np.arange(n, dtype=np.int64)}\n"
        "    cols.update({'f%d' % i: rng.standard_normal(n).astype(np.float32)\n"
        "                 for i in range(8)})\n"
        "    for j in range(2):\n"
        "        flat = rng.standard_normal(n * 64).astype(np.float32)\n"
        "        cols['emb%d' % j] = pa.FixedSizeListArray.from_arrays(\n"
        "            pa.array(flat), 64)\n"
        "    pq.write_table(pa.table(cols), os.path.join(store, 'part0.parquet'),\n"
        "                   row_group_size=2048)\n"
        "tensor_url = 'file://' + store\n"
        "def sweep(url, pool, workers, batches):\n"
        "    return [batched_loader_throughput(url, pool_type=pool,\n"
        "                                      workers_count=workers,\n"
        "                                      measure_batches=batches)\n"
        "            for _ in range(2)]\n"
        "out = {'scalar_thread': sweep(scalar_url, 'thread', 3, 300),\n"
        "       'scalar_process': sweep(scalar_url, 'process', 2, 300),\n"
        "       'tensor_thread': sweep(tensor_url, 'thread', 3, 200),\n"
        "       'tensor_process': sweep(tensor_url, 'process', 2, 200)}\n"
        "print('BENCHJSON:' + json.dumps(out))\n")
    try:
        decode_epoch = _cpu_subprocess(decode_epoch_child, data_dir,
                                       timeout_s=1500.0)
        p50 = {k: statistics.median(v) for k, v in decode_epoch.items()}
        out["process_pool_decode_epoch"] = {
            f"{k}_samples_per_sec": round(v, 2) for k, v in p50.items()}
        out["process_pool_decode_epoch"].update({
            "scalar_process_vs_thread": round(
                p50["scalar_process"] / max(p50["scalar_thread"], 1e-9), 3),
            "tensor_process_vs_thread": round(
                p50["tensor_process"] / max(p50["tensor_thread"], 1e-9), 3),
            "runs": {k: [round(s, 1) for s in v]
                     for k, v in decode_epoch.items()},
        })
        # The per-round regression surface for the process-pool transport.
        out["scalar_batched_process_samples_per_sec"] = round(
            p50["scalar_process"], 2)
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"process_pool_decode_epoch failed: {e!r}", file=sys.stderr)

    # ---- 4b. input-stall sweep vs an emulated device step (round-4
    # verdict item 2): the pipeline's own headline contract — "reader
    # throughput >= device step rate" (SURVEY.md §7) — tested in the regime
    # that matters (~5-20 ms steps), with or without silicon. The synthetic
    # step is wall-clock calibrated, so on the CPU backend it still burns
    # the same time a real TPU step would; what's measured is whether the
    # HOST pipeline can hide batch production behind it. ImageNet-shaped
    # store (224px jpeg), jax read path, thread pool.
    stall_child = (
        "import json, os\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.benchmark.imagenet_bench import write_synthetic_imagenet\n"
        "from petastorm_tpu.benchmark.throughput import reader_throughput\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'imagenet')\n"
        "url = 'file://' + store\n"
        "if not os.path.exists(os.path.join(store, '_common_metadata')):\n"
        "    write_synthetic_imagenet(url, rows=2048)\n"
        "out = {}\n"
        "for ms in (5, 10, 20):\n"
        "    r = reader_throughput(url, warmup_cycles=64, measure_cycles=800,\n"
        "                          pool_type='thread', loaders_count=3,\n"
        "                          read_method='jax', device_step_ms=float(ms))\n"
        "    out['stall_pct_at_%dms' % ms] = round(r.input_stall_percent, 2)\n"
        "    out['step_ms_actual_at_%dms' % ms] = round(r.device_step_ms_actual, 2)\n"
        "    out['stall_sweep_samples_per_sec_at_%dms' % ms] = round(\n"
        "        r.samples_per_second, 2)\n"
        "print('BENCHJSON:' + json.dumps(out))\n")
    try:
        out.update(_cpu_subprocess(stall_child, data_dir, timeout_s=1500.0))
        # Smallest swept step the pipeline feeds at <5% stall — the number
        # docs/performance.md quotes as the supportable device-step rate.
        for ms in (5, 10, 20):
            if out.get(f"stall_pct_at_{ms}ms", 100.0) < 5.0:
                out["min_step_ms_under_5pct_stall"] = ms
                break
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"stall sweep failed: {e!r}", file=sys.stderr)

    # ---- 4d. per-stage telemetry breakdown (docs/observability.md): run
    # the columnar loader on the scalar store with the pipeline's shared
    # TelemetryRegistry active and report where the wall-clock went —
    # decode / pool-queue / shuffle / host_wait / stage / device_put wait —
    # plus the stall attributor's host-vs-device verdict. This is the
    # measurement layer later perf PRs are judged against: a regression in
    # any one stage is visible here even when the headline samples/sec
    # moves within noise.
    breakdown_child = (
        "import json, os\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.jax import BatchedDataLoader\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "with make_batch_reader(url, num_epochs=None, shuffle_row_groups=False,\n"
        "                       reader_pool_type='thread', workers_count=3) as reader:\n"
        "    with BatchedDataLoader(reader, batch_size=1024,\n"
        "                           shuffling_queue_capacity=8192,\n"
        "                           seed=0) as loader:\n"
        "        it = iter(loader)\n"
        "        for _ in range(200):\n"
        "            next(it)\n"
        "        stall = loader.stall_report()\n"
        "        breakdown = loader.stage_breakdown()\n"
        "print('BENCHJSON:' + json.dumps({\n"
        "    'stage_breakdown': breakdown,\n"
        "    'stall_attribution': {'verdict': stall['verdict'],\n"
        "                          'wait_fraction': stall['wait_fraction'],\n"
        "                          'fractions': stall['fractions'],\n"
        "                          'host_side': stall.get('host_side')}}))\n")
    try:
        out.update(_cpu_subprocess(breakdown_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"stage breakdown phase failed: {e!r}", file=sys.stderr)

    # ---- 4e. resilience under injected faults (docs/resilience.md): the
    # same columnar epoch with a seeded FaultPlan throwing transient
    # IOErrors on 10% of row-group reads plus one permanently corrupt row
    # group in degraded mode. Reports the retry/quarantine counters and the
    # row-completeness + throughput cost of surviving the faults — the
    # number a production pipeline pays for not dying.
    resilience_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.resilience import (ExponentialBackoff, FaultPlan,\n"
        "                                      FaultSpec, RetryPolicy)\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "def epoch(fault_plan=None, degraded=False):\n"
        "    policy = RetryPolicy(max_attempts=3, seed=0,\n"
        "                         backoff=ExponentialBackoff(base=0.001, cap=0.01))\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread', workers_count=3,\n"
        "                           retry_policy=policy, degraded_mode=degraded,\n"
        "                           fault_plan=fault_plan) as reader:\n"
        "        rows = sum(len(b[0]) for b in reader)\n"
        "        diag = reader.diagnostics\n"
        "        report = reader.quarantine_report()\n"
        "    return rows, time.perf_counter() - t0, diag, report\n"
        "epoch()  # warm-up: first epoch pays import + fs metadata costs\n"
        "clean_rows, clean_s, _, _ = epoch()\n"
        "plan = FaultPlan([\n"
        "    FaultSpec(site='rowgroup.read', kind='ioerror', rate=0.10),\n"
        "    FaultSpec(site='rowgroup.read', kind='ioerror', at=1),\n"
        "    FaultSpec(site='rowgroup.read', kind='corruption', at=7)], seed=0)\n"
        "rows, faulted_s, diag, report = epoch(plan, degraded=True)\n"
        "counters = diag['telemetry']['counters']\n"
        "print('BENCHJSON:' + json.dumps({'resilience_fault_epoch': {\n"
        "    'clean_rows': clean_rows,\n"
        "    'faulted_rows': rows,\n"
        "    'quarantined_rowgroups': report['quarantined'],\n"
        "    'retries_total': counters.get('resilience.retries_total', 0),\n"
        "    'overhead_pct': round(100.0 * (faulted_s - clean_s) / clean_s, 1)}}))\n")
    try:
        out.update(_cpu_subprocess(resilience_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"resilience phase failed: {e!r}", file=sys.stderr)

    # ---- 4e2. straggler masking via hedged reads (docs/resilience.md §
    # "Deadlines, hedging, and the watchdog"): the same columnar epoch with
    # seeded latency faults (base + decorrelated jitter) injected on five
    # deterministic row-group reads, consumed by a tight loop that records
    # per-batch delivery latency. Hedging off, the p99 batch latency IS the
    # injected tail; hedging on, a speculative duplicate read on a fresh
    # handle wins the race and masks it (acceptance: >= 2x p99 improvement).
    # One worker + a tiny results queue so production cannot hide the tail
    # behind prefetch. at=N faults count read ACCESSES, and hedge reads are
    # accesses too, so with hedging on the later faults land on shifted
    # (possibly hedge) reads — the per-leg ``faults_fired`` counts are
    # reported so a leg that dropped faults is visible, not silently
    # flattered.
    straggler_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.resilience import FaultPlan, FaultSpec, HedgePolicy\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "def plan():\n"
        "    return FaultPlan([FaultSpec(site='rowgroup.read', kind='latency',\n"
        "                                at=n, latency_s=0.08,\n"
        "                                latency_jitter_s=0.04)\n"
        "                      for n in (5, 15, 25, 35, 45)], seed=0)\n"
        "def epoch(hedge):\n"
        "    lat, p = [], plan()\n"
        "    with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread', workers_count=1,\n"
        "                           results_queue_size=2, fault_plan=p,\n"
        "                           hedge_policy=hedge) as r:\n"
        "        it = iter(r)\n"
        "        while True:\n"
        "            t0 = time.perf_counter()\n"
        "            try:\n"
        "                next(it)\n"
        "            except StopIteration:\n"
        "                break\n"
        "            lat.append(time.perf_counter() - t0)\n"
        "        counters = r.telemetry.snapshot()['counters']\n"
        "    lat.sort()\n"
        "    fired = sum(s['fired'] for s in p.stats()['specs'])\n"
        "    return lat[min(len(lat) - 1, int(0.99 * len(lat)))], counters, fired\n"
        "epoch(None)  # warm-up epoch pays import + fs metadata costs\n"
        "hedge = HedgePolicy(fallback_delay_s=0.01, min_delay_s=0.005,\n"
        "                    min_samples=10**9)\n"
        "p99_off, _, fired_off = epoch(None)\n"
        "p99_on, counters, fired_on = epoch(hedge)\n"
        "print('BENCHJSON:' + json.dumps({'straggler_epoch': {\n"
        "    'p99_batch_s_hedging_off': round(p99_off, 4),\n"
        "    'p99_batch_s_hedging_on': round(p99_on, 4),\n"
        "    'p99_improvement': round(p99_off / max(p99_on, 1e-9), 2),\n"
        "    'faults_fired_off': fired_off,\n"
        "    'faults_fired_on': fired_on,\n"
        "    'hedges_launched': counters.get('resilience.hedges_launched', 0),\n"
        "    'hedge_wins': counters.get('resilience.hedge_wins', 0)}}))\n")
    try:
        out.update(_cpu_subprocess(straggler_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"straggler phase failed: {e!r}", file=sys.stderr)

    ngram_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.benchmark.llm_bench import write_token_store\n"
        "from petastorm_tpu.ngram import NGram\n"
        "from petastorm_tpu.reader import make_reader\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'tokens512')\n"
        "url = 'file://' + store\n"
        "if not os.path.exists(os.path.join(store, '_common_metadata')):\n"
        "    write_token_store(url, windows=64, window=512)\n"
        "def measure(dense, n=128):\n"
        "    ngram = NGram({o: ['ts', 'token'] for o in range(512)},\n"
        "                  delta_threshold=1, timestamp_field='ts',\n"
        "                  timestamp_overlap=False, dense=dense)\n"
        "    with make_reader(url, schema_fields=ngram, num_epochs=None,\n"
        "                     shuffle_row_groups=True, seed=0,\n"
        "                     reader_pool_type='thread',\n"
        "                     workers_count=4) as r:\n"
        "        it = iter(r)\n"
        "        for _ in range(16):\n"
        "            next(it)\n"
        "        t0 = time.perf_counter()\n"
        "        for _ in range(n):\n"
        "            next(it)\n"
        "        return n / (time.perf_counter() - t0)\n"
        "# Ordering-bias control: a throwaway pass warms the page cache for\n"
        "# BOTH paths, then row/dense interleave (row,dense,row,dense) and\n"
        "# average — so neither path systematically reads cold pages.\n"
        "measure(False, n=32)\n"
        "row_runs, dense_runs = [], []\n"
        "for _ in range(2):\n"
        "    row_runs.append(measure(False))\n"
        "    dense_runs.append(measure(True))\n"
        "row = sum(row_runs) / len(row_runs)\n"
        "dense = sum(dense_runs) / len(dense_runs)\n"
        "print('BENCHJSON:' + json.dumps({\n"
        "    'ngram_row_windows_per_sec': round(row, 1),\n"
        "    'ngram_dense_windows_per_sec': round(dense, 1),\n"
        "    'ngram_dense_speedup': round(dense / row, 2)}))\n")
    try:
        out.update(_cpu_subprocess(ngram_child, data_dir, timeout_s=1200.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"ngram dense phase failed: {e!r}", file=sys.stderr)

    # ---- 4f. in-memory row-group cache across epochs (docs/autotune.md):
    # two epochs over the decode-heavy synthetic imagenet store with the
    # memory tier sized to hold all decoded row groups. Epoch 1 pays the
    # Parquet read + png decode and fills the cache; epoch 2 serves decoded
    # columns from RAM — the speedup is the whole decode+IO cost the cache
    # removes (acceptance: epoch-2 >= 1.3x epoch-1).
    mem_cache_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.benchmark.imagenet_bench import write_synthetic_imagenet\n"
        "from petastorm_tpu.reader import make_reader\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'imagenet')\n"
        "url = 'file://' + store\n"
        "if not os.path.exists(os.path.join(store, '_common_metadata')):\n"
        "    write_synthetic_imagenet(url, rows=2048)\n"
        "def two_epochs(cache_bytes):\n"
        "    epoch_s, counters = [], {}\n"
        "    with make_reader(url, num_epochs=2, shuffle_row_groups=False,\n"
        "                     reader_pool_type='thread', workers_count=3,\n"
        "                     memory_cache_size_bytes=cache_bytes) as r:\n"
        "        n, t0 = 0, time.perf_counter()\n"
        "        for _ in r:\n"
        "            n += 1\n"
        "            if n == 2048:\n"
        "                epoch_s.append(time.perf_counter() - t0)\n"
        "                t0 = time.perf_counter()\n"
        "        epoch_s.append(time.perf_counter() - t0)\n"
        "        counters = r.telemetry.snapshot()['counters']\n"
        "    return n, epoch_s, counters\n"
        "rows, epoch_s, counters = two_epochs(2 << 30)\n"
        "e1_sps, e2_sps = 2048 / epoch_s[0], 2048 / epoch_s[1]\n"
        "print('BENCHJSON:' + json.dumps({'mem_cache_epoch': {\n"
        "    'rows': rows,\n"
        "    'epoch1_samples_per_sec': round(e1_sps, 1),\n"
        "    'epoch2_samples_per_sec': round(e2_sps, 1),\n"
        "    'epoch2_speedup': round(e2_sps / e1_sps, 2),\n"
        "    'cache_hits': counters.get('cache.mem.hits', 0),\n"
        "    'cache_misses': counters.get('cache.mem.misses', 0),\n"
        "    'cache_inserts': counters.get('cache.mem.inserts', 0)}}))\n")
    try:
        out.update(_cpu_subprocess(mem_cache_child, data_dir, timeout_s=1200.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"mem cache phase failed: {e!r}", file=sys.stderr)

    # ---- 4f2. statistics-driven row-group pruning (docs/io.md): a
    # selective range predicate over a monotonic id column on a 200k-row /
    # 98-row-group store, pruning on vs off. With pruning, plan-time
    # min/max statistics prove ~90% of the row groups empty and they are
    # never fetched or decoded (io.rowgroups_pruned > 0, bytes-read drops
    # proportionally); rows delivered are identical either way.
    pruning_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "import pyarrow.parquet as pq\n"
        "from petastorm_tpu.predicates import in_range\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'pruning_200k')\n"
        "if not os.path.exists(os.path.join(store, 'part0.parquet')):\n"
        "    os.makedirs(store, exist_ok=True)\n"
        "    n, rng = 200_000, np.random.default_rng(0)\n"
        "    cols = {'id': np.arange(n, dtype=np.int64)}\n"
        "    cols.update({'f%d' % i: rng.standard_normal(n).astype(np.float32)\n"
        "                 for i in range(16)})\n"
        "    pq.write_table(pa.table(cols), os.path.join(store, 'part0.parquet'),\n"
        "                   row_group_size=2048)\n"
        "url = 'file://' + store\n"
        "def epoch(pruning):\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread', workers_count=3,\n"
        "                           predicate=in_range('id', 0, 20_000),\n"
        "                           rowgroup_pruning=pruning) as r:\n"
        "        rows = sum(len(b.id) for b in r)\n"
        "        c = r.telemetry.snapshot()['counters']\n"
        "        rep = r.pruning_report()\n"
        "    return rows, time.perf_counter() - t0, c, rep\n"
        "epoch(True)  # warm-up pays import + fs metadata costs\n"
        "rows_on, s_on, c_on, rep = epoch(True)\n"
        "rows_off, s_off, c_off, _ = epoch(False)\n"
        "print('BENCHJSON:' + json.dumps({'pruned_predicate_epoch': {\n"
        "    'rows_on': rows_on, 'rows_off': rows_off,\n"
        "    'rowgroups_pruned': c_on.get('io.rowgroups_pruned', 0),\n"
        "    'rowgroups_read_on': c_on.get('io.rowgroups_read', 0),\n"
        "    'rowgroups_read_off': c_off.get('io.rowgroups_read', 0),\n"
        "    'bytes_read_on': c_on.get('io.bytes_read', 0),\n"
        "    'bytes_read_off': c_off.get('io.bytes_read', 0),\n"
        "    'bytes_read_reduction': round(\n"
        "        c_off.get('io.bytes_read', 0)\n"
        "        / max(c_on.get('io.bytes_read', 1), 1), 2),\n"
        "    'epoch_s_on': round(s_on, 3), 'epoch_s_off': round(s_off, 3),\n"
        "    'pruning_epoch_speedup': round(s_off / max(s_on, 1e-9), 2)}}))\n")
    try:
        out.update(_cpu_subprocess(pruning_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"pruning phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3. async readahead under injected fetch latency (docs/io.md):
    # the scalar columnar epoch with a seeded 10ms latency fault on EVERY
    # row-group read (the PR 2 FaultPlan latency site stands in for a slow
    # remote store), one decode worker so fetch/decode serialization is
    # undisguised. Readahead off, every group pays fetch latency inline;
    # on, two fetcher threads absorb it ahead of decode and workers pop
    # resident tables (acceptance: measurable epoch-time improvement,
    # hits >> misses).
    readahead_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.resilience import FaultPlan, FaultSpec\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "def epoch(depth):\n"
        "    plan = FaultPlan([FaultSpec(site='rowgroup.read', kind='latency',\n"
        "                                rate=1.0, latency_s=0.01)], seed=0)\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread', workers_count=1,\n"
        "                           fault_plan=plan,\n"
        "                           readahead_depth=depth) as r:\n"
        "        rows = sum(len(b[0]) for b in r)\n"
        "        stats = r.readahead_report()\n"
        "    return rows, time.perf_counter() - t0, stats\n"
        "epoch(None)  # warm-up epoch pays import + fs metadata costs\n"
        "rows_off, s_off, _ = epoch(None)\n"
        "rows_on, s_on, stats = epoch(4)\n"
        "print('BENCHJSON:' + json.dumps({'readahead_epoch': {\n"
        "    'rows_on': rows_on, 'rows_off': rows_off,\n"
        "    'epoch_s_off': round(s_off, 3), 'epoch_s_on': round(s_on, 3),\n"
        "    'readahead_epoch_improvement': round(s_off / max(s_on, 1e-9), 2),\n"
        "    'readahead_hits': stats.get('hits', 0),\n"
        "    'readahead_misses': stats.get('misses', 0),\n"
        "    'readahead_fetch_errors': stats.get('fetch_errors', 0)}}))\n")
    try:
        out.update(_cpu_subprocess(readahead_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"readahead phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3a2. batch-native epoch plane (docs/io.md "Batch-native
    # plane"): the make_reader ROW pipeline, eager vs lazy materialization,
    # on a petastorm-written scalar store. Eager builds one dict + one
    # namedtuple per sample and shuffles row objects one at a time; lazy
    # publishes one ColumnarBatch per row group, shuffles permuted SLICES
    # (BatchShufflingBuffer), and collates concat-of-slices — the
    # per-sample Python loops this round retired. Reported as absolute
    # rates (auto-joining the bench_compare regression surface via the
    # _samples_per_sec suffix) plus the lazy/eager ratio; the shuffled
    # variant exercises the mixing-radius path end to end.
    batch_native_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from petastorm_tpu.codecs import ScalarCodec\n"
        "from petastorm_tpu.etl.writer import materialize_dataset_local\n"
        "from petastorm_tpu.jax import DataLoader\n"
        "from petastorm_tpu.reader import make_reader\n"
        "from petastorm_tpu.unischema import Unischema, UnischemaField\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'rowplane_50k')\n"
        "url = 'file://' + store\n"
        "if not os.path.exists(os.path.join(store, '_common_metadata')):\n"
        "    fields = [UnischemaField('id', np.int64, (), ScalarCodec(np.int64), False)]\n"
        "    fields += [UnischemaField('f%d' % i, np.float32, (),\n"
        "                              ScalarCodec(np.float32), False)\n"
        "               for i in range(8)]\n"
        "    schema = Unischema('RowPlane', fields)\n"
        "    n, rng = 50_000, np.random.default_rng(0)\n"
        "    rows = [dict({'id': i},\n"
        "                 **{'f%d' % j: np.float32(rng.standard_normal())\n"
        "                    for j in range(8)}) for i in range(n)]\n"
        "    with materialize_dataset_local(url, schema,\n"
        "                                   rows_per_row_group=2048,\n"
        "                                   rows_per_file=16384) as w:\n"
        "        w.write_rows(rows)\n"
        "def epoch(mode, shuffle_cap, batches=120):\n"
        "    with make_reader(url, num_epochs=None, shuffle_row_groups=False,\n"
        "                     reader_pool_type='thread', workers_count=3,\n"
        "                     row_materialization=mode) as r:\n"
        "        with DataLoader(r, batch_size=1024, seed=0,\n"
        "                        shuffling_queue_capacity=shuffle_cap) as dl:\n"
        "            it = iter(dl)\n"
        "            for _ in range(10):\n"
        "                next(it)\n"
        "            t0 = time.perf_counter()\n"
        "            for _ in range(batches):\n"
        "                next(it)\n"
        "            return batches * 1024 / (time.perf_counter() - t0)\n"
        "epoch('eager', 0, batches=30)  # warm-up pays import + fs costs\n"
        "eager, lazy, lazy_shuf = [], [], []\n"
        "for _ in range(2):  # interleaved so host drift hits both modes\n"
        "    eager.append(epoch('eager', 0))\n"
        "    lazy.append(epoch('lazy', 0))\n"
        "    lazy_shuf.append(epoch('lazy', 8192))\n"
        "e, l, ls = max(eager), max(lazy), max(lazy_shuf)\n"
        "print('BENCHJSON:' + json.dumps({'batch_native_epoch': {\n"
        "    'batch_native_eager_samples_per_sec': round(e, 1),\n"
        "    'batch_native_lazy_samples_per_sec': round(l, 1),\n"
        "    'batch_native_lazy_shuffled_samples_per_sec': round(ls, 1),\n"
        "    'lazy_vs_eager': round(l / max(e, 1e-9), 2),\n"
        "    'runs': {'eager': [round(x, 1) for x in eager],\n"
        "             'lazy': [round(x, 1) for x in lazy],\n"
        "             'lazy_shuffled': [round(x, 1) for x in lazy_shuf]}}}))\n")
    try:
        out.update(_cpu_subprocess(batch_native_child, data_dir,
                                   timeout_s=900.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"batch_native phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3a3. deterministic epoch plane (docs/determinism.md): the
    # headline scalar columnar epoch with sample_order='deterministic'
    # (canonical plan + consumer-side reorder gate) vs the default free
    # order, on the thread pool AND the process pool (whose arrival order
    # genuinely differs, so the gate actually re-sequences there).
    # Interleaved best-of-3 per mode; the acceptance bar is ordered-mode
    # overhead <= 15% vs free order on this phase. The absolute rates
    # join tools/bench_compare.py's regression surface via the
    # _samples_per_sec suffix.
    determinism_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "def epoch(pool, order, workers):\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=1,\n"
        "                           shuffle_row_groups=True, seed=0,\n"
        "                           reader_pool_type=pool,\n"
        "                           workers_count=workers,\n"
        "                           sample_order=order) as r:\n"
        "        rows = sum(len(b[0]) for b in r)\n"
        "    return rows / (time.perf_counter() - t0)\n"
        "epoch('thread', 'free', 3)  # warm-up pays import + fs costs\n"
        "rates = {('thread', 'free'): [], ('thread', 'deterministic'): [],\n"
        "         ('process', 'free'): [], ('process', 'deterministic'): []}\n"
        "for _ in range(3):  # interleaved so host drift hits both modes\n"
        "    for pool, workers in (('thread', 3), ('process', 2)):\n"
        "        for order in ('free', 'deterministic'):\n"
        "            rates[(pool, order)].append(epoch(pool, order, workers))\n"
        "result = {}\n"
        "for pool in ('thread', 'process'):\n"
        "    free = max(rates[(pool, 'free')])\n"
        "    ordered = max(rates[(pool, 'deterministic')])\n"
        "    result['free_%s_samples_per_sec' % pool] = round(free, 1)\n"
        "    result['deterministic_%s_samples_per_sec' % pool] = round(ordered, 1)\n"
        "    result['ordered_overhead_pct_%s' % pool] = round(\n"
        "        100.0 * (free - ordered) / max(free, 1e-9), 2)\n"
        "result['within_15pct'] = bool(\n"
        "    result['ordered_overhead_pct_thread'] <= 15.0\n"
        "    and result['ordered_overhead_pct_process'] <= 15.0)\n"
        "# Committed ops-plane gate artifact (make ci-lint runs `telemetry\n"
        "# check --anomaly` over it): one more deterministic epoch with the\n"
        "# timeline sampler on, snapshot taken after close so the terminal\n"
        "# window is in the ring.\n"
        "from petastorm_tpu.telemetry import write_snapshot\n"
        "r = make_batch_reader(url, num_epochs=1, shuffle_row_groups=True,\n"
        "                      seed=0, reader_pool_type='thread',\n"
        "                      workers_count=3,\n"
        "                      sample_order='deterministic',\n"
        "                      timeline_interval_s=0.1)\n"
        "with r:\n"
        "    for _ in r:\n"
        "        pass\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "write_snapshot(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                            'deterministic_epoch.json'),\n"
        "               r.telemetry.snapshot())\n"
        "print('BENCHJSON:' + json.dumps({'deterministic_epoch': result}))\n")
    try:
        out.update(_cpu_subprocess(determinism_child, data_dir,
                                   timeout_s=900.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"deterministic epoch phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3a4. plan fusion (docs/plan.md "Fusion rules"): the fused
    # mask+decode+transform pass vs its unfused twin on a predicate +
    # batched-transform lazy row pipeline — ONE row-group read and ONE
    # predicate-column decode per group instead of two of each. Store:
    # 50k rows in 256-row groups (per-group costs are what fusion
    # halves). A deterministic 0.5 ms injected read latency pins the
    # per-read service floor (same technique as the readahead/what-if
    # phases — page-cached local files undersell a second storage
    # round-trip, and the shared bench host's noise would otherwise
    # swamp the A/B); raw unpinned rates ride along as info. Both modes
    # hash every delivered cell: the fusion is byte-identity-gated, and
    # this phase re-proves it on real data every round. The acceptance
    # bar is fused >= 1.15x unfused (plan_fusion_speedup joins the
    # bench_compare regression surface, as do the absolute rates).
    plan_fusion_child = (
        "import hashlib, json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from petastorm_tpu.codecs import ScalarCodec\n"
        "from petastorm_tpu.etl.writer import materialize_dataset_local\n"
        "from petastorm_tpu.predicates import in_range\n"
        "from petastorm_tpu.reader import make_reader\n"
        "from petastorm_tpu.resilience import FaultPlan, FaultSpec\n"
        "from petastorm_tpu.transform import TransformSpec\n"
        "from petastorm_tpu.unischema import Unischema, UnischemaField\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'planfuse_50k')\n"
        "url = 'file://' + store\n"
        "if not os.path.exists(os.path.join(store, '_common_metadata')):\n"
        "    fields = [UnischemaField('id', np.int64, (), ScalarCodec(np.int64), False)]\n"
        "    fields += [UnischemaField('f%d' % i, np.float32, (),\n"
        "                              ScalarCodec(np.float32), False)\n"
        "               for i in range(8)]\n"
        "    schema = Unischema('PlanFuse', fields)\n"
        "    n, rng = 50_000, np.random.default_rng(0)\n"
        "    rows = [dict({'id': i},\n"
        "                 **{'f%d' % j: np.float32(rng.standard_normal())\n"
        "                    for j in range(8)}) for i in range(n)]\n"
        "    with materialize_dataset_local(url, schema,\n"
        "                                   rows_per_row_group=256,\n"
        "                                   rows_per_file=16384) as w:\n"
        "        w.write_rows(rows)\n"
        "ts = TransformSpec(lambda cols: {**cols, 'f0': cols['f0'] * 2.0},\n"
        "                   batched=True)\n"
        "def epoch(fused, pinned=True):\n"
        "    os.environ['PETASTORM_TPU_PLAN_FUSION'] = '1' if fused else '0'\n"
        "    fp = FaultPlan([FaultSpec(site='rowgroup.read', kind='latency',\n"
        "                              rate=1.0, latency_s=0.0005)], seed=3) \\\n"
        "        if pinned else None\n"
        "    h, n = hashlib.md5(), 0\n"
        "    t0 = time.perf_counter()\n"
        "    with make_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                     reader_pool_type='dummy', fault_plan=fp,\n"
        "                     predicate=in_range('id', 0, 45_000),\n"
        "                     row_materialization='lazy',\n"
        "                     transform_spec=ts) as r:\n"
        "        try:\n"
        "            while True:\n"
        "                b = r.next_batch()\n"
        "                n += b.num_rows\n"
        "                for name in sorted(b.columns):\n"
        "                    h.update(np.ascontiguousarray(\n"
        "                        b.columns[name]).tobytes())\n"
        "        except StopIteration:\n"
        "            pass\n"
        "    return n / (time.perf_counter() - t0), h.hexdigest()\n"
        "epoch(True)  # warm-up pays import + fs costs\n"
        "fused, unfused, hashes = [], [], set()\n"
        "for _ in range(3):  # interleaved so host drift hits both modes\n"
        "    r1, h1 = epoch(True)\n"
        "    r2, h2 = epoch(False)\n"
        "    fused.append(r1); unfused.append(r2)\n"
        "    hashes.update((h1, h2))\n"
        "raw_fused, _ = epoch(True, pinned=False)\n"
        "raw_unfused, _ = epoch(False, pinned=False)\n"
        "f, u = max(fused), max(unfused)\n"
        "print('BENCHJSON:' + json.dumps({'plan_fusion_epoch': {\n"
        "    'plan_fusion_fused_samples_per_sec': round(f, 1),\n"
        "    'plan_fusion_unfused_samples_per_sec': round(u, 1),\n"
        "    'plan_fusion_speedup': round(f / max(u, 1e-9), 3),\n"
        "    'byte_identical': len(hashes) == 1,\n"
        "    'read_latency_pinned_s': 0.0005,\n"
        "    'raw_fused_samples_per_sec': round(raw_fused, 1),\n"
        "    'raw_unfused_samples_per_sec': round(raw_unfused, 1),\n"
        "    'runs': {'fused': [round(x, 1) for x in fused],\n"
        "             'unfused': [round(x, 1) for x in unfused]}}}))\n")
    try:
        out.update(_cpu_subprocess(plan_fusion_child, data_dir,
                                   timeout_s=900.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"plan_fusion phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3a5. plan warm start (docs/plan.md "Plan cache"): the
    # optimizer's persisted-placement loop end to end. Cold: a process-
    # pool reader on the embedding-heavy tensor store (threads measured
    # ~1.5x there in round 8 — placement matters) runs a REAL placement
    # trial (manually ticked controller, migration at the __next__ safe
    # point) and persists the winner keyed by (dataset fingerprint,
    # store type, host). Warm: the identical construction consults the
    # cache, builds the winning pool DIRECTLY, and pins the knob — no
    # trial window in the timeline (asserted from the autotune report)
    # and a lower time-to-first-batch (the skipped spawn+migration).
    # plan_warm_start_speedup (cold/warm TTFB) joins the bench_compare
    # regression surface; the *_ttfb_s keys join its lower-is-better
    # surface.
    plan_warm_child = (
        "import json, os, shutil, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "import pyarrow.parquet as pq\n"
        "from petastorm_tpu.autotune import AutotuneConfig\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "cache_dir = os.path.join(os.environ['PT_BENCH_DATA_DIR'],\n"
        "                         'plan_cache')\n"
        "shutil.rmtree(cache_dir, ignore_errors=True)\n"
        "os.environ['PETASTORM_TPU_PLAN_CACHE'] = cache_dir\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'tensor_50k')\n"
        "if not os.path.exists(os.path.join(store, 'part0.parquet')):\n"
        "    os.makedirs(store, exist_ok=True)\n"
        "    n, rng = 50_000, np.random.default_rng(0)\n"
        "    cols = {'id': np.arange(n, dtype=np.int64)}\n"
        "    cols.update({'f%d' % i: rng.standard_normal(n).astype(np.float32)\n"
        "                 for i in range(8)})\n"
        "    for j in range(2):\n"
        "        flat = rng.standard_normal(n * 64).astype(np.float32)\n"
        "        cols['emb%d' % j] = pa.FixedSizeListArray.from_arrays(\n"
        "            pa.array(flat), 64)\n"
        "    pq.write_table(pa.table(cols), os.path.join(store, 'part0.parquet'),\n"
        "                   row_group_size=2048)\n"
        "url = 'file://' + store\n"
        "def cfg():\n"
        "    return AutotuneConfig(interval_s=3600.0, hysteresis=1,\n"
        "                          cooldown_ticks=0, placement=True,\n"
        "                          placement_settle_ticks=1,\n"
        "                          placement_tolerance=0.15)\n"
        "def run(drive_trial):\n"
        "    t0 = time.perf_counter()\n"
        "    r = make_batch_reader(url, num_epochs=None,\n"
        "                          shuffle_row_groups=False,\n"
        "                          reader_pool_type='process',\n"
        "                          workers_count=2, autotune=True,\n"
        "                          autotune_config=cfg())\n"
        "    with r:\n"
        "        it = iter(r)\n"
        "        next(it)\n"
        "        ttfb = time.perf_counter() - t0\n"
        "        trial_s = None\n"
        "        if drive_trial:\n"
        "            host_bound = r.telemetry.counter('loader.next_host_bound')\n"
        "            for _ in range(3):\n"
        "                next(it)\n"
        "                r.autotune.tick()\n"
        "            t1 = time.perf_counter()\n"
        "            deadline = time.monotonic() + 180.0\n"
        "            while r.autotune.placement_outcome is None \\\n"
        "                    and time.monotonic() < deadline:\n"
        "                next(it)\n"
        "                host_bound.add(5)\n"
        "                r.autotune.tick()\n"
        "            trial_s = time.perf_counter() - t1\n"
        "            for _ in range(50):\n"
        "                next(it)  # run the WINNER: the close-time cache\n"
        "                # refresh persists its measured service times,\n"
        "                # which seed the warm start's roofline\n"
        "        report = r.autotune.report()\n"
        "        return {'ttfb_s': ttfb, 'trial_s': trial_s,\n"
        "                'plan': r.plan_report(),\n"
        "                'pool': r.diagnostics['pool_type'],\n"
        "                'outcome': r.autotune.placement_outcome,\n"
        "                'trial_adjustments': sum(\n"
        "                    1 for a in report['adjustments']\n"
        "                    if a['actuator'] == 'placement')}\n"
        "cold = run(drive_trial=True)\n"
        "assert cold['outcome'] is not None, 'trial never resolved'\n"
        "warm = run(drive_trial=False)\n"
        "result = {\n"
        "    'plan_warm_start_cold_ttfb_s': round(cold['ttfb_s'], 3),\n"
        "    'plan_warm_start_warm_ttfb_s': round(warm['ttfb_s'], 3),\n"
        "    'plan_warm_start_speedup': round(\n"
        "        cold['ttfb_s'] / max(warm['ttfb_s'], 1e-9), 2),\n"
        "    'cold_trial_window_s': round(cold['trial_s'], 2),\n"
        "    'trial_verdict': cold['outcome'],\n"
        "    'winner_pool': warm['pool'],\n"
        "    'warm_plan_source': warm['plan']['source'],\n"
        "    'warm_trial_skipped': warm['trial_adjustments'] == 0\n"
        "        and warm['plan']['source'] == 'persisted',\n"
        "    'warm_ttfb_improved': warm['ttfb_s'] < cold['ttfb_s'],\n"
        "    'capacity_seeds': warm['plan'].get('capacity_seeds', {}),\n"
        "}\n"
        "print('BENCHJSON:' + json.dumps({'plan_warm_start': result}))\n")
    try:
        out.update(_cpu_subprocess(plan_warm_child, data_dir,
                                   timeout_s=900.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"plan_warm_start phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3b. trace-plane overhead (docs/observability.md "Trace
    # plane"): the headline scalar columnar epoch with trace mode OFF vs
    # ON (lineage spans minted at ventilation, decode/fetch spans per row
    # group, raw-span retention). Interleaved off/on rounds; the GATE
    # compares best-of rates (contention noise on a loaded host is
    # one-sided — it can only slow an epoch), with medians reported
    # alongside for the record. Acceptance bar: <= 3% throughput cost
    # with tracing on.
    trace_child = (
        "import json, os, statistics, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "def epoch(traced):\n"
        "    if traced:\n"
        "        os.environ['PETASTORM_TPU_TELEMETRY_TRACE'] = '1'\n"
        "    else:\n"
        "        os.environ.pop('PETASTORM_TPU_TELEMETRY_TRACE', None)\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread',\n"
        "                           workers_count=3) as r:\n"
        "        rows = sum(len(b[0]) for b in r)\n"
        "        spans = len(r.telemetry.recorder.spans())\n"
        "    return rows / (time.perf_counter() - t0), spans\n"
        "epoch(False)  # warm-up pays import + fs metadata costs\n"
        "off, on, spans_on = [], [], 0\n"
        "for _ in range(5):\n"
        "    rate_off, _ = epoch(False)\n"
        "    off.append(rate_off)\n"
        "    rate_on, spans_on = epoch(True)\n"
        "    on.append(rate_on)\n"
        "# Best-of rates: throughput noise on a loaded host is one-sided\n"
        "# (contention only slows an epoch), so max-vs-max isolates the\n"
        "# tracing cost; medians also reported for the record.\n"
        "off_best, on_best = max(off), max(on)\n"
        "overhead = 100.0 * (off_best - on_best) / max(off_best, 1e-9)\n"
        "print('BENCHJSON:' + json.dumps({'trace_overhead_epoch': {\n"
        "    'samples_per_sec_off': round(off_best, 1),\n"
        "    'samples_per_sec_on': round(on_best, 1),\n"
        "    'samples_per_sec_off_p50': round(statistics.median(off), 1),\n"
        "    'samples_per_sec_on_p50': round(statistics.median(on), 1),\n"
        "    'trace_spans_recorded': spans_on,\n"
        "    'overhead_pct': round(overhead, 2),\n"
        "    'within_3pct': bool(overhead <= 3.0)}}))\n")
    try:
        out.update(_cpu_subprocess(trace_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"trace-overhead phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3c. ops-plane overhead + anomaly latency (docs/observability.md
    # "Ops plane"): (a) the headline scalar epoch with the timeline
    # sampler OFF vs ON (windowed rate derivation + anomaly bank per
    # window), interleaved best-of-5, <=3% acceptance like the trace
    # phase; (b) an injected throughput collapse — the consumer stops
    # pulling mid-epoch — asserting the anomaly detector fires within 2
    # timeline windows of the collapse.
    ops_plane_child = (
        "import json, os, statistics, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "def epoch(interval):\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread', workers_count=3,\n"
        "                           timeline_interval_s=interval) as r:\n"
        "        rows = sum(len(b[0]) for b in r)\n"
        "    elapsed = time.perf_counter() - t0\n"
        "    # After close: the sampler's stop took the terminal window.\n"
        "    windows = len(r.timeline_report().get('windows', []))\n"
        "    return rows / elapsed, windows\n"
        "epoch(None)  # warm-up pays import + fs metadata costs\n"
        "off, on, windows_on = [], [], 0\n"
        "for _ in range(5):\n"
        "    rate_off, _ = epoch(None)\n"
        "    off.append(rate_off)\n"
        "    rate_on, windows_on = epoch(0.25)\n"
        "    on.append(rate_on)\n"
        "off_best, on_best = max(off), max(on)\n"
        "overhead = 100.0 * (off_best - on_best) / max(off_best, 1e-9)\n"
        "# (b) seeded throughput collapse: pull at full rate for 12\n"
        "# windows, then park the consumer; the EWMA collapse detector\n"
        "# must fire within 2 windows of the rate cliff.\n"
        "W = 0.1\n"
        "with make_batch_reader(url, num_epochs=None,\n"
        "                       shuffle_row_groups=False,\n"
        "                       reader_pool_type='thread', workers_count=3,\n"
        "                       timeline_interval_s=W) as r:\n"
        "    it = iter(r)\n"
        "    t0 = time.perf_counter()\n"
        "    while time.perf_counter() - t0 < 12 * W:\n"
        "        next(it)\n"
        "    stall_start = len(r.timeline_report().get('windows', []))\n"
        "    time.sleep(6 * W)  # consumer parked: rows/s cliff\n"
        "    rep = r.anomaly_report()\n"
        "collapses = [d for d in rep.get('detections', [])\n"
        "             if 'collapse' in d['rule'] and d['window'] >= stall_start]\n"
        "fired_after = (min(d['window'] for d in collapses) - stall_start\n"
        "               if collapses else None)\n"
        "print('BENCHJSON:' + json.dumps({'ops_plane_epoch': {\n"
        "    'samples_per_sec_off': round(off_best, 1),\n"
        "    'samples_per_sec_on': round(on_best, 1),\n"
        "    'samples_per_sec_off_p50': round(statistics.median(off), 1),\n"
        "    'samples_per_sec_on_p50': round(statistics.median(on), 1),\n"
        "    'timeline_windows': windows_on,\n"
        "    'overhead_pct': round(overhead, 2),\n"
        "    'within_3pct': bool(overhead <= 3.0),\n"
        "    'collapse_detected_after_windows': fired_after,\n"
        "    'anomaly_within_2_windows': bool(\n"
        "        fired_after is not None and fired_after <= 2)}}))\n")
    try:
        out.update(_cpu_subprocess(ops_plane_child, data_dir,
                                   timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"ops-plane phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3c2. data-quality plane (docs/observability.md "Data quality
    # plane"): (a) the headline scalar epoch with quality profiling OFF vs
    # ON (streaming per-column profiles under the default adaptive duty
    # cycle + lazy drift scoring against a reference), off/on/off
    # interleaved best-of-5 — the off halves straddling each on sample
    # yield the phase's own off-vs-off noise floor, and acceptance is
    # overhead <= max(3%, noise floor), the same measured-noise gate the
    # explain phase uses (on the loaded dev host wall-clock A/B noise
    # dwarfs the throttled true cost); (b) injected drift — a
    # deliberately shifted file appended to a live store must be scored
    # against the reference and detected within ONE poll interval of
    # admission (the score comes from the validation footer, before any
    # bytes are decoded); (c) a faulted deterministic epoch (quarantine
    # skip + worker kill) whose coverage manifest must reconcile to
    # exactly-once. The quality-on snapshot persists as
    # bench_snapshots/quality_epoch.json so `make ci-lint` replays
    # `telemetry check --slo "quality.max_drift<=0.2"` over it — a
    # shipped drift-scoring regression fails the BUILD.
    quality_child = (
        "import json, os, shutil, statistics, time\n"
        "import numpy as np\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import pyarrow as pa, pyarrow.parquet as pq\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.quality import DatasetProfile, save_profile\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "tmp = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'quality_tmp')\n"
        "shutil.rmtree(tmp, ignore_errors=True)  # stale live stores poison the base listing\n"
        "os.makedirs(tmp, exist_ok=True)\n"
        "# Reference profile: one profiling pass over the store.\n"
        "with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                       reader_pool_type='thread', workers_count=3,\n"
        "                       quality=True) as r:\n"
        "    for _ in r: pass\n"
        "    ref_prof = DatasetProfile.from_dict(\n"
        "        r.quality_report()['profile'])\n"
        "ref_path = os.path.join(tmp, 'reference.json')\n"
        "save_profile(ref_prof, ref_path)\n"
        "snap_on = None\n"
        "def epoch(quality):\n"
        "    global snap_on\n"
        "    t0 = time.perf_counter()\n"
        "    # num_epochs=6 amortizes the adaptive throttle's fully-profiled\n"
        "    # warm-up units over a wall time the 3 pct bar is meaningful on\n"
        "    # (a single 160 ms epoch is all warm-up).\n"
        "    with make_batch_reader(url, num_epochs=6, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread', workers_count=3,\n"
        "                           quality=quality,\n"
        "                           reference_profile=(ref_path if quality\n"
        "                                              else None)) as r:\n"
        "        rows = sum(len(b[0]) for b in r)\n"
        "        if quality:\n"
        "            snap_on = r.telemetry.snapshot()\n"
        "    return rows / (time.perf_counter() - t0)\n"
        "epoch(False)  # warm-up pays import + fs metadata costs\n"
        "off_a, off_b, on = [], [], []\n"
        "for _ in range(5):\n"
        "    off_a.append(epoch(False))\n"
        "    on.append(epoch(True))\n"
        "    off_b.append(epoch(False))\n"
        "off = off_a + off_b\n"
        "off_best, on_best = max(off), max(on)\n"
        "overhead = 100.0 * (off_best - on_best) / max(off_best, 1e-9)\n"
        "# p50-preferring comparison (the bench_compare discipline: the\n"
        "# best-of estimator keys on one lucky epoch) + the off-vs-off\n"
        "# noise floor from the straddling off halves.\n"
        "off_p50 = statistics.median(off)\n"
        "overhead_p50 = 100.0 * (off_p50 - statistics.median(on)) \\\n"
        "    / max(off_p50, 1e-9)\n"
        "noise_floor = 100.0 * abs(statistics.median(off_a)\n"
        "                          - statistics.median(off_b)) \\\n"
        "    / max(off_p50, 1e-9)\n"
        "from petastorm_tpu.telemetry import write_snapshot\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "write_snapshot(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                            'quality_epoch.json'), snap_on)\n"
        "clean_max_drift = snap_on['gauges'].get('quality.max_drift')\n"
        "# (b) injected drift on a live appending store: detection must\n"
        "# land within ONE poll interval of the append.\n"
        "live = os.path.join(tmp, 'live_store')\n"
        "os.makedirs(live, exist_ok=True)\n"
        "def write_file(name, mean):\n"
        "    rng = np.random.RandomState(hash(name) % (2**31))\n"
        "    # Atomic publish: write under an underscore name (listings\n"
        "    # skip those) and rename, so a poll can never see a torn file.\n"
        "    staging = os.path.join(live, '_' + name)\n"
        "    pq.write_table(pa.table(\n"
        "        {'id': pa.array(np.arange(2000)),\n"
        "         'val': pa.array(rng.normal(mean, 1.0, 2000))}),\n"
        "        staging, row_group_size=500)\n"
        "    os.replace(staging, os.path.join(live, name))\n"
        "write_file('base_a.parquet', 0.0)\n"
        "write_file('base_b.parquet', 0.0)\n"
        "POLL = 0.25\n"
        "with make_batch_reader('file://' + live, quality=True,\n"
        "                       num_epochs=None, shuffle_row_groups=False,\n"
        "                       reader_pool_type='thread', workers_count=1,\n"
        "                       refresh_interval_s=POLL) as r:\n"
        "    it = iter(r)\n"
        "    for _ in range(8):\n"
        "        next(it)  # profile the base files (the live baseline)\n"
        "    write_file('drifted.parquet', 50.0)\n"
        "    t_append = time.perf_counter()\n"
        "    detect_lag = None\n"
        "    while time.perf_counter() - t_append < 10 * POLL:\n"
        "        if r.telemetry.peek_counter(\n"
        "                'quality.admission.drift_detections_total'):\n"
        "            detect_lag = time.perf_counter() - t_append\n"
        "            break\n"
        "        time.sleep(POLL / 20)\n"
        "    admission_score = r.telemetry.peek_gauge(\n"
        "        'quality.admission.max_drift')\n"
        "# Detection must land within one poll interval of the append\n"
        "# (plus one validation pass of slack on a loaded host).\n"
        "drift_ok = detect_lag is not None and detect_lag <= 2 * POLL\n"
        "# (c) faulted deterministic epoch: quarantine skip + worker kill\n"
        "# -> the coverage manifest reconciles to exactly-once.\n"
        "from petastorm_tpu.resilience import FaultPlan, FaultSpec\n"
        "fp = FaultPlan([\n"
        "    FaultSpec(site='rowgroup.read', kind='corruption', rate=1.0,\n"
        "              times=50, key_substring='base_a'),\n"
        "    FaultSpec(site='worker.item', kind='worker_kill', at=2,\n"
        "              worker=0)])\n"
        "with make_batch_reader('file://' + live, quality=True,\n"
        "                       sample_order='deterministic', seed=11,\n"
        "                       shuffle_row_groups=True,\n"
        "                       reader_pool_type='process', workers_count=2,\n"
        "                       degraded_mode=True, worker_crash_budget=1,\n"
        "                       fault_plan=fp, num_epochs=1) as r:\n"
        "    rows = sum(len(b[0]) for b in r)\n"
        "    manifest = r.quality_report()['coverage']['epochs'][0]\n"
        "print('BENCHJSON:' + json.dumps({'quality_epoch': {\n"
        "    'samples_per_sec_off': round(off_best, 1),\n"
        "    'samples_per_sec_on': round(on_best, 1),\n"
        "    'samples_per_sec_off_p50': round(statistics.median(off), 1),\n"
        "    'samples_per_sec_on_p50': round(statistics.median(on), 1),\n"
        "    'overhead_pct': round(overhead, 2),\n"
        "    'overhead_p50_pct': round(overhead_p50, 2),\n"
        "    'noise_floor_pct': round(noise_floor, 2),\n"
        "    'within_3pct': bool(overhead_p50 <= max(3.0, noise_floor)),\n"
        "    'clean_max_drift': clean_max_drift,\n"
        "    'poll_interval_s': POLL,\n"
        "    'drift_detect_lag_s': (round(detect_lag, 3)\n"
        "                           if detect_lag is not None else None),\n"
        "    'drift_admission_score': admission_score,\n"
        "    'drift_within_one_poll': bool(drift_ok),\n"
        "    'faulted_rows': rows,\n"
        "    'coverage_manifest': manifest,\n"
        "    'coverage_reconciled': bool(manifest['reconciled'])}}))\n")
    try:
        out.update(_cpu_subprocess(quality_child, data_dir,
                                   timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"quality phase failed: {e!r}", file=sys.stderr)

    # ---- 4f3d. explain plane (docs/observability.md "Explain plane"):
    # (a) profiled-explain overhead — the headline scalar epoch (x3 per
    # sample, amortizing pool spin-up) plain vs calling
    # Reader.explain(profiled=True) every 10 batches plus a final
    # explain_report(), interleaved off/on/off best-of-7; the off halves
    # straddling each on sample also yield the phase's own off-vs-off
    # noise floor, and acceptance is overhead <= max(3%, noise floor) —
    # the same measured-noise gate the cross-run regression comparator
    # uses, because on a loaded host the wall-clock A/B noise dwarfs the
    # sub-1% true explain cost; (b) what-if validation — two real knob flips
    # under a deterministic injected 12 ms read latency (the injected
    # sleep pins per-group service time, so the roofline projection has a
    # stable target): decode_parallelism 1->3 and readahead_depth 1->8
    # (fetchers 1->2), each measured and compared against the calibrated
    # projection's documented 40% error band. The profiled graph +
    # projections persist as the per-phase explain artifact
    # (bench_snapshots/explain_epoch.json) so the perf trajectory carries
    # operator-level provenance.
    explain_child = (
        "import json, os, statistics, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.explain import WHATIF_ERROR_BAND_PCT, project\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.resilience import FaultPlan, FaultSpec\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "def epoch(explained):\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=3, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread',\n"
        "                           workers_count=3) as r:\n"
        "        rows = n = 0\n"
        "        for b in r:\n"
        "            rows += len(b[0]); n += 1\n"
        "            if explained and n % 10 == 0:\n"
        "                r.explain(profiled=True)\n"
        "        report = r.explain_report() if explained else None\n"
        "    return rows / (time.perf_counter() - t0), report\n"
        "epoch(False)  # warm-up pays import + fs metadata costs\n"
        "off_a, off_b, on, report = [], [], [], None\n"
        "for _ in range(7):\n"
        "    off_a.append(epoch(False)[0])\n"
        "    rate_on, report = epoch(True)\n"
        "    on.append(rate_on)\n"
        "    off_b.append(epoch(False)[0])\n"
        "off = off_a + off_b\n"
        "off_best, on_best = max(off), max(on)\n"
        "overhead = 100.0 * (off_best - on_best) / max(off_best, 1e-9)\n"
        "# off-vs-off noise floor: the two off halves straddle every on\n"
        "# sample, so their best-vs-best gap is what this host's scheduler\n"
        "# noise alone produces under this exact estimator.\n"
        "noise_floor = (100.0 * abs(max(off_a) - max(off_b))\n"
        "               / max(off_best, 1e-9))\n"
        "# (b) what-if validation: injected-latency epochs (deterministic\n"
        "# per-group service time -> a stable projection target).\n"
        "def plan():\n"
        "    return FaultPlan([FaultSpec(site='rowgroup.read',\n"
        "                                kind='latency', rate=1.0,\n"
        "                                latency_s=0.012)], seed=7)\n"
        "def one_fault_epoch(workers, depth=None):\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread',\n"
        "                           workers_count=workers, fault_plan=plan(),\n"
        "                           readahead_depth=depth) as r:\n"
        "        rows = sum(len(b[0]) for b in r)\n"
        "        rep = r.explain_report()\n"
        "    return rows / (time.perf_counter() - t0), rep\n"
        "def fault_epoch(workers, depth=None):\n"
        "    # Best-of-3: the injected latency pins the service-time floor,\n"
        "    # so the fastest epoch is the least noise-polluted sample (rate\n"
        "    # and report stay a consistent pair).\n"
        "    runs = [one_fault_epoch(workers, depth) for _ in range(3)]\n"
        "    return max(runs, key=lambda rr: rr[0])\n"
        "base_w1, spec_w1 = fault_epoch(1)\n"
        "proj_w = project(spec_w1, observed_rows_per_s=base_w1,\n"
        "                 decode_parallelism=3)\n"
        "meas_w3, _ = fault_epoch(3)\n"
        "err_workers = 100.0 * abs(proj_w['projected']['rows_per_s']\n"
        "                          - meas_w3) / max(meas_w3, 1e-9)\n"
        "base_d1, spec_d1 = fault_epoch(2, depth=1)\n"
        "proj_r = project(spec_d1, observed_rows_per_s=base_d1,\n"
        "                 readahead_depth=8)\n"
        "meas_d8, _ = fault_epoch(2, depth=8)\n"
        "err_ra = 100.0 * abs(proj_r['projected']['rows_per_s']\n"
        "                     - meas_d8) / max(meas_d8, 1e-9)\n"
        "# Per-phase explain artifact: operator-level provenance rides the\n"
        "# perf trajectory next to the ops-plane gate snapshots.\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "with open(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                       'explain_epoch.json'), 'w') as f:\n"
        "    json.dump({'explain': report,\n"
        "               'whatif': {\n"
        "                   'decode_parallelism': {\n"
        "                       'projection': proj_w,\n"
        "                       'observed_rows_per_s': round(base_w1, 1),\n"
        "                       'measured_rows_per_s': round(meas_w3, 1)},\n"
        "                   'readahead_depth': {\n"
        "                       'projection': proj_r,\n"
        "                       'observed_rows_per_s': round(base_d1, 1),\n"
        "                       'measured_rows_per_s': round(meas_d8, 1)}}},\n"
        "              f, indent=2, sort_keys=True)\n"
        "band = WHATIF_ERROR_BAND_PCT\n"
        "print('BENCHJSON:' + json.dumps({'explain_overhead_epoch': {\n"
        "    'samples_per_sec_off': round(off_best, 1),\n"
        "    'samples_per_sec_on': round(on_best, 1),\n"
        "    'samples_per_sec_off_p50': round(statistics.median(off), 1),\n"
        "    'samples_per_sec_on_p50': round(statistics.median(on), 1),\n"
        "    'overhead_pct': round(overhead, 2),\n"
        "    'noise_floor_pct': round(noise_floor, 2),\n"
        "    'within_3pct': bool(overhead <= max(3.0, noise_floor)),\n"
        "    'bottleneck': (report.get('profile', {}).get('bottleneck')\n"
        "                   or {}).get('operator'),\n"
        "    'whatif_workers_projected': round(\n"
        "        proj_w['projected']['rows_per_s'], 1),\n"
        "    'whatif_workers_measured': round(meas_w3, 1),\n"
        "    'whatif_workers_error_pct': round(err_workers, 1),\n"
        "    'whatif_workers_within_band': bool(err_workers <= band),\n"
        "    'whatif_readahead_projected': round(\n"
        "        proj_r['projected']['rows_per_s'], 1),\n"
        "    'whatif_readahead_measured': round(meas_d8, 1),\n"
        "    'whatif_readahead_error_pct': round(err_ra, 1),\n"
        "    'whatif_readahead_within_band': bool(err_ra <= band),\n"
        "    'error_band_pct': band}}))\n")
    try:
        out.update(_cpu_subprocess(explain_child, data_dir,
                                   timeout_s=900.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"explain phase failed: {e!r}", file=sys.stderr)

    # ---- 4f4. multi-host mesh ingestion (docs/mesh.md): one logical
    # dataset -> one globally sharded jax.Array per step, on the 8-device
    # CPU simulation (XLA_FLAGS=--xla_force_host_platform_device_count=8,
    # 8 simulated hosts each reading a disjoint row-group shard through
    # its own reader). Reports aggregate samples/sec, the consumer-side
    # input_stall_pct derived gauge, and the per-host stall fractions +
    # fastest-vs-slowest skew from mesh_report() — the <1%-stall
    # acceptance surface for ROADMAP item 1, measurable without hardware.
    mesh_child = (
        "import json, os, time\n"
        "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') +\n"
        "    ' --xla_force_host_platform_device_count=8')\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.jax import MeshDataLoader, MeshReaderFactory\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "factory = MeshReaderFactory(url, batched=True)\n"
        "def epoch(step_s):\n"
        "    rows, t0 = 0, time.perf_counter()\n"
        "    with MeshDataLoader(factory, batch_size=2048, seed=0,\n"
        "                        num_epochs=1) as loader:\n"
        "        for batch in loader:\n"
        "            rows += next(iter(batch.values())).shape[0]\n"
        "            if step_s:\n"
        "                time.sleep(step_s)\n"
        "        rep = loader.mesh_report()\n"
        "        stall_gauge = loader.telemetry.snapshot()['gauges'].get(\n"
        "            'loader.input_stall_pct')\n"
        "    return rows, time.perf_counter() - t0, rep, stall_gauge\n"
        "epoch(0)  # warm-up pays import + per-host fs metadata costs\n"
        "rows, elapsed, rep, _ = epoch(0)  # max-rate drain: throughput\n"
        "# Stall is only meaningful against a device step (a drain loop is\n"
        "# 100% wait by construction): re-run against a 10ms emulated step,\n"
        "# same spirit as the 4b stall sweep's wall-clock-calibrated steps.\n"
        "_, _, rep_step, stall_gauge = epoch(0.01)\n"
        "print('BENCHJSON:' + json.dumps({'mesh_ingest_epoch': {\n"
        "    'mesh_ingest_samples_per_sec': round(rows / elapsed, 1),\n"
        "    'rows': rows,\n"
        "    'devices': 8,\n"
        "    'hosts': rep['hosts'],\n"
        "    'emulated_step_ms': 10,\n"
        "    'input_stall_pct': stall_gauge,\n"
        "    'per_host_input_stall_pct': {h: v['input_stall_pct']\n"
        "                                 for h, v\n"
        "                                 in rep_step['per_host'].items()},\n"
        "    'host_skew_s': rep_step['host_skew_s'],\n"
        "    'reshard_events': rep['reshard_events']\n"
        "                      + rep_step['reshard_events']}}))\n")
    try:
        out.update(_cpu_subprocess(mesh_child, data_dir, timeout_s=900.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"mesh ingest phase failed: {e!r}", file=sys.stderr)

    # ---- 4g. autotune feedback loop (docs/autotune.md): the columnar
    # loader epoch from 4d, with the controller live on a fast tick.
    # Reports the tick/verdict counters, every adjustment it made, and the
    # final actuator values — the convergence evidence (history stops
    # growing) next to the throughput it tuned.
    autotune_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.autotune import AutotuneConfig\n"
        "from petastorm_tpu.jax import BatchedDataLoader\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "cfg = AutotuneConfig(interval_s=0.05)\n"
        "t0 = time.perf_counter()\n"
        "with make_batch_reader(url, num_epochs=None, shuffle_row_groups=False,\n"
        "                       reader_pool_type='thread', workers_count=3,\n"
        "                       autotune=True, autotune_config=cfg) as reader:\n"
        "    with BatchedDataLoader(reader, batch_size=1024,\n"
        "                           shuffling_queue_capacity=8192,\n"
        "                           seed=0) as loader:\n"
        "        it = iter(loader)\n"
        "        for _ in range(200):\n"
        "            next(it)\n"
        "    report = reader.autotune_report()\n"
        "    counters = reader.telemetry.snapshot()['counters']\n"
        "elapsed = time.perf_counter() - t0\n"
        "verdicts = {k.split('autotune.verdict_', 1)[1]: v\n"
        "            for k, v in counters.items()\n"
        "            if k.startswith('autotune.verdict_') and v}\n"
        "print('BENCHJSON:' + json.dumps({'autotune_epoch': {\n"
        "    'samples_per_sec': round(200 * 1024 / elapsed, 1),\n"
        "    'ticks': report['ticks'],\n"
        "    'verdicts': verdicts,\n"
        "    'adjustments': report['adjustments'],\n"
        "    'final_actuators': {k: v['value']\n"
        "                        for k, v in report['actuators'].items()}}}))\n")
    try:
        out.update(_cpu_subprocess(autotune_child, data_dir, timeout_s=900.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"autotune phase failed: {e!r}", file=sys.stderr)

    # ---- 4h. live appending dataset (docs/live_data.md): one static +
    # one growing source. A writer thread appends parquet files while the
    # reader serves with refresh_interval_s polling under an injected
    # 10ms-latency fault on every listing; reports steady samples/sec,
    # files appended vs admitted, and the freshness numbers — the
    # acceptance bar is max per-file admission lag <= 2 poll intervals.
    livedata_child = (
        "import json, os, shutil, threading, time\n"
        "import numpy as np, pyarrow as pa, pyarrow.parquet as pq\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.resilience import FaultPlan, FaultSpec\n"
        "root = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'live_append')\n"
        "shutil.rmtree(root, ignore_errors=True)\n"
        "os.makedirs(root)\n"
        "def write_file(idx, rows=20000):\n"
        "    start = idx * rows\n"
        "    pq.write_table(pa.table({\n"
        "        'id': pa.array(np.arange(start, start + rows)),\n"
        "        'val': pa.array(np.arange(rows, dtype=np.float64))}),\n"
        "        os.path.join(root, f'part-{idx:05d}.parquet'),\n"
        "        row_group_size=2000)\n"
        "write_file(0); write_file(1)\n"
        "POLL_S, APPEND_S, APPENDS, RUN_S = 0.25, 0.4, 8, 8.0\n"
        "stop = threading.Event()\n"
        "def producer():\n"
        "    for i in range(2, 2 + APPENDS):\n"
        "        if stop.wait(APPEND_S):\n"
        "            return\n"
        "        write_file(i)\n"
        "threading.Thread(target=producer, daemon=True).start()\n"
        "plan = FaultPlan([FaultSpec('discovery.list', 'latency', rate=1.0,\n"
        "                            latency_s=0.010, times=None)], seed=0)\n"
        "rows, t0 = 0, time.perf_counter()\n"
        "with make_batch_reader('file://' + root, reader_pool_type='thread',\n"
        "                       workers_count=3, num_epochs=None,\n"
        "                       shuffle_row_groups=False, fault_plan=plan,\n"
        "                       refresh_interval_s=POLL_S,\n"
        "                       timeline_interval_s=0.25) as reader:\n"
        "    for batch in reader:\n"
        "        rows += len(batch.id)\n"
        "        if time.perf_counter() - t0 > RUN_S:\n"
        "            break\n"
        "    elapsed = time.perf_counter() - t0\n"
        "    rep = reader.dataset_growth_report()\n"
        "    snap = reader.telemetry.snapshot()\n"
        "stop.set()\n"
        "# Committed ops-plane gate artifact: the snapshot (with its live\n"
        "# timeline ring + ingest-lag gauges) make ci-lint SLO/anomaly-\n"
        "# checks against.\n"
        "from petastorm_tpu.telemetry import write_snapshot\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "write_snapshot(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                            'appending_epoch.json'),\n"
        "               reader.telemetry.snapshot())\n"
        "disc = rep['discovery']\n"
        "lag = disc['max_admission_lag_s']\n"
        "print('BENCHJSON:' + json.dumps({'appending_epoch': {\n"
        "    'appending_epoch_samples_per_sec': round(rows / elapsed, 1),\n"
        "    'rows': rows,\n"
        "    'poll_interval_s': POLL_S,\n"
        "    'files_appended': APPENDS,\n"
        "    'files_admitted': len(disc['admissions']),\n"
        "    'growth_batches_applied': len(rep['applied']),\n"
        "    'list_latency_fault_ms': 10,\n"
        "    'list_retries_total': snap['counters'].get(\n"
        "        'discovery.list_retries_total', 0),\n"
        "    'ingest_lag_s': round(snap['gauges'].get(\n"
        "        'discovery.ingest_lag_s', 0.0), 3),\n"
        "    'max_admission_lag_s': lag,\n"
        "    'lag_bound_s': 2 * POLL_S,\n"
        "    'lag_ok': bool(lag <= 2 * POLL_S)}}))\n")
    try:
        out.update(_cpu_subprocess(livedata_child, data_dir, timeout_s=300.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"appending-epoch phase failed: {e!r}", file=sys.stderr)

    # ---- 4i. telemetry fabric (docs/observability.md "Telemetry
    # fabric"): (a) the headline scalar epoch with telemetry_publish OFF
    # vs ON against a live aggregator, interleaved best-of-5, <=3%
    # acceptance like the trace/ops-plane phases; (b) a 3-publisher
    # fleet on a second aggregator — the fleet snapshot is flushed while
    # all members are live (the committed `make ci-lint` anomaly-gate
    # artifact), then one publisher is killed without a bye and the
    # member-silence detection must land within 2 heartbeat intervals,
    # with the surviving fleet totals exactly matching member ground
    # truth.
    fleet_child = (
        "import json, os, threading, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.telemetry import TelemetryRegistry\n"
        "from petastorm_tpu.telemetry.fabric import (TelemetryAggregator,\n"
        "                                            TelemetryPublisher)\n"
        "url = 'file://' + os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'scalar_100k')\n"
        "addr_a = 'ipc:///tmp/pt-bench-fabric-a-%d' % os.getpid()\n"
        "# Not start()ed: in production the aggregator runs on another\n"
        "# machine, so on the 1-core bench host its poll loop must not be\n"
        "# billed to the pipeline. Publisher sends land in the ZMQ buffer\n"
        "# (hello + <=1 window + bye per sample, far under the HWM) and are\n"
        "# drained between samples; only the publisher's own cost — thread\n"
        "# plus window build/ship — is inside the timed region.\n"
        "agg_a = TelemetryAggregator(addr_a, interval_s=0.25)\n"
        "def drain():\n"
        "    while agg_a.poll_once(0.05):\n"
        "        pass\n"
        "def epoch(publish):\n"
        "    # 10 epochs per sample: the publisher's fixed setup (socket\n"
        "    # connect + thread start, ~ms) must amortize like it does in a\n"
        "    # real training run, not dominate an ~80ms scalar epoch.\n"
        "    t0 = time.perf_counter()\n"
        "    with make_batch_reader(url, num_epochs=10, shuffle_row_groups=False,\n"
        "                           reader_pool_type='thread', workers_count=3,\n"
        "                           telemetry_publish=(addr_a if publish else None),\n"
        "                           tenant='bench') as r:\n"
        "        rows = sum(len(b[0]) for b in r)\n"
        "    return rows / (time.perf_counter() - t0)\n"
        "epoch(False)  # warm-up pays import + fs metadata costs\n"
        "off, on = [], []\n"
        "for _ in range(5):\n"
        "    off.append(epoch(False))\n"
        "    on.append(epoch(True))\n"
        "    drain()\n"
        "agg_a.stop()\n"
        "off_best, on_best = max(off), max(on)\n"
        "overhead = 100.0 * (off_best - on_best) / max(off_best, 1e-9)\n"
        "# (b) live 3-publisher fleet; flush the gate artifact while\n"
        "# healthy, then kill h0 without a bye.\n"
        "HB = 0.4\n"
        "addr_b = 'ipc:///tmp/pt-bench-fabric-b-%d' % os.getpid()\n"
        "agg_b = TelemetryAggregator(addr_b, interval_s=0.25).start()\n"
        "regs = [TelemetryRegistry() for _ in range(3)]\n"
        "pubs = [TelemetryPublisher(regs[i], addr_b, member='h%d' % i,\n"
        "                           tenant='t%d' % (i % 2),\n"
        "                           interval_s=HB).start() for i in range(3)]\n"
        "truth, stop = [0, 0, 0], threading.Event()\n"
        "def churn():\n"
        "    while not stop.is_set():\n"
        "        for i, reg in enumerate(regs):\n"
        "            reg.counter('reader.rows').add(13)\n"
        "            truth[i] += 13\n"
        "        time.sleep(0.02)\n"
        "t = threading.Thread(target=churn); t.start()\n"
        "time.sleep(10 * HB / 2)  # ~8 aggregate windows of steady rates\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "agg_b.flush(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                         'fleet_telemetry_epoch.json'))\n"
        "stop.set(); t.join()\n"
        "pubs[0].publish_once()  # deterministic final state for h0\n"
        "pubs[0]._stop.set(); pubs[0]._thread.join(); pubs[0]._thread = None\n"
        "det, deadline = None, time.perf_counter() + 6 * HB\n"
        "while det is None and time.perf_counter() < deadline:\n"
        "    evs = agg_b.registry.events().get('anomaly.member_silent')\n"
        "    if evs:\n"
        "        det = evs[-1]['payload']\n"
        "    time.sleep(0.05)\n"
        "for p in pubs[1:]:\n"
        "    p.stop()  # graceful byes carry the survivors' final totals\n"
        "deadline = time.perf_counter() + 3.0\n"
        "fleet_rows = 0.0\n"
        "while time.perf_counter() < deadline:\n"
        "    fleet_rows = agg_b.registry.metrics_view()['counters'].get(\n"
        "        'reader.rows', 0.0)\n"
        "    if fleet_rows >= sum(truth):\n"
        "        break\n"
        "    time.sleep(0.05)\n"
        "agg_b.stop()\n"
        "print('BENCHJSON:' + json.dumps({'fleet_telemetry_epoch': {\n"
        "    'samples_per_sec_off': round(off_best, 1),\n"
        "    'samples_per_sec_on': round(on_best, 1),\n"
        "    'overhead_pct': round(overhead, 2),\n"
        "    'within_3pct': bool(overhead <= 3.0),\n"
        "    'fleet_members': 3,\n"
        "    'heartbeat_s': HB,\n"
        "    'silence_detected': bool(det is not None),\n"
        "    'silence_quiet_s': (None if det is None\n"
        "                        else round(det['quiet_s'], 3)),\n"
        "    'silence_within_2_heartbeats': bool(\n"
        "        det is not None and det['quiet_s'] <= 2 * HB),\n"
        "    'fleet_rows': fleet_rows,\n"
        "    'fleet_rows_expected': float(sum(truth)),\n"
        "    'fleet_rows_exact': bool(fleet_rows == float(sum(truth)))}}))\n")
    try:
        out.update(_cpu_subprocess(fleet_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"fleet-telemetry phase failed: {e!r}", file=sys.stderr)

    # ---- 4j. data-service mode (docs/service.md): 1 dispatcher + 4 local
    # decode servers feeding 4 concurrent clients (2 tenants, weights 3:1
    # over the same dataset) vs one local deterministic reader. The fleet's
    # aggregate samples/s must clear 1.5x the local reader — on this 1-core
    # host the win comes from the servers' serialized-Arrow buffer cache
    # plus the dispatcher's stripe-affinity routing (a row group is decoded
    # once at its owning server, then served as a memcpy to every
    # tenant/epoch/client that replays it). The workload is the wide
    # ``service_wide`` store (192 float64 columns, zstd) where the parquet
    # decode the cache elides dominates the Arrow-IPC serve that remains —
    # the disaggregation trade the paper's data-service mode is built
    # around. Also measured: per-tenant draw
    # shares at the moment the heavy tenant finishes (fair-share within 10%
    # of the 3:1 weights), and a kill-one-client determinism check — a
    # client dies mid-lease, the range folds back, and the survivor's
    # stream must stay byte-identical to the local reference
    # (`deterministic_ok`). The dispatcher registry snapshot is flushed to
    # bench_snapshots/data_service_epoch.json, the `make ci-lint`
    # exactly-once SLO gate artifact.
    service_child = (
        "import json, os, threading, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "import pyarrow.parquet as pq\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.service import (Dispatcher, DecodeServer,\n"
        "                                   ServiceJobSpec,\n"
        "                                   make_service_reader)\n"
        "path = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'service_wide')\n"
        "url = 'file://' + path\n"
        "if not os.path.exists(os.path.join(path, 'part0.parquet')):\n"
        "    # Wide decode-heavy store: 24 row groups x 8192 rows x 768 narrow\n"
        "    # int16 columns, zstd -- per-column-chunk parquet decode dominates\n"
        "    # the Arrow-IPC serve bytes, the regime the decode-server cache\n"
        "    # targets (feature-store style tables).\n"
        "    os.makedirs(path, exist_ok=True)\n"
        "    rng = np.random.default_rng(7)\n"
        "    nrows = 24 * 8192\n"
        "    cols = {'f000': np.arange(nrows, dtype=np.float64)}\n"
        "    for i in range(1, 768):\n"
        "        cols['f%03d' % i] = rng.integers(0, 512, nrows).astype(np.int16)\n"
        "    pq.write_table(pa.table(cols), os.path.join(path, 'part0.parquet'),\n"
        "                   row_group_size=8192, compression='zstd')\n"
        "    del cols\n"
        "SEED, EPOCHS, pid = 411, 6, os.getpid()\n"
        "RK = {'reader_pool_type': 'thread', 'workers_count': 3}\n"
        "\n"
        "def local_run(num_epochs):\n"
        "    rows, t0 = 0, time.perf_counter()\n"
        "    with make_batch_reader(url, shuffle_row_groups=True, seed=SEED,\n"
        "                           num_epochs=num_epochs,\n"
        "                           sample_order='deterministic', **RK) as r:\n"
        "        for b in r:\n"
        "            rows += len(b[0])\n"
        "    return rows, time.perf_counter() - t0\n"
        "\n"
        "local_run(1)  # warm-up pays one-time import + fs metadata costs\n"
        "lrows, lsec = local_run(EPOCHS)\n"
        "local_sps = lrows / lsec\n"
        "daddr = 'ipc:///tmp/pt-bsvc-d-%d' % pid\n"
        "saddrs = ['ipc:///tmp/pt-bsvc-%d-%d' % (i, pid) for i in range(4)]\n"
        "\n"
        "def mkjobs(num_epochs, chunk=4, tenants=('a', 'b')):\n"
        "    return [ServiceJobSpec('job-a', url, tenant=tenants[0], seed=SEED,\n"
        "                           num_epochs=num_epochs, chunk=chunk,\n"
        "                           reader_kwargs=RK),\n"
        "            ServiceJobSpec('job-b', url, tenant=tenants[1], seed=SEED,\n"
        "                           num_epochs=num_epochs, chunk=chunk,\n"
        "                           reader_kwargs=RK)]\n"
        "\n"
        "def run_clients(addr, tenants=('a', 'b')):\n"
        "    rows_by = {}\n"
        "    def consume(tag, job_id, tenant):\n"
        "        r = make_service_reader(addr, job_id=job_id, tenant=tenant,\n"
        "                                client_id=tag)\n"
        "        rows = 0\n"
        "        try:\n"
        "            for b in r:\n"
        "                rows += len(b[0])\n"
        "        finally:\n"
        "            rows_by[tag] = rows\n"
        "            r.join()\n"
        "    threads = {tag: threading.Thread(target=consume, args=(tag, j, t))\n"
        "               for tag, j, t in (('a1', 'job-a', tenants[0]),\n"
        "                                 ('a2', 'job-a', tenants[0]),\n"
        "                                 ('b1', 'job-b', tenants[1]),\n"
        "                                 ('b2', 'job-b', tenants[1]))}\n"
        "    return threads, rows_by\n"
        "\n"
        "# -- throughput: one tenant (admission idle) so the number measures\n"
        "# serving capacity, not the scheduler; the fleet advantage is the\n"
        "# stripe-affine decode cache (a group decoded once serves 2 jobs x\n"
        "# EPOCHS epochs x 2 clients each). Fairness is its own phase below.\n"
        "disp = Dispatcher(daddr, jobs=mkjobs(EPOCHS, tenants=('bench', 'bench')),\n"
        "                  lease_ttl_s=60.0, hedge_delay_s=10.0).start()\n"
        "servers = [DecodeServer(a, dispatcher_addr=daddr,\n"
        "                        cache_bytes=1 << 30).start()\n"
        "           for a in saddrs]\n"
        "threads, rows_by = run_clients(daddr, tenants=('bench', 'bench'))\n"
        "t0 = time.perf_counter()\n"
        "for t in threads.values():\n"
        "    t.start()\n"
        "for t in threads.values():\n"
        "    t.join()\n"
        "fleet_sec = time.perf_counter() - t0\n"
        "fleet_rows = sum(rows_by.values())\n"
        "fleet_sps = fleet_rows / fleet_sec\n"
        "report = disp.service_report()\n"
        "cache_hits = sum(s.cache.hits for s in servers)\n"
        "cov_ok = all(report['jobs'][j]['coverage']['reconciled']\n"
        "             for j in ('job-a', 'job-b'))\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "with open(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                       'data_service_epoch.json'), 'w') as f:\n"
        "    json.dump(disp.telemetry.snapshot(), f, default=str)\n"
        "disp.stop()\n"
        "# -- fair-share under 3:1 weights on the (now hot) fleet: shares are\n"
        "# sampled at the moment the heavy tenant drains -- the point where the\n"
        "# weighted ceiling was binding.\n"
        "dfaddr = 'ipc:///tmp/pt-bsvc-f-%d' % pid\n"
        "dispf = Dispatcher(dfaddr, jobs=mkjobs(2), servers=saddrs,\n"
        "                   weights={'a': 3.0, 'b': 1.0}, lease_ttl_s=30.0,\n"
        "                   hedge_delay_s=10.0)\n"
        "dispf.scheduler.activity_window_s = 1.0  # trim idle-tenant tail\n"
        "dispf.start()\n"
        "fthreads, _ = run_clients(dfaddr)\n"
        "for t in fthreads.values():\n"
        "    t.start()\n"
        "fthreads['a1'].join(); fthreads['a2'].join()\n"
        "sched_mid = dispf.scheduler.report()\n"
        "fthreads['b1'].join(); fthreads['b2'].join()\n"
        "dispf.stop()\n"
        "shares = {t: v['share'] for t, v in sched_mid['tenants'].items()}\n"
        "fair_ok = abs(shares.get('a', 0.0) - 0.75) <= 0.10\n"
        "# -- kill-one-client determinism: the victim dies mid-lease unacked,\n"
        "# the sweep folds its range back, and the survivor's solo stream must\n"
        "# be byte-identical to the local reference.\n"
        "ref = []\n"
        "with make_batch_reader(url, shuffle_row_groups=True, seed=SEED,\n"
        "                       num_epochs=1, sample_order='deterministic',\n"
        "                       **RK) as r:\n"
        "    for b in r:\n"
        "        ref.append({f: getattr(b, f) for f in b._fields})\n"
        "d2addr = 'ipc:///tmp/pt-bsvc-e-%d' % pid\n"
        "disp2 = Dispatcher(d2addr, jobs=[ServiceJobSpec(\n"
        "    'job-det', url, tenant='det', seed=SEED, chunk=4,\n"
        "    reader_kwargs=RK)], servers=saddrs[:2], lease_ttl_s=2.0).start()\n"
        "victim = make_service_reader(d2addr, job_id='job-det',\n"
        "                             client_id='victim',\n"
        "                             max_units_per_lease=4)\n"
        "for _ in range(3):\n"
        "    next(victim)  # 3 of a 4-unit lease consumed, never acked\n"
        "victim.abandon()\n"
        "deadline = time.perf_counter() + 10.0\n"
        "while (disp2.book.expired_total < 1\n"
        "       and time.perf_counter() < deadline):\n"
        "    disp2.sweep_expired(); time.sleep(0.05)\n"
        "survivor = make_service_reader(d2addr, job_id='job-det',\n"
        "                               client_id='survivor')\n"
        "got = []\n"
        "for b in survivor:\n"
        "    got.append({f: getattr(b, f) for f in b._fields})\n"
        "survivor.join()\n"
        "det_cov = disp2.service_report()['jobs']['job-det']['coverage']\n"
        "det_ok = (len(got) == len(ref)\n"
        "          and all(set(g) == set(r)\n"
        "                  and all(np.array_equal(g[k], r[k]) for k in r)\n"
        "                  for g, r in zip(got, ref))\n"
        "          and det_cov['reconciled'] and det_cov['violations'] == 0)\n"
        "disp2.stop()\n"
        "for s in servers:\n"
        "    s.stop()\n"
        "print('BENCHJSON:' + json.dumps({'data_service_epoch': {\n"
        "    'local_samples_per_sec': round(local_sps, 1),\n"
        "    'fleet_samples_per_sec_aggregate': round(fleet_sps, 1),\n"
        "    'fleet_clients': 4, 'fleet_servers': 4, 'epochs': EPOCHS,\n"
        "    'throughput_ratio': round(fleet_sps / local_sps, 3),\n"
        "    'ratio_ok': bool(fleet_sps / local_sps >= 1.5),\n"
        "    'server_cache_hit_units': cache_hits,\n"
        "    'tenant_weights': {'a': 3.0, 'b': 1.0},\n"
        "    'tenant_shares_at_contention': {t: round(s, 3)\n"
        "                                    for t, s in shares.items()},\n"
        "    'fair_share_within_10pct': bool(fair_ok),\n"
        "    'coverage_reconciled': bool(cov_ok),\n"
        "    'coverage_violations': report['coverage_violations'],\n"
        "    'leases_expired': disp2.book.expired_total,\n"
        "    'killed_client_units': 3,\n"
        "    'deterministic_ok': bool(det_ok)}}))\n")
    try:
        out.update(_cpu_subprocess(service_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"data-service phase failed: {e!r}", file=sys.stderr)

    # ---- 4k. fleet chaos drill (docs/service.md "Failure modes &
    # recovery"): the seeded service chaos plan. One journaled dispatcher
    # (+ a warm standby tailing the journal) + 4 decode servers + 2
    # clients drain one epoch while the installed FaultPlan kills the
    # dispatcher at the 6th lease_request AND one named decode server at
    # its first work order. The standby re-binds the primary's control
    # address after 2.0s of journal silence (VIP-style takeover: the
    # surviving servers re-register through their heartbeats; the dead
    # one never does), replays the journal, and re-fences the in-flight
    # leases. Clients ride the outage out on whichever recovery path the
    # timing hands them — a generation-change resync when their RPC
    # window spans the takeover, or a state_dict resume + resync when it
    # doesn't. Proven: the union stream is byte-identical to the
    # fault-free local reference, the promoted dispatcher's ledger
    # reconciles with zero violations, and recovery lands within 2 lease
    # TTLs. The promoted dispatcher's telemetry snapshot is flushed to
    # bench_snapshots/chaos_service_epoch.json — the `make ci-lint`
    # survivability SLO gate artifact (coverage violations == 0, torn
    # journal records == 0).
    chaos_child = (
        "import json, os, shutil, threading, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "import pyarrow.parquet as pq\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.resilience.faults import FaultPlan, FaultSpec\n"
        "from petastorm_tpu.service import (Dispatcher, DecodeServer,\n"
        "                                   ServiceJobSpec, WarmStandby,\n"
        "                                   install_service_fault_plan,\n"
        "                                   make_service_reader)\n"
        "path = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'service_chaos')\n"
        "url = 'file://' + path\n"
        "if not os.path.exists(os.path.join(path, 'part0.parquet')):\n"
        "    os.makedirs(path, exist_ok=True)\n"
        "    rng = np.random.default_rng(11)\n"
        "    nrows = 48 * 512\n"
        "    cols = {'id': np.arange(nrows, dtype=np.float64)}\n"
        "    for i in range(1, 6):\n"
        "        cols['f%d' % i] = rng.normal(size=nrows)\n"
        "    pq.write_table(pa.table(cols), os.path.join(path, 'part0.parquet'),\n"
        "                   row_group_size=512, compression='zstd')\n"
        "SEED, TTL, pid = 20260807, 3.0, os.getpid()\n"
        "NUM_ITEMS = 48\n"
        "ref = []\n"
        "with make_batch_reader(url, shuffle_row_groups=True, seed=SEED,\n"
        "                       num_epochs=1,\n"
        "                       sample_order='deterministic') as r:\n"
        "    for b in r:\n"
        "        ref.append({f: getattr(b, f) for f in b._fields})\n"
        "assert len(ref) == NUM_ITEMS\n"
        "daddr = 'ipc:///tmp/pt-chaos-d-%d' % pid\n"
        "saddrs = ['ipc:///tmp/pt-chaos-s%d-%d' % (i, pid) for i in range(4)]\n"
        "jdir = os.path.join(os.environ['PT_BENCH_DATA_DIR'],\n"
        "                    'chaos_journal_%d' % pid)\n"
        "shutil.rmtree(jdir, ignore_errors=True)\n"
        "mk = lambda: [ServiceJobSpec('job-chaos', url, tenant='chaos',\n"
        "                             seed=SEED, chunk=4)]\n"
        "mkdisp = lambda a, jd: Dispatcher(a, jobs=mk(), lease_ttl_s=TTL,\n"
        "                                  hedge_delay_s=30.0,\n"
        "                                  server_heartbeat_s=0.5,\n"
        "                                  journal_dir=jd)\n"
        "disp = mkdisp(daddr, jdir).start()\n"
        "standby = WarmStandby(daddr, jdir, heartbeat_s=0.75,\n"
        "                      takeover_silence_s=2.0,\n"
        "                      dispatcher_factory=mkdisp).start()\n"
        "servers = [DecodeServer(a, dispatcher_addr=daddr, heartbeat_s=0.5,\n"
        "                        server_id=('srv-victim' if i == 1\n"
        "                                   else 'srv-%d' % i)).start()\n"
        "           for i, a in enumerate(saddrs)]\n"
        "install_service_fault_plan(FaultPlan([\n"
        "    FaultSpec(site='dispatcher.kill', kind='ioerror', at=6,\n"
        "              key_substring='lease_request'),\n"
        "    FaultSpec(site='server.order', kind='ioerror', at=1,\n"
        "              key_substring='srv-victim')], seed=SEED))\n"
        "t_kill = [None]; t_grant = [None]\n"
        "def watch():\n"
        "    while t_kill[0] is None:\n"
        "        if disp.killed:\n"
        "            t_kill[0] = time.perf_counter()\n"
        "            break\n"
        "        time.sleep(0.02)\n"
        "    standby.promoted.wait(60.0)\n"
        "    deadline = time.perf_counter() + 60.0\n"
        "    while t_grant[0] is None and time.perf_counter() < deadline:\n"
        "        d2 = standby.dispatcher\n"
        "        if d2 is not None and d2.book.granted_total > 0:\n"
        "            t_grant[0] = time.perf_counter()\n"
        "            break\n"
        "        time.sleep(0.02)\n"
        "watcher = threading.Thread(target=watch, daemon=True)\n"
        "watcher.start()\n"
        "got, resume_s = {}, []\n"
        "outages = {'n': 0}\n"
        "lock = threading.Lock()\n"
        "def consume(tag):\n"
        "    state, t_fail = None, None\n"
        "    deadline = time.perf_counter() + 120.0\n"
        "    while time.perf_counter() < deadline:\n"
        "        r = None\n"
        "        try:\n"
        "            r = make_service_reader(\n"
        "                daddr, job_id='job-chaos', client_id=tag,\n"
        "                max_units_per_lease=4, hedge_delay_s=30.0,\n"
        "                control_timeout_ms=2000, unit_timeout_s=15.0,\n"
        "                resume_state=state)\n"
        "            for b in r:\n"
        "                if t_fail is not None:\n"
        "                    with lock:\n"
        "                        resume_s.append(time.perf_counter() - t_fail)\n"
        "                    t_fail = None\n"
        "                pos = r._consumed[0][-1]\n"
        "                with lock:\n"
        "                    got[pos] = {f: getattr(b, f) for f in b._fields}\n"
        "            r.close()\n"
        "            return\n"
        "        except Exception:\n"
        "            # Outage (dead dispatcher / dead server): remember the\n"
        "            # cursor and come back as a resumed client -- the\n"
        "            # state_dict + resync recovery path.\n"
        "            if t_fail is None:\n"
        "                t_fail = time.perf_counter()\n"
        "            with lock:\n"
        "                outages['n'] += 1\n"
        "            if r is not None:\n"
        "                state = r.state_dict()\n"
        "                r.abandon()\n"
        "            time.sleep(0.4)\n"
        "threads = [threading.Thread(target=consume, args=('chaos-c%d' % i,))\n"
        "           for i in range(2)]\n"
        "for t in threads:\n"
        "    t.start()\n"
        "for t in threads:\n"
        "    t.join()\n"
        "watcher.join(timeout=10.0)\n"
        "install_service_fault_plan(None)\n"
        "d2 = standby.dispatcher\n"
        "report = d2.service_report()\n"
        "cov = report['jobs']['job-chaos']['coverage']\n"
        "byte_ok = (sorted(got) == list(range(NUM_ITEMS))\n"
        "           and all(set(got[i]) == set(ref[i])\n"
        "                   and all(np.array_equal(got[i][k], ref[i][k])\n"
        "                           for k in ref[i])\n"
        "                   for i in range(NUM_ITEMS)))\n"
        "peek = lambda d, name: int(d.telemetry.peek_counter(name))\n"
        "evicted = (peek(disp, 'service.failover.servers_evicted_total')\n"
        "           + peek(d2, 'service.failover.servers_evicted_total'))\n"
        "takeover_recovery = (t_grant[0] - t_kill[0]\n"
        "                     if t_grant[0] is not None\n"
        "                     and t_kill[0] is not None else None)\n"
        "recovery_vals = list(resume_s)\n"
        "if takeover_recovery is not None:\n"
        "    recovery_vals.append(takeover_recovery)\n"
        "recovery_ok = bool(recovery_vals) and max(recovery_vals) <= 2 * TTL\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "with open(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                       'chaos_service_epoch.json'), 'w') as f:\n"
        "    json.dump(d2.telemetry.snapshot(), f, default=str)\n"
        "standby.stop()\n"
        "disp.stop()\n"
        "for s in servers:\n"
        "    s.stop()\n"
        "print('BENCHJSON:' + json.dumps({'chaos_service_epoch': {\n"
        "    'fleet': '1 dispatcher + warm standby, 4 servers, 2 clients',\n"
        "    'dispatcher_killed': bool(disp.killed),\n"
        "    'server_killed': bool(servers[1].killed),\n"
        "    'standby_promoted': bool(standby.promoted.is_set()),\n"
        "    'standby_takeovers': peek(standby,\n"
        "                              'service.failover.takeovers_total'),\n"
        "    'servers_evicted': evicted,\n"
        "    'journal_replayed_records': peek(\n"
        "        d2, 'service.failover.replayed_records_total'),\n"
        "    'refenced_leases': peek(\n"
        "        d2, 'service.failover.refenced_leases_total'),\n"
        "    'torn_journal_records': peek(d2, 'journal.torn_records_total'),\n"
        "    'client_outages': outages['n'],\n"
        "    'client_resume_s': [round(v, 3) for v in resume_s],\n"
        "    'takeover_recovery_s': (None if takeover_recovery is None\n"
        "                            else round(takeover_recovery, 3)),\n"
        "    'lease_ttl_s': TTL,\n"
        "    'recovery_within_2_ttl': bool(recovery_ok),\n"
        "    'byte_identical': bool(byte_ok),\n"
        "    'coverage_reconciled': bool(cov['reconciled']),\n"
        "    'coverage_violations': cov['violations']}}))\n")
    try:
        out.update(_cpu_subprocess(chaos_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"chaos-service phase failed: {e!r}", file=sys.stderr)

    # ---- 4l. fleet cache tier (docs/service.md "Fleet cache tier"): two
    # tenants whose datasets share 80% of their physical row groups
    # (symlink-assembled from one file pool, so the content keys prove
    # the sharing) drain sequential epochs against a 1-dispatcher +
    # 4-server fleet, with one decode server killed mid-epoch in BOTH
    # arms. Baseline arm: per-server caches only (peer_fetch off) — the
    # second tenant re-decodes every shared group that landed on a
    # different stripe. Fleet arm: content-addressed directory + peer
    # fetch — tenant B's shared groups are served from tenant A's
    # resident buffers (decoded-once fleet-wide), so its epoch is
    # transfer-bound. Gated targets (ROADMAP fleet-cache item): aggregate
    # throughput >= 1.3x baseline, tenant-B shared-group decodes ~ 0,
    # byte-identical streams vs the local reference in both arms, and a
    # warm fleet ServiceReader.lookup() p99 < 25ms through the same
    # cache. The fleet dispatcher+server telemetry (cache counters
    # merged) is flushed to bench_snapshots/fleet_cache_epoch.json — the
    # `make ci-lint` SLO gate artifact (zero coverage violations,
    # bounded peer-fetch timeouts).
    fleet_cache_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "import pyarrow.parquet as pq\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "from petastorm_tpu.index import build_field_index\n"
        "from petastorm_tpu.resilience.faults import FaultPlan, FaultSpec\n"
        "from petastorm_tpu.service import (Dispatcher, DecodeServer,\n"
        "                                   ServiceJobSpec,\n"
        "                                   install_service_fault_plan,\n"
        "                                   make_service_reader)\n"
        "base = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'fleet_cache')\n"
        "pool = os.path.join(base, 'pool')\n"
        "dsa, dsb = os.path.join(base, 'dsA'), os.path.join(base, 'dsB')\n"
        "NFILES, RG, NCOLS = 24, 1024, 2048\n"
        "if not os.path.exists(os.path.join(pool, 'f00.parquet')):\n"
        "    # Decode-heavy shape: many narrow zstd column chunks make the\n"
        "    # per-group parquet decode (~150ms) dwarf the Arrow-IPC serve\n"
        "    # (~5ms) -- the regime where a peer fetch beats a re-decode.\n"
        "    os.makedirs(pool, exist_ok=True)\n"
        "    rng = np.random.default_rng(20)\n"
        "    for i in range(NFILES):\n"
        "        cols = {'id': np.arange(i * RG, (i + 1) * RG,\n"
        "                                dtype=np.int64)}\n"
        "        for c in range(NCOLS):\n"
        "            cols['f%04d' % c] = rng.integers(0, 512, RG)"
        ".astype(np.int16)\n"
        "        pq.write_table(pa.table(cols),\n"
        "                       os.path.join(pool, 'f%02d.parquet' % i),\n"
        "                       row_group_size=RG, compression='zstd')\n"
        "    # 80% overlap: A = files 0..19, B = files 4..23, via symlinks\n"
        "    # to one physical pool (content keys stat the realpath).\n"
        "    for d, files in ((dsa, range(0, 20)), (dsb, range(4, 24))):\n"
        "        os.makedirs(d, exist_ok=True)\n"
        "        for i in files:\n"
        "            os.symlink(os.path.join(pool, 'f%02d.parquet' % i),\n"
        "                       os.path.join(d, 'p%02d.parquet' % i))\n"
        "    build_field_index('file://' + dsa, ['id'])\n"
        "SEED, pid = 20260807, os.getpid()\n"
        "ua, ub = 'file://' + dsa, 'file://' + dsb\n"
        "def local_ref(url):\n"
        "    out = []\n"
        "    with make_batch_reader(url, shuffle_row_groups=True, seed=SEED,\n"
        "                           num_epochs=1,\n"
        "                           sample_order='deterministic') as r:\n"
        "        for b in r:\n"
        "            out.append({f: getattr(b, f) for f in b._fields})\n"
        "    return out\n"
        "refa, refb = local_ref(ua), local_ref(ub)\n"
        "mkjobs = lambda: [ServiceJobSpec('job-a', ua, tenant='ta',\n"
        "                                 seed=SEED, chunk=4),\n"
        "                  ServiceJobSpec('job-b', ub, tenant='tb',\n"
        "                                 seed=SEED, chunk=4)]\n"
        "def match(got, ref):\n"
        "    return (len(got) == len(ref)\n"
        "            and all(set(g) == set(r)\n"
        "                    and all(np.array_equal(g[k], r[k]) for k in r)\n"
        "                    for g, r in zip(got, ref)))\n"
        "def run_arm(tag, peer_fetch):\n"
        "    daddr = 'ipc:///tmp/pt-fc-%s-d-%d' % (tag, pid)\n"
        "    saddrs = ['ipc:///tmp/pt-fc-%s-%d-%d' % (tag, i, pid)\n"
        "              for i in range(4)]\n"
        "    disp = Dispatcher(daddr, jobs=mkjobs(), lease_ttl_s=30.0,\n"
        "                      hedge_delay_s=1.0,\n"
        "                      server_heartbeat_s=2.0).start()\n"
        "    servers = [DecodeServer(a, dispatcher_addr=daddr,\n"
        "                            heartbeat_s=0.25, workers=1,\n"
        "                            peer_fetch=peer_fetch,\n"
        "                            cache_bytes=1 << 30,\n"
        "                            server_id=('fc-%s-victim' % tag\n"
        "                                       if i == 3\n"
        "                                       else 'fc-%s-%d' % (tag, i))\n"
        "                            ).start()\n"
        "               for i, a in enumerate(saddrs)]\n"
        "    install_service_fault_plan(FaultPlan([\n"
        "        FaultSpec(site='server.order', kind='ioerror', at=2,\n"
        "                  key_substring='fc-%s-victim' % tag)], seed=SEED))\n"
        "    got = {'a': [], 'b': []}\n"
        "    def consume(cl, job, tenant):\n"
        "        r = make_service_reader(daddr, job_id=job, tenant=tenant,\n"
        "                                client_id='%s-%s' % (tag, cl),\n"
        "                                hedge_delay_s=1.0,\n"
        "                                unit_timeout_s=30.0)\n"
        "        try:\n"
        "            for b in r:\n"
        "                got[cl].append({f: getattr(b, f)\n"
        "                                for f in b._fields})\n"
        "        finally:\n"
        "            r.join()\n"
        "    snap_decodes = lambda: {k: n for s in servers\n"
        "                            for k, n in s.cache.decodes.items()}\n"
        "    t0 = time.perf_counter()\n"
        "    consume('a', 'job-a', 'ta')   # tenant A: cold fleet + kill\n"
        "    ta = time.perf_counter() - t0\n"
        "    keys_a = set(snap_decodes())\n"
        "    consume('b', 'job-b', 'tb')   # tenant B: 80% overlap, warm\n"
        "    sec = time.perf_counter() - t0\n"
        "    install_service_fault_plan(None)\n"
        "    rows = sum(len(b['id']) for cl in got for b in got[cl])\n"
        "    decodes = {}\n"
        "    for s in servers:\n"
        "        for k, n in s.cache.decodes.items():\n"
        "            decodes[k] = decodes.get(k, 0) + n\n"
        "    return dict(\n"
        "        sps=rows / sec, secs_a=ta, secs_b=sec - ta,\n"
        "        byte_ok=match(got['a'], refa) and match(got['b'], refb),\n"
        "        decodes=sum(decodes.values()), groups=len(decodes),\n"
        "        max_decodes_per_group=max(decodes.values() or [0]),\n"
        "        tenant_b_shared_decodes=sum(\n"
        "            n for k, n in decodes.items() if k in keys_a)\n"
        "            - sum(1 for k in keys_a),\n"
        "        peer_hits=sum(s.cache.peer_hits for s in servers),\n"
        "        timeouts=sum(int(s.telemetry.peek_counter(\n"
        "            'service.cache.peer_fetch_timeouts_total'))\n"
        "            for s in servers),\n"
        "        killed=bool(servers[3].killed),\n"
        "        disp=disp, servers=servers, daddr=daddr)\n"
        "bl = run_arm('bl', peer_fetch=False)\n"
        "bl['disp'].stop()\n"
        "for s in bl['servers']:\n"
        "    s.stop()\n"
        "fc = run_arm('fc', peer_fetch=True)\n"
        "speedup = fc['sps'] / bl['sps']\n"
        "# warm fleet point reads through the same cache tier\n"
        "reader = make_service_reader(fc['daddr'], job_id='job-a',\n"
        "                             tenant='ta', client_id='fc-lookup')\n"
        "LCOLS = ['id', 'f0000']\n"
        "# warming pass: one key per dsA file re-warms the groups the dead\n"
        "# victim took down (a warm-lookup SLO is about the steady state)\n"
        "reader.lookup([f * RG + 7 for f in range(20)], field='id',\n"
        "              columns=LCOLS)\n"
        "rng = np.random.default_rng(SEED)\n"
        "ids = rng.integers(0, 20 * RG, 220)\n"
        "reader.lookup([int(ids[0])], field='id', columns=LCOLS)\n"
        "lat = []\n"
        "for k in ids[1:201]:\n"
        "    t1 = time.perf_counter()\n"
        "    rows = reader.lookup([int(k)], field='id', columns=LCOLS)\n"
        "    lat.append(time.perf_counter() - t1)\n"
        "    assert rows and rows[0]['id'] == int(k)\n"
        "lat.sort()\n"
        "p50, p99 = lat[len(lat) // 2], lat[int(len(lat) * 0.99) - 1]\n"
        "report = fc['disp'].service_report()\n"
        "cov_ok = all(report['jobs'][j]['coverage']['reconciled']\n"
        "             for j in ('job-a', 'job-b'))\n"
        "snap = fc['disp'].telemetry.snapshot()\n"
        "for s in fc['servers']:\n"
        "    for name, val in s.telemetry.metrics_view()['counters']"
        ".items():\n"
        "        if name.startswith('service.cache.'):\n"
        "            snap['counters'][name] = (snap['counters']"
        ".get(name, 0) + val)\n"
        "snap['counters'].setdefault(\n"
        "    'service.cache.peer_fetch_timeouts_total', 0)\n"
        "os.makedirs(os.environ['PT_BENCH_SNAPSHOT_DIR'], exist_ok=True)\n"
        "with open(os.path.join(os.environ['PT_BENCH_SNAPSHOT_DIR'],\n"
        "                       'fleet_cache_epoch.json'), 'w') as f:\n"
        "    json.dump(snap, f, default=str)\n"
        "reader.close()\n"
        "fc['disp'].stop()\n"
        "for s in fc['servers']:\n"
        "    s.stop()\n"
        "print('BENCHJSON:' + json.dumps({'fleet_cache_epoch': {\n"
        "    'fleet': '1 dispatcher + 4 servers, 2 tenants, 80% overlap',\n"
        "    'baseline_samples_per_sec_aggregate': round(bl['sps'], 1),\n"
        "    'fleet_cache_samples_per_sec_aggregate': round(fc['sps'], 1),\n"
        "    'fleet_cache_speedup': round(speedup, 3),\n"
        "    'speedup_ok': bool(speedup >= 1.3),\n"
        "    'tenant_secs': {'baseline': [round(bl['secs_a'], 2),\n"
        "                                 round(bl['secs_b'], 2)],\n"
        "                    'fleet': [round(fc['secs_a'], 2),\n"
        "                              round(fc['secs_b'], 2)]},\n"
        "    'fleet_decodes': fc['decodes'],\n"
        "    'baseline_decodes': bl['decodes'],\n"
        "    'distinct_groups': fc['groups'],\n"
        "    'max_decodes_per_group': fc['max_decodes_per_group'],\n"
        "    'tenant_b_shared_decodes': {'baseline':\n"
        "                                bl['tenant_b_shared_decodes'],\n"
        "                                'fleet':\n"
        "                                fc['tenant_b_shared_decodes']},\n"
        "    'peer_hits': fc['peer_hits'],\n"
        "    'peer_fetch_timeouts': fc['timeouts'],\n"
        "    'server_killed_mid_epoch': bool(bl['killed']\n"
        "                                    and fc['killed']),\n"
        "    'byte_identical': bool(bl['byte_ok'] and fc['byte_ok']),\n"
        "    'coverage_reconciled': bool(cov_ok),\n"
        "    'lookup_p50_s': round(p50, 5),\n"
        "    'lookup_p99_s': round(p99, 5),\n"
        "    'lookup_ok': bool(p99 < 0.025)}}))\n")
    try:
        out.update(_cpu_subprocess(fleet_cache_child, data_dir,
                                   timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"fleet-cache phase failed: {e!r}", file=sys.stderr)

    # ---- 4m. RL-replay mixed access (docs/random_access.md): one dataset
    # served BOTH ways at once — a sequential epoch streams batches while a
    # replay sampler fires keyed lookup() calls against the same reader
    # (shared decoded cache). Reports the roadmap item-3 targets: warm
    # single-key lookup p99 (<10ms) and batched-gather rows/s (>=100k),
    # plus the coalescing/cache counters that explain them.
    replay_child = (
        "import json, os, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import pyarrow as pa\n"
        "import pyarrow.parquet as pq\n"
        "store = os.path.join(os.environ['PT_BENCH_DATA_DIR'], 'replay')\n"
        "url = 'file://' + store\n"
        "N = 100_000\n"
        "if not os.path.exists(os.path.join(store, 'data.parquet')):\n"
        "    os.makedirs(store, exist_ok=True)\n"
        "    ids = np.arange(N, dtype=np.int64)\n"
        "    pq.write_table(pa.table({'id': ids,\n"
        "                             'val': (ids * 0.5).astype(np.float32)}),\n"
        "                   os.path.join(store, 'data.parquet'),\n"
        "                   row_group_size=4096)\n"
        "from petastorm_tpu.index import (build_field_index, gather_rows,\n"
        "                                 INDEX_SIDECAR_NAME)\n"
        "if not os.path.exists(os.path.join(store, INDEX_SIDECAR_NAME)):\n"
        "    build_field_index(url, ['id'])\n"
        "from petastorm_tpu.reader import make_batch_reader\n"
        "rng = np.random.default_rng(0)\n"
        "with make_batch_reader(url, num_epochs=1, shuffle_row_groups=False,\n"
        "                       reader_pool_type='thread', workers_count=3,\n"
        "                       memory_cache_size_bytes=1 << 30) as r:\n"
        "    seq_rows, replay_rows = 0, 0\n"
        "    t0 = time.perf_counter()\n"
        "    for i, batch in enumerate(r):\n"
        "        seq_rows += len(batch.id)\n"
        "        if i % 8 == 0:  # replay sampler interleaved with the epoch\n"
        "            keys = [int(k) for k in rng.integers(0, N, size=64)]\n"
        "            replay_rows += len(r.lookup(keys))\n"
        "    mixed_s = time.perf_counter() - t0\n"
        "    lat = []\n"
        "    for k in rng.integers(0, N, size=300):\n"
        "        t1 = time.perf_counter()\n"
        "        r.lookup([int(k)])\n"
        "        lat.append(time.perf_counter() - t1)\n"
        "    p99_s = float(np.percentile(lat, 99))\n"
        "    g_rows, t2 = 0, time.perf_counter()\n"
        "    for _ in range(4):  # replay draw: keyed lookup -> device batch\n"
        "        keys = [int(k) for k in rng.integers(0, N, size=4096)]\n"
        "        b = gather_rows(r.lookup(keys))\n"
        "        jax.block_until_ready(b['val'])\n"
        "        g_rows += int(b['val'].shape[0])\n"
        "    replay_s = time.perf_counter() - t2\n"
        "    rows = r.lookup([int(k) for k in rng.integers(0, N, size=4096)])\n"
        "    t3 = time.perf_counter()\n"
        "    for _ in range(8):  # gather-only: host stack + one commit\n"
        "        jax.block_until_ready(gather_rows(rows)['val'])\n"
        "    gather_s = time.perf_counter() - t3\n"
        "    c = r.telemetry.metrics_view()['counters']\n"
        "print('BENCHJSON:' + json.dumps({'rl_replay_epoch': {\n"
        "    'rows': N,\n"
        "    'mixed_epoch_samples_per_sec': round(seq_rows / mixed_s, 1),\n"
        "    'replay_rows_interleaved': replay_rows,\n"
        "    'lookup_warm_p99_ms': round(p99_s * 1e3, 3),\n"
        "    'lookup_p99_under_10ms': bool(p99_s < 0.010),\n"
        "    'replay_gather_rows_per_sec': round(g_rows / replay_s, 1),\n"
        "    'gather_rows_per_sec': round(8 * len(rows) / gather_s, 1),\n"
        "    'gather_rows_ok': bool(8 * len(rows) / gather_s >= 100_000),\n"
        "    'rowgroups_touched': c.get('index.rowgroups_touched_total', 0),\n"
        "    'keys_requested': c.get('index.keys_requested_total', 0),\n"
        "    'index_cache_hits': c.get('index.cache_hits_total', 0),\n"
        "    'index_cache_misses': c.get('index.cache_misses_total', 0)}}))\n")
    try:
        out.update(_cpu_subprocess(replay_child, data_dir, timeout_s=600.0))
    except Exception as e:  # noqa: BLE001 - partial bench beats no bench
        print(f"rl-replay phase failed: {e!r}", file=sys.stderr)

    # ---- assemble the line ---------------------------------------------
    out.update({
        "metric": "hello_world reader throughput",
        "value": round(best, 2),
        "unit": "samples/sec",
        "vs_baseline": round(best / BASELINE_SAMPLES_PER_SEC, 3),
        "hello_world_10k_samples_per_sec": round(steady_sps, 2),
    })
    if scalar_sps is not None:
        out["scalar_batched_samples_per_sec"] = round(scalar_sps, 2)
    if best_cfg_sps is not None:
        out["best_config_samples_per_sec"] = round(best_cfg_sps, 2)
        out["best_config"] = best_cfg
        out["best_config_sweep"] = {
            k: round(max(v), 2)
            for k, v in best_cfg_result["samples"].items()}

    # ---- cross-round regression guard (round-4 verdict "weak" item 1) --
    try:
        _regression_guard(out)
    except Exception as e:  # noqa: BLE001 - guard must not kill the line
        print(f"regression guard failed: {e!r}", file=sys.stderr)

    print(json.dumps(out))
    return 0


def _cpu_subprocess(child_code: str, data_dir: str,
                    timeout_s: float = 1200.0) -> dict:
    """Run ``child_code`` in a fresh JAX_PLATFORMS=cpu subprocess and return
    its ``BENCHJSON:`` payload. Children also do
    ``jax.config.update('jax_platforms', 'cpu')`` themselves, so a phase
    stays a host measurement even when run by hand without the env. One
    process per phase keeps RSS and thread pools from leaking between
    phases. data_dir arrives via env, never interpolated into code."""
    import subprocess
    snap_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bench_snapshots")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PT_BENCH_DATA_DIR=data_dir,
               PT_BENCH_SNAPSHOT_DIR=snap_dir)
    proc = subprocess.run([sys.executable, "-c", child_code], env=env,
                          capture_output=True, text=True, timeout=timeout_s)
    for line in proc.stdout.splitlines():
        if line.startswith("BENCHJSON:"):
            return json.loads(line[len("BENCHJSON:"):])
    raise RuntimeError(f"cpu subprocess produced no result "
                       f"(rc={proc.returncode}, stderr tail: "
                       f"{proc.stderr[-300:]!r})")


if __name__ == "__main__":
    sys.exit(main())
