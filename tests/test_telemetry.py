"""Pipeline telemetry subsystem: spans, histograms, gauges, stall
attribution, exporters, the CLI, and the end-to-end wiring through
Reader/pools/loaders (docs/observability.md).

All tier-1: these run in the smoke tier (``pytest -m 'not slow'``).
"""
import json
import shutil
import threading
import time

import pytest

from petastorm_tpu import metrics as metrics_mod
from petastorm_tpu.metrics import PipelineMetrics, trace, traced_span
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.telemetry import (SIZE_BOUNDS, SNAPSHOT_SCHEMA_VERSION,
                                     TELEMETRY_EXPORT_ENV, PeriodicExporter,
                                     SpanRecorder, StallAttributor,
                                     StreamingHistogram, TelemetryRegistry,
                                     from_json, make_registry,
                                     parse_prometheus_text, to_json,
                                     to_prometheus_text, write_snapshot)
from petastorm_tpu.telemetry.__main__ import main as telemetry_cli

pytestmark = pytest.mark.telemetry


# --------------------------------------------------------------------------
# StreamingHistogram
# --------------------------------------------------------------------------

def test_histogram_basic_stats():
    h = StreamingHistogram()
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(0.107)
    d = h.as_dict()
    assert d["count"] == 4
    assert d["min"] == pytest.approx(0.001)
    assert d["max"] == pytest.approx(0.1)
    assert d["min"] <= d["p50"] <= d["p95"] <= d["p99"] <= d["max"]


def test_histogram_buckets_cumulative_with_inf():
    h = StreamingHistogram(bounds=[1.0, 10.0])
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.buckets() == [[1.0, 1], [10.0, 2], [None, 4]]


def test_histogram_quantile_of_empty_is_zero():
    assert StreamingHistogram().quantile(0.5) == 0.0


def test_histogram_merge_and_reset():
    a, b = StreamingHistogram(bounds=[1.0]), StreamingHistogram(bounds=[1.0])
    a.observe(0.5)
    b.observe(2.0)
    a.merge(b)
    assert a.count == 2 and a.sum == pytest.approx(2.5)
    with pytest.raises(ValueError, match="different bounds"):
        a.merge(StreamingHistogram(bounds=[2.0]))
    a.reset()
    assert a.count == 0 and a.as_dict()["max"] == 0.0


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError, match="ascending"):
        StreamingHistogram(bounds=[2.0, 1.0])
    with pytest.raises(ValueError, match="ascending"):
        StreamingHistogram(bounds=[])


# --------------------------------------------------------------------------
# SpanRecorder
# --------------------------------------------------------------------------

def test_recorder_disabled_times_but_skips_the_ring():
    """``disable()`` is the operator's switch: a span still times itself
    (its clock pair feeds the site's counters) and the ring stays empty."""
    r = SpanRecorder(enabled=False)
    with r.span("a") as sp:
        time.sleep(0.001)
    assert sp.duration_s >= 0.001
    r.record("direct", 0.0, 1.0)
    assert r.spans() == [] and r.dropped == 0


def test_recorder_records_provenance_and_aggregates():
    r = SpanRecorder(enabled=True)
    with r.span("stage", extra={"batch": 1}):
        time.sleep(0.001)
    r.record_event("epoch_end")
    spans = r.spans()
    assert [s.name for s in spans] == ["stage", "epoch_end"]
    assert spans[0].duration_s >= 0.001
    assert spans[0].thread == threading.current_thread().name
    assert spans[0].pid > 0
    assert spans[0].as_dict()["extra"] == {"batch": 1}
    agg = r.aggregate()
    assert agg["stage"]["count"] == 1
    assert agg["stage"]["total_s"] >= 0.001
    assert agg["epoch_end"]["total_s"] == 0.0


def test_recorder_ring_bound_and_dropped_count():
    r = SpanRecorder(capacity=3, enabled=True)
    for i in range(5):
        r.record(f"s{i}", 0.0, 0.1)
    assert [s.name for s in r.spans()] == ["s2", "s3", "s4"]
    assert r.dropped == 2
    assert r.drain() and r.spans() == []
    with pytest.raises(ValueError, match="capacity"):
        SpanRecorder(capacity=0)


def test_recorder_disabled_hot_path_overhead():
    """What a disabled recorder owes the hot path: 10k spans leave the ring
    empty, drop nothing and never take the ring's lock (each span still
    times itself for its site's counters; no duration is asserted)."""
    registry = TelemetryRegistry()
    recorder = registry.recorder
    recorder.disable()

    class CountingLock:
        def __init__(self, lock):
            self._lock, self.acquisitions = lock, 0

        def __enter__(self):
            self.acquisitions += 1
            return self._lock.__enter__()

        def __exit__(self, *exc):
            return self._lock.__exit__(*exc)

    recorder._lock = lock = CountingLock(recorder._lock)
    for _ in range(10_000):
        with registry.span("hot") as live:
            pass
    assert live.duration_s >= 0.0          # the span still timed itself
    assert lock.acquisitions == 0
    assert recorder.spans() == [] and recorder.dropped == 0
    recorder.enable()                  # the same span, enabled, is counted
    before = lock.acquisitions
    with registry.span("hot"):
        pass
    assert lock.acquisitions == before + 1
    assert [s.name for s in recorder.spans()] == ["hot"]


# --------------------------------------------------------------------------
# TelemetryRegistry
# --------------------------------------------------------------------------

def test_registry_get_or_create_idempotent():
    reg = TelemetryRegistry()
    assert reg.counter("c") is reg.counter("c")
    assert reg.gauge("g") is reg.gauge("g")
    assert reg.histogram("h") is reg.histogram("h")


def test_registry_counter_rejects_negative():
    with pytest.raises(ValueError, match="Gauge"):
        TelemetryRegistry().counter("c").add(-1)


def test_registry_function_gauge_and_dead_gauge():
    reg = TelemetryRegistry()
    items = [1, 2, 3]
    reg.gauge("depth", lambda: len(items))
    assert reg.snapshot()["gauges"]["depth"] == 3.0

    def dead():
        raise RuntimeError("torn down")
    reg.gauge("gone", dead)
    snap = reg.snapshot()
    assert snap["gauges"]["gone"] is None
    # Dead gauges are skipped (not exported as a lie) in Prometheus text.
    assert "gone" not in to_prometheus_text(snap)


def test_registry_snapshot_schema_and_reset_returns_prior():
    reg = TelemetryRegistry()
    reg.counter("n").add(5)
    reg.histogram("lat").observe(0.01)
    reg.gauge("q").set(7)
    with reg.span("work"):
        pass
    snap = reg.reset()
    assert snap["schema_version"] == SNAPSHOT_SCHEMA_VERSION
    assert snap["counters"]["n"] == 5
    assert snap["histograms"]["lat"]["count"] == 1
    assert snap["spans"]["work"]["count"] == 1
    after = reg.snapshot()
    assert after["counters"]["n"] == 0
    assert after["histograms"]["lat"]["count"] == 0
    assert after["spans"] == {}
    assert after["gauges"]["q"] == 7.0  # gauges are live views: untouched


def test_counter_reset_is_atomic_under_concurrency():
    """No increment may be lost between read and reset — the exact race the
    old two-call PipelineMetrics pattern had. Every add() must land exactly
    once: in a harvested snapshot or in the final reset."""
    reg = TelemetryRegistry()
    c = reg.counter("n")
    per_thread, threads_n = 500, 4

    def bump():
        for _ in range(per_thread):
            c.add(1)

    threads = [threading.Thread(target=bump) for _ in range(threads_n)]
    for t in threads:
        t.start()
    harvested = 0.0
    while any(t.is_alive() for t in threads):
        harvested += c.reset()
    for t in threads:
        t.join()
    harvested += c.reset()
    assert harvested == per_thread * threads_n


# --------------------------------------------------------------------------
# PipelineMetrics (view over the registry)
# --------------------------------------------------------------------------

def test_pipeline_metrics_records_and_reads_through():
    m = PipelineMetrics()
    m.record_batch(samples=32, nbytes=1024, host_wait_s=0.5, stage_s=0.25)
    m.record_batch(samples=32, nbytes=1024, host_wait_s=0.5, stage_s=0.25)
    assert m.batches == 2 and m.samples == 64 and m.bytes_staged == 2048
    assert m.as_dict() == {"batches": 2, "samples": 64, "bytes_staged": 2048,
                           "host_wait_s": 1.0, "stage_s": 0.5}
    # The same numbers are visible in the backing registry's snapshot.
    snap = m.telemetry.snapshot()
    assert snap["counters"]["loader.batches"] == 2
    assert snap["histograms"]["loader.stage_seconds"]["count"] == 2
    assert snap["histograms"]["loader.batch_bytes"]["sum"] == 2048


def test_pipeline_metrics_reset_returns_pre_reset_snapshot():
    m = PipelineMetrics()
    m.record_batch(samples=8, nbytes=64, host_wait_s=0.1, stage_s=0.2)
    snap = m.reset()
    assert snap == {"batches": 1, "samples": 8, "bytes_staged": 64,
                    "host_wait_s": 0.1, "stage_s": 0.2}
    assert m.as_dict()["batches"] == 0
    # The shared registry histograms are NOT reset: they may be exported
    # (Prometheus series never decrease) and sibling loaders share them.
    assert m.telemetry.snapshot()["histograms"]["loader.stage_seconds"]["count"] == 1


def test_pipeline_metrics_reset_race_loses_no_batches():
    """N recorder threads + a polling resetter: the sum of all reset
    snapshots plus the final state must equal exactly what was recorded."""
    m = PipelineMetrics()
    per_thread, threads_n = 200, 4

    def record():
        for _ in range(per_thread):
            m.record_batch(samples=1, nbytes=1, host_wait_s=0.0, stage_s=0.0)

    threads = [threading.Thread(target=record) for _ in range(threads_n)]
    for t in threads:
        t.start()
    harvested = 0
    while any(t.is_alive() for t in threads):
        harvested += m.reset()["batches"]
    for t in threads:
        t.join()
    harvested += m.reset()["batches"]
    assert harvested == per_thread * threads_n


# --------------------------------------------------------------------------
# Stall attribution
# --------------------------------------------------------------------------

def test_stall_classification_thresholds():
    s = StallAttributor()
    assert s.observe(wait_s=0.0, busy_s=1.0) == "device_bound"
    assert s.observe(wait_s=0.04, busy_s=0.96) == "device_bound"
    assert s.observe(wait_s=0.1, busy_s=0.9) == "balanced"
    assert s.observe(wait_s=0.5, busy_s=0.5) == "host_bound"
    assert s.observe(wait_s=1.0, busy_s=0.0) == "host_bound"
    assert s.steps == 5
    rep = s.report()
    assert rep["counts"] == {"host_bound": 2, "device_bound": 2,
                             "balanced": 1}
    assert rep["last"] == "host_bound"
    assert 0.0 < rep["wait_fraction"] < 1.0
    assert sum(rep["fractions"].values()) == pytest.approx(1.0)


def test_stall_report_idle_and_threshold_validation():
    assert StallAttributor().report()["verdict"] == "idle"
    with pytest.raises(ValueError, match="device_bound_below"):
        StallAttributor(device_bound_below=0.5, host_bound_above=0.25)


def test_stall_host_side_sub_attribution():
    m = PipelineMetrics()
    m.record_batch(samples=1, nbytes=1, host_wait_s=3.0, stage_s=1.0)
    s = StallAttributor()
    s.observe(wait_s=1.0, busy_s=0.1)
    host = s.report(m)["host_side"]
    assert host["dominant"] == "production"
    assert host["production_fraction"] == pytest.approx(0.75)


def test_stall_mirrors_into_registry():
    reg = TelemetryRegistry()
    s = StallAttributor(registry=reg)
    s.observe(wait_s=1.0, busy_s=0.0)
    counters = reg.snapshot()["counters"]
    assert counters["loader.next_host_bound"] == 1
    assert counters["loader.delivery_wait_s"] == 1.0


# --------------------------------------------------------------------------
# Exporters
# --------------------------------------------------------------------------

def _populated_registry():
    reg = TelemetryRegistry()
    reg.counter("loader.batches").add(3)
    reg.counter("loader.host_wait_s").add(0.5)
    reg.gauge("shuffle_buffer.fill").set(42)
    h = reg.histogram("reader.pool_wait_s")
    for v in (0.001, 0.01, 0.1):
        h.observe(v)
    reg.histogram("loader.batch_bytes", bounds=SIZE_BOUNDS).observe(4096)
    with reg.span("petastorm_tpu.stage"):
        pass
    return reg


def test_prometheus_text_parses_and_is_consistent():
    reg = _populated_registry()
    text = to_prometheus_text(reg.snapshot())
    parsed = parse_prometheus_text(text)
    assert parsed["petastorm_tpu_loader_batches"][""] == 3.0
    assert parsed["petastorm_tpu_shuffle_buffer_fill"][""] == 42.0
    assert parsed["petastorm_tpu_reader_pool_wait_s_count"][""] == 3.0
    assert parsed["petastorm_tpu_reader_pool_wait_s_sum"][""] == pytest.approx(0.111)
    # Histogram buckets are cumulative and end at +Inf == _count.
    bucket_series = parsed["petastorm_tpu_reader_pool_wait_s_bucket"]
    values = [bucket_series[k] for k in bucket_series]
    assert values == sorted(values)
    assert bucket_series['le="+Inf"'] == 3.0
    # Span aggregates carry a name label.
    assert parsed["petastorm_tpu_span_count"][
        'name="petastorm_tpu.stage"'] == 1.0
    # Every sample line is well-formed (TYPE headers on all families).
    assert text.count("# TYPE") >= 5


def test_prometheus_parser_rejects_malformed():
    with pytest.raises(ValueError, match="malformed"):
        parse_prometheus_text("this is { not a metric\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("ok_name notanumber\n")


def test_json_snapshot_round_trips_with_documented_keys():
    reg = _populated_registry()
    snap = reg.snapshot()
    restored = from_json(to_json(snap))
    assert restored == json.loads(json.dumps(snap))  # JSON-safe throughout
    assert restored["schema_version"] == SNAPSHOT_SCHEMA_VERSION
    assert set(restored) == {"schema_version", "pipeline_id", "created_at",
                             "counters", "gauges", "histograms", "spans"}
    assert restored["pipeline_id"].startswith("p")
    assert restored["created_at"] > 0
    h = restored["histograms"]["reader.pool_wait_s"]
    assert set(h) == {"count", "sum", "min", "max", "p50", "p95", "p99",
                      "buckets"}
    assert set(restored["spans"]["petastorm_tpu.stage"]) == {
        "count", "total_s", "max_s"}


def test_write_snapshot_formats(tmp_path):
    reg = _populated_registry()
    jpath, ppath = str(tmp_path / "t.json"), str(tmp_path / "t.prom")
    write_snapshot(jpath, reg.snapshot(), fmt="json")
    write_snapshot(ppath, reg.snapshot(), fmt="prometheus")
    with open(jpath) as f:
        assert from_json(f.read())["counters"]["loader.batches"] == 3
    with open(ppath) as f:
        assert parse_prometheus_text(f.read())
    with pytest.raises(ValueError, match="fmt"):
        write_snapshot(jpath, reg.snapshot(), fmt="xml")


def test_periodic_exporter_writes_and_final_flush(tmp_path):
    reg = TelemetryRegistry()
    reg.counter("n").add(1)
    path = str(tmp_path / "snap.json")
    exp = PeriodicExporter(reg, path, interval_s=0.05).start()
    with pytest.raises(RuntimeError, match="already started"):
        exp.start()
    deadline = time.monotonic() + 5.0
    while not (tmp_path / "snap.json").exists():
        assert time.monotonic() < deadline, "exporter never wrote"
        time.sleep(0.01)
    reg.counter("n").add(1)
    exp.stop()  # final flush must capture the last add
    with open(path) as f:
        assert from_json(f.read())["counters"]["n"] == 2
    with pytest.raises(ValueError, match="interval_s"):
        PeriodicExporter(reg, path, interval_s=0)


# --------------------------------------------------------------------------
# trace() / traced_span() — jax.profiler coherence and the no-op path
# --------------------------------------------------------------------------

@pytest.fixture
def _reset_trace_resolution():
    saved = metrics_mod._TRACE_ANNOTATION
    yield
    metrics_mod._TRACE_ANNOTATION = saved


def test_trace_noop_when_jax_profiler_unavailable(monkeypatch,
                                                  _reset_trace_resolution):
    """With jax.profiler unimportable, trace() must resolve to (and cache)
    the no-op path instead of raising — worker processes pinned off the
    accelerator run exactly this branch."""
    metrics_mod._TRACE_ANNOTATION = None  # force re-resolution
    monkeypatch.setitem(__import__("sys").modules, "jax.profiler", None)
    ran = False
    with trace("petastorm_tpu.test"):
        ran = True
    assert ran
    assert metrics_mod._TRACE_ANNOTATION is False  # cached: no retry per call


def test_trace_noop_path_is_reentrant(_reset_trace_resolution):
    metrics_mod._TRACE_ANNOTATION = False
    with trace("a"), trace("b"):
        pass


def test_traced_span_mirrors_name_into_recorder(_reset_trace_resolution):
    metrics_mod._TRACE_ANNOTATION = False  # profiler absent: span still lands
    reg = TelemetryRegistry()
    with traced_span("petastorm_tpu.stage", reg):
        pass
    assert reg.recorder.spans()[0].name == "petastorm_tpu.stage"


def test_traced_span_without_registry_is_plain_trace(_reset_trace_resolution):
    metrics_mod._TRACE_ANNOTATION = False
    with traced_span("petastorm_tpu.stage"):
        pass


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def test_cli_dump_pretty_json_prometheus(tmp_path, capsys):
    path = str(tmp_path / "snap.json")
    write_snapshot(path, _populated_registry().snapshot())
    assert telemetry_cli(["dump", path]) == 0
    pretty = capsys.readouterr().out
    assert "loader.batches" in pretty and "per-stage seconds" in pretty
    assert telemetry_cli(["dump", path, "--format", "json"]) == 0
    assert from_json(capsys.readouterr().out)["counters"]["loader.batches"] == 3
    assert telemetry_cli(["dump", path, "--format", "prometheus"]) == 0
    assert parse_prometheus_text(capsys.readouterr().out)


def test_cli_watch_count_and_missing_file(tmp_path, capsys):
    path = str(tmp_path / "snap.json")
    write_snapshot(path, _populated_registry().snapshot())
    assert telemetry_cli(["watch", path, "--interval", "0.01",
                          "--count", "2"]) == 0
    assert capsys.readouterr().out.count("schema_version") == 2
    assert telemetry_cli(["dump", str(tmp_path / "nope.json")]) == 1
    assert "cannot read" in capsys.readouterr().err


# --------------------------------------------------------------------------
# Unified pool diagnostics schema (satellite)
# --------------------------------------------------------------------------

_UNIFIED_KEYS = {"output_queue_size", "items_ventilated", "items_processed",
                 "items_inprocess", "workers_count",
                 "results_queue_capacity"}


def test_pool_diagnostics_schema_is_unified():
    from petastorm_tpu.workers_pool.dummy_pool import DummyPool
    from petastorm_tpu.workers_pool.process_pool import ProcessPool
    from petastorm_tpu.workers_pool.thread_pool import ThreadPool

    pools = [DummyPool(), ThreadPool(workers_count=2)]
    proc = ProcessPool(workers_count=1, transport="zmq")
    pools.append(proc)
    try:
        for pool in pools:
            d = pool.diagnostics
            assert set(d) == _UNIFIED_KEYS, type(pool).__name__
            assert all(isinstance(v, int) for v in d.values()), \
                type(pool).__name__
    finally:
        shutil.rmtree(proc._ipc_dir, ignore_errors=True)


# --------------------------------------------------------------------------
# End-to-end wiring: Reader -> pool -> loader -> one registry
# --------------------------------------------------------------------------

def test_reader_diagnostics_include_unified_schema_and_telemetry(
        synthetic_dataset):
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False,
                     reader_pool_type="dummy") as reader:
        for _ in range(20):
            next(reader)
        d = reader.diagnostics
    assert _UNIFIED_KEYS <= set(d)
    assert "ventilator_backlog" in d
    snap = d["telemetry"]
    assert snap["schema_version"] == SNAPSHOT_SCHEMA_VERSION
    assert snap["counters"]["reader.rows"] == 20
    # Dummy pool decodes inline in-process: decode histogram populated.
    assert snap["histograms"]["worker.decode_s"]["count"] > 0
    assert snap["histograms"]["reader.pool_wait_s"]["count"] > 0
    assert snap["gauges"]["pool.results_queue_depth"] is not None
    assert snap["gauges"]["ventilator.backlog"] is not None
    # The live snapshot exports cleanly in both formats.
    assert parse_prometheus_text(to_prometheus_text(snap))
    assert from_json(to_json(snap)) == json.loads(json.dumps(snap))


def test_thread_pool_reader_populates_worker_decode(synthetic_dataset):
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False, reader_pool_type="thread",
                     workers_count=2) as reader:
        for _ in range(20):
            next(reader)
        snap = reader.telemetry.snapshot()
    assert snap["histograms"]["worker.decode_s"]["count"] > 0


def test_loader_adopts_reader_registry_and_stage_breakdown(scalar_dataset):
    from petastorm_tpu.jax import BatchedDataLoader
    with make_batch_reader(scalar_dataset.url, schema_fields=["id"],
                           shuffle_row_groups=False,
                           reader_pool_type="dummy") as reader:
        loader = BatchedDataLoader(reader, batch_size=25,
                                   shuffling_queue_capacity=60, seed=0)
        n_batches = len(list(loader))
        assert loader.telemetry is reader.telemetry  # ONE pipeline registry
        breakdown = loader.stage_breakdown()
        stall = loader.stall_report()
    assert n_batches == 4
    assert set(breakdown) == {"decode_s", "pool_queue_s", "shuffle_s",
                              "host_wait_s", "stage_s", "device_put_wait_s"}
    assert all(v >= 0.0 for v in breakdown.values())
    assert breakdown["decode_s"] > 0.0       # dummy pool decodes in-process
    assert breakdown["shuffle_s"] > 0.0      # shuffling buffer was active
    assert stall["steps"] == n_batches - 1   # first delivery excluded
    assert stall["verdict"] in ("host_bound", "device_bound", "balanced")
    assert stall["host_side"]["dominant"] in ("production", "staging")
    # Shuffle-buffer gauges were registered against the live buffer.
    gauges = loader.telemetry.snapshot()["gauges"]
    assert gauges["shuffle_buffer.capacity"] is not None
    assert "loader.prefetch_queue_depth" in gauges


def test_second_loader_over_same_reader_starts_at_zero(scalar_dataset):
    """The registry is pipeline-cumulative, but each loader's metrics /
    stage_breakdown view is per-loader: a second loader over the same
    reader must not inherit the first one's totals."""
    from petastorm_tpu.jax import BatchedDataLoader
    with make_batch_reader(scalar_dataset.url, schema_fields=["id"],
                           shuffle_row_groups=False,
                           reader_pool_type="dummy") as reader:
        first = BatchedDataLoader(reader, batch_size=25,
                                  shuffling_queue_capacity=60, seed=0)
        list(first)
        assert first.metrics.batches > 0
        first_bd = first.stage_breakdown()
        assert first_bd["shuffle_s"] > 0.0

        second = BatchedDataLoader(reader, batch_size=25,
                                   shuffling_queue_capacity=60, seed=0)
        assert second.metrics.batches == 0
        assert second.metrics.samples == 0
        bd = second.stage_breakdown()
        assert bd["shuffle_s"] == 0.0
        assert bd["host_wait_s"] == 0.0
        assert bd["device_put_wait_s"] == 0.0
        # The shared registry kept the pipeline-cumulative totals.
        assert reader.telemetry.snapshot()["counters"]["loader.batches"] \
            == first.metrics.batches


def test_gauge_clear_function_is_identity_checked():
    """A stale iteration's teardown must not null the closure a newer
    iteration re-registered under the same gauge name."""
    reg = TelemetryRegistry()
    old_fn, new_fn = (lambda: 1.0), (lambda: 2.0)
    g = reg.gauge("q.depth", old_fn)
    reg.gauge("q.depth", new_fn)      # newer iteration re-registers
    g.clear_function(old_fn)          # stale teardown: no-op
    assert g.value == 2.0
    g.clear_function(new_fn)          # the owner's teardown clears
    assert g._fn is None


def test_pipeline_metrics_survive_registry_reset():
    """telemetry.reset() zeroes the shared counters underneath live views;
    deltas must re-baseline at the reset point, never go negative."""
    m = PipelineMetrics()
    m.record_batch(samples=8, nbytes=64, host_wait_s=0.1, stage_s=0.2)
    m.telemetry.reset()
    assert m.batches == 0 and m.as_dict()["samples"] == 0
    m.record_batch(samples=4, nbytes=32, host_wait_s=0.1, stage_s=0.2)
    assert m.batches == 1 and m.samples == 4


def test_dummy_pool_inline_decode_not_double_counted():
    """DummyPool decodes inline inside get_results; the pool-wait timer
    must subtract that time so decode_s and pool_queue_s stay disjoint."""
    from petastorm_tpu.reader import _PoolWaitTimer
    from petastorm_tpu.workers_pool.dummy_pool import DummyPool

    class _SleepWorker:
        def __init__(self, worker_id, publish, args):
            self._publish = publish

        def process(self, item, **kwargs):
            time.sleep(0.02)
            self._publish([item])

        def shutdown(self):
            pass

    reg = make_registry()
    pool = DummyPool()
    pool.telemetry = reg
    pool.start(_SleepWorker)
    timer = _PoolWaitTimer(pool, reg)
    for i in range(3):
        pool.ventilate(i)
    for _ in range(3):
        timer.get_results()
    hists = reg.snapshot()["histograms"]
    assert hists["worker.decode_s"]["sum"] >= 0.05
    assert hists["reader.pool_wait_s"]["sum"] < 0.02


def test_stall_attribution_sees_consumer_step_time(synthetic_dataset):
    """The consumer's device step elapses while the loader generator is
    suspended in its yield; busy_s must span that suspension. A slow
    consumer over a fast pipeline is device_bound — the regression was
    timing only generator-resume overhead (~µs), which classified every
    run host_bound regardless of the consumer."""
    from petastorm_tpu.jax import DataLoader
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False,
                     reader_pool_type="dummy") as reader:
        loader = DataLoader(reader, batch_size=10)
        for _ in loader:
            time.sleep(0.05)  # the "device step"
        rep = loader.stall_report()
    assert rep["consumer_busy_s"] >= 0.3, rep
    assert rep["verdict"] == "device_bound", rep


def test_metrics_reset_leaves_registry_counters_cumulative():
    """PipelineMetrics.reset() advances its baseline; the shared registry
    counters never decrease (Prometheus counter semantics)."""
    m = PipelineMetrics()
    m.record_batch(samples=8, nbytes=64, host_wait_s=0.1, stage_s=0.2)
    m.reset()
    assert m.batches == 0
    assert m.telemetry.snapshot()["counters"]["loader.batches"] == 1


def test_gauge_closures_released_after_iteration(synthetic_dataset):
    """Prefetch-queue and shuffle-buffer gauges must not pin the queue /
    buffer after iteration ends — the registry lives as long as the
    reader."""
    import gc
    import weakref
    from petastorm_tpu.jax import DataLoader
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False,
                     reader_pool_type="dummy") as reader:
        loader = DataLoader(reader, batch_size=10,
                            shuffling_queue_capacity=50, seed=1)
        it = iter(loader)
        next(it)
        fill = reader.telemetry.gauge("shuffle_buffer.fill")
        buf_ref = weakref.ref(fill._fn.__closure__[0].cell_contents)
        assert buf_ref() is not None
        it.close()  # early consumer exit, mid-epoch
        gc.collect()
    assert buf_ref() is None, "shuffling buffer retained after close"
    assert fill._fn is None
    depth = reader.telemetry.gauge("loader.prefetch_queue_depth")
    assert depth._fn is None
    # Capacity is a plain value, never a loader-pinning closure.
    capacity = reader.telemetry.gauge("loader.prefetch_queue_capacity")
    assert capacity._fn is None and capacity.value == 2


def test_row_loader_stage_breakdown(synthetic_dataset):
    from petastorm_tpu.jax import DataLoader
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False,
                     reader_pool_type="dummy") as reader:
        loader = DataLoader(reader, batch_size=10,
                            shuffling_queue_capacity=50, seed=1)
        batches = list(loader)
        breakdown = loader.stage_breakdown()
    assert len(batches) == 10
    assert breakdown["shuffle_s"] > 0.0
    assert breakdown["stage_s"] > 0.0


def test_reader_env_export_writes_snapshot(synthetic_dataset, tmp_path,
                                           monkeypatch):
    path = str(tmp_path / "live.json")
    monkeypatch.setenv(TELEMETRY_EXPORT_ENV, path)
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False,
                     reader_pool_type="dummy") as reader:
        for _ in range(10):
            next(reader)
    # Reader.stop() flushes a final snapshot even if no interval elapsed.
    with open(path) as f:
        snap = from_json(f.read())
    assert snap["counters"]["reader.rows"] == 10


def test_reader_records_spans_by_default(synthetic_dataset):
    """No knob: a reader's registry records its span sites from
    construction."""
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False,
                     reader_pool_type="dummy") as reader:
        for _ in range(10):
            next(reader)
        spans = reader.telemetry.snapshot()["spans"]
    assert spans["petastorm_tpu.worker_decode"]["count"] > 0
    assert spans["petastorm_tpu.pool_wait"]["count"] > 0


def test_make_registry_ring_on_and_bounded():
    """The default ring holds a measured window whole (four chips at the
    image cell's rate: ~19.5k spans) and never grows past its capacity."""
    from petastorm_tpu.telemetry.recorder import SPAN_CAPACITY
    reg = make_registry()
    assert reg.recorder.enabled is True
    assert reg.recorder.capacity == SPAN_CAPACITY >= 32768
    small = TelemetryRegistry(span_capacity=8)
    for i in range(20):
        with small.span(f"s{i}"):
            pass
    assert len(small.recorder.spans()) == 8
    assert small.recorder.dropped == 12
    small.recorder.disable()
    with small.span("off"):
        pass
    assert small.recorder.dropped == 12


# --------------------------------------------------------------------------
# tools/check_monotonic.py lint guard (satellite)
# --------------------------------------------------------------------------

def test_check_monotonic_flags_wall_clock(tmp_path):
    from tools.check_monotonic import check_file, main as lint_main

    bad = tmp_path / "bad.py"
    bad.write_text("import time\n"
                   "deadline = time.time() + 5\n"
                   "stamp = time.time()  # wall-clock-ok\n"
                   "from time import time as now\n"
                   "t = now()\n"
                   "ok = time.monotonic()\n")
    violations = check_file(str(bad))
    assert len(violations) == 2            # line 2 and the aliased call
    assert "bad.py:2" in violations[0]
    assert "bad.py:5" in violations[1]
    assert lint_main([str(bad)]) == 1

    good = tmp_path / "good.py"
    good.write_text("import time\nt = time.perf_counter()\n")
    assert check_file(str(good)) == []
    assert lint_main([str(good)]) == 0


def test_repo_hot_path_is_monotonic_clean():
    from tools.check_monotonic import main as lint_main
    assert lint_main([]) == 0  # [] = the default hot-path set


# --------------------------------------------------------------------------
# the CLI's per-stage rendering
# --------------------------------------------------------------------------

def test_stage_breakdown_keys_match_cli_stage_order():
    """The CLI's stage breakdown derives from the documented metric
    schema: every key it renders is a stage of ``_STAGE_ORDER``."""
    from petastorm_tpu.telemetry.__main__ import _STAGE_ORDER, _stage_breakdown
    reg = _populated_registry()
    reg.counter("loader.shuffle_s").add(0.1)
    out = _stage_breakdown(reg.snapshot())
    assert set(out) <= set(_STAGE_ORDER)
    assert out["reader.pool_wait_s"] == pytest.approx(0.111)
    assert out["loader.shuffle_s"] == pytest.approx(0.1)
