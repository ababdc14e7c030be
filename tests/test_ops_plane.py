"""Ops plane (docs/observability.md "Ops plane"): rolling time-series
telemetry, cross-host federation, anomaly detection, and the postmortem
black box — plus their CI surfaces (`telemetry check --anomaly`,
`telemetry timeline`/`top`/`postmortem`, the metric-docs lint).

All tier-1 except where marked ``process_pool`` (spawned-worker e2e).
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.telemetry import (MetricsTimeline, PeriodicExporter,
                                     SeriesSpec, TelemetryRegistry,
                                     TimelineSampler, federate_snapshots,
                                     federate_timelines, write_snapshot)
from petastorm_tpu.telemetry import postmortem as postmortem_mod
from petastorm_tpu.telemetry.__main__ import main as telemetry_cli
from petastorm_tpu.telemetry.anomaly import (AnomalyMonitor, AnomalyRule,
                                             default_anomaly_rules,
                                             detect_over_timeline)
from petastorm_tpu.telemetry.postmortem import (BlackBox, load_bundle,
                                                render_report)
from petastorm_tpu.telemetry.timeseries import (concat_timeline_dicts,
                                                timeline_interval_from_env)

pytestmark = pytest.mark.opsplane


@pytest.fixture(autouse=True)
def _reset_bundle_cap():
    """The per-process bundle cap is global state; tests must not starve
    each other."""
    postmortem_mod._process_bundle_count = 0
    yield
    postmortem_mod._process_bundle_count = 0


@pytest.fixture(scope="module")
def scalar_store(tmp_path_factory):
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = tmp_path_factory.mktemp("ops_scalar")
    n = 20000
    pq.write_table(
        pa.table({"id": pa.array(np.arange(n, dtype=np.int64)),
                  "v": pa.array(np.arange(n, dtype=np.float64))}),
        str(path / "part0.parquet"), row_group_size=500)
    return f"file://{path}"


def _windows(values, name="rows_per_s", interval=1.0):
    """Synthetic timeline dict with one series."""
    return {"interval_s": interval, "window_count": 120,
            "windows_total": len(values),
            "windows": [{"index": i, "t_s": (i + 1) * interval,
                         "dt_s": interval,
                         "series": (dict(v) if isinstance(v, dict)
                                    else {name: v})}
                        for i, v in enumerate(values)]}


# ==========================================================================
# MetricsTimeline
# ==========================================================================

class TestTimeline:
    def test_series_spec_validation(self):
        with pytest.raises(ValueError, match="unknown kind"):
            SeriesSpec("x", "median", "a.b")
        with pytest.raises(ValueError, match="at most one"):
            SeriesSpec("x{}", "rate", "a.*.b.*")
        with pytest.raises(ValueError, match="placeholder"):
            SeriesSpec("x", "rate", "a.*.b")
        with pytest.raises(ValueError, match="interval_s"):
            MetricsTimeline(interval_s=0)

    def test_first_sample_is_baseline_only(self):
        tl = MetricsTimeline(interval_s=1.0)
        assert tl.sample({"counters": {"reader.rows": 5.0}}) is None
        assert tl.windows() == []

    def test_counter_rate_derivation(self):
        tl = MetricsTimeline(interval_s=1.0)
        t0 = time.perf_counter()
        tl.sample({"counters": {"reader.rows": 100.0}}, now_s=t0)
        w = tl.sample({"counters": {"reader.rows": 350.0}}, now_s=t0 + 2.0)
        assert w["series"]["rows_per_s"] == pytest.approx(125.0)
        assert w["dt_s"] == pytest.approx(2.0)

    def test_counter_reset_never_goes_negative(self):
        """Satellite: a registry.reset() mid-stream restarts the counter;
        the windowed delta is the NEW value, never negative."""
        registry = TelemetryRegistry()
        tl = MetricsTimeline(interval_s=1.0)
        c = registry.counter("reader.rows")
        c.add(1000)
        t0 = time.perf_counter()
        tl.sample(registry.metrics_view(), now_s=t0)
        registry.reset()
        c.add(40)
        w = tl.sample(registry.metrics_view(), now_s=t0 + 1.0)
        assert w["series"]["rows_per_s"] == pytest.approx(40.0)
        for window in tl.windows():
            for value in window["series"].values():
                assert value is None or value >= 0

    def test_histogram_reset_never_goes_negative(self):
        registry = TelemetryRegistry()
        tl = MetricsTimeline(interval_s=1.0)
        h = registry.histogram("worker.decode_s")
        for _ in range(50):
            h.observe(0.01)
        t0 = time.perf_counter()
        tl.sample(registry.metrics_view(), now_s=t0)
        registry.reset()
        for _ in range(10):
            h.observe(0.05)
        w = tl.sample(registry.metrics_view(), now_s=t0 + 1.0)
        assert w["series"]["decode_p99_s"] > 0

    def test_frac_clamped_to_unit_interval(self):
        tl = MetricsTimeline(
            interval_s=1.0,
            series=(SeriesSpec("busy", "frac", "x.busy_s"),))
        t0 = time.perf_counter()
        tl.sample({"counters": {"x.busy_s": 0.0}}, now_s=t0)
        w = tl.sample({"counters": {"x.busy_s": 9.0}}, now_s=t0 + 2.0)
        assert w["series"]["busy"] == 1.0

    def test_gauge_passthrough_and_dead_gauge(self):
        tl = MetricsTimeline(
            interval_s=1.0,
            series=(SeriesSpec("lag", "gauge", "discovery.ingest_lag_s"),))
        t0 = time.perf_counter()
        tl.sample({"gauges": {"discovery.ingest_lag_s": 1.0}}, now_s=t0)
        w = tl.sample({"gauges": {"discovery.ingest_lag_s": None}},
                      now_s=t0 + 1.0)
        assert w["series"]["lag"] is None  # dead gauge: honest gap

    def test_windowed_quantile_uses_delta_not_cumulative(self):
        """p99 must describe the WINDOW's observations: 1000 fast samples
        before the window must not drown 10 slow ones inside it."""
        registry = TelemetryRegistry()
        tl = MetricsTimeline(interval_s=1.0)
        h = registry.histogram("worker.decode_s")
        for _ in range(1000):
            h.observe(0.001)
        t0 = time.perf_counter()
        tl.sample(registry.metrics_view(), now_s=t0)
        for _ in range(10):
            h.observe(1.0)
        w = tl.sample(registry.metrics_view(), now_s=t0 + 1.0)
        assert w["series"]["decode_p99_s"] > 0.1

    def test_ring_bound(self):
        tl = MetricsTimeline(interval_s=1.0, window_count=4)
        t0 = time.perf_counter()
        for i in range(10):
            tl.sample({"counters": {"reader.rows": float(i)}},
                      now_s=t0 + i)
        assert len(tl.windows()) == 4
        assert tl.as_dict()["windows_total"] == 9
        assert [w["index"] for w in tl.windows()] == [5, 6, 7, 8]

    def test_family_wildcard_series(self):
        tl = MetricsTimeline(interval_s=1.0)
        t0 = time.perf_counter()
        counters = {"mesh.host0.rows": 0.0, "mesh.host3.rows": 0.0}
        tl.sample({"counters": counters}, now_s=t0)
        counters = {"mesh.host0.rows": 100.0, "mesh.host3.rows": 50.0}
        w = tl.sample({"counters": counters}, now_s=t0 + 1.0)
        assert w["series"]["mesh.host0.rows_per_s"] == pytest.approx(100.0)
        assert w["series"]["mesh.host3.rows_per_s"] == pytest.approx(50.0)

    def test_default_series_cover_live_data_and_mixer(self):
        """Satellite: ingest_lag_s / max_admission_lag_s and the mixer
        starvation gauges are first-class default series."""
        tl = MetricsTimeline(interval_s=1.0)
        t0 = time.perf_counter()
        view = {"counters": {"mixer.m0.starved_total": 0.0},
                "gauges": {"discovery.ingest_lag_s": 3.0,
                           "discovery.max_admission_lag_s": 0.4,
                           "mixer.m0.lag_s": 1.5}}
        tl.sample(view, now_s=t0)
        view = {"counters": {"mixer.m0.starved_total": 2.0},
                "gauges": {"discovery.ingest_lag_s": 4.0,
                           "discovery.max_admission_lag_s": 0.5,
                           "mixer.m0.lag_s": 2.5}}
        w = tl.sample(view, now_s=t0 + 1.0)
        assert w["series"]["ingest_lag_s"] == 4.0
        assert w["series"]["max_admission_lag_s"] == 0.5
        assert w["series"]["mixer.m0.lag_s"] == 2.5
        assert w["series"]["mixer.m0.starved_per_s"] == pytest.approx(2.0)

    def test_listener_fires_and_exceptions_swallowed(self):
        tl = MetricsTimeline(interval_s=1.0)
        seen = []
        tl.add_listener(lambda w: (_ for _ in ()).throw(RuntimeError()))
        tl.add_listener(seen.append)
        t0 = time.perf_counter()
        tl.sample({"counters": {"reader.rows": 0.0}}, now_s=t0)
        tl.sample({"counters": {"reader.rows": 10.0}}, now_s=t0 + 1)
        assert len(seen) == 1 and seen[0]["series"]["rows_per_s"] == 10.0

    def test_as_dict_json_safe_and_series_accessors(self):
        tl = MetricsTimeline(interval_s=0.5)
        t0 = time.perf_counter()
        for i in range(3):
            tl.sample({"counters": {"reader.rows": float(i * 10)}},
                      now_s=t0 + i)
        d = tl.as_dict()
        json.dumps(d)
        assert d["interval_s"] == 0.5
        assert tl.series("rows_per_s") == [10.0, 10.0]
        assert "rows_per_s" in tl.series_names()
        assert tl.latest()["index"] == 1

    def test_concat_timeline_dicts(self):
        a = _windows([1.0, 2.0])
        b = _windows([3.0])
        merged = concat_timeline_dicts([a, b])
        assert [w["index"] for w in merged["windows"]] == [0, 1, 2]
        assert [w["series"]["rows_per_s"]
                for w in merged["windows"]] == [1.0, 2.0, 3.0]
        assert merged["windows"][2]["t_s"] > merged["windows"][1]["t_s"]
        assert concat_timeline_dicts([])["windows"] == []

    def test_sampler_lifecycle_and_terminal_window(self):
        registry = TelemetryRegistry()
        tl = MetricsTimeline(interval_s=30.0)  # no periodic tick in-test
        sampler = TimelineSampler(registry, tl, interval_s=30.0).start()
        registry.counter("reader.rows").add(42)
        sampler.stop()  # takes the terminal window
        assert len(tl.windows()) == 1
        assert tl.windows()[0]["series"]["rows_per_s"] > 0
        assert registry.counter("timeline.samples_total").value == 1

    def test_timeline_rides_snapshot_not_metrics_view(self):
        registry = TelemetryRegistry()
        tl = MetricsTimeline(interval_s=1.0)
        registry.timeline = tl
        t0 = time.perf_counter()
        tl.sample(registry.metrics_view(), now_s=t0)
        registry.counter("reader.rows").add(1)
        tl.sample(registry.metrics_view(), now_s=t0 + 1)
        assert "timeline" in registry.snapshot()
        assert "timeline" not in registry.metrics_view()

    def test_interval_from_env(self, monkeypatch):
        monkeypatch.delenv("PETASTORM_TPU_TIMELINE", raising=False)
        assert timeline_interval_from_env() is None
        monkeypatch.setenv("PETASTORM_TPU_TIMELINE", "0.5")
        assert timeline_interval_from_env() == 0.5
        monkeypatch.setenv("PETASTORM_TPU_TIMELINE", "yes")
        assert timeline_interval_from_env() == 1.0
        monkeypatch.setenv("PETASTORM_TPU_TIMELINE", "0")
        assert timeline_interval_from_env() is None
        # An intended off-switch (or a typo) must never silently enable
        # the sampler at the default interval.
        for off in ("off", "false", "no", "0.5s"):
            monkeypatch.setenv("PETASTORM_TPU_TIMELINE", off)
            assert timeline_interval_from_env() is None, off


# ==========================================================================
# Federation
# ==========================================================================

class TestFederation:
    def test_snapshot_rollup_sums_and_prefixes(self):
        fed = federate_snapshots({
            "h0": {"counters": {"reader.rows": 100.0, "io.bytes_read": 10.0},
                   "gauges": {"ventilator.backlog": 3.0}},
            "h1": {"counters": {"reader.rows": 60.0}},
        })
        assert fed["counters"]["reader.rows"] == 160.0
        assert fed["counters"]["h0:reader.rows"] == 100.0
        assert fed["counters"]["h1:reader.rows"] == 60.0
        assert fed["gauges"]["h0:ventilator.backlog"] == 3.0
        assert fed["skew"]["rows_spread_frac"] == pytest.approx(0.4)
        assert fed["members"] == ["h0", "h1"]

    def test_histogram_merge_exact_and_approximate(self):
        from petastorm_tpu.telemetry import StreamingHistogram
        from petastorm_tpu.telemetry.federation import merge_histogram_dicts
        a, b = StreamingHistogram([1.0, 10.0]), StreamingHistogram([1.0, 10.0])
        a.observe(0.5)
        b.observe(5.0)
        b.observe(50.0)
        merged = merge_histogram_dicts(a.as_dict(), b.as_dict())
        assert merged["count"] == 3
        assert merged["buckets"] == [[1.0, 1], [10.0, 2], [None, 3]]
        assert merged["p50"] > 0
        other = StreamingHistogram([2.0])
        other.observe(1.0)
        approx = merge_histogram_dicts(a.as_dict(), other.as_dict())
        assert approx["approximate"] and approx["count"] == 2

    def test_timeline_federation_fleet_and_skew(self):
        fed = federate_timelines({
            "h0": _windows([100.0, 100.0, 100.0]),
            "h1": _windows([100.0, 100.0, 25.0]),
        })
        assert fed["depth"] == 3
        assert fed["series"]["h0:rows_per_s"] == [100.0, 100.0, 100.0]
        assert fed["series"]["fleet:rows_per_s"] == [200.0, 200.0, 125.0]
        assert fed["series"]["skew:rows_per_s"][-1] == pytest.approx(0.75)

    def test_timeline_federation_aligns_from_newest_end(self):
        """Members start staggered; only the common newest suffix is
        comparable."""
        fed = federate_timelines({
            "h0": _windows([1.0, 2.0, 3.0, 4.0]),
            "h1": _windows([30.0, 40.0]),
        })
        assert fed["depth"] == 2
        assert fed["series"]["h0:rows_per_s"] == [3.0, 4.0]
        assert fed["series"]["fleet:rows_per_s"] == [33.0, 44.0]

    def test_tenant_keying_is_a_parameter(self):
        fed = federate_snapshots(
            {"tenant7": {"counters": {"reader.rows": 1.0}}},
            key_label="tenant")
        assert fed["key_label"] == "tenant"
        assert "tenant7:reader.rows" in fed["counters"]

    def test_federation_racing_reset_hammer(self):
        """Satellite: federation merge + timeline sampling racing
        registry.reset() and trace-ring growth must neither crash nor
        produce negative rates."""
        registry = TelemetryRegistry()
        tl = MetricsTimeline(interval_s=0.001)
        registry.timeline = tl
        c = registry.counter("reader.rows")
        stop = threading.Event()
        errors = []

        def mutate():
            while not stop.is_set():
                c.add(5)
                registry.record_event("e", {"x": 1})
                registry.recorder.record("s", 0.0, 0.001, stage="decode")

        def reset():
            while not stop.is_set():
                registry.reset()
                time.sleep(0)

        def grow():
            # Recorder ring growth mid-flight (enable_trace re-allocates
            # the deque) racing appends and snapshot reads.
            while not stop.is_set():
                registry.recorder.enable_trace(capacity=8192)
                time.sleep(0.001)

        def observe():
            while not stop.is_set():
                try:
                    tl.sample(registry.metrics_view())
                    fed = federate_snapshots({"a": registry.snapshot(),
                                              "b": registry.snapshot()})
                    json.dumps(fed, default=repr)
                    for w in tl.windows():
                        r = w["series"].get("rows_per_s")
                        assert r is None or r >= 0
                except Exception as e:  # noqa: BLE001 - the hammer's assert
                    errors.append(e)
                    return

        registry.recorder.enable()
        threads = [threading.Thread(target=fn)
                   for fn in (mutate, reset, grow, observe, observe)]
        for t in threads:
            t.start()
        time.sleep(0.6)
        stop.set()
        for t in threads:
            t.join(5.0)
        assert not errors, errors[0]


# ==========================================================================
# Anomaly detection
# ==========================================================================

class TestAnomaly:
    def test_collapse_fires_once_per_incident(self):
        tl = _windows([1000.0] * 8 + [10.0] * 4)
        dets = detect_over_timeline(tl)
        collapses = [d for d in dets if d["rule"] == "throughput_collapse"]
        assert len(collapses) == 1
        # persist=2: the first collapsed window (8) is a burst gap; the
        # second consecutive one (9) is the incident.
        assert collapses[0]["window"] == 9
        assert "EWMA" in collapses[0]["detail"]
        assert "consecutive" in collapses[0]["detail"]

    def test_collapse_recovery_rearms(self):
        tl = _windows([1000.0] * 8 + [10.0] * 2 + [1000.0] * 4
                      + [10.0] * 2)
        dets = [d for d in detect_over_timeline(tl)
                if d["rule"] == "throughput_collapse"]
        assert [d["window"] for d in dets] == [9, 15]

    def test_collapse_respects_min_value(self):
        """An idle pipeline collapsing from 3 rows/s to 1 is noise."""
        tl = _windows([3.0] * 8 + [1.0] * 4)
        assert not [d for d in detect_over_timeline(tl)
                    if d["kind"] == "collapse"]

    def test_spike_fires_on_stall_jump(self):
        tl = _windows([0.01] * 10 + [0.6] * 2, name="stall_frac")
        dets = [d for d in detect_over_timeline(tl)
                if d["rule"] == "stall_spike"]
        assert len(dets) == 1 and dets[0]["window"] == 11

    def test_spike_absolute_floor(self):
        # Statistically loud but absolutely harmless: 0.001 -> 0.05.
        tl = _windows([0.001] * 10 + [0.05] * 2, name="stall_frac")
        assert not [d for d in detect_over_timeline(tl)
                    if d["rule"] == "stall_spike"]

    def test_slope_fires_on_monotonic_lag_growth(self):
        tl = _windows([1.0, 1.5, 2.2, 3.0, 4.1, 5.0], name="ingest_lag_s")
        dets = [d for d in detect_over_timeline(tl)
                if d["rule"] == "ingest_lag_growth"]
        assert dets and dets[0]["window"] == 4

    def test_slope_needs_monotonicity(self):
        tl = _windows([1.0, 4.0, 2.0, 5.0, 3.0, 6.0, 2.0, 5.5],
                      name="ingest_lag_s")
        assert not [d for d in detect_over_timeline(tl)
                    if d["rule"] == "ingest_lag_growth"]

    def test_skew_needs_persistence(self):
        burst = {"mesh.host0.rows_per_s": 1000.0,
                 "mesh.host1.rows_per_s": 100.0}
        even = {"mesh.host0.rows_per_s": 1000.0,
                "mesh.host1.rows_per_s": 900.0}
        # 3 skewed windows, then recovery: under the 4-window persistence.
        tl = _windows([burst, burst, burst, even, burst, burst])
        assert not [d for d in detect_over_timeline(tl)
                    if d["rule"] == "host_skew_divergence"]
        tl = _windows([burst] * 4)
        dets = [d for d in detect_over_timeline(tl)
                if d["rule"] == "host_skew_divergence"]
        assert dets and dets[0]["window"] == 3

    def test_steady_noisy_series_no_false_positive(self):
        rng = np.random.default_rng(0)
        values = (1000.0 + 50.0 * rng.standard_normal(60)).tolist()
        assert detect_over_timeline(_windows(values)) == []

    def test_monitor_records_events_counters_and_callback(self):
        registry = TelemetryRegistry()
        fired = []
        monitor = AnomalyMonitor(registry, on_detection=fired.append)
        for i, v in enumerate([1000.0] * 8 + [10.0] * 3):
            monitor.observe_window(
                {"index": i, "t_s": float(i), "dt_s": 1.0,
                 "series": {"rows_per_s": v}})
        assert registry.counter("anomaly.detections_total").value == 1
        assert registry.counter(
            "anomaly.throughput_collapse_total").value == 1
        events = registry.events("anomaly.throughput_collapse")
        assert len(events) == 1
        assert fired[0]["rule"] == "throughput_collapse"
        rep = monitor.report()
        assert rep["detections_total"] == 1
        assert rep["currently_active"] == ["throughput_collapse"]

    def test_monitor_detection_list_is_bounded(self):
        registry = TelemetryRegistry()
        monitor = AnomalyMonitor(registry)
        # A flapping detector on a long-lived pipeline: warm up, collapse
        # for `persist` windows (fires), recover one window (re-arms) —
        # repeat far past the retention cap.
        i = 0
        for _ in range(8):  # warm-up
            monitor.observe_window({"index": i, "t_s": float(i), "dt_s": 1.0,
                                    "series": {"rows_per_s": 1000.0}})
            i += 1
        for _ in range(AnomalyMonitor.MAX_DETECTIONS + 20):
            for v in (10.0, 10.0, 1000.0):  # fire, then recover/re-arm
                monitor.observe_window(
                    {"index": i, "t_s": float(i), "dt_s": 1.0,
                     "series": {"rows_per_s": v}})
                i += 1
        rep = monitor.report()
        assert rep["detections_total"] > AnomalyMonitor.MAX_DETECTIONS
        assert len(rep["detections"]) == AnomalyMonitor.MAX_DETECTIONS
        # Newest retained: the last detection's window is the most recent.
        assert rep["detections"][-1]["window"] > rep["detections"][0]["window"]

    def test_offline_replay_matches_live(self):
        values = [800.0] * 10 + [10.0] * 3 + [800.0] * 5
        registry = TelemetryRegistry()
        monitor = AnomalyMonitor(registry)
        live = []
        for w in _windows(values)["windows"]:
            live.extend(monitor.observe_window(w))
        offline = detect_over_timeline(_windows(values))
        assert [(d["rule"], d["window"]) for d in live] \
            == [(d["rule"], d["window"]) for d in offline]

    def test_composes_with_slo_counter_rule(self):
        from petastorm_tpu.telemetry.slo import evaluate_rules, parse_rules
        registry = TelemetryRegistry()
        monitor = AnomalyMonitor(registry)
        for i, v in enumerate([1000.0] * 8 + [10.0, 10.0]):
            monitor.observe_window({"index": i, "t_s": float(i),
                                    "dt_s": 1.0,
                                    "series": {"rows_per_s": v}})
        rules = parse_rules("counter:anomaly.detections_total<=0")
        violations = evaluate_rules(registry.snapshot(), rules)
        assert violations and violations[0]["value"] == 1

    def test_rule_validation(self):
        with pytest.raises(ValueError, match="kind"):
            AnomalyRule("x", "s", "drop", 1.0)
        with pytest.raises(ValueError, match="min_windows"):
            AnomalyRule("x", "s", "collapse", 1.0, min_windows=1)
        assert len(default_anomaly_rules()) == 5


# ==========================================================================
# Postmortem black box
# ==========================================================================

class TestBlackBox:
    def _registry_with_history(self):
        registry = TelemetryRegistry()
        registry.counter("trace.critical_path.decode").add(7)
        registry.counter("trace.critical_path.stage").add(2)
        registry.histogram("trace.self.decode_s").observe(0.02)
        registry.record_event("anomaly.throughput_collapse", {"value": 1})
        tl = MetricsTimeline(interval_s=1.0)
        registry.timeline = tl
        t0 = time.perf_counter()
        tl.sample({"counters": {"reader.rows": 0.0}}, now_s=t0)
        tl.sample({"counters": {"reader.rows": 100.0}}, now_s=t0 + 1)
        return registry

    def test_bundle_files_and_manifest(self, tmp_path):
        registry = self._registry_with_history()
        box = BlackBox(str(tmp_path), registry, label="reader",
                       config={"workers_count": 3})
        box.add_collector("quarantine", lambda: {"quarantined": 0})
        box.add_collector("broken", lambda: 1 / 0)
        try:
            raise RuntimeError("boom")
        except RuntimeError as e:
            path = box.write_bundle("RuntimeError", exc=e)
        assert path and os.path.isdir(path)
        bundle = load_bundle(path)
        m = bundle["manifest"]
        assert m["reason"] == "RuntimeError"
        assert m["error"]["type"] == "RuntimeError"
        assert "boom" in m["error"]["traceback"]
        assert bundle["config"]["workers_count"] == 3
        assert bundle["reports"]["quarantine"] == {"quarantined": 0}
        assert "collector_error" in bundle["reports"]["broken"]
        assert bundle["timeline"]["windows"]
        assert any("MainThread" in k for k in bundle["stacks"])

    def test_bundle_latches_per_reason(self, tmp_path):
        box = BlackBox(str(tmp_path), TelemetryRegistry())
        first = box.write_bundle("slo_stall")
        again = box.write_bundle("slo_stall")
        other = box.write_bundle("anomaly_collapse")
        assert first == again and other != first
        assert sorted(box.bundles()) == ["anomaly_collapse", "slo_stall"]

    def test_process_bundle_cap(self, tmp_path):
        box = BlackBox(str(tmp_path), TelemetryRegistry())
        paths = [box.write_bundle(f"r{i}") for i in range(12)]
        assert sum(p is not None for p in paths) \
            == postmortem_mod._MAX_BUNDLES_PER_PROCESS

    def test_render_report_names_critical_path_edge(self, tmp_path):
        registry = self._registry_with_history()
        box = BlackBox(str(tmp_path), registry, label="reader")
        path = box.write_bundle("PipelineHungError")
        report = render_report(load_bundle(path))
        assert "POSTMORTEM: reader" in report
        assert "dominant edge: decode" in report
        assert "rows_per_s" in report      # terminal timeline
        assert "anomaly.throughput_collapse" in report

    def test_load_bundle_rejects_non_bundle(self, tmp_path):
        with pytest.raises(OSError):
            load_bundle(str(tmp_path / "nope"))

    def test_watchdog_abort_triggers_hook(self):
        from petastorm_tpu.resilience.watchdog import PipelineWatchdog

        class _StubPool:
            diagnostics = {}

            def abort(self, exc):
                self.aborted = exc

        pool = _StubPool()
        dog = PipelineWatchdog(pool, hang_timeout_s=1.0)
        seen = []
        dog.on_abort = seen.append
        dog._abort(5.0)
        assert seen and "no progress" in str(seen[0])
        assert pool.aborted is seen[0]


# ==========================================================================
# Reader / loader wiring e2e
# ==========================================================================

class TestReaderWiring:
    def test_reader_timeline_and_reports(self, scalar_store):
        with make_batch_reader(scalar_store, num_epochs=2, workers_count=2,
                               shuffle_row_groups=False,
                               timeline_interval_s=0.05) as r:
            for b in r:
                time.sleep(0.002)
            tl = r.timeline_report()
            rep = r.anomaly_report()
            snap = r.telemetry.snapshot()
        assert tl["windows"], "sampler closed no windows"
        rates = [w["series"].get("rows_per_s") for w in tl["windows"]]
        assert any(v and v > 0 for v in rates)
        assert rep["rules"] and rep["detections_total"] == 0
        assert snap["timeline"]["windows"]
        assert snap["counters"]["timeline.samples_total"] >= 1

    def test_reader_fatal_writes_bundle(self, scalar_store, tmp_path,
                                        monkeypatch):
        from petastorm_tpu.resilience import FaultPlan, FaultSpec
        monkeypatch.setenv("PETASTORM_TPU_BLACKBOX", str(tmp_path))
        plan = FaultPlan([FaultSpec("rowgroup.read", "ioerror", rate=1.0,
                                    times=None)], seed=0)
        r = make_batch_reader(scalar_store, num_epochs=1, workers_count=2,
                              shuffle_row_groups=False, fault_plan=plan,
                              timeline_interval_s=0.05)
        with pytest.raises(Exception, match="injected ioerror"):
            with r:
                for _ in r:
                    pass
        bundles = list(r.blackbox.bundles().values())
        assert len(bundles) == 1
        bundle = load_bundle(bundles[0])
        assert "InjectedIOError" in bundle["manifest"]["error"]["type"]
        assert bundle["reports"]["quarantine"]["quarantined"] == 0
        assert bundle["config"]["pool_type"] == "thread"
        # Renders end to end, with the terminal timeline in it.
        assert "POSTMORTEM: reader" in render_report(bundle)

    def test_slo_trip_writes_bundle(self, scalar_store, tmp_path,
                                    monkeypatch):
        monkeypatch.setenv("PETASTORM_TPU_BLACKBOX", str(tmp_path))
        monkeypatch.setenv("PETASTORM_TPU_SLO_WATCH",
                           "counter:reader.rows<=0")
        with make_batch_reader(scalar_store, num_epochs=1, workers_count=2,
                               shuffle_row_groups=False) as r:
            for _ in r:
                break
            r.slo_watcher.check_once()
            bundles = r.blackbox.bundles()
        assert any(reason.startswith("slo_") for reason in bundles)

    def test_anomaly_trip_writes_bundle(self, scalar_store, tmp_path,
                                        monkeypatch):
        monkeypatch.setenv("PETASTORM_TPU_BLACKBOX", str(tmp_path))
        with make_batch_reader(scalar_store, num_epochs=1, workers_count=2,
                               shuffle_row_groups=False,
                               timeline_interval_s=30.0) as r:
            for i, v in enumerate([1000.0] * 8 + [10.0, 10.0]):
                r.anomaly_monitor.observe_window(
                    {"index": i, "t_s": float(i), "dt_s": 1.0,
                     "series": {"rows_per_s": v}})
            bundles = r.blackbox.bundles()
        assert "anomaly_throughput_collapse" in bundles

    def test_live_collapse_detected_within_two_windows(self, scalar_store):
        """Acceptance: a seeded throughput collapse (the consumer parks)
        trips the EWMA detector within 2 timeline windows."""
        W = 0.1
        with make_batch_reader(scalar_store, num_epochs=None,
                               workers_count=2, shuffle_row_groups=False,
                               timeline_interval_s=W) as r:
            it = iter(r)
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 14 * W:
                next(it)
                time.sleep(0.001)
            stall_start = len(r.timeline_report().get("windows", []))
            time.sleep(8 * W)  # parked consumer: rows/s cliff
            dets = [d for d in r.anomaly_report()["detections"]
                    if "collapse" in d["rule"]
                    and d["window"] >= stall_start]
        assert dets, "collapse not detected"
        assert min(d["window"] for d in dets) - stall_start <= 2

    def test_loader_timeline_report_shares_reader_ring(self, scalar_store):
        from petastorm_tpu.jax import BatchedDataLoader
        with make_batch_reader(scalar_store, num_epochs=1, workers_count=2,
                               shuffle_row_groups=False,
                               timeline_interval_s=0.05) as r:
            with BatchedDataLoader(r, batch_size=512) as loader:
                for _ in loader:
                    pass
                assert loader.telemetry is r.telemetry
                # The one-batch epoch can finish inside the sampler's first
                # 50 ms interval: wait (bounded) for it to close a window
                # instead of racing it.
                deadline = time.monotonic() + 10.0
                tl = loader.timeline_report()
                while not tl["windows"] and time.monotonic() < deadline:
                    time.sleep(0.05)
                    tl = loader.timeline_report()
        assert tl["windows"]

    def test_exporter_atexit_flush_on_abandoned_reader(self, tmp_path):
        """Satellite: a reader abandoned without close() still writes its
        terminal snapshot (atexit finalizer)."""
        out = tmp_path / "abandoned.json"
        code = (
            "import petastorm_tpu.telemetry as t\n"
            "reg = t.TelemetryRegistry()\n"
            "reg.counter('reader.rows').add(123)\n"
            "exp = t.PeriodicExporter(reg, %r, interval_s=600.0).start()\n"
            "# no stop(), no close(): the atexit finalizer must flush\n"
            % str(out))
        subprocess.run([sys.executable, "-c", code], check=True,
                       timeout=120)
        snap = json.loads(out.read_text())
        assert snap["counters"]["reader.rows"] == 123

    def test_exporter_stop_unregisters_from_atexit_set(self):
        from petastorm_tpu.telemetry import exporters as exp_mod
        registry = TelemetryRegistry()
        exporter = PeriodicExporter(registry, "/tmp/_pt_unused.json",
                                    interval_s=600.0).start()
        assert exporter in exp_mod._LIVE_EXPORTERS
        exporter.stop()
        assert exporter not in exp_mod._LIVE_EXPORTERS


# ==========================================================================
# Process-pool federation + killed-run postmortem (spawned e2e)
# ==========================================================================

@pytest.mark.process_pool
class TestProcessPoolOps:
    def test_killed_pool_leaves_renderable_bundle(self, scalar_store,
                                                  tmp_path, monkeypatch):
        """Acceptance: a killed process-pool run leaves a postmortem
        bundle that `telemetry postmortem` renders with the critical-path
        edge (the loader's attributor fed the registry before the
        death)."""
        from petastorm_tpu.jax import BatchedDataLoader
        from petastorm_tpu.resilience import FaultPlan, FaultSpec
        monkeypatch.setenv("PETASTORM_TPU_BLACKBOX", str(tmp_path))
        plan = FaultPlan([FaultSpec(site="worker.item", kind="worker_kill",
                                    at=8, worker=0)])
        r = make_batch_reader(scalar_store, reader_pool_type="process",
                              workers_count=2, shuffle_row_groups=False,
                              num_epochs=2, fault_plan=plan,
                              timeline_interval_s=0.1)
        with pytest.raises(RuntimeError, match="died unexpectedly"):
            with r:
                with BatchedDataLoader(r, batch_size=512) as loader:
                    for _ in loader:
                        pass
        bundles = list(r.blackbox.bundles().values())
        assert bundles, "no postmortem bundle written"
        # Per-worker federation counters arrived over the ctrl channel
        # before the death.
        bundle = load_bundle(bundles[0])
        counters = bundle["snapshot"]["counters"]
        assert any(k.startswith("pool.w") and k.endswith(".items")
                   for k in counters)
        report = render_report(bundle)
        assert "dominant edge:" in report
        # The CLI renders the same bundle (exit 0).
        assert telemetry_cli(["postmortem", bundles[0]]) == 0

    def test_per_worker_counters_feed_timeline_family(self, scalar_store):
        with make_batch_reader(scalar_store, reader_pool_type="process",
                               workers_count=2, shuffle_row_groups=False,
                               num_epochs=1,
                               timeline_interval_s=0.1) as r:
            for _ in r:
                pass
            counters = r.telemetry.metrics_view()["counters"]
        # After close: the sampler's terminal window has been taken, so a
        # window is guaranteed to have seen the per-worker counter family
        # even when the epoch outran the periodic cadence.
        tl = r.timeline_report()
        worker_counters = [k for k in counters
                           if k.startswith("pool.w")
                           and k.endswith(".items")]
        assert worker_counters, "processed markers carried no worker ids"
        names = set()
        for w in tl["windows"]:
            names.update(w["series"])
        assert any(n.startswith("pool.w") and n.endswith(".items_per_s")
                   for n in names)


# ==========================================================================
# Mesh federation e2e (8 simulated hosts via conftest XLA_FLAGS)
# ==========================================================================

class TestMeshFederation:
    def test_mesh_epoch_yields_one_federated_rollup(self, scalar_store):
        """Acceptance: an 8-simulated-host mesh epoch with timelines on
        yields ONE federated rollup with per-host rows/s series and a
        skew view."""
        from petastorm_tpu.jax import MeshDataLoader, MeshReaderFactory
        factory = MeshReaderFactory(scalar_store, batched=True,
                                    timeline_interval_s=0.05)
        with MeshDataLoader(factory, batch_size=256, seed=0, num_epochs=1,
                            drop_last=False, pad_last=True,
                            timeline_interval_s=0.05) as loader:
            rows = 0
            for batch in loader:
                rows += next(iter(batch.values())).shape[0]
            rep = loader.mesh_report()
        assert rows >= 20000
        fed = rep["timeline"]
        assert fed is not None and fed["key_label"] == "host"
        # Every host contributed a member timeline + the mesh's own ring.
        host_members = [m for m in fed["members"] if m.startswith("h")]
        assert len(host_members) == 8 and "mesh" in fed["members"]
        # Per-host throughput series from BOTH planes: each host reader's
        # own rows_per_s, and the mesh ring's mesh.host{h}.rows_per_s
        # family derived from the assembler-side counters.
        for h in host_members:
            assert f"{h}:rows_per_s" in fed["series"]
        mesh_family = [s for s in fed["series"]
                       if s.startswith("mesh:mesh.host")
                       and s.endswith(".rows_per_s")]
        assert len(mesh_family) == 8
        assert "fleet:rows_per_s" in fed["series"]
        assert "skew:rows_per_s" in fed["series"]
        # The federated snapshot rollup sums host counters under bare
        # names while keeping per-host series addressable.
        snaps = {m: {"counters": {"reader.rows": 1.0}}
                 for m in host_members}
        rollup = federate_snapshots(snaps)
        assert rollup["counters"]["reader.rows"] == len(host_members)


# ==========================================================================
# CLI
# ==========================================================================

class TestCli:
    def _snapshot_file(self, tmp_path, values, name="snap.json"):
        registry = TelemetryRegistry()
        registry.counter("reader.rows").add(sum(values))
        snap = registry.snapshot()
        snap["timeline"] = _windows(values)
        path = tmp_path / name
        write_snapshot(str(path), snap)
        return str(path)

    def test_check_anomaly_gate_exits_2_on_collapse(self, tmp_path, capsys):
        path = self._snapshot_file(tmp_path, [1000.0] * 8 + [10.0] * 3)
        rc = telemetry_cli(["check", path, "--anomaly"])
        out = capsys.readouterr().out
        assert rc == 2
        assert "FAIL anomaly throughput_collapse" in out

    def test_check_anomaly_gate_ok_on_steady(self, tmp_path, capsys):
        path = self._snapshot_file(tmp_path, [1000.0] * 10)
        rc = telemetry_cli(["check", path, "--anomaly"])
        assert rc == 0
        assert "ok   anomaly" in capsys.readouterr().out

    def test_check_anomaly_skips_without_timeline(self, tmp_path, capsys):
        registry = TelemetryRegistry()
        path = tmp_path / "plain.json"
        write_snapshot(str(path), registry.snapshot())
        rc = telemetry_cli(["check", str(path), "--anomaly"])
        assert rc == 0
        assert "skip anomaly" in capsys.readouterr().out

    def test_check_anomaly_respects_live_counter(self, tmp_path, capsys):
        """Windows fell off the ring but the live monitor counted a
        detection: the gate must still fail."""
        registry = TelemetryRegistry()
        registry.counter("anomaly.detections_total").add(2)
        snap = registry.snapshot()
        snap["timeline"] = _windows([1000.0] * 5)
        path = tmp_path / "live.json"
        write_snapshot(str(path), snap)
        rc = telemetry_cli(["check", str(path), "--anomaly"])
        assert rc == 2
        assert "live_monitor" in capsys.readouterr().out

    def test_timeline_subcommand_renders_and_flushes(self, tmp_path,
                                                     capsys):
        path = self._snapshot_file(tmp_path, [10.0, 20.0, 30.0])
        out_json = tmp_path / "series.json"
        rc = telemetry_cli(["timeline", path, "--json", str(out_json)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "rows_per_s" in out
        flushed = json.loads(out_json.read_text())
        assert flushed["series"]["rows_per_s"] == [10.0, 20.0, 30.0]

    def test_timeline_subcommand_last_truncates_json_too(self, tmp_path,
                                                         capsys):
        path = self._snapshot_file(tmp_path, [10.0, 20.0, 30.0])
        out_json = tmp_path / "series_last.json"
        rc = telemetry_cli(["timeline", path, "--last", "2",
                            "--json", str(out_json)])
        capsys.readouterr()
        assert rc == 0
        flushed = json.loads(out_json.read_text())
        assert flushed["series"]["rows_per_s"] == [20.0, 30.0]

    def test_timeline_subcommand_federates_files(self, tmp_path, capsys):
        a = self._snapshot_file(tmp_path, [10.0, 20.0], name="h0.json")
        b = self._snapshot_file(tmp_path, [30.0, 40.0], name="h1.json")
        rc = telemetry_cli(["timeline", a, b])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fleet:rows_per_s" in out
        assert "h0:rows_per_s" in out

    def test_timeline_subcommand_errors_without_timeline(self, tmp_path,
                                                         capsys):
        registry = TelemetryRegistry()
        path = tmp_path / "plain.json"
        write_snapshot(str(path), registry.snapshot())
        assert telemetry_cli(["timeline", str(path)]) == 1

    def test_top_renders_sparklines(self, tmp_path, capsys):
        path = self._snapshot_file(tmp_path, [10.0, 20.0, 30.0])
        rc = telemetry_cli(["top", path, "--count", "1", "--no-clear"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "petastorm-tpu top" in out
        assert "rows_per_s" in out

    def test_postmortem_subcommand_exit_codes(self, tmp_path, capsys):
        assert telemetry_cli(["postmortem", str(tmp_path / "nope")]) == 1
        box = BlackBox(str(tmp_path), TelemetryRegistry(), label="reader")
        path = box.write_bundle("test")
        assert telemetry_cli(["postmortem", path]) == 0
        assert "POSTMORTEM" in capsys.readouterr().out


# ==========================================================================
# Lint: check_metric_docs
# ==========================================================================

class TestMetricDocsLint:
    def test_repo_is_clean(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "tools",
                                          "check_metric_docs.py")],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr

    def test_lint_catches_undocumented_metric(self, tmp_path, monkeypatch):
        import importlib
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        try:
            mod = importlib.import_module("check_metric_docs")
        finally:
            sys.path.pop(0)
        pkg = tmp_path / "petastorm_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            "def f(reg):\n"
            "    reg.counter('totally.undocumented_total').add(1)\n"
            "    reg.gauge('waived.metric')  # metric-doc-ok: test\n")
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "observability.md").write_text("| `some.other_metric` |\n")
        monkeypatch.setattr(mod, "PACKAGE", str(pkg))
        monkeypatch.setattr(mod, "DOCS",
                            (str(docs / "observability.md"),))
        assert mod.main([]) == 1
        (docs / "observability.md").write_text(
            "| `totally.undocumented_total` |\n")
        assert mod.main([]) == 0

    def test_wildcard_matching(self):
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        try:
            import check_metric_docs as mod
        finally:
            sys.path.pop(0)
        assert mod._normalize("mesh.host{h}.rows") == "mesh.host*.rows"
        assert mod._wildcard_match("mesh.host*.rows", "mesh.host*.rows")
        assert mod._wildcard_match("pool.w7.items", "pool.w*.items")
        assert not mod._wildcard_match("pool.w7.items", "pool.w*.busy_s")
