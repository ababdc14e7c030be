"""Trace plane (docs/observability.md): cross-process batch lineage,
Chrome-trace export, critical-path attribution, SLO watch, and the
exporter edge cases that ride along (PR 8)."""
import json
import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from petastorm_tpu.telemetry import (CriticalPathAttributor, SloWatcher,
                                     TelemetryRegistry, TraceContext,
                                     complete_lineages, evaluate_rules,
                                     lineage_index, parse_prometheus_text,
                                     parse_rules, to_chrome_trace,
                                     to_prometheus_text)

pytestmark = pytest.mark.telemetry


@pytest.fixture(scope="module")
def scalar_store(tmp_path_factory):
    """Plain Parquet store: 200 rows / 10 row groups of 20 rows."""
    path = tmp_path_factory.mktemp("trace_scalar")
    n = 200
    pq.write_table(
        pa.table({"id": np.arange(n, dtype=np.int64),
                  "x": (np.arange(n) * 0.5).astype(np.float32)}),
        str(path / "part0.parquet"), row_group_size=20)
    return f"file://{path}"


# --------------------------------------------------------------- identity
def test_trace_context_roundtrip():
    ctx = TraceContext(epoch=3, ordinal=17)
    assert ctx.id == "e3:g17"
    assert TraceContext.parse("e3:g17") == ctx
    assert TraceContext.parse("b12") is None
    assert TraceContext.parse("garbage") is None


def test_recorder_trace_fields_ride_snapshot():
    reg = TelemetryRegistry()
    reg.recorder.enable_trace()
    with reg.span("petastorm_tpu.worker_decode", trace="e0:g1",
                  stage="decode", track="worker:1"):
        pass
    snap = reg.snapshot()
    [span] = snap["trace_events"]
    assert span["trace"] == "e0:g1"
    assert span["stage"] == "decode"
    assert span["track"] == "worker:1"
    assert span["span_id"] > 0
    # stage self-time mirrors into the span-fed counter
    assert snap["counters"]["trace.span.decode_s"] > 0
    # periodic writers can skip the raw-span payload (PeriodicExporter's
    # per-tick path); the metrics themselves are unaffected
    slim = reg.snapshot(include_trace=False)
    assert "trace_events" not in slim
    assert slim["counters"] == snap["counters"]
    # reset drains the raw spans too
    out = reg.reset()
    assert len(out["trace_events"]) == 1
    assert "trace_events" not in reg.snapshot()


def test_enable_trace_grows_ring_preserving():
    from petastorm_tpu.telemetry.recorder import (SpanRecorder,
                                                  TRACE_SPAN_CAPACITY)
    rec = SpanRecorder(capacity=4, enabled=True)
    for i in range(3):
        rec.record(f"s{i}", 0.0, 0.001)
    rec.enable_trace()
    assert rec.capacity == TRACE_SPAN_CAPACITY
    assert [sp.name for sp in rec.spans()] == ["s0", "s1", "s2"]
    assert rec.trace_enabled


def test_record_remote_anchors_to_local_clock():
    import time
    rec_reg = TelemetryRegistry()
    rec = rec_reg.recorder
    now = time.perf_counter()
    rec.record_remote([("petastorm_tpu.worker_decode", "decode", 0.25,
                        "e0:g4", "worker:2")], pid=4242)
    [span] = rec.spans()
    assert span.trace == "e0:g4" and span.pid == 4242
    assert span.duration_s == 0.25
    # ends ~now on OUR clock
    assert abs((span.start_s + span.duration_s) - now) < 1.0
    assert rec_reg.peek_counter("trace.span.decode_s") == 0.25


# --------------------------------------------------------------- exporter
def test_chrome_trace_tracks_and_instants():
    spans = [
        {"name": "petastorm_tpu.ventilate", "start_s": 1.0,
         "duration_s": 0.0, "thread": "vent", "thread_id": 1, "pid": 9,
         "trace": "e0:g0", "stage": "ventilate", "track": "ventilator"},
        {"name": "petastorm_tpu.worker_decode", "start_s": 1.1,
         "duration_s": 0.2, "thread": "w", "thread_id": 2, "pid": 9,
         "trace": "e0:g0", "stage": "decode", "track": "h3:worker:0"},
    ]
    ct = to_chrome_trace(spans, metadata={"k": 1})
    events = ct["traceEvents"]
    procs = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    threads = {e["args"]["name"] for e in events
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert procs == {"pid9", "host3"}
    assert threads == {"ventilator", "worker:0"}
    kinds = {e["ph"] for e in events}
    assert "i" in kinds and "X" in kinds  # instant + complete events
    x = next(e for e in events if e["ph"] == "X")
    assert x["dur"] == pytest.approx(0.2e6)
    assert x["args"]["trace"] == "e0:g0"
    assert ct["otherData"] == {"k": 1}
    json.dumps(ct)  # must be JSON-serializable as-is


def test_lineage_helpers():
    spans = [
        {"name": "v", "trace": "e0:g0", "stage": "ventilate"},
        {"name": "d", "trace": "e0:g0", "stage": "decode"},
        {"name": "v", "trace": "e0:g1", "stage": "ventilate"},
        {"name": "s", "trace": "b1", "stage": "stage"},  # batch-scoped
        {"name": "x"},                                   # no lineage
    ]
    idx = lineage_index(spans)
    assert set(idx) == {"e0:g0", "e0:g1"}
    assert complete_lineages(spans) == ["e0:g0"]


# ---------------------------------------------------------- critical path
def test_critical_path_names_longest_edge():
    reg = TelemetryRegistry()
    cp = CriticalPathAttributor(reg)
    reg.counter("loader.stage_s").add(0.1)
    reg.counter("loader.shuffle_s").add(0.4)
    assert cp.observe_batch() == "shuffle"
    reg.histogram("worker.decode_s").observe(0.9)
    reg.counter("loader.stage_s").add(0.2)
    assert cp.observe_batch() == "decode"
    # mesh host-plane decode sync counts into the same edge
    reg.counter("mesh.host_decode_s").add(5.0)
    assert cp.observe_batch() == "decode"
    assert cp.observe_batch() is None  # nothing moved between deliveries
    rep = cp.report()
    assert rep["batches"] == 4 and rep["attributed"] == 3
    assert rep["counts"]["decode"] == 2 and rep["dominant"] == "decode"
    assert rep["recent"][-1]["critical"] is None
    # per-batch self-times landed as histograms
    snap = reg.snapshot()
    assert snap["histograms"]["trace.self.decode_s"]["count"] == 2


def test_critical_path_is_lazy_about_metric_creation():
    reg = TelemetryRegistry()
    CriticalPathAttributor(reg)
    snap = reg.snapshot()
    assert not any(k.startswith("trace.") for k in snap["counters"])
    assert not any(k.startswith(("io.", "transport.", "mesh.", "loader."))
                   for k in snap["counters"])


# ------------------------------------------------------------- end-to-end
def test_reader_epoch_traces_every_rowgroup(scalar_store, monkeypatch):
    monkeypatch.setenv("PETASTORM_TPU_TELEMETRY_TRACE", "1")
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=1,
                           shuffle_row_groups=True, seed=7,
                           reader_pool_type="thread", workers_count=2,
                           readahead_depth=3) as r:
        rows = sum(len(b.id) for b in r)
        spans = [sp.as_dict() for sp in r.telemetry.recorder.spans()]
        ra = r.readahead_report()
    assert rows == 200
    # one complete ventilate->decode lineage per row group
    assert len(complete_lineages(spans)) == 10
    # trace ordinals are plan-stable even under the seeded epoch shuffle
    assert set(lineage_index(spans)) == {f"e0:g{i}" for i in range(10)}
    # fetcher provenance is first-class: fetch spans on fetch:{idx} tracks,
    # never phantom worker ids (satellite: worker_id 1000+i is fault-plan
    # keying only)
    fetch_tracks = {sp["track"] for sp in spans
                    if sp.get("stage") == "fetch"}
    assert fetch_tracks and all(t.startswith("fetch:")
                                for t in fetch_tracks)
    assert ra["provenance"]["stage"] == "fetch"
    assert ra["provenance"]["tracks"][0] == "fetch:0"


def test_dummy_pool_epoch_traces(scalar_store, monkeypatch):
    monkeypatch.setenv("PETASTORM_TPU_TELEMETRY_TRACE", "1")
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=1,
                           shuffle_row_groups=False,
                           reader_pool_type="dummy") as r:
        rows = sum(len(b.id) for b in r)
        spans = [sp.as_dict() for sp in r.telemetry.recorder.spans()]
    assert rows == 200
    assert len(complete_lineages(spans)) == 10
    decode_tracks = {sp["track"] for sp in spans
                     if sp.get("stage") == "decode"}
    assert decode_tracks == {"worker:0"}


@pytest.mark.process_pool
def test_process_pool_trace_crosses_the_boundary(scalar_store, monkeypatch):
    """Spawned workers piggyback decode spans on the processed-marker ctrl
    frame; the consumer re-anchors them with lineage intact, and the
    transport stage is accounted consumer-side."""
    monkeypatch.setenv("PETASTORM_TPU_TELEMETRY_TRACE", "1")
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=1,
                           shuffle_row_groups=False,
                           reader_pool_type="process",
                           workers_count=2) as r:
        rows = sum(len(b.id) for b in r)
        spans = [sp.as_dict() for sp in r.telemetry.recorder.spans()]
        counters = r.telemetry.snapshot()["counters"]
    assert rows == 200
    remote = [sp for sp in spans if sp.get("stage") == "decode"]
    assert len(remote) == 10
    assert {sp["trace"] for sp in remote} == {f"e0:g{i}" for i in range(10)}
    assert all(sp["thread"] == "remote" for sp in remote)
    assert {sp["track"] for sp in remote} <= {"worker:0", "worker:1"}
    assert counters.get("transport.deserialize_s", 0) > 0
    assert len(complete_lineages(spans)) == 10


@pytest.mark.process_pool
def test_process_pool_trace_enabled_after_start(scalar_store):
    """The injected trace_context kwarg is a LIVE per-item signal: trace
    mode enabled after the pool spawned (the mesh rollup path, or
    enable_trace() before export_trace) still yields remote decode spans
    for items ventilated after the flip."""
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=2,
                           shuffle_row_groups=False,
                           reader_pool_type="process",
                           workers_count=1) as r:
        it = iter(r)
        next(it)  # pool is up and working, tracing off
        r.telemetry.recorder.enable_trace()
        rows = 20 + sum(len(b.id) for b in it)
        spans = [sp.as_dict() for sp in r.telemetry.recorder.spans()]
    assert rows == 400
    remote = [sp for sp in spans if sp.get("stage") == "decode"]
    # items ventilated before the flip have no trace_context (and some may
    # already be in flight at flip time) — but the stream after it does
    assert remote and all(sp["trace"].startswith("e") for sp in remote)


@pytest.mark.process_pool
def test_migration_diagnostics_stay_monotonic(scalar_store):
    """Satellite bug fix: a placement migration must not make
    Reader.diagnostics jump backwards (the fresh pool restarts its item
    counters from zero) or keep reporting the old backend."""
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=2, seed=0,
                           shuffle_row_groups=False,
                           reader_pool_type="thread",
                           workers_count=2) as r:
        it = iter(r)
        got = [next(it) for _ in range(3)]
        pre = r.diagnostics
        assert pre["pool_type"] == "thread"
        pre_ventilated = pre["items_ventilated"]
        assert pre_ventilated >= 3
        r._request_pool_migration("process")
        got.extend(it)
        post = r.diagnostics
    assert post["pool_type"] == "process"
    assert post["items_ventilated"] >= pre_ventilated
    assert post["items_ventilated"] == post["items_processed"] == 20
    # gauges re-synced at the safe point: the process backend reports
    # backend=1 and a disabled queue-shape pair
    gauges = post["telemetry"]["gauges"]
    assert gauges["pool.backend"] == 1.0
    assert gauges["pool.results_queue_capacity"] == 0
    rows = sorted(int(v) for g in got for v in np.asarray(g.id).tolist())
    assert rows == sorted(list(range(200)) * 2)


# -------------------------------------------------------------------- CLI
def _traced_snapshot_file(tmp_path, name="snap.json"):
    reg = TelemetryRegistry()
    reg.recorder.enable_trace()
    with reg.span("petastorm_tpu.worker_decode", trace="e0:g0",
                  stage="decode", track="worker:0"):
        pass
    reg.recorder.record_event("petastorm_tpu.ventilate", trace="e0:g0",
                              stage="ventilate", track="ventilator")
    reg.counter("trace.critical_path.decode").add(3)
    path = tmp_path / name
    path.write_text(json.dumps(reg.snapshot()))
    return str(path)


def test_cli_trace_exports_chrome_json(tmp_path, capsys):
    from petastorm_tpu.telemetry.__main__ import main
    snap = _traced_snapshot_file(tmp_path)
    out = str(tmp_path / "trace.json")
    assert main(["trace", snap, "--out", out]) == 0
    printed = capsys.readouterr().out
    assert "critical path" in printed and "decode=3" in printed
    ct = json.loads(open(out).read())
    assert ct["traceEvents"]
    assert ct["otherData"]["critical_path"] == {"decode": 3}
    assert ct["otherData"]["complete_lineages"] == 1


def test_cli_trace_re_anchors_multi_host_snapshots(tmp_path, capsys):
    """Merging per-host snapshot files re-anchors each file's earliest
    span to t=0: perf_counter is per-machine, and without alignment hosts
    land arbitrarily far apart on the merged timeline."""
    from petastorm_tpu.telemetry.__main__ import main

    def snap_file(name, base_s):
        span = {"name": "petastorm_tpu.worker_decode", "start_s": base_s,
                "duration_s": 0.5, "thread": "w", "thread_id": 1, "pid": 1,
                "trace": "e0:g0", "stage": "decode", "track": "worker:0"}
        path = tmp_path / name
        path.write_text(json.dumps({"counters": {}, "gauges": {},
                                    "histograms": {}, "spans": {},
                                    "trace_events": [span]}))
        return str(path)

    a = snap_file("host_a.json", 17.0)         # host A booted recently
    b = snap_file("host_b.json", 9_000_000.0)  # host B up for months
    out = str(tmp_path / "merged.json")
    assert main(["trace", a, b, "--out", out]) == 0
    capsys.readouterr()
    ct = json.loads(open(out).read())
    ts = [e["ts"] for e in ct["traceEvents"] if e["ph"] != "M"]
    assert len(ts) == 2 and all(t == 0.0 for t in ts)


def test_cli_trace_refuses_traceless_snapshot(tmp_path, capsys):
    from petastorm_tpu.telemetry.__main__ import main
    path = tmp_path / "plain.json"
    path.write_text(json.dumps(TelemetryRegistry().snapshot()))
    assert main(["trace", str(path),
                 "--out", str(tmp_path / "t.json")]) == 1
    assert "PETASTORM_TPU_TELEMETRY_TRACE" in capsys.readouterr().err


def test_cli_check_pass_and_fail(tmp_path, capsys):
    from petastorm_tpu.telemetry.__main__ import main
    ok = {"gauges": {"loader.input_stall_pct": 0.4}, "counters": {},
          "histograms": {}}
    bad = {"gauges": {"loader.input_stall_pct": 42.0},
           "counters": {"resilience.quarantined_rowgroups": 2},
           "histograms": {}}
    ok_p, bad_p = tmp_path / "ok.json", tmp_path / "bad.json"
    ok_p.write_text(json.dumps(ok))
    bad_p.write_text(json.dumps(bad))
    assert main(["check", str(ok_p)]) == 0
    assert main(["check", str(bad_p)]) == 2
    err = capsys.readouterr()
    assert "FAIL input_stall_pct" in err.out
    # explicit rule specs override defaults; absent metrics report as
    # "skip", never as a passing "ok"
    assert main(["check", str(bad_p), "--slo",
                 "input_stall_pct<=50,counter:foo.bar<=1"]) == 0
    out = capsys.readouterr().out
    assert "skip foo.bar" in out and "ok   input_stall_pct" in out
    assert main(["check", str(tmp_path / "missing.json")]) == 1
    # rate rules engage once a --prev window exists
    prev = {"gauges": {}, "counters": {"resilience.hedges_launched": 0},
            "histograms": {}}
    cur = {"gauges": {}, "counters": {"resilience.hedges_launched": 300},
           "histograms": {}}
    prev_p, cur_p = tmp_path / "prev.json", tmp_path / "cur.json"
    prev_p.write_text(json.dumps(prev))
    cur_p.write_text(json.dumps(cur))
    capsys.readouterr()
    assert main(["check", str(cur_p), "--prev", str(prev_p),
                 "--window-s", "10"]) == 2
    assert "FAIL hedge_rate" in capsys.readouterr().out
    # --prev without a window is an error, not a silent skip
    assert main(["check", str(cur_p), "--prev", str(prev_p)]) == 1


def test_dump_renders_events_and_mesh(tmp_path, capsys):
    """Satellite: watch/dump surface the PR 4 event rings and the PR 7
    mesh.* family (the same pretty renderer serves both subcommands)."""
    from petastorm_tpu.telemetry.__main__ import main
    reg = TelemetryRegistry()
    reg.record_event("mesh.host_lost", {"host": 3, "error": "boom"})
    reg.counter("mesh.host3.rows").add(100)
    reg.counter("mesh.host3.rowgroups").add(5)
    reg.counter("mesh.reshard_events").add(1)
    reg.gauge("mesh.hosts").set(8)
    path = tmp_path / "mesh.json"
    path.write_text(json.dumps(reg.snapshot()))
    assert main(["dump", str(path)]) == 0
    out = capsys.readouterr().out
    assert "mesh:" in out and "per-host" in out and "host3" in out
    assert "events" in out and "mesh.host_lost" in out and "boom" in out


# -------------------------------------------------------------- SLO watch
def test_slo_rule_parsing_and_evaluation():
    rules = parse_rules("input_stall_pct<=1,counter:resilience.worker_crashes<=0")
    assert [r.max_value for r in rules] == [1.0, 0.0]
    snap = {"gauges": {"loader.input_stall_pct": 3.0},
            "counters": {"resilience.worker_crashes": 0.0},
            "histograms": {}}
    violations = evaluate_rules(snap, rules)
    assert [v["rule"] for v in violations] == ["input_stall_pct"]
    with pytest.raises(ValueError):
        parse_rules("unknown_rule<=2")
    # rate rules need a window
    rate = parse_rules("hedge_rate<=1")
    prev = {"counters": {"resilience.hedges_launched": 0.0}}
    cur = {"counters": {"resilience.hedges_launched": 30.0}, "gauges": {},
           "histograms": {}}
    assert evaluate_rules(cur, rate) == []  # no window: not evaluable
    [v] = evaluate_rules(cur, rate, prev=prev, dt_s=10.0)
    assert v["value"] == pytest.approx(3.0)


def test_slo_watcher_records_events_and_counts():
    reg = TelemetryRegistry()
    reg.gauge("loader.input_stall_pct").set(50.0)
    watcher = SloWatcher(reg, rules=parse_rules("input_stall_pct<=5"),
                         interval_s=60.0)
    [v] = watcher.check_once()
    assert v["rule"] == "input_stall_pct"
    assert reg.peek_counter("slo.violations_total") == 1
    events = reg.events("slo.violation")
    assert events and events[0]["payload"]["value"] == 50.0
    rep = watcher.report()
    assert rep["currently_violating"] == ["input_stall_pct"]
    assert rep["violations_by_rule"] == {"input_stall_pct": 1}
    reg.gauge("loader.input_stall_pct").set(0.0)
    assert watcher.check_once() == []
    assert watcher.report()["currently_violating"] == []
    watcher.stop()


def test_reader_slo_env_wiring(scalar_store, monkeypatch):
    monkeypatch.setenv("PETASTORM_TPU_SLO_WATCH", "input_stall_pct<=5")
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=1,
                           shuffle_row_groups=False,
                           reader_pool_type="dummy") as r:
        assert r.slo_watcher is not None
        assert [x.name for x in r.slo_watcher.rules] == ["input_stall_pct"]
        list(r)
        rep = r.slo_report()
        assert rep["rules"][0]["metric"] == "loader.input_stall_pct"
    # stopped with the reader
    assert r.slo_watcher._thread is None


# --------------------------------------------------- exporter edge cases
def test_prometheus_label_escaping_survives_hostile_span_names():
    """Satellite: quotes/backslashes/newlines (a pathological dataset path
    in a span name) must not corrupt the exposition format."""
    reg = TelemetryRegistry()
    evil = 'read "/data/ds\\v1\nshard"'
    reg.recorder.record(evil, 0.0, 0.5)
    text = to_prometheus_text(reg.snapshot())
    parsed = parse_prometheus_text(text)  # raises on malformed lines
    labels = next(iter(
        parsed["petastorm_tpu_span_seconds_total"].keys()))
    assert "\\n" in labels and '\\"' in labels and "\\\\" in labels
    assert "\n" not in labels


def test_histogram_bucket_boundary_values():
    """A value equal to a bucket's upper bound counts in that bucket
    (Prometheus ``le`` = less-or-equal semantics)."""
    from petastorm_tpu.telemetry import StreamingHistogram
    h = StreamingHistogram(bounds=[1.0, 2.0, 4.0])
    h.observe(2.0)   # exactly on a bound
    h.observe(4.0)   # exactly on the last bound
    h.observe(4.0000001)  # just past it: +Inf bucket
    buckets = dict((b if b is not None else "inf", c)
                   for b, c in h.buckets())
    assert buckets[2.0] == 1     # le=2 includes the 2.0 observation
    assert buckets[4.0] == 2     # le=4 includes both
    assert buckets["inf"] == 3
    # Prometheus rendering keeps the same cumulative counts
    reg = TelemetryRegistry()
    reg.histogram("b", bounds=[1.0, 2.0, 4.0]).observe(2.0)
    parsed = parse_prometheus_text(to_prometheus_text(reg.snapshot()))
    assert parsed["petastorm_tpu_b_bucket"]['le="2.0"'] == 1


def test_snapshot_during_reset_loses_nothing():
    """Satellite: concurrent add() during a reset() storm lands either in
    a returned snapshot or in the final state — never in neither."""
    reg = TelemetryRegistry()
    counter = reg.counter("x")
    hist = reg.histogram("h")
    n = 20000
    done = threading.Event()

    def hammer():
        for _ in range(n):
            counter.add(1)
            hist.observe(1.0)
        done.set()

    t = threading.Thread(target=hammer)
    t.start()
    captured = 0.0
    captured_h = 0
    while not done.is_set():
        snap = reg.reset()
        captured += snap["counters"].get("x", 0.0)
        captured_h += snap["histograms"].get("h", {}).get("count", 0)
    t.join()
    final = reg.reset()
    captured += final["counters"].get("x", 0.0)
    captured_h += final["histograms"].get("h", {}).get("count", 0)
    assert captured == n
    assert captured_h == n


# --------------------------------------------------- mesh acceptance e2e
@pytest.mark.mesh
def test_mesh_trace_acceptance(tmp_path, monkeypatch, capsys):
    """The PR acceptance surface: an 8-simulated-host mesh epoch in trace
    mode yields valid Chrome-trace JSON with one process per host, one
    track per stage, >= 1 complete lineage per row group, and a per-batch
    critical-path attribution summary."""
    monkeypatch.setenv("PETASTORM_TPU_TELEMETRY_TRACE", "1")
    store = tmp_path / "mesh_store"
    store.mkdir()
    n = 800
    pq.write_table(
        pa.table({"id": np.arange(n, dtype=np.int64),
                  "x": (np.arange(n) * 0.5).astype(np.float32)}),
        str(store / "part0.parquet"), row_group_size=20)
    from petastorm_tpu.jax import MeshDataLoader, MeshReaderFactory
    from petastorm_tpu.telemetry import write_snapshot
    factory = MeshReaderFactory(f"file://{store}", batched=True)
    with MeshDataLoader(factory, batch_size=80, seed=3,
                        num_epochs=1) as loader:
        rows = sum(len(b["id"]) for b in loader)
        rep = loader.mesh_report()
        snap = loader.telemetry.snapshot()
    assert rows == 800

    # ≥1 complete lineage per row group, through the mesh pull plane
    spans = snap["trace_events"]
    lineages = lineage_index(spans)
    assert len(lineages) == 40  # 800 rows / 20-row groups
    assert len(complete_lineages(
        spans, required=("ventilate", "decode", "pull"))) == 40

    # per-batch critical-path attribution exists and sums to the batches
    cp = rep["critical_path"]
    assert cp["batches"] == 10
    assert cp["attributed"] >= 1
    assert sum(cp["counts"].values()) == cp["attributed"]

    # the CLI converts the exported snapshot into valid Chrome-trace JSON
    from petastorm_tpu.telemetry.__main__ import main
    snap_path = str(tmp_path / "mesh_snap.json")
    write_snapshot(snap_path, snap)
    out = str(tmp_path / "mesh_trace.json")
    assert main(["trace", snap_path, "--out", out]) == 0
    printed = capsys.readouterr().out
    assert "critical path" in printed
    ct = json.loads(open(out).read())
    procs = {e["args"]["name"] for e in ct["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {f"host{h}" for h in range(8)} <= procs
    threads = {e["args"]["name"] for e in ct["traceEvents"]
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"pull", "ventilator", "worker:0", "assemble",
            "stager"} <= threads
    # every non-metadata event is well-formed
    for e in ct["traceEvents"]:
        assert e["ph"] in ("M", "X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e


# ------------------------------------- one span vocabulary, two sinks
# (docs/observability.md "Spans"): every hot-path site goes through
# metrics.traced_span, which records into the ring AND emits a profiler
# annotation of the same name; spans sit where the work happens.
@pytest.fixture
def annotations(monkeypatch):
    """``jax.profiler.TraceAnnotation`` replaced by a recorder of
    ``(name, thread)`` per closed annotation."""
    from petastorm_tpu import metrics
    seen = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            self.thread = threading.current_thread().name
            return self

        def __exit__(self, *exc):
            assert threading.current_thread().name == self.thread
            seen.append((self.name, self.thread))
            return False

    monkeypatch.setattr(metrics, "_TRACE_ANNOTATION", FakeAnnotation)
    return seen


def _sink_counts(recorder, annotations):
    """-> (ring, profiler): per-name counts of the locally recorded
    ``petastorm_tpu.*`` spans and of the annotations."""
    from collections import Counter
    ring = Counter(sp.name for sp in recorder.spans()
                   if sp.thread != "remote" and sp.stage != "ventilate")
    return ring, Counter(name for name, _ in annotations)


def _thread_loader(synthetic_dataset, scalar_store, tmp_path):
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.reader import make_reader
    import time
    with make_reader(synthetic_dataset.url, schema_fields=["id", "matrix"],
                     num_epochs=2, shuffle_row_groups=False,
                     reader_pool_type="thread", workers_count=2,
                     results_queue_size=1) as reader:
        loader = DataLoader(reader, batch_size=10, prefetch=1)
        for _ in loader:
            time.sleep(0.01)    # slow consumer: queues fill, threads park
        return reader.telemetry.recorder


def _dummy_batched_shuffle(synthetic_dataset, scalar_store, tmp_path):
    from petastorm_tpu.jax import BatchedDataLoader
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=1,
                           reader_pool_type="dummy") as reader:
        loader = BatchedDataLoader(reader, batch_size=25,
                                   shuffling_queue_capacity=60, seed=0)
        assert len(list(loader)) == 8
        return reader.telemetry.recorder


def _readahead(synthetic_dataset, scalar_store, tmp_path):
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=1,
                           reader_pool_type="thread", workers_count=2,
                           readahead_depth=3) as reader:
        assert sum(len(b.id) for b in reader) == 200
        return reader.telemetry.recorder


def _process_pool(synthetic_dataset, scalar_store, tmp_path):
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_store, num_epochs=1,
                           reader_pool_type="process",
                           workers_count=2) as reader:
        assert sum(len(b.id) for b in reader) == 200
        return reader.telemetry.recorder


def _mesh(synthetic_dataset, scalar_store, tmp_path):
    from petastorm_tpu.jax import MeshDataLoader, MeshReaderFactory
    factory = MeshReaderFactory(scalar_store, batched=True)
    with MeshDataLoader(factory, batch_size=40, seed=3,
                        num_epochs=1) as loader:
        assert sum(len(b["id"]) for b in loader) == 200
        return loader.telemetry.recorder


@pytest.mark.parametrize("scenario,names,exact", [
    (_thread_loader, ["worker_decode", "publish_wait", "pool_wait",
                      "collate", "host_batch", "stage", "queue_full", "h2d",
                      "deliver"], True),
    (_dummy_batched_shuffle, ["worker_decode", "pool_wait", "shuffle_add",
                              "shuffle_retrieve", "host_batch", "stage",
                              "h2d", "deliver"], True),
    (_readahead, ["fetch", "worker_decode", "pool_wait"], True),
    pytest.param(_process_pool, ["transport", "pool_wait"], True,
                 marks=pytest.mark.process_pool),
    # The per-host readers keep registries of their own; the annotations
    # are the process's: only the mesh plane's own names compare exactly.
    pytest.param(_mesh, ["mesh_pull", "mesh_assemble"], False,
                 marks=pytest.mark.mesh),
], ids=["thread_loader", "dummy_batched_shuffle", "readahead",
        "process_pool", "mesh"])
def test_every_span_site_lands_in_both_sinks_under_one_name(
        scenario, names, exact, annotations, synthetic_dataset, scalar_store,
        tmp_path):
    ring, profiler = _sink_counts(
        scenario(synthetic_dataset, scalar_store, tmp_path), annotations)
    for name in names:
        full = f"petastorm_tpu.{name}"
        assert ring[full] > 0, (name, dict(ring))
        assert ring[full] == profiler[full], (name, ring, profiler)
    if exact:
        assert ring == profiler


def test_spans_nest_on_their_thread_and_feed_the_counters(
        synthetic_dataset):
    """``pool_wait`` and ``collate`` are children of the ``host_batch``
    they ran in; one clock pair feeds a span and its site's counters."""
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.reader import make_reader
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False, reader_pool_type="thread",
                     workers_count=2) as reader:
        loader = DataLoader(reader, batch_size=10)
        assert len(list(loader)) == 10
        spans = reader.telemetry.recorder.spans()
        snap = reader.telemetry.snapshot()
        metrics = loader.metrics.as_dict()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name.split(".", 1)[1], []).append(sp)
    batches = {sp.span_id: sp for sp in by_name["host_batch"]}
    assert all(sp.trace.startswith("b") for sp in batches.values())
    for child in by_name["pool_wait"] + by_name["collate"]:
        parent = batches[child.parent_id]
        assert parent.thread == child.thread == "petastorm-tpu-stage"
        assert parent.start_s <= child.start_s
        assert (child.start_s + child.duration_s
                <= parent.start_s + parent.duration_s)
    assert {sp.thread for sp in by_name["h2d"]} == {"petastorm-tpu-h2d"}
    assert {sp.trace for sp in by_name["h2d"]} >= {
        sp.trace for sp in by_name["deliver"] if sp.trace}
    decode_s = sum(sp.duration_s for sp in by_name["worker_decode"])
    assert snap["histograms"]["worker.decode_s"]["sum"] == \
        pytest.approx(decode_s, abs=1e-5)
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("pool.w") and k.endswith(".busy_s")) == \
        pytest.approx(decode_s, abs=1e-4)
    assert metrics["stage_s"] == pytest.approx(
        sum(sp.duration_s for sp in by_name["stage"]), abs=1e-3)


def test_worker_decode_excludes_a_blocked_publish():
    """Results queue of 1 and a slow consumer: the decode span ends at the
    item's first publish, the blocked put is ``publish_wait``, and neither
    ``worker.decode_s`` nor ``pool.w0.busy_s`` counts it."""
    import time
    from petastorm_tpu.workers_pool import EmptyResultError
    from petastorm_tpu.workers_pool.thread_pool import ThreadPool
    from petastorm_tpu.workers_pool.worker_base import WorkerBase

    planted = []

    class SleepyWorker(WorkerBase):
        def process(self, n):
            t0 = time.perf_counter()
            time.sleep(0.02)                     # the planted decode
            planted.append(time.perf_counter() - t0)
            self.publish_func(n)

    reg = TelemetryRegistry()
    pool = ThreadPool(1, results_queue_size=1)
    pool.telemetry = reg
    pool.start(SleepyWorker)
    for n in range(6):
        pool.ventilate(n)
    got = []
    t0 = time.perf_counter()
    while len(got) < 6:
        time.sleep(0.06)                         # the slow consumer
        got.append(pool.get_results())
    wall = time.perf_counter() - t0
    pool.stop()
    pool.join()
    assert got == list(range(6))
    spans = reg.recorder.spans()
    decode = [sp for sp in spans
              if sp.name == "petastorm_tpu.worker_decode"]
    blocked = [sp for sp in spans
               if sp.name == "petastorm_tpu.publish_wait"]
    decode_s = sum(sp.duration_s for sp in decode)
    assert len(decode) == 6 and decode_s <= 1.1 * sum(planted)
    # The worker's wall is decode + blocked publish: the rest is all there.
    blocked_s = sum(sp.duration_s for sp in blocked)
    assert blocked and {sp.thread for sp in blocked} == {"pt-worker-0"}
    assert decode_s + blocked_s >= 0.8 * wall
    assert blocked_s >= decode_s
    snap = reg.snapshot()
    assert snap["histograms"]["worker.decode_s"]["sum"] == \
        pytest.approx(decode_s, abs=1e-5)
    assert snap["counters"]["pool.w0.busy_s"] == \
        pytest.approx(decode_s, abs=1e-5)
    assert snap["counters"]["trace.span.decode_s"] == \
        pytest.approx(decode_s, abs=1e-5)
    with pytest.raises(EmptyResultError):
        pool.get_results()


def _slow_rows(seconds):
    import time
    from petastorm_tpu.transform import TransformSpec

    def slow(row):
        time.sleep(seconds)
        return row
    return TransformSpec(slow)


def test_deliver_brackets_what_the_consumer_pays_when_starved(
        synthetic_dataset):
    """A sleeping transform starves the loader: the sum of ``deliver``
    equals, within 5%, what the test itself measures around ``next(it)``
    (the agreement under starvation PERF.md called not measured), and
    ``loader.delivery_wait_s`` is fed from those spans."""
    import time
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.reader import make_reader
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     num_epochs=3, shuffle_row_groups=False,
                     reader_pool_type="thread", workers_count=1,
                     transform_spec=_slow_rows(0.003)) as reader:
        loader = DataLoader(reader, batch_size=10)
        it = iter(loader)
        next(it)                                  # spin-up
        waits = []
        for _ in range(20):
            t0 = time.perf_counter()
            next(it)
            waits.append(time.perf_counter() - t0)
        delivered = [sp for sp in reader.telemetry.recorder.spans()
                     if sp.name == "petastorm_tpu.deliver"]
        it.close()
        report = loader.stall_report()
    assert len(delivered) == 21
    inside = sum(sp.duration_s for sp in delivered[1:])
    assert sum(waits) > 0.3                       # starved: ~30 ms a batch
    assert inside == pytest.approx(sum(waits), rel=0.05)
    assert all(sp.extra["depth"] == 0 for sp in delivered[2:])
    assert report["steps"] == 20
    assert report["delivery_wait_s"] == pytest.approx(inside, abs=1e-4)
    assert report["verdict"] == "host_bound"


def _stager_tiling(synthetic_dataset, consumer_s, row_s):
    """One short run -> (names of the staging thread's top-level spans,
    the share of its wall they cover, every span)."""
    import time
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.reader import make_reader
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     num_epochs=3, shuffle_row_groups=False,
                     reader_pool_type="thread", workers_count=2,
                     transform_spec=_slow_rows(row_s)) as reader:
        loader = DataLoader(reader, batch_size=10)
        for n, _ in enumerate(loader):
            time.sleep(consumer_s)
            if n == 25:
                break
        spans = reader.telemetry.recorder.spans()
    top = [sp for sp in spans if sp.thread == "petastorm-tpu-stage"
           and not sp.parent_id][2:-2]
    wall = (top[-1].start_s + top[-1].duration_s) - top[0].start_s
    return ({sp.name.split(".", 1)[1] for sp in top},
            sum(sp.duration_s for sp in top) / wall, spans)


@pytest.mark.parametrize("consumer_s,row_s", [(0.03, 0.0), (0.0, 0.003)],
                         ids=["fed", "starved"])
def test_staging_threads_spans_tile_its_wall(synthetic_dataset, consumer_s,
                                             row_s):
    """``host_batch`` + ``stage`` + ``queue_full`` cover at least 98% of
    the staging thread's wall, so its busy share can be read off the
    spans (99.9% in both cells on the chip, PERF.md). What lies between
    two spans is a few bytecodes; a thread descheduled right there on a
    loaded test host reads lower, so the best of three short runs
    counts."""
    for _ in range(3):
        names, share, spans = _stager_tiling(synthetic_dataset, consumer_s,
                                             row_s)
        assert names <= {"host_batch", "stage", "queue_full"}
        if consumer_s:      # fed: the stager parks, every transfer landed
            assert "queue_full" in names
            assert all(sp.extra["ready"] for sp in spans[40:]
                       if sp.name == "petastorm_tpu.deliver" and sp.trace)
        if share >= 0.98:
            return
    assert share >= 0.98, (names, share)


class _PlantedArray:
    """Stands in for a staged device array whose transfer takes
    ``seconds`` (or that was deleted before it was seen ready)."""

    class sharding:
        device_set = (0,)

    def __init__(self, seconds, deleted=False):
        self.seconds, self.deleted = seconds, deleted
        self.nbytes = 8

    def block_until_ready(self):
        import time
        if self.deleted:
            raise RuntimeError("Array has been deleted with shape=int64[8].")
        time.sleep(self.seconds)

    def is_ready(self):
        return False


def test_h2d_closes_off_the_staging_thread(synthetic_dataset, monkeypatch):
    """With a planted slow readiness the ``h2d`` span of ``b{n}`` covers
    it, and does not serialise the stager: ``collate`` of ``b{n+1}``
    starts before ``h2d`` of ``b{n}`` ends. A deleted array closes it."""
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.reader import make_reader
    with make_reader(synthetic_dataset.url, schema_fields=["id"],
                     shuffle_row_groups=False,
                     reader_pool_type="dummy") as reader:
        loader = DataLoader(reader, batch_size=10, prefetch=4)
        staged = iter(range(100))
        monkeypatch.setattr(
            loader, "_stage", lambda hb: {"x": _PlantedArray(
                0.05, deleted=next(staged) == 3)})
        assert len(list(loader)) == 10
        spans = reader.telemetry.recorder.spans()
    h2d = {sp.trace: sp for sp in spans if sp.name == "petastorm_tpu.h2d"}
    batch_of = {sp.span_id: sp.trace for sp in spans
                if sp.name == "petastorm_tpu.host_batch"}
    collate = {batch_of[sp.parent_id]: sp for sp in spans
               if sp.name == "petastorm_tpu.collate"}
    stage = {sp.trace: sp for sp in spans
             if sp.name == "petastorm_tpu.stage"}
    assert len(h2d) == 10
    # handed over while the (planted) transfer was still in flight
    assert not any(sp.extra["ready"] for sp in spans
                   if sp.name == "petastorm_tpu.deliver"
                   and sp.trace in ("b1", "b2", "b3"))
    assert h2d["b4"].extra.get("deleted") is True
    for n in (1, 2, 3):
        this, following = h2d[f"b{n}"], collate[f"b{n + 1}"]
        assert this.duration_s >= 0.05 and "deleted" not in this.extra
        assert this.extra == {"bytes": 0, "shards": 1}
        # it opens where stage closed, on the stager's clock pair
        assert this.start_s == pytest.approx(
            stage[f"b{n}"].start_s + stage[f"b{n}"].duration_s, abs=1e-9)
        assert following.start_s < this.start_s + this.duration_s


def test_anchor_places_the_ring_on_unix_nanoseconds():
    import time
    from petastorm_tpu.telemetry import SpanRecorder
    perf_ns, unix_ns = SpanRecorder.anchor()
    with SpanRecorder().span("x") as sp:
        now_ns = time.time_ns()
    mapped = sp.start_s * 1e9 - perf_ns + unix_ns
    assert abs(mapped - now_ns) < 1e6      # within 1 ms


# --------------------------------------------- tools/check_spans.py lint
def _lint(tmp_path, body, required):
    from tools import check_spans
    (tmp_path / "mod.py").write_text(body)
    return check_spans.check_file("mod.py", required, str(tmp_path))


def test_check_spans_registry_matches_the_code():
    from tools import check_spans
    assert check_spans.main([]) == 0
    sites = {name for fns in check_spans.ENTRY_POINTS.values()
             for names in fns.values() for name in names}
    assert {"worker_decode", "publish_wait", "pool_wait", "collate",
            "host_batch", "stage", "queue_full", "h2d", "deliver", "fetch",
            "transport", "shuffle_add", "shuffle_retrieve", "mesh_pull",
            "mesh_assemble"} <= sites


@pytest.mark.parametrize("body,fault", [
    ("def f(t):\n    with t.span('petastorm_tpu.stage'):\n        pass\n",
     "must open the span 'petastorm_tpu.stage' through traced_span"),
    ("def f(t):\n    with traced_span('petastorm_tpu.other', t):\n"
     "        pass\n", "must open the span 'petastorm_tpu.stage'"),
    ("def g(t):\n    pass\n", "entry point f not found"),
    ("def f(t, rows):\n    with traced_span('petastorm_tpu.stage', t):\n"
     "        pass\n    for row in rows:\n"
     "        with traced_span('petastorm_tpu.x', t):\n            pass\n",
     "inside a per-row loop"),
], ids=["ring_only", "other_name", "out_of_sync", "per_row"])
def test_check_spans_flags(tmp_path, body, fault):
    violations = _lint(tmp_path, body, {"f": ["stage"]})
    assert len(violations) == 1 and fault in violations[0], violations


def test_check_spans_passes_a_site_in_both_sinks(tmp_path):
    body = ("def f(t):  \n    with traced_span('petastorm_tpu.stage', t):\n"
            "        pass\n"
            "def w(t):  # span-ok: spans in its caller\n    pass\n")
    assert _lint(tmp_path, body, {"f": ["stage"], "w": ["stage"]}) == []
