"""The readers of the program's own spans (``chipbench/layer_metrics/
_spans.py``): each gives a finite reading through a traced rehearsal of its
cell, on one and on four virtual devices; each falls silent when the ring
dropped spans of the window or holds none (the parent program); the clock
check and the idle gaps by stage on hand-built traces."""
import importlib
import json
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from chipbench import run, window
from chipbench.layer_metrics import _spans
from petastorm_tpu.telemetry import Span, SpanRecorder

ROOT = run.ROOT
NEW = {"rn50-jpeg224-1chip": [
           "decode_thread_ms_per_image", "stager_busy_pct.image",
           "h2d_ms_per_step.image", "delivery_wait_pct.image",
           "queue_depth_mean.image"],
       "mistral7b-tok4k-1chip": [
           "decode_thread_us_per_token", "stager_busy_pct.tokens",
           "h2d_ms_per_step.tokens", "delivery_wait_pct.tokens",
           "queue_depth_mean.tokens"]}


def test_the_manifest_lists_each_new_reader_for_its_cell_alone():
    """Alone when PR 26 brought them; later cells of the same kind append
    their names, so the cell is among the metric's ``workloads``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert cell in per_layer[name]["workloads"]
            assert per_layer[name]["source"] == "program_span"
            assert callable(run.layer_metric_reader(name))


@pytest.mark.parametrize("cell", sorted(NEW))
def test_each_reader_reads_a_traced_rehearsal_on_one_device(cell):
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell,
         "--seed", "2147483659", "--seconds", "1.5", "--trace", "1",
         "--rehearse-cpu"], cwd=ROOT, capture_output=True, text=True,
        timeout=400, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    readings = json.loads(done.stdout.splitlines()[-1])["rehearsal_readings"]
    for name in NEW[cell]:
        assert math.isfinite(readings[name]["value"]), (name, readings)
    stall = readings[[n for n in readings if n.startswith("input_stall")][0]]
    inside = readings[[n for n in NEW[cell] if n.startswith("delivery")][0]]
    assert inside["value"] <= stall["value"]   # the inside twin
    depth = readings[[n for n in NEW[cell] if n.startswith("queue")][0]]
    assert 0 <= depth["value"] <= 2            # prefetch=2


def test_each_image_reader_reads_a_rehearsal_on_four_devices(tmp_path):
    """One ``h2d`` span a batch, closed by the last of its four shards."""
    pipeline = importlib.import_module("chipbench.pipelines.image_classifier")
    cell_name = "rn50-jpeg224-1chip"
    bench, cell, config, traffic = run.load_cell(cell_name, rehearsal=True)
    job = pipeline.Job(config, traffic, jax.devices()[:4], 11,
                       str(tmp_path / "store"))
    captured = {}
    reader_of = run.layer_metric_reader

    def capturing(name):
        def read(run_dict):
            captured["run"] = run_dict
            return reader_of(name)(run_dict)
        return read

    run.layer_metric_reader = capturing
    try:
        result = run.drive(
            job, cell=cell, bench=bench, seconds=1.0, trace=True, seed=11,
            device={"platform": "cpu", "kind": "cpu", "count": 4},
            emit=lambda line: None, started_at=0.0, rehearsal=True)
    finally:
        run.layer_metric_reader = reader_of
    readings = result["rehearsal_readings"]
    for name in NEW[cell_name]:
        assert math.isfinite(readings[name]["value"]), (name, readings)
    by_name = _spans.spans_in(captured["run"], captured["run"]["log"])
    assert {s.extra["shards"] for _, _, s in by_name["h2d"]} == {4}
    assert _spans.stager_tile_share(
        captured["run"], captured["run"]["log"]) >= _spans.TILE_SHARE
    assert _spans.self_seconds(by_name, "host_batch") < _spans.seconds(
        by_name["host_batch"])
    # The clock check on this backend's own trace: the pairs agree.
    check = _spans.clock_check(captured["run"])
    assert check["pairs"] == captured["run"]["traced_log"].steps
    assert check["ok"], check


# ----------------------------------------------------- hand-built rings
def span(name, start, dur, thread=_spans.STAGER_THREAD, **kw):
    return Span(_spans.PREFIX + name, start, dur, thread, 1, 1, **kw)


def fed_window(capacity=1024, before=True):
    """A run whose ring holds a 10 s window of a fed loader: one batch a
    second, the stager parked most of it."""
    rec = SpanRecorder(capacity=capacity)
    spans = [span("host_batch", -1.0, 0.5)] if before else []
    sid = 0
    for n in range(10):
        t = float(n)
        sid += 3
        spans += [
            span("host_batch", t, 0.3, span_id=sid, trace=f"b{n}"),
            span("pool_wait", t, 0.1, parent_id=sid),
            span("collate", t + 0.2, 0.1, parent_id=sid),
            span("stage", t + 0.3, 0.1, trace=f"b{n}"),
            span("queue_full", t + 0.4, 0.6, trace=f"b{n}"),
            span("h2d", t + 0.4, 0.004 + 0.001 * (n % 3), "petastorm-tpu-h2d",
                 extra={"bytes": 8, "shards": 1}),
            span("deliver", t + 0.5, 0.002, "MainThread",
                 extra={"depth": 2, "ready": True}),
            span("worker_decode", t, 0.8, "pt-worker-0"),
            span("publish_wait", t + 0.8, 0.2, "pt-worker-0")]
    rec.ingest(sorted(spans, key=lambda s: s.start_s + s.duration_s))
    log = window.WindowLog(t_open=0.0, t_close=10.0,
                           completed_at=[float(n + 1) for n in range(10)])
    job = SimpleNamespace(
        _loader=SimpleNamespace(telemetry=SimpleNamespace(recorder=rec)),
        items_per_step=100)
    return {"job": job, "log": log, "trace": None, "traced_log": None}


def test_readers_on_a_hand_built_ring():
    made = fed_window()
    assert _spans.decode_thread_s_per_item(made) == pytest.approx(8.0 / 1000)
    assert _spans.stager_busy_pct(made) == pytest.approx(30.0)
    assert _spans.h2d_ms(made) == pytest.approx(5.0)
    assert _spans.delivery_wait_pct(made) == pytest.approx(0.2)
    assert _spans.queue_depth_mean(made) == 2.0
    by_name = _spans.spans_in(made, made["log"])
    assert _spans.self_seconds(by_name, "host_batch") == pytest.approx(1.0)
    assert "publish_wait" in by_name          # never part of decode


READERS = [name for names in NEW.values() for name in names]


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_when_the_window_was_dropped(name):
    """The ring evicted spans that closed inside the window: nothing the
    reader could say about the window would be whole."""
    read = run.layer_metric_reader(name)
    assert read(fed_window()) is not None
    dropped = fed_window(capacity=40)
    assert dropped["job"]._loader.telemetry.recorder.dropped > 0
    assert read(dropped) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_without_spans_or_tiling(name):
    read = run.layer_metric_reader(name)
    empty = fed_window()
    empty["job"]._loader.telemetry.recorder.drain()
    assert read(empty) is None                 # the parent program
    assert read({"job": object(), "log": empty["log"]}) is None
    untiled = fed_window()
    rec = untiled["job"]._loader.telemetry.recorder
    kept = [s for s in rec.drain() if not s.name.endswith("queue_full")]
    rec.ingest(kept)
    if "h2d" in name:    # a transfer's length needs no tiling of the stager
        assert read(untiled) is not None
    else:
        assert read(untiled) is None


def test_a_ring_that_dropped_only_before_the_window_is_whole():
    """Dropping is judged against the window, not for ever: what closed
    before it opened may be gone."""
    made = fed_window()
    rec = made["job"]._loader.telemetry.recorder
    spans = rec.drain()
    small = SpanRecorder(capacity=len(spans))
    small.ingest([span("host_batch", -3.0, 0.5)] * 5)
    small.ingest(spans)
    assert small.dropped == 5
    made["job"]._loader.telemetry.recorder = small
    assert _spans.stager_busy_pct(made) == pytest.approx(30.0)


def traced(made, offset_ns=1.7e18, jitter_ns=(0, 40_000, -30_000)):
    """Lay a traced window over ``made``: three steps, the device idle
    between them, ``chipbench/readback`` spans ending at the stamps."""
    log = window.WindowLog(t_open=2.0, t_close=5.0,
                           completed_at=[3.0, 4.0, 5.0])
    made["traced_log"] = log
    made["trace"] = {
        "spans": [("readback", offset_ns + (t - 0.05) * 1e9,
                   0.05e9 + j) for t, j in zip(log.completed_at, jitter_ns)],
        "devices": {0: [("op", offset_ns + s * 1e9, d * 1e9) for s, d in
                        # busy 2.00-2.05, 2.15-2.25, 3.35-3.50, 4.95-5.00
                        ((2.0, 0.05), (2.15, 0.1), (3.35, 0.15),
                         (4.95, 0.05))]}}
    return made


def test_clock_check_takes_the_offset_from_the_pairs():
    check = _spans.clock_check(traced(fed_window()))
    assert check["pairs"] == 3 and check["ok"]
    assert check["offset_ns"] == pytest.approx(1.7e18)
    assert check["residual_us"] == pytest.approx(40.0, abs=1.0)
    assert "anchor_gap_us" in check
    off = _spans.clock_check(traced(fed_window(), jitter_ns=(0, 0, 9e5)))
    assert off["ok"] is False and off["residual_us"] == pytest.approx(900.0, abs=1.0)
    assert _spans.clock_check(fed_window()) is None


def test_idle_seconds_go_to_the_link_of_the_chain_they_fall_on():
    gaps = dict(_spans.idle_seconds_by_stage(traced(fed_window())))
    # 2.05-2.15 lies in pool_wait of b2 (2.0-2.1) for 0.05 and in its row
    # walk (2.1-2.2) for 0.05: pool_wait comes first of equals.
    # 2.25-3.35: collate 2.2-2.3 (0.05), stage 2.3-2.4, queue_full 2.4-3.0
    # (0.6) -> queue_full. 3.50-4.95: queue_full 3.5-4.0 and 4.4-4.95.
    # (Unix nanoseconds in a double resolve to 256 ns.)
    assert gaps == {"pool_wait": pytest.approx(0.1, abs=1e-5),
                    "queue_full": pytest.approx(1.1 + 1.45, abs=1e-5)}
    assert "deliver" not in gaps and "worker_decode" not in gaps
    bad_clock = traced(fed_window(), jitter_ns=(0, 0, 9e5))
    assert _spans.idle_seconds_by_stage(bad_clock) is None
    assert _spans.idle_seconds_by_stage(fed_window()) is None
