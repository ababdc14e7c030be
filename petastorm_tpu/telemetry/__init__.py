"""End-to-end pipeline telemetry.

One :class:`TelemetryRegistry` per pipeline collects:

* **spans** — monotonic-clock timed sections with thread/process provenance
  (ring-buffer bounded, :class:`SpanRecorder`), kept name-coherent with the
  ``jax.profiler`` trace annotations emitted on the same paths;
* **histograms** — streaming fixed-bucket latency / byte-size distributions
  (:class:`StreamingHistogram`);
* **gauges** — live queue depths: ventilator backlog, worker-pool results
  queue, shuffling-buffer fill, prefetch queue;
* **counters** — rows, batches, bytes, per-stage cumulative seconds;
* **stall attribution** — per-``__next__`` host-bound / device-bound /
  balanced classification (:class:`StallAttributor`).

Exports: Prometheus text format and JSON snapshots
(:mod:`petastorm_tpu.telemetry.exporters`), plus a ``python -m
petastorm_tpu.telemetry`` CLI to dump/watch a live pipeline. See
``docs/observability.md``.

Stage metric names (the documented schema; also the keys behind the
loaders' ``stage_breakdown()``):

==============================  =================================================
metric                          meaning
==============================  =================================================
``worker.decode_s``             in-worker row-group read+decode (histogram; in-
                                process pools only — 0 for spawned process pools)
``reader.pool_wait_s``          consumer blocked on the pool's results queue
``loader.shuffle_s``            shuffling-buffer add/retrieve time (counter)
``loader.host_wait_s``          staging thread waiting on batch production
``loader.stage_s``              sanitize + ``device_put`` dispatch (histogram)
``loader.delivery_wait_s``      consumer blocked on the staged-batch queue
                                (the "device_put wait" a training step sees)
``ventilator.backlog``          ventilated-but-unprocessed row groups (gauge)
``pool.results_queue_depth``    results queue fill (gauge)
``shuffle_buffer.fill``         shuffling-buffer occupancy (gauge)
==============================  =================================================
"""
from petastorm_tpu.telemetry.exporters import (PeriodicExporter, from_json,
                                               parse_prometheus_text,
                                               to_json, to_prometheus_text,
                                               write_snapshot)
from petastorm_tpu.telemetry.histogram import (LATENCY_BOUNDS_S, SIZE_BOUNDS,
                                               StreamingHistogram)
from petastorm_tpu.telemetry.recorder import Span, SpanRecorder
from petastorm_tpu.telemetry.registry import (SNAPSHOT_SCHEMA_VERSION,
                                              Counter, Gauge,
                                              TelemetryRegistry)
from petastorm_tpu.telemetry.stall import StallAttributor

#: Environment variable: when set to a path, every Reader auto-starts a
#: PeriodicExporter writing JSON snapshots there (``.prom`` suffix switches
#: to Prometheus text format) — the hook ``python -m petastorm_tpu.telemetry
#: watch <path>`` consumes.
TELEMETRY_EXPORT_ENV = "PETASTORM_TPU_TELEMETRY_EXPORT"

#: Environment variable: any non-empty value puts every new registry in
#: TRACE mode — row-group lineage ids minted at ventilation, raw spans in
#: snapshots, ring capacity grown so a whole epoch survives for ``python -m
#: petastorm_tpu.telemetry trace`` export. (Spans themselves are recorded
#: from construction; ``registry.recorder.disable()`` turns them off.)
TELEMETRY_TRACE_ENV = "PETASTORM_TPU_TELEMETRY_TRACE"

#: Environment variable: start an :class:`~petastorm_tpu.telemetry.slo.
#: SloWatcher` on every Reader's pipeline registry. ``1`` = the default
#: rule set; any other value is a ``parse_rules`` spec, e.g.
#: ``input_stall_pct<=1,batch_p99_s<=0.5``.
SLO_WATCH_ENV = "PETASTORM_TPU_SLO_WATCH"


def make_registry() -> TelemetryRegistry:
    """A registry honoring :data:`TELEMETRY_TRACE_ENV`."""
    import os
    registry = TelemetryRegistry()
    if os.environ.get(TELEMETRY_TRACE_ENV):
        registry.recorder.enable_trace()
    return registry


from petastorm_tpu.telemetry.slo import (DEFAULT_RULES, SloRule,  # noqa: E402
                                         SloWatcher, evaluate_rules,
                                         parse_rules)
from petastorm_tpu.telemetry.trace import (CriticalPathAttributor,  # noqa: E402
                                           TraceContext, complete_lineages,
                                           lineage_index, to_chrome_trace,
                                           write_chrome_trace)
from petastorm_tpu.telemetry.timeseries import (DEFAULT_SERIES,  # noqa: E402
                                                TIMELINE_ENV,
                                                MetricsTimeline, SeriesSpec,
                                                TimelineSampler,
                                                timeline_interval_from_env)
from petastorm_tpu.telemetry.federation import (federate_snapshots,  # noqa: E402
                                                federate_timelines)
from petastorm_tpu.telemetry.anomaly import (AnomalyMonitor,  # noqa: E402
                                             AnomalyRule,
                                             default_anomaly_rules,
                                             detect_over_timeline)
from petastorm_tpu.telemetry.postmortem import (BLACKBOX_ENV,  # noqa: E402
                                                BlackBox,
                                                blackbox_dir_from_env)
from petastorm_tpu.telemetry.fabric import (FABRIC_SCHEMA_VERSION,  # noqa: E402
                                            TELEMETRY_PUBLISH_ENV,
                                            TelemetryAggregator,
                                            TelemetryPublisher,
                                            fabric_available,
                                            publish_addr_from_env)
from petastorm_tpu.telemetry.accounting import (  # noqa: E402
    ACCOUNTING_FIELDS, ACCOUNTING_SCHEMA_VERSION, AccountingLedger,
    accounting_totals, merge_accounting_reports)

__all__ = [
    "ACCOUNTING_FIELDS", "ACCOUNTING_SCHEMA_VERSION", "AccountingLedger",
    "AnomalyMonitor", "AnomalyRule", "BLACKBOX_ENV", "BlackBox",
    "Counter", "CriticalPathAttributor", "DEFAULT_RULES", "DEFAULT_SERIES",
    "FABRIC_SCHEMA_VERSION", "Gauge", "LATENCY_BOUNDS_S", "MetricsTimeline",
    "PeriodicExporter", "SIZE_BOUNDS", "SLO_WATCH_ENV",
    "SNAPSHOT_SCHEMA_VERSION", "SeriesSpec", "SloRule", "SloWatcher",
    "Span", "SpanRecorder", "StallAttributor", "StreamingHistogram",
    "TELEMETRY_EXPORT_ENV", "TELEMETRY_PUBLISH_ENV", "TELEMETRY_TRACE_ENV",
    "TIMELINE_ENV", "TelemetryAggregator",
    "TelemetryPublisher", "TelemetryRegistry", "TimelineSampler",
    "TraceContext", "accounting_totals", "blackbox_dir_from_env",
    "complete_lineages", "default_anomaly_rules", "detect_over_timeline",
    "evaluate_rules", "fabric_available", "federate_snapshots",
    "federate_timelines", "from_json", "lineage_index", "make_registry",
    "merge_accounting_reports", "parse_prometheus_text", "parse_rules",
    "publish_addr_from_env", "timeline_interval_from_env",
    "to_chrome_trace", "to_json", "to_prometheus_text",
    "write_chrome_trace", "write_snapshot",
]
