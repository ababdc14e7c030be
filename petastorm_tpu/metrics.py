"""Structured pipeline metrics + profiler trace annotations.

The reference's observability is three ad-hoc hooks (cProfile-wrapped
threads, per-pool diagnostics dicts, a TF queue-size node — SURVEY.md §5).
Here every loader keeps a :class:`PipelineMetrics` — a thread-safe view over
the pipeline's :class:`~petastorm_tpu.telemetry.TelemetryRegistry` — and the
staging path is wrapped in ``jax.profiler`` trace annotations, so
input-pipeline time shows up by name in TPU profiler traces next to the
device steps. The full per-stage picture (spans, queue gauges, stall
attribution, Prometheus/JSON export) lives in
:mod:`petastorm_tpu.telemetry`; see ``docs/observability.md``.
"""
from __future__ import annotations

import sys
import threading


class PipelineMetrics:
    """Thread-safe counters for one loader/reader pipeline.

    Backed by a :class:`~petastorm_tpu.telemetry.TelemetryRegistry` (the
    loader's, so one registry covers the whole pipeline): ``record_batch``
    feeds the registry's counters and per-stage latency/size histograms, and
    :meth:`as_dict` is a view over those counters. The registry itself is
    pipeline-cumulative — a second loader built over the same reader
    CONTINUES the pipeline's ``loader.*`` totals (Prometheus counters never
    go backwards) — so this view subtracts a construction-time baseline:
    the public attributes (``batches``, ``samples``, ``bytes_staged``,
    ``host_wait_s``, ``stage_s``) always count this instance's batches
    only, matching the old per-loader dataclass semantics.
    """

    _FIELDS = ("batches", "samples", "bytes_staged", "host_wait_s",
               "stage_s")

    def __init__(self, telemetry=None):
        if telemetry is None:
            from petastorm_tpu.telemetry import make_registry
            telemetry = make_registry()
        self.telemetry = telemetry
        self._lock = threading.Lock()
        from petastorm_tpu.telemetry import SIZE_BOUNDS
        self._counters = {
            "batches": telemetry.counter("loader.batches"),
            "samples": telemetry.counter("loader.samples"),
            "bytes_staged": telemetry.counter("loader.bytes_staged"),
            "host_wait_s": telemetry.counter("loader.host_wait_s"),
            "stage_s": telemetry.counter("loader.stage_s"),
        }
        self._host_wait_hist = telemetry.histogram("loader.host_wait_seconds")
        self._stage_hist = telemetry.histogram("loader.stage_seconds")
        self._bytes_hist = telemetry.histogram("loader.batch_bytes",
                                               bounds=SIZE_BOUNDS)
        self._base = {f: 0.0 for f in self._FIELDS}
        self._base = self._read_raw()

    def _read_raw(self) -> dict:
        raw = {f: self._counters[f].value for f in self._FIELDS}
        # A registry-wide ``telemetry.reset()`` zeroes the shared counters
        # underneath every live view; a raw value below our baseline can
        # only mean that happened, so re-baseline at zero (the reset point)
        # instead of reporting negative deltas forever after.
        for f, v in raw.items():
            if v < self._base[f]:
                self._base[f] = 0.0
        return raw

    def _delta(self, field: str):
        v = self._counters[field].value
        if v < self._base[field]:
            self._base[field] = 0.0
        return v - self._base[field]

    # ------------------------------------------------------- compat fields
    @property
    def batches(self) -> int:
        return int(self._delta("batches"))

    @property
    def samples(self) -> int:
        return int(self._delta("samples"))

    @property
    def bytes_staged(self) -> int:
        return int(self._delta("bytes_staged"))

    @property
    def host_wait_s(self) -> float:
        return self._delta("host_wait_s")

    @property
    def stage_s(self) -> float:
        return self._delta("stage_s")

    # ------------------------------------------------------------ recording
    def record_batch(self, samples: int, nbytes: int, host_wait_s: float,
                     stage_s: float) -> None:
        with self._lock:
            self._counters["batches"].add(1)
            self._counters["samples"].add(samples)
            self._counters["bytes_staged"].add(nbytes)
            self._counters["host_wait_s"].add(host_wait_s)
            self._counters["stage_s"].add(stage_s)
        # Distributions are additive — no need to hold the group lock.
        self._host_wait_hist.observe(host_wait_s)
        self._stage_hist.observe(stage_s)
        self._bytes_hist.observe(nbytes)

    @staticmethod
    def _rounded(raw: dict, base: dict) -> dict:
        return {"batches": int(raw["batches"] - base["batches"]),
                "samples": int(raw["samples"] - base["samples"]),
                "bytes_staged": int(raw["bytes_staged"]
                                    - base["bytes_staged"]),
                "host_wait_s": round(raw["host_wait_s"]
                                     - base["host_wait_s"], 4),
                "stage_s": round(raw["stage_s"] - base["stage_s"], 4)}

    def as_dict(self) -> dict:
        with self._lock:
            return self._rounded(self._read_raw(), self._base)

    def reset(self) -> dict:
        """Zero this view and return the pre-reset snapshot — one atomic
        operation, so a metrics poller can never lose a batch recorded
        between a separate read and reset (the old two-call race). Only
        the baseline advances; the shared registry metrics — counters AND
        the ``loader.*`` histograms — are untouched, because they may be
        exported (Prometheus series must never decrease) and are shared
        with any sibling loader over the same reader. Use
        ``telemetry.reset()`` to drain the whole registry."""
        with self._lock:
            raw = self._read_raw()
            snapshot = self._rounded(raw, self._base)
            self._base = raw
        return snapshot


_TRACE_ANNOTATION = None  # resolved once; False = jax unavailable


class _NoTrace:
    """Shared stand-in where there is no profiler to annotate for."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_TRACE = _NoTrace()


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``, or None where there is no
    profiler to annotate for: jax is not loaded in this process (a
    reader-only job, a worker pinned off the TPU — no profiler can be
    tracing it, and its spans must not import jax), or its profiler is
    unimportable. The import is attempted once (failed imports are not
    cached by python, and this sits on the per-batch hot path)."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_ANNOTATION = TraceAnnotation
        except ImportError:  # pragma: no cover
            _TRACE_ANNOTATION = False
    return _TRACE_ANNOTATION(name) if _TRACE_ANNOTATION else None


def trace(name: str):
    """A ``jax.profiler`` annotation context manager, or a shared no-op
    (see :func:`_annotation`)."""
    return _annotation(name) or _NO_TRACE


def traced_span(name: str, telemetry=None, extra=None, **span_kw):
    """THE span entry point of the hot path: one context manager, two
    sinks. It records ``name`` into ``telemetry``'s span ring and emits a
    ``jax.profiler`` annotation of the same name over the same interval,
    so an operator's Perfetto / XProf view shows the loader and worker
    lanes beside ``XLA Ops`` by construction, and the ring's readers and
    the profiler attribute time to identical labels. ``extra`` and the
    keyword args (``trace=`` / ``stage=`` / ``track=`` / ``start_s=``)
    pass through to :meth:`SpanRecorder.span`. Without a registry it is
    the bare annotation."""
    if telemetry is None:
        return trace(name)
    return telemetry.recorder.span(name, extra, annotation=_annotation(name),
                                   **span_kw)


__all__ = ["PipelineMetrics", "trace", "traced_span"]
