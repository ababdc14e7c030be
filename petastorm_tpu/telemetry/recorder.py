"""Low-overhead span/event recorder.

A :class:`SpanRecorder` collects named, monotonic-clock spans with thread and
process provenance into a bounded ring buffer (old spans are evicted, the
pipeline never grows without bound). It records from construction: span
sites fire per row group and per batch, never per row, so the ring costs a
few hundred appends a second (``disable()`` is the operator's switch: a
span then still times itself for its site's counters, and skips the ring).

A span has two sinks. The ring is one; the other is an optional
``annotation`` context manager (``jax.profiler.TraceAnnotation`` of the
same name, supplied by :func:`petastorm_tpu.metrics.traced_span`) entered
and left around the same interval, so a device trace shows the span in its
thread's lane. Spans opened on one thread nest: the innermost open span is
the parent of the next one opened there (``parent_id``), which is what a
reader needs for self time (duration minus children).

Trace mode (docs/observability.md "Trace plane") layers batch lineage on
top: spans may carry a ``trace`` id (``e{epoch}:g{ordinal}`` — the work
item's epoch/row-group-ordinal lineage), a ``stage`` name (``ventilate``,
``fetch``, ``decode``, ``transport``, ``shuffle``, ``stage``, ``pull``,
``assemble``), and a ``track`` (the display lane — ``worker:2``,
``fetch:0``, ``h3:pull``). :meth:`enable_trace` turns retention up so a
whole epoch's raw spans survive for Chrome-trace export
(:mod:`petastorm_tpu.telemetry.trace`); spans recorded in other processes
cross the boundary as compact tuples via :meth:`record_remote`.

Clock discipline: spans use ``time.perf_counter()`` exclusively.
``time.time()`` is wall-clock and can step backwards under NTP slew — it is
banned from hot paths repo-wide (enforced by ``tools/check_monotonic.py``).
:meth:`SpanRecorder.anchor` pairs the two clocks once, so a reader can place
a span on Unix nanoseconds (the profiler's clock).
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional, Sequence

__all__ = ["Span", "SpanRecorder", "SPAN_CAPACITY", "TRACE_SPAN_CAPACITY"]

#: Default ring capacity. One measured window of four chips at the image
#: cell's per-chip rate is ~650 spans/s x 30 s = 19.5k spans (row groups x
#: 3 + batches x 6); the ring must hold such a window whole, because a
#: reader refuses a window the ring dropped spans of.
SPAN_CAPACITY = 32768

#: Ring capacity :meth:`SpanRecorder.enable_trace` grows to: large enough
#: that an 8-host simulated mesh epoch (hundreds of row groups x ~6 stages)
#: retains every lineage span, small enough to stay a bounded buffer.
TRACE_SPAN_CAPACITY = 65536

#: Process-wide span-id allocator (``itertools.count.__next__`` is atomic
#: on CPython); 0 means "no id assigned".
_SPAN_IDS = itertools.count(1)

#: Cached pid for span provenance: ``os.getpid()`` is a real syscall and
#: under seccomp-filtered sandboxes costs tens of microseconds — per-record
#: that dwarfed the whole recording path. The pid only changes across
#: fork(), so refresh it in fork children; spawned workers (this repo's
#: process pools) re-import the module and cache their own.
_PID = os.getpid()


def _refresh_pid() -> None:
    global _PID
    _PID = os.getpid()


if hasattr(os, "register_at_fork"):  # pragma: no branch
    os.register_at_fork(after_in_child=_refresh_pid)


@dataclass(slots=True)
class Span:
    """One completed span. ``start_s`` is a ``perf_counter`` timestamp —
    meaningful only relative to other spans from the same process (remote
    spans are re-anchored to the consumer's clock on ingest)."""
    name: str
    start_s: float
    duration_s: float
    thread: str
    thread_id: int
    pid: int
    extra: Optional[dict] = field(default=None)
    #: Lineage id (``e{epoch}:g{ordinal}`` for row-group work items,
    #: ``b{n}`` for assembled batches); None outside trace mode.
    trace: Optional[str] = field(default=None)
    #: Pipeline stage this span's time belongs to (critical-path edge).
    stage: Optional[str] = field(default=None)
    #: Display lane for trace export (one track per host/worker/stage).
    track: Optional[str] = field(default=None)
    span_id: int = 0
    parent_id: int = 0

    def as_dict(self) -> dict:
        d = {"name": self.name, "start_s": round(self.start_s, 6),
             "duration_s": round(self.duration_s, 6), "thread": self.thread,
             "thread_id": self.thread_id, "pid": self.pid}
        if self.extra:
            d["extra"] = dict(self.extra)
        if self.trace is not None:
            d["trace"] = self.trace
        if self.stage is not None:
            d["stage"] = self.stage
        if self.track is not None:
            d["track"] = self.track
        if self.span_id:
            d["span_id"] = self.span_id
        if self.parent_id:
            d["parent_id"] = self.parent_id
        return d


#: Innermost open span per thread (``.span`` attribute): the parent of
#: the next span opened on that thread.
_OPEN = threading.local()


class _LiveSpan:
    """One open span: the ring's record and (optionally) the profiler's
    annotation over the same interval. ``start_s`` / ``duration_s`` stay
    readable after the span closed, so the one clock pair also feeds the
    site's counters. ``close()`` ends it early (idempotent), for a span
    whose end is not the end of a ``with`` block."""
    __slots__ = ("_recorder", "_name", "extra", "trace", "_stage",
                 "_track", "_parent", "_annotation", "_open", "span_id",
                 "parent_id", "start_s", "duration_s")

    def __init__(self, recorder, name, extra, trace=None, stage=None,
                 track=None, parent_id=0, annotation=None, start_s=None):
        self._recorder = recorder
        self._name = name
        self.extra = extra
        self.trace = trace
        self._stage = stage
        self._track = track
        self._annotation = annotation
        self._open = False
        self.parent_id = parent_id
        self.span_id = 0
        self.start_s = start_s
        self.duration_s = 0.0

    def __enter__(self):
        # Stamped first and (in close) last: a span covers its own
        # bookkeeping, so the spans of one thread tile its wall time and
        # what a caller pays around a span is a few bytecodes.
        if self.start_s is None:
            self.start_s = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__enter__()
        self._parent = getattr(_OPEN, "span", None)
        if not self.parent_id and self._parent is not None:
            self.parent_id = self._parent.span_id
        _OPEN.span = self
        self.span_id = next(_SPAN_IDS)
        self._open = True
        return self

    def close(self):
        if not self._open:
            return
        self._open = False
        _OPEN.span = self._parent
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        self._recorder._close(self)

    def __exit__(self, *exc):
        self.close()
        return False


class SpanRecorder:
    """Ring-buffer bounded span sink.

    :param capacity: max retained spans (oldest evicted first)
    :param enabled: record spans when True (the default); when False a
        span still times itself and annotates, and the ring stays empty
    """

    def __init__(self, capacity: int = SPAN_CAPACITY, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._spans: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped = 0
        self.enabled = bool(enabled)
        #: Trace mode: raw spans (with lineage fields) are retained for
        #: Chrome-trace export and included in registry snapshots.
        self.trace_enabled = False
        self.capacity = capacity
        #: Optional callback ``(stage, duration_s)`` invoked for every
        #: recorded span carrying a stage — the registry wires it to the
        #: ``trace.span.{stage}_s`` self-time counters.
        self.on_stage = None

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def enable_trace(self, capacity: Optional[int] = None) -> None:
        """Turn on trace retention: spans on, lineage fields recorded, and
        the ring grown to ``capacity`` (default
        :data:`TRACE_SPAN_CAPACITY`) so a whole epoch's spans survive for
        export. Growing preserves already-recorded spans."""
        cap = int(capacity) if capacity else TRACE_SPAN_CAPACITY
        with self._lock:
            if cap > (self._spans.maxlen or 0):
                self._spans = deque(self._spans, maxlen=cap)
                self.capacity = cap
        self.enabled = True
        self.trace_enabled = True

    @staticmethod
    def anchor() -> tuple:
        """A fresh ``(perf_counter_ns, time_ns)`` pair taken back to back:
        ``start_s * 1e9 - perf_ns + unix_ns`` places a span on Unix
        nanoseconds, the clock of a profiler trace's events."""
        perf_ns = time.perf_counter_ns()
        unix_ns = time.time_ns()  # wall-clock-ok: one-shot stamp pairing the two clocks, never a duration
        return perf_ns, unix_ns

    def span(self, name: str, extra: Optional[dict] = None, *,
             trace: Optional[str] = None, stage: Optional[str] = None,
             track: Optional[str] = None, parent_id: int = 0,
             annotation=None, start_s: Optional[float] = None):
        """Context manager timing one span. ``trace`` / ``stage`` /
        ``track`` attach lineage provenance; ``annotation`` is the second
        sink, entered and left with the span; ``start_s`` backdates the
        record to a
        ``perf_counter`` stamp taken on another thread (a span handed over
        to the thread that closes it). While the recorder is disabled the
        span still times itself (``start_s`` / ``duration_s`` feed the
        site's counters) and still annotates; only the ring is skipped."""
        return _LiveSpan(self, name, extra, trace, stage, track, parent_id,
                         annotation, start_s)

    def record(self, name: str, start_s: float, duration_s: float,
               extra: Optional[dict] = None, trace: Optional[str] = None,
               stage: Optional[str] = None, track: Optional[str] = None,
               span_id: int = 0, parent_id: int = 0) -> None:
        if not self.enabled:
            return
        t = threading.current_thread()
        sp = Span(name, start_s, duration_s, t.name, t.ident or 0,
                  _PID, extra, trace, stage, track, span_id,
                  parent_id)
        self._append((sp,))
        if stage is not None and self.on_stage is not None:
            self.on_stage(stage, duration_s)

    def _close(self, live: "_LiveSpan") -> None:
        """End ``live``: its end stamp is the last thing taken before the
        ring append, under the lock (see :meth:`_LiveSpan.__enter__`)."""
        if not self.enabled:
            live.duration_s = time.perf_counter() - live.start_s
            return
        t = threading.current_thread()
        sp = Span(live._name, live.start_s, 0.0, t.name, t.ident or 0, _PID,
                  live.extra, live.trace, live._stage, live._track,
                  live.span_id, live.parent_id)
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._dropped += 1
            live.duration_s = sp.duration_s = (time.perf_counter()
                                               - live.start_s)
            self._spans.append(sp)
        if sp.stage is not None and self.on_stage is not None:
            self.on_stage(sp.stage, sp.duration_s)

    def record_event(self, name: str, extra: Optional[dict] = None, *,
                     trace: Optional[str] = None,
                     stage: Optional[str] = None,
                     track: Optional[str] = None) -> None:
        """Zero-duration marker (e.g. 'epoch_end', 'worker_failure')."""
        self.record(name, time.perf_counter(), 0.0, extra=extra,
                    trace=trace, stage=stage, track=track)

    def record_remote(self, compact_spans: Sequence, pid: int = 0,
                      anchor_s: Optional[float] = None) -> None:
        """Ingest spans recorded in ANOTHER process, shipped as compact
        ``(name, stage, duration_s, trace, track)`` tuples (the ctrl-frame
        piggyback — see docs/observability.md "Cross-process propagation").
        Remote ``perf_counter`` clocks are not comparable to ours, so each
        span is re-anchored: it *ends* at ``anchor_s`` (default: now, i.e.
        the moment its processed marker arrived)."""
        if not self.enabled or not compact_spans:
            return
        end = time.perf_counter() if anchor_s is None else anchor_s
        spans = [Span(name, end - duration_s, duration_s, "remote", 0,
                      pid, None, trace, stage, track, 0, 0)
                 for name, stage, duration_s, trace, track in compact_spans]
        self._append(spans)
        if self.on_stage is not None:
            for sp in spans:
                if sp.stage is not None:
                    self.on_stage(sp.stage, sp.duration_s)

    def ingest(self, spans: Sequence[Span]) -> None:
        """Bulk-append already-built :class:`Span` objects (the mesh
        loader's per-host registry rollup; same-process clocks, so
        timestamps carry over unchanged)."""
        self._append(spans)

    def _append(self, spans) -> None:
        """The ring-append path of completed spans (one lock hold for the
        whole sequence): capacity eviction and the dropped count live here
        and in :meth:`_close`, which appends one live span."""
        with self._lock:
            for sp in spans:
                if len(self._spans) == self._spans.maxlen:
                    self._dropped += 1
                self._spans.append(sp)

    # ------------------------------------------------------------ readout
    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    def drain(self) -> list:
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
            return out

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def aggregate(self) -> dict:
        """Per-name aggregate of currently retained spans:
        ``{name: {"count", "total_s", "max_s"}}``."""
        return self.aggregate_spans(self.spans())

    @staticmethod
    def aggregate_spans(spans) -> dict:
        """:meth:`aggregate` over an explicit span list — lets a caller
        aggregate exactly what :meth:`drain` returned, with no window for
        concurrent records to slip between the two."""
        out: dict = {}
        for sp in spans:
            agg = out.setdefault(sp.name, {"count": 0, "total_s": 0.0,
                                           "max_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += sp.duration_s
            agg["max_s"] = max(agg["max_s"], sp.duration_s)
        for agg in out.values():
            agg["total_s"] = round(agg["total_s"], 6)
            agg["max_s"] = round(agg["max_s"], 6)
        return out
