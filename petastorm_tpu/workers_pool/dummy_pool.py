"""Inline single-threaded pool: work executes lazily inside ``get_results``.

No threads, no processes — the debugging/profiling flavor. Ventilated items
are queued; each ``get_results`` call processes items until the worker
publishes at least one result, then drains publications in order.

Parity: reference petastorm/workers_pool/dummy_pool.py — ``DummyPool`` (:20),
``get_results`` (:50).
"""
from __future__ import annotations

import time
from collections import deque

from petastorm_tpu.metrics import traced_span
from petastorm_tpu.resilience.quarantine import (RowGroupSkipped,
                                                 RowGroupSkippedMessage)
from petastorm_tpu.workers_pool import (EmptyResultError,
                                        ITEM_CONTEXT_KWARG,
                                        VentilatedItemProcessedMessage)


class DummyPool:
    def __init__(self, workers_count: int = 1, results_queue_size: int = 0,
                 profiling_enabled: bool = False, **_ignored):
        self.workers_count = 1
        self._pending = deque()      # ventilated (args, kwargs) not yet processed
        self._results = deque()      # published results not yet consumed
        self._worker = None
        self._ventilator = None
        self._stopped = False
        self._abort_exc = None
        self._ventilated = 0
        self._processed = 0
        #: Liveness stamp (item boundaries) for the pipeline watchdog. One
        #: inline "worker": a single slot.
        self.heartbeats = [0.0]
        #: Optional StageDeadline (assigned by the Reader before start());
        #: item-level soft overruns are counted around the inline decode.
        self.stage_deadline = None
        self._straggler = None
        # Pipeline telemetry registry (assigned by the owning Reader before
        # start()); decode runs inline so it is timed right here. The decode
        # histogram is resolved once and cached — per-item registry lookups
        # would pay a lock acquire on every row group.
        self.telemetry = None
        self._decode_hist = None
        # Consumer-side RowGroupQuarantine aggregator (assigned by the Reader
        # before start(); same contract as the threaded pools).
        self.quarantine = None
        #: Uniform knob surface with ThreadPool. None: work runs inline in
        #: the consumer's own thread — there is no concurrency to gate.
        self.concurrency_gate = None
        #: Cumulative seconds of decode run INLINE inside ``get_results``.
        #: The reader's pool-wait timer wraps ``get_results`` and subtracts
        #: the growth of this value, so ``reader.pool_wait_s`` and
        #: ``worker.decode_s`` stay disjoint stages for this pool too
        #: (threaded pools decode off-thread, so only this pool needs it).
        self.inline_decode_s = 0.0

    def start(self, worker_class, worker_args=None, ventilator=None):
        if self._worker is not None:
            raise RuntimeError("DummyPool already started")
        self._worker = worker_class(0, self._publish, worker_args)
        if self.stage_deadline is not None:
            from petastorm_tpu.resilience.deadline import StragglerMonitor
            self._straggler = StragglerMonitor(self.stage_deadline,
                                               telemetry=self.telemetry,
                                               scope="item",
                                               site="pool.item")
        if ventilator is not None:
            self._ventilator = ventilator
            self._ventilator.start()

    def _publish(self, data):
        self._results.append(data)

    def ventilate(self, *args, **kwargs):
        self._ventilated += 1
        self._pending.append((args, kwargs))

    def get_results(self):
        while True:
            # Watchdog abort outranks the stop poison pill: the consumer
            # sees the hang diagnosis, not a silent end-of-data.
            if self._abort_exc is not None:
                raise self._abort_exc
            # stop() is a poison pill: consumers see end-of-data promptly.
            if self._stopped:
                raise EmptyResultError()
            while self._results:
                result = self._results.popleft()
                if isinstance(result, RowGroupSkippedMessage):
                    if self.quarantine is not None:
                        self.quarantine.add(result.record)
                    continue
                if isinstance(result, VentilatedItemProcessedMessage):
                    self._processed += 1
                    if self._ventilator:
                        self._ventilator.processed_item(result.item_context)
                    continue
                return result
            if self._pending:
                args, kwargs = self._pending.popleft()
                # Lineage id from the reader's ventilate wrapper (trace
                # mode); popped before the worker impl sees the kwargs.
                trace = kwargs.pop("trace_context", None)
                self.heartbeats[0] = time.monotonic()
                t0 = time.perf_counter()
                if self.telemetry is not None:
                    if self._decode_hist is None:
                        self._decode_hist = self.telemetry.histogram(
                            "worker.decode_s")
                        # Per-worker identity family (the dummy pool's one
                        # inline "worker"), so the timeline's
                        # `pool.utilization` covers every backend.
                        wid = 0
                        self._c_w_items = self.telemetry.counter(
                            f"pool.w{wid}.items")
                        self._c_w_busy = self.telemetry.counter(
                            f"pool.w{wid}.busy_s")
                    # Publishing is a deque append here: nothing blocks, so
                    # the whole item is decode. One clock pair (the span's)
                    # feeds the histogram and the counters.
                    with traced_span("petastorm_tpu.worker_decode",
                                     self.telemetry, trace=trace,
                                     stage="decode",
                                     track="worker:0") as decode:
                        self._process_item(args, kwargs)
                    dt = decode.duration_s
                    self._decode_hist.observe(dt)
                    self.inline_decode_s += dt
                    self._c_w_busy.add(dt)
                    self._c_w_items.add(1)
                else:
                    self._process_item(args, kwargs)
                self._results.append(VentilatedItemProcessedMessage(
                    kwargs.get(ITEM_CONTEXT_KWARG)))
                self.heartbeats[0] = time.monotonic()
                if self._straggler is not None:
                    self._straggler.observe(time.perf_counter() - t0,
                                            worker_id=0)
                continue
            if self._ventilator is None or self._ventilator.completed():
                raise EmptyResultError()
            # The ventilator thread may still be feeding us; yield briefly.
            time.sleep(0.001)

    def _process_item(self, args, kwargs):
        try:
            self._worker.process(*args, **kwargs)
        except RowGroupSkipped as skip:
            # Degraded-mode give-up: record replaces the item's data; the
            # processed marker the caller appends keeps accounting exact.
            self._results.append(RowGroupSkippedMessage(skip.record))

    def stop(self):
        if self._ventilator:
            self._ventilator.stop()
        self._stopped = True

    def abort(self, exc: BaseException):
        """Watchdog escalation endpoint (limited reach here: work runs
        inline in the consumer's own thread, so an in-flight wedged decode
        only sees the abort once it returns to the poll loop)."""
        self._abort_exc = exc
        self.stop()

    def join(self):
        if self._worker is not None:
            self._worker.shutdown()

    def results_qsize(self) -> int:
        return len(self._results)

    @property
    def diagnostics(self):
        """Unified pool schema (same keys across thread/process/dummy
        pools). ``output_queue_size`` counts pending publications, which may
        include processed-item markers not yet consumed."""
        return {"output_queue_size": len(self._results),
                "items_ventilated": self._ventilated,
                "items_processed": self._processed,
                "items_inprocess": self._ventilated - self._processed,
                "workers_count": self.workers_count,
                "results_queue_capacity": 0}
