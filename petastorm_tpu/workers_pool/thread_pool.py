"""Thread pool with deterministic round-robin result readout.

Work items are assigned round-robin to per-worker input queues, and results
are read round-robin from per-worker output queues. With a seeded ventilator
this makes the whole pipeline **order-deterministic** — the property the TPU
reader leans on for reproducible training input and for keeping multi-host
shards in lockstep. When the consumer explicitly opts out of determinism
(unseeded row shuffling), readout switches to non-blocking first-come order
for better latency.

Workers publish a :class:`VentilatedItemProcessedMessage` marker after each
item; since markers trail the item's data in the same queue, the pool's
accounting (items assigned == markers seen and queues drained) is exact with
no data race on end-of-epoch detection.

Parity: reference petastorm/workers_pool/thread_pool.py — ``WorkerThread``
(:36), ``ThreadPool`` (:77), round-robin assign (:155), ``get_results``
(:172), ``_stop_aware_put`` (:242), ``diagnostics`` (:261).
"""
from __future__ import annotations

import cProfile
import logging
import pstats
import queue
import sys
import time
import threading
from traceback import format_exc
from typing import Optional

from petastorm_tpu.metrics import traced_span
from petastorm_tpu.resilience.quarantine import (RowGroupSkipped,
                                                 RowGroupSkippedMessage)
from petastorm_tpu.workers_pool import (EmptyResultError,
                                        ITEM_CONTEXT_KWARG,
                                        TimeoutWaitingForResultError,
                                        VentilatedItemProcessedMessage,
                                        WorkerFailure)

logger = logging.getLogger(__name__)

_IO_TIMEOUT_S = 0.001
_END_OF_VENTILATION_POLL_S = 0.1


class WorkerTerminationRequested(Exception):
    """Raised inside a worker thread to unwind when the pool is stopping."""


class ConcurrencyGate:
    """Admission gate over live worker concurrency.

    All ``workers_count`` threads stay alive, but only ``limit`` of them may
    be *processing an item* at once — the rest park before taking their next
    item. This is the runtime decode-concurrency knob the autotune subsystem
    actuates (``set_limit`` is the knob setter; ``tools/check_knobs.py``
    lints that only :mod:`petastorm_tpu.autotune` calls it): concurrency
    changes take effect at the next item boundary with no thread churn, no
    lost items, and no effect on the round-robin result determinism (parked
    workers simply publish later; readout order is unchanged).

    Deadlock safety under the strict-order consumer: a slot-holding worker
    blocked publishing into its FULL result queue *yields* its slot
    (:meth:`yield_if_held` from the pool's bounded put) so a parked worker —
    possibly the exact one the round-robin consumer is waiting on — can run;
    the yielder re-acquires before resuming decode. Without this, limit <
    workers_count could wedge: consumer waits on a parked worker while every
    slot holder waits on the consumer.
    """

    def __init__(self, limit: int):
        self._limit = max(1, int(limit))
        self._active = 0
        self._holders: set = set()   # thread idents holding a slot
        self._cv = threading.Condition()

    @property
    def limit(self) -> int:
        with self._cv:
            return self._limit

    @property
    def active(self) -> int:
        with self._cv:
            return self._active

    def set_limit(self, limit: int) -> None:
        with self._cv:
            self._limit = max(1, int(limit))
            self._cv.notify_all()

    def acquire(self, stop_event) -> bool:
        """Block until a processing slot frees (or the pool stops: False)."""
        with self._cv:
            while self._active >= self._limit:
                if stop_event.is_set():
                    return False
                self._cv.wait(_END_OF_VENTILATION_POLL_S)
            self._active += 1
            self._holders.add(threading.get_ident())
            return True

    def release(self) -> None:
        """Free the calling thread's slot; no-op when it holds none (so the
        worker loop's unconditional release composes with a mid-publish
        yield)."""
        self.yield_if_held()

    def yield_if_held(self) -> bool:
        """Backpressure escape hatch: release the calling thread's slot if
        it holds one; returns whether it did (caller re-acquires later)."""
        with self._cv:
            ident = threading.get_ident()
            if ident not in self._holders:
                return False
            self._holders.discard(ident)
            self._active = max(0, self._active - 1)
            self._cv.notify_all()
            return True

    def nudge(self) -> None:
        """Watchdog hook: wake every parked waiter in case the stall is a
        lost wakeup (harmless when it isn't — waiters re-check and park)."""
        with self._cv:
            self._cv.notify_all()


class _WorkerThread(threading.Thread):
    def __init__(self, worker_class, worker_id, worker_args, input_queue,
                 result_queue, stop_event, put_fn, prof=None, telemetry=None,
                 gate=None, heartbeats=None, straggler=None):
        super().__init__(name=f"pt-worker-{worker_id}", daemon=True)
        # The worker impl publishes through this thread, so the thread
        # sees where an item's decode ends and its publish begins.
        self._worker_impl = worker_impl = worker_class(
            worker_id, self._publish, worker_args)
        self._input_queue = input_queue
        self._result_queue = result_queue
        self._stop_event = stop_event
        self._put = put_fn
        self._gate = gate
        # Liveness signal for the pipeline watchdog: stamped when this
        # worker takes an item and when it completes one, so "no heartbeat
        # motion anywhere" distinguishes a wedged decode from an idle pool.
        self._heartbeats = heartbeats
        # Pool-level (whole-item) soft-deadline accounting — covers decode
        # PLUS result-queue backpressure, complementing the worker impl's
        # per-attempt enforcement.
        self._straggler = straggler
        self.prof = prof  # per-worker cProfile; pre-3.12 only (see ThreadPool)
        # Shared pipeline registry (set by the reader through the pool):
        # in-worker decode time is only observable from inside the worker.
        self._decode_hist = (telemetry.histogram("worker.decode_s")
                             if telemetry is not None else None)
        self._telemetry = telemetry
        # Per-worker identity counters, same family the process pool's
        # consumer-side marker accounting feeds — the timeline derives
        # `pool.w{id}.busy_frac` per worker and the fleet-level
        # `pool.utilization` series from them on BOTH pool backends.
        wid = worker_impl.worker_id
        self._track = f"worker:{wid}"
        self._c_items = (telemetry.counter(f"pool.w{wid}.items")
                         if telemetry is not None else None)
        self._c_busy = (telemetry.counter(f"pool.w{wid}.busy_s")
                        if telemetry is not None else None)
        # The open item: its lineage id and its decode span, which the
        # first publish closes.
        self._trace = None
        self._decode = None

    def _beat(self):
        if self._heartbeats is not None:
            self._heartbeats[self._worker_impl.worker_id] = time.monotonic()

    def run(self):
        # ANY exit path that isn't an explicit stop must surface to the
        # consumer as a WorkerFailure: a worker that dies silently (e.g. an
        # error before/around the processing loop) leaves its assigned items
        # forever unprocessed and the pipeline spinning in get_results().
        try:
            if self.prof:
                self.prof.enable()  # inside the guard: a failed enable()
                # (single profiler slot) must surface, not hang the pipeline
            self._loop()
        except WorkerTerminationRequested:
            pass
        except Exception as e:  # noqa: BLE001 - propagate to consumer
            tb = format_exc()
            sys.stderr.write(f"Worker {self._worker_impl.worker_id} terminated: {tb}\n")
            try:
                self._put(WorkerFailure(e, tb))
            except WorkerTerminationRequested:
                pass
        finally:
            self._worker_impl.shutdown()
            if self.prof:
                self.prof.disable()

    def _loop(self):
        while not self._stop_event.is_set():
            try:
                args, kwargs = self._input_queue.get(block=True, timeout=_IO_TIMEOUT_S)
            except queue.Empty:
                continue
            # Lineage id the reader's ventilate wrapper injected (trace
            # mode); popped so the worker impl's signature never sees it.
            self._trace = kwargs.pop("trace_context", None)
            # Admission gate: park until a processing slot frees. The item
            # stays ours (round-robin assignment is fixed), so determinism
            # holds; a stop while parked drops the item like any other stop.
            if self._gate is not None and not self._gate.acquire(self._stop_event):
                return
            self._beat()
            t0 = time.perf_counter()
            try:
                if self._telemetry is not None:
                    # Item taken -> the item's first publish (_publish
                    # closes it there): decode WITHOUT the blocked put.
                    self._decode = traced_span(
                        "petastorm_tpu.worker_decode", self._telemetry,
                        trace=self._trace, stage="decode", track=self._track)
                    with self._decode:
                        self._process_item(args, kwargs)
                    # One clock pair: the span's, for the histogram and
                    # the per-worker busy counter too.
                    self._decode_hist.observe(self._decode.duration_s)
                    self._c_busy.add(self._decode.duration_s)
                    self._c_items.add(1)
                    self._decode = None
                else:
                    self._process_item(args, kwargs)
            finally:
                if self._gate is not None:
                    self._gate.release()
            self._publish(VentilatedItemProcessedMessage(
                kwargs.get(ITEM_CONTEXT_KWARG)))
            self._beat()
            if self._straggler is not None:
                self._straggler.observe(time.perf_counter() - t0,
                                        worker_id=self._worker_impl.worker_id)

    def _publish(self, data):
        """Hand ``data`` to the consumer. The item's decode ends here; time
        blocked on the full results queue is ``publish_wait``, a span of
        its own that feeds no decode counter."""
        if self._decode is not None:
            self._decode.close()
        try:
            self._result_queue.put_nowait(data)
            return
        except queue.Full:
            pass
        if self._telemetry is None:
            self._put(data)
            return
        with traced_span("petastorm_tpu.publish_wait", self._telemetry,
                         trace=self._trace, track=self._track):
            self._put(data)

    def _process_item(self, args, kwargs):
        try:
            self._worker_impl.process(*args, **kwargs)
        except RowGroupSkipped as skip:
            # Degraded-mode give-up: the skip record replaces the item's
            # data; the processed marker still follows, so pool accounting
            # treats the item as complete.
            self._publish(RowGroupSkippedMessage(skip.record))


class ThreadPool:
    """:param workers_count: number of worker threads
    :param results_queue_size: bound of each per-worker result queue
    :param profiling_enabled: cProfile the pool; stats print on ``join()``.
        On CPython 3.12+ cProfile registers a process-global
        ``sys.monitoring`` tool — one profiler enabled at ``start()``
        already observes every thread, and a second ``enable()`` raises
        "Another profiling tool is already active" — so 3.12+ uses ONE
        pool-level profile (covering workers plus whatever the consumer
        thread ran between start and join). Pre-3.12, ``enable()`` is
        per-thread (``PyEval_SetProfile``), so each worker gets its own
        profile and ``join()`` merges them — the reference's design
        (thread_pool.py:47-52).
    :param shuffle_rows/seed: when rows are shuffled without a seed, result
        readout is non-blocking (no determinism to preserve)
    """

    def __init__(self, workers_count: int, results_queue_size: int = 50,
                 profiling_enabled: bool = False, shuffle_rows: bool = False,
                 seed: Optional[int] = None):
        self.workers_count = workers_count
        self._results_queue_size = results_queue_size
        self._profiling_enabled = profiling_enabled
        self._prof = None
        self._strict_order = not (shuffle_rows and seed is None)
        self._stop_event = threading.Event()
        self._abort_exc = None
        self._workers = []
        self._input_queues = []
        self._result_queues = []
        self._assigned = [0] * workers_count
        self._processed = [0] * workers_count
        self._next_assign = 0
        self._next_read = 0
        self._ventilator = None
        # Pipeline telemetry registry; the owning Reader assigns it before
        # start() so worker threads can publish in-worker decode timings.
        self.telemetry = None
        # Consumer-side RowGroupQuarantine aggregator (assigned by the Reader
        # before start() when degraded mode is available); skip messages are
        # dropped with a warning when nothing is attached.
        self.quarantine = None
        #: Runtime decode-concurrency knob: always present (one lock
        #: round-trip per row group, noise next to a decode), actuated only
        #: when the owning Reader enables autotune.
        self.concurrency_gate = ConcurrencyGate(workers_count)
        #: Per-worker liveness stamps (monotonic seconds, updated at item
        #: boundaries) — the watchdog's progress/attribution signal.
        self.heartbeats = [0.0] * workers_count
        #: Optional :class:`~petastorm_tpu.resilience.StageDeadline`
        #: (assigned by the Reader before start()): item-level soft-overrun
        #: accounting happens in the worker loop.
        self.stage_deadline = None

    # ------------------------------------------------------------------ api
    def start(self, worker_class, worker_args=None, ventilator=None):
        if self._stop_event.is_set():
            raise RuntimeError("A ThreadPool cannot be restarted after stop()")
        if self._workers:
            raise RuntimeError("ThreadPool already started")
        straggler = None
        if self.stage_deadline is not None:
            from petastorm_tpu.resilience.deadline import StragglerMonitor
            straggler = StragglerMonitor(self.stage_deadline,
                                         telemetry=self.telemetry,
                                         scope="item", site="pool.item")
        for i in range(self.workers_count):
            in_q = queue.Queue()
            out_q = queue.Queue(maxsize=self._results_queue_size)
            self._input_queues.append(in_q)
            self._result_queues.append(out_q)
            per_worker_prof = (cProfile.Profile() if self._profiling_enabled
                               and sys.version_info < (3, 12) else None)
            self._workers.append(_WorkerThread(worker_class, i, worker_args,
                                               in_q, out_q, self._stop_event,
                                               self._make_put(i), per_worker_prof,
                                               telemetry=self.telemetry,
                                               gate=self.concurrency_gate,
                                               heartbeats=self.heartbeats,
                                               straggler=straggler))
        if self._profiling_enabled and sys.version_info >= (3, 12):
            self._prof = cProfile.Profile()
            try:
                self._prof.enable()
            except ValueError:  # another sys.monitoring tool already active
                logger.warning("profiling_enabled ignored: another profiler "
                               "is already active in this process")
                self._prof = None
        for w in self._workers:
            w.start()
        if ventilator is not None:
            self._ventilator = ventilator
            self._ventilator.start()

    def _make_put(self, worker_id):
        gate = self.concurrency_gate

        def _put(data):
            # Bounded put that aborts when the pool is stopping, so workers
            # never deadlock against a full queue (reference :242). While
            # blocked on a FULL queue, a slot-holding worker yields its
            # admission slot (see ConcurrencyGate): with a shrunk
            # concurrency limit the strict-order consumer may be waiting on
            # a PARKED worker, and a slot holder waiting on the consumer
            # would complete the cycle.
            yielded = False
            try:
                while True:
                    try:
                        self._result_queues[worker_id].put(data, block=True, timeout=_IO_TIMEOUT_S)
                        return
                    except queue.Full:
                        if self._stop_event.is_set():
                            raise WorkerTerminationRequested()
                        if not yielded:
                            yielded = gate.yield_if_held()
            finally:
                if yielded and not gate.acquire(self._stop_event):
                    raise WorkerTerminationRequested()
        return _put

    def ventilate(self, *args, **kwargs):
        wid = self._next_assign
        self._next_assign = (self._next_assign + 1) % self.workers_count
        self._assigned[wid] += 1
        self._input_queues[wid].put((args, kwargs))

    def _worker_drained(self, wid) -> bool:
        return (self._processed[wid] == self._assigned[wid]
                and self._result_queues[wid].empty())

    def get_results(self, timeout: float = None):
        """Next published result, in deterministic round-robin order.

        Raises :class:`EmptyResultError` when all ventilated work is done and
        drained; re-raises worker exceptions. ``stop()`` acts as a poison
        pill: a consumer blocked here (e.g. a loader staging thread) sees
        :class:`EmptyResultError` promptly instead of polling forever while
        teardown proceeds under it. With ``timeout``, raises
        :class:`TimeoutWaitingForResultError` once that many seconds pass
        without a result (the migration drain's bounded re-check).
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        empty_sweeps = 0
        while True:
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutWaitingForResultError()
            if self._abort_exc is not None:
                raise self._abort_exc
            if self._stop_event.is_set():
                raise EmptyResultError()
            if all(self._worker_drained(i) for i in range(self.workers_count)):
                if self._ventilator is None or self._ventilator.completed():
                    raise EmptyResultError()

            wid = self._next_read
            if self._worker_drained(wid):
                self._next_read = (self._next_read + 1) % self.workers_count
                empty_sweeps += 1
                if empty_sweeps >= self.workers_count:
                    time.sleep(_IO_TIMEOUT_S)  # backoff-ok: queue-poll yield, not a retry
                    empty_sweeps = 0
                continue
            try:
                result = self._result_queues[wid].get(
                    block=self._strict_order, timeout=_END_OF_VENTILATION_POLL_S)
            except queue.Empty:
                if not self._strict_order:
                    self._next_read = (self._next_read + 1) % self.workers_count
                    empty_sweeps += 1
                    if empty_sweeps >= self.workers_count:
                        time.sleep(_IO_TIMEOUT_S)  # backoff-ok: queue-poll yield, not a retry
                        empty_sweeps = 0
                continue
            empty_sweeps = 0
            if isinstance(result, RowGroupSkippedMessage):
                if self.quarantine is not None:
                    self.quarantine.add(result.record)
                else:
                    logger.warning("Row group quarantined with no aggregator "
                                   "attached: %s", result.record.piece)
                continue  # the item's processed marker follows on this queue
            if isinstance(result, VentilatedItemProcessedMessage):
                self._processed[wid] += 1
                if self._ventilator:
                    self._ventilator.processed_item(result.item_context)
                self._next_read = (self._next_read + 1) % self.workers_count
                continue
            if isinstance(result, WorkerFailure):
                self.stop()
                self.join()
                raise result.exception
            return result

    def stop(self):
        if self._ventilator:
            self._ventilator.stop()
        self._stop_event.set()

    def abort(self, exc: BaseException):
        """Watchdog escalation endpoint: fail the pipeline with ``exc`` —
        a consumer blocked in :meth:`get_results` raises it promptly
        instead of EmptyResultError, and teardown proceeds as a stop."""
        self._abort_exc = exc
        self.stop()

    def nudge(self):
        """Watchdog hook: wake any lost-wakeup parkers (admission gate)."""
        self.concurrency_gate.nudge()

    def join(self):
        for w in self._workers:
            if w.is_alive():
                if self._abort_exc is not None:
                    # The pipeline was declared hung: a wedged worker thread
                    # may never exit — bound the wait so "never blocks
                    # indefinitely" extends to teardown (daemon threads die
                    # with the process).
                    w.join(timeout=5.0)
                    if w.is_alive():
                        logger.warning(
                            "Worker thread %s still wedged after abort; "
                            "abandoning it (daemon)", w.name)
                else:
                    w.join()
        if self._prof is not None:  # 3.12+: one pool-level profile
            self._prof.disable()
            pstats.Stats(self._prof).sort_stats("cumulative").print_stats()
            self._prof = None
        elif self._profiling_enabled:  # pre-3.12: merge per-worker profiles
            profs = [w.prof for w in self._workers if w.prof is not None]
            if profs:
                stats = pstats.Stats(profs[0])
                for p in profs[1:]:
                    stats.add(p)
                stats.sort_stats("cumulative").print_stats()

    def results_qsize(self) -> int:
        return sum(q.qsize() for q in self._result_queues)

    @property
    def diagnostics(self):
        """Unified pool schema (same keys across thread/process/dummy pools,
        zero-valued where a pool cannot observe them — see
        docs/observability.md)."""
        ventilated = sum(self._assigned)
        processed = sum(self._processed)
        return {"output_queue_size": self.results_qsize(),
                "items_ventilated": ventilated,
                "items_processed": processed,
                "items_inprocess": ventilated - processed,
                "workers_count": self.workers_count,
                "results_queue_capacity": self._results_queue_size}
