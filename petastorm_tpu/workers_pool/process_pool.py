"""Process pool: spawned worker processes over ZeroMQ ``ipc://`` sockets.

Topology (three sockets, mirroring the reference's diagram
petastorm/workers_pool/process_pool.py:53-74, but over ipc:// instead of
tcp://127.0.0.1 — unix domain sockets skip the loopback TCP stack):

```
   main process                               worker process (xN, spawned)
   ───────────                                ──────────────
   PUSH ──── work items (pickle) ───────────▶ PULL
   PUB  ──── control: FINISH/STOP ──────────▶ SUB
   PULL ◀─── results (serializer) / ctrl ──── PUSH
```

Result frames are multipart ``[kind, payload]``: ``b"data"`` payloads go
through the pluggable serializer (pickle or Arrow IPC — the Arrow path hands
the consumer a zero-copy view of the receive buffer), ``b"ctrl"`` payloads
(ready-handshake, item-processed markers, worker exceptions) are always
pickle.

Zero-copy data plane (docs/zero_copy.md): on the shm transport a data frame
is deserialized straight from the mapped ring memory, the consumer-side
``result_transform`` converts it to numpy views over the Arrow buffers
(no copy), and the ring record is pinned by a :class:`_SegmentClaim` that
releases — recycling the segment — only when the consumer (or a shuffle
buffer holding the batch) drops its last view. Decoded columns are written
once, by the worker, and viewed everywhere after.

Safety: workers watch the parent PID and exit if it dies (no orphans,
reference :320); worker start blocks on a ready-handshake from every worker
so no ventilated item is ever lost to a ZMQ slow joiner (reference :292).

Workers are **spawned, never forked**, and pinned to ``JAX_PLATFORMS=cpu``
so a worker can never initialize (or corrupt) the parent's TPU runtime —
the TPU-specific constraint that rules out fork-based pools entirely.
"""
from __future__ import annotations

import logging
import os
import pickle
import sys
import tempfile
import threading
import time
import uuid
from traceback import format_exc

from petastorm_tpu.metrics import traced_span
from petastorm_tpu.reader_impl.epoch_plan import OrderedUnit
from petastorm_tpu.reader_impl.pickle_serializer import PickleSerializer
from petastorm_tpu.resilience.quarantine import (RowGroupSkipped,
                                                 RowGroupSkippedMessage)
from petastorm_tpu.resilience.recovery import (CrashBudgetExceededError,
                                               ItemStartedMessage)
from petastorm_tpu.workers_pool import (EmptyResultError,
                                        ITEM_CONTEXT_KWARG,
                                        TimeoutWaitingForResultError,
                                        VentilatedItemProcessedMessage,
                                        WorkerFailure)
from petastorm_tpu.workers_pool.exec_in_new_process import exec_in_new_process

logger = logging.getLogger(__name__)

_KIND_DATA = b"data"
_KIND_CTRL = b"ctrl"
_CONTROL_FINISH = b"FINISH"
_WORKER_START_TIMEOUT_S = 60
_JOIN_TIMEOUT_S = 30
_POLL_MS = 100


class _WorkerReady:
    def __init__(self, worker_id):
        self.worker_id = worker_id


class _SegmentClaim:
    """Pins one shm ring record while zero-copy numpy views of it are live.

    The poll registers a ``weakref.finalize`` on every result array that
    aliases the mapped ring region; the record's release is deferred until
    the last such array is garbage-collected — so the consumer (or a
    shuffle buffer, or a dlpack-staged device batch holding the host array)
    can keep a batch as long as it likes and the ring simply backpressures
    that worker instead of recycling memory under the view. Thread-safe:
    finalizers fire on whatever thread drops the last reference; the ring
    tail is only ever advanced from the consumer's poll thread
    (:meth:`RingReader.reap`)."""

    __slots__ = ("view", "_outstanding", "_lock", "__weakref__")

    def __init__(self, view):
        self.view = view
        self._outstanding = 0
        self._lock = threading.Lock()

    def track(self, arr) -> None:
        import weakref
        with self._lock:
            self._outstanding += 1
        weakref.finalize(arr, self._drop)

    def _drop(self) -> None:
        with self._lock:
            self._outstanding -= 1
        if self._outstanding <= 0:
            try:
                self.view.release()
            except BufferError:  # pragma: no cover - racing release
                pass

    @property
    def released(self) -> bool:
        with self._lock:
            return self._outstanding <= 0


def _resolve_auto_transport() -> str:
    """The rule for ``transport="auto"``: **shm when the ring builds, zmq
    otherwise.** ``PETASTORM_TPU_TRANSPORT`` (``shm``/``zmq``) overrides
    outright.

    Basis: pool payloads are serialized row-group batches — hundreds of KB
    to MB, where one shared-memory write replaces the kernel copies of
    pipe-class IPC (sandbox counts only; no cell of the benchmark runs a
    process pool yet, PERF.md "Open questions"). Thread-vs-process is the
    caller's ``reader_pool_type`` choice, not this rule's."""
    forced = os.environ.get("PETASTORM_TPU_TRANSPORT", "").strip().lower()
    if forced:
        if forced not in ("shm", "zmq"):
            raise ValueError(
                f"PETASTORM_TPU_TRANSPORT={forced!r}: expected 'shm' or "
                f"'zmq' (a silently ignored override is worse than none)")
        return forced
    from petastorm_tpu.native import ring_available
    return "shm" if ring_available() else "zmq"


class ProcessPool:
    """:param workers_count: number of spawned worker processes
    :param serializer: result payload serializer (default pickle; pass
        :class:`ArrowTableSerializer` for columnar zero-copy transport)
    :param zmq_copy_buffers: when False, Arrow payloads are exposed to the
        serializer as zero-copy buffers (reference :127-130)
    """

    def __init__(self, workers_count: int, serializer=None,
                 zmq_copy_buffers: bool = True, results_queue_size: int = 50,
                 transport: str = "auto", ring_capacity: int = 128 << 20):
        self.workers_count = workers_count
        self._serializer = serializer or PickleSerializer()
        self._zmq_copy = zmq_copy_buffers
        self._results_hwm = results_queue_size
        if transport == "auto":
            transport = _resolve_auto_transport()
        if transport not in ("shm", "zmq"):
            raise ValueError(f"transport must be 'auto', 'shm' or 'zmq', got {transport!r}")
        self._transport = transport
        self._ring_capacity = ring_capacity
        self._rings = []           # consumer-side ring per worker (shm mode)
        self._readers = []         # RingReader per ring (multi-record reads)
        self._ring_impl = None     # pinned at start(): 'native' or 'py'
        self._ring_poll_idx = 0
        # worker_id -> [reassembly bytearray, write offset]: chunked
        # payloads fill ONE preallocated buffer (sized by the S start
        # frame) instead of concatenating per-chunk.
        self._partial = {}
        self._ring_mem = {}        # worker_id -> numpy view over ring data
        # Optional callable applied to deserialized data results INSIDE the
        # poll. On the shm transport it runs while the zero-copy view is
        # still valid, so the copying conversion (e.g. Arrow -> numpy)
        # reads straight from mapped memory with no intermediate copy.
        self.result_transform = None
        self._context = None
        self._work_socket = None
        self._control_socket = None
        self._results_socket = None
        self._processes = []
        self._ventilator = None
        self._ventilated = 0
        self._processed = 0
        self._stopped = False
        self._abort_exc = None
        # Pipeline telemetry registry (assigned by the owning Reader before
        # start()). Spawned workers cannot share it, so in-worker decode
        # time is not observable here — the consumer-side pool wait recorded
        # by the reader is this pool's queueing signal.
        self.telemetry = None
        # Consumer-side resilience hooks, assigned by the owning Reader
        # before start() (like telemetry): a RowGroupQuarantine aggregator
        # for degraded-mode skip records, and a WorkerCrashRecovery ledger
        # that turns dead-worker detection into re-ventilation of the lost
        # row groups instead of a fatal RuntimeError.
        self.quarantine = None
        self.recovery = None
        #: Uniform knob surface with ThreadPool. None: spawned workers pull
        #: work through pre-buffering PUSH/PULL sockets, so parking one
        #: would strand the items already routed to its receive buffer (an
        #: epoch stall, not a concurrency reduction). The process pool's
        #: producer-side knob is the ventilator's in-flight cap instead
        #: (docs/autotune.md).
        self.concurrency_gate = None
        # Lazily-resolved transport.deserialize_s counter (telemetry is
        # assigned by the Reader after construction).
        self._c_deser = None
        self._h_decode = None
        # Per-worker federation counters, cached per worker id (the
        # registry lock is not for per-item paths).
        self._c_w_items = {}
        self._c_w_busy = {}
        ipc_dir = tempfile.mkdtemp(prefix="pt_pool_")
        token = uuid.uuid4().hex[:8]
        self._endpoints = {
            "work": f"ipc://{ipc_dir}/work-{token}",
            "control": f"ipc://{ipc_dir}/ctrl-{token}",
            "results": f"ipc://{ipc_dir}/res-{token}",
        }
        self._ipc_dir = ipc_dir

    # ------------------------------------------------------------------ api
    def start(self, worker_class, worker_args=None, ventilator=None):
        import zmq
        if self._context is not None:
            raise RuntimeError("ProcessPool already started")
        self._context = zmq.Context()
        self._work_socket = self._context.socket(zmq.PUSH)
        self._work_socket.bind(self._endpoints["work"])
        self._control_socket = self._context.socket(zmq.PUB)
        self._control_socket.bind(self._endpoints["control"])
        self._results_socket = self._context.socket(zmq.PULL)
        self._results_socket.set_hwm(self._results_hwm)
        self._results_socket.bind(self._endpoints["results"])

        ring_names = None
        if self._transport == "shm":
            from petastorm_tpu.native import make_ring, resolve_ring_impl
            # Pin ONE impl for consumer and workers alike: a native consumer
            # attached to a python-fallback producer (or vice versa) would
            # disagree on torn-frame semantics.
            self._ring_impl = resolve_ring_impl()
            token = uuid.uuid4().hex[:10]
            ring_names = [f"/ptring_{token}_{i}" for i in range(self.workers_count)]
            from petastorm_tpu.reader_impl.shm_ring import RingReader
            self._rings = [make_ring(name, capacity=self._ring_capacity,
                                     create=True, impl=self._ring_impl)
                           for name in ring_names]
            self._readers = [RingReader(ring) for ring in self._rings]

        for worker_id in range(self.workers_count):
            p = exec_in_new_process(
                _worker_bootstrap, worker_id, worker_class, worker_args,
                type(self._serializer), self._endpoints, os.getpid(),
                ring_names[worker_id] if ring_names else None,
                # Claim frames cost a control send per item; only pay when a
                # crash-recovery ledger is attached to consume them.
                self.recovery is not None,
                self._ring_impl)
            self._processes.append(p)

        # Ready-handshake: every worker's PUSH is connected before any
        # ventilation, so no work item can hit a half-built topology.
        ready = set()
        deadline = time.monotonic() + _WORKER_START_TIMEOUT_S
        # A worker that crashes during startup consumes crash budget like a
        # mid-epoch death; the handshake then only waits for the survivors.
        while len(ready) < self.workers_count - (
                len(self.recovery.dead_workers) if self.recovery is not None
                else 0):
            if time.monotonic() > deadline:
                self.stop(); self.join()
                raise RuntimeError(
                    f"Only {len(ready)}/{self.workers_count} workers started within "
                    f"{_WORKER_START_TIMEOUT_S}s")
            msg = self._poll_result(timeout_ms=_POLL_MS)
            if msg is None:
                self._check_processes_alive()
                continue
            if isinstance(msg, _WorkerReady):
                ready.add(msg.worker_id)
            elif isinstance(msg, WorkerFailure):
                self.stop(); self.join()
                raise msg.exception

        if ventilator is not None:
            self._ventilator = ventilator
            self._ventilator.start()

    def ventilate(self, *args, **kwargs):
        if self.recovery is not None:
            self.recovery.on_ventilated(kwargs.get(ITEM_CONTEXT_KWARG),
                                        (args, kwargs))
        self._ventilated += 1
        self._work_socket.send_pyobj((args, kwargs))

    def get_results(self, timeout: float = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            # Watchdog abort outranks the stop poison pill: the consumer
            # sees the hang diagnosis, not a silent end-of-data.
            if self._abort_exc is not None:
                raise self._abort_exc
            # stop() is a poison pill: blocked consumers unblock promptly.
            if self._stopped:
                raise EmptyResultError()
            all_done = (self._processed == self._ventilated)
            if all_done and (self._ventilator is None or self._ventilator.completed()):
                raise EmptyResultError()
            msg = self._poll_result(timeout_ms=_POLL_MS)
            if msg is None:
                self._check_processes_alive()
                if self.recovery is not None:
                    # Post-crash sweep: items that sat unclaimed in a dead
                    # worker's receive buffer surface once the pool quiesces.
                    for item in self.recovery.unaccounted_after_quiesce():
                        self._resend(item)
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutWaitingForResultError()
                continue
            if isinstance(msg, VentilatedItemProcessedMessage):
                self._processed += 1
                spans = getattr(msg, "spans", None)
                if spans and self.telemetry is not None:
                    # Spawned-worker trace spans, piggybacked on the ctrl
                    # frame: re-anchored to OUR clock at arrival (remote
                    # perf_counter bases are not comparable).
                    self.telemetry.recorder.record_remote(spans)
                wid = getattr(msg, "worker_id", None)
                if wid is not None and self.telemetry is not None:
                    # Per-worker federation counters (docs/observability.md
                    # "Federation"): spawned workers cannot reach the
                    # registry, so their identity + busy time ride the
                    # processed marker and land here — the timeline's
                    # pool.w{id} family derives per-worker rates from them.
                    self._worker_counters(wid).add(1)
                    busy = getattr(msg, "busy_s", None)
                    if busy:
                        self._worker_busy(wid).add(busy)
                        # The same number is the pipeline's decode time:
                        # one measurement, whichever pool decoded.
                        self._decode_hist().observe(busy)
                if self.recovery is not None:
                    self.recovery.on_processed(msg.item_context)
                if self._ventilator:
                    self._ventilator.processed_item(msg.item_context)
                continue
            if isinstance(msg, ItemStartedMessage):
                if self.recovery is not None:
                    self.recovery.on_started(msg.worker_id, msg.item_context)
                continue
            if isinstance(msg, RowGroupSkippedMessage):
                if self.quarantine is not None:
                    self.quarantine.add(msg.record)
                else:
                    logger.warning("Row group quarantined with no aggregator "
                                   "attached: %s", msg.record.piece)
                continue
            if isinstance(msg, WorkerFailure):
                logger.error("Worker failed:\n%s", msg.traceback_str)
                self.stop(); self.join()
                raise msg.exception
            if isinstance(msg, _WorkerReady):
                continue
            return msg

    def _worker_counters(self, worker_id: int):
        c = self._c_w_items.get(worker_id)
        if c is None:
            c = self._c_w_items[worker_id] = self.telemetry.counter(
                f"pool.w{worker_id}.items")
        return c

    def _decode_hist(self):
        h = self._h_decode
        if h is None:
            h = self._h_decode = self.telemetry.histogram("worker.decode_s")
        return h

    def _worker_busy(self, worker_id: int):
        c = self._c_w_busy.get(worker_id)
        if c is None:
            c = self._c_w_busy[worker_id] = self.telemetry.counter(
                f"pool.w{worker_id}.busy_s")
        return c

    def abort(self, exc: BaseException):
        """Watchdog escalation endpoint: fail the pipeline with ``exc`` —
        a consumer blocked in :meth:`get_results` raises it promptly."""
        self._abort_exc = exc
        self.stop()

    def kill_worker(self, worker_id: int) -> bool:
        """Watchdog escalation: SIGKILL one stuck worker process. The
        normal dead-PID sweep (:meth:`_check_processes_alive`) then treats
        it exactly like an organic crash — with a recovery ledger attached,
        its claimed row groups re-ventilate onto the survivors (the PR 2
        claim protocol); without one, the pool fails fast. Returns whether
        a live process was actually signalled."""
        if not 0 <= worker_id < len(self._processes) or self._stopped:
            return False
        p = self._processes[worker_id]
        if p.poll() is not None:
            return False  # already dead
        logger.warning("Killing stuck worker process %d (watchdog "
                       "escalation)", worker_id)
        p.kill()
        return True

    def stop(self):
        if self._ventilator:
            self._ventilator.stop()
        if self._control_socket is not None and not self._stopped:
            try:
                self._control_socket.send(_CONTROL_FINISH)
            except Exception:  # noqa: BLE001 - socket may already be dead
                pass
        # Unblock workers stuck in a blocking ring write against a full ring
        # (nobody will drain it anymore): the closed flag is shared memory, so
        # setting it from this side makes the worker's write raise RingClosed
        # immediately instead of stalling join() into its SIGKILL deadline.
        for ring in self._rings:
            try:
                ring.close_producer()
            except Exception:  # noqa: BLE001 - ring may already be closed
                pass
        self._stopped = True

    def join(self):
        # Re-send FINISH while waiting: a worker whose SUB connected after
        # the first send (slow joiner) would otherwise never hear it.
        deadline = time.monotonic() + _JOIN_TIMEOUT_S
        while any(p.poll() is None for p in self._processes) and time.monotonic() < deadline:
            if self._control_socket is not None:
                try:
                    self._control_socket.send(_CONTROL_FINISH)
                except Exception:  # noqa: BLE001
                    break
            time.sleep(0.05)  # backoff-ok: graceful-shutdown pacing, not a retry
        for p in self._processes:
            if p.poll() is None:
                p.kill()
                p.wait()
        for sock in (self._work_socket, self._control_socket, self._results_socket):
            if sock is not None:
                sock.close(linger=0)
        if self._context is not None:
            self._context.term()
            self._context = None
        # Drop the alias-probe arrays FIRST: they view ring memory and must
        # not outlive an unmapped ring.
        self._ring_mem.clear()
        for idx, ring in enumerate(self._rings):
            reader = self._readers[idx] if idx < len(self._readers) else None
            if reader is not None:
                reader.reap()
                pinned = reader.pinned
                reader.close()
                if pinned:
                    # The consumer still holds zero-copy views into this
                    # ring's mapping (a batch kept past reader teardown):
                    # unmapping would SIGSEGV those arrays, so unlink the
                    # name and leak the mapping for the life of the process.
                    logger.debug("Leaking shm ring %s mapping: consumer "
                                 "still holds zero-copy views", ring.name)
                    ring.close(leak_mapping=True)
                    continue
            ring.close()
        self._rings = []
        self._readers = []
        import shutil
        shutil.rmtree(self._ipc_dir, ignore_errors=True)

    def results_qsize(self) -> int:
        return 0  # not observable across the socket; parity with reference :303

    @property
    def diagnostics(self):
        """Unified pool schema (same keys across thread/process/dummy pools;
        ``output_queue_size`` is zero-valued here — queued results live in
        ZMQ/ring buffers that are not observable across the socket, parity
        with reference :303)."""
        return {"output_queue_size": self.results_qsize(),
                "items_ventilated": self._ventilated,
                "items_processed": self._processed,
                "items_inprocess": self._ventilated - self._processed,
                "workers_count": self.workers_count,
                "results_queue_capacity": self._results_hwm}

    # ------------------------------------------------------------ internals
    def _poll_result(self, timeout_ms: int):
        if self._transport == "shm" and self._rings:
            return self._poll_result_shm(timeout_ms)
        return self._poll_result_zmq(timeout_ms)

    def _deserialize_timed(self, buf, idx=None):
        """Deserialize one data payload (+ the consumer-side
        ``result_transform``), accounting the time as the pipeline's
        **transport** stage: the ``transport.deserialize_s`` counter
        always, plus a ``petastorm_tpu.transport`` span in trace mode
        (per-item lineage is unknown here — data frames precede their
        context-bearing processed marker — so transport spans carry track
        provenance only)."""
        tele = self.telemetry
        if tele is None:
            result = self._serializer.deserialize(buf)
            return self._apply_transform(result)
        c = self._c_deser
        if c is None:
            c = self._c_deser = tele.counter("transport.deserialize_s")
        track = "transport" if idx is None else f"transport:{idx}"
        with traced_span("petastorm_tpu.transport", tele, stage="transport",
                         track=track) as span:
            result = self._serializer.deserialize(buf)
            result = self._apply_transform(result)
        c.add(span.duration_s)
        return result

    def _apply_transform(self, result):
        """Consumer-side ``result_transform``, applied INSIDE an
        OrderedUnit envelope (deterministic mode, docs/determinism.md): the
        ordinal wrapper must reach the reorder gate intact while the
        payload still converts zero-copy."""
        if self.result_transform is None:
            return result
        if isinstance(result, OrderedUnit):
            if result.payload is not None:
                result.payload = self.result_transform(result.payload)
            return result
        return self.result_transform(result)

    def _poll_result_shm(self, timeout_ms: int):
        """Round-robin over worker rings. Frames: first byte C (pickled
        control), D (serialized data), or — for payloads bigger than half a
        ring — S (8-byte total length) followed by P chunks and a final D,
        reassembled into ONE preallocated buffer.

        Data frames are deserialized ZERO-COPY from the mapped ring memory
        and, when the ``result_transform`` yields numpy views over the
        mapped Arrow buffers, the record is pinned by a
        :class:`_SegmentClaim`: the :class:`RingReader` keeps reading
        records FORWARD of it (several batches may be outstanding at once —
        a shuffle buffer can hold many) while ring memory is recycled
        strictly in order, only after the consumer drops its last view of
        the oldest record. Backpressure lands on the producing worker when
        its pinned span approaches the ring capacity — never on memory
        safety."""
        deadline = time.monotonic() + timeout_ms / 1000.0
        while True:
            progressed = False
            for _ in range(len(self._readers)):
                idx = self._ring_poll_idx
                self._ring_poll_idx = (self._ring_poll_idx + 1) % len(self._readers)
                reader = self._readers[idx]
                reader.reap()
                rec = reader.try_read()
                if rec is None:
                    continue
                kind, view = rec
                progressed = True
                claimed = False
                # The record is consumed no matter what (a payload that
                # fails to deserialize/convert must not be re-read forever);
                # only a registered claim defers its release.
                try:
                    if kind == ord("C"):
                        # Ctrl frames deserialize straight from the mapped
                        # view (pickle copies out; no intermediate bytes).
                        return pickle.loads(view)
                    if kind == ord("S"):
                        # copy-ok: 8-byte length prefix of a chunked payload.
                        total = int.from_bytes(bytes(view[:8]), "little")
                        self._partial[idx] = [bytearray(total), 0]
                        continue
                    if kind == ord("P") or idx in self._partial:
                        entry = self._partial.get(idx)
                        if entry is None:  # P without S: unsized frame
                            entry = self._partial[idx] = [bytearray(), 0]
                        buf, off = entry
                        end = off + len(view)
                        if len(buf) >= end:
                            buf[off:end] = view  # fill preallocated buffer
                        else:
                            buf += view
                        entry[1] = end
                        if kind == ord("P"):
                            continue
                        del self._partial[idx]
                        # Reassembled payloads live in consumer-owned
                        # memory: results may alias `buf` freely (GC keeps
                        # it alive).
                        return self._deserialize_timed(memoryview(buf), idx)
                    # Single-record data frame.
                    if (self.result_transform is not None
                            or not getattr(self._serializer, "aliases_input",
                                           True)):
                        # Zero-copy: deserialize straight from mapped memory.
                        # Safe because either deserialization itself copies
                        # (e.g. pickle, which cannot alias the reused ring)
                        # or the transform's aliasing outputs get a claim.
                        result = self._deserialize_timed(view, idx)
                        claimed = self._maybe_claim(reader, idx, view, result)
                    else:
                        # One safe copy so the result cannot alias the
                        # reused ring (no copying transform downstream).
                        # copy-ok: aliasing-unsafe consumer needs the copy
                        result = self._deserialize_timed(bytes(view), idx)
                    return result
                finally:
                    if not claimed:
                        try:
                            view.release()
                        except BufferError:
                            # Something still references the mapped region (a
                            # bug or an in-flight exception); releasing the
                            # record regardless is required for progress —
                            # the error path owns the risk.
                            pass
                        reader.complete()
                        reader.reap()
            if not progressed:
                if time.monotonic() >= deadline:
                    return None
                time.sleep(0.0001)  # backoff-ok: ring poll yield, not a retry

    def _maybe_claim(self, reader, idx: int, view, result) -> bool:
        """Register a :class:`_SegmentClaim` when ``result`` carries numpy
        arrays that alias the mapped ring region (the zero-copy Arrow →
        numpy transform path); returns whether the record was claimed —
        the caller releases it immediately otherwise."""
        if isinstance(result, OrderedUnit):
            # Deterministic-mode envelope: the aliasing arrays live on the
            # payload; the claim pins the record for them exactly as for a
            # bare dict.
            result = result.payload
        if not isinstance(result, dict):
            return False
        import numpy as np
        mem = self._ring_mem.get(idx)
        if mem is None:
            mem = self._ring_mem[idx] = np.frombuffer(
                self._rings[idx].data_view(), dtype=np.uint8)
        aliasing = [v for v in result.values()
                    if isinstance(v, np.ndarray) and v.size
                    and np.may_share_memory(v, mem)]
        if not aliasing:
            return False
        claim = _SegmentClaim(view)
        for arr in aliasing:
            claim.track(arr)
        reader.claim(claim)
        if self.telemetry is not None:
            self.telemetry.counter("transport.zero_copy_batches").add(1)
            self.telemetry.counter("transport.zero_copy_bytes").add(
                sum(int(a.nbytes) for a in aliasing))
        return True

    def _poll_result_zmq(self, timeout_ms: int):
        import zmq
        if not self._results_socket.poll(timeout_ms, zmq.POLLIN):
            return None
        kind, payload = self._results_socket.recv_multipart(copy=self._zmq_copy)
        # copy-ok: the 4-byte kind tag, not the payload.
        kind = bytes(memoryview(kind)) if not isinstance(kind, bytes) else kind
        if kind == _KIND_CTRL:
            # pickle.loads accepts any buffer and copies out of it: the ctrl
            # frame deserializes straight from the zmq receive buffer.
            return pickle.loads(payload if isinstance(payload, bytes)
                                else memoryview(payload))
        if isinstance(payload, bytes):
            return self._deserialize_timed(payload)
        # Zero-copy: the zmq frame owns its buffer and anything aliasing
        # it (Arrow buffers -> numpy views) keeps it alive through
        # ordinary refcounting — unlike the shm ring, nothing recycles
        # this memory, so no claim protocol is needed here.
        return self._deserialize_timed(memoryview(payload))

    def _resend(self, item):
        """Re-ventilate a lost work item WITHOUT bumping ``_ventilated``:
        the original ventilation already counted it, and the dead worker
        will never send its processed marker — the re-sent copy's marker
        balances the books. ZMQ routes the send to a connected (live) PULL
        peer; the dead worker's socket is gone."""
        args, kwargs = item
        self._work_socket.send_pyobj((args, kwargs))

    def _check_processes_alive(self):
        for i, p in enumerate(self._processes):
            rc = p.poll()
            if rc is None or rc == 0 or self._stopped:
                continue
            if self.recovery is not None:
                if i in self.recovery.dead_workers:
                    continue  # already recovered
                if self._transport == "shm" and i < len(self._readers) \
                        and self._readers[i].has_pending():
                    # The dead worker's ring still holds published records
                    # — data the consumer must deliver and claim/marker
                    # frames the recovery books need. A worker that
                    # publishes and dies between the poll sweep and this
                    # aliveness check would otherwise have its item BOTH
                    # delivered from the ring and re-ventilated (duplicate
                    # row group). The producer is dead, so normal polls
                    # drain the ring to a fixed point; recovery proceeds on
                    # a later sweep with exact books.
                    continue
                try:
                    lost = self.recovery.on_worker_death(i, rc)
                except CrashBudgetExceededError:
                    self.stop(); self.join()
                    raise
                self._reclaim_ring(i)
                logger.warning(
                    "Worker process %d died with exit code %s; re-ventilating "
                    "%d claimed item(s) onto the %d surviving worker(s)",
                    i, rc, len(lost),
                    self.workers_count - len(self.recovery.dead_workers))
                for item in lost:
                    self._resend(item)
                continue
            self.stop(); self.join()
            raise RuntimeError(
                f"Worker process {i} died unexpectedly with exit code {rc}")

    def _reclaim_ring(self, idx: int) -> None:
        """Worker-crash segment reclamation sweep for the dead worker's
        ring. Death is only ever acted on from the poll's no-message branch,
        i.e. AFTER every record the worker managed to publish — data,
        claim frames, processed markers — was consumed (the PR 2 books
        depend on those markers; this is why the sweep must NOT discard
        records wholesale). What can still be held: a stale chunk-reassembly
        buffer (S/P consumed, the final D died with the worker — its item is
        claimed-but-unprocessed and re-ventilates onto a survivor) and any
        not-yet-released segment claims (released by GC as usual; the
        producer being dead just means no backpressure ever builds). Torn
        mid-write frames cannot surface at all — both ring impls publish
        the record length and head only after the payload is fully
        written, so a crash mid-write leaves the record invisible
        (``RingReader.discard_pending`` exists for transports that detect
        death earlier; this pool's quiesce-point detection never needs
        it)."""
        if self._transport != "shm" or idx >= len(self._readers):
            return
        reader = self._readers[idx]
        reader.reap()
        stale_partial = self._partial.pop(idx, None) is not None
        if self.telemetry is not None:
            self.telemetry.counter("transport.rings_reclaimed").add(1)
        logger.info("Reclaimed dead worker %d's shm ring (%d record(s) "
                    "still pinned by consumer views%s)", idx, reader.pinned,
                    "; dropped a stale partial payload" if stale_partial
                    else "")


# ------------------------------------------------------------- worker side
def _worker_bootstrap(worker_id, worker_class, worker_args, serializer_cls,
                      endpoints, parent_pid, ring_name=None,
                      send_claims=False, ring_impl="native"):
    """Entry function of a spawned worker process (reference :330)."""
    import zmq

    from petastorm_tpu.resilience.faults import mark_spawned_worker
    # Legalize worker_kill faults (they refuse to fire in non-pool
    # processes) and let fault plans key per-process determinism.
    mark_spawned_worker()

    context = zmq.Context()
    work_socket = context.socket(zmq.PULL)
    work_socket.connect(endpoints["work"])
    control_socket = context.socket(zmq.SUB)
    control_socket.connect(endpoints["control"])
    control_socket.setsockopt(zmq.SUBSCRIBE, b"")
    results_socket = context.socket(zmq.PUSH)
    results_socket.connect(endpoints["results"])

    serializer = serializer_cls()

    ring = None
    _RING_CLOSED_ERRORS: tuple = ()
    if ring_name is not None:
        from petastorm_tpu.native import RingClosed, make_ring
        _RING_CLOSED_ERRORS = (RingClosed,)
        ring = make_ring(ring_name, create=False, impl=ring_impl)
        max_frame = max(4096, int(ring.capacity) // 2 - 4096)

        def send_ctrl(obj):
            ring.write_tagged(ord("C"), pickle.dumps(obj))

        def publish(data):
            payload = memoryview(serializer.serialize(data))
            # Chunk payloads bigger than half the ring so one giant row
            # group can never deadlock against its own backpressure;
            # memoryview slices keep chunking copy-free, and the S start
            # frame announces the total so the consumer preallocates ONE
            # reassembly buffer instead of concatenating per-chunk.
            if len(payload) > max_frame:
                ring.write_tagged(ord("S"),
                                  len(payload).to_bytes(8, "little"))
                while len(payload) > max_frame:
                    ring.write_tagged(ord("P"), payload[:max_frame])
                    payload = payload[max_frame:]
            ring.write_tagged(ord("D"), payload)
    else:
        def send_ctrl(obj):
            results_socket.send_multipart([_KIND_CTRL, pickle.dumps(obj)])

        def publish(data):
            results_socket.send_multipart([_KIND_DATA, serializer.serialize(data)])

    # Orphan watchdog: exit hard if the parent dies (reference :320-327).
    def _watch_parent():
        import psutil
        try:
            parent = psutil.Process(parent_pid)
            while parent.is_running() and parent.status() != psutil.STATUS_ZOMBIE:
                time.sleep(1)
        except psutil.NoSuchProcess:
            pass
        os._exit(0)

    threading.Thread(target=_watch_parent, daemon=True).start()

    worker = worker_class(worker_id, publish, worker_args)
    send_ctrl(_WorkerReady(worker_id))
    worker_track = f"worker:{worker_id}"

    poller = zmq.Poller()
    poller.register(work_socket, zmq.POLLIN)
    poller.register(control_socket, zmq.POLLIN)
    try:
        while True:
            events = dict(poller.poll())
            if control_socket in events:
                if control_socket.recv() == _CONTROL_FINISH:
                    break
            if work_socket in events:
                args, kwargs = work_socket.recv_pyobj()
                trace = kwargs.pop("trace_context", None)
                try:
                    # Claim frame BEFORE processing: on a hard crash the
                    # consumer's recovery ledger knows exactly which item
                    # this worker owned and re-ventilates it. Data precedes
                    # the processed marker on the same FIFO transport, so a
                    # claimed-but-unmarked item is never half-delivered.
                    # Skipped when no recovery ledger is attached — the
                    # consumer would just discard the frame.
                    if send_claims:
                        send_ctrl(ItemStartedMessage(
                            worker_id, kwargs.get(ITEM_CONTEXT_KWARG)))
                    t0 = time.perf_counter()
                    try:
                        worker.process(*args, **kwargs)
                    except RowGroupSkipped as skip:
                        # Degraded mode: ship the quarantine record; the
                        # processed marker below completes the item.
                        send_ctrl(RowGroupSkippedMessage(skip.record))
                    # Trace mode rides the injected trace_context kwarg
                    # itself — a LIVE per-item signal, so tracing enabled
                    # after this pool started (programmatic enable_trace,
                    # the mesh rollup path) still propagates: each item's
                    # decode is timed here and shipped as a compact span
                    # tuple on the processed marker (the consumer
                    # re-anchors it; perf_counter does not cross process
                    # boundaries).
                    busy_s = time.perf_counter() - t0
                    spans = ([("petastorm_tpu.worker_decode", "decode",
                               busy_s, trace, worker_track)]
                             if trace is not None else None)
                    send_ctrl(VentilatedItemProcessedMessage(
                        kwargs.get(ITEM_CONTEXT_KWARG), spans=spans,
                        worker_id=worker_id, busy_s=busy_s))
                except _RING_CLOSED_ERRORS:
                    # The consumer stopped and closed our ring mid-publish
                    # (early reader shutdown): a clean exit, not a failure.
                    break
                except Exception as e:  # noqa: BLE001 - ship to parent
                    sys.stderr.write(f"Worker {worker_id} exception:\n{format_exc()}\n")
                    try:
                        send_ctrl(WorkerFailure(e, format_exc()))
                    except Exception:  # noqa: BLE001 - unpicklable exception
                        send_ctrl(WorkerFailure(
                            RuntimeError(f"Worker {worker_id} failed: {e!r} "
                                         f"(original exception not picklable)"),
                            format_exc()))
                    break
    finally:
        worker.shutdown()
        for sock in (work_socket, control_socket, results_socket):
            sock.close(linger=1000)
        context.term()
        os._exit(0)
