"""Async row-group readahead: decouple raw Parquet IO from decode.

Without readahead, fetch and decode serialize on the same worker thread:
every row group blocks its decode worker on the filesystem before a single
cell is decoded. The :class:`ReadaheadFetcher` is a small pool of fetcher
threads fed in ventilation order (the Reader wraps ``pool.ventilate`` with
:meth:`submit`): it reads Arrow tables *ahead* of the decode workers —
coalescing every needed column of a row group into ONE
``read_row_group(s)`` call — so workers pop already-resident tables
(:meth:`pop`) instead of blocking on IO. The software-pipelining move
tf.data identifies as the single largest input-pipeline win (PAPERS.md),
applied at the row-group fetch stage.

Bounds and composition (docs/io.md):

* **depth** — at most ``depth`` row groups ahead (ready + in flight); a
  live knob (:meth:`set_readahead_depth`) actuated by the PR 3 autotune
  controller through ``ReadaheadDepthActuator``;
* **bytes** — fetched tables are charged to a
  :class:`~petastorm_tpu.autotune.budget.MemoryBudget` (the PR 3 shared
  ledger when the Reader has one, else a private allowance); fetchers
  stall while it is exhausted;
* **hedging (PR 4)** — the *fetch* is the hedged unit: with a
  ``hedge_policy`` each fetcher races a straggling read against a
  duplicate on a fresh handle, exactly as the workers do inline. Decode
  is never hedged;
* **retry/quarantine (PR 2)** — a prefetch that fails is *discarded* and
  only counted (``io.readahead.fetch_errors``): the decode worker's
  in-guard inline read re-attempts under the RetryPolicy and owns the
  quarantine decision, so readahead can neither duplicate nor lose a row
  group, and a transient prefetch error never burns a retry budget;
* **fault injection (PR 2)** — fetcher reads consult the plan's
  ``rowgroup.read`` site like any other read attempt (``worker_id`` =
  ``1000 + fetcher index``, so worker-pinned specs never fire here — a
  fault-plan keying detail ONLY: telemetry and traces identify fetchers
  first-class as ``stage="fetch"`` / ``fetch:{idx}``, never as phantom
  workers).

Telemetry (pipeline registry): ``io.readahead.hits`` / ``misses`` /
``fetch_errors`` / ``fetched_total`` counters, the cumulative
``io.readahead.fetch_s`` seconds counter (the "fetch" edge the
critical-path attributor arbitrates), ``io.readahead.depth`` /
``bytes_in_flight`` / ``ahead`` gauges, plus the shared ``io.bytes_read``
/ ``io.rowgroups_read`` counters the inline path also feeds. In trace
mode each fetch records a ``petastorm_tpu.fetch`` span with the work
item's lineage id on track ``fetch:{idx}`` (docs/observability.md).

In-process pools only: the fetched-table store cannot cross a spawn
boundary, so ``reader_pool_type='process'`` ignores readahead with a
warning (each spawned worker already overlaps against its siblings).
"""
from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Optional

from petastorm_tpu.metrics import traced_span

logger = logging.getLogger(__name__)

#: Bounded condition-variable poll (tools/check_timeouts.py: every wait in
#: this module must bear a timeout; a wedged fetch is the watchdog's to
#: catch, not ours to block on).
_WAIT_POLL_S = 0.05

#: Fault-plan worker id offset for fetcher threads: keeps their seeded rate
#: streams distinct from every pool worker's and makes worker-pinned specs
#: (``FaultSpec(worker=...)``) miss the fetch stage by construction.
FETCHER_WORKER_ID_BASE = 1000


def rowgroup_key(rowgroup) -> tuple:
    """Store key of one ventilated row-group work item (``row_group`` may
    be an int or a coalesced tuple of ordinals)."""
    return (rowgroup.path, rowgroup.row_group)


class ReadaheadFetcher:
    """:param filesystem: fsspec filesystem the dataset resolves through
    :param columns: the full column set any worker may request — one fetch
        covers the union, so predicate-first loading hits the same table
    :param depth: max row groups ahead (ready + in flight); >= 1
    :param fetchers: fetcher thread count (defaults to ``min(2, depth)``)
    :param budget: optional :class:`MemoryBudget` charged per fetched
        table (``force=True`` — the bytes exist once read; the overshoot
        is exactly the back-off signal); fetchers stall while exhausted
    :param fault_plan: PR 2 fault plan consulted at ``rowgroup.read``
    :param hedge_policy: PR 4 policy making each fetch a hedged read
    :param telemetry: pipeline registry (attached by the owning Reader)
    :param max_queue: cap on not-yet-fetched announcements; a submit
        beyond it is dropped (the inline read simply wins for that item).
        Bounds the stage when workers stop popping entirely — e.g. a warm
        row-group cache serving epochs >= 2 never reaches the read call —
        so announcements cannot accumulate across an unbounded epoch count.
    """

    def __init__(self, filesystem, columns, depth: int = 4,
                 fetchers: Optional[int] = None, budget=None,
                 fault_plan=None, hedge_policy=None, telemetry=None,
                 max_queue: Optional[int] = None):
        if depth < 1:
            raise ValueError(f"readahead depth must be >= 1, got {depth}")
        self._fs = filesystem
        self._columns = sorted(columns)
        self._depth = int(depth)
        self._fetchers_count = max(1, int(fetchers) if fetchers is not None
                                   else min(2, depth))
        self._max_queue = (int(max_queue) if max_queue is not None
                           else max(16, 4 * self._depth))
        self.budget = budget
        self._fault_plan = fault_plan
        self._hedge_policy = hedge_policy
        self._telemetry = telemetry

        self._cv = threading.Condition()
        self._queue: deque = deque()        # (key, rowgroup) awaiting fetch
        self._queued: dict = {}             # key -> count of queue entries
        self._claimed: dict = {}            # key -> inline-read claim-backs
        self._inflight: dict = {}           # key -> in-flight fetch count
        self._ready: dict = {}              # key -> deque[(table, nbytes)]
        self._ahead = 0                     # ready entries + in-flight fetches
        self._bytes = 0                     # resident fetched bytes
        self._stop = threading.Event()
        self._threads: list = []
        self._local = threading.local()     # per-fetcher file handles/hedger

        self._counters = None
        self._fetch_s = None
        if telemetry is not None:
            self._counters = {
                name: telemetry.counter(f"io.readahead.{name}")
                for name in ("hits", "misses", "fetch_errors",
                             "fetched_total", "submit_dropped")}
            self._fetch_s = telemetry.counter("io.readahead.fetch_s")
            self._bytes_read = telemetry.counter("io.bytes_read")
            self._rowgroups_read = telemetry.counter("io.rowgroups_read")
            telemetry.gauge("io.readahead.depth", lambda: self._depth)
            telemetry.gauge("io.readahead.bytes_in_flight",
                            lambda: self._bytes)
            telemetry.gauge("io.readahead.ahead", lambda: self._ahead)
        else:
            self._bytes_read = None
            self._rowgroups_read = None
        # Local mirrors so tests and reports have numbers even without a
        # registry (same pattern as HedgedReadExecutor.local_stats).
        self.local_stats = {"hits": 0, "misses": 0, "fetch_errors": 0,
                            "fetched_total": 0, "submit_dropped": 0}

    def _count(self, name: str) -> None:
        self.local_stats[name] += 1
        if self._counters is not None:
            self._counters[name].add(1)

    # ------------------------------------------------------------------ api
    def start(self) -> "ReadaheadFetcher":
        if self._threads:
            return self
        for i in range(self._fetchers_count):
            t = threading.Thread(target=self._fetch_loop, args=(i,),
                                 name=f"pt-readahead-{i}", daemon=True)
            self._threads.append(t)
            t.start()
        return self

    def submit(self, rowgroup, trace: Optional[str] = None) -> None:
        """Announce one ventilated work item (called from the ventilation
        thread, never blocks): fetchers pick it up in submission order. In
        normal flow the ventilator's in-flight cap bounds this queue;
        ``max_queue`` is the backstop for consumers that stop popping (a
        warm cache) — an over-cap submit is dropped and that item simply
        reads inline. ``trace`` carries the item's lineage id so fetch
        spans join the ventilate → decode chain."""
        with self._cv:
            if len(self._queue) >= self._max_queue:
                self._count("submit_dropped")
                return
            key = rowgroup_key(rowgroup)
            self._queue.append((key, rowgroup, trace))
            self._queued[key] = self._queued.get(key, 0) + 1
            self._cv.notify_all()

    def pop(self, rowgroup, checkpoint=None):
        """The decode worker's take: the fetched Arrow table for this work
        item, or ``None`` (a miss — read inline). A queued-but-unstarted
        fetch is *claimed back* (the inline read wins; fetchers discard the
        claimed entry when they reach it — O(1), no queue scan); an
        in-flight fetch is awaited with bounded polls, invoking
        ``checkpoint`` between them so stage-deadline/watchdog cancellation
        reaches the wait."""
        key = rowgroup_key(rowgroup)
        while True:
            with self._cv:
                dq = self._ready.get(key)
                if dq:
                    table, nbytes = dq.popleft()
                    if not dq:
                        del self._ready[key]
                    self._ahead -= 1
                    self._bytes -= nbytes
                    if self.budget is not None:
                        self.budget.release(nbytes)
                    self._cv.notify_all()
                    self._count("hits")
                    return table
                if not self._inflight.get(key):
                    # Not fetched and not being fetched: claim a queued
                    # request back (inline read wins), or it was never
                    # submitted / already errored — either way, a miss.
                    if self._queued.get(key, 0) > self._claimed.get(key, 0):
                        self._claimed[key] = self._claimed.get(key, 0) + 1
                    self._count("misses")
                    return None
                self._cv.wait(_WAIT_POLL_S)
            if checkpoint is not None:
                checkpoint()
            if self._stop.is_set():
                self._count("misses")
                return None

    def set_readahead_depth(self, n: int) -> None:
        """Runtime knob over how far fetchers run ahead (autotune's
        ``readahead_depth`` actuator; ``tools/check_knobs.py`` lints that
        only :mod:`petastorm_tpu.autotune` calls this). Shrinking below
        the current occupancy just pauses fetching until workers drain the
        excess; resident tables are never dropped."""
        with self._cv:
            self._depth = max(1, int(n))
            self._cv.notify_all()

    @property
    def depth(self) -> int:
        with self._cv:
            return self._depth

    def stats(self) -> dict:
        """JSON-safe snapshot for reports and tests. Fetcher threads are
        first-class pipeline citizens: ``provenance`` names the stage and
        its thread lanes (``fetch:{idx}``) — the identity traces and
        diagnostics display, never the synthetic fault-plan worker ids."""
        with self._cv:
            return {"depth": self._depth,
                    "fetchers": self._fetchers_count,
                    "ahead": self._ahead,
                    "bytes_in_flight": self._bytes,
                    "queued": len(self._queue),
                    "provenance": {
                        "stage": "fetch",
                        "tracks": [f"fetch:{i}"
                                   for i in range(self._fetchers_count)]},
                    **dict(self.local_stats)}

    def close(self) -> None:
        """Stop fetchers (bounded joins) and drop every resident table,
        releasing their budget charge."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []
        with self._cv:
            self._queue.clear()
            self._queued.clear()
            self._claimed.clear()
            for dq in self._ready.values():
                for _table, nbytes in dq:
                    self._bytes -= nbytes
                    if self.budget is not None:
                        self.budget.release(nbytes)
            self._ready.clear()
            self._ahead = 0

    # ------------------------------------------------------------ internals
    def _admissible(self) -> bool:
        """May another fetch start right now? (Called under the lock.)"""
        if self._ahead >= self._depth:
            return False
        if self.budget is not None and self.budget.available <= 0:
            return False
        return True

    def _next_request(self):
        """Next unclaimed ``(key, rowgroup, trace)`` off the queue,
        discarding entries an inline read already claimed back (O(1) per
        entry); ``None`` when the queue drained. Called under the lock."""
        while self._queue:
            key, rowgroup, trace = self._queue.popleft()
            n = self._queued.get(key, 1) - 1
            if n:
                self._queued[key] = n
            else:
                self._queued.pop(key, None)
            c = self._claimed.get(key, 0)
            if c:
                if c == 1:
                    del self._claimed[key]
                else:
                    self._claimed[key] = c - 1
                continue  # inline read won this item: nothing to fetch
            return key, rowgroup, trace
        return None

    def _fetch_loop(self, idx: int) -> None:
        while not self._stop.is_set():
            with self._cv:
                while not self._stop.is_set() and \
                        not (self._queue and self._admissible()):
                    self._cv.wait(_WAIT_POLL_S)
                if self._stop.is_set():
                    return
                request = self._next_request()
                if request is None:
                    continue  # every queued entry had been claimed back
                key, rowgroup, trace = request
                self._inflight[key] = self._inflight.get(key, 0) + 1
                self._ahead += 1
            table = None
            # First-class fetch provenance: stage="fetch" on the fetcher's
            # own track, carrying the item's lineage id. The span's clock
            # pair feeds io.readahead.fetch_s too (a failed fetch counts).
            span = traced_span("petastorm_tpu.fetch", self._telemetry,
                               trace=trace, stage="fetch",
                               track=f"fetch:{idx}")
            try:
                with span:
                    table = self._fetch(rowgroup, idx)
            except Exception as e:  # noqa: BLE001 - inline read owns retries
                self._count("fetch_errors")
                logger.debug("readahead fetch of %s failed (inline read "
                             "will retry): %s", key, e)
            if self._fetch_s is not None:
                self._fetch_s.add(span.duration_s)
            nbytes = int(table.nbytes) if table is not None else 0
            with self._cv:
                self._inflight[key] -= 1
                if not self._inflight[key]:
                    del self._inflight[key]
                if table is None or self._stop.is_set():
                    self._ahead -= 1
                else:
                    self._ready.setdefault(key, deque()).append(
                        (table, nbytes))
                    self._bytes += nbytes
                    if self.budget is not None:
                        # The bytes exist the moment the read returned;
                        # forced overshoot IS the fetch-admission back-off
                        # signal (same contract as the shuffling buffers).
                        self.budget.reserve(nbytes, force=True)
                    self._count("fetched_total")
                    if self._bytes_read is not None:
                        self._bytes_read.add(nbytes)
                        self._rowgroups_read.add(1)
                self._cv.notify_all()

    def _thread_state(self, idx: int):
        """Per-fetcher-thread file handles (and hedger, when hedging):
        fetchers never share ParquetFile objects across threads."""
        state = getattr(self._local, "state", None)
        if state is None:
            from petastorm_tpu.reader_impl.row_reader_worker import (
                _HedgeHandlePool, _ParquetFileLRU)
            hedger = None
            if self._hedge_policy is not None:
                from petastorm_tpu.resilience import HedgedReadExecutor
                hedger = HedgedReadExecutor(
                    self._hedge_policy, telemetry=self._telemetry,
                    worker_id=FETCHER_WORKER_ID_BASE + idx)
            state = self._local.state = {
                "files": _ParquetFileLRU(self._fs),
                "pool": _HedgeHandlePool(self._fs),
                "hedger": hedger,
            }
        return state

    def _fetch(self, rowgroup, idx: int):
        from petastorm_tpu.reader_impl.row_reader_worker import \
            _read_row_group
        state = self._thread_state(idx)
        worker_id = FETCHER_WORKER_ID_BASE + idx
        if state["hedger"] is None:
            return _read_row_group(state["files"], rowgroup, self._columns,
                                   fault_plan=self._fault_plan,
                                   worker_id=worker_id)

        def attempt(_cancel):
            private = state["pool"].acquire()
            try:
                return _read_row_group(private, rowgroup, self._columns,
                                       fault_plan=self._fault_plan,
                                       worker_id=worker_id)
            finally:
                state["pool"].release(private)

        return state["hedger"].read(attempt, attempt,
                                    key=str(rowgroup.path))
