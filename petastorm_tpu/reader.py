"""Reader API: ``make_reader`` (petastorm row datasets) and
``make_batch_reader`` (any Parquet store).

A :class:`Reader` plans the dataset's row groups (predicate pushdown,
index-selector pruning, multi-host sharding), feeds them through a worker
pool behind a backpressured ventilator, and yields decoded samples:
per-row namedtuples (``make_reader``) or namedtuples of numpy arrays, one
per row group (``make_batch_reader``).

TPU-first behaviors beyond the reference:

* ``cur_shard="auto"`` derives the shard from ``jax.process_index()`` /
  ``jax.process_count()`` so every TPU host reads a disjoint row-group slice
  of the same seeded global order with zero configuration;
* fully seeded determinism end-to-end (shard pre-shuffle, ventilation order,
  in-group shuffling, round-robin readout) so multi-host input pipelines stay
  in lockstep — a requirement for GSPMD global-batch assembly;
* the columnar path keeps data in Arrow until the JAX loader stages it.

Parity: reference petastorm/reader.py — ``make_reader`` (:60),
``make_batch_reader`` (:209), ``Reader`` (:355), ``_filter_row_groups``
(:533), ``_partition_row_groups`` (:573, ``index % shard_count == cur_shard``
:596), ``_create_ventilator`` (:666), ``__next__`` (:708), ``reset`` (:503).
"""
from __future__ import annotations

import logging
import os
import threading
import time
import warnings
from collections import deque
from typing import Optional, Sequence

from petastorm_tpu.cache import NullCache
from petastorm_tpu.errors import MetadataError, NoDataAvailableError
from petastorm_tpu.etl.dataset_metadata import (DatasetContext, get_schema,
                                                infer_or_load_unischema,
                                                load_row_groups)
from petastorm_tpu.metrics import traced_span
from petastorm_tpu.ngram import NGram
from petastorm_tpu.reader_impl.batch_plane import ColumnarBatch
from petastorm_tpu.reader_impl.batch_reader_worker import (BatchReaderWorker,
                                                           arrow_table_to_numpy_dict)
from petastorm_tpu.reader_impl.row_reader_worker import RowReaderWorker
from petastorm_tpu.telemetry import (PeriodicExporter, SLO_WATCH_ENV,
                                     TELEMETRY_EXPORT_ENV, make_registry)
from petastorm_tpu.transform import transform_schema
from petastorm_tpu.unischema import Unischema, UnischemaField
from petastorm_tpu.workers_pool import EmptyResultError, ITEM_CONTEXT_KWARG
from petastorm_tpu.workers_pool.dummy_pool import DummyPool
from petastorm_tpu.workers_pool.process_pool import ProcessPool
from petastorm_tpu.workers_pool.thread_pool import ThreadPool
from petastorm_tpu.workers_pool.ventilator import ConcurrentVentilator

logger = logging.getLogger(__name__)

# In-flight row groups beyond one per worker (reference reader.py:45).
_VENTILATE_EXTRA_ROWGROUPS = 3


def _coalesce_row_groups(refs, max_per_item: int):
    """Merge runs of same-file row groups (post filter/shard, pre shuffle)
    into single work items whose ``row_group`` is a tuple of ordinals — the
    worker reads them in one ``read_row_groups`` IO call. Partition values
    are per file, so a same-path run shares them by construction."""
    import dataclasses
    out, run = [], []

    def flush():
        if not run:
            return
        first = run[0]
        if len(run) == 1:
            out.append(first)
        else:
            out.append(dataclasses.replace(
                first, row_group=tuple(r.row_group for r in run)))
        run.clear()

    for ref in refs:
        if run and (ref.path != run[0].path or len(run) >= max_per_item):
            flush()
        run.append(ref)
    flush()
    return out


_FILTER_OPS = ("=", "==", "!=", "<", "<=", ">", ">=", "in", "not in")


def _filter_value_eq(val, ref) -> bool:
    """Hive partition values arrive as path strings; compare by string
    render — with a numeric fallback so ``("year", "=", 2024.0)`` still
    matches the ``year=2024`` directory (same coercion the ordering ops
    use; string-only equality would silently match nothing)."""
    if str(val) == str(ref):
        return True
    try:
        return float(val) == float(ref)
    except (TypeError, ValueError):
        return False


def _filter_compare(val, op: str, ref) -> bool:
    if op in ("=", "=="):
        return _filter_value_eq(val, ref)
    if op == "!=":
        return not _filter_value_eq(val, ref)
    if op == "in":
        return any(_filter_value_eq(val, r) for r in ref)
    if op == "not in":
        return not any(_filter_value_eq(val, r) for r in ref)
    # ordering: numeric when both sides coerce, else lexicographic
    try:
        a, b = float(val), float(ref)
    except (TypeError, ValueError):
        a, b = str(val), str(ref)
    return {"<": a < b, "<=": a <= b, ">": a > b, ">=": a >= b}[op]


def _normalize_filters(filters):
    """-> list of AND-groups (DNF). Accepts the two standard pyarrow forms:
    ``[(col, op, val), ...]`` (one conjunction) and
    ``[[(col, op, val), ...], ...]`` (disjunction of conjunctions).
    Validation is EAGER — ops, clause shapes, empty groups, and in/not-in
    reference types are all checked here, not lazily during matching where
    short-circuiting would make errors data-dependent."""
    if not filters:
        return []
    if all(isinstance(f, tuple) for f in filters):
        groups = [list(filters)]
    elif all(isinstance(f, (list, tuple)) for f in filters):
        groups = [list(g) for g in filters]
    else:
        raise ValueError("filters must be a list of (col, op, val) tuples "
                         "or a list of such lists")
    for g in groups:
        if not g:
            raise ValueError("empty filter conjunction [] matches nothing "
                             "meaningfully; remove it or add clauses")
        for clause in g:
            if not (isinstance(clause, tuple) and len(clause) == 3):
                raise ValueError(f"bad filter clause {clause!r}; expected "
                                 f"(column, op, value)")
            _col, op, ref = clause
            if op not in _FILTER_OPS:
                raise ValueError(f"unsupported filter op {op!r} "
                                 f"(supported: {' '.join(_FILTER_OPS)})")
            if op in ("in", "not in") and isinstance(ref, (str, bytes)):
                raise ValueError(
                    f"filter ({_col!r}, {op!r}, {ref!r}): the reference "
                    f"must be a list/tuple/set of values, not a string "
                    f"(iterating a string compares its characters)")
    return groups


def _row_group_matches_filters(partition_dict: dict, groups) -> bool:
    # A row group lacking a referenced key (heterogeneous multi-URL stores)
    # can never satisfy the clause: non-match, not KeyError.
    def clause_ok(col, op, ref):
        if col not in partition_dict:
            return False
        return _filter_compare(partition_dict[col], op, ref)

    return any(all(clause_ok(*clause) for clause in g) for g in groups)


def _partition_keys(row_groups) -> set:
    """Union of hive partition keys across all row groups (multi-URL views
    can mix partitioned and unpartitioned stores)."""
    keys: set = set()
    for rg in row_groups:
        keys.update(k for k, _ in rg.partition_values)
    return keys


def _warn_compat_kwargs(hdfs_driver, pyarrow_serialize):
    """Reference kwargs accepted for drop-in compatibility but meaningless
    here; warn (once per process via the warnings registry) instead of
    raising TypeError on ported call sites."""
    if hdfs_driver is not None:
        warnings.warn("hdfs_driver is ignored: hdfs access goes through "
                      "fsspec/pyarrow (HA failover via petastorm_tpu.hdfs)",
                      stacklevel=3)  # point at the make_*_reader caller
    if pyarrow_serialize:
        warnings.warn("pyarrow_serialize was deprecated in petastorm and "
                      "is a no-op here", DeprecationWarning, stacklevel=3)


#: Per-process memo for one-shot configuration warnings, keyed by the
#: kwarg name that triggered them. The ``warnings`` module dedupes by
#: source location, but each Reader construction re-derives the message in
#: a fresh call context, so pre-mesh these caveats fired once per READER —
#: a mesh ingestion epoch builds one reader per (simulated) host per epoch
#: and would repeat a process-wide fact H x epochs times (docs/mesh.md).
_ONE_SHOT_WARNED: set = set()


def _warn_once(kwarg: str, message: str, stacklevel: int = 2) -> None:
    """Emit ``message`` at most once per process for ``kwarg``. The caveat
    depends only on process-wide configuration (kwarg x pool flavor), so
    the first reader that hits it speaks for every later one."""
    if kwarg in _ONE_SHOT_WARNED:
        return
    _ONE_SHOT_WARNED.add(kwarg)
    warnings.warn(message, stacklevel=stacklevel)


def _reset_one_shot_warnings() -> None:
    """Test hook: forget which one-shot warnings already fired."""
    _ONE_SHOT_WARNED.clear()


def _resolve_shard(cur_shard, shard_count):
    """``cur_shard="auto"`` -> this JAX process's (index, count)."""
    if cur_shard == "auto":
        import jax
        return jax.process_index(), (shard_count or jax.process_count())
    return cur_shard, shard_count


def _resolve_seed(seed, resume_state, shuffle_row_groups, shuffle_rows,
                  sample_order):
    """Seeded-by-default (docs/determinism.md): when any ordering decision
    is randomized and no seed was given, mint one at plan time and record
    it in ``state_dict`` — an unseeded shuffle is statistically identical
    but unresumable. A ``resume_state`` supplies its recorded seed instead
    (the offsets index THAT permutation); a restored state that lacks one
    (saved before seeds were recorded, or hand-built) stays ``None`` so
    the resume-requires-seed check below can refuse honestly rather than
    silently repositioning a fresh random order."""
    if seed is not None:
        return seed
    if resume_state is not None:
        saved = resume_state.get("seed")
        return None if saved is None else int(saved)
    if shuffle_row_groups or shuffle_rows or sample_order != "free":
        from petastorm_tpu.reader_impl.epoch_plan import mint_seed
        return mint_seed()
    return None


#: Give-up deadline for a placement migration's old-pool drain: past this,
#: the migration aborts and the reader stays on the live pool (migratable
#: configurations run without the watchdog, so this bound is what keeps a
#: wedged worker from hanging the consumer in the swap).
_MIGRATION_DRAIN_TIMEOUT_S = 120.0

#: Default per-worker shm ring capacity, and the clamp range applied when
#: the PR 3 MemoryBudget ledger sizes the rings instead (docs/zero_copy.md).
_DEFAULT_RING_CAPACITY = 128 << 20
_RING_CAPACITY_MIN = 16 << 20
_RING_CAPACITY_MAX = 512 << 20


def _ring_capacity_from_budget(autotune_config, workers_count: int) -> int:
    """Per-worker shm ring bytes: an even split of the autotune
    ``memory_budget_bytes`` ledger across workers (clamped so one worker
    can still carry a multi-MB row group and a huge budget doesn't map
    gigabytes of shm per worker); the documented default otherwise."""
    budget = getattr(autotune_config, "memory_budget_bytes", None)
    if not budget:
        return _DEFAULT_RING_CAPACITY
    per_worker = int(budget) // max(1, workers_count)
    return max(_RING_CAPACITY_MIN, min(_RING_CAPACITY_MAX, per_worker))


def _make_pool(reader_pool_type, workers_count, results_queue_size, serializer,
               shuffle_rows, seed, zmq_copy_buffers=True,
               pool_profiling_enabled=False,
               ring_capacity=_DEFAULT_RING_CAPACITY):
    if reader_pool_type == "thread":
        return ThreadPool(workers_count, results_queue_size=results_queue_size,
                          profiling_enabled=pool_profiling_enabled,
                          shuffle_rows=shuffle_rows, seed=seed)
    if pool_profiling_enabled:
        # cProfile instruments python frames in THIS process; process-pool
        # workers run elsewhere and the dummy pool has no worker threads
        # (reference scopes profiling to the thread pool the same way:
        # petastorm/workers_pool/thread_pool.py:47-52).
        warnings.warn(f"pool_profiling_enabled only applies to "
                      f"reader_pool_type='thread'; ignored for "
                      f"{reader_pool_type!r}")
    if reader_pool_type == "process":
        return ProcessPool(workers_count, serializer=serializer,
                           zmq_copy_buffers=zmq_copy_buffers,
                           results_queue_size=results_queue_size,
                           ring_capacity=ring_capacity)
    if reader_pool_type == "dummy":
        return DummyPool()
    raise ValueError(f"Unknown reader_pool_type {reader_pool_type!r} "
                     f"(expected 'thread', 'process' or 'dummy')")


def _warn_predicate_bypasses_cache(predicate, memory_cache_size_bytes):
    """Both reader workers evaluate worker-side predicates on freshly-read
    columns and never consult the row-group cache on that path (a cached
    payload cannot be predicate-filtered without freezing the row set), so
    a memory cache sized per the docs would silently record zero hits."""
    if predicate is not None and memory_cache_size_bytes:
        warnings.warn(
            "predicate= bypasses row-group caching: every epoch re-reads "
            "and re-decodes the predicate's row groups, and the "
            f"{memory_cache_size_bytes}-byte memory cache will record no "
            "hits. Drop the predicate (filter after read) to cache, or "
            "drop memory_cache_size_bytes to silence this.")


def _fingerprint_fields(schema, schema_fields) -> list:
    """The plan-cache fingerprint's field ingredient (docs/plan.md "Plan
    cache"): the NARROWED output view's names — two readers selecting
    different column subsets of one dataset are different workloads and
    must never share a persisted placement verdict. Falls back to the
    full schema for NGram windows and for view errors (the Reader raises
    the real error later on the normal path)."""
    from petastorm_tpu.ngram import NGram
    if schema_fields is not None and not isinstance(schema_fields, NGram):
        try:
            return sorted(schema.create_schema_view(schema_fields).fields)
        except Exception:  # noqa: BLE001 - fingerprint is best-effort
            pass
    return sorted(schema.fields)


def _make_cache(cache_type, cache_location, cache_size_limit, cache_row_size_estimate,
                cache_extra_settings, retry_policy=None, fault_plan=None,
                memory_cache_size_bytes=None):
    if memory_cache_size_bytes:
        # (memory_cache_size_bytes x cache_type conflicts raise in the
        # plan-time validation pass before this factory runs.)
        from petastorm_tpu.autotune import InMemoryRowGroupCache
        return InMemoryRowGroupCache(memory_cache_size_bytes,
                                     fault_plan=fault_plan)
    if cache_type in (None, "null"):
        return NullCache()
    if cache_type == "local-disk":
        from petastorm_tpu.local_disk_cache import LocalDiskCache
        return LocalDiskCache(cache_location, cache_size_limit,
                              cache_row_size_estimate or 0,
                              retry_policy=retry_policy,
                              fault_plan=fault_plan,
                              **(cache_extra_settings or {}))
    raise ValueError(f"Unknown cache_type {cache_type!r}")


def make_reader(dataset_url,
                schema_fields=None,
                reader_pool_type: str = "thread",
                workers_count: int = 4,
                results_queue_size: int = 50,
                shuffle_row_groups: bool = True,
                shuffle_rows: bool = False,
                shuffle_row_drop_partitions: int = 1,
                predicate=None,
                rowgroup_selector=None,
                filters=None,
                num_epochs: Optional[int] = 1,
                cur_shard=None,
                shard_count: Optional[int] = None,
                shard_seed: Optional[int] = None,
                seed: Optional[int] = None,
                cache_type: str = "null",
                cache_location: Optional[str] = None,
                cache_size_limit: Optional[int] = None,
                cache_row_size_estimate: Optional[int] = None,
                cache_extra_settings: Optional[dict] = None,
                transform_spec=None,
                storage_options: Optional[dict] = None,
                filesystem=None,
                zmq_copy_buffers: bool = True,
                resume_state: Optional[dict] = None,
                rowgroup_coalescing: int = 1,
                pool_profiling_enabled: bool = False,
                hdfs_driver: Optional[str] = None,
                pyarrow_serialize: bool = False,
                convert_early_to_numpy: Optional[bool] = None,
                retry_policy=None,
                degraded_mode: bool = False,
                fault_plan=None,
                worker_crash_budget: int = 0,
                autotune: bool = False,
                autotune_config=None,
                memory_cache_size_bytes: Optional[int] = None,
                stage_deadline_s=None,
                hedge_policy=None,
                hang_timeout_s: Optional[float] = None,
                rowgroup_pruning: bool = True,
                readahead_depth: Optional[int] = None,
                readahead_max_bytes: Optional[int] = None,
                rowgroup_subset: Optional[Sequence[int]] = None,
                row_materialization: str = "eager",
                sample_order: str = "free",
                shuffle_window: int = 0,
                refresh_interval_s: Optional[float] = None,
                timeline_interval_s: Optional[float] = None,
                timeline_anomaly: bool = True,
                quality: bool = False,
                quality_config=None,
                reference_profile=None,
                telemetry_publish: Optional[str] = None,
                tenant: Optional[str] = None):
    """Reader for **petastorm-written** datasets (codec-decoded rows).

    :param schema_fields: list of UnischemaField / name regexes narrowing the
        output, or an :class:`NGram` for windowed sequence readout
    :param reader_pool_type: 'thread' | 'process' | 'dummy'
    :param shuffle_row_groups: shuffle row-group order (seeded by ``seed``)
    :param shuffle_rows: shuffle rows inside each row group
    :param shuffle_row_drop_partitions: ventilate each row group N times,
        each reading a different 1/N slice (decorrelates at memory cost)
    :param filters: standard pyarrow partition filters — ``[(col, op,
        val), ...]`` (ANDed) or a list of such lists (ORed) with ops
        ``= == != < <= > >= in "not in"`` — pruning whole row groups by
        hive partition values at planning time (columns must be partition
        keys; use ``predicate`` for row-level filtering)
    :param num_epochs: passes over the dataset; ``None`` = infinite
    :param cur_shard/shard_count: this process's shard; ``cur_shard="auto"``
        derives both from the JAX distributed runtime
    :param shard_seed: seed for pre-shard row-group shuffling
    :param seed: master seed for all shuffling (determinism when set)
    :param rowgroup_coalescing: read up to N same-file row groups per work
        item in ONE IO call — amortizes per-group costs on stores with many
        tiny groups. Coarsens shuffle/shard/resume granularity to the
        coalesced unit, and NGram windows may span the original group
        boundaries inside a unit (no equivalent in the reference).
    :param pool_profiling_enabled: (thread pool only) cProfile the pool and
        print stats when the reader closes (parity: reference
        thread_pool.py:47-52; exposed as ``--profile-threads`` on the
        throughput CLI like the reference's benchmark/cli.py). Per-worker
        merged profiles pre-3.12; on 3.12+ one process-wide profile that
        also captures consumer-thread frames (see
        :class:`~petastorm_tpu.workers_pool.thread_pool.ThreadPool`)
    :param hdfs_driver: accepted for drop-in petastorm compatibility and
        ignored — hdfs access goes through fsspec/pyarrow here, with HA
        namenode failover handled by :mod:`petastorm_tpu.hdfs`
    :param pyarrow_serialize: deprecated no-op, as in the reference
        (reader.py:96,167-168)
    :param convert_early_to_numpy: accepted for drop-in compatibility; the
        row path always decodes to numpy inside the workers (the "early"
        behavior), so both values are satisfied
    :param retry_policy: a :class:`petastorm_tpu.resilience.RetryPolicy`
        governing row-group IO/decode retries in the workers and (with its
        classifier swapped to the sqlite flavor) disk-cache fills; default
        :data:`~petastorm_tpu.resilience.DEFAULT_READ_POLICY`
    :param degraded_mode: when True, a row group that still fails after
        retries is **quarantined** (skipped, with provenance on
        :meth:`Reader.quarantine_report`) instead of killing the epoch
    :param fault_plan: a :class:`petastorm_tpu.resilience.FaultPlan` for
        deterministic fault injection (tests/benchmarks only)
    :param worker_crash_budget: with ``reader_pool_type='process'``, tolerate
        up to N hard worker deaths per epoch by re-ventilating the lost row
        groups onto surviving workers (0 = any crash is fatal, the previous
        behavior). See docs/resilience.md.
    :param autotune: start a background
        :class:`~petastorm_tpu.autotune.AutotuneController` that samples
        this pipeline's telemetry and adjusts worker concurrency,
        ventilation depth, shuffle-buffer target, and (when a JAX loader
        consumes this reader) prefetch depth — with hysteresis and clamped
        safe ranges. See docs/autotune.md.
    :param autotune_config: an
        :class:`~petastorm_tpu.autotune.AutotuneConfig` overriding the
        controller's interval/hysteresis/watermarks
    :param memory_cache_size_bytes: enable the in-memory **decoded**
        row-group LRU cache with this byte budget — epochs >= 2 serve from
        RAM instead of re-reading and re-decoding Parquet. Mutually
        exclusive with ``cache_type='local-disk'``; a worker-side
        ``predicate`` bypasses row-group caching entirely (a warning says
        so). With ``reader_pool_type='process'`` each spawned worker keeps
        a private cache of this size over its own item subset (the budget
        multiplies by ``workers_count``).
    :param stage_deadline_s: per-attempt latency budget for each work
        item's load+decode: a number ``h`` means hard deadline ``h`` with
        a soft (straggler-telemetry-only) budget at ``h/2``; pass a
        :class:`petastorm_tpu.resilience.StageDeadline` for independent
        soft/hard budgets. Hard overruns cancel the attempt into the
        retry/quarantine machinery (docs/resilience.md § "Deadlines,
        hedging, and the watchdog").
    :param hedge_policy: a :class:`petastorm_tpu.resilience.HedgePolicy`
        enabling speculative duplicate row-group reads once the primary
        read straggles past a quantile-tracked delay; first result wins,
        byte-identical either way (seeded epochs stay reproducible).
    :param hang_timeout_s: start a :class:`petastorm_tpu.resilience.
        PipelineWatchdog`: if the consumer starves for this long with no
        progress anywhere in the pipeline, thread stacks are dumped to
        telemetry and the watchdog escalates nudge -> cancel/kill ->
        ``PipelineHungError`` — the reader never blocks indefinitely.
    :param rowgroup_pruning: (default True) when ``predicate`` describes
        its acceptable values via :meth:`PredicateBase.intervals` (the
        built-in equality/in-set/range predicates do), evaluate Parquet
        per-row-group column min/max/null-count statistics at plan time
        and drop row groups **no row of which can possibly match** —
        skipped groups are never fetched or decoded
        (``io.rowgroups_pruned`` telemetry; :meth:`Reader.pruning_report`).
        Predicates without ``intervals()`` fall back to fetch-then-filter
        with zero behavior change. See docs/io.md.
    :param readahead_depth: enable the async readahead fetch stage
        (docs/io.md): a small pool of fetcher threads reads up to this
        many row groups' Arrow tables ahead of the decode workers, so
        decode pops resident tables instead of blocking on the
        filesystem. In-process pools only (ignored with a warning for
        ``reader_pool_type='process'``); an autotune actuator when
        ``autotune=True``; composes with retry/quarantine (a failed
        prefetch is discarded and re-read inline under the RetryPolicy)
        and hedging (the fetch is the hedged unit). ``None``/0 = off.
    :param readahead_max_bytes: byte allowance for fetched-ahead tables
        (default 256 MiB); with ``autotune_config.memory_budget_bytes``
        the PR 3 shared ledger is charged instead.
    :param rowgroup_subset: explicit plan restriction — ordinals into the
        dataset's deterministic row-group order (``load_row_groups``),
        read in exactly the given order. This is how the mesh ingestion
        layer (docs/mesh.md) expresses per-host shard plans and
        reassigns a lost host's remaining range to survivors; the
        ordinals compose with predicate/selector/statistics pruning
        (which still run after the restriction) and are mutually
        exclusive with ``cur_shard`` — a subset IS a shard assignment.
    :param row_materialization: ``'eager'`` (default — per-row dicts are
        built inside the workers, byte-identical to every earlier round)
        or ``'lazy'`` — the batch-native epoch plane (docs/io.md): workers
        publish ONE columnar batch per row group, ``__next__`` yields rows
        as *views* into the shared batch (cells index the batch's column
        stacks — holding a row pins its batch, writing a cell writes the
        batch), and :meth:`Reader.next_batch` exposes whole batches so the
        JAX loaders collate by slicing columns instead of looping rows.
        Same rows, same per-epoch multiset under a seed; the per-sample
        Python loops just never run. Falls back to eager (with a warning)
        for NGram readers and per-row ``TransformSpec`` funcs
        (``TransformSpec(batched=True)`` composes with lazy).
    :param sample_order: ``'free'`` (default — delivery order depends on
        pool type, worker count and timing, today's behavior) or
        ``'deterministic'`` — the **deterministic epoch plane**
        (docs/determinism.md): the delivered stream is a pure function of
        ``(seed, epoch_idx, shard_plan)``, byte-identical across
        thread/process/dummy pools, worker counts, autotune actuation,
        readahead depth, hedging, placement migration, crash
        re-ventilation, and mid-epoch resume. A consumer-side reorder
        stage re-sequences out-of-order completions; quarantine skips
        advance the watermark deterministically and ride the checkpoint
        cursor. Seeded-by-default: with no ``seed`` one is minted at plan
        time and recorded in :meth:`Reader.state_dict`.
    :param shuffle_window: with ``sample_order='deterministic'``, shuffle
        the ordered stream inside consecutive windows of this many work
        items via a seeded, position-indexed block permutation — a
        function of the cursor, not of arrival timing, so it is exactly
        resumable and has a **provable mixing radius** (a row group is
        delivered within ``shuffle_window`` plan positions of its slot;
        docs/determinism.md for the math). ``0`` = exact plan order.
    :param refresh_interval_s: **live appending datasets**
        (docs/live_data.md): start a :class:`~petastorm_tpu.discovery.
        DatasetWatcher` that re-lists the store — every
        ``refresh_interval_s`` seconds from a background thread, or (with
        ``0``) synchronously at each ``reset()``/:meth:`Reader.
        refresh_dataset` call — validates every new file (torn footers
        quarantine ``pending_retry`` and are re-tried next poll;
        incompatible schema drift is refused loudly while serving
        continues on the last good snapshot) and extends the plan
        **monotonically**: new row groups get ordinals after the existing
        range, effective from a not-yet-planned epoch, so deterministic
        mode, already-planned epochs, mid-epoch cursors, and statistics
        pruning (run incrementally on just the new footers) all survive
        growth. Surfaces: :meth:`Reader.dataset_growth_report`,
        ``discovery.*`` telemetry, and the ``ingest_lag_s`` SLO rule.
        Typically combined with ``num_epochs=None``. Mutually exclusive
        with ``rowgroup_subset`` (the mesh layer folds growth itself,
        docs/mesh.md) and ``shard_seed`` (a pre-shuffled shard stream
        cannot extend monotonically). ``None`` = today's static snapshot.
    :param timeline_interval_s: **ops plane** (docs/observability.md "Ops
        plane"): attach a rolling :class:`~petastorm_tpu.telemetry.
        MetricsTimeline` to this pipeline's registry, sampled every
        ``timeline_interval_s`` seconds by a background thread — windowed
        rates (rows/s, bytes/s, stall fraction, hedge rate, ingest lag)
        and rolling quantiles, exported under ``snapshot()["timeline"]``
        and :meth:`Reader.timeline_report`, rendered live by ``python -m
        petastorm_tpu.telemetry top``. ``None`` defers to the
        ``PETASTORM_TPU_TIMELINE`` env var; unset = off.
    :param timeline_anomaly: run the default anomaly-detector bank
        (:func:`petastorm_tpu.telemetry.default_anomaly_rules`) over every
        timeline window, recording ``anomaly.*`` events/counters and — with
        ``PETASTORM_TPU_BLACKBOX`` armed — writing a postmortem bundle on
        a detection's entry edge. ``False`` keeps the ring without the
        detectors (the right setting for sub-feeds whose local rates
        legitimately gap, e.g. mesh host readers).
    :param quality: **data-quality plane** (docs/observability.md "Data
        quality plane"): attach a :class:`~petastorm_tpu.quality.
        QualityMonitor` — streaming per-column profiles (count/null-rate/
        min-max/moments, fixed-bucket histogram, distinct sketch; ndarray
        columns profile shape/dtype/NaN-fraction) updated in one
        vectorized pass per delivered unit, PSI/chi-square drift scoring
        against ``reference_profile`` surfaced as ``quality.drift.{col}``
        gauges + ``quality.max_drift`` (SLO-gateable), and an epoch
        **coverage auditor** (exact per-ordinal with
        ``sample_order='deterministic'``; unit counts otherwise). With
        live discovery, newly admitted files are scored against the
        reference from their footer statistics *before* their bytes join
        an epoch. Read via :meth:`Reader.quality_report`.
    :param quality_config: a :class:`~petastorm_tpu.quality.QualityConfig`
        overriding bucket counts, tracked columns, drift thresholds, and
        the admission action (implies ``quality=True``).
    :param reference_profile: the drift baseline — a path to a JSON
        profile written by :func:`petastorm_tpu.quality.save_profile`, a
        profile dict, or a :class:`~petastorm_tpu.quality.DatasetProfile`
        (implies ``quality=True``). Without it the live profile is still
        built (and becomes the admission baseline); drift scores need the
        reference.

    Parity: reference reader.py:60.
    """
    _warn_compat_kwargs(hdfs_driver, pyarrow_serialize)
    del convert_early_to_numpy  # row workers always decode early
    # Resolve the seed BEFORE the pool factory closes over it: a minted
    # seed must reach worker RNGs and the thread pool's readout-order
    # choice, not just the ventilator (docs/determinism.md).
    seed = _resolve_seed(seed, resume_state, shuffle_row_groups,
                         shuffle_rows, sample_order)
    ctx = DatasetContext(dataset_url, storage_options=storage_options,
                         filesystem=filesystem)
    try:
        stored_schema = get_schema(ctx)
    except MetadataError as e:
        raise MetadataError(
            f"Dataset at {dataset_url} is missing petastorm metadata "
            f"(underlying error: {e}). If this is a plain Parquet store, use "
            f"make_batch_reader() instead.") from e

    # ---------------- plan lowering (docs/plan.md): kwargs -> executable
    # PipelinePlan — one consolidated validation pass, operator
    # materialization, fusion passes, and the optimizer's persisted-plan
    # consult (which may override the pool backend on an opted-in warm
    # start; plan.pool_type is what construction stands up).
    from petastorm_tpu.plan import lower_reader_kwargs
    plan = lower_reader_kwargs(
        "row",
        {"dataset_url": dataset_url, "reader_pool_type": reader_pool_type,
         "workers_count": workers_count,
         "results_queue_size": results_queue_size,
         "shuffle_row_groups": shuffle_row_groups,
         "shuffle_rows": shuffle_rows,
         "shuffle_row_drop_partitions": shuffle_row_drop_partitions,
         "predicate": predicate, "transform_spec": transform_spec,
         "num_epochs": num_epochs,
         "cur_shard": cur_shard, "shard_seed": shard_seed, "seed": seed,
         "cache_type": cache_type, "cache_location": cache_location,
         "cache_size_limit": cache_size_limit,
         "memory_cache_size_bytes": memory_cache_size_bytes,
         "rowgroup_coalescing": rowgroup_coalescing,
         "zmq_copy_buffers": zmq_copy_buffers,
         "readahead_depth": readahead_depth,
         "readahead_max_bytes": readahead_max_bytes,
         "rowgroup_subset": rowgroup_subset,
         "row_materialization": row_materialization,
         "sample_order": sample_order, "shuffle_window": shuffle_window,
         "refresh_interval_s": refresh_interval_s,
         "autotune": autotune, "autotune_config": autotune_config},
        schema_field_names=_fingerprint_fields(stored_schema,
                                               schema_fields),
        ngram=isinstance(schema_fields, NGram))
    reader_pool_type = plan.pool_type

    _warn_predicate_bypasses_cache(predicate, memory_cache_size_bytes)
    cache = _make_cache(cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings,
                        retry_policy=retry_policy, fault_plan=fault_plan,
                        memory_cache_size_bytes=memory_cache_size_bytes)

    from petastorm_tpu.reader_impl.pickle_serializer import PickleSerializer

    def pool_factory(target):
        return _make_pool(target, workers_count, results_queue_size,
                          PickleSerializer(), shuffle_rows, seed,
                          zmq_copy_buffers, pool_profiling_enabled,
                          ring_capacity=_ring_capacity_from_budget(
                              autotune_config, workers_count))
    # ONE construction path: the initial pool and any pool a placement
    # migration later builds go through the same factory.
    pool = pool_factory(reader_pool_type)

    return Reader(ctx, stored_schema,
                  plan=plan,
                  pool_factory=pool_factory,
                  dataset_url_or_urls=dataset_url,
                  schema_fields=schema_fields,
                  worker_class=RowReaderWorker,
                  pool=pool,
                  is_batched_reader=False,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_rows=shuffle_rows,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate,
                  rowgroup_selector=rowgroup_selector,
                  num_epochs=num_epochs,
                  cur_shard=cur_shard,
                  shard_count=shard_count,
                  shard_seed=shard_seed,
                  seed=seed,
                  cache=cache,
                  transform_spec=transform_spec,
                  storage_options=storage_options,
                  resume_state=resume_state,
                  filters=filters,
                  filesystem=filesystem,
                  rowgroup_coalescing=rowgroup_coalescing,
                  retry_policy=retry_policy,
                  degraded_mode=degraded_mode,
                  fault_plan=fault_plan,
                  worker_crash_budget=worker_crash_budget,
                  autotune=autotune,
                  autotune_config=autotune_config,
                  stage_deadline_s=stage_deadline_s,
                  hedge_policy=hedge_policy,
                  hang_timeout_s=hang_timeout_s,
                  rowgroup_pruning=rowgroup_pruning,
                  readahead_depth=readahead_depth,
                  readahead_max_bytes=readahead_max_bytes,
                  rowgroup_subset=rowgroup_subset,
                  row_materialization=row_materialization,
                  sample_order=sample_order,
                  shuffle_window=shuffle_window,
                  refresh_interval_s=refresh_interval_s,
                  timeline_interval_s=timeline_interval_s,
                  timeline_anomaly=timeline_anomaly,
                  quality=quality,
                  quality_config=quality_config,
                  reference_profile=reference_profile,
                  telemetry_publish=telemetry_publish,
                  tenant=tenant)


def make_batch_reader(dataset_url_or_urls,
                      schema_fields=None,
                      reader_pool_type: str = "thread",
                      workers_count: int = 4,
                      results_queue_size: int = 50,
                      shuffle_row_groups: bool = True,
                      shuffle_rows: bool = False,
                      shuffle_row_drop_partitions: int = 1,
                      predicate=None,
                      filters=None,
                      num_epochs: Optional[int] = 1,
                      cur_shard=None,
                      shard_count: Optional[int] = None,
                      shard_seed: Optional[int] = None,
                      seed: Optional[int] = None,
                      cache_type: str = "null",
                      cache_location: Optional[str] = None,
                      cache_size_limit: Optional[int] = None,
                      cache_row_size_estimate: Optional[int] = None,
                      cache_extra_settings: Optional[dict] = None,
                      transform_spec=None,
                      storage_options: Optional[dict] = None,
                      filesystem=None,
                      zmq_copy_buffers: bool = True,
                      convert_early_to_numpy: bool = False,
                      resume_state: Optional[dict] = None,
                      rowgroup_coalescing: int = 1,
                      pool_profiling_enabled: bool = False,
                      rowgroup_selector=None,
                      hdfs_driver: Optional[str] = None,
                      retry_policy=None,
                      degraded_mode: bool = False,
                      fault_plan=None,
                      worker_crash_budget: int = 0,
                      autotune: bool = False,
                      autotune_config=None,
                      memory_cache_size_bytes: Optional[int] = None,
                      stage_deadline_s=None,
                      hedge_policy=None,
                      hang_timeout_s: Optional[float] = None,
                      rowgroup_pruning: bool = True,
                      readahead_depth: Optional[int] = None,
                      readahead_max_bytes: Optional[int] = None,
                      serializer=None,
                      rowgroup_subset: Optional[Sequence[int]] = None,
                      sample_order: str = "free",
                      shuffle_window: int = 0,
                      refresh_interval_s: Optional[float] = None,
                      timeline_interval_s: Optional[float] = None,
                      timeline_anomaly: bool = True,
                      quality: bool = False,
                      quality_config=None,
                      reference_profile=None,
                      telemetry_publish: Optional[str] = None,
                      tenant: Optional[str] = None):
    """Columnar reader for **any** Parquet store (one numpy batch per row
    group; batch size = row-group size).

    ``schema_fields`` is a list of column names or name regexes.
    ``filters`` takes standard pyarrow partition-filter tuples (see
    :func:`make_reader`).
    ``convert_early_to_numpy`` moves the Arrow->numpy conversion into the
    workers (parity: reference reader.py:227, arrow_reader_worker.py:279) —
    useful when worker parallelism should absorb the conversion cost; the
    default converts at the consumer (zero-copy from shared memory on the
    process pool's shm transport).
    ``rowgroup_selector`` prunes row groups through stored inverted indexes
    exactly as in :func:`make_reader` (parity: reference reader.py:216).
    ``hdfs_driver`` is accepted for drop-in compatibility and ignored.
    ``retry_policy`` / ``degraded_mode`` / ``fault_plan`` /
    ``worker_crash_budget`` behave exactly as in :func:`make_reader`
    (see docs/resilience.md).
    ``autotune`` / ``autotune_config`` / ``memory_cache_size_bytes`` behave
    exactly as in :func:`make_reader` (see docs/autotune.md); the memory
    cache holds this reader's raw row-group tables — the columnar path has
    no codec decode to cache past.
    ``stage_deadline_s`` / ``hedge_policy`` / ``hang_timeout_s`` behave
    exactly as in :func:`make_reader` (docs/resilience.md § "Deadlines,
    hedging, and the watchdog").
    ``rowgroup_pruning`` / ``readahead_depth`` / ``readahead_max_bytes``
    behave exactly as in :func:`make_reader` (docs/io.md) — plain Parquet
    stores usually carry the richest column statistics, so this is the
    path pruning pays off most on.
    ``serializer`` is the escape hatch over the process-pool payload
    transport (docs/zero_copy.md): the default is
    :class:`~petastorm_tpu.reader_impl.arrow_table_serializer.
    ArrowTableSerializer` — columnar Arrow IPC the shm transport
    deserializes zero-copy — except with ``convert_early_to_numpy`` (numpy
    dicts need pickle). Pass
    :class:`~petastorm_tpu.reader_impl.pickle_serializer.PickleSerializer`
    to force the bytes round-trip (e.g. to A/B the transports, or for a
    custom worker payload Arrow IPC cannot carry); thread/dummy pools
    ignore it (nothing is serialized in-process).
    ``rowgroup_subset`` restricts the plan to explicit row-group ordinals
    in the given order, exactly as in :func:`make_reader` — the mesh
    ingestion layer's shard-plan/reshard mechanism (docs/mesh.md).
    ``sample_order`` / ``shuffle_window`` behave exactly as in
    :func:`make_reader` (docs/determinism.md): ``'deterministic'`` pins
    the delivered batch stream to ``f(seed, epoch_idx, shard_plan)``
    across every pool type, knob, fault, and resume point.
    ``refresh_interval_s`` enables live appending-dataset discovery
    exactly as in :func:`make_reader` (docs/live_data.md) — plain Parquet
    stores that other producers append to are the primary live-data
    shape.
    ``quality`` / ``quality_config`` / ``reference_profile`` attach the
    data-quality plane exactly as in :func:`make_reader`
    (docs/observability.md "Data quality plane") — batched readers
    profile the delivered column dicts directly, so this is the
    zero-overhead-iest surface for it.
    Parity: reference reader.py:209.
    """
    _warn_compat_kwargs(hdfs_driver, False)
    seed = _resolve_seed(seed, resume_state, shuffle_row_groups,
                         shuffle_rows, sample_order)
    ctx = DatasetContext(dataset_url_or_urls, storage_options=storage_options,
                         filesystem=filesystem)
    schema = infer_or_load_unischema(ctx)

    if isinstance(schema_fields, NGram):
        raise ValueError("NGram is not supported by make_batch_reader; use make_reader")

    # ---------------- plan lowering (docs/plan.md) — see make_reader.
    from petastorm_tpu.plan import lower_reader_kwargs
    plan = lower_reader_kwargs(
        "batch",
        {"dataset_url_or_urls": dataset_url_or_urls,
         "reader_pool_type": reader_pool_type,
         "workers_count": workers_count,
         "results_queue_size": results_queue_size,
         "shuffle_row_groups": shuffle_row_groups,
         "shuffle_rows": shuffle_rows,
         "shuffle_row_drop_partitions": shuffle_row_drop_partitions,
         "predicate": predicate, "transform_spec": transform_spec,
         "num_epochs": num_epochs,
         "cur_shard": cur_shard, "shard_seed": shard_seed, "seed": seed,
         "cache_type": cache_type, "cache_location": cache_location,
         "cache_size_limit": cache_size_limit,
         "memory_cache_size_bytes": memory_cache_size_bytes,
         "rowgroup_coalescing": rowgroup_coalescing,
         "zmq_copy_buffers": zmq_copy_buffers,
         "readahead_depth": readahead_depth,
         "readahead_max_bytes": readahead_max_bytes,
         "rowgroup_subset": rowgroup_subset,
         "convert_early_to_numpy": convert_early_to_numpy,
         "serializer": serializer,
         "sample_order": sample_order, "shuffle_window": shuffle_window,
         "refresh_interval_s": refresh_interval_s,
         "autotune": autotune, "autotune_config": autotune_config},
        schema_field_names=_fingerprint_fields(schema, schema_fields))
    reader_pool_type = plan.pool_type

    _warn_predicate_bypasses_cache(predicate, memory_cache_size_bytes)
    cache = _make_cache(cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings,
                        retry_policy=retry_policy, fault_plan=fault_plan,
                        memory_cache_size_bytes=memory_cache_size_bytes)

    from petastorm_tpu.reader_impl.pickle_serializer import PickleSerializer
    if serializer is None:
        if convert_early_to_numpy:
            # Workers publish numpy dicts, which Arrow IPC cannot carry.
            serializer = PickleSerializer()
        else:
            from petastorm_tpu.reader_impl.arrow_table_serializer import ArrowTableSerializer
            serializer = ArrowTableSerializer()
    elif convert_early_to_numpy and not isinstance(serializer,
                                                   PickleSerializer):
        raise ValueError(
            "convert_early_to_numpy publishes numpy dicts, which only the "
            "PickleSerializer can carry; drop serializer= or "
            "convert_early_to_numpy")
    def pool_factory(target):
        return _make_pool(target, workers_count, results_queue_size,
                          serializer, shuffle_rows, seed, zmq_copy_buffers,
                          pool_profiling_enabled,
                          ring_capacity=_ring_capacity_from_budget(
                              autotune_config, workers_count))
    # ONE construction path: the initial pool and any pool a placement
    # migration later builds go through the same factory.
    pool = pool_factory(reader_pool_type)

    return Reader(ctx, schema,
                  plan=plan,
                  pool_factory=pool_factory,
                  dataset_url_or_urls=dataset_url_or_urls,
                  schema_fields=schema_fields,
                  worker_class=BatchReaderWorker,
                  pool=pool,
                  is_batched_reader=True,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_rows=shuffle_rows,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate,
                  rowgroup_selector=rowgroup_selector,
                  num_epochs=num_epochs,
                  cur_shard=cur_shard,
                  shard_count=shard_count,
                  shard_seed=shard_seed,
                  seed=seed,
                  cache=cache,
                  transform_spec=transform_spec,
                  storage_options=storage_options,
                  resume_state=resume_state,
                  filters=filters,
                  filesystem=filesystem,
                  convert_early_to_numpy=convert_early_to_numpy,
                  rowgroup_coalescing=rowgroup_coalescing,
                  retry_policy=retry_policy,
                  degraded_mode=degraded_mode,
                  fault_plan=fault_plan,
                  worker_crash_budget=worker_crash_budget,
                  autotune=autotune,
                  autotune_config=autotune_config,
                  stage_deadline_s=stage_deadline_s,
                  hedge_policy=hedge_policy,
                  hang_timeout_s=hang_timeout_s,
                  rowgroup_pruning=rowgroup_pruning,
                  readahead_depth=readahead_depth,
                  readahead_max_bytes=readahead_max_bytes,
                  rowgroup_subset=rowgroup_subset,
                  sample_order=sample_order,
                  shuffle_window=shuffle_window,
                  refresh_interval_s=refresh_interval_s,
                  timeline_interval_s=timeline_interval_s,
                  timeline_anomaly=timeline_anomaly,
                  quality=quality,
                  quality_config=quality_config,
                  reference_profile=reference_profile,
                  telemetry_publish=telemetry_publish,
                  tenant=tenant)


class Reader:
    """Iterator over dataset samples. Context manager; supports ``reset()``
    after an epoch ends, ``stop()``/``join()`` for shutdown, and
    ``diagnostics`` for queue introspection."""

    def __init__(self, ctx: DatasetContext, stored_schema: Unischema, *,
                 dataset_url_or_urls, schema_fields, worker_class, pool,
                 is_batched_reader, shuffle_row_groups, shuffle_rows,
                 shuffle_row_drop_partitions, predicate, rowgroup_selector,
                 num_epochs, cur_shard, shard_count, shard_seed, seed, cache,
                 transform_spec, storage_options, resume_state=None,
                 filesystem=None, convert_early_to_numpy=False,
                 rowgroup_coalescing=1, filters=None, retry_policy=None,
                 degraded_mode=False, fault_plan=None, worker_crash_budget=0,
                 autotune=False, autotune_config=None, stage_deadline_s=None,
                 hedge_policy=None, hang_timeout_s=None,
                 rowgroup_pruning=True, readahead_depth=None,
                 readahead_max_bytes=None, pool_factory=None,
                 rowgroup_subset=None, row_materialization="eager",
                 sample_order="free", shuffle_window=0,
                 refresh_interval_s=None, timeline_interval_s=None,
                 timeline_anomaly=True, quality=False, quality_config=None,
                 reference_profile=None, telemetry_publish=None,
                 tenant=None, plan=None):
        self._ctx = ctx
        #: The lowered :class:`~petastorm_tpu.plan.PipelinePlan` this
        #: reader executes (docs/plan.md) — None for direct ``Reader(...)``
        #: constructions, which skip lowering (explain falls back to the
        #: live-graph builder and no fusion applies).
        self._plan = plan
        self._pool = pool
        self.is_batched_reader = is_batched_reader
        self.last_row_consumed = False
        self._error = None
        # Placement-migration plumbing (docs/zero_copy.md): the factory
        # rebuilds a pool of either flavor with this reader's construction
        # parameters; the pending target is flipped by the autotune
        # placement actuator and honored at the consumer-thread safe point
        # in __next__.
        self._pool_factory = pool_factory
        self._worker_class = worker_class
        self._worker_crash_budget = worker_crash_budget
        self._convert_early_to_numpy = convert_early_to_numpy
        self._pending_pool_target = None
        self._placement_actuator = None
        # A hard mid-migration failure poisons the reader: __next__
        # re-raises it instead of letting a stopped pool read as a clean,
        # silently-truncated epoch.
        self._migration_error = None
        # One registry covers the whole pipeline: the pool's worker decode
        # timings, the ventilator backlog gauge, this reader's pool-wait
        # histogram, and (when a JAX loader consumes this reader) the
        # loader's staging/stall metrics all land here. See
        # docs/observability.md for the metric schema.
        self.telemetry = make_registry()
        self._telemetry_exporter = None
        # Ops plane (docs/observability.md "Ops plane"): rolling timeline +
        # anomaly monitor + postmortem black box, armed further down once
        # the pool exists (their collectors read pool state).
        self._timeline = None
        self._timeline_sampler = None
        self.anomaly_monitor = None
        self.blackbox = None

        # ---------------- plan-time validation (docs/plan.md): the one
        # consolidated mutual-exclusion pass. make_* already ran it inside
        # lowering; direct Reader(...) constructions get the same rules
        # (and the same messages) here.
        from petastorm_tpu.plan import validate_reader_config
        _validation_cfg = {
            "rowgroup_subset": rowgroup_subset, "cur_shard": cur_shard,
            "shuffle_row_groups": shuffle_row_groups,
            "refresh_interval_s": refresh_interval_s,
            "shard_seed": shard_seed, "sample_order": sample_order,
            "shuffle_window": shuffle_window,
        }
        if not is_batched_reader:
            _validation_cfg["row_materialization"] = row_materialization
        validate_reader_config(_validation_cfg)

        # ---------------- deterministic epoch plane (docs/determinism.md)
        shuffle_window = int(shuffle_window or 0)
        #: ``'free'`` or ``'deterministic'`` — the delivery-order contract
        #: this reader runs under (docs/determinism.md).
        self.sample_order = sample_order
        self._shuffle_window = shuffle_window
        # Defensive re-resolution for direct Reader(...) constructions (the
        # make_* entry points already resolved before building the pool).
        if seed is None:
            seed = _resolve_seed(seed, resume_state, shuffle_row_groups,
                                 shuffle_rows, sample_order)
        self._seed = seed

        cur_shard, shard_count = _resolve_shard(cur_shard, shard_count)
        if (cur_shard is None) != (shard_count is None):
            raise ValueError("cur_shard and shard_count must be used together")
        if cur_shard is not None and not (0 <= cur_shard < shard_count):
            raise ValueError(f"cur_shard {cur_shard} out of range [0, {shard_count})")
        # (rowgroup_subset x cur_shard / x shuffle_row_groups conflicts:
        # raised by the consolidated plan-time validation pass above.)

        # ---------------- schema views
        self.ngram: Optional[NGram] = None
        if isinstance(schema_fields, NGram):
            self.ngram = schema_fields
            self.ngram.resolve_regex_field_names(stored_schema)
            view_schema = stored_schema
        elif schema_fields is not None:
            view_schema = stored_schema.create_schema_view(schema_fields)
        else:
            view_schema = stored_schema

        if self.ngram is not None and not self.ngram.timestamp_overlap \
                and shuffle_row_drop_partitions > 1:
            raise NotImplementedError("shuffle_row_drop_partitions with "
                                      "non-overlapping ngrams is not supported")

        self._stored_schema = stored_schema
        if transform_spec is not None:
            self.schema = transform_schema(view_schema, transform_spec)
        else:
            self.schema = view_schema

        # ---------------- batch-native plane (docs/io.md)
        #: ``'lazy'`` when workers publish columnar batches and rows are
        #: views (``make_reader(row_materialization=...)``); always
        #: ``'eager'`` for batched readers (their payload is already a
        #: whole columnar row group — :meth:`next_batch` works either way).
        self.row_materialization = "eager"
        if not is_batched_reader:
            if row_materialization == "lazy":
                if self.ngram is not None:
                    warnings.warn(
                        "row_materialization='lazy' does not apply to NGram "
                        "readers (windows are assembled per sample); "
                        "falling back to eager")
                elif (transform_spec is not None
                      and transform_spec.func is not None
                      and not getattr(transform_spec, "batched", False)):
                    warnings.warn(
                        "row_materialization='lazy' needs a batch-native "
                        "TransformSpec (batched=True, columns in/columns "
                        "out) — a per-row func forces per-row "
                        "materialization; falling back to eager")
                else:
                    self.row_materialization = "lazy"

        # ---------------- live appending datasets (docs/live_data.md)
        if refresh_interval_s is not None:
            if refresh_interval_s < 0:
                raise ValueError(f"refresh_interval_s must be >= 0, "
                                 f"got {refresh_interval_s}")
            # (refresh x rowgroup_subset / x shard_seed conflicts: raised
            # by the consolidated plan-time validation pass above.)
            if ctx.is_multi_path:
                raise ValueError(
                    "refresh_interval_s needs a single dataset root to "
                    "watch; multi-URL views enumerate a fixed file list")
        self._refresh_interval_s = refresh_interval_s
        #: Background :class:`~petastorm_tpu.discovery.DatasetWatcher`
        #: when ``refresh_interval_s`` is set (built after the resilience
        #: wiring below — admission shares the reader's quarantine).
        self._discovery = None
        #: Applied growth batches: {"epoch", "files", "items", ...} each.
        self._growth_batches: list = []
        self._base_manifest = None
        self._live_plan = None

        # ---------------- row-group planning
        #: Plan-time pruning provenance — filled by the selector pass and
        #: the statistics pruner below; see :meth:`pruning_report`.
        self._pruning_report = {"enabled": False}
        #: Per-column aggregate of the footer ColumnStats the pruning scan
        #: harvests (retained instead of dropped — the quality plane's
        #: zero-IO seed; see :meth:`_fold_plan_column_stats`).
        self._plan_column_stats: dict = {}
        self._subset_kept_ordinals = None
        resume_manifest = (resume_state.get("manifest")
                           if isinstance(resume_state, dict) else None)
        if resume_manifest:
            # Live-data resume (docs/live_data.md): the cursor's manifest —
            # not the (sorted, growth-unstable) listing — defines the base
            # ordinal assignment; growth batches are replayed below at
            # their recorded epochs, so the restored plan is the exact plan
            # the cursor indexed.
            if shard_seed is not None:
                raise ValueError(
                    "a live-data manifest cursor cannot resume with "
                    "shard_seed (the shard stream must extend "
                    "monotonically; docs/live_data.md)")
            from petastorm_tpu.discovery import DatasetSnapshot
            base_snapshot = DatasetSnapshot.from_manifest(
                resume_manifest["base"], ctx.root_path)
            all_row_groups = base_snapshot.row_group_refs(ctx)
        else:
            base_snapshot = None
            all_row_groups = load_row_groups(ctx)
        filtered = self._filter_row_groups(all_row_groups, predicate,
                                           rowgroup_selector, cur_shard,
                                           shard_count, shard_seed,
                                           filters=filters,
                                           rowgroup_subset=rowgroup_subset)
        if not filtered:
            raise NoDataAvailableError(
                "No row groups left after predicate/selector/shard filtering. "
                f"(dataset has {len(all_row_groups)} row groups; "
                f"cur_shard={cur_shard}, shard_count={shard_count})")
        logger.debug("Reading %d/%d row groups", len(filtered), len(all_row_groups))

        # Trace identities (docs/observability.md "Trace plane"): every
        # planned row group gets a stable lineage ordinal — the
        # dataset-global ordinal when the plan came from rowgroup_subset
        # (so mesh pull spans and per-host reader spans agree), the plan
        # position otherwise. Keyed by (path, row_group) because the
        # ventilator shuffles item ORDER per epoch; coalesced work items
        # (tuple row_group keys) fall back to their epoch position.
        self._trace_ordinal_by_key = {
            (rg.path, rg.row_group):
                (self._subset_kept_ordinals[i]
                 if self._subset_kept_ordinals is not None else i)
            for i, rg in enumerate(filtered)}
        #: Next trace/lineage ordinal a growth batch's groups start at.
        self._trace_next_ordinal = len(filtered)

        # ---------------- statistics pruning (docs/io.md). AFTER sharding,
        # so shard membership — and therefore which host owns which
        # surviving rows — is identical with pruning on or off, and each
        # shard only reads statistics for its own files. Pruning to an
        # EMPTY plan is legal (the predicate provably matches nothing):
        # that is an empty epoch, exactly what fetch-then-filter would
        # have yielded, not a configuration error.
        self._pruning_report.update({"row_groups_planned": len(filtered),
                                     "row_groups_pruned": 0,
                                     "row_groups_kept": len(filtered)})
        if rowgroup_pruning and predicate is not None:
            filtered = self._prune_row_groups_with_statistics(filtered,
                                                              predicate)

        if rowgroup_coalescing > 1:
            filtered = _coalesce_row_groups(filtered, rowgroup_coalescing)

        # ---------------- ventilation items
        items = []
        for rg in filtered:
            for part in range(shuffle_row_drop_partitions):
                items.append({"rowgroup": rg,
                              "shuffle_row_drop_partition": (part, shuffle_row_drop_partitions)})

        # ---------------- live growth plan state (docs/live_data.md)
        # Captured whenever discovery (or a manifest resume) is in play:
        # growth batches replay the SAME filter/shard/prune/coalesce
        # pipeline the base plan went through, with the shard stream
        # continuing where the base left off.
        base_items_count = len(items)
        growth_segments = None
        if refresh_interval_s is not None or resume_manifest:
            from petastorm_tpu.discovery import DatasetSnapshot
            if base_snapshot is None:
                base_snapshot = DatasetSnapshot.from_row_groups(
                    all_row_groups)
            self._base_snapshot = base_snapshot
            self._base_manifest = base_snapshot.manifest(ctx.root_path)
            self._live_plan = {
                "filters": filters, "predicate": predicate,
                "cur_shard": cur_shard, "shard_count": shard_count,
                "pruning": rowgroup_pruning,
                "coalescing": rowgroup_coalescing,
                "drop_partitions": shuffle_row_drop_partitions,
                "selector": rowgroup_selector,
            }
            if rowgroup_selector is not None:
                _warn_once(
                    "refresh_selector",
                    "rowgroup_selector indexes are stored at write time "
                    "and cannot cover appended files; discovery admits "
                    "new files WITHOUT selector pruning "
                    "(docs/live_data.md)")
            if resume_manifest and resume_manifest.get("growth"):
                segments = [(0, base_items_count)]
                for batch in resume_manifest["growth"]:
                    files = [(os.path.join(ctx.root_path, rel), int(n), None)
                             for rel, n in batch["files"]]
                    new_items, info = self._plan_growth_batch(files)
                    if len(new_items) != int(batch["items"]):
                        raise ValueError(
                            f"live-data resume planned {len(new_items)} "
                            f"work item(s) for the growth batch at epoch "
                            f"{batch['epoch']} but the cursor recorded "
                            f"{batch['items']} — the appended files (or "
                            f"predicate/filters) changed since the "
                            f"checkpoint")
                    items.extend(new_items)
                    epoch_from = int(batch["epoch"])
                    if segments[-1][0] == epoch_from:
                        segments[-1] = (epoch_from, len(items))
                    else:
                        segments.append((epoch_from, len(items)))
                    self._growth_batches.append(
                        {"epoch": epoch_from,
                         "files": [[r, int(n)] for r, n in batch["files"]],
                         "items": len(new_items), **info})
                if len(segments) > 1:
                    growth_segments = segments

        # A live filesystem handle is only shared with in-process workers;
        # spawned process workers rebuild from URL + storage_options (live
        # connections/locks don't survive the boundary — factory semantics,
        # like the reference's filesystem_factory; the nulling itself
        # happens in _spawnable_worker_args).
        if filesystem is not None and isinstance(self._pool, ProcessPool):
            warnings.warn("reader_pool_type='process' workers reconnect from the "
                          "dataset URL; the custom filesystem object is used for "
                          "planning only. Pass storage_options for credentials.")
        self._cache = cache

        # ---------------- memory-cache wiring (docs/autotune.md)
        from petastorm_tpu.autotune import InMemoryRowGroupCache
        if isinstance(cache, InMemoryRowGroupCache):
            if isinstance(self._pool, ProcessPool):
                # The cache pickles as an EMPTY per-worker cache (live
                # entries and telemetry cannot cross the spawn boundary), so
                # each spawned worker holds a private budget of the full
                # configured size over its own round-robin item subset.
                _warn_once(
                    "memory_cache_size_bytes",
                    "memory_cache_size_bytes with reader_pool_type='process' "
                    "keeps a PRIVATE cache of that size in every spawned "
                    f"worker: up to {self._pool.workers_count}x the "
                    "configured bytes host-wide. Size accordingly, or use "
                    "the thread pool to share one cache.")
            else:
                # In-process pools share this one instance with every
                # worker: hits/misses/evictions land on the pipeline
                # registry.
                cache.attach_telemetry(self.telemetry)

        # ---------------- async readahead (docs/io.md)
        #: Background :class:`~petastorm_tpu.reader_impl.readahead.
        #: ReadaheadFetcher` when ``readahead_depth`` is set (else None):
        #: fetches row-group Arrow tables ahead of the decode workers.
        self.readahead = None
        if readahead_depth:
            if readahead_depth < 0:
                raise ValueError(f"readahead_depth must be >= 1, "
                                 f"got {readahead_depth}")
            if isinstance(self._pool, ProcessPool):
                # The fetched-table store is shared memory; it cannot cross
                # the spawn boundary (spawned workers already overlap IO
                # against their sibling processes).
                _warn_once("readahead_depth",
                           "readahead_depth only applies to in-process "
                           "pools (reader_pool_type='thread'/'dummy'); "
                           "ignored for the process pool")
            else:
                from petastorm_tpu.autotune import MemoryBudget
                from petastorm_tpu.reader_impl.readahead import \
                    ReadaheadFetcher
                # One fetch covers every column any worker request will
                # slice: the schema view (all NGram timesteps when
                # windowed) plus the predicate's fields.
                if self.ngram is not None:
                    fetch_columns = set(
                        self.ngram.get_field_names_at_all_timesteps())
                else:
                    fetch_columns = set(view_schema.fields.keys())
                if predicate is not None:
                    fetch_columns |= set(predicate.get_fields())
                self.readahead = ReadaheadFetcher(
                    ctx.filesystem, fetch_columns,
                    depth=int(readahead_depth),
                    budget=MemoryBudget(readahead_max_bytes or (256 << 20)),
                    fault_plan=fault_plan, hedge_policy=hedge_policy,
                    telemetry=self.telemetry,
                    # Announcement backstop: normal flow is bounded by the
                    # ventilator's in-flight cap; size for its autotuned
                    # ceiling (4x) so a consumer that stops popping (warm
                    # cache epochs) can't accumulate submissions forever.
                    max_queue=4 * self._pool.workers_count
                    * (1 + _VENTILATE_EXTRA_ROWGROUPS))

        # ---------------- resilience wiring (docs/resilience.md)
        from petastorm_tpu.resilience import (CancellationToken, HedgePolicy,
                                              RowGroupQuarantine,
                                              StageDeadline,
                                              WorkerCrashRecovery)
        #: Consumer-side aggregator of degraded-mode skip records; query via
        #: :meth:`quarantine_report`. Attached to every pool type.
        self.quarantine = RowGroupQuarantine(telemetry=self.telemetry)
        self._pool.quarantine = self.quarantine
        #: Lazily-built random-access plane (docs/random_access.md):
        #: constructed by the first :meth:`lookup` / :meth:`dataset_view`
        #: from the dataset's persisted field-index sidecar; shares this
        #: reader's decoded cache, quarantine aggregator, and telemetry.
        self._lookup_plane = None
        if worker_crash_budget:
            if isinstance(self._pool, ProcessPool):
                self._pool.recovery = WorkerCrashRecovery(
                    worker_crash_budget, telemetry=self.telemetry)
            else:
                # In-process workers can't die independently of the trainer;
                # a crash budget only means something for spawned processes.
                warnings.warn("worker_crash_budget only applies to "
                              "reader_pool_type='process'; ignored")

        # ---------------- straggler & hang defense (docs/resilience.md)
        stage_deadline = StageDeadline.from_arg(stage_deadline_s)
        self._stage_deadline = stage_deadline
        if hedge_policy is not None and not isinstance(hedge_policy,
                                                       HedgePolicy):
            raise TypeError(
                f"hedge_policy must be a petastorm_tpu.resilience."
                f"HedgePolicy (or None), got {type(hedge_policy).__name__}")
        if hang_timeout_s is not None and hang_timeout_s <= 0:
            raise ValueError(f"hang_timeout_s must be positive, "
                             f"got {hang_timeout_s}")
        # One shared cancel token covers in-process workers: deadline
        # checkpoints consult it, the watchdog's cancel rung requests it.
        # Spawned workers get None — there is no cross-process flag to
        # flip; the watchdog escalates to the crash-recovery kill there.
        self._cancel_token = (
            CancellationToken()
            if (stage_deadline is not None or hang_timeout_s is not None)
            and not isinstance(self._pool, ProcessPool) else None)
        if hasattr(self._pool, "stage_deadline"):
            # Thread/dummy pools also account whole-item soft overruns
            # (decode + publish backpressure) on top of the workers'
            # per-attempt enforcement.
            self._pool.stage_deadline = stage_deadline

        # ---------------- data-quality plane (docs/observability.md
        # "Data quality plane"): streaming column profiles + drift scoring
        # + coverage auditing. Observation happens at the consumer
        # delivery point (the results readers below) — pool-agnostic and
        # migration-safe; the coverage ledger attaches to the ordered
        # gate (exact per-ordinal audit) or counts units in free mode.
        #: :class:`~petastorm_tpu.quality.QualityMonitor` when the plane
        #: is enabled (``quality=`` / ``quality_config=`` /
        #: ``reference_profile=``), else None.
        self.quality_monitor = None
        if quality or quality_config is not None \
                or reference_profile is not None:
            from petastorm_tpu.quality import QualityConfig, QualityMonitor
            if quality_config is not None \
                    and not isinstance(quality_config, QualityConfig):
                raise TypeError(
                    f"quality_config must be a petastorm_tpu.quality."
                    f"QualityConfig (or None), got "
                    f"{type(quality_config).__name__}")
            if self.ngram is not None:
                warnings.warn(
                    "quality profiling does not apply to NGram readers "
                    "(windows are views over rows other units profile); "
                    "unit counters still run, column profiles stay empty")
            self.quality_monitor = QualityMonitor(
                quality_config, telemetry=self.telemetry,
                reference=reference_profile,
                stats_seed=self._plan_column_stats)
            self.telemetry.quality = self._quality_payload

        # ---------------- live discovery wiring (docs/live_data.md)
        if refresh_interval_s is not None:
            from petastorm_tpu.discovery import DatasetWatcher
            from petastorm_tpu.discovery.listing import \
                DEFAULT_LIST_DEADLINE
            # The watcher's snapshot must cover everything already in the
            # plan: the base files plus any growth batches a manifest
            # resume replayed above.
            watch_snapshot = self._base_snapshot
            for batch in self._growth_batches:
                watch_snapshot = watch_snapshot.extended(
                    [(os.path.join(ctx.root_path, rel), n, 0.0, -1)
                     for rel, n in batch["files"]])
            try:
                reference_schema = ctx.arrow_schema()
            except Exception as e:  # noqa: BLE001 - drift check is best-effort
                reference_schema = None
                warnings.warn(f"live discovery could not resolve the "
                              f"dataset's Arrow schema ({e!r}); appended "
                              f"files will be admitted without schema-"
                              f"drift classification")
            stats_cols = set()
            if rowgroup_pruning and predicate is not None \
                    and hasattr(predicate, "intervals"):
                constraints = predicate.intervals()
                if constraints:
                    stats_cols = {f for f, _ in constraints}
            if self.quality_monitor is not None:
                # Admission scoring reads the SAME validation footer the
                # watcher already parses: harvest stats for every planned
                # column so a new file can be scored against the
                # reference at zero extra IO (docs/observability.md
                # "Data quality plane").
                stats_cols |= set(view_schema.fields.keys())
            self._discovery = DatasetWatcher(
                ctx, base_snapshot=watch_snapshot,
                reference_schema=reference_schema,
                poll_interval_s=(refresh_interval_s
                                 if refresh_interval_s > 0 else None),
                retry_policy=retry_policy,
                deadline=(stage_deadline if stage_deadline is not None
                          else DEFAULT_LIST_DEADLINE),
                fault_plan=fault_plan, telemetry=self.telemetry,
                quarantine=self.quarantine,
                stats_columns=sorted(stats_cols),
                quality_scorer=(
                    None if self.quality_monitor is None
                    else self.quality_monitor.score_admitted_file))
            if refresh_interval_s > 0:
                self._discovery.start()

        # Built as the IN-PROCESS variant; _spawnable_worker_args derives
        # the process-pool copy (live handles nulled). Both kept on self so
        # a placement migration (docs/zero_copy.md) can stand up either
        # pool flavor mid-flight.
        self._worker_args_inproc = {
            "dataset_url_or_urls": dataset_url_or_urls,
            "storage_options": storage_options,
            "filesystem": filesystem,
            "schema": stored_schema,
            "view_schema": view_schema,
            "output_schema": self.schema,
            "ngram": self.ngram,
            "predicate": predicate,
            "transform_spec": transform_spec,
            "cache": cache,
            "shuffle_rows": shuffle_rows,
            "seed": seed,
            "convert_early_to_numpy": convert_early_to_numpy,
            "retry_policy": retry_policy,
            "degraded_mode": degraded_mode,
            "fault_plan": fault_plan,
            # Straggler defense: the deadline/hedge policies are picklable
            # values (spawned workers enforce them in-process); the cancel
            # token is in-process only (None for process pools).
            "stage_deadline": stage_deadline,
            "hedge_policy": hedge_policy,
            "cancel_token": self._cancel_token,
            # In-process-only shared fetch stage (None for spawned
            # workers; see the readahead block above).
            "readahead": self.readahead,
            "resilience_telemetry": self.telemetry,
            # Batch-native plane: lazy workers publish ColumnarBatch
            # payloads (docs/io.md); validated above.
            "row_materialization": self.row_materialization,
            # Deterministic plane: workers publish one OrderedUnit envelope
            # per work item (docs/determinism.md).
            "sample_order": sample_order,
            # Data-quality plane: in-process workers publish predicate
            # selectivity telemetry (quality.predicate.*) when enabled —
            # the one quality signal only the workers can see (rows the
            # mask dropped never reach the consumer).
            "quality": self.quality_monitor is not None,
            # Plan fusions (docs/plan.md "Fusion rules"): the byte-identity
            # -gated operator fusions the lowered plan applied. The
            # decode->transport fusion only holds while decode runs
            # in-process; _spawnable_worker_args strips it.
            "plan_fusions": (self._plan.fusion_names()
                             if self._plan is not None else frozenset()),
        }
        worker_args = (self._spawnable_worker_args()
                       if isinstance(self._pool, ProcessPool)
                       else self._worker_args_inproc)

        if is_batched_reader and not convert_early_to_numpy \
                and hasattr(self._pool, "result_transform"):
            # Process pool: convert Arrow -> numpy inside the poll, as VIEWS
            # over the transport's Arrow buffers (no defensive copy). On the
            # shm ring the pool's segment-claim protocol pins the record
            # until the consumer drops its last view; on ZMQ the frame's own
            # refcount keeps the buffer alive (docs/zero_copy.md).
            from functools import partial as _partial
            self._pool.result_transform = _partial(arrow_table_to_numpy_dict,
                                                   schema=self.schema,
                                                   force_copy=False)

        start_epoch, start_offset = 0, 0
        resume_window_k, resume_skips = 0, ()
        if resume_state is not None:
            if shuffle_row_groups and seed is None:
                # Reached only when the RESTORED state lacks a recorded
                # seed (pre-seeded-by-default checkpoints, hand-built
                # dicts): a fresh reader auto-mints and records one, so
                # resume-by-default holds for every state_dict() saved
                # since (docs/determinism.md).
                raise ValueError(
                    "Exact resume requires a seed when shuffle_row_groups is on "
                    "(the epoch permutation must be reproducible) — this "
                    "resume_state records none. States saved by "
                    "state_dict() carry their auto-minted seed.")
            saved_seed = resume_state.get("seed")
            if saved_seed is not None and seed is not None \
                    and int(saved_seed) != int(seed) \
                    and (shuffle_row_groups or shuffle_rows
                         or sample_order == "deterministic"):
                raise ValueError(
                    f"resume_state was saved under seed {saved_seed} but "
                    f"this reader shuffles with seed {seed} — the offset "
                    f"would point into a different permutation")
            saved_order = resume_state.get("sample_order")
            if saved_order is not None and saved_order != sample_order:
                raise ValueError(
                    f"resume_state was saved with sample_order="
                    f"{saved_order!r} but this reader runs "
                    f"{sample_order!r}; the cursors do not transfer")
            saved_window = resume_state.get("window")
            if saved_window is not None \
                    and int(saved_window) != shuffle_window:
                raise ValueError(
                    f"resume_state was saved with shuffle_window="
                    f"{saved_window} but this reader uses {shuffle_window}; "
                    f"the in-window position would index a different "
                    f"block permutation")
            saved_plan = resume_state.get("plan")
            if saved_plan is not None \
                    and bool(saved_plan.get("shuffled")) \
                    != bool(shuffle_row_groups):
                raise ValueError(
                    f"resume_state was saved with shuffle_row_groups="
                    f"{bool(saved_plan.get('shuffled'))} but this reader "
                    f"uses {bool(shuffle_row_groups)} — the offset would "
                    f"index a different permutation")
            saved_items = resume_state.get("items")
            if saved_items is not None and int(saved_items) != len(items):
                raise ValueError(
                    f"resume_state was saved over {saved_items} work items but "
                    f"this reader plans {len(items)} — the offset would point "
                    "at different data. Resume with the same dataset, filters, "
                    "sharding, shuffle_row_drop_partitions and "
                    "rowgroup_coalescing as the saved run.")
            start_epoch = int(resume_state.get("epoch", 0))
            start_offset = int(resume_state.get("offset", 0))
            resume_window_k = int(resume_state.get("window_delivered", 0))
            resume_skips = resume_state.get("skipped_ordinals", ())
            if start_offset >= len(items):
                raise ValueError(f"resume offset {start_offset} >= {len(items)} work items "
                                 "(did the dataset or its filtering change?)")
            if shuffle_window > 1 and start_offset % shuffle_window:
                # Windowed cursors always record block starts; a misaligned
                # offset (a free-mode or hand-built cursor) would make the
                # gate demand plan positions BEFORE the ventilation restart
                # — an unfillable wait, not a resumable stream.
                raise ValueError(
                    f"resume offset {start_offset} is not aligned to "
                    f"shuffle_window={shuffle_window}: windowed cursors "
                    f"record window-block starts; this state was not saved "
                    f"by a shuffle_window={shuffle_window} reader")
        self._num_items = len(items)

        #: The canonical epoch plan + order-restoring gate (deterministic
        #: mode only; docs/determinism.md). The gate sits between
        #: ``pool.get_results()`` and the results reader; its cursor — not
        #: the ventilator watermark — is this reader's checkpoint.
        self._epoch_plan = None
        self._gate = None
        if sample_order == "deterministic":
            from petastorm_tpu.reader_impl.epoch_plan import (
                EpochPlan, OrderedDeliveryGate)
            self._epoch_plan = EpochPlan(seed=seed,  # operator-ok: the canonical plan the ventilate/order operators execute, not an operator
                                         num_items=base_items_count,
                                         shuffled=shuffle_row_groups,
                                         window=shuffle_window,
                                         growth=(growth_segments[1:]
                                                 if growth_segments else ()))
            quality_ledger = None
            if self.quality_monitor is not None:
                # Exact per-ordinal coverage audit: the gate accounts
                # every plan position as delivered/empty/skip and every
                # dropped duplicate (docs/observability.md "Data quality
                # plane").
                from petastorm_tpu.quality import CoverageLedger
                quality_ledger = CoverageLedger(plan=self._epoch_plan,
                                                telemetry=self.telemetry)
                self.quality_monitor.ledger = quality_ledger
            self._gate = OrderedDeliveryGate(
                self._epoch_plan, start_epoch=start_epoch,
                start_offset=start_offset,
                window_delivered=resume_window_k, skipped=resume_skips,
                telemetry=self.telemetry, ledger=quality_ledger)
            self.telemetry.gauge("order.buffer_depth",
                                 lambda: self._gate.buffered_count)
        elif self.quality_monitor is not None:
            # Free order: no consumer-side ordinals — unit-count audit
            # (a lower bound that still catches silent truncation).
            from petastorm_tpu.quality import CoverageLedger
            self.quality_monitor.ledger = CoverageLedger(
                num_items=self._num_items, num_epochs=num_epochs,
                telemetry=self.telemetry)
        self._ventilator = ConcurrentVentilator(
            self._make_ventilate_fn(self._pool), items,
            iterations=num_epochs,
            randomize_item_order=shuffle_row_groups,
            random_seed=seed,
            max_ventilation_queue_size=self._pool.workers_count * (1 + _VENTILATE_EXTRA_ROWGROUPS),
            start_epoch=start_epoch,
            start_offset=start_offset,
            growth_segments=growth_segments,
            # Workers key intra-row-group shuffle RNG by (seed, epoch,
            # position) so a resumed run replays the same row order inside
            # each group as an uninterrupted one; pools echo the same context
            # in processed markers for the exact-resume watermark.
            item_context_key=ITEM_CONTEXT_KWARG)
        # Queue gauges: sampled lazily at snapshot time, so they cost nothing
        # on the hot path. The pool gets the shared registry BEFORE start()
        # so worker threads can publish in-worker decode timings.
        self.telemetry.gauge("ventilator.backlog",
                             lambda: self._ventilator.inflight)
        self.telemetry.gauge("ventilator.max_inflight",
                             lambda: self._ventilator.max_inflight)
        # Item-accounting carried across placement migrations: pool
        # counters restart from zero in a freshly built pool, so
        # ``Reader.diagnostics`` adds the retired pools' final tallies —
        # a dashboard's ventilated/processed series must stay monotonic
        # through a mid-epoch backend swap (docs/zero_copy.md).
        self._pool_items_base = {"items_ventilated": 0,
                                 "items_processed": 0}
        # Guards the (base, live pool) pair: a migration retires the old
        # pool's tallies into the base and swaps self._pool under this
        # lock, so a concurrent diagnostics() poll can never see the same
        # items counted in both (or in neither).
        self._diag_lock = threading.Lock()
        self._sync_pool_gauges(self._pool)
        self.telemetry.counter("reader.rows")
        self._pool.telemetry = self.telemetry

        # ---------------- autotune wiring (docs/autotune.md)
        #: Background :class:`~petastorm_tpu.autotune.AutotuneController`
        #: when ``autotune=True`` (else None). A JAX loader consuming this
        #: reader registers its prefetch/shuffle knobs here, so ONE feedback
        #: loop sees the whole pipeline.
        self.autotune = None
        if autotune:
            from petastorm_tpu.autotune import (AutotuneController,
                                                VentilatorDepthActuator,
                                                WorkerConcurrencyActuator)
            # The memory cache's PRIVATE budget is deliberately NOT the
            # controller's pressure signal: an LRU cache sits at ~100% of
            # its byte budget in steady state by design, which would read
            # as permanent memory_pressure and throttle every knob to its
            # floor. memory_pressure engages only against an explicit
            # host-payload allowance (AutotuneConfig.memory_budget_bytes):
            # one shared ledger the cache charges, sized above the cache
            # limit so crossing the watermark means the PIPELINE is eating
            # into headroom, not that the cache is healthy-full.
            budget = None
            budget_bytes = getattr(autotune_config, "memory_budget_bytes",
                                   None)
            if budget_bytes:
                from petastorm_tpu.autotune import MemoryBudget
                budget = MemoryBudget(budget_bytes, telemetry=self.telemetry)
                if isinstance(cache, InMemoryRowGroupCache):
                    # Before any fill: repoint the cache's accounting at
                    # the shared ledger (its size_limit still caps it).
                    cache.budget = budget
                if self.readahead is not None:
                    # Same move for the fetch stage: before any fetch,
                    # charge the one shared ledger so readahead backs off
                    # when the PIPELINE is eating into host headroom.
                    self.readahead.budget = budget
            self.autotune = AutotuneController(self.telemetry,
                                               autotune_config,
                                               budget=budget)
            gate = getattr(self._pool, "concurrency_gate", None)
            if gate is not None:
                self.autotune.register(WorkerConcurrencyActuator(
                    gate, self._pool.workers_count))
            self.autotune.register(VentilatorDepthActuator(self._ventilator))
            if self.readahead is not None:
                from petastorm_tpu.autotune import ReadaheadDepthActuator
                self.autotune.register(ReadaheadDepthActuator(self.readahead))
            persisted_plan = (self._plan is not None
                              and self._plan.source == "persisted")
            if getattr(autotune_config, "placement", False) \
                    and not persisted_plan:
                # Cedar-style placement tuning (docs/zero_copy.md): only
                # when a migration can actually be performed — a factory
                # exists, the pool is a migratable flavor, and no
                # in-process-only machinery (readahead fetch stage,
                # watchdog) is welded to the current pool.
                migratable = (
                    self._pool_factory is not None
                    and isinstance(self._pool, (ThreadPool, ProcessPool))
                    and self.readahead is None
                    and hang_timeout_s is None)
                if migratable:
                    from petastorm_tpu.autotune import PlacementActuator
                    self._placement_actuator = self.autotune.register(
                        PlacementActuator(
                            self._request_pool_migration,
                            "process" if isinstance(self._pool, ProcessPool)
                            else "thread"))
                    # When this run's trial resolves, the verdict persists
                    # to the plan cache so the NEXT start skips the trial
                    # (docs/plan.md "Plan cache").
                    self.autotune.on_placement_resolved = \
                        self._on_placement_resolved
                else:
                    warnings.warn(
                        "autotune_config.placement=True ignored: placement "
                        "migration needs a thread/process pool without "
                        "readahead_depth or hang_timeout_s")
            elif persisted_plan:
                # Warm start (docs/plan.md): the pool was CONSTRUCTED on
                # the persisted winner; pin the placement knob so no trial
                # window ever opens, and seed the registered actuators
                # with the persisted run's converged values (clamped by
                # each actuator's own safe range).
                self.autotune.pin_placement(
                    {"verdict": "persisted",
                     "backend": self._plan.pool_type,
                     "trial": self._plan.trial})
                for name, value in (self._plan.capacity_seeds.get(
                        "actuators") or {}).items():
                    seeded = self.autotune.actuator(name)
                    if seeded is not None:
                        seeded.set(value)
            self.autotune.start()

        if self.readahead is not None:
            self.readahead.start()
        self._pool.start(worker_class, worker_args, ventilator=self._ventilator)

        # ---------------- watchdog (docs/resilience.md)
        #: Background :class:`~petastorm_tpu.resilience.PipelineWatchdog`
        #: when ``hang_timeout_s`` is set (else None). The pool-wait timer
        #: below reports consumer starvation to it; see
        #: :meth:`watchdog_report`.
        self.watchdog = None
        if hang_timeout_s is not None:
            from petastorm_tpu.resilience import PipelineWatchdog
            self.watchdog = PipelineWatchdog(
                self._pool, ventilator=self._ventilator,
                telemetry=self.telemetry, hang_timeout_s=hang_timeout_s,
                recovery=getattr(self._pool, "recovery", None),
                cancel_token=self._cancel_token).start()

        if is_batched_reader:
            self._results_reader = _BatchResultsReader(self._pool, self.schema,
                                                       telemetry=self.telemetry,
                                                       watchdog=self.watchdog,
                                                       gate=self._gate,
                                                       quality=self.quality_monitor)
        else:
            self._results_reader = _RowResultsReader(self._pool, self.schema,
                                                     self.ngram,
                                                     telemetry=self.telemetry,
                                                     watchdog=self.watchdog,
                                                     gate=self._gate,
                                                     quality=self.quality_monitor)

        export_path = os.environ.get(TELEMETRY_EXPORT_ENV)
        if export_path:
            self._telemetry_exporter = PeriodicExporter(
                self.telemetry, export_path,
                fmt=("prometheus" if export_path.endswith(".prom")
                     else "json")).start()

        # ---------------- SLO watch (docs/observability.md "SLO watch")
        #: Background :class:`~petastorm_tpu.telemetry.slo.SloWatcher`
        #: when :data:`~petastorm_tpu.telemetry.SLO_WATCH_ENV` is set
        #: (``1`` = default rules, else a ``parse_rules`` spec); rolling
        #: detectors over this pipeline's registry, violations recorded as
        #: ``slo.violation`` events. Stops with the reader.
        # ---------------- postmortem black box (docs/observability.md
        # "Postmortem black box"): armed by PETASTORM_TPU_BLACKBOX=/dir.
        # Collectors snapshot every report surface at trigger time; the
        # triggers are wired below (SLO/anomaly entry edges, watchdog
        # abort) and in __next__ (any fatal escaping the pipeline).
        from petastorm_tpu.telemetry.postmortem import (BlackBox,
                                                        blackbox_dir_from_env)
        bb_dir = blackbox_dir_from_env()
        if bb_dir:
            self.blackbox = BlackBox(
                bb_dir, self.telemetry, label="reader",
                config=self._config_summary())
            self.blackbox.add_collector("cursor", self.state_dict)
            # Postmortems show what the optimizer chose and why: plan
            # source (default/persisted/trial), trial verdict, fusions
            # (docs/plan.md).
            self.blackbox.add_collector("plan", self.plan_report)
            self.blackbox.add_collector("quarantine", self.quarantine_report)
            self.blackbox.add_collector("pruning", self.pruning_report)
            self.blackbox.add_collector("readahead", self.readahead_report)
            self.blackbox.add_collector("autotune", self.autotune_report)
            self.blackbox.add_collector("growth", self.dataset_growth_report)
            self.blackbox.add_collector("slo", self.slo_report)
            self.blackbox.add_collector("anomaly", self.anomaly_report)
            self.blackbox.add_collector("watchdog", self.watchdog_report)
            if self.quality_monitor is not None:
                # A dead run's bundle shows what the DATA looked like when
                # it died: profiles, drift scores, coverage manifests.
                self.blackbox.add_collector("quality", self.quality_report)
            if self.watchdog is not None:
                self.watchdog.on_abort = (
                    lambda err: self.blackbox.write_bundle("watchdog_abort",
                                                           exc=err))

        self.slo_watcher = None
        slo_spec = os.environ.get(SLO_WATCH_ENV, "").strip()
        if slo_spec:
            from petastorm_tpu.telemetry.slo import (SloWatcher,
                                                     default_rules,
                                                     parse_rules)
            rules = (default_rules() if slo_spec in ("1", "default")
                     else parse_rules(slo_spec))
            self.slo_watcher = SloWatcher(
                self.telemetry, rules,
                on_violation=self._on_slo_violation).start()

        # ---------------- rolling timeline + anomaly monitor
        # (docs/observability.md "Ops plane"): `timeline_interval_s=` or
        # PETASTORM_TPU_TIMELINE=seconds attach a MetricsTimeline to this
        # pipeline's registry, fed by a background sampler (monotonic
        # clock), with the default anomaly detector bank listening on
        # every closed window.
        from petastorm_tpu.telemetry.timeseries import (
            MetricsTimeline, TimelineSampler, timeline_interval_from_env)
        interval = (timeline_interval_s if timeline_interval_s is not None
                    else timeline_interval_from_env())
        if interval:
            self._timeline = MetricsTimeline(interval_s=interval)
            self.telemetry.timeline = self._timeline
            if timeline_anomaly:
                # `timeline_anomaly=False` keeps the ring without the
                # detector bank — the right setting for SUB-feeds whose
                # local rates legitimately gap (mesh host readers parked
                # on assembler backpressure look "collapsed" from their
                # own ring; the fleet-level monitor owns their health).
                from petastorm_tpu.telemetry.anomaly import AnomalyMonitor
                self.anomaly_monitor = AnomalyMonitor(
                    self.telemetry, on_detection=self._on_anomaly)
                self._timeline.add_listener(
                    self.anomaly_monitor.observe_window)
            self._timeline_sampler = TimelineSampler(
                self.telemetry, self._timeline, interval).start()

        # ---------------- telemetry fabric (docs/observability.md
        # "Telemetry fabric"): `telemetry_publish=` or
        # PETASTORM_TPU_TELEMETRY_PUBLISH=addr streams this registry's
        # delta-encoded metric windows (plus the per-tenant accounting
        # record) to a live aggregator. `tenant=` labels every window
        # regardless of whether a publisher runs — it also stamps
        # accounting_report().
        self._telemetry_publisher = None
        self._tenant = tenant
        from petastorm_tpu.telemetry.fabric import publish_addr_from_env
        publish_addr = (telemetry_publish if telemetry_publish is not None
                        else publish_addr_from_env())
        if publish_addr:
            from petastorm_tpu.telemetry.fabric import TelemetryPublisher
            self._telemetry_publisher = TelemetryPublisher(
                self.telemetry, publish_addr, tenant=tenant).start()

        # ---------------- explain plane (docs/observability.md "Explain
        # plane"): the operator graph is materialized lazily on the first
        # explain() call and re-snapshotted — previous spec flagged
        # superseded — whenever a dynamic reconfiguration (placement
        # migration, autotune knob change, live growth) changes the live
        # knob signature. The registry attachment embeds the profiled
        # graph in every exported snapshot and black-box bundle.
        self._explain_lock = threading.Lock()
        self._explain_spec = None
        self._explain_version = 0
        self._explain_dirty = False
        self._explain_t0 = time.perf_counter()
        self.telemetry.explain = self._explain_payload
        if self.blackbox is not None:
            self.blackbox.add_collector("explain", self.explain_report)

    # ------------------------------------------------------------- planning
    def _filter_row_groups(self, row_groups, predicate, rowgroup_selector,
                           cur_shard, shard_count, shard_seed, filters=None,
                           rowgroup_subset=None):
        filtered = list(row_groups)
        if filters:
            filtered = self._apply_filters(filtered, filters)
        if predicate is not None:
            filtered = self._apply_partition_predicate(filtered, predicate)
        if rowgroup_selector is not None:
            filtered = self._apply_selector(row_groups, filtered, rowgroup_selector)
        # Live growth (docs/live_data.md) continues the shard stream where
        # the base plan's ``index % shard_count`` walk stopped.
        self._shard_stream_index = len(filtered)
        if cur_shard is not None:
            filtered = self._partition_row_groups(filtered, cur_shard, shard_count,
                                                  shard_seed)
        if rowgroup_subset is not None:
            filtered = self._apply_rowgroup_subset(row_groups, filtered,
                                                   rowgroup_subset)
        return filtered

    def _apply_rowgroup_subset(self, all_row_groups, filtered, rowgroup_subset):
        """Restrict the plan to explicit ordinals into the deterministic
        unfiltered row-group order — IN THE SUBSET'S ORDER. The subset
        stands in for the shard partition (the mesh layer pre-computes and
        possibly pre-shuffles it), so ventilation order follows the caller's
        list, which is what makes per-host delivery watermarks map back to
        plan positions (docs/mesh.md). Groups the earlier filter stages
        dropped stay dropped; an out-of-range or duplicate ordinal is a
        caller bug and raises. The kept ordinals also become the plan's
        TRACE identities — lineage ids in mesh mode name the dataset-global
        ordinal, so per-host reader spans and the mesh loader's pull spans
        agree (docs/observability.md "Trace plane")."""
        seen = set()
        for ordinal in rowgroup_subset:
            if not 0 <= ordinal < len(all_row_groups):
                raise ValueError(
                    f"rowgroup_subset ordinal {ordinal} out of range "
                    f"[0, {len(all_row_groups)}) for this dataset")
            if ordinal in seen:
                raise ValueError(
                    f"rowgroup_subset contains duplicate ordinal {ordinal}")
            seen.add(ordinal)
        kept_ids = {id(rg) for rg in filtered}
        kept = [i for i in rowgroup_subset
                if id(all_row_groups[i]) in kept_ids]
        self._subset_kept_ordinals = kept
        return [all_row_groups[i] for i in kept]

    @staticmethod
    def _apply_filters(row_groups, filters):
        """Standard pyarrow-style partition filters (``(col, op, val)``
        DNF), pruning whole row groups against their hive partition values
        at planning time — the reference hands the same syntax to
        ``pq.ParquetDataset(filters=...)`` (reference reader.py:408,:433).
        Columns must be partition keys: unlike a worker-side ``predicate``
        there is nothing to evaluate them against later, so a typo'd or
        non-partition column raises instead of silently matching nothing."""
        groups = _normalize_filters(filters)
        if not groups:
            return row_groups
        partition_keys = _partition_keys(row_groups)
        referenced = {col for g in groups for col, _, _ in g}
        unknown = referenced - partition_keys
        if unknown:
            raise ValueError(
                f"filters reference non-partition column(s) "
                f"{sorted(unknown)}; this dataset's partition keys are "
                f"{sorted(partition_keys) or '(none - unpartitioned store)'}. "
                f"Use predicate=... for row-level filtering")
        return [rg for rg in row_groups
                if _row_group_matches_filters(rg.partition_dict, groups)]

    @staticmethod
    def _apply_partition_predicate(row_groups, predicate):
        """When every predicate field is a hive partition key, whole row
        groups are pruned at planning time (reference reader.py:620).
        Groups missing one of the keys (heterogeneous multi-URL views) are
        kept — the worker-side evaluation decides for them."""
        fields = predicate.get_fields()
        if not row_groups:
            return row_groups
        if not fields or not fields.issubset(_partition_keys(row_groups)):
            return row_groups
        return [rg for rg in row_groups
                if not fields.issubset(set(rg.partition_dict))
                or predicate.do_include(rg.partition_dict)]

    def _apply_selector(self, all_row_groups, filtered, selector):
        from petastorm_tpu.etl.rowgroup_indexing import get_row_group_indexes
        indexes = get_row_group_indexes(self._ctx)
        for name in selector.get_index_names():
            if name not in indexes:
                raise ValueError(f"Index {name!r} not found in dataset metadata "
                                 f"(available: {sorted(indexes)})")
        selected_ordinals = selector.select_row_groups(indexes)
        # Ordinals refer to the unfiltered, deterministic row-group order.
        selected = {id(all_row_groups[i]) for i in selected_ordinals
                    if i < len(all_row_groups)}
        kept = [rg for rg in filtered if id(rg) in selected]
        # Same provenance surface as the statistics pruner: the report says
        # which selector dropped how many groups at plan time.
        self._pruning_report["selector"] = selector.describe() \
            if hasattr(selector, "describe") else type(selector).__name__
        self._pruning_report["selector_pruned"] = len(filtered) - len(kept)
        return kept

    @staticmethod
    def _partition_row_groups(row_groups, cur_shard, shard_count, shard_seed):
        """Deterministic ``index % shard_count == cur_shard`` sharding, with
        an optional seeded pre-shuffle (reference reader.py:573-597)."""
        if shard_seed is not None:
            import random
            rng = random.Random(shard_seed)
            row_groups = list(row_groups)
            rng.shuffle(row_groups)
        shard = [rg for i, rg in enumerate(row_groups) if i % shard_count == cur_shard]
        if not shard:
            raise NoDataAvailableError(
                f"Shard {cur_shard}/{shard_count} received zero row groups "
                f"({len(row_groups)} total). Use fewer shards or larger datasets.")
        return shard

    def _prune_row_groups_with_statistics(self, row_groups, predicate):
        """Statistics-driven pruning (docs/io.md): drop row groups the
        predicate's :meth:`~petastorm_tpu.predicates.PredicateBase.intervals`
        constraints prove empty against per-row-group column min/max/
        null-count statistics. Strictly an optimization: any unusable
        signal — predicate without ``intervals()``, missing/disabled
        statistics, NaN bounds, cross-type comparisons — keeps the group,
        and the worker-side evaluation decides as before. Hive partition
        keys prune too: a constant per-group value is a ``min == max``
        statistic."""
        report = self._pruning_report
        constraints = predicate.intervals()
        if not constraints:
            report["reason"] = "predicate declares no intervals()"
            return row_groups
        report["enabled"] = True
        fields = sorted({f for f, _ in constraints})
        report["fields"] = fields

        from petastorm_tpu.etl.dataset_metadata import load_row_group_stats
        stats = load_row_group_stats(self._ctx, row_groups, fields,
                                     telemetry=self.telemetry)
        # Retain the harvested per-group statistics as per-column
        # aggregates (satellite of the data-quality plane,
        # docs/observability.md): the SAME footer scan that prunes also
        # seeds the quality plane's reference bounds and histogram edges —
        # zero extra IO. Exposed in pruning_report()["column_stats"].
        self._fold_plan_column_stats(stats.values())
        report["column_stats"] = dict(self._plan_column_stats)
        kept, pruned_per_file = self._prune_with_stats(row_groups,
                                                       constraints, stats)
        pruned = len(row_groups) - len(kept)
        report.update({"row_groups_pruned": pruned,
                       "row_groups_kept": len(kept),
                       "pruned_per_file": pruned_per_file})
        self.telemetry.counter("io.rowgroups_pruned").add(pruned)
        self.telemetry.counter("io.rowgroups_planned").add(len(kept))
        if pruned:
            logger.debug("Statistics pruning dropped %d/%d row groups "
                         "(fields: %s)", pruned, len(row_groups), fields)
        return kept

    def _fold_plan_column_stats(self, per_group_stats) -> None:
        """Fold harvested per-row-group ``{column: ColumnStats}`` dicts
        into the plan-level per-column aggregate
        (``self._plan_column_stats``): min of mins, max of maxes, summed
        null/row counts. Previously these were dropped after pruning;
        retaining them costs nothing and gives the quality plane its
        zero-IO reference seed (docs/observability.md "Data quality
        plane")."""
        agg = self._plan_column_stats
        for group in per_group_stats:
            for name, st in group.items():
                rec = agg.get(name)
                if rec is None:
                    rec = agg[name] = {"min": None, "max": None,
                                       "null_count": 0, "num_rows": 0,
                                       "groups": 0}
                rec["groups"] += 1
                if st.num_rows is not None:
                    rec["num_rows"] += int(st.num_rows)
                if st.null_count is not None:
                    rec["null_count"] += int(st.null_count)
                if getattr(st, "has_min_max", False):
                    try:
                        lo, hi = float(st.min), float(st.max)
                    except (TypeError, ValueError):
                        continue  # non-numeric bounds stay unaggregated
                    rec["min"] = lo if rec["min"] is None \
                        else min(rec["min"], lo)
                    rec["max"] = hi if rec["max"] is None \
                        else max(rec["max"], hi)

    @staticmethod
    def _prune_with_stats(row_groups, constraints, stats):
        """The statistics-admission core shared by plan-time pruning and
        incremental live-growth pruning (docs/live_data.md): returns
        ``(kept, pruned_per_file)`` given pre-loaded per-group stats."""
        from petastorm_tpu.etl.dataset_metadata import ColumnStats
        fields = {f for f, _ in constraints}
        kept, pruned_per_file = [], {}
        for rg in row_groups:
            group_stats = dict(stats.get((rg.path, rg.row_group), {}))
            for key, value in rg.partition_values:
                if key in fields and key not in group_stats:
                    group_stats[key] = ColumnStats(min=value, max=value,
                                                   null_count=0,
                                                   has_min_max=True)
            admits = all(
                domain.admits_stats(group_stats[field])
                for field, domain in constraints
                if field in group_stats)
            if admits:
                kept.append(rg)
            else:
                pruned_per_file[rg.path] = pruned_per_file.get(rg.path, 0) + 1
        return kept, pruned_per_file

    # ------------------------------------------------- live growth plane
    def _plan_growth_batch(self, files):
        """Plan one admitted-growth batch (docs/live_data.md): the same
        filter -> shard -> statistics-prune -> coalesce pipeline the base
        plan ran, continuing the shard stream and lineage ordinals where
        the plan left off. ``files`` is ``[(abs_path, num_row_groups,
        per_group_stats_or_None), ...]`` in admission order; stats come
        from the watcher's validation footers (zero extra IO) or — on a
        manifest resume, where only file names are recorded — from a
        footer scan of just those files. Returns ``(new_items, info)``."""
        from petastorm_tpu.etl.dataset_metadata import (RowGroupRef,
                                                        load_row_group_stats)
        plan = self._live_plan
        refs = []
        stats_by_key = {}
        have_stats = True
        for path, n_groups, stats in files:
            pv = self._ctx.partition_values_for(path)
            for i in range(n_groups):
                refs.append(RowGroupRef(path, i, pv))
                if stats is not None and i < len(stats):
                    stats_by_key[(path, i)] = stats[i]
            if stats is None:
                have_stats = False
        total = len(refs)
        kept = refs
        if plan["filters"]:
            kept = self._apply_filters(kept, plan["filters"])
        if plan["predicate"] is not None:
            kept = self._apply_partition_predicate(kept, plan["predicate"])
        if plan["cur_shard"] is not None:
            start = self._shard_stream_index
            self._shard_stream_index = start + len(kept)
            kept = [rg for i, rg in enumerate(kept, start=start)
                    if i % plan["shard_count"] == plan["cur_shard"]]
        # Lineage ordinals continue after everything already planned, so
        # trace ids stay unique and monotonic across growth.
        for rg in kept:
            self._trace_ordinal_by_key[(rg.path, rg.row_group)] = \
                self._trace_next_ordinal
            self._trace_next_ordinal += 1
        pruned = 0
        predicate = plan["predicate"]
        if plan["pruning"] and predicate is not None:
            constraints = predicate.intervals()
            if constraints:
                fields = sorted({f for f, _ in constraints})
                stats = (stats_by_key if have_stats
                         else load_row_group_stats(self._ctx, kept, fields,
                                                   telemetry=self.telemetry))
                self._fold_plan_column_stats(stats.values())
                if self._pruning_report.get("enabled"):
                    self._pruning_report["column_stats"] = \
                        dict(self._plan_column_stats)
                kept2, pruned_per_file = self._prune_with_stats(
                    kept, constraints, stats)
                pruned = len(kept) - len(kept2)
                kept = kept2
                if self._pruning_report.get("enabled"):
                    self._pruning_report["row_groups_pruned"] = \
                        self._pruning_report.get("row_groups_pruned", 0) \
                        + pruned
                    self._pruning_report["row_groups_kept"] = \
                        self._pruning_report.get("row_groups_kept", 0) \
                        + len(kept)
                self.telemetry.counter("io.rowgroups_pruned").add(pruned)
                self.telemetry.counter("io.rowgroups_planned").add(len(kept))
        if plan["coalescing"] > 1:
            kept = _coalesce_row_groups(kept, plan["coalescing"])
        drop_parts = plan["drop_partitions"]
        new_items = [{"rowgroup": rg,
                      "shuffle_row_drop_partition": (part, drop_parts)}
                     for rg in kept for part in range(drop_parts)]
        return new_items, {"row_groups": total, "pruned": pruned}

    def _apply_dataset_growth(self) -> None:
        """Fold staged admitted files into the live plan at the consumer
        safe point (docs/live_data.md). The extension is **monotonic**:
        new work items land after the existing range, effective from the
        first epoch the ventilator has not planned yet — every
        already-planned epoch (including the one being consumed) is
        byte-identical with or without the growth, deterministic cursors
        stay valid, and the epoch after admission is a pure function of
        ``(seed, epoch, extended plan)``."""
        staged = self._discovery.drain_staged()
        if not staged:
            return
        new_items, info = self._plan_growth_batch(
            [(a.path, a.num_row_groups, a.stats) for a in staged])
        effective = self._ventilator.extend_items(new_items)
        if self._epoch_plan is not None and new_items:
            self._epoch_plan.extend(effective,
                                    self._num_items + len(new_items))
        self._num_items += len(new_items)
        batch = {"epoch": effective,
                 "files": [[os.path.relpath(a.path, self._ctx.root_path),
                            a.num_row_groups] for a in staged],
                 "items": len(new_items), **info}
        self._growth_batches.append(batch)
        if self._lookup_plane is not None:
            # Random-access plane rides the same admission point
            # (docs/random_access.md): the appended files' keys become
            # visible to lookup()/DatasetView the moment the epoch plan
            # grows. Best-effort — an index-extension failure must never
            # take down the epoch stream the growth is really for.
            try:
                self._lookup_plane.extend_files(
                    [(a.path, a.num_row_groups) for a in staged])
            except Exception:  # noqa: BLE001
                logger.exception("field-index growth extension failed; "
                                 "lookups will not see the appended files")
        # Explain-plane safe point: the plan just grew — re-snapshot the
        # operator graph (plan_items / growth capacities changed).
        self._explain_dirty = True
        self.telemetry.counter("discovery.items_extended").add(
            len(new_items))
        self.telemetry.record_event(
            "discovery.growth_applied",
            {"epoch": effective, "files": len(staged),
             "row_groups": info["row_groups"], "items": len(new_items),
             "pruned": info["pruned"]})
        logger.info(
            "live growth applied: %d file(s), %d row group(s) -> %d work "
            "item(s) (%d pruned), effective from epoch %d",
            len(staged), info["row_groups"], len(new_items),
            info["pruned"], effective)

    def refresh_dataset(self) -> dict:
        """Synchronous discovery pass: poll the store once, fold any
        admitted growth into the plan, and return
        :meth:`dataset_growth_report`. The explicit companion to the
        background ``refresh_interval_s > 0`` mode (with ``0``, this and
        :meth:`reset` are the only polling points)."""
        if self._discovery is None:
            raise RuntimeError(
                "refresh_dataset() needs make_reader(refresh_interval_s=...) "
                "(docs/live_data.md)")
        self._discovery.poll_once()
        self._apply_dataset_growth()
        return self.dataset_growth_report()

    def dataset_growth_report(self) -> dict:
        """Live-data readout (docs/live_data.md): the watcher's admission
        state machine (pending / refused / admitted files, poll and
        freshness stats) plus every growth batch applied to this reader's
        plan. ``{"enabled": False}`` when ``refresh_interval_s`` is off."""
        if self._discovery is None and not self._growth_batches:
            return {"enabled": False}
        report = {"enabled": self._discovery is not None,
                  "refresh_interval_s": self._refresh_interval_s,
                  "items": self._num_items,
                  "applied": [dict(b) for b in self._growth_batches]}
        if self._discovery is not None:
            report["discovery"] = self._discovery.report()
        return report

    # ------------------------------------------------------------------
    # Random-access plane (docs/random_access.md)
    # ------------------------------------------------------------------
    def _ensure_lookup_plane(self):
        """Build the lookup plane from the dataset's persisted field-index
        sidecar on first use. Shares this reader's decoded cache (so
        lookups and the epoch stream warm each other and return
        byte-identical cells), quarantine aggregator, retry/degraded
        policy, and telemetry registry. Growth batches already applied to
        the epoch plan are folded in, so a late-built plane sees exactly
        the files the plan does."""
        if self._lookup_plane is None:
            from petastorm_tpu.index import FieldIndex, IndexLookupPlane
            index = FieldIndex.load(self._ctx)
            args = self._worker_args_inproc
            self._lookup_plane = IndexLookupPlane(
                self._ctx, index, self._stored_schema,
                dataset_url_or_urls=args["dataset_url_or_urls"],
                storage_options=args.get("storage_options"),
                filesystem=args.get("filesystem"),
                cache=self._cache,
                retry_policy=args.get("retry_policy"),
                degraded_mode=args.get("degraded_mode", False),
                fault_plan=args.get("fault_plan"),
                hedge_policy=args.get("hedge_policy"),
                telemetry=self.telemetry, quarantine=self.quarantine,
                default_columns=sorted(
                    n for n in self.schema.fields
                    if n in self._stored_schema.fields))
            # Reconcile the plane with every file this reader's plan
            # covers: growth batches applied before the plane was built,
            # AND base-plan files newer than the persisted sidecar (a
            # fresh reader over a grown store lists appended files as
            # base, not growth). extend_files dedupes per file, so
            # already-indexed entries are untouched.
            newer = [(os.path.join(self._ctx.root_path, rel), n)
                     for rel, n in (self._base_manifest or [])]
            newer += [(os.path.join(self._ctx.root_path, rel), n)
                      for b in self._growth_batches for rel, n in b["files"]]
            newer = [(path, n) for path, n in newer
                     if not self._lookup_plane.index.has_file(
                         os.path.relpath(path, self._ctx.root_path))]
            if newer:
                self._lookup_plane.extend_files(newer)
        return self._lookup_plane

    def lookup(self, keys, field=None, columns=None, on_missing="error"):
        """Keyed point reads (docs/random_access.md): fetch the decoded
        rows holding each value of ``field``, coalescing co-resident keys
        into one row-group read and serving warm keys straight from the
        decoded in-memory cache. Returns a list of row dicts in key
        order; cells are byte-identical to a sequential epoch read of the
        same rows. Requires a persisted field index
        (``petastorm_tpu.index.build_field_index``). Predicates,
        transforms, and shuffling do not apply — this is the raw
        random-access surface next to the epoch stream."""
        return self._ensure_lookup_plane().lookup(
            keys, field=field, columns=columns, on_missing=on_missing)

    def dataset_view(self, columns=None):
        """:class:`~petastorm_tpu.index.DatasetView` over this reader's
        lookup plane: random access by global row ordinal, stable across
        resume and monotonic under live growth (the ordinal space is the
        index sidecar's append-only file table, not the epoch plan)."""
        from petastorm_tpu.index import DatasetView
        return DatasetView(self._ensure_lookup_plane(), columns=columns)

    def _current_manifest(self) -> dict:
        """The cursor-side plan manifest: base files plus applied growth
        batches, in admission order (docs/live_data.md)."""
        return {"base": [list(entry) for entry in self._base_manifest],
                "growth": [{"epoch": b["epoch"],
                            "files": [list(f) for f in b["files"]],
                            "items": b["items"]}
                           for b in self._growth_batches]}

    def _make_ventilate_fn(self, pool):
        """The ventilation entry point for ``pool``: announces each work
        item to the readahead fetch stage (when enabled) and — in trace
        mode — mints the item's lineage id (``e{epoch}:g{ordinal}``),
        records the instant ``ventilate`` span, and injects the id as a
        ``trace_context`` kwarg the pools pop before the worker impl sees
        the item. One construction path: the initial ventilator and any
        pool a placement migration later repoints both go through here, so
        a migration never silently drops tracing or readahead."""
        pool_ventilate = pool.ventilate
        # Spawned workers cannot pop the in-process fetched-table store
        # (they receive readahead=None): announcing to the fetchers for a
        # process-pool target would read every row group from storage
        # TWICE and pin fetched tables to the byte budget with no consumer
        # — relevant on the migration path, where the live pool's flavor
        # can differ from construction's.
        readahead = (None if isinstance(pool, ProcessPool)
                     else self.readahead)
        recorder = self.telemetry.recorder
        trace_ordinals = self._trace_ordinal_by_key

        def ventilate_fn(**kwargs):
            trace = None
            if recorder.trace_enabled:
                ctx = kwargs.get(ITEM_CONTEXT_KWARG)
                if ctx is not None:
                    epoch, pos = ctx
                    rg = kwargs["rowgroup"]
                    ordinal = trace_ordinals.get((rg.path, rg.row_group),
                                                 pos)
                    trace = f"e{epoch}:g{ordinal}"
                    kwargs["trace_context"] = trace
                    recorder.record_event("petastorm_tpu.ventilate",
                                          trace=trace, stage="ventilate",
                                          track="ventilator")
            if readahead is not None:
                # Ventilation announces each work item to the fetch stage
                # the moment it is admitted: fetchers run ahead in
                # ventilation order, bounded by their depth/byte budget.
                readahead.submit(kwargs["rowgroup"], trace=trace)
            pool_ventilate(**kwargs)
        return ventilate_fn

    # ----------------------------------------------- placement migration
    def _spawnable_worker_args(self) -> dict:
        """The worker-args variant a SPAWNED worker can receive: live
        in-process handles nulled — spawned workers rebuild a filesystem
        from the URL, retry without the shared registry, read inline
        instead of popping the shared readahead store, and have no
        cross-process cancel flag to consult."""
        from petastorm_tpu.plan import FUSION_DECODE_TRANSPORT
        return {**self._worker_args_inproc,
                "filesystem": None,
                "resilience_telemetry": None,
                "cancel_token": None,
                "readahead": None,
                # Spawned workers must publish Arrow tables — the process
                # pool's Arrow IPC serializer is the transport; the
                # in-process decode->transport fusion does not apply there
                # (docs/plan.md "Fusion rules").
                "plan_fusions": frozenset(
                    self._worker_args_inproc.get("plan_fusions") or ())
                - {FUSION_DECODE_TRANSPORT}}

    def _sync_pool_gauges(self, pool) -> None:
        """Point every pool-derived telemetry gauge at ``pool`` — one sync
        routine shared by construction and the migration safe point, so a
        post-migration snapshot can never mix the old backend's queue
        shape with the new backend's counters (the PR 6 drift: readers
        kept reporting the retired pool's keys until the next snapshot
        happened to re-register them).

        ``pool.results_queue_depth``/``capacity`` are zeroed for the
        process pool: its results_qsize() is a constant 0 (queued results
        live in ZMQ/ring buffers, unobservable across the socket), and a
        permanently-empty-looking queue would read as producer_bound
        forever in the autotune fallback diagnosis — capacity 0 disables
        the fill-fraction path there instead. ``pool.backend`` mirrors the
        live flavor (0 = thread/dummy, 1 = process) so exported snapshots
        name the backend they describe."""
        depth_gauge = self.telemetry.gauge("pool.results_queue_depth")
        cap_gauge = self.telemetry.gauge("pool.results_queue_capacity")
        is_process = isinstance(pool, ProcessPool)
        self.telemetry.gauge("pool.backend").set(1.0 if is_process else 0.0)
        if is_process:
            depth_gauge.set_function(None)
            depth_gauge.set(0)
            cap_gauge.set(0)
        else:
            # Aggregate bound: results_qsize() sums every per-worker queue,
            # so the fill fraction's denominator must scale the per-queue
            # capacity by the worker count or a 1/N-full pool reads full.
            depth_gauge.set_function(pool.results_qsize)
            cap_gauge.set(pool.diagnostics["results_queue_capacity"]
                          * max(1, pool.workers_count))

    def _request_pool_migration(self, backend: str) -> None:
        """Placement-actuator endpoint (any thread): schedule a decode-pool
        migration; the swap happens at the next ``__next__`` boundary on
        the consumer thread (docs/zero_copy.md)."""
        self._pending_pool_target = backend

    def _perform_pool_migration(self) -> None:
        """Swap the decode stage thread<->process at a consumer-thread safe
        point: park the ventilator before its next item, drain the old
        pool's in-flight work (buffering drained results for in-order
        delivery), stand up the new pool, repoint ventilation, and swap
        the results reader. Row groups are neither lost nor duplicated:
        everything ventilated into the old pool is consumed from it, and
        the parked ventilator resumes into the new one."""
        target, self._pending_pool_target = self._pending_pool_target, None
        current = ("process" if isinstance(self._pool, ProcessPool)
                   else "thread")
        if target == current or self._pool_factory is None:
            if self._placement_actuator is not None:
                self._placement_actuator.mark_applied()
            return
        logger.info("Migrating decode stage: %s pool -> %s pool", current,
                    target)
        t0 = time.perf_counter()
        old_pool = self._pool
        if not self._ventilator.pause():
            warnings.warn("placement migration skipped: ventilator did not "
                          "quiesce in time")
            self._ventilator.resume()
            if self._placement_actuator is not None:
                # The actuator must not report a backend that never went
                # live; re-sync it to the pool actually running.
                self._placement_actuator.mark_failed(current)
            return
        buffered = []
        migrated = False
        aborted = False
        try:
            from petastorm_tpu.workers_pool import \
                TimeoutWaitingForResultError
            # Bounded drain: a wedged worker must not turn a migration into
            # a permanent hang (migratable configs have the watchdog off by
            # construction, so the deadline here IS the escape hatch).
            drain_deadline = time.monotonic() + _MIGRATION_DRAIN_TIMEOUT_S
            while True:
                d = old_pool.diagnostics
                if d["items_inprocess"] <= 0 and d["output_queue_size"] <= 0:
                    break
                if time.monotonic() > drain_deadline:
                    warnings.warn(
                        f"placement migration aborted: the {current} pool "
                        f"did not drain within "
                        f"{_MIGRATION_DRAIN_TIMEOUT_S:.0f}s "
                        f"({d['items_inprocess']} item(s) still in flight); "
                        f"staying on the {current} pool")
                    aborted = True
                    return
                try:
                    # Bounded waits: trailing processed-markers are consumed
                    # inside get_results without yielding a result, so the
                    # drain must re-check the accounting between attempts.
                    buffered.append(old_pool.get_results(timeout=0.25))
                except TimeoutWaitingForResultError:
                    continue
                except EmptyResultError:
                    break
            # Detach the ventilator BEFORE stopping: pool.stop() would
            # otherwise stop ventilation for good. The old pool's final
            # item tallies are captured here but retired into the
            # cumulative base only WITH the pool swap below — doing it now
            # would double-count them for any diagnostics() poll landing
            # during the (seconds-long, spawn-including) window where
            # self._pool is still the old pool.
            old_pool._ventilator = None
            final = old_pool.diagnostics
            old_pool.stop()
            old_pool.join()

            new_pool = self._pool_factory(target)
            new_pool.telemetry = self.telemetry
            new_pool.quarantine = self.quarantine
            if target == "process" and self._worker_crash_budget:
                from petastorm_tpu.resilience import WorkerCrashRecovery
                new_pool.recovery = WorkerCrashRecovery(
                    self._worker_crash_budget, telemetry=self.telemetry)
            if hasattr(new_pool, "stage_deadline"):
                new_pool.stage_deadline = self._stage_deadline
            if self.is_batched_reader and not self._convert_early_to_numpy \
                    and hasattr(new_pool, "result_transform"):
                from functools import partial as _partial
                new_pool.result_transform = _partial(
                    arrow_table_to_numpy_dict, schema=self.schema,
                    force_copy=False)
            worker_args = (self._spawnable_worker_args()
                           if target == "process"
                           else self._worker_args_inproc)
            new_pool.start(self._worker_class, worker_args, ventilator=None)
            # The (already running) ventilator belongs to the new pool now:
            # completion checks and processed-item credits flow to it, and
            # the parked ventilation thread re-reads the fn on resume —
            # through _make_ventilate_fn, so trace-mode lineage injection
            # survives the swap.
            new_pool._ventilator = self._ventilator
            self._ventilator.set_ventilate_fn(
                self._make_ventilate_fn(new_pool))

            # Gauges follow the pool through the ONE sync routine
            # construction used — done at the safe point, before the swap
            # is visible, so no snapshot can mix backends.
            self._sync_pool_gauges(new_pool)
            if self.autotune is not None:
                self.autotune.unregister("worker_concurrency")
                gate = getattr(new_pool, "concurrency_gate", None)
                if gate is not None:
                    from petastorm_tpu.autotune import \
                        WorkerConcurrencyActuator
                    self.autotune.register(WorkerConcurrencyActuator(
                        gate, new_pool.workers_count))

            # Retire the old pool's tallies and swap the live pool as ONE
            # step (diagnostics stays monotonic: a fresh pool restarts its
            # own counters from zero, the base carries the history).
            with self._diag_lock:
                self._pool_items_base["items_ventilated"] += \
                    final["items_ventilated"]
                self._pool_items_base["items_processed"] += \
                    final["items_processed"]
                self._pool = new_pool
            self._results_reader.swap_pool(new_pool, buffered)
            buffered = []
            migrated = True
            # Explain-plane safe point: the operator graph's decode
            # placement (and possibly the transport operator) just
            # changed; the next explain() re-snapshots and flags the
            # previous spec superseded.
            self._explain_dirty = True
        except BaseException as exc:
            # Hard failure mid-swap (pool start, spawn, ...): the old pool
            # may already be stopped, so the pipeline is broken — remember
            # the error so every later __next__ re-raises it instead of a
            # stopped pool's EmptyResultError masquerading as a clean,
            # silently-truncated epoch.
            self._migration_error = exc
            raise
        finally:
            if buffered:
                # The drained results must still reach the consumer before
                # whatever error surfaces next.
                self._results_reader.push_pending(buffered)
            if self._placement_actuator is not None:
                if migrated:
                    self._placement_actuator.mark_applied()
                else:
                    # The actuator must not report a backend that never
                    # went live (the controller cancels its trial on this).
                    self._placement_actuator.mark_failed(current)
            if migrated or aborted:
                # aborted: the old pool is untouched and stays live. A hard
                # failure leaves the ventilator parked — resuming it would
                # feed items into a stopped pool and lose them.
                self._ventilator.resume()
        self.telemetry.counter("autotune.placement_migrations").add(1)
        logger.info("Decode stage now on the %s pool (migration took "
                    "%.2fs)", target, time.perf_counter() - t0)

    # ------------------------------------------------------------ iteration
    def __iter__(self):
        return self

    def __next__(self):
        if self._migration_error is not None \
                and not self._results_reader.has_buffered():
            # Results drained before the migration failed are served first
            # (they are real, fully-read row groups); once they run out the
            # broken pipeline surfaces as the original error, never as a
            # clean-looking truncated epoch.
            raise self._migration_error
        if self._pending_pool_target is not None:
            self._perform_pool_migration()
        if self._discovery is not None and self._discovery.has_growth:
            # Consumer-thread safe point, like migrations: the extension
            # only affects not-yet-planned epochs, so folding it here is
            # invisible to the epoch being consumed (docs/live_data.md).
            self._apply_dataset_growth()
        try:
            sample = self._results_reader.read_next()
            return sample
        except EmptyResultError:
            self.last_row_consumed = True
            raise StopIteration
        except StopIteration:
            raise
        except Exception as e:
            # Fatal escaping the pipeline (PipelineHungError, a pool
            # abort, crash-budget exhaustion, a worker exception): the
            # black box writes its bundle BEFORE the consumer unwinds —
            # the registry/timeline/stacks still describe the death.
            self._record_fatal(e)
            raise

    def next_batch(self):
        """Next whole decoded unit as COLUMNS — the batch-native consumer
        API (docs/io.md "Batch-native plane"). For batched readers: the
        row group's ``{column: ndarray}`` dict (the same arrays
        ``__next__`` would wrap in a namedtuple). For
        ``row_materialization='lazy'`` row readers: the worker's
        :class:`~petastorm_tpu.reader_impl.batch_plane.ColumnarBatch`
        (a partially row-iterated batch yields its remainder, so mixing
        ``__next__`` and ``next_batch`` never duplicates rows). Raises
        ``StopIteration`` at end of stream like ``__next__``; eager row
        readers raise ``TypeError`` — there is no batch payload to
        expose."""
        if self._migration_error is not None \
                and not self._results_reader.has_buffered():
            raise self._migration_error
        if self._pending_pool_target is not None:
            self._perform_pool_migration()
        if self._discovery is not None and self._discovery.has_growth:
            self._apply_dataset_growth()
        try:
            return self._results_reader.read_next_batch()
        except EmptyResultError:
            self.last_row_consumed = True
            raise StopIteration
        except StopIteration:
            raise
        except Exception as e:
            self._record_fatal(e)
            raise

    def next(self):
        return self.__next__()

    def state_dict(self) -> dict:
        """Checkpoint of the read position at row-group granularity: pass it
        back as ``resume_state=`` to a new reader (same dataset, filters,
        sharding, seed) to continue the stream. The recorded ``seed`` is
        the (possibly auto-minted) shuffle seed, so a resumed reader needs
        no explicit seed of its own.

        Free mode: the cursor is a watermark over confirmed-consumed work
        items, exact even when multi-worker pools complete row groups out
        of ventilation order: groups at or after the cursor that were
        partially delivered are re-read on resume — bounded duplication,
        never loss. The reference has no resume at all (its reset() is
        epoch-end only, reader.py:503).

        Deterministic mode (docs/determinism.md): the cursor is the
        **delivery** position — ``(epoch, plan offset, window_delivered,
        skipped_ordinals)`` plus the plan record — and the resumed stream
        is byte-identical to the uninterrupted one's remainder. A
        partially row-iterated work item backs the cursor up one unit, so
        resume re-reads that unit whole (the resumed stream is then an
        exact suffix of the full stream: bounded duplication, still
        byte-identical order)."""
        if self._gate is not None:
            cur = self._gate.cursor(
                back_up=self._results_reader.has_partial_unit())
            cur.update({"items": self._num_items, "seed": self._seed,
                        "sample_order": "deterministic",
                        "window": self._shuffle_window,
                        "plan": self._epoch_plan.describe()})
            if self._base_manifest is not None:
                # Live-data cursor (docs/live_data.md): the manifest pins
                # the admission-ordered file set so resume rebuilds this
                # exact ordinal assignment — the sorted listing would
                # interleave appended files into the middle.
                cur["manifest"] = self._current_manifest()
            return cur
        s = self._ventilator.state
        cur = {"epoch": s["epoch"], "offset": s["offset"],
               # Work-item count: lets resume reject a plan whose offsets
               # mean different data (changed filters, sharding,
               # shuffle_row_drop_partitions, or rowgroup_coalescing).
               "items": self._num_items,
               "seed": self._seed}
        if self._base_manifest is not None:
            cur["manifest"] = self._current_manifest()
        return cur

    def reset(self):
        """Start another pass. Only legal after the current pass finished
        (parity: reference reader.py:503-527).

        With live discovery (docs/live_data.md) a reset is a **plan
        rebase**: the store is polled (synchronously in the
        ``refresh_interval_s=0`` between-epochs mode), staged growth is
        folded in, and the new pass plans every admitted item from its
        epoch 0 — a fresh pass over the grown dataset, rather than a
        replay of the previous pass's admission schedule."""
        if not self.last_row_consumed:
            raise RuntimeError(
                "reset() is only supported after the previous pass was fully consumed")
        if self._discovery is not None:
            if not self._refresh_interval_s:
                self._discovery.poll_once()
            if self._discovery.has_growth:
                self._apply_dataset_growth()
        if self._growth_batches:
            # Rebase: collapse the growth schedule so the NEW pass covers
            # the full admitted plan from its first epoch. Keyed on growth
            # having been applied — a manifest-resumed reader carries
            # growth batches even with discovery off, and its restarted
            # epoch counter must not be read against the previous run's
            # absolute effective epochs.
            self._ventilator.rebase_growth()
            if self._epoch_plan is not None:
                self._epoch_plan.rebase()
            for batch in self._growth_batches:
                self._base_manifest.extend([list(f)
                                            for f in batch["files"]])
            self._growth_batches = []
        self._ventilator.reset()
        if self._gate is not None:
            # Another pass replays the exact same canonical order from the
            # stream's origin (the ventilator reset restarts at epoch 0).
            self._gate.reset()
        elif self.quality_monitor is not None \
                and self.quality_monitor.ledger is not None:
            # Count-mode coverage audits ONE pass (the gate reset covers
            # the ordinal ledger).
            self.quality_monitor.ledger.reset()
        self.last_row_consumed = False

    # ------------------------------------------------------------- lifetime
    def stop(self):
        if self._plan is not None and self._plan.source == "trial" \
                and self._plan.trial is not None \
                and self._plan.trial.get("verdict") in ("kept", "reverted"):
            # Refresh the persisted record with end-of-run evidence: the
            # at-resolution snapshot was taken moments after the migration
            # (the winning pool's counters near zero), so the full-epoch
            # profile and final knob positions seed the next warm start's
            # roofline far better (docs/plan.md "Plan cache").
            try:
                from petastorm_tpu.plan import record_trial_outcome
                record_trial_outcome(
                    self._plan, self._plan.trial,
                    actuators=(self.autotune.actuator_values()
                               if self.autotune is not None else {}),
                    profile=self.explain(profiled=True).profile)
            except Exception:  # noqa: BLE001 - persistence never kills IO
                logger.exception("plan-cache refresh at close failed")
        if self._discovery is not None:
            self._discovery.stop()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.slo_watcher is not None:
            self.slo_watcher.stop()
        if self._timeline_sampler is not None:
            # Before the exporter's final flush: the sampler's stop takes
            # the terminal window, so the last exported snapshot carries
            # the complete timeline ring.
            self._timeline_sampler.stop()
        if self.autotune is not None:
            self.autotune.stop()
        if self._telemetry_publisher is not None:
            # After the sampler stop for the same reason as the exporter:
            # the publisher's final (`bye`) window ships the terminal
            # state the aggregator bills and renders last.
            self._telemetry_publisher.stop()
            self._telemetry_publisher = None
        if self._telemetry_exporter is not None:
            self._telemetry_exporter.stop()
            self._telemetry_exporter = None
        if self._lookup_plane is not None:
            self._lookup_plane.close()
        self._pool.stop()
        if self.readahead is not None:
            # After the pool: a worker blocked in a readahead pop sees the
            # stop flag and falls back to a miss; close() then drops every
            # resident table and releases its budget charge.
            self.readahead.close()

    def join(self):
        self._pool.join()
        # Close the cache with the reader (sqlite connections otherwise leak
        # past shutdown); cleanup() is idempotent, so an explicit
        # cleanup_cache() before or after this is fine.
        try:
            self._cache.cleanup()
        except OSError as e:
            logger.warning("Error closing cache on reader shutdown: %s", e)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        self.join()
        return False

    @property
    def diagnostics(self):
        """Pipeline health view: the pool's unified queue/item counters
        (same keys for every pool type), the ventilator backlog, and the
        full telemetry snapshot (counters/gauges/histograms/spans) under
        ``"telemetry"`` — one dict a dashboard can serialize as-is.

        Stable across placement migrations: ``items_ventilated`` /
        ``items_processed`` include every retired pool's final tally (a
        freshly built pool restarts its own counters from zero — without
        the base a dashboard would see the series jump backwards at the
        swap), and ``pool_type`` names the live backend."""
        with self._diag_lock:
            pool = self._pool
            d = dict(pool.diagnostics)
            d["items_ventilated"] += \
                self._pool_items_base["items_ventilated"]
            d["items_processed"] += self._pool_items_base["items_processed"]
        d["pool_type"] = ("process" if isinstance(pool, ProcessPool)
                          else "dummy" if isinstance(pool, DummyPool)
                          else "thread")
        d["ventilator_backlog"] = self._ventilator.inflight
        d["telemetry"] = self.telemetry.snapshot()
        return d

    def quarantine_report(self) -> dict:
        """Degraded-mode outcome of this reader so far: how many row groups
        were skipped, per-error-type tallies, and each skipped piece's full
        provenance (path, row group, exception, attempts burned, worker).
        Empty report when ``degraded_mode`` is off or nothing failed. See
        docs/resilience.md for the schema."""
        return self.quarantine.report()

    def pruning_report(self) -> dict:
        """Plan-time statistics-pruning outcome, applied identically to
        every epoch this reader runs: whether pruning engaged, the
        constrained fields, planned/pruned/kept row-group counts, and a
        per-file breakdown of what was dropped (``enabled=False`` with a
        ``reason`` when the predicate declares no ``intervals()``; see
        docs/io.md for the schema)."""
        return dict(self._pruning_report)

    def readahead_report(self) -> dict:
        """Fetch-stage readout: depth/fetchers plus live hit/miss/
        fetch-error/bytes-in-flight counts. Empty dict when
        ``readahead_depth`` is off."""
        return {} if self.readahead is None else self.readahead.stats()

    def autotune_report(self) -> dict:
        """Controller readout: tick count, per-actuator current values and
        safe ranges, and every adjustment it made (tick, actuator, old, new,
        verdict). Empty dict when ``autotune`` is off. See docs/autotune.md
        for the schema."""
        return {} if self.autotune is None else self.autotune.report()

    def plan_report(self) -> dict:
        """The executed plan's decisions (docs/plan.md): placement and its
        source (``default``/``persisted``/``trial``), the trial verdict
        when one resolved, applied/declined fusions, the plan-cache
        consult outcome, and capacity seeds. Empty dict for direct
        ``Reader(...)`` constructions (no lowering ran)."""
        return {} if self._plan is None else self._plan.describe()

    def _on_placement_resolved(self, outcome: dict) -> None:
        """Controller callback at placement-trial resolution: record the
        verdict on the live plan and persist the winner (plus the tuned
        actuator values and the measured operator profile, the warm
        start's capacity seeds) to the plan cache. Failures only cost the
        warm start, never the run."""
        if self._plan is None:
            return
        if outcome.get("verdict") not in ("kept", "reverted"):
            # Apply-failure pins are not measured verdicts; persisting one
            # would freeze a backend that was never compared.
            self._plan.trial = dict(outcome)
            self._explain_dirty = True
            return
        try:
            from petastorm_tpu.plan import record_trial_outcome
            actuators = (self.autotune.actuator_values()
                         if self.autotune is not None else {})
            try:
                profile = self.explain(profiled=True).profile
            except Exception:  # noqa: BLE001 - profile is a best-effort seed
                profile = None
            record_trial_outcome(self._plan, outcome, actuators=actuators,
                                 profile=profile)
        except Exception:  # noqa: BLE001 - persistence must never kill IO
            logger.exception("plan-cache persist failed; trial verdict "
                             "still applies to this run")
        self._explain_dirty = True

    def slo_report(self) -> dict:
        """SLO watcher readout: the rule set, violation tallies per rule,
        and what is violating right now. Empty dict when
        :data:`~petastorm_tpu.telemetry.SLO_WATCH_ENV` is unset. See
        docs/observability.md "SLO watch"."""
        return {} if self.slo_watcher is None else self.slo_watcher.report()

    def timeline_report(self) -> dict:
        """The rolling timeline ring (``MetricsTimeline.as_dict()`` form:
        windowed rates + rolling quantiles). Empty dict when
        ``timeline_interval_s``/:data:`~petastorm_tpu.telemetry.
        TIMELINE_ENV` is off. See docs/observability.md "Ops plane"."""
        return {} if self._timeline is None else self._timeline.as_dict()

    def anomaly_report(self) -> dict:
        """Anomaly monitor readout: the detector bank, every detection so
        far, and what is actively anomalous. Empty dict when the timeline
        is off (the detectors run over timeline windows)."""
        return ({} if self.anomaly_monitor is None
                else self.anomaly_monitor.report())

    def accounting_report(self) -> dict:
        """Per-pipeline resource-accounting totals (docs/observability.md
        "Telemetry fabric"): rows, bytes read/decoded, decode/fetch
        seconds, and cache hits derived from this registry's counters,
        stamped with the pipeline id and the ``tenant=`` label — the
        same record a running publisher streams to the aggregator's
        ledger. Always available (the source counters are always on)."""
        from petastorm_tpu.telemetry.accounting import (
            ACCOUNTING_SCHEMA_VERSION, accounting_totals)
        return {"schema_version": ACCOUNTING_SCHEMA_VERSION,
                "pipeline_id": self.telemetry.pipeline_id,
                "tenant": self._tenant,
                "totals": accounting_totals(self.telemetry.metrics_view())}

    def quality_report(self) -> dict:
        """Data-quality plane readout (docs/observability.md "Data
        quality plane"): the streaming column profiles, drift scores
        against the reference profile, live-admission scoring, and the
        epoch coverage manifests. Empty dict when the plane is off
        (``quality=`` / ``quality_config=`` / ``reference_profile=``)."""
        if self.quality_monitor is None:
            return {}
        return self.quality_monitor.report(
            quarantine_count=len(self.quarantine))

    def _quality_payload(self):
        """Registry snapshot attachment (never raises; see
        ``TelemetryRegistry.quality``)."""
        try:
            return self.quality_report() or None
        except Exception:  # noqa: BLE001 - snapshots must not die on a report
            return None

    # ------------------------------------------------------ explain plane
    def _explain_signature(self) -> tuple:
        """The live knob values the operator graph depends on: a change —
        a placement migration, an autotune actuation, a growth extension —
        means the cached spec no longer describes the pipeline and the
        next :meth:`explain` re-snapshots it (flagging the old spec
        ``superseded``). Cheap attribute reads only."""
        pool = self._pool
        gate = getattr(pool, "concurrency_gate", None)
        return (type(pool).__name__,
                getattr(pool, "workers_count", 1),
                int(gate.limit) if gate is not None else None,
                self._ventilator.max_inflight,
                self.readahead.depth if self.readahead is not None else None,
                self._num_items)

    def explain(self, profiled: bool = False):
        """This reader's operator graph as a
        :class:`~petastorm_tpu.explain.PipelineSpec` — every pipeline
        stage the configuration induced (ventilation, fetch, decode,
        transport, ordering, materialization, caches, discovery) with its
        layer, placement, parallelism, live capacity, and the kwargs that
        induced it (docs/observability.md "Explain plane").

        ``profiled=True`` additionally binds each operator to its measured
        cost evidence from this pipeline's registry — per-stage self-time
        p50/p99, busy seconds, utilization, queue depths, bytes — and
        names the measured **bottleneck operator** (agreeing with the PR 8
        critical-path attributor's winner whenever one ran).

        The returned object is JSON-serializable (:meth:`~petastorm_tpu.
        explain.PipelineSpec.to_dict`) and supports what-if capacity
        projections (:meth:`~petastorm_tpu.explain.PipelineSpec.whatif`).
        It describes the pipeline *as configured now*: a later dynamic
        reconfiguration re-snapshots the spec and flags this one
        ``superseded=True``."""
        from petastorm_tpu.explain import build_reader_spec, profile_spec
        with self._explain_lock:
            sig = self._explain_signature()
            if (self._explain_spec is None or self._explain_dirty
                    or self._explain_spec.signature != sig):
                old = self._explain_spec
                if old is not None:
                    old.superseded = True
                self._explain_version += 1
                spec = build_reader_spec(
                    self, version=self._explain_version,
                    pipeline_id=self.telemetry.pipeline_id)
                spec.signature = sig
                self._explain_spec = spec
                self._explain_dirty = False
            spec = self._explain_spec
        if profiled:
            spec.profile = profile_spec(
                spec, self.telemetry,
                wall_s=time.perf_counter() - self._explain_t0)
        return spec

    def explain_report(self) -> dict:
        """JSON-safe profiled explain payload: :meth:`explain`
        ``(profiled=True)`` as a plain dict — the form exported snapshots
        embed under ``"explain"`` and black-box bundles record."""
        return self.explain(profiled=True).to_dict()

    def _explain_payload(self):
        """Registry snapshot attachment (never raises; see
        ``TelemetryRegistry.explain``)."""
        return self.explain_report()

    # ------------------------------------------------ ops-plane internals
    def _config_summary(self) -> dict:
        """JSON-safe construction summary for the black box's
        ``config.json`` — what an operator needs to reproduce the run's
        shape, not every kwarg."""
        return {
            "dataset_url": str(self._ctx.path_or_paths),
            "pool_type": ("process" if isinstance(self._pool, ProcessPool)
                          else "dummy" if isinstance(self._pool, DummyPool)
                          else "thread"),
            "workers_count": getattr(self._pool, "workers_count", None),
            "is_batched_reader": self.is_batched_reader,
            "row_materialization": self.row_materialization,
            "sample_order": self.sample_order,
            "shuffle_window": self._shuffle_window,
            "seed": self._seed,
            "num_items": getattr(self, "_num_items", None),
            "plan_source": (self._plan.source if self._plan is not None
                            else None),
        }

    def _record_fatal(self, exc: BaseException) -> None:
        """Black-box trigger for any fatal escaping the consumer API; the
        exception class names the bundle (``pipelinehungerror``,
        ``workercrashbudgetexceeded``, ...), so distinct failure modes
        latch distinct bundles."""
        if self.blackbox is not None:
            self.blackbox.write_bundle(type(exc).__name__, exc=exc)

    def _on_slo_violation(self, violation: dict) -> None:
        if self.blackbox is not None:
            self.blackbox.write_bundle(f"slo_{violation.get('rule', '?')}")

    def _on_anomaly(self, detection: dict) -> None:
        if self.blackbox is not None:
            self.blackbox.write_bundle(
                f"anomaly_{detection.get('rule', '?')}")

    def watchdog_report(self) -> dict:
        """Watchdog readout: hang detections/recoveries, the current
        escalation stage, and the latest thread-stack dump. Empty dict
        when ``hang_timeout_s`` is off. See docs/resilience.md."""
        return {} if self.watchdog is None else self.watchdog.report()

    def cleanup_cache(self):
        """Remove this reader's row-group cache contents (parity: reference
        reader.py:693 — a no-op with the default NullCache)."""
        try:
            self._cache.cleanup()
        except OSError as e:
            logger.warning("Error cleaning cache: %s", e)

    @property
    def batched_output(self):
        return self.is_batched_reader


class _PoolWaitTimer:
    """Times consumer blocking in ``pool.get_results()`` into the pipeline
    registry (``reader.pool_wait_s`` histogram + a recorder span) — the
    "pool-queue" stage of the per-stage breakdown.

    With an :class:`~petastorm_tpu.reader_impl.epoch_plan.
    OrderedDeliveryGate` (deterministic mode, docs/determinism.md), every
    read routes through the gate, which drains the raw pool stream and
    releases payloads in canonical plan order."""

    def __init__(self, pool, telemetry, watchdog=None, gate=None):
        self._pool = pool
        self._telemetry = telemetry
        # Results drained from a pool being migrated away from: served
        # FIRST, in drain order, before the new pool is consulted.
        self._pending = deque()
        # The pipeline watchdog (when enabled) learns here whether the
        # consumer is actually starving: a hang is only a hang while
        # someone is blocked waiting on the pipeline.
        self._watchdog = watchdog
        self._gate = gate
        self._wait_hist = (telemetry.histogram("reader.pool_wait_s")
                           if telemetry is not None else None)
        # DummyPool decodes INLINE inside get_results; subtract that growth
        # so pool_wait_s and worker.decode_s stay disjoint stages. Resolved
        # once: threaded/process pools (no such attribute) skip the reads.
        self._inline_decode_pool = (
            pool if hasattr(pool, "inline_decode_s") else None)

    def swap_pool(self, pool, buffered=None) -> None:
        """Placement migration: read from ``pool`` from now on, after the
        results drained from the old pool (``buffered``) are served."""
        if buffered:
            self._pending.extend(buffered)
        self._pool = pool
        self._inline_decode_pool = (
            pool if hasattr(pool, "inline_decode_s") else None)

    def push_pending(self, results) -> None:
        self._pending.extend(results)

    def has_buffered(self) -> bool:
        """Undelivered results that do not require the live pool."""
        return bool(self._pending)

    def has_partial_unit(self) -> bool:
        """Whether the most recently delivered work item is only partially
        served to the consumer (deterministic checkpoints back up one unit
        over it — bounded duplication instead of row loss)."""
        return False

    def get_results(self):
        if self._gate is not None:
            return self._gate.pull(self._fetch_once)
        return self._fetch_once()

    def _fetch_once(self):
        if self._pending:
            return self._pending.popleft()
        if self._watchdog is not None:
            self._watchdog.enter_wait()
        try:
            return self._timed_get_results()
        finally:
            if self._watchdog is not None:
                self._watchdog.exit_wait()

    def _timed_get_results(self):
        if self._wait_hist is None:
            return self._pool.get_results()
        inline0 = (self._inline_decode_pool.inline_decode_s
                   if self._inline_decode_pool is not None else 0.0)
        with traced_span("petastorm_tpu.pool_wait", self._telemetry,
                         stage="deliver", track="consumer") as span:
            result = self._pool.get_results()
        wait = span.duration_s
        if self._inline_decode_pool is not None:
            wait -= self._inline_decode_pool.inline_decode_s - inline0
        self._wait_hist.observe(max(0.0, wait))
        return result


class _RowResultsReader(_PoolWaitTimer):
    """Buffers published row lists; yields one namedtuple (or ngram dict of
    namedtuples) per ``read_next`` (parity: py_dict_reader_worker.py:64-97).

    Lazy-mode payloads (:class:`~petastorm_tpu.reader_impl.batch_plane.
    ColumnarBatch`, docs/io.md) are held WHOLE: ``read_next`` serves rows
    as namedtuples of views into the shared columns (one cursor advance,
    no per-row dict), and ``read_next_batch`` hands the batch over
    untouched. Rows-counter credit for a batch lands once, at adoption —
    batch-granular accounting instead of a locked add per row."""

    def __init__(self, pool, schema, ngram, telemetry=None, watchdog=None,
                 gate=None, quality=None):
        super().__init__(pool, telemetry, watchdog=watchdog, gate=gate)
        self._schema = schema
        self._ngram = ngram
        # Data-quality plane (docs/observability.md): payloads are
        # profiled HERE — the consumer delivery point — one vectorized
        # pass per column per unit, pool-agnostic and migration-safe.
        self._quality = quality
        self._buffer = deque()
        self._rows = (telemetry.counter("reader.rows")
                      if telemetry is not None else None)
        self._telemetry_reg = telemetry
        self._rows_per_op = None
        # Lazy-mode cursor state: the adopted batch, its per-field column
        # list (aligned with the namedtuple fields; None for fields the
        # batch lacks), and the next row to serve.
        self._batch = None
        self._batch_cols = None
        self._batch_pos = 0

    def has_buffered(self) -> bool:
        return (bool(self._buffer) or self._batch is not None
                or super().has_buffered())

    def has_partial_unit(self) -> bool:
        """Rows of the last delivered unit still sit in the row buffer (or
        a lazy batch cursor is mid-batch): the deterministic cursor must
        re-read that unit whole on resume."""
        return bool(self._buffer) or self._batch is not None

    def _adopt(self, batch) -> None:
        tt = self._schema.namedtuple
        self._batch = batch
        self._batch_cols = [batch.columns.get(name) for name in tt._fields]
        self._batch_pos = 0
        if self._quality is not None:
            self._quality.observe_columns(batch.columns, batch.num_rows)
        if self._rows is not None:
            self._rows.add(batch.num_rows)
        if self._telemetry_reg is not None:
            if self._rows_per_op is None:
                self._rows_per_op = self._telemetry_reg.histogram(
                    "batch.rows_per_op")
            self._rows_per_op.observe(batch.num_rows)

    def _batch_remainder(self):
        """The adopted batch's unserved rows as a ColumnarBatch (views of
        the column storage when partially row-iterated)."""
        from petastorm_tpu.reader_impl.batch_plane import ColumnarBatch
        batch, pos = self._batch, self._batch_pos
        self._batch = None
        self._batch_cols = None
        if pos == 0:
            return batch
        return ColumnarBatch({name: col[pos:]  # operator-ok: per-batch data container, not an operator

                              for name, col in batch.columns.items()},
                             batch.num_rows - pos)

    def read_next(self):
        while True:
            if self._batch is not None:
                i = self._batch_pos
                tt = self._schema.namedtuple
                row = tt(*[None if c is None else c[i]
                           for c in self._batch_cols])
                self._batch_pos = i + 1
                if self._batch_pos >= self._batch.num_rows:
                    self._batch = None
                    self._batch_cols = None
                return row
            if self._buffer:
                item = self._buffer.popleft()
                if self._rows is not None:
                    self._rows.add(1)
                if self._ngram is not None:
                    return item  # already {offset: namedtuple}
                return self._schema.make_namedtuple_from_dict(item)
            result = self.get_results()
            if isinstance(result, ColumnarBatch):
                if result.num_rows:
                    self._adopt(result)
            else:
                if self._quality is not None:
                    self._quality.observe_rows(result)
                self._buffer.extend(result)

    def read_next_batch(self):
        """Next whole ColumnarBatch (lazy mode); a batch partially served
        through ``read_next`` yields its remainder first."""
        while True:
            if self._batch is not None:
                return self._batch_remainder()
            if self._buffer:
                raise TypeError(
                    "next_batch() needs "
                    "make_reader(row_materialization='lazy'); this reader's "
                    "workers publish per-row payloads")
            result = self.get_results()
            if isinstance(result, ColumnarBatch):
                if result.num_rows:
                    self._adopt(result)
            elif result:
                if self._quality is not None:
                    self._quality.observe_rows(result)
                self._buffer.extend(result)


class _BatchResultsReader(_PoolWaitTimer):
    """Yields one namedtuple-of-numpy-arrays per row group
    (parity: arrow_reader_worker.py:89-111, batched_output=True)."""

    def __init__(self, pool, schema, telemetry=None, watchdog=None,
                 gate=None, quality=None):
        super().__init__(pool, telemetry, watchdog=watchdog, gate=gate)
        self._schema = schema
        self._quality = quality
        self._rows = (telemetry.counter("reader.rows")
                      if telemetry is not None else None)
        self._telemetry_reg = telemetry
        self._rows_per_op = None

    def _next_columns(self) -> dict:
        result = self.get_results()
        if not isinstance(result, dict):
            # Payload shape depends on convert_early_to_numpy, not pool type:
            # workers publish Tables by default (converted here) and numpy
            # dicts when converting early (incl. the process pool's shm
            # result_transform path).
            result = arrow_table_to_numpy_dict(result, self._schema)
        if result:
            n = len(next(iter(result.values())))
            if self._quality is not None:
                # One vectorized profile pass per column per row group
                # (docs/observability.md "Data quality plane").
                self._quality.observe_columns(result, n)
            if self._rows is not None:
                self._rows.add(n)
        return result

    def read_next(self):
        return self._schema.make_namedtuple_from_dict(self._next_columns())

    def read_next_batch(self) -> dict:
        """The next row group's raw column dict — the batch-native consumer
        path (docs/io.md): no namedtuple wrap, no per-field getattr walk in
        the loaders."""
        result = self._next_columns()
        if self._telemetry_reg is not None and result:
            if self._rows_per_op is None:
                self._rows_per_op = self._telemetry_reg.histogram(
                    "batch.rows_per_op")
            self._rows_per_op.observe(len(next(iter(result.values()))))
        return result
