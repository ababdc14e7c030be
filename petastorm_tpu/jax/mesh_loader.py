"""Multi-host GSPMD mesh ingestion: one logical dataset -> one globally
sharded ``jax.Array`` pytree per step across the slice.

:class:`MeshDataLoader` closes ROADMAP item 1: it wraps N per-host readers
(one per ``jax.process_index()`` on a real slice; N simulated hosts in one
process under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and
yields, per step, one batch dict of **global** arrays assembled with
``jax.make_array_from_single_device_arrays`` under a
``NamedSharding(mesh, PartitionSpec(...))``.

Shard plan
----------
The per-host shard assignment reuses the reader's existing
``cur_shard``/``shard_count`` arithmetic verbatim:
:meth:`~petastorm_tpu.reader.Reader._partition_row_groups` is applied to
the dataset's row-group *ordinals* (optionally pre-shuffled by
``seed + epoch``), and each host's reader is opened with
``rowgroup_subset=plan[host]`` — so shard membership is bit-identical to a
``cur_shard=h, shard_count=H`` reader, and statistics pruning still runs
*after* sharding exactly as in PR 5. One plan, three consumers: the
readers read it, the reshard path reassigns it, the resume cursor indexes
into it.

Delivery accounting and elastic reshard
---------------------------------------
Each host puller forwards whole decoded row groups ("parts") to the
assembler. The PR 2/PR 4 resilience stack *inside* each reader (retry,
quarantine, crash budget, watchdog) is the per-host failure detector: any
exception that escapes a host's reader — or an injected
:meth:`MeshDataLoader.kill_host` — marks that host lost. Unless
``strict=True`` (or the topology is multi-process, where no in-process
reassignment is possible), the loader then reassigns the host's
**undelivered** row-group range round-robin to the survivors by opening
recovery readers over ``rowgroup_subset`` slices.

Delivered-ness is a per-source watermark: with the default
:class:`MeshReaderFactory` configuration (columnar reader, one in-process
worker) results arrive in ventilation order and the watermark equals the
enqueue count — a lost host's range is re-read **exactly once**. With
out-of-order pools (``workers_count > 1``) the reader's own
``state_dict()`` watermark is used instead: never loss, bounded
duplication (the same contract resume has always had).

Staging
-------
A background assembler feeds the inherited double-buffered staging
pipeline (``prefetch=2`` => the ``device_put`` of step k+1 overlaps step
k's compute), extending the PR 6 dlpack path: on CPU backends the default
device's shard is adopted zero-copy via ``jax.dlpack`` when large enough,
the rest dispatch in one batched ``device_put``.

See docs/mesh.md for the shard-plan diagram, the reshard semantics, and
the interaction matrix with pruning/readahead/quarantine/autotune.
"""
from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from petastorm_tpu.errors import NoDataAvailableError
from petastorm_tpu.jax.dtypes import sanitize_batch
from petastorm_tpu.jax.loader import LoaderBase
from petastorm_tpu.metrics import traced_span
from petastorm_tpu.reader_impl.batch_plane import ColumnarBatch

logger = logging.getLogger(__name__)

__all__ = ["MeshDataLoader", "MeshReaderFactory", "MeshHostLostError"]


class MeshHostLostError(RuntimeError):
    """A per-host input pipeline died and elastic resharding was not
    available: ``strict=True``, a multi-process topology (a peer process's
    range cannot be reassigned from here), or no surviving hosts."""


class _HostKilled(Exception):
    """Internal: :meth:`MeshDataLoader.kill_host` interrupting a puller."""


class _ConfigError(Exception):
    """Internal: a deterministic collation/configuration error. Every
    survivor would fail identically on the reassigned groups, so this must
    poison the loader directly instead of triggering a reshard storm."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause


class MeshReaderFactory:
    """Default per-host reader factory over one dataset URL.

    ``MeshDataLoader`` calls the factory with a row-group ordinal list and
    expects a single-epoch reader over exactly those groups in that order;
    this implementation forwards every other ``make_reader`` /
    ``make_batch_reader`` kwarg untouched (resilience policies, pruning,
    readahead, caches, pool choice ... all compose per host).

    ``workers_count`` defaults to **1**: with one in-process decode worker
    per (simulated) host, results arrive in ventilation order, which
    upgrades the loader's delivery accounting from watermark-conservative
    to count-exact — the exactly-once reshard guarantee (docs/mesh.md).
    Cross-host parallelism comes from the H hosts, not from per-host
    worker fan-out; raise it only if you accept bounded re-delivery on a
    reshard.
    """

    #: Kwargs the mesh loader owns: it IS the shard plan, the epoch loop,
    #: and the (mesh-level, seeded) row-group order.
    _OWNED = frozenset({"cur_shard", "shard_count", "shard_seed",
                        "rowgroup_subset", "num_epochs",
                        "shuffle_row_groups", "resume_state"})

    def __init__(self, dataset_url: str, batched: bool = False,
                 **reader_kwargs):
        owned = self._OWNED & set(reader_kwargs)
        if owned:
            raise ValueError(
                f"MeshDataLoader owns {sorted(owned)}; configure sharding/"
                f"epochs/order on the loader, not the factory (docs/mesh.md)")
        self.dataset_url = dataset_url
        self.batched = bool(batched)
        self.reader_kwargs = dict(reader_kwargs)
        self.reader_kwargs.setdefault("workers_count", 1)
        # Host readers keep their timeline rings (the federation members)
        # but not the per-reader anomaly bank by default: a host parked on
        # assembler backpressure reads as a local throughput collapse, and
        # fleet health is the MESH monitor's job (host_skew_divergence).
        # Unconditional — PETASTORM_TPU_TIMELINE enables host timelines
        # without a timeline_interval_s kwarg. Override explicitly if
        # per-host detectors are wanted.
        self.reader_kwargs.setdefault("timeline_anomaly", False)
        pool = self.reader_kwargs.get("reader_pool_type", "thread")
        #: True when per-host delivery order provably equals ventilation
        #: order (columnar one-item-per-group stream through a single
        #: in-process worker): the loader's reshard bookkeeping is then
        #: exactly-once instead of watermark-bounded.
        self.fifo_delivery = (
            self.batched
            and self.reader_kwargs["workers_count"] == 1
            and pool in ("thread", "dummy")
            and self.reader_kwargs.get("rowgroup_coalescing", 1) in (None, 1))

    def num_rowgroups(self) -> int:
        from petastorm_tpu.etl.dataset_metadata import (DatasetContext,
                                                        load_row_groups)
        ctx = DatasetContext(self.dataset_url,
                             storage_options=self.reader_kwargs.get(
                                 "storage_options"),
                             filesystem=self.reader_kwargs.get("filesystem"))
        return len(load_row_groups(ctx))

    def __call__(self, rowgroup_subset: Sequence[int]):
        from petastorm_tpu.reader import make_batch_reader, make_reader
        make = make_batch_reader if self.batched else make_reader
        return make(self.dataset_url, rowgroup_subset=list(rowgroup_subset),
                    shuffle_row_groups=False, num_epochs=1,
                    **self.reader_kwargs)


class _Source:
    """One reader's worth of work for a host: an ordinal list, read in
    order. ``pulled`` counts items enqueued to the assembler."""

    __slots__ = ("ordinals", "reader", "pulled", "recovery", "plan_base",
                 "fifo", "counted", "safe_delivered", "plan_positions",
                 "audited")

    def __init__(self, ordinals, recovery: bool = False, plan_base: int = 0,
                 plan_positions=None):
        self.ordinals = list(ordinals)
        self.reader = None
        self.pulled = 0
        self.recovery = recovery
        #: Offset of ``ordinals[0]`` within the host's full epoch plan —
        #: lets a consumed watermark map back to a plan position for the
        #: resume cursor (primary sources only).
        self.plan_base = plan_base
        #: Full-plan position of each ordinal when the list has HOLES (a
        #: resume excluded ordinals already delivered through recovery
        #: sources): ``None`` means contiguous from ``plan_base``. The
        #: watermark arithmetic maps delivered counts back through this,
        #: and the skipped holes stay covered by the cursor's
        #: ``recovered`` set (docs/mesh.md "Cursors after a reshard").
        self.plan_positions = (None if plan_positions is None
                               else list(plan_positions))
        #: Effective count-exact accounting for THIS source: the factory's
        #: fifo_delivery claim re-validated against the live reader
        #: (one item == one row group only holds for batched output — a
        #: factory mis-claiming fifo on a row reader must degrade to the
        #: watermark, not turn reshard arithmetic into data loss).
        self.fifo = False
        #: Row groups already reflected in the host's rowgroups counter.
        self.counted = 0
        #: Delivered-groups watermark as of the LAST successful enqueue —
        #: the only number the reshard range may trust. The live
        #: ``delivered_groups()`` can already count an item pulled but not
        #: yet enqueued (the reader confirms on pull); slicing past it
        #: would drop that in-hand group from the epoch entirely.
        self.safe_delivered = 0
        #: Groups already fed to the coverage auditor (docs/observability.md
        #: "Data quality plane") — _mark_consumed feeds only the delta.
        self.audited = 0

    def plan_watermark(self, delivered: int) -> int:
        """Full-plan position watermark after ``delivered`` groups of THIS
        source reached the stream (primary sources only)."""
        if self.plan_positions is None:
            return self.plan_base + delivered
        if delivered <= 0:
            return self.plan_base
        return self.plan_positions[min(delivered, len(self.plan_positions))
                                   - 1] + 1

    def delivered_groups(self) -> int:
        """Lower bound on row groups delivered to the assembler. FIFO
        sources count enqueues (exact); otherwise the reader's own
        consumed-items watermark (conservative: never counts an
        undelivered group, may under-count delivered ones — reshard then
        re-reads those, bounded duplication instead of loss)."""
        if self.fifo:
            return self.pulled
        if self.reader is None:
            return 0
        try:
            return int(self.reader.state_dict().get("offset", 0))
        except Exception:  # noqa: BLE001 - a dying reader still has a plan
            return 0


class _Part:
    """One decoded row group's batchable columns, consumed incrementally
    by the assembler."""

    __slots__ = ("host", "cols", "rows", "off", "source", "delivered_after")

    def __init__(self, host: int, cols: Dict[str, np.ndarray], rows: int,
                 source: _Source):
        self.host = host
        self.cols = cols
        self.rows = rows
        self.off = 0
        self.source = source
        #: ``source.delivered_groups`` taken at enqueue time: once this
        #: part is fully consumed into a delivered batch, at least this
        #: many of the source's groups are irrevocably in the stream.
        self.delivered_after = 0


class _HostFeed:
    """Per-host pipeline state: a deque of sources, the puller thread, a
    bounded ready-part queue, and loss/consumption bookkeeping."""

    def __init__(self, idx: int, stop: threading.Event):
        self.idx = idx
        #: The owning EPOCH's teardown flag — shared by that epoch's feeds
        #: and permanently set at its teardown, so a puller that outlives
        #: the 10s teardown join (wedged in a storage read) still sees the
        #: signal whenever it resurfaces, instead of a recycled flag.
        self.stop = stop
        self.sources: collections.deque = collections.deque()
        self.current: Optional[_Source] = None
        self.queue: collections.deque = collections.deque()
        self.thread: Optional[threading.Thread] = None
        self.killed = threading.Event()
        self.lost: Optional[BaseException] = None
        self.exhausted = False
        #: Plan-position resume watermark: groups of THIS host's primary
        #: plan fully consumed into delivered batches.
        self.primary_consumed = 0


class MeshDataLoader(LoaderBase):
    """N per-host readers -> one globally sharded ``jax.Array`` batch per
    step (docs/mesh.md).

    :param reader_factory: ``callable(ordinal_list) -> Reader`` producing a
        single-epoch reader over exactly those row-group ordinals in that
        order (see :class:`MeshReaderFactory`, which also supplies
        ``num_rowgroups()`` and the ``fifo_delivery`` accounting hint).
    :param batch_size: **global** rows per step, split across the mesh's
        batch-dim shards (must divide evenly).
    :param mesh: ``jax.sharding.Mesh``; default is a 1-D ``("data",)``
        mesh over every device.
    :param partition_spec: batch ``PartitionSpec``; default ``P("data")``.
    :param num_hosts: feeding hosts. Defaults to ``jax.process_count()``
        on a multi-process slice (pinned — one host is one process) and to
        one simulated host per mesh device in a single process.
    :param num_epochs: passes over the dataset (``None`` = endless).
    :param seed: mesh-level row-group shuffle seed; epoch e uses
        ``seed + e`` through the reader's own shard-shuffle arithmetic.
        ``None`` keeps ordinal order.
    :param strict: a lost host raises :class:`MeshHostLostError` instead
        of resharding (always the behavior on multi-process topologies).
    :param resume_state: a previous :meth:`state_dict` — restores the
        epoch index and each host's plan position.
    :param num_rowgroups: override the factory's ``num_rowgroups()`` probe.
    :param host_queue_depth: decoded row groups buffered per host ahead of
        assembly (host-side backpressure).

    Remaining kwargs are :class:`~petastorm_tpu.jax.loader.LoaderBase`'s
    (``prefetch``, ``pad_last``, ``dtype_policy``, ``echo``,
    ``steps_per_epoch``, ...). The tail batch must be dropped (default) or
    padded — a ragged global array cannot be laid out across the mesh.
    """

    def __init__(self, reader_factory, batch_size: int, mesh=None,
                 partition_spec=None, num_hosts: Optional[int] = None,
                 num_epochs: Optional[int] = 1, seed: Optional[int] = None,
                 strict: bool = False, resume_state: Optional[dict] = None,
                 num_rowgroups: Optional[int] = None,
                 host_queue_depth: int = 2,
                 timeline_interval_s: Optional[float] = None,
                 telemetry_publish: Optional[str] = None,
                 tenant: Optional[str] = None, **kwargs):
        from jax.sharding import NamedSharding, PartitionSpec

        from petastorm_tpu.parallel.mesh import (batch_shard_count, make_mesh,
                                                 mesh_feed_topology)
        super().__init__(batch_size, **kwargs)
        if not self._drop_last and not self._pad_last:
            raise ValueError(
                "a ragged tail batch cannot form a global sharded array; "
                "keep drop_last=True or pass pad_last=True")
        if mesh is None:
            mesh = make_mesh([-1], ["data"])
        self._mesh = mesh
        self._spec = (partition_spec if partition_spec is not None
                      else PartitionSpec("data"))
        self._global_sharding = NamedSharding(mesh, self._spec)
        shards0 = batch_shard_count(mesh, self._spec)
        if batch_size % shards0:
            raise ValueError(
                f"global batch_size {batch_size} must divide evenly over "
                f"the {shards0} batch-dim shard(s) of {self._spec} on this "
                f"mesh")
        self._H, self._local_host, self._multiprocess = mesh_feed_topology(
            mesh, num_hosts)
        if self._multiprocess and batch_size % self._H:
            raise ValueError(
                f"global batch_size {batch_size} must divide evenly over "
                f"{self._H} feeding processes")
        # Per-step rows THIS process contributes, and their global offset.
        self._step_rows = (batch_size // self._H if self._multiprocess
                           else batch_size)
        self._row_offset = ((self._local_host or 0) * self._step_rows
                            if self._multiprocess else 0)
        # Cross-process reshard needs a coordinator this in-process loader
        # does not have: a lost peer would leave collectives hanging either
        # way, so multi-process topologies are strict by construction.
        self._strict = bool(strict) or self._multiprocess

        self._factory = reader_factory
        if num_rowgroups is None:
            probe = getattr(reader_factory, "num_rowgroups", None)
            if probe is None:
                raise ValueError(
                    "pass num_rowgroups= or a factory exposing "
                    "num_rowgroups() (MeshReaderFactory does)")
            num_rowgroups = int(probe())
        if num_rowgroups < 1:
            raise ValueError(f"dataset has no row groups ({num_rowgroups})")
        self._G = num_rowgroups
        from petastorm_tpu.utils.growth import GrowthSchedule
        #: Live-growth schedule (docs/live_data.md): epoch e plans over
        #: ``_g_at(e)`` ordinals, so growth admitted mid-run extends
        #: FUTURE epochs monotonically while every already-planned epoch
        #: keeps its exact shard plans.
        self._g_schedule = GrowthSchedule.base(self._G)
        #: Latest epoch whose per-host plan has been minted (None before
        #: the first); growth lands at ``_planned_through + 1``.
        self._planned_through: Optional[int] = None
        self._fifo = bool(getattr(reader_factory, "fifo_delivery", False))
        self._seed = seed
        if num_epochs is not None and num_epochs < 1:
            raise ValueError(f"num_epochs must be >= 1 or None, "
                             f"got {num_epochs}")
        self._num_epochs = num_epochs
        self._host_queue_depth = max(1, int(host_queue_depth))

        self._resume_epoch = 0
        self._resume_offsets: Optional[List[int]] = None
        self._resume_recovered: List[int] = []
        if resume_state is not None:
            self._load_resume_state(resume_state)

        # ----- epoch-scoped machinery (rebuilt by _epoch_batches)
        self._cond = threading.Condition()
        self._feeds: List[_HostFeed] = []
        self._outstanding = 0
        self._epoch_done = False
        self._fatal: Optional[BaseException] = None
        self._collate_lock = threading.Lock()
        self._canonical_keys: Optional[frozenset] = None
        self._batch_seq = 0
        #: Did the CURRENT epoch reshard? Provenance on the cursor (a
        #: resumed run knows its stream crossed a reshard); reset at each
        #: epoch's setup.
        self._epoch_resharded = False
        #: Global ordinals delivered through RECOVERY sources this epoch:
        #: folded into the cursor's ``recovered`` set so a post-reshard
        #: checkpoint stays valid — resume excludes them from every host's
        #: remaining plan instead of refusing (docs/mesh.md).
        self._recovered_live: set = set()
        #: The live epoch's stop event while one is running — close() sets
        #: it so an assembler blocked waiting for parts exits promptly.
        self._live_stop: Optional[threading.Event] = None
        #: Loader-level closing flag: distinguishes an epoch abandoned by
        #: close() from one that completed (the epoch loop must not start
        #: the NEXT epoch's readers during teardown).
        self._closing = False
        #: None until probed: CPU default device for dlpack shard adoption
        #: (False disables after a failed attempt).
        self._adopt_device = None
        self._adopt_enabled: Optional[bool] = None
        self._lost_hosts: List[dict] = []
        self._epoch_t0: Optional[float] = None

        # ----- telemetry (docs/observability.md "mesh.*")
        self.telemetry.gauge("mesh.hosts").set(self._H)
        self.telemetry.gauge("mesh.host_skew_s").set(0.0)
        self._c_reshard = self.telemetry.counter("mesh.reshard_events")
        self._c_lost = self.telemetry.counter("mesh.hosts_lost")
        self._c_wall = self.telemetry.counter("mesh.ingest_wall_s")
        self._c_assemble_stall = self.telemetry.counter(
            "mesh.assemble_stall_s")
        #: Global-batch assembly self-time (slice + concatenate across host
        #: parts) — the "assemble" edge the critical-path attributor reads.
        self._c_assemble = self.telemetry.counter("mesh.assemble_s")
        # Per-host stage self-times live in each reader's OWN registry;
        # pullers sync per-pull deltas into these mesh-level counters so
        # the critical-path attributor sees decode/fetch/transport too
        # (decode lands on mesh.host_decode_s — the reader-side source is
        # a histogram, and this registry's worker.decode_s must stay a
        # faithful in-process distribution).
        self._c_stage_sync = {
            "decode": self.telemetry.counter("mesh.host_decode_s"),
            "fetch": self.telemetry.counter("io.readahead.fetch_s"),
            "transport": self.telemetry.counter("transport.deserialize_s")}
        self._host_ids = ([self._local_host] if self._multiprocess
                          else list(range(self._H)))
        self._c_host_stall = {h: self.telemetry.counter(
            f"mesh.host{h}.input_stall_s") for h in self._host_ids}
        self._c_host_rows = {h: self.telemetry.counter(
            f"mesh.host{h}.rows") for h in self._host_ids}
        self._c_host_groups = {h: self.telemetry.counter(
            f"mesh.host{h}.rowgroups") for h in self._host_ids}

        # Checkpointable from step 0: before the first delivered batch the
        # cursor is the (possibly resumed) epoch start. LoaderBase.__iter__
        # keeps a non-None _last_input_state.
        hosts0 = {str(h): 0 for h in range(self._H)}
        if self._resume_offsets is not None:
            hosts0 = {str(h): o for h, o in enumerate(self._resume_offsets)}
        self._last_input_state = {
            "mesh": True, "epoch": self._resume_epoch, "hosts": hosts0,
            "num_rowgroups": self._G, "num_hosts": self._H}

        # ----- ops plane (docs/observability.md "Ops plane"): the mesh
        # registry's own rolling timeline (its mesh.host{h}.rows counters
        # feed per-host rows/s family series), per-host reader timelines
        # captured at source teardown for the federated mesh_report view,
        # the anomaly bank (host_skew_divergence watches the family), and
        # the postmortem black box for mesh-level fatals.
        from petastorm_tpu.telemetry.timeseries import (
            MetricsTimeline, TimelineSampler, timeline_interval_from_env)
        self._host_timelines: Dict[str, list] = {}
        #: Per-host profiled operator graphs captured at source teardown
        #: (explain-plane federation, keyed ``h{idx}``).
        self._host_specs: Dict[str, dict] = {}
        # ----- data-quality plane (docs/observability.md "Data quality
        # plane"): the mesh coverage auditor proves every planned global
        # row-group ordinal was delivered (or quarantine-skip-accounted)
        # exactly once per epoch — primary and reshard-recovery sources
        # alike; per-host quality reports are captured at source teardown
        # (same keying as timelines/specs) and federated in mesh_report().
        from petastorm_tpu.quality import MeshCoverageLedger
        self._quality_ledger = MeshCoverageLedger(self._g_at,
                                                  telemetry=self.telemetry)
        self._host_quality: Dict[str, dict] = {}
        self._timeline = None
        self._timeline_sampler = None
        self.anomaly_monitor = None
        self.blackbox = None
        interval = (timeline_interval_s if timeline_interval_s is not None
                    else timeline_interval_from_env())
        if interval:
            from petastorm_tpu.telemetry.anomaly import AnomalyMonitor
            self._timeline = MetricsTimeline(interval_s=interval)
            self.telemetry.timeline = self._timeline
            self.anomaly_monitor = AnomalyMonitor(
                self.telemetry, on_detection=self._on_anomaly)
            self._timeline.add_listener(self.anomaly_monitor.observe_window)
            self._timeline_sampler = TimelineSampler(
                self.telemetry, self._timeline, interval).start()
        # Telemetry fabric (docs/observability.md "Telemetry fabric"):
        # stream the mesh coordinator's registry — which already rolls up
        # per-host counters — as one fabric member.
        self._telemetry_publisher = None
        self._tenant = tenant
        from petastorm_tpu.telemetry.fabric import publish_addr_from_env
        publish_addr = (telemetry_publish if telemetry_publish is not None
                        else publish_addr_from_env())
        if publish_addr:
            from petastorm_tpu.telemetry.fabric import TelemetryPublisher
            self._telemetry_publisher = TelemetryPublisher(
                self.telemetry, publish_addr, tenant=tenant).start()
        from petastorm_tpu.telemetry.postmortem import (
            BlackBox, blackbox_dir_from_env)
        bb_dir = blackbox_dir_from_env()
        if bb_dir:
            self.blackbox = BlackBox(
                bb_dir, self.telemetry, label="mesh",
                config={"hosts": self._H, "batch_size": batch_size,
                        "num_rowgroups": self._G, "seed": seed,
                        "multiprocess": self._multiprocess,
                        "strict": self._strict})
            self.blackbox.add_collector("mesh", self.mesh_report)
            self.blackbox.add_collector("explain", self.explain_report)
            self.blackbox.add_collector(
                "anomaly", lambda: (self.anomaly_monitor.report()
                                    if self.anomaly_monitor else {}))
            self.blackbox.add_collector("cursor",
                                        lambda: self._last_input_state)

    # ------------------------------------------------------------- planning
    def _g_at(self, epoch: int) -> int:
        """Row-group count of ``epoch`` under the growth schedule."""
        return self._g_schedule.size_at(epoch)

    def admit_growth(self, num_rowgroups: int,
                     fold_into_live_epoch: bool = False) -> dict:
        """Live appending datasets (docs/live_data.md): the dataset now
        has ``num_rowgroups`` total row groups (monotonic — ordinals
        ``[old_G, num_rowgroups)`` are NEW, appended after the existing
        range, e.g. by a :class:`~petastorm_tpu.discovery.DatasetWatcher`
        whose snapshot grew).

        Default: growth takes effect at the next not-yet-planned epoch —
        every future ``epoch_plan`` shards the extended ordinal range with
        the same seeded arithmetic, so determinism and cursors survive
        exactly like the single-reader plane. With
        ``fold_into_live_epoch=True`` the new ordinals ALSO join the epoch
        currently running, round-robined to live hosts as recovery sources
        — the PR 7 reshard machinery — and their deliveries fold into the
        cursor's ``recovered`` set, so mid-epoch checkpoints stay valid.
        Returns ``{"admitted", "effective_epoch", "folded"}``."""
        with self._cond:
            new_g = int(num_rowgroups)
            if new_g < self._G:
                raise ValueError(
                    f"mesh growth is monotonic: {new_g} row groups < "
                    f"current {self._G} (a live dataset only appends)")
            if new_g == self._G:
                return {"admitted": 0, "effective_epoch": None, "folded": 0}
            new_ordinals = list(range(self._G, new_g))
            self._G = new_g
            if self._planned_through is not None:
                proposed = self._planned_through + 1
            elif self._resume_offsets is not None:
                # Resumed but not yet running: the cursor's epoch was
                # planned by the PREVIOUS run (its per-host offsets index
                # that plan), so growth must not rewrite it — same rule
                # the while-down path in _load_resume_state applies.
                proposed = self._resume_epoch + 1
            else:
                proposed = self._resume_epoch
            effective = self._g_schedule.extend(proposed, new_g)
            folded = 0
            if fold_into_live_epoch and self._feeds and not self._epoch_done \
                    and self._fatal is None:
                if self._multiprocess:
                    # Each process folds only ITS shard of the new range
                    # (the same i % H rule epoch_plan uses): every process
                    # runs this method, and handing the full range to the
                    # one local feed would deliver every new group H times
                    # across the mesh.
                    fold_ordinals = [o for i, o in enumerate(new_ordinals)
                                     if i % self._H == self._local_host]
                    active = [self._feeds[self._local_host]]
                else:
                    fold_ordinals = new_ordinals
                    active = self._feeds
                live = [f for f in active
                        if f.lost is None and not f.exhausted
                        and not f.killed.is_set()]
                if live and fold_ordinals:
                    buckets: List[List[int]] = [[] for _ in live]
                    for i, o in enumerate(fold_ordinals):
                        buckets[i % len(live)].append(o)
                    added = 0
                    for f, bucket in zip(live, buckets):
                        if bucket:
                            f.sources.append(_Source(bucket, recovery=True))
                            added += 1
                    self._outstanding += added
                    folded = len(fold_ordinals)
            self.telemetry.counter("mesh.growth_admitted").add(
                len(new_ordinals))
            self.telemetry.record_event(
                "mesh.growth", {"new_rowgroups": len(new_ordinals),
                                "effective_epoch": effective,
                                "folded": folded})
            self._cond.notify_all()
        logger.info("mesh growth admitted: %d new row group(s), effective "
                    "from epoch %d%s", len(new_ordinals), effective,
                    f" ({folded} folded into the live epoch)" if folded
                    else "")
        return {"admitted": len(new_ordinals), "effective_epoch": effective,
                "folded": folded}

    def epoch_plan(self, epoch: int) -> List[List[int]]:
        """Per-host row-group ordinal lists for ``epoch`` — the reader's
        own ``index % shard_count`` arithmetic (with the seeded
        pre-shuffle) applied to ordinals, so host h's list is exactly what
        a ``cur_shard=h, shard_count=H`` reader would plan. Hosts may come
        up empty on tiny datasets; unlike a standalone reader that is not
        an error here (the host simply feeds nothing this epoch). Under
        live growth the ordinal range is ``_g_at(epoch)`` — the count in
        force when the epoch was (or will be) planned."""
        from petastorm_tpu.reader import Reader
        ordinals = list(range(self._g_at(epoch)))
        shard_seed = (None if self._seed is None
                      else int(self._seed) + int(epoch))
        plan: List[List[int]] = []
        for h in range(self._H):
            try:
                plan.append([int(o) for o in Reader._partition_row_groups(
                    ordinals, h, self._H, shard_seed)])
            except NoDataAvailableError:
                plan.append([])
        return plan

    def _load_resume_state(self, state: dict) -> None:
        if not isinstance(state, dict) or "hosts" not in state:
            raise ValueError(f"not a MeshDataLoader state_dict: {state!r}")
        if state.get("num_hosts") != self._H:
            raise ValueError(
                f"resume_state was saved over {state.get('num_hosts')} "
                f"hosts but this loader plans {self._H}; the per-host "
                f"shard cursors do not transfer")
        self._resume_epoch = int(state.get("epoch", 0))
        recorded = int(state.get("num_rowgroups", -1))
        growth = [(int(e), int(g)) for e, g in state.get("growth", [])]
        if growth:
            # Growth-aware cursor (docs/live_data.md): adopt the recorded
            # schedule so the resumed epoch replans over the range its
            # offsets indexed; groups that appeared while the job was down
            # join from the NEXT epoch.
            if growth[0][0] != 0 or growth[-1][1] != recorded:
                raise ValueError(f"malformed growth table in resume_state: "
                                 f"{growth} (final size must equal "
                                 f"num_rowgroups={recorded})")
            if self._G < recorded:
                raise ValueError(
                    f"resume_state records {recorded} row groups but the "
                    f"dataset now has {self._G}: live datasets only "
                    f"append — is this the right dataset?")
            from petastorm_tpu.utils.growth import GrowthSchedule
            probed = self._G
            self._g_schedule = GrowthSchedule(growth)
            self._G = recorded
            if probed > recorded:
                # While-down growth: extend from the first epoch past both
                # the cursor and the recorded schedule (the schedule
                # clamps) — nothing at or before it has been planned by
                # this loader.
                self._g_schedule.extend(self._resume_epoch + 1, probed)
                self._G = probed
        elif recorded >= 0 and self._G > recorded:
            # While-down growth on a cursor saved BEFORE the first
            # admission (no growth table yet): adopt it exactly like the
            # growth-aware branch — the resumed epoch replans over the
            # recorded range, the extra groups join from the next epoch.
            from petastorm_tpu.utils.growth import GrowthSchedule
            probed = self._G
            self._g_schedule = GrowthSchedule.base(recorded)
            self._g_schedule.extend(self._resume_epoch + 1, probed)
            logger.info(
                "mesh resume: dataset grew %d -> %d row groups while the "
                "job was down; the new ordinals join from epoch %d",
                recorded, probed, self._resume_epoch + 1)
        elif recorded != self._G:
            raise ValueError(
                f"resume_state was saved over {recorded} row groups but "
                f"this loader plans {self._G}; live datasets only append "
                f"— is this the right dataset? (docs/live_data.md)")
        hosts = state["hosts"]
        if isinstance(hosts, dict):
            offsets = [int(hosts.get(str(h), hosts.get(h, 0)))
                       for h in range(self._H)]
        else:
            offsets = [int(v) for v in hosts]
        if len(offsets) != self._H:
            raise ValueError(f"resume_state carries {len(offsets)} host "
                             f"cursors, need {self._H}")
        self._resume_offsets = offsets
        # Post-reshard cursors (docs/mesh.md): global ordinals already
        # delivered through RECOVERY sources; the resumed epoch excludes
        # them from every host's remaining plan instead of refusing.
        self._resume_recovered = sorted(
            int(o) for o in state.get("recovered", ()))

    # ------------------------------------------------------------ host side
    def kill_host(self, host: int) -> None:
        """Fault injection / failover drill: sever host ``host``'s input
        pipeline at its next item boundary. Parts already handed to the
        assembler stay in the stream (they were transported); the host's
        unread row-group range is resharded to survivors (or raises under
        ``strict``). Only meaningful while an epoch is live."""
        if self._multiprocess:
            raise NotImplementedError(
                "kill_host simulates in-process host loss; on a real "
                "multi-process slice kill the process")
        with self._cond:
            feeds = self._feeds
            if not feeds:
                raise RuntimeError("no live epoch to kill a host in")
            if not 0 <= host < len(feeds):
                raise ValueError(f"host {host} out of range [0, {len(feeds)})")
            feeds[host].killed.set()
            self._cond.notify_all()

    def _pull_host(self, feed: _HostFeed) -> None:
        try:
            while True:
                with self._cond:
                    while (not feed.sources and not self._epoch_done
                           and not feed.killed.is_set()
                           and not feed.stop.is_set()):
                        self._cond.wait(0.1)
                    if feed.stop.is_set():
                        return
                    if feed.killed.is_set():
                        raise _HostKilled(f"host {feed.idx} killed")
                    if not feed.sources:
                        return  # epoch complete
                    src = feed.sources.popleft()
                    feed.current = src
                self._run_source(feed, src)
                # Cleared only on clean completion: a raising source must
                # stay visible to _on_host_lost, whose reshard range is
                # current.ordinals past the delivered watermark.
                feed.current = None
        except _ConfigError as e:
            with self._cond:
                if self._fatal is None:
                    self._fatal = e.cause
                self._cond.notify_all()
        except BaseException as e:  # noqa: BLE001 - becomes the loss signal
            self._on_host_lost(feed, e)
        finally:
            with self._cond:
                feed.exhausted = True
                self._cond.notify_all()

    def _run_source(self, feed: _HostFeed, src: _Source) -> None:
        reader = self._factory(src.ordinals)
        src.reader = reader
        src.fifo = self._fifo and bool(reader.batched_output)
        rec = self.telemetry.recorder
        if rec.trace_enabled:
            # Propagate trace mode into the per-host reader's own registry
            # (already on when PETASTORM_TPU_TELEMETRY_TRACE is set — this
            # covers programmatic enable_trace() on the mesh registry; a
            # few construction-time ventilations may predate the flip).
            reader.telemetry.recorder.enable_trace()
        stage_base = {"decode": 0.0, "fetch": 0.0, "transport": 0.0,
                      "groups": -1}
        try:
            if getattr(reader, "row_materialization", "eager") == "lazy":
                # Batch-native pulls (docs/io.md): one ColumnarBatch per
                # row group off next_batch() — N-row parts instead of N
                # 1-row parts, same delivery-watermark semantics as any
                # non-FIFO row source (never-loss / bounded-dup).
                def _batches():
                    while True:
                        try:
                            yield reader.next_batch()
                        except StopIteration:
                            return
                it = _batches()
                per_group = True
            else:
                it = iter(reader)
                per_group = bool(reader.batched_output)
            # The ring is on by default and a span site fires per row group,
            # never per row: a row/windowed source (one item a row) spans
            # its pulls in trace mode only, where the operator asked.
            span_pulls = per_group or rec.trace_enabled
            while True:
                if feed.killed.is_set():
                    raise _HostKilled(f"host {feed.idx} killed")
                try:
                    if span_pulls:
                        # Per-host pull span: per-host reader epochs are
                        # single-epoch (e0), so the lineage id matches the
                        # reader's own spans for this global ordinal.
                        # Indexed by the GROUP watermark (src.counted),
                        # not the item count (src.pulled): row/windowed
                        # readers deliver many items per row group, and
                        # pulled would race past the ordinal list after
                        # the first group. Batched sources keep the two
                        # equal, so the common mesh config stays exact;
                        # other flavors are group-granular approximations.
                        ordinal = src.ordinals[min(src.counted,
                                                   len(src.ordinals) - 1)]
                        with traced_span(
                                "petastorm_tpu.mesh_pull", self.telemetry,
                                trace=f"e0:g{ordinal}", stage="pull",
                                track=f"h{feed.idx}:pull"):
                            item = next(it)
                    else:
                        item = next(it)
                except StopIteration:
                    break
                # Sync at GROUP granularity: src.counted advances once per
                # delivered row group, so row/windowed sources (many items
                # per group) don't pay the registry peeks per row.
                if src.counted != stage_base["groups"]:
                    stage_base["groups"] = src.counted
                    self._sync_host_stage_times(reader, stage_base)
                part = self._part_from_item(feed, src, item)
                if part is None:
                    # Empty after column selection: the group is delivered
                    # vacuously; the next part's watermark covers it.
                    src.pulled += 1
                    continue
                with self._cond:
                    while (len(feed.queue) >= self._host_queue_depth
                           and not feed.killed.is_set()
                           and not feed.stop.is_set()):
                        self._cond.wait(0.05)
                    if feed.stop.is_set():
                        return
                    if feed.killed.is_set():
                        raise _HostKilled(f"host {feed.idx} killed")
                    src.pulled += 1
                    part.delivered_after = src.delivered_groups()
                    src.safe_delivered = part.delivered_after
                    feed.queue.append(part)
                    self._c_host_rows[feed.idx].add(part.rows)
                    # Row-GROUP counter, for every reader flavor: advance
                    # by the delivered-groups watermark delta (1 per item
                    # on batched sources; row/window items only tick it as
                    # their group completes).
                    if part.delivered_after > src.counted:
                        self._c_host_groups[feed.idx].add(
                            part.delivered_after - src.counted)
                        src.counted = part.delivered_after
                    self._cond.notify_all()
            # Final stage-time sync: the last group's decode lands after
            # the loop's last boundary check.
            self._sync_host_stage_times(reader, stage_base)
            # Clean completion: every group of this source was delivered —
            # top up past any watermark lag (row readers confirm the last
            # group only after its final row is pulled).
            if src.counted < len(src.ordinals):
                self._c_host_groups[feed.idx].add(
                    len(src.ordinals) - src.counted)
                src.counted = len(src.ordinals)
            # Coverage-audit top-up (docs/observability.md "Data quality
            # plane"): a cleanly drained source delivered every planned
            # group EXCEPT quarantine skips, which are skip-accounted
            # (count level — a skip shifts the positional enqueue
            # accounting, so per-ordinal attribution past it would lie).
            quarantined = len(getattr(reader, "quarantine", ()) or ())
            epoch_idx = self._planned_through
            deliver_to = max(src.audited, len(src.ordinals) - quarantined)
            if deliver_to > src.audited:
                self._quality_ledger.record_delivered(
                    epoch_idx, src.ordinals[src.audited:deliver_to],
                    recovery=src.recovery)
                src.audited = deliver_to
            if quarantined:
                self._quality_ledger.record_skipped(epoch_idx, quarantined)
            with self._cond:
                self._source_done(1)
        finally:
            self._rollup_host_trace(feed.idx, reader)
            self._rollup_host_timeline(feed.idx, reader)
            self._rollup_host_spec(feed.idx, reader)
            self._rollup_host_quality(feed.idx, reader)
            try:
                reader.stop()
                reader.join()
            except Exception as e:  # noqa: BLE001 - teardown best-effort
                logger.warning("mesh host %d reader teardown failed: %s",
                               feed.idx, e)

    def _sync_host_stage_times(self, reader, base: Dict[str, float]) -> None:
        """Mirror one pull's worth of the host reader's stage self-times
        (decode / fetch / transport) into the mesh registry, so per-batch
        critical-path attribution can arbitrate the host plane against
        staging/assembly. Called once per delivered row group (the caller
        gates on the ``src.counted`` watermark) — noise next to a
        group-sized read+decode."""
        rt = getattr(reader, "telemetry", None)
        if rt is None:
            return
        # Decode is one measurement on every pool flavor: worker.decode_s
        # (mirrors CriticalPathAttributor._cumulative).
        cur = {"decode": rt.peek_histogram_sum("worker.decode_s"),
               "fetch": rt.peek_counter("io.readahead.fetch_s"),
               "transport": rt.peek_counter("transport.deserialize_s")}
        for key, value in cur.items():
            delta = value - base[key]
            if delta > 0:
                self._c_stage_sync[key].add(delta)
            base[key] = value

    def _rollup_host_trace(self, host: int, reader) -> None:
        """Cross-host(-boundary) trace rollup: drain the per-host reader's
        span ring into the mesh registry BEFORE the reader is torn down,
        re-tracked under an ``h{host}:`` prefix so the Chrome-trace export
        shows one process lane per host (docs/observability.md). Simulated
        hosts share this process's clock, so timestamps carry over; on a
        real slice each process exports its own snapshot and the trace CLI
        merges them."""
        rec = self.telemetry.recorder
        if not rec.trace_enabled:
            return
        src_rec = getattr(getattr(reader, "telemetry", None), "recorder",
                          None)
        if src_rec is None or not src_rec.enabled:
            return
        import dataclasses
        prefix = f"h{host}:"
        rec.ingest([
            dataclasses.replace(sp, track=prefix + (sp.track or sp.thread))
            for sp in src_rec.drain()])

    def _rollup_host_timeline(self, host: int, reader) -> None:
        """Cross-host timeline rollup: capture the per-host reader's
        timeline ring at source teardown (before the reader is gone) under
        its ``h{idx}`` federation key. A host that ran several sources
        (recovery after a reshard) contributes each source's ring in
        order; ``mesh_report()`` concatenates them
        (docs/observability.md "Federation")."""
        timeline = getattr(getattr(reader, "telemetry", None), "timeline",
                           None)
        if timeline is None:
            return
        # reader.stop() has not run yet — take the terminal window so the
        # captured ring covers the source's full life.
        sampler = getattr(reader, "_timeline_sampler", None)
        if sampler is not None:
            try:
                sampler.sample_once()
            except Exception:  # noqa: BLE001 - rollup best-effort
                pass
        with self._cond:
            self._host_timelines.setdefault(f"h{host}", []).append(
                timeline.as_dict())

    def _rollup_host_quality(self, host: int, reader) -> None:
        """Data-quality rollup (docs/observability.md "Data quality
        plane"): capture the per-host reader's quality report at source
        teardown under its ``h{idx}`` federation key — the mergeable
        profiles federate into one dataset profile in
        ``mesh_report()["quality"]``. A host that ran several sources
        keeps the newest report per source; profiles merge across them at
        report time."""
        try:
            rep = reader.quality_report()
        except Exception:  # noqa: BLE001 - rollup best-effort at teardown
            return
        if rep:
            with self._cond:
                self._host_quality.setdefault(f"h{host}", []).append(rep)

    def _rollup_host_spec(self, host: int, reader) -> None:
        """Explain-plane rollup (docs/observability.md "Explain plane"):
        capture the per-host reader's profiled operator graph at source
        teardown under its ``h{idx}`` federation key — the same keying as
        the PR 12 snapshot/timeline federation, so per-host graphs and
        per-host rates line up. A host that ran several sources (recovery
        after a reshard) keeps its NEWEST graph (the one describing the
        plan it finished on)."""
        try:
            spec = reader.explain_report()
        except Exception:  # noqa: BLE001 - rollup best-effort at teardown
            return
        with self._cond:
            self._host_specs[f"h{host}"] = spec

    def explain_report(self) -> dict:
        """Mesh explain rollup: every host reader's operator graph keyed
        ``h{idx}`` (captured at source teardown), a fleet bottleneck
        census over the per-host profiled verdicts, and the mesh-level
        assemble plane (hosts, the PR 8 critical-path dominant edge over
        the whole mesh pipeline)."""
        with self._cond:
            hosts = dict(self._host_specs)
        bottlenecks: Dict[str, int] = {}
        for rep in hosts.values():
            op = ((rep.get("profile") or {}).get("bottleneck")
                  or {}).get("operator")
            if op:
                bottlenecks[op] = bottlenecks.get(op, 0) + 1
        return {
            "schema_version": 1,
            "key_label": "host",
            "hosts": hosts,
            "bottlenecks": bottlenecks,
            "assemble": {
                "hosts": self._H,
                "multiprocess": self._multiprocess,
                "critical_path_dominant":
                    self.critical_path.report()["dominant"],
            },
        }

    def _record_fatal(self, exc: BaseException) -> None:
        if self.blackbox is not None:
            self.blackbox.write_bundle(type(exc).__name__, exc=exc)

    def _on_anomaly(self, detection: dict) -> None:
        if self.blackbox is not None:
            self.blackbox.write_bundle(
                f"anomaly_{detection.get('rule', '?')}")

    def _source_done(self, n: int) -> None:
        """Caller holds ``self._cond``."""
        self._outstanding -= n
        if self._outstanding <= 0:
            self._epoch_done = True
        self._cond.notify_all()

    def _on_host_lost(self, feed: _HostFeed, exc: BaseException) -> None:
        with self._cond:
            if feed.stop.is_set() or feed.lost is not None:
                return
            feed.lost = exc
            self._c_lost.add(1)
            self._lost_hosts.append({"host": feed.idx, "error": repr(exc)})
            self.telemetry.record_event(
                "mesh.host_lost", {"host": feed.idx,
                                   "error": repr(exc)[:200]})
            # The host's undelivered range: the in-flight source past its
            # delivered watermark, plus every source it never started.
            # Parts already in feed.queue were transported — the assembler
            # still drains them, so they are NOT re-read.
            undelivered: List[int] = []
            abandoned = 0
            if feed.current is not None:
                s = feed.current
                # safe_delivered, NOT delivered_groups(): the live
                # watermark may count a group pulled-but-never-enqueued
                # (dying with the puller) — slicing past it loses rows.
                undelivered.extend(s.ordinals[s.safe_delivered:])
                abandoned += 1
            for s in feed.sources:
                undelivered.extend(s.ordinals)
            abandoned += len(feed.sources)
            feed.sources.clear()
            survivors = [f for f in self._feeds
                         if f is not feed and f.lost is None
                         and f.thread is not None and not f.exhausted]
            if self._strict or not survivors:
                why = ("strict=True" if self._strict
                       else "no surviving hosts")
                fatal = MeshHostLostError(
                    f"host {feed.idx} lost mid-epoch with "
                    f"{len(undelivered)} row group(s) undelivered and "
                    f"elastic reshard unavailable ({why}): {exc!r}")
                fatal.__cause__ = (exc if isinstance(exc, Exception)
                                   else None)
                self._fatal = fatal
                self._source_done(abandoned)
                return
            # Elastic degradation: round-robin the range to survivors.
            # Cursors for the rest of this epoch stay VALID: recovery
            # deliveries fold into the cursor's ``recovered`` ordinal set
            # as they are consumed (_mark_consumed), so a checkpoint
            # describes the stream exactly — the flag below is provenance
            # only (docs/mesh.md "Cursors after a reshard").
            self._epoch_resharded = True
            buckets: List[List[int]] = [[] for _ in survivors]
            for i, o in enumerate(undelivered):
                buckets[i % len(survivors)].append(o)
            added = 0
            for f, bucket in zip(survivors, buckets):
                if bucket:
                    f.sources.append(_Source(bucket, recovery=True))
                    added += 1
            self._c_reshard.add(1)
            self.telemetry.record_event(
                "mesh.reshard", {"host": feed.idx,
                                 "reassigned_rowgroups": len(undelivered),
                                 "survivors": [f.idx for f in survivors]})
            logger.warning(
                "mesh host %d lost (%r); resharded %d row group(s) to %d "
                "survivor(s)", feed.idx, exc, len(undelivered),
                len(survivors))
            self._outstanding += added
            self._source_done(abandoned)

    # ------------------------------------------------------------- collation
    def _part_from_item(self, feed: _HostFeed, src: _Source,
                        item) -> Optional[_Part]:
        try:
            with self._collate_lock:
                if isinstance(item, ColumnarBatch):
                    # Batch-native plane (docs/io.md): lazy row readers
                    # hand whole decoded row groups over as columns — the
                    # per-host pull moves one batch, not N 1-row parts.
                    cols = self._lazy_batch_columns(item)
                elif hasattr(item, "_fields"):
                    if src.reader.batched_output:
                        cols = self._batchable_columns(item)
                    else:
                        cols = self._row_columns(item)
                elif isinstance(item, dict):
                    cols = self._ngram_columns(item)
                else:
                    raise TypeError(
                        f"mesh host reader yielded {type(item).__name__}; "
                        f"expected a namedtuple, a ColumnarBatch, or an "
                        f"NGram dense window dict")
                if not cols:
                    return None
                rows = len(next(iter(cols.values())))
                keys = frozenset(cols)
                if self._canonical_keys is None:
                    self._canonical_keys = keys
                elif keys != self._canonical_keys:
                    raise ValueError(
                        f"host {feed.idx} produced batchable columns "
                        f"{sorted(keys)} but the stream established "
                        f"{sorted(self._canonical_keys)}; make nullable/"
                        f"ragged columns uniform with a TransformSpec (or "
                        f"exclude them) so every host contributes the same "
                        f"fields")
        except (TypeError, ValueError) as e:
            # Deterministic layout/config errors fail the LOADER, not the
            # host: reassigning the groups would reproduce the same error
            # on every survivor (observed as a reshard storm otherwise).
            raise _ConfigError(e) from e
        return _Part(feed.idx, cols, rows, src)

    def _lazy_batch_columns(self, batch: ColumnarBatch) -> Dict[str, np.ndarray]:
        """One ColumnarBatch -> batchable columns, vectorized: ndarray
        columns pass straight through; list columns stack once (skipped
        with the standard warning when null/ragged/non-numeric, like the
        row path)."""
        cols, skipped = {}, []
        for name, col in batch.columns.items():
            if isinstance(col, np.ndarray):
                if col.dtype == object or col.dtype.kind in "US":
                    skipped.append(name)
                else:
                    cols[name] = col
                continue
            try:
                if any(v is None for v in col):
                    skipped.append(name)
                    continue
                arr = np.stack([np.asarray(v) for v in col])
            except (TypeError, ValueError):
                skipped.append(name)
                continue
            if arr.dtype == object or arr.dtype.kind in "US":
                skipped.append(name)
            else:
                cols[name] = arr
        self._warn_skipped_fields(skipped)
        return cols

    def _row_columns(self, row) -> Dict[str, np.ndarray]:
        """One row-reader namedtuple -> 1-row column dict (strings/objects
        drop with the standard skip warning, like the batched path)."""
        cols, skipped = {}, []
        for name in row._fields:
            value = getattr(row, name)
            if value is None:
                skipped.append(name)
                continue
            arr = np.asarray(value)
            if arr.dtype == object or arr.dtype.kind in "US":
                skipped.append(name)
                continue
            cols[name] = arr[None]
        self._warn_skipped_fields(skipped)
        return cols

    def _ngram_columns(self, window: dict) -> Dict[str, np.ndarray]:
        """One dense-NGram window dict -> 1-row column dict; the window
        axis becomes dim 1, exactly like DataLoader's dense collate."""
        first = next(iter(window.values()), None)
        if hasattr(first, "_fields"):
            raise ValueError(
                "mesh ingestion of NGram readers requires dense=True "
                "(column-major window assembly); per-offset namedtuple "
                "windows have no fixed-shape batch layout")
        cols = {}
        for name, value in window.items():
            arr = np.asarray(value)
            if arr.dtype == object:
                raise ValueError(
                    f"Field {name!r} contains nulls or ragged values; fill "
                    f"them with a TransformSpec before mesh batching")
            cols[name] = arr[None]
        return cols

    # ------------------------------------------------------------- assembly
    def _host_batches(self):
        epoch = self._resume_epoch
        offsets = self._resume_offsets
        recovered = self._resume_recovered
        passes = 0
        while self._num_epochs is None or passes < self._num_epochs:
            yield from self._epoch_batches(epoch, offsets, recovered)
            if self._closing:
                # close() abandoned the epoch above; starting the next
                # one's readers mid-teardown would race interpreter exit.
                return
            offsets = None
            recovered = ()
            passes += 1
            epoch += 1

    def _epoch_batches(self, epoch: int, offsets: Optional[List[int]],
                       recovered=()):
        with self._cond:
            # Growth admitted from here on lands at epoch + 1: this
            # epoch's per-host plans are being minted NOW.
            self._planned_through = epoch
        plan = self.epoch_plan(epoch)
        stop = threading.Event()
        self._epoch_resharded = bool(recovered)
        self._recovered_live = set(int(o) for o in recovered)
        self._live_stop = stop
        feeds = [_HostFeed(h, stop) for h in range(self._H)]
        active = ([feeds[self._local_host]] if self._multiprocess else feeds)
        with self._cond:
            self._feeds = feeds
            self._epoch_done = False
            self._fatal = None
            self._outstanding = 0
            for feed in active:
                base = offsets[feed.idx] if offsets else 0
                feed.primary_consumed = base
                if self._recovered_live:
                    # Post-reshard resume (docs/mesh.md): ordinals already
                    # delivered through recovery sources are excluded; the
                    # explicit position list keeps the plan watermark
                    # arithmetic exact across the holes.
                    positions = [i for i in range(base, len(plan[feed.idx]))
                                 if plan[feed.idx][i]
                                 not in self._recovered_live]
                    ordinals = [plan[feed.idx][i] for i in positions]
                    src = (_Source(ordinals, plan_base=base,
                                   plan_positions=positions)
                           if ordinals else None)
                else:
                    ordinals = plan[feed.idx][base:]
                    src = _Source(ordinals, plan_base=base) if ordinals \
                        else None
                if src is not None:
                    feed.sources.append(src)
                    self._outstanding += 1
            if self._outstanding == 0:
                self._epoch_done = True
        for feed in active:
            # EVERY active feed gets a puller — including ones whose plan
            # is empty (tiny dataset, resume-exhausted shard): an idle
            # puller parks on the condition until the epoch ends, and is
            # exactly what lets a reshard hand it a recovery source. A
            # source appended to a thread-less feed would never drain and
            # the epoch would hang on its outstanding count.
            feed.thread = threading.Thread(
                target=self._pull_host, args=(feed,), daemon=True,
                name=f"pt-mesh-host{feed.idx}")
            feed.thread.start()

        pool: collections.deque = collections.deque()
        pool_rows = 0
        self._epoch_t0 = time.perf_counter()
        try:
            while True:
                with self._cond:
                    if self._fatal is not None:
                        self._record_fatal(self._fatal)
                        raise self._fatal
                    if stop.is_set():
                        # close() mid-iteration: abandon the epoch NOW —
                        # blocked here the assembler would only learn of
                        # the closure at its next yield, which never comes
                        # once the consumer is gone (observed as a
                        # staging-thread join timeout + C++ abort at
                        # interpreter exit).
                        return
                    for feed in active:
                        while feed.queue:
                            part = feed.queue.popleft()
                            pool.append(part)
                            pool_rows += part.rows
                    self._cond.notify_all()  # wake depth-parked pullers
                    if pool_rows < self._step_rows:
                        pending = (self._outstanding > 0
                                   or any(f.queue for f in active))
                        if not pending:
                            break
                        t0 = time.perf_counter()
                        self._cond.wait(0.05)
                        waited = time.perf_counter() - t0
                        self._c_assemble_stall.add(waited)
                        for feed in active:
                            # Starved = live, nothing ready, and actually
                            # owed work (an idle empty-plan puller parked
                            # for potential recovery sources is not late).
                            if (not feed.queue and feed.lost is None
                                    and not feed.exhausted
                                    and (feed.current is not None
                                         or feed.sources)):
                                self._c_host_stall[feed.idx].add(waited)
                        self._update_skew()
                        continue
                while pool_rows >= self._step_rows:
                    self._batch_seq += 1
                    with traced_span("petastorm_tpu.mesh_assemble",
                                     self.telemetry,
                                     trace=f"b{self._batch_seq}",
                                     stage="assemble",
                                     track="assemble") as span:
                        batch = self._assemble(pool, self._step_rows, epoch)
                    self._c_assemble.add(span.duration_s)
                    pool_rows -= self._step_rows
                    yield batch
            if pool_rows:
                cols, consumed = self._take(pool, pool_rows)
                # Pad target is the per-step quota THIS process contributes
                # (== batch_size in single-process simulation, batch/H on a
                # multi-process slice); init guarantees drop_last/pad_last.
                tail = self._finalize_tail(cols, pool_rows,
                                           target_rows=self._step_rows)
                if tail is not None:
                    self._mark_consumed(consumed, epoch)
                    yield tail
            # Epoch complete: the safe cursor for anything staged after
            # this point is the NEXT epoch's start.
            self._pending_safe_state = self._cursor(epoch + 1, fresh=True)
        finally:
            self._c_wall.add(time.perf_counter() - self._epoch_t0)
            self._epoch_t0 = None
            self._live_stop = None
            self._teardown_feeds(feeds, stop)

    def _take(self, pool, n: int):
        """Consume ``n`` rows off the part pool; returns (columns dict,
        fully-consumed parts)."""
        chunks: Dict[str, list] = {}
        consumed = []
        need = n
        while need:
            part = pool[0]
            take = min(need, part.rows - part.off)
            for name, arr in part.cols.items():
                chunks.setdefault(name, []).append(
                    arr[part.off:part.off + take])
            part.off += take
            need -= take
            if part.off == part.rows:
                pool.popleft()
                consumed.append(part)
        return ({name: np.concatenate(parts) for name, parts
                 in chunks.items()}, consumed)

    def _assemble(self, pool, n: int, epoch: int) -> Dict[str, np.ndarray]:
        cols, consumed = self._take(pool, n)
        self._mark_consumed(consumed, epoch)
        return cols

    def _mark_consumed(self, consumed_parts, epoch: int) -> None:
        """Advance resume watermarks for fully consumed primary parts,
        fold recovery deliveries into the epoch's ``recovered`` set, and
        refresh the loss-safe cursor the staging thread snapshots."""
        for part in consumed_parts:
            src = part.source
            if src.recovery:
                # A reassigned range's delivered prefix is irrevocably in
                # the stream: record the global ordinals so the cursor
                # stays valid after the reshard (resume excludes them).
                self._recovered_live.update(
                    src.ordinals[:part.delivered_after])
            else:
                feed = self._feeds[part.host]
                feed.primary_consumed = max(
                    feed.primary_consumed,
                    src.plan_watermark(part.delivered_after))
            if part.delivered_after > src.audited:
                # Coverage audit: only the newly-consumed slice (the set
                # dedupes, but the redelivery counter must not see a
                # source's own prefix twice).
                self._quality_ledger.record_delivered(
                    epoch, src.ordinals[src.audited:part.delivered_after],
                    recovery=src.recovery)
                src.audited = part.delivered_after
        self._pending_safe_state = self._cursor(epoch)

    def _cursor(self, epoch: int, fresh: bool = False) -> dict:
        hosts = {str(f.idx): (0 if fresh else f.primary_consumed)
                 for f in (self._feeds if not self._multiprocess
                           else [self._feeds[self._local_host]])}
        state = {"mesh": True, "epoch": epoch, "hosts": hosts,
                 "num_rowgroups": self._G, "num_hosts": self._H}
        if self._g_schedule.grown:
            # Live growth (docs/live_data.md): the segment table pins
            # which ordinal range each epoch's shard plans covered, so a
            # resumed loader replans the cursor's epoch over the SAME
            # range even though the dataset kept growing.
            state["growth"] = [[e, g] for e, g in self._g_schedule.segments]
        if not fresh and self._recovered_live:
            # Reshard fold-in (docs/mesh.md): these global ordinals were
            # delivered by recovery sources; together with the per-host
            # plan positions they describe the stream exactly, so the
            # cursor stays checkpointable mid-epoch after a host loss.
            state["recovered"] = sorted(int(o)
                                        for o in self._recovered_live)
        if self._epoch_resharded and not fresh:
            state["resharded"] = True  # provenance, no longer a poison
        return state

    def state_dict(self):
        """Resume cursor of the delivered stream (see
        :meth:`LoaderBase.state_dict`). Valid **after a mid-epoch reshard
        too** (PR 7 refused these per-cursor): a lost host's reassigned
        row groups fold into the cursor as a ``recovered`` ordinal set —
        resume excludes them from every host's remaining plan, so the
        stream completes with no loss (bounded duplication at worst: a
        recovery range's non-FIFO watermark is conservative, exactly the
        contract single-reader resume has always had; docs/mesh.md
        "Cursors after a reshard")."""
        return super().state_dict()

    def _update_skew(self) -> None:
        stalls = [c.value for c in self._c_host_stall.values()]
        if stalls:
            self.telemetry.gauge("mesh.host_skew_s").set(
                round(max(stalls) - min(stalls), 6))

    def _teardown_feeds(self, feeds, stop: threading.Event) -> None:
        # The epoch's stop flag stays set FOREVER (each epoch owns a fresh
        # event): a puller wedged past the bounded join below still exits
        # at its next flag check instead of reading on against a revoked
        # signal and parking in the backpressure wait for process life.
        stop.set()
        with self._cond:
            self._cond.notify_all()
        for feed in feeds:
            if feed.thread is not None:
                feed.thread.join(10.0)
                if feed.thread.is_alive():
                    logger.warning(
                        "mesh host %d puller still busy at teardown (reader "
                        "stalled mid-group?); it exits at its next stop-"
                        "flag check", feed.idx)
        with self._cond:
            self._feeds = []

    # -------------------------------------------------------------- staging
    def _stage(self, host_batch: Dict[str, np.ndarray]) -> dict:
        device_cols, host_cols = sanitize_batch(host_batch, self._policy)
        self._last_staged_bytes = sum(v.nbytes for v in device_cols.values())
        staged = {name: self._make_global(value)
                  for name, value in device_cols.items()}
        if self._keep_host and host_cols:
            staged = {**staged, **host_cols}
        return staged

    def _dlpack_target_device(self):
        """CPU default device when dlpack shard adoption applies (the PR 6
        zero-copy staging path, extended to the per-device shard loop);
        None on accelerator backends where device_put is the real
        host->HBM copy."""
        if self._adopt_device is None:
            import jax
            # A backend that fails to come up raises here, on the staging
            # path, rather than reading as "not a CPU".
            self._adopt_device = (jax.local_devices()[0]
                                  if jax.default_backend() == "cpu"
                                  else False)
        return self._adopt_device or None

    def _make_global(self, value: np.ndarray):
        """One column -> one global sharded ``jax.Array``: slice the local
        rows per the sharding's addressable index map, place each shard on
        its device, and bind them under the global shape."""
        import jax
        gshape = (self._batch_size,) + value.shape[1:]
        idx_map = self._global_sharding.addressable_devices_indices_map(
            gshape)
        adopt_dev = self._dlpack_target_device()
        arrays = []
        put_shards, put_devices, put_slots = [], [], []
        for slot, (device, idx) in enumerate(idx_map.items()):
            shard = value[self._local_index(idx, gshape, value)]
            adopted = None
            if (self._adopt_enabled is not False and adopt_dev is not None
                    and device == adopt_dev
                    and LoaderBase._dlpack_adoptable(shard)):
                try:
                    adopted = jax.dlpack.from_dlpack(shard)
                    self._adopt_enabled = True
                except Exception:  # noqa: BLE001 - odd layout: copy path
                    self._adopt_enabled = False
            arrays.append(adopted)
            if adopted is None:
                put_shards.append(shard)
                put_devices.append(device)
                put_slots.append(slot)
        if put_shards:
            # ONE batched dispatch for every non-adopted shard.
            placed = jax.device_put(put_shards, put_devices)
            for slot, arr in zip(put_slots, placed):
                arrays[slot] = arr
        return jax.make_array_from_single_device_arrays(
            gshape, self._global_sharding, arrays)

    def _local_index(self, idx, gshape, value):
        """Translate a global index-map entry to this process's local row
        range (identity in single-process simulation)."""
        idx = idx if isinstance(idx, tuple) else (idx,)
        full = list(idx) + [slice(None)] * (value.ndim - len(idx))
        dim0 = full[0]
        start = 0 if dim0.start is None else dim0.start
        stop = gshape[0] if dim0.stop is None else dim0.stop
        lo, hi = start - self._row_offset, stop - self._row_offset
        if lo < 0 or hi > value.shape[0]:
            raise ValueError(
                f"mesh device order assigns global rows [{start}, {stop}) "
                f"to an addressable device, but this process holds "
                f"[{self._row_offset}, "
                f"{self._row_offset + value.shape[0]}); arrange the mesh "
                f"so each process's devices cover one contiguous batch "
                f"range (docs/mesh.md)")
        full[0] = slice(lo, hi)
        return tuple(full)

    def close(self):
        """Stop the staging pipeline AND the live epoch's host plane: the
        assembler may be parked waiting for parts (not at a yield), so the
        inherited stage-stop flag alone cannot reach it. Then WAIT for the
        pullers — each stops and joins its own readers on its own thread,
        and returning while that still runs lets interpreter exit race
        reader teardown (observed as a C++ abort at shutdown)."""
        self._closing = True
        with self._cond:
            if self._live_stop is not None:
                self._live_stop.set()
            feeds = list(self._feeds)
            self._cond.notify_all()
        super().close()
        for feed in feeds:
            if feed.thread is not None:
                feed.thread.join(15.0)
        if self._timeline_sampler is not None:
            # After the host plane joined: the terminal window covers the
            # last per-host counter syncs.
            self._timeline_sampler.stop()
        if self._telemetry_publisher is not None:
            # Last: the final (`bye`) window ships the fully-joined state.
            self._telemetry_publisher.stop()
            self._telemetry_publisher = None

    # ------------------------------------------------------------ reporting
    def mesh_report(self) -> dict:
        """Mesh ingestion health: per-host rows/row-groups/input-stall (and
        the stall as a fraction of ingest wall time), reshard/lost-host
        tallies, and the fastest-vs-slowest host skew."""
        wall = self._c_wall.value
        if self._epoch_t0 is not None:
            wall += time.perf_counter() - self._epoch_t0
        per_host = {}
        for h in self._host_ids:
            stall = self._c_host_stall[h].value
            per_host[h] = {
                "rows": int(self._c_host_rows[h].value),
                "rowgroups": int(self._c_host_groups[h].value),
                "input_stall_s": round(stall, 6),
                "input_stall_pct": (round(100.0 * stall / wall, 2)
                                    if wall else 0.0),
            }
        stalls = [v["input_stall_s"] for v in per_host.values()]
        report = {
            "hosts": self._H,
            "multiprocess": self._multiprocess,
            "ingest_wall_s": round(wall, 6),
            "reshard_events": int(self._c_reshard.value),
            "hosts_lost": self._lost_hosts,
            "host_skew_s": round(max(stalls) - min(stalls), 6) if stalls
            else 0.0,
            "per_host": per_host,
            # Per-batch critical-path attribution over the whole mesh
            # pipeline (fetch/decode/transport/shuffle/stage/assemble) —
            # the rollup the data-service dispatcher will export.
            "critical_path": self.critical_path.report(),
        }
        timeline = self._federated_timeline()
        if timeline is not None:
            report["timeline"] = timeline
        report["quality"] = self.quality_report()
        return report

    def quality_report(self) -> dict:
        """Mesh data-quality rollup (docs/observability.md "Data quality
        plane"): the coverage auditor's per-epoch manifests (every global
        row-group ordinal delivered or skip-accounted exactly once,
        reshard redeliveries counted), plus — when host readers run with
        ``quality=True`` — their captured profiles federated into ONE
        dataset profile (the merge is exact: Chan moments, histogram
        bucket sums, KMV unions) with per-host drift maxima."""
        out = {"coverage": self._quality_ledger.report()}
        with self._cond:
            hosts = {k: list(reps) for k, reps in self._host_quality.items()}
        if hosts:
            from petastorm_tpu.quality import DatasetProfile
            merged = DatasetProfile()
            per_host = {}
            drift_max = 0.0
            for key in sorted(hosts):
                host_drift = 0.0
                host_rows = 0
                for rep in hosts[key]:
                    prof = rep.get("profile")
                    if prof:
                        merged.merge(DatasetProfile.from_dict(prof))
                    host_rows += rep.get("rows_observed", 0)
                    host_drift = max(host_drift,
                                     (rep.get("drift") or {}).get("max", 0.0))
                per_host[key] = {"rows_observed": host_rows,
                                 "drift_max": round(host_drift, 6)}
                drift_max = max(drift_max, host_drift)
            out["profile"] = merged.to_dict()
            out["per_host"] = per_host
            out["drift_max"] = round(drift_max, 6)
        return out

    def _federated_timeline(self) -> Optional[dict]:
        """ONE fleet-level timeline rollup (docs/observability.md
        "Federation"): the mesh registry's own ring (whose
        ``mesh.host{h}.rows`` counter family yields per-host rows/s
        series) federated with every captured per-host reader timeline,
        keyed ``mesh`` / ``h{idx}`` — fleet-sum and skew series included.
        None when the ops plane is off."""
        from petastorm_tpu.telemetry.federation import federate_timelines
        from petastorm_tpu.telemetry.timeseries import concat_timeline_dicts
        with self._cond:
            members = {key: concat_timeline_dicts(parts)
                       for key, parts in self._host_timelines.items()}
        if self._timeline is not None:
            members["mesh"] = self._timeline.as_dict()
        if not members:
            return None
        return federate_timelines(members, key_label="host")
