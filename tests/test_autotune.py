"""Autotune subsystem tests: memory budget, actuators, the feedback
controller (convergence + no-oscillation-under-faults), the in-memory
decoded row-group cache, and the reader/loader integration — including the
autotune x resilience interplay (quarantined row groups never enter the
cache; fault-induced stalls hold every knob)."""
import pickle
import threading
import time

import numpy as np
import pytest

from petastorm_tpu.autotune import (Actuator, AutotuneConfig,
                                    AutotuneController, InMemoryRowGroupCache,
                                    MemoryBudget, PrefetchDepthActuator,
                                    ShuffleTargetActuator,
                                    VentilatorDepthActuator,
                                    WorkerConcurrencyActuator, payload_nbytes)
from petastorm_tpu.reader import make_reader
from petastorm_tpu.resilience import FaultPlan, FaultSpec, InjectedIOError
from petastorm_tpu.telemetry import TelemetryRegistry
from petastorm_tpu.workers_pool.thread_pool import ConcurrencyGate

pytestmark = pytest.mark.autotune


# ---------------------------------------------------------------------------
# payload_nbytes / MemoryBudget
# ---------------------------------------------------------------------------
class TestPayloadNbytes:
    def test_numpy_reports_buffer_size(self):
        a = np.zeros((10, 10), dtype=np.float32)
        assert payload_nbytes(a) == 400

    def test_bytes_str_and_scalars(self):
        assert payload_nbytes(b"abcd") == 4
        assert payload_nbytes("abcd") == 4
        assert payload_nbytes(None) == 32
        assert payload_nbytes(7) == 32

    def test_containers_sum_elements(self):
        d = {"a": np.zeros(8, dtype=np.int64), "b": b"xy"}
        assert payload_nbytes(d) >= 64 + 2
        assert payload_nbytes([b"xy", b"zw"]) >= 4

    def test_unrecognized_falls_back_to_pickle_len(self):
        class Blob:
            x = 1
        assert payload_nbytes(Blob()) > 0


class TestMemoryBudget:
    def test_validation(self):
        with pytest.raises(ValueError):
            MemoryBudget(0)

    def test_reserve_release_pressure(self):
        b = MemoryBudget(100)
        assert b.reserve(60)
        assert not b.reserve(50)
        assert b.reserve(40)
        assert b.pressure == 1.0
        b.release(50)
        assert b.used == 50
        assert b.available == 50
        assert b.would_fit(50)
        assert not b.would_fit(51)

    def test_forced_reservation_overshoots_visibly(self):
        b = MemoryBudget(100)
        assert b.reserve(90)
        assert b.reserve(20, force=True)
        assert b.pressure > 1.0

    def test_release_floors_at_zero_and_rejects_negative(self):
        b = MemoryBudget(10)
        b.release(5)
        assert b.used == 0
        with pytest.raises(ValueError):
            b.reserve(-1)
        with pytest.raises(ValueError):
            b.release(-1)

    def test_telemetry_gauges(self):
        reg = TelemetryRegistry()
        b = MemoryBudget(100, telemetry=reg)
        b.reserve(30)
        gauges = reg.snapshot()["gauges"]
        assert gauges["budget.capacity_bytes"] == 100
        assert gauges["budget.used_bytes"] == 30


# ---------------------------------------------------------------------------
# Actuators
# ---------------------------------------------------------------------------
class _FakeActuator(Actuator):
    """Records every applied value; no underlying component."""

    def __init__(self, name="fake", lo=1, hi=10, initial=4, telemetry=None):
        self.applied = []
        super().__init__(name, lo, hi, initial, telemetry=telemetry)

    def _apply(self, value):
        self.applied.append(value)


class TestActuator:
    def test_invalid_range(self):
        with pytest.raises(ValueError, match="lo"):
            _FakeActuator(lo=5, hi=2)

    def test_set_clamps_and_applies(self):
        a = _FakeActuator(lo=2, hi=6, initial=4)
        assert a.set(100) == 6
        assert a.set(-3) == 2
        assert a.applied == [6, 2]
        assert a.at_min and not a.at_max

    def test_idempotent_set_records_nothing(self):
        reg = TelemetryRegistry()
        a = _FakeActuator(initial=4, telemetry=reg)
        a.set(4)
        assert a.applied == []
        assert reg.snapshot()["counters"]["autotune.adjustments_total"] == 0

    def test_nudge_and_telemetry_mirror(self):
        reg = TelemetryRegistry()
        a = _FakeActuator(initial=4, telemetry=reg)
        assert a.nudge(+2) == 6
        assert a.nudge(-10) == 1
        snap = reg.snapshot()
        assert snap["gauges"]["autotune.fake"] == 1
        assert snap["counters"]["autotune.adjustments_total"] == 2

    def test_component_actuators_drive_their_knobs(self):
        gate = ConcurrencyGate(4)
        wc = WorkerConcurrencyActuator(gate, 4)
        wc.set(2)
        assert gate.limit == 2
        wc.set(100)
        assert gate.limit == 4  # clamped to workers_count

        class FakeVent:
            max_inflight = 8

            def set_max_inflight(self, n):
                self.max_inflight = n
        vent = FakeVent()
        va = VentilatorDepthActuator(vent)
        assert (va.lo, va.hi) == (2, 32)
        va.nudge(+100)
        assert vent.max_inflight == 32

        class FakeLoader:
            prefetch_depth = 2

            def set_prefetch_depth(self, n):
                self.prefetch_depth = n
        pa = PrefetchDepthActuator(FakeLoader())
        assert (pa.lo, pa.hi) == (1, 8)

    def test_shuffle_actuator_floor_respects_min_target(self):
        class FakeBuf:
            capacity = 100
            min_target = 60

            def set_target_capacity(self, n):
                self.capacity_set = n
        buf = FakeBuf()
        sa = ShuffleTargetActuator(buf)
        assert (sa.lo, sa.hi) == (60, 100)
        sa.set(1)
        assert buf.capacity_set == 60


# ---------------------------------------------------------------------------
# ConcurrencyGate
# ---------------------------------------------------------------------------
class TestConcurrencyGate:
    def test_limit_floor_is_one(self):
        gate = ConcurrencyGate(0)
        assert gate.limit == 1
        gate.set_limit(-5)
        assert gate.limit == 1

    def test_acquire_release_accounting(self):
        gate = ConcurrencyGate(2)
        stop = threading.Event()
        assert gate.acquire(stop)
        assert gate.active == 1
        gate.release()
        assert gate.active == 0
        gate.release()  # releasing without a slot is a no-op
        assert gate.active == 0

    def test_limit_enforced_and_raise_wakes_parked(self):
        gate = ConcurrencyGate(1)
        stop = threading.Event()
        acquired = []

        def worker():
            if gate.acquire(stop):
                acquired.append(threading.get_ident())

        assert gate.acquire(stop)  # main thread takes the only slot
        t = threading.Thread(target=worker, daemon=True)
        t.start()
        time.sleep(0.1)
        assert not acquired  # parked behind the limit
        gate.set_limit(2)     # raising the limit admits the parked worker
        t.join(timeout=2)
        assert len(acquired) == 1

    def test_stop_unblocks_parked_acquire(self):
        gate = ConcurrencyGate(1)
        stop = threading.Event()
        assert gate.acquire(stop)
        result = []
        t = threading.Thread(
            target=lambda: result.append(gate.acquire(stop)), daemon=True)
        t.start()
        stop.set()
        t.join(timeout=2)
        assert result == [False]

    def test_yield_if_held_releases_and_reacquires(self):
        gate = ConcurrencyGate(1)
        stop = threading.Event()
        assert not gate.yield_if_held()  # no slot held yet
        assert gate.acquire(stop)
        assert gate.yield_if_held()
        assert gate.active == 0
        assert gate.acquire(stop)  # re-acquire the freed slot
        gate.release()


# ---------------------------------------------------------------------------
# AutotuneController
# ---------------------------------------------------------------------------
def _controller(reg=None, budget=None, hysteresis=1, cooldown=0, **kw):
    reg = reg or TelemetryRegistry()
    cfg = AutotuneConfig(hysteresis=hysteresis, cooldown_ticks=cooldown, **kw)
    return AutotuneController(reg, cfg, budget=budget), reg


class TestControllerDiagnosis:
    def test_config_validation(self):
        with pytest.raises(ValueError):
            AutotuneConfig(hysteresis=0)
        with pytest.raises(ValueError):
            AutotuneConfig(cooldown_ticks=-1)
        with pytest.raises(ValueError):
            AutotuneConfig(memory_high_watermark=2.0)
        with pytest.raises(ValueError):
            AutotuneConfig(memory_budget_bytes=0)

    def test_loader_stall_counters_drive_verdicts(self):
        ctrl, reg = _controller()
        reg.counter("loader.next_host_bound").add(5)
        assert ctrl.tick() == "producer_bound"
        reg.counter("loader.next_device_bound").add(9)
        assert ctrl.tick() == "consumer_bound"
        reg.counter("loader.next_balanced").add(9)
        assert ctrl.tick() == "balanced"

    def test_idle_without_signals(self):
        ctrl, _reg = _controller()
        assert ctrl.tick() == "idle"

    def test_queue_shape_fallback(self):
        ctrl, reg = _controller()
        reg.gauge("pool.results_queue_capacity").set(10)
        depth = reg.gauge("pool.results_queue_depth")
        backlog = reg.gauge("ventilator.backlog")
        rows = reg.counter("reader.rows")

        rows.add(100)
        depth.set(0)
        backlog.set(4)  # consumer found an empty queue, work in flight
        assert ctrl.tick() == "producer_bound"

        rows.add(100)
        depth.set(10)
        assert ctrl.tick() == "consumer_bound"

        rows.add(100)
        depth.set(5)
        assert ctrl.tick() == "balanced"

    def test_fault_deltas_override_stall_signal(self):
        ctrl, reg = _controller()
        reg.counter("loader.next_host_bound").add(5)
        reg.counter("resilience.retries_total").add(1)
        assert ctrl.tick() == "fault_hold"
        # Faults cleared, stall persists: back to shape diagnosis.
        reg.counter("loader.next_host_bound").add(5)
        assert ctrl.tick() == "producer_bound"

    def test_memory_pressure_beats_stall_shape(self):
        budget = MemoryBudget(100)
        budget.reserve(95)
        ctrl, reg = _controller(budget=budget)
        reg.counter("loader.next_host_bound").add(5)
        assert ctrl.tick() == "memory_pressure"


class TestControllerActuation:
    def test_hysteresis_defers_action(self):
        ctrl, reg = _controller(hysteresis=3)
        act = ctrl.register(_FakeActuator("worker_concurrency"))
        for i in range(2):
            reg.counter("loader.next_host_bound").add(5)
            ctrl.tick()
            assert act.value == 4, f"acted too early on tick {i}"
        reg.counter("loader.next_host_bound").add(5)
        ctrl.tick()
        assert act.value == 5
        assert ctrl.history == [(3, "worker_concurrency", 4, 5,
                                 "producer_bound")]

    def test_cooldown_holds_after_adjustment(self):
        ctrl, reg = _controller(hysteresis=1, cooldown=2)
        act = ctrl.register(_FakeActuator("worker_concurrency"))
        for _ in range(4):
            reg.counter("loader.next_host_bound").add(5)
            ctrl.tick()
        # tick1 acts, ticks 2-3 cool down, tick 4 acts again.
        assert act.value == 6
        assert [h[0] for h in ctrl.history] == [1, 4]

    def test_producer_bound_escalation_ladder(self):
        ctrl, reg = _controller()
        wc = ctrl.register(_FakeActuator("worker_concurrency", lo=1, hi=4,
                                         initial=4))  # already at max
        vent = ctrl.register(_FakeActuator("ventilate_ahead", lo=1, hi=8,
                                           initial=8))  # also at max
        pf = ctrl.register(_FakeActuator("prefetch_depth", lo=1, hi=4,
                                         initial=2))
        reg.counter("loader.next_host_bound").add(5)
        ctrl.tick()
        # Saturated knobs are skipped; the ladder lands on prefetch.
        assert (wc.value, vent.value, pf.value) == (4, 8, 3)

    def test_consumer_bound_shrinks_prefetch(self):
        ctrl, reg = _controller()
        pf = ctrl.register(_FakeActuator("prefetch_depth", lo=1, hi=4,
                                         initial=3))
        reg.counter("loader.next_device_bound").add(9)
        ctrl.tick()
        assert pf.value == 2

    def test_consumer_bound_sheds_concurrency_once_prefetch_floored(self):
        ctrl, reg = _controller()
        pf = ctrl.register(_FakeActuator("prefetch_depth", lo=1, hi=4,
                                         initial=1))  # already at floor
        wc = ctrl.register(_FakeActuator("worker_concurrency", lo=1, hi=4,
                                         initial=4))
        reg.counter("loader.next_device_bound").add(9)
        ctrl.tick()
        assert (pf.value, wc.value) == (1, 3)
        # ...and a later producer_bound streak raises it back: two-way knob.
        reg.counter("loader.next_host_bound").add(9)
        ctrl.tick()
        assert wc.value == 4

    def test_memory_pressure_backs_off_every_memory_knob(self):
        budget = MemoryBudget(100)
        budget.reserve(99)
        ctrl, _reg = _controller(budget=budget)
        sh = ctrl.register(_FakeActuator("shuffle_target", lo=10, hi=1000,
                                         initial=1000))
        pf = ctrl.register(_FakeActuator("prefetch_depth", lo=1, hi=4,
                                         initial=4))
        vent = ctrl.register(_FakeActuator("ventilate_ahead", lo=1, hi=8,
                                           initial=8))
        ctrl.tick()
        assert sh.value == 500   # halved
        assert pf.value == 3
        assert vent.value == 6

    def test_no_oscillation_under_fault_induced_stalls(self):
        """The acceptance guarantee: a window with resilience activity holds
        every knob, even when the faults also make the pipeline look
        producer-bound — and the fault ticks reset the streak so the stale
        trend cannot act the moment faults clear."""
        ctrl, reg = _controller(hysteresis=2)
        act = ctrl.register(_FakeActuator("worker_concurrency"))
        reg.counter("loader.next_host_bound").add(5)
        ctrl.tick()  # streak producer_bound = 1
        for _ in range(10):
            reg.counter("loader.next_host_bound").add(5)
            reg.counter("resilience.retries_total").add(2)
            assert ctrl.tick() == "fault_hold"
        assert act.value == 4
        assert ctrl.history == []
        # One clean producer-bound tick must NOT act (streak was reset).
        reg.counter("loader.next_host_bound").add(5)
        assert ctrl.tick() == "producer_bound"
        assert ctrl.history == []
        counters = reg.snapshot()["counters"]
        assert counters["autotune.verdict_fault_hold"] == 10
        assert counters["autotune.ticks_total"] == 12

    def test_convergence_on_steady_workload(self):
        """Acceptance: actuator values stabilize within a bounded number of
        ticks on a steady workload, every adjustment recorded in autotune.*
        telemetry."""
        reg = TelemetryRegistry()
        ctrl, _ = _controller(reg=reg, hysteresis=2, cooldown=1)
        wc = ctrl.register(_FakeActuator("worker_concurrency", lo=1, hi=8,
                                         initial=2, telemetry=reg))
        # Steady producer-bound workload: concurrency can only rise to its
        # ceiling, after which the ladder has nothing else registered and
        # every later tick holds — that plateau IS convergence.
        values = []
        for _ in range(40):
            reg.counter("loader.next_host_bound").add(10)
            ctrl.tick()
            values.append(wc.value)
        assert wc.value == 8
        settle = values.index(8)
        assert settle <= 20, f"did not converge within bound: {values}"
        assert values[settle:] == [8] * (len(values) - settle)
        # Every adjustment is visible in history AND telemetry.
        assert [h[3] for h in ctrl.history] == [3, 4, 5, 6, 7, 8]
        snap = reg.snapshot()
        assert snap["counters"]["autotune.adjustments_total"] == 6
        assert snap["gauges"]["autotune.worker_concurrency"] == 8
        report = ctrl.report()
        assert report["ticks"] == 40
        assert len(report["adjustments"]) == 6
        assert report["actuators"]["worker_concurrency"]["value"] == 8

    def test_background_thread_ticks_and_stops(self):
        ctrl, reg = _controller(interval_s=0.01)
        reg.counter("loader.next_balanced").add(1)
        ctrl.start()
        assert ctrl.start() is ctrl  # idempotent
        deadline = time.monotonic() + 5
        while ctrl.report()["ticks"] < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        ctrl.stop()
        ctrl.stop()  # idempotent
        ticks = ctrl.report()["ticks"]
        assert ticks >= 3
        time.sleep(0.05)
        assert ctrl.report()["ticks"] == ticks  # really stopped

    def test_unregister_mid_flight(self):
        ctrl, reg = _controller()
        ctrl.register(_FakeActuator("prefetch_depth", initial=3))
        ctrl.unregister("prefetch_depth")
        assert ctrl.actuator("prefetch_depth") is None
        reg.counter("loader.next_device_bound").add(5)
        ctrl.tick()  # no actuator: nothing to act on, no crash
        assert ctrl.history == []


# ---------------------------------------------------------------------------
# InMemoryRowGroupCache
# ---------------------------------------------------------------------------
class TestInMemoryRowGroupCache:
    def test_miss_fills_then_hits(self):
        cache = InMemoryRowGroupCache(1 << 20)
        calls = []

        def fill():
            calls.append(1)
            return {"col": np.arange(10)}
        v1 = cache.get("k", fill)
        v2 = cache.get("k", fill)
        assert len(calls) == 1
        assert v1 is v2
        assert "k" in cache and len(cache) == 1

    def test_lru_eviction_order(self):
        entry = np.zeros(400, dtype=np.uint8)
        cache = InMemoryRowGroupCache(1000)
        slow = 1.0  # uniform fill cost: admission is pure LRU here

        def put(key):
            cache._admit(key, entry, fill_s=slow)
        put("a")
        put("b")
        cache.get("a", lambda: entry)  # refresh recency of a
        put("c")                       # displaces b (LRU), not a
        assert "a" in cache and "c" in cache and "b" not in cache

    def test_budget_accounting_and_release_on_evict(self):
        cache = InMemoryRowGroupCache(1000)
        cache._admit("a", np.zeros(600, dtype=np.uint8), fill_s=0.1)
        assert cache.budget.used == 600
        cache._admit("b", np.zeros(600, dtype=np.uint8), fill_s=0.2)
        assert cache.budget.used == 600  # a evicted, bytes released
        assert cache.keys() == ["b"]

    def test_cost_aware_admission_protects_slow_fills(self):
        cache = InMemoryRowGroupCache(1000)
        cache._admit("slow", np.zeros(800, dtype=np.uint8), fill_s=5.0)
        # A fast-to-fill candidate must not displace a slow-to-fill one.
        cache._admit("fast", np.zeros(800, dtype=np.uint8), fill_s=0.001)
        assert "slow" in cache and "fast" not in cache
        # A slower candidate may.
        cache._admit("slower", np.zeros(800, dtype=np.uint8), fill_s=9.0)
        assert "slower" in cache and "slow" not in cache

    def test_own_size_limit_enforced_under_larger_shared_budget(self):
        """When the Reader repoints ``cache.budget`` at a bigger shared
        ledger, the cache must still cap residency at its own
        size_limit_bytes — LRU-evicting within it, not growing to the
        ledger."""
        shared = MemoryBudget(100_000)
        cache = InMemoryRowGroupCache(1000, budget=shared)
        for i in range(5):
            cache._admit(f"k{i}", np.zeros(400, dtype=np.uint8), fill_s=0.1)
        assert cache.size_bytes() <= 1000
        assert len(cache) == 2  # LRU held at the cache's own limit
        assert shared.used == cache.size_bytes()  # ledger stays honest

    def test_oversized_payload_rejected(self):
        cache = InMemoryRowGroupCache(100)
        v = cache.get("big", lambda: np.zeros(500, dtype=np.uint8))
        assert v.nbytes == 500  # still returned to the caller
        assert len(cache) == 0

    def test_raising_fill_caches_nothing(self):
        cache = InMemoryRowGroupCache(1 << 20)

        def bad_fill():
            raise IOError("permanent corruption")
        with pytest.raises(IOError):
            cache.get("k", bad_fill)
        assert len(cache) == 0
        ok = cache.get("k", lambda: b"fine")
        assert ok == b"fine"

    def test_cache_fill_fault_site(self):
        plan = FaultPlan([FaultSpec(site="cache.fill", at=1)])
        cache = InMemoryRowGroupCache(1 << 20, fault_plan=plan)
        with pytest.raises(InjectedIOError):
            cache.get("k", lambda: b"v")
        assert len(cache) == 0  # injected fault never poisons the cache
        assert cache.get("k", lambda: b"v") == b"v"

    def test_telemetry_counters(self):
        reg = TelemetryRegistry()
        cache = InMemoryRowGroupCache(1000, telemetry=reg)

        def slow_fill():
            # Measurably slower than a's instant fill, so cost-aware
            # admission deterministically allows displacing it.
            time.sleep(0.01)
            return np.zeros(600, dtype=np.uint8)
        cache.get("a", lambda: np.zeros(600, dtype=np.uint8))
        cache.get("a", lambda: np.zeros(600, dtype=np.uint8))
        cache.get("b", slow_fill)  # evicts a
        c = reg.snapshot()["counters"]
        assert c["cache.mem.hits"] == 1
        assert c["cache.mem.misses"] == 2
        assert c["cache.mem.inserts"] == 2
        assert c["cache.mem.evictions"] == 1
        gauges = reg.snapshot()["gauges"]
        assert gauges["cache.mem.entries"] == 1
        assert gauges["cache.mem.bytes"] == 600

    def test_stats_and_cleanup(self):
        cache = InMemoryRowGroupCache(1000)
        cache.get("a", lambda: np.zeros(100, dtype=np.uint8))
        s = cache.stats()
        assert s["entries"] == 1
        assert s["resident_bytes"] == 100
        assert s["budget_used_bytes"] == 100
        cache.cleanup()
        assert len(cache) == 0
        assert cache.budget.used == 0

    def test_pickles_as_empty_cache_with_same_policy(self):
        plan = FaultPlan([FaultSpec(site="cache.fill", at=99)])
        cache = InMemoryRowGroupCache(12345, fault_plan=plan)
        cache.get("a", lambda: b"payload")
        clone = pickle.loads(pickle.dumps(cache))
        assert len(clone) == 0
        assert clone._size_limit == 12345
        assert clone._fault_plan is not None
        clone.get("b", lambda: b"v")  # fully functional post-unpickle
        assert "b" in clone

    def test_container_cells_copied_on_hit(self):
        """User codecs may decode to mutable containers (lists/dicts): the
        hit path must deep-copy them, not just ndarrays, or an in-place
        transform writes through to the cache."""
        from petastorm_tpu.reader_impl.row_reader_worker import \
            RowReaderWorker
        cols = {"a": [[1, 2], [3, 4]], "b": [{"k": 1}, {"k": 2}],
                "c": ["imm", "utable"]}
        row = RowReaderWorker._rows_from_decoded(
            object.__new__(RowReaderWorker), cols, [0])[0]
        row["a"].append(99)
        row["b"]["k"] = -1
        assert cols["a"][0] == [1, 2]
        assert cols["b"][0] == {"k": 1}
        assert row["c"] is cols["c"][0]  # immutables pass by reference

    def test_concurrent_fillers_one_entry(self):
        cache = InMemoryRowGroupCache(1 << 20)
        barrier = threading.Barrier(4)

        def fill():
            barrier.wait(timeout=5)
            return np.zeros(100, dtype=np.uint8)
        threads = [threading.Thread(target=cache.get, args=("k", fill))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert len(cache) == 1
        assert cache.budget.used == 100


# ---------------------------------------------------------------------------
# Reader integration
# ---------------------------------------------------------------------------
class TestReaderIntegration:
    def test_memory_cache_excludes_disk_cache(self, synthetic_dataset,
                                               tmp_path):
        with pytest.raises(ValueError, match="mutually exclusive"):
            make_reader(synthetic_dataset.url, cache_type="local-disk",
                        cache_location=str(tmp_path / "cache"),
                        cache_size_limit=1 << 20,
                        cache_row_size_estimate=1024,
                        memory_cache_size_bytes=1 << 20)

    def test_multi_epoch_cache_hits_and_identical_samples(
            self, synthetic_dataset):
        def read(cache_bytes):
            with make_reader(synthetic_dataset.url, num_epochs=2,
                             shuffle_row_groups=False,
                             reader_pool_type="thread", workers_count=2,
                             memory_cache_size_bytes=cache_bytes) as r:
                rows = {}
                for row in r:
                    rows.setdefault(row.id, row)
                counters = r.telemetry.snapshot()["counters"]
            return rows, counters

        cached, counters = read(1 << 30)
        assert counters["cache.mem.hits"] > 0          # epoch 2 from RAM
        assert counters["cache.mem.misses"] == counters["cache.mem.inserts"]
        uncached, _ = read(None)
        assert sorted(cached) == sorted(uncached)
        for rid in cached:
            a, b = cached[rid], uncached[rid]
            np.testing.assert_array_equal(a.image_png, b.image_png)
            np.testing.assert_array_equal(a.matrix, b.matrix)
            np.testing.assert_array_equal(a.varlen, b.varlen)
            assert a.partition_key == b.partition_key
            assert a.decimal_col == b.decimal_col

    def test_epoch2_faster_on_synthetic_store(self, synthetic_dataset):
        """Soft perf sanity: the cached epoch must never be slower than the
        decode-everything one."""
        with make_reader(synthetic_dataset.url, num_epochs=3,
                         shuffle_row_groups=False, reader_pool_type="dummy",
                         memory_cache_size_bytes=1 << 30) as r:
            rows_per_epoch = len(synthetic_dataset.rows)
            n, t0, epochs = 0, time.perf_counter(), []
            for _ in r:
                n += 1
                if n % rows_per_epoch == 0:
                    epochs.append(time.perf_counter() - t0)
                    t0 = time.perf_counter()
        assert len(epochs) == 3
        assert min(epochs[1], epochs[2]) < epochs[0] * 1.25

    def test_quarantined_rowgroup_never_enters_cache(self, synthetic_dataset):
        """Autotune x resilience acceptance: under injected rowgroup.read
        faults in degraded mode, the quarantined row group's key never
        appears in the memory cache (a raising fill caches nothing)."""
        plan = FaultPlan([FaultSpec(site="rowgroup.read", kind="corruption",
                                    rate=1.0, key_substring="part-00000")])
        with make_reader(synthetic_dataset.url, num_epochs=2,
                         shuffle_row_groups=False, reader_pool_type="thread",
                         workers_count=2, memory_cache_size_bytes=1 << 30,
                         degraded_mode=True, fault_plan=plan) as r:
            ids = sorted({row.id for row in r})
            report = r.quarantine_report()
            cache_keys = r._cache.keys()
        assert report["quarantined"] > 0
        assert all("part-00000" not in k for k in cache_keys)
        assert cache_keys  # healthy row groups ARE cached
        assert len(ids) < len(synthetic_dataset.rows)  # pieces were skipped

    def test_cache_fill_fault_with_degraded_mode(self, synthetic_dataset):
        plan = FaultPlan([FaultSpec(site="cache.fill", at=1)])
        with make_reader(synthetic_dataset.url, num_epochs=1,
                         shuffle_row_groups=False, reader_pool_type="dummy",
                         memory_cache_size_bytes=1 << 30,
                         degraded_mode=True, fault_plan=plan) as r:
            ids = sorted({row.id for row in r})
            report = r.quarantine_report()
            cache_keys = r._cache.keys()
        # Exactly one fill was injected: that row group is skipped (not
        # retried into the cache with a corrupt payload) or retried clean —
        # either way no cache entry ever held a poisoned fill.
        assert len(cache_keys) >= 8
        assert report["quarantined"] <= 1
        assert len(ids) >= 90

    def test_autotune_reader_smoke(self, synthetic_dataset):
        cfg = AutotuneConfig(interval_s=0.02)
        with make_reader(synthetic_dataset.url, num_epochs=2,
                         shuffle_row_groups=False, reader_pool_type="thread",
                         workers_count=2, autotune=True,
                         autotune_config=cfg) as r:
            assert r.autotune is not None
            vals = r.autotune.actuator_values()
            assert "worker_concurrency" in vals
            assert "ventilate_ahead" in vals
            n = sum(1 for _ in r)
            deadline = time.monotonic() + 5
            while (r.telemetry.snapshot()["counters"]
                   ["autotune.ticks_total"] < 2
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            report = r.autotune_report()
            snap = r.telemetry.snapshot()
            counters = snap["counters"]
            # Aggregate queue bound: the depth gauge sums every per-worker
            # queue, so the capacity gauge must scale by workers_count.
            assert snap["gauges"]["pool.results_queue_capacity"] == 2 * 50
        assert n == 2 * len(synthetic_dataset.rows)
        assert counters["autotune.ticks_total"] >= 2
        assert set(report) == {"ticks", "actuators", "adjustments"}
        # Every adjustment the controller made is clamped to the safe range.
        for adj in report["adjustments"]:
            rng = report["actuators"][adj["actuator"]]
            assert rng["lo"] <= adj["new"] <= rng["hi"]

    def test_cache_hits_are_mutation_isolated(self, synthetic_dataset):
        """In-place mutation of a delivered row (a mutating TransformSpec
        or training loop) must never write through to the cache-resident
        decoded columns: epoch 2 serves pristine data."""
        from petastorm_tpu.transform import TransformSpec

        def scrub(row):
            row["matrix"] = row["matrix"] * 0.0  # pure, for the baseline
            return row

        def scrub_inplace(row):
            row["matrix"] *= 0.0  # writes into the delivered array
            return row

        def epochs(spec):
            with make_reader(synthetic_dataset.url, num_epochs=2,
                             shuffle_row_groups=False,
                             reader_pool_type="dummy",
                             memory_cache_size_bytes=1 << 30,
                             transform_spec=TransformSpec(spec)) as r:
                out = [row.matrix.copy() for row in r]
                assert r.telemetry.snapshot()["counters"]["cache.mem.hits"] > 0
            return out

        for mats in (epochs(scrub), epochs(scrub_inplace)):
            # Epoch-2 inputs were NOT pre-scrubbed by epoch 1's transform:
            # had the mutation written through, the assertion would still
            # hold — so also check the source rows below.
            assert all((m == 0).all() for m in mats)
        # Direct check: a consumer mutating the delivered array leaves the
        # next retrieval of the same cached row group untouched.
        with make_reader(synthetic_dataset.url, num_epochs=2,
                         shuffle_row_groups=False, reader_pool_type="dummy",
                         memory_cache_size_bytes=1 << 30) as r:
            it = iter(r)
            first = next(it)
            first.matrix[:] = -1.0
            rows = {row.id: row for row in it}
        expected = synthetic_dataset.rows[first.id]["matrix"]
        np.testing.assert_array_equal(rows[first.id].matrix, expected)

    def test_controller_budget_not_wired_to_private_cache(
            self, synthetic_dataset):
        """A full LRU cache is healthy, not memory pressure: the reader
        must not hand the cache's private budget to the controller (a
        steady-state-full cache would otherwise verdict memory_pressure
        every tick and floor every knob)."""
        tiny = 200_000  # smaller than the decoded dataset: stays pinned full
        cfg = AutotuneConfig(interval_s=60)
        with make_reader(synthetic_dataset.url, num_epochs=2,
                         shuffle_row_groups=False, reader_pool_type="thread",
                         workers_count=2, autotune=True, autotune_config=cfg,
                         memory_cache_size_bytes=tiny) as r:
            assert r.autotune.budget is None
            sum(1 for _ in r)
            before = r.autotune.actuator_values()
            for _ in range(10):
                verdict = r.autotune.tick()
                assert verdict != "memory_pressure"
            assert r.autotune.actuator_values() == before

    def test_shared_memory_budget_engages_memory_pressure(
            self, synthetic_dataset):
        """AutotuneConfig.memory_budget_bytes is the public path to the
        memory_pressure verdict (and with it, shuffle_target back-off):
        the Reader points the memory cache's accounting at one shared
        ledger, so a pipeline eating past the watermark of the allowance
        is visible to the controller."""
        cfg = AutotuneConfig(interval_s=60, hysteresis=1, cooldown_ticks=0,
                             memory_budget_bytes=1_000_000)
        with make_reader(synthetic_dataset.url, num_epochs=2,
                         shuffle_row_groups=False, reader_pool_type="dummy",
                         autotune=True, autotune_config=cfg,
                         memory_cache_size_bytes=500_000) as r:
            budget = r.autotune.budget
            assert budget is not None and budget.capacity == 1_000_000
            assert r._cache.budget is budget  # one shared ledger
            sum(1 for _ in r)
            assert budget.used > 0  # the cache charges the shared ledger
            # Another holder charges the ledger past the watermark (the
            # force path the budget documents for buffers): the controller
            # sees it and the shuffle knob becomes reachable.
            budget.reserve(budget.capacity - budget.used - 1, force=True)
            sh = r.autotune.register(_FakeActuator("shuffle_target", lo=10,
                                                   hi=1000, initial=1000))
            assert r.autotune.tick() == "memory_pressure"
            assert sh.value == 500  # the shuffle knob IS reachable

    def test_predicate_with_memory_cache_warns(self, synthetic_dataset):
        from petastorm_tpu.predicates import in_lambda
        with pytest.warns(UserWarning, match="bypasses row-group caching"):
            with make_reader(synthetic_dataset.url, num_epochs=1,
                             reader_pool_type="dummy",
                             predicate=in_lambda(["id"],
                                                 lambda v: v["id"] < 50),
                             memory_cache_size_bytes=1 << 20) as r:
                n = sum(1 for _ in r)
        assert n == 50

    def test_autotune_off_by_default(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, num_epochs=1,
                         reader_pool_type="dummy") as r:
            assert r.autotune is None
            assert r.autotune_report() == {}
            counters = r.telemetry.snapshot()["counters"]
            assert "autotune.ticks_total" not in counters

    def test_dummy_pool_has_no_concurrency_actuator(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, num_epochs=1,
                         reader_pool_type="dummy", autotune=True) as r:
            vals = r.autotune.actuator_values()
            assert "worker_concurrency" not in vals
            assert "ventilate_ahead" in vals
            sum(1 for _ in r)


class TestLoaderIntegration:
    def test_loader_registers_and_unregisters_prefetch_actuator(
            self, synthetic_dataset):
        from petastorm_tpu.jax import DataLoader
        with make_reader(synthetic_dataset.url, num_epochs=None,
                         schema_fields=["id", "id2", "matrix"],
                         shuffle_row_groups=False, reader_pool_type="thread",
                         workers_count=2, autotune=True,
                         autotune_config=AutotuneConfig(interval_s=60)) as r:
            with DataLoader(r, batch_size=10, shuffling_queue_capacity=40,
                            min_after_retrieve=20, seed=0) as loader:
                it = iter(loader)
                for _ in range(5):
                    next(it)
                assert r.autotune.actuator("prefetch_depth") is not None
                assert r.autotune.actuator("shuffle_target") is not None
                it.close()
                # Iterator closed: the loader's knobs left the controller.
                assert r.autotune.actuator("prefetch_depth") is None
                assert r.autotune.actuator("shuffle_target") is None

    def test_prefetch_depth_knob_live_on_loader(self, synthetic_dataset):
        from petastorm_tpu.jax import DataLoader
        with make_reader(synthetic_dataset.url, num_epochs=None,
                         schema_fields=["id", "id2", "matrix"],
                         shuffle_row_groups=False,
                         reader_pool_type="dummy") as r:
            loader = DataLoader(r, batch_size=10, prefetch=2)
            try:
                assert loader.prefetch_depth == 2
                loader.set_prefetch_depth(5)  # knob-ok: direct-knob unit test
                assert loader.prefetch_depth == 5
                loader.set_prefetch_depth(0)  # knob-ok: direct-knob unit test
                assert loader.prefetch_depth == 1  # floor: single buffering
                it = iter(loader)
                for _ in range(3):
                    next(it)
                it.close()
            finally:
                loader.close()

    def test_shuffle_buffer_target_knob(self):
        from petastorm_tpu.reader_impl.shuffling_buffer import \
            RandomShufflingBuffer
        buf = RandomShufflingBuffer(100, min_after_retrieve=10,
                                    extra_capacity=50, seed=0)
        assert buf.min_target == 11
        buf.set_target_capacity(50)   # knob-ok: direct-knob unit test
        assert buf.capacity == 50
        buf.set_target_capacity(5)    # knob-ok: direct-knob unit test
        assert buf.capacity == 11     # clamped to the shuffle-quality floor
        buf.set_target_capacity(999)  # knob-ok: direct-knob unit test
        assert buf.capacity == 100    # never past the configured bound

    def test_row_buffer_bulk_add_survives_concurrent_shrink(self):
        """A controller-thread shrink between the producer's can_add and
        its bulk add_many must not trip the overfill guard: the slack
        contract (one whole row group after can_add) is sized against the
        CONFIGURED capacity."""
        from petastorm_tpu.reader_impl.shuffling_buffer import \
            RandomShufflingBuffer
        buf = RandomShufflingBuffer(100, min_after_retrieve=10,
                                    extra_capacity=50, seed=0)
        buf.add_many(range(99))       # nearly full per the live target
        buf.set_target_capacity(20)   # knob-ok: direct-knob unit test
        buf.add_many(range(40))       # within configured(100)+extra(50)
        assert buf.size == 139

    def test_batched_shuffle_buffer_target_knob(self):
        from petastorm_tpu.jax.batched_buffer import \
            BatchedRandomShufflingBuffer
        buf = BatchedRandomShufflingBuffer(
            100, min_after_retrieve=10, batch_size=5, extra_capacity=50,
            seed=0)
        assert buf.min_target == 15
        buf.set_target_capacity(2)    # knob-ok: direct-knob unit test
        assert buf.capacity == 15
        buf.set_target_capacity(400)  # knob-ok: direct-knob unit test
        assert buf.capacity == 100

    def test_batched_buffer_store_survives_shrink_then_grow(self):
        """The column store is sized from the CONFIGURED capacity: a tuned
        shrink before the first add, followed by a grow back, must not
        overrun the allocation."""
        from petastorm_tpu.jax.batched_buffer import \
            BatchedRandomShufflingBuffer
        buf = BatchedRandomShufflingBuffer(
            100, min_after_retrieve=10, batch_size=5, extra_capacity=20,
            seed=0)
        buf.set_target_capacity(20)   # knob-ok: direct-knob unit test
        buf.add_many({"x": np.arange(10)})  # store allocated while shrunk
        buf.set_target_capacity(100)  # knob-ok: direct-knob unit test
        while buf.can_add:
            buf.add_many({"x": np.arange(10)})  # refill to configured bound
        assert buf.size >= 100

    def test_batched_buffer_tight_range_never_exceeds_configured(self):
        from petastorm_tpu.jax.batched_buffer import \
            BatchedRandomShufflingBuffer
        # min_after + batch_size > configured capacity: the store is
        # pre-allocated at the configured size, so the configured bound
        # must win over the (inverted) quality floor.
        buf = BatchedRandomShufflingBuffer(
            100, min_after_retrieve=98, batch_size=16, seed=0)
        assert buf.min_target > 100
        buf.set_target_capacity(50)   # knob-ok: direct-knob unit test
        assert buf.capacity == 100
        buf.set_target_capacity(500)  # knob-ok: direct-knob unit test
        assert buf.capacity == 100

    def test_ventilator_max_inflight_knob(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, num_epochs=1,
                         reader_pool_type="dummy") as r:
            vent = r._ventilator
            before = vent.max_inflight
            vent.set_max_inflight(before + 4)  # knob-ok: direct-knob unit test
            assert vent.max_inflight == before + 4
            vent.set_max_inflight(0)           # knob-ok: direct-knob unit test
            assert vent.max_inflight == 1      # floor
            sum(1 for _ in r)
