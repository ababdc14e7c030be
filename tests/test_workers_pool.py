"""Identical behavioral suite over thread/process/dummy pools with stub
workers (strategy parity: reference workers_pool/tests/test_workers_pool.py).
"""
import pytest

from petastorm_tpu.test_util.stub_workers import (CoeffMultiplierWorker,
                                                  ExceptionAtNWorker,
                                                  IdentityWorker,
                                                  MultiOutputWorker,
                                                  SilentWorker, WorkerIdWorker)
from petastorm_tpu.workers_pool import EmptyResultError
from petastorm_tpu.workers_pool.dummy_pool import DummyPool
from petastorm_tpu.workers_pool.process_pool import ProcessPool
from petastorm_tpu.workers_pool.thread_pool import ThreadPool
from petastorm_tpu.workers_pool.ventilator import ConcurrentVentilator

POOL_FACTORIES = [
    pytest.param(lambda: DummyPool(), id="dummy"),
    pytest.param(lambda: ThreadPool(1), id="thread-1"),
    pytest.param(lambda: ThreadPool(4), id="thread-4"),
    pytest.param(lambda: ProcessPool(2), id="process-2", marks=pytest.mark.process_pool),
]


def _drain(pool):
    out = []
    while True:
        try:
            out.append(pool.get_results())
        except EmptyResultError:
            return out


@pytest.mark.parametrize("pool_factory", POOL_FACTORIES)
def test_matches_ventilated_items(pool_factory):
    pool = pool_factory()
    vent = ConcurrentVentilator(pool.ventilate, [{"value": i} for i in range(20)])
    pool.start(CoeffMultiplierWorker, {"coeff": 3}, ventilator=vent)
    results = _drain(pool)
    assert sorted(results) == [3 * i for i in range(20)]
    pool.stop()
    pool.join()


@pytest.mark.parametrize("pool_factory", POOL_FACTORIES)
def test_manual_ventilation_then_empty(pool_factory):
    pool = pool_factory()
    pool.start(IdentityWorker)
    for i in range(5):
        pool.ventilate(value=i)
    got = []
    for _ in range(5):
        got.append(pool.get_results())
    assert sorted(got) == list(range(5))
    with pytest.raises(EmptyResultError):
        pool.get_results()
    # Ventilating again revives the pool.
    pool.ventilate(value=99)
    assert pool.get_results() == 99
    pool.stop()
    pool.join()


@pytest.mark.parametrize("pool_factory", POOL_FACTORIES)
def test_multi_output_items(pool_factory):
    pool = pool_factory()
    pool.start(MultiOutputWorker)
    pool.ventilate(values=[1, 2, 3])
    pool.ventilate(values=[])
    pool.ventilate(values=[4])
    assert sorted(_drain(pool)) == [1, 2, 3, 4]
    pool.stop()
    pool.join()


@pytest.mark.parametrize("pool_factory", POOL_FACTORIES)
def test_zero_output_worker_terminates(pool_factory):
    pool = pool_factory()
    pool.start(SilentWorker)
    for i in range(7):
        pool.ventilate(value=i)
    assert _drain(pool) == []
    pool.stop()
    pool.join()


@pytest.mark.parametrize("pool_factory", POOL_FACTORIES)
def test_exception_propagates_to_caller(pool_factory):
    pool = pool_factory()
    pool.start(ExceptionAtNWorker, {"bad_value": 3})
    for i in range(6):
        pool.ventilate(value=i)
    with pytest.raises(ValueError, match="poisoned value 3"):
        _drain(pool)


def test_thread_pool_deterministic_round_robin_order():
    """Strict round-robin readout: results come back in ventilation order."""
    for _ in range(3):
        pool = ThreadPool(4)
        pool.start(IdentityWorker)
        for i in range(40):
            pool.ventilate(value=i)
        assert _drain(pool) == list(range(40))
        pool.stop()
        pool.join()


def test_thread_pool_work_distribution():
    pool = ThreadPool(4)
    pool.start(WorkerIdWorker)
    for i in range(16):
        pool.ventilate(value=i)
    results = _drain(pool)
    by_worker = {}
    for wid, value in results:
        by_worker.setdefault(wid, []).append(value)
    assert len(by_worker) == 4
    assert all(len(v) == 4 for v in by_worker.values())
    pool.stop()
    pool.join()


@pytest.mark.process_pool
def test_process_pool_workers_are_pinned_to_cpu(monkeypatch):
    """One process for each chip: the parent holds it, so every spawned
    worker sees JAX_PLATFORMS=cpu even when the parent exported tpu."""
    from petastorm_tpu.test_util.stub_workers import EnvReportWorker
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    pool = ProcessPool(2)
    pool.start(EnvReportWorker, {"name": "JAX_PLATFORMS"})
    try:
        for i in range(4):
            pool.ventilate(value=i)
        assert [pool.get_results() for _ in range(4)] == ["cpu"] * 4
    finally:
        pool.stop()
        pool.join()


@pytest.mark.process_pool
def test_process_pool_stop_with_full_ring_is_fast():
    """Early shutdown while workers are blocked writing into a full shm ring:
    stop() closes the rings so blocked writers fail out immediately instead of
    stalling join() into its 30s SIGKILL deadline."""
    import time
    from petastorm_tpu.native import ring_available
    from petastorm_tpu.test_util.stub_workers import BlobWorker
    if not ring_available():
        pytest.skip("C++ shm ring not available")
    pool = ProcessPool(2, transport="shm", ring_capacity=1 << 20)
    pool.start(BlobWorker, {"size": 300 << 10})
    for i in range(40):
        pool.ventilate(value=i)
    pool.get_results()          # at least one item flowed
    time.sleep(1.0)             # let both workers block on their full rings
    t0 = time.time()
    pool.stop()
    pool.join()
    assert time.time() - t0 < 20


@pytest.mark.process_pool
def test_process_pool_arrow_serializer():
    import pyarrow as pa
    from petastorm_tpu.reader_impl.arrow_table_serializer import ArrowTableSerializer
    from petastorm_tpu.test_util.stub_workers import ArrowTableWorker

    pool = ProcessPool(2, serializer=ArrowTableSerializer(), zmq_copy_buffers=False)
    pool.start(ArrowTableWorker)
    pool.ventilate(n=5)
    pool.ventilate(n=3)
    tables = _drain(pool)
    assert sorted(t.num_rows for t in tables) == [3, 5]
    assert all(isinstance(t, pa.Table) for t in tables)
    values = sorted(tables[0].column("x").to_pylist() + tables[1].column("x").to_pylist())
    assert values == sorted(list(range(5)) + list(range(3)))
    pool.stop()
    pool.join()
