"""Worker-pool stress and failure-injection tests (strategy parity:
reference workers_pool/tests/test_workers_pool.py — orphan kill :228,
stop-with-full-queue :139, dead-worker detection)."""
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from petastorm_tpu.test_util.stub_workers import (BlobWorker, IdentityWorker,
                                                  SleepyWorker)
from petastorm_tpu.workers_pool import EmptyResultError
from petastorm_tpu.workers_pool.process_pool import ProcessPool
from petastorm_tpu.workers_pool.thread_pool import ThreadPool


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False


@pytest.mark.process_pool
def test_workers_die_when_parent_killed(tmp_path):
    """kill -9 the pool's owner process: the orphan watchdog must take every
    worker down with it (reference test_workers_pool.py:228)."""
    script = textwrap.dedent("""
        import sys, time
        from petastorm_tpu.test_util.stub_workers import IdentityWorker
        from petastorm_tpu.workers_pool.process_pool import ProcessPool
        pool = ProcessPool(2)
        pool.start(IdentityWorker)
        print("WORKERS", " ".join(str(p.pid) for p in pool._processes), flush=True)
        time.sleep(120)  # parent hangs until killed
    """)
    parent = subprocess.Popen([sys.executable, "-c", script],
                              stdout=subprocess.PIPE, text=True)
    try:
        line = parent.stdout.readline()
        assert line.startswith("WORKERS"), line
        worker_pids = [int(p) for p in line.split()[1:]]
        assert worker_pids and all(_pid_alive(p) for p in worker_pids)
        parent.kill()  # SIGKILL: no cleanup code runs in the parent
        parent.wait()
        deadline = time.time() + 15  # watchdog polls every second
        while time.time() < deadline and any(_pid_alive(p) for p in worker_pids):
            time.sleep(0.2)
        assert not any(_pid_alive(p) for p in worker_pids), \
            f"orphaned workers survived: {[p for p in worker_pids if _pid_alive(p)]}"
    finally:
        if parent.poll() is None:
            parent.kill()


@pytest.mark.parametrize("pool_factory", [
    pytest.param(lambda: ThreadPool(2), id="thread"),
    pytest.param(lambda: ProcessPool(2, transport="zmq", results_queue_size=2),
                 id="process-zmq", marks=pytest.mark.process_pool),
])
def test_stop_with_full_results_queue(pool_factory):
    """stop()+join() must return promptly while many unread results are
    queued (reference test_workers_pool.py:139)."""
    pool = pool_factory()
    pool.start(IdentityWorker)
    for i in range(200):
        pool.ventilate(value=i)
    pool.get_results()       # at least one result flowed
    time.sleep(0.5)          # let the results backlog build
    t0 = time.time()
    pool.stop()
    pool.join()
    assert time.time() - t0 < 20


@pytest.mark.process_pool
def test_dead_worker_detected():
    """A worker killed -9 mid-stream surfaces as an error to the consumer
    instead of a silent hang."""
    pool = ProcessPool(2)
    pool.start(SleepyWorker, {"sleep_s": 0.4})
    for i in range(50):
        pool.ventilate(value=i)
    os.kill(pool._processes[0].pid, signal.SIGKILL)
    with pytest.raises(RuntimeError, match="died unexpectedly"):
        for _ in range(50):
            pool.get_results()


@pytest.mark.process_pool
def test_zmq_transport_stop_with_blocked_publishers():
    """The zmq transport path of the same early-shutdown scenario covered
    for shm rings: blocked PUSH sends must not stall join to SIGKILL."""
    pool = ProcessPool(2, transport="zmq", results_queue_size=1)
    pool.start(BlobWorker, {"size": 1 << 20})
    for i in range(40):
        pool.ventilate(value=i)
    pool.get_results()
    time.sleep(0.5)
    t0 = time.time()
    pool.stop()
    pool.join()
    assert time.time() - t0 < 25


def test_thread_pool_backpressure_tiny_queue():
    """results_queue_size=1 forces full producer/consumer lockstep without
    deadlock or loss."""
    pool = ThreadPool(3, results_queue_size=1)
    pool.start(IdentityWorker)
    for i in range(100):
        pool.ventilate(value=i)
    got = []
    while True:
        try:
            got.append(pool.get_results())
        except EmptyResultError:
            break
    assert sorted(got) == list(range(100))
    pool.stop()
    pool.join()


def test_thread_pool_stop_mid_stream_no_hang():
    pool = ThreadPool(4)
    pool.start(SleepyWorker, {"sleep_s": 0.05})
    for i in range(100):
        pool.ventilate(value=i)
    for _ in range(5):
        pool.get_results()
    t0 = time.time()
    pool.stop()
    pool.join()
    assert time.time() - t0 < 10


def test_ventilator_single_inflight_completes():
    """max_ventilation_queue_size=1: strict lockstep ventilation finishes."""
    from petastorm_tpu.workers_pool.ventilator import ConcurrentVentilator
    pool = ThreadPool(2)
    vent = ConcurrentVentilator(pool.ventilate,
                                [{"value": i} for i in range(30)],
                                max_ventilation_queue_size=1)
    pool.start(IdentityWorker, ventilator=vent)
    got = []
    while True:
        try:
            got.append(pool.get_results())
        except EmptyResultError:
            break
    assert sorted(got) == list(range(30))
    pool.stop()
    pool.join()


def test_stop_is_poison_pill_for_blocked_consumer():
    """stop() unblocks a consumer parked inside get_results with
    EmptyResultError (ADVICE r2: the loader staging thread must exit
    deterministically when the reader stops mid-batch)."""
    import threading

    pool = ThreadPool(2)
    pool.start(SleepyWorker, {"sleep_s": 2.0})
    pool.ventilate(value=1)   # nothing completes for ~2s
    outcome = {}

    def consume():
        try:
            pool.get_results()
            outcome["result"] = "value"
        except EmptyResultError:
            outcome["result"] = "empty"

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    time.sleep(0.3)           # consumer is now blocked polling for results
    t0 = time.time()
    pool.stop()
    t.join(5.0)
    assert not t.is_alive(), "consumer still blocked after stop()"
    assert outcome["result"] == "empty"
    assert time.time() - t0 < 5
    pool.join()


@pytest.mark.process_pool
def test_stop_is_poison_pill_for_blocked_consumer_process_pool():
    import threading

    pool = ProcessPool(1)
    pool.start(SleepyWorker, {"sleep_s": 5.0})
    pool.ventilate(value=1)
    outcome = {}

    def consume():
        try:
            pool.get_results()
            outcome["result"] = "value"
        except EmptyResultError:
            outcome["result"] = "empty"

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    time.sleep(0.5)
    pool.stop()
    t.join(10.0)
    assert not t.is_alive() and outcome["result"] == "empty"
    pool.join()


def test_worker_infrastructure_failure_surfaces_not_hangs():
    """A worker that dies OUTSIDE its process() call (infrastructure
    failure — e.g. cProfile's single sys.monitoring slot on 3.12 used to
    kill the second worker in prof.enable()) must surface as a raised
    failure in the consumer, not leave its assigned items spinning
    get_results() forever."""
    pool = ThreadPool(1)
    pool.start(IdentityWorker)
    # Poison the input queue with an item the dispatch loop itself cannot
    # unpack: the failure happens before process() is entered.
    pool._input_queues[0].put("not-a-(args, kwargs)-tuple")
    pool._assigned[0] += 1
    with pytest.raises((ValueError, TypeError)):
        pool.get_results()
    pool.stop()
    pool.join()


def test_pool_profiling_prints_worker_frames(capsys):
    """profiling_enabled=True: one pool-level cProfile (3.12's global
    sys.monitoring slot forbids per-worker profiles) captures worker-thread
    frames; stats print on join()."""
    pool = ThreadPool(2, profiling_enabled=True)
    pool.start(IdentityWorker)
    for i in range(20):
        pool.ventilate(value=i)
    got = sorted(pool.get_results() for _ in range(20))
    assert got == list(range(20))
    pool.stop()
    pool.join()
    out = capsys.readouterr().out
    assert "function calls" in out and "cumulative" in out
    assert "stub_workers" in out  # a worker-side frame, not just consumer
