"""Batching table queue depth and CLI-surface tests (strategy parity:
reference pyarrow_helpers/tests/test_batch_buffer.py, benchmark/cli.py,
tools/spark_session_cli.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from petastorm_tpu.pyarrow_helpers.batching_table_queue import BatchingTableQueue


def _table(start, n):
    return pa.table({"id": np.arange(start, start + n, dtype=np.int64),
                     "x": np.arange(start, start + n, dtype=np.float64) * 0.5})


# ------------------------------------------------------- batching queue ----

def test_rechunk_one_table_into_smaller_batches():
    q = BatchingTableQueue(batch_size=4)
    q.put(_table(0, 10))
    batches = []
    while not q.empty():
        batches.append(q.get())
    assert [len(b) for b in batches] == [4, 4]
    assert batches[0].column("id").to_pylist() == [0, 1, 2, 3]
    assert batches[1].column("id").to_pylist() == [4, 5, 6, 7]


def test_rechunk_across_table_boundaries():
    q = BatchingTableQueue(batch_size=4)
    q.put(_table(0, 10))
    q.put(_table(10, 10))
    ids = []
    while not q.empty():
        b = q.get()
        assert len(b) == 4
        ids.extend(b.column("id").to_pylist())
    assert ids == list(range(20))[:len(ids)]
    assert len(ids) == 20


def test_batch_larger_than_single_table():
    q = BatchingTableQueue(batch_size=16)
    for s in range(0, 30, 10):
        q.put(_table(s, 10))
    first = q.get()
    assert len(first) == 16
    assert first.column("id").to_pylist() == list(range(16))


def test_batch_size_one():
    q = BatchingTableQueue(batch_size=1)
    q.put(_table(0, 3))
    got = [q.get().column("id").to_pylist() for _ in range(3)]
    assert got == [[0], [1], [2]]


def test_random_table_and_batch_sizes_preserve_order():
    rng = np.random.default_rng(7)
    for batch_size in rng.integers(1, 9, 5):
        q = BatchingTableQueue(batch_size=int(batch_size))
        total, start = 0, 0
        for _ in range(6):
            n = int(rng.integers(1, 12))
            q.put(_table(start, n))
            start += n
            total += n
        ids = []
        while not q.empty():
            b = q.get()
            assert len(b) == batch_size
            ids.extend(b.column("id").to_pylist())
        assert ids == list(range(len(ids)))
        assert total - len(ids) < batch_size  # only the tail remains


# ------------------------------------------------------------------ CLIs ---

def test_throughput_cli_json_output(synthetic_dataset):
    from petastorm_tpu.benchmark import cli
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([synthetic_dataset.url, "-p", "dummy", "-m", "2",
                       "-n", "10", "--json"])
    assert rc in (0, None)
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert out["samples_per_second"] > 0


def test_throughput_cli_profile_threads(synthetic_dataset):
    """--profile-threads wires ThreadPool(profiling_enabled=True): merged
    per-worker cProfile stats print on reader close (parity: reference
    benchmark/cli.py ``--profile-threads``, thread_pool.py:47-52)."""
    from petastorm_tpu.benchmark import cli
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([synthetic_dataset.url, "-p", "thread", "-w", "2",
                       "-m", "2", "-n", "10", "--profile-threads"])
    assert rc in (0, None)
    out = buf.getvalue()
    # pstats report + the worker's own processing frames prove the profile
    # covered the worker loop, not an empty profiler.
    assert "cumulative" in out and "function calls" in out
    assert "row_reader_worker" in out
    assert "samples/sec" in out


def test_throughput_cli_spawn_new_process(synthetic_dataset):
    """--spawn-new-process re-runs the measurement in a fresh interpreter
    (methodology parity: reference throughput.py:144-149)."""
    proc = subprocess.run(
        [sys.executable, "-m", "petastorm_tpu.benchmark.cli",
         synthetic_dataset.url, "-p", "dummy", "-m", "2", "-n", "10",
         "--json", "--spawn-new-process"],
        capture_output=True, text=True, timeout=240,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__)))})
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["samples_per_second"] > 0


def test_spark_session_cli_builds_config():
    import argparse
    from petastorm_tpu.tools import spark_session_cli
    parser = argparse.ArgumentParser()
    spark_session_cli.add_configure_spark_arguments(parser)
    args = parser.parse_args(["--master", "local[2]",
                              "--spark-session-config", "a.b=1", "c.d=x"])
    assert args.master == "local[2]"
    assert args.spark_session_config == ["a.b=1", "c.d=x"]
