"""Spawn helper + serializer unit tests (strategy parity: reference
test_run_in_subprocess.py and the serializer round-trip tests)."""
import os
import tempfile

import numpy as np
import pyarrow as pa
import pytest

from petastorm_tpu.reader_impl.arrow_table_serializer import ArrowTableSerializer
from petastorm_tpu.reader_impl.pickle_serializer import PickleSerializer
from petastorm_tpu.test_util.spawn_helpers import (report_canary,
                                                    report_jax_platform_env,
                                                    write_marker)
from petastorm_tpu.workers_pool.exec_in_new_process import exec_in_new_process


def test_exec_in_new_process_runs_function(tmp_path):
    marker = str(tmp_path / "out.txt")
    p = exec_in_new_process(write_marker, marker, "hello-from-child")
    assert p.wait(timeout=60) == 0
    with open(marker) as f:
        assert f.read() == "hello-from-child"


def test_exec_in_new_process_is_fresh_interpreter(tmp_path):
    """Spawn, not fork: the child must not inherit parent module state."""
    import petastorm_tpu
    petastorm_tpu._spawn_test_canary = "set-in-parent"
    try:
        marker = str(tmp_path / "canary.txt")
        p = exec_in_new_process(report_canary, marker)
        assert p.wait(timeout=60) == 0
        with open(marker) as f:
            assert f.read() == "absent"
    finally:
        del petastorm_tpu._spawn_test_canary


def test_exec_in_new_process_pins_cpu(tmp_path, monkeypatch):
    """One process for each chip: a parent that exported JAX_PLATFORMS=tpu
    (it holds the chip) must still spawn children that cannot reach it."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    marker = str(tmp_path / "platform.txt")
    p = exec_in_new_process(report_jax_platform_env, marker)
    assert p.wait(timeout=60) == 0
    with open(marker) as f:
        assert f.read() == "cpu"


def test_exec_in_new_process_cleans_payload(tmp_path):
    before = set(os.listdir(tempfile.gettempdir()))
    p = exec_in_new_process(write_marker, str(tmp_path / "x"), "y")
    assert p.wait(timeout=60) == 0
    leftover = [f for f in os.listdir(tempfile.gettempdir())
                if f.startswith("pt_spawn_") and f not in before]
    assert leftover == []


def test_pickle_serializer_round_trip():
    s = PickleSerializer()
    rows = [{"a": np.arange(4), "b": "text"}, {"a": np.zeros(2), "b": None}]
    out = s.deserialize(s.serialize(rows))
    assert out[1]["b"] is None
    np.testing.assert_array_equal(out[0]["a"], np.arange(4))


def test_arrow_serializer_round_trip_table():
    s = ArrowTableSerializer()
    table = pa.table({"x": np.arange(10), "y": [f"s{i}" for i in range(10)]})
    out = s.deserialize(s.serialize(table))
    assert isinstance(out, pa.Table)
    assert out.equals(table)


def test_arrow_serializer_zero_copy_view():
    """Deserializing from a memoryview keeps Arrow buffers referencing the
    source memory (the shm-ring zero-copy contract) — asserted by address
    range, not just value equality."""
    s = ArrowTableSerializer()
    table = pa.table({"x": np.arange(1000, dtype=np.int64)})
    source = np.frombuffer(bytes(s.serialize(table)), dtype=np.uint8)
    src_start = source.ctypes.data
    src_end = src_start + source.nbytes
    out = s.deserialize(memoryview(source))
    np.testing.assert_array_equal(out.column("x").to_numpy(), np.arange(1000))
    data_buf = out.column("x").chunks[0].buffers()[1]
    assert src_start <= data_buf.address < src_end, \
        "deserialize copied the buffers instead of aliasing the source view"
