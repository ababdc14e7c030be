"""Synthetic dataset builders for end-to-end tests
(strategy parity: reference petastorm/tests/test_common.py — TestSchema +
create_test_dataset, but written through this package's Spark-free writer)."""
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from petastorm_tpu.codecs import (CompressedImageCodec, CompressedNdarrayCodec,
                                  NdarrayCodec, ScalarCodec)
from petastorm_tpu.etl.writer import materialize_dataset_local
from petastorm_tpu.unischema import Unischema, UnischemaField

TestSchema = Unischema("TestSchema", [
    UnischemaField("id", np.int64, (), ScalarCodec(np.int64), False),
    UnischemaField("id2", np.int32, (), ScalarCodec(np.int32), False),
    UnischemaField("partition_key", str, (), ScalarCodec(str), False),
    UnischemaField("image_png", np.uint8, (32, 16, 3), CompressedImageCodec("png"), False),
    UnischemaField("matrix", np.float32, (32, 16, 3), NdarrayCodec(), False),
    UnischemaField("matrix_uint16", np.uint16, (2, 3), CompressedNdarrayCodec(), False),
    UnischemaField("decimal_col", Decimal, (), ScalarCodec(Decimal), False),
    UnischemaField("varlen", np.int32, (None,), NdarrayCodec(), True),
    UnischemaField("nullable_int", np.int32, (), ScalarCodec(np.int32), True),
])


def make_test_row(i, rng):
    row = {
        "id": i,
        "id2": i % 10,
        "partition_key": f"p_{i % 4}",
        "image_png": rng.integers(0, 255, (32, 16, 3)).astype(np.uint8),
        "matrix": rng.normal(size=(32, 16, 3)).astype(np.float32),
        "matrix_uint16": rng.integers(0, 2 ** 16 - 1, (2, 3)).astype(np.uint16),
        "decimal_col": Decimal(i) / Decimal(10),
        "varlen": np.arange(i % 5 + 1, dtype=np.int32),
    }
    if i % 3 == 0:
        row["nullable_int"] = np.int32(i * 2)
    return row


def create_test_dataset(url, num_rows=100, rows_per_row_group=10, seed=0):
    """Write the synthetic petastorm dataset; returns the expected rows."""
    rng = np.random.default_rng(seed)
    rows = [make_test_row(i, rng) for i in range(num_rows)]
    with materialize_dataset_local(url, TestSchema,
                                   rows_per_row_group=rows_per_row_group,
                                   rows_per_file=rows_per_row_group * 2) as w:
        w.write_rows(rows)
    return rows


def create_test_scalar_dataset(url, num_rows=100, row_group_size=10):
    """A *plain* (non-petastorm) Parquet store for make_batch_reader tests
    (parity: reference test_common.py:161)."""
    rng = np.random.default_rng(1)
    data = {
        "id": np.arange(num_rows, dtype=np.int64),
        "int_col": rng.integers(-100, 100, num_rows).astype(np.int32),
        "float_col": rng.normal(size=num_rows),
        "string_col": np.array([f"item_{i}" for i in range(num_rows)]),
        "vector_col": [rng.normal(size=4).astype(np.float32) for _ in range(num_rows)],
    }
    table = pa.table({
        "id": data["id"],
        "int_col": data["int_col"],
        "float_col": data["float_col"],
        "string_col": data["string_col"],
        "vector_col": pa.array([v.tolist() for v in data["vector_col"]],
                               type=pa.list_(pa.float32())),
    })
    import os
    path = url[len("file://"):]
    os.makedirs(path, exist_ok=True)
    half = num_rows // 2
    pq.write_table(table.slice(0, half), f"{path}/a.parquet", row_group_size=row_group_size)
    pq.write_table(table.slice(half), f"{path}/b.parquet", row_group_size=row_group_size)
    return data


def rows_equal(actual, expected_row) -> bool:
    """Compare a yielded namedtuple against the expected row dict."""
    for name, expected in expected_row.items():
        got = getattr(actual, name)
        if isinstance(expected, np.ndarray):
            if not np.array_equal(got, expected):
                return False
        elif got != expected:
            return False
    return True


def write_token_store(url, windows, window, vocab=32000, seed=0):
    """Timestamped token store, one NGram window per row group (windows
    never cross row groups — same layout contract as the reference's
    NGram, ngram.py:86-91 there)."""
    schema = Unischema("TokSchema", [
        UnischemaField("ts", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("token", np.int32, (), ScalarCodec(np.int32), False),
    ])
    rng = np.random.default_rng(seed)
    with materialize_dataset_local(url, schema,
                                   rows_per_row_group=window) as w:
        for i in range(windows * window):
            w.write_row({"ts": np.int64(i),
                         "token": np.int32(rng.integers(0, vocab))})
