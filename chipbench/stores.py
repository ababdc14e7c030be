"""Seeded store generators: the benchmark's traffic, written with pyarrow and
indexed by the program's ``write_dataset_metadata`` (the path a user with a
Spark-made store takes). Copies of ``write_synthetic_imagenet`` /
``write_token_store``'s content, without their per-row Python writer.

Every row carries an ``id`` (images) or a ``ts`` (tokens) of its own, so that
what a batch holds can be traced back to the stored bytes.
"""
from __future__ import annotations

import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

_ROW_GROUPS_PER_FILE = 16


def image_schema(image_size: int, quality: int):
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    return Unischema("ChipbenchImages", [
        UnischemaField("id", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("image", np.uint8, (image_size, image_size, 3),
                       CompressedImageCodec("jpeg", quality), False),
        UnischemaField("label", np.int32, (), ScalarCodec(np.int32), False),
    ])


def token_schema():
    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    return Unischema("ChipbenchTokens", [
        UnischemaField("ts", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("token", np.int32, (), ScalarCodec(np.int32), False),
    ])


def _fresh_dir(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)


def _write_parts(path: str, table: pa.Table, rows_per_row_group: int,
                 schema) -> None:
    from petastorm_tpu.etl.dataset_metadata import write_dataset_metadata
    per_file = rows_per_row_group * _ROW_GROUPS_PER_FILE
    for part, start in enumerate(range(0, table.num_rows, per_file)):
        pq.write_table(table.slice(start, per_file),
                       os.path.join(path, f"part-{part:05d}.parquet"),
                       row_group_size=rows_per_row_group,
                       compression="snappy", use_dictionary=False)
    write_dataset_metadata(f"file://{path}", schema)


def _encode_group(args):
    """JPEG-encode one row group's images (cv2 releases the GIL)."""
    import cv2
    protos, labels, seed, group, image_size, quality = args
    rng = np.random.default_rng([seed, group])
    up = image_size // 8
    blobs = []
    for label in labels:
        base = np.kron(protos[label], np.ones((up, up, 1), np.uint8))
        noise = rng.integers(0, 60, (image_size, image_size, 3),
                             dtype=np.uint8)
        rgb = np.clip(base + noise, 0, 255).astype(np.uint8)
        ok, enc = cv2.imencode(".jpg", np.ascontiguousarray(rgb[..., ::-1]),
                               [int(cv2.IMWRITE_JPEG_QUALITY), quality])
        if not ok:
            raise RuntimeError("JPEG encode failed")
        blobs.append(enc.tobytes())
    return blobs


def write_image_store(path: str, rows: int, classes: int, seed: int,
                      image_size: int = 224, rows_per_row_group: int = 64,
                      quality: int = 85, threads: int = 8) -> None:
    """Class-separable synthetic JPEGs: a per-class 8x8 proto upsampled to
    ``image_size`` plus uniform noise (compresses like a photo, trains like
    a toy), ``id`` = row number."""
    if image_size % 8:
        raise ValueError("image_size must be a multiple of 8")
    _fresh_dir(path)
    rng = np.random.default_rng(seed)
    protos = rng.integers(60, 195, (classes, 8, 8, 3)).astype(np.uint8)
    labels = rng.integers(0, classes, rows).astype(np.int32)
    jobs = [(protos, labels[s:s + rows_per_row_group], seed, g, image_size,
             quality)
            for g, s in enumerate(range(0, rows, rows_per_row_group))]
    with ThreadPoolExecutor(threads) as pool:
        blobs = [b for group in pool.map(_encode_group, jobs) for b in group]
    schema = image_schema(image_size, quality)
    table = pa.Table.from_pydict(
        {"id": pa.array(np.arange(rows, dtype=np.int64)),
         "image": pa.array(blobs, type=pa.binary()),
         "label": pa.array(labels)}, schema=schema.as_arrow_schema())
    _write_parts(path, table, rows_per_row_group, schema)


def write_token_store(path: str, windows: int, window: int, vocab: int,
                      seed: int) -> None:
    """Timestamped tokens uniform over the vocabulary, one NGram window per
    row group (windows never cross row groups)."""
    _fresh_dir(path)
    rng = np.random.default_rng(seed)
    n = windows * window
    schema = token_schema()
    table = pa.Table.from_pydict(
        {"ts": pa.array(np.arange(n, dtype=np.int64)),
         "token": pa.array(rng.integers(0, vocab, n).astype(np.int32))},
        schema=schema.as_arrow_schema())
    _write_parts(path, table, window, schema)


def read_columns(path: str, columns) -> dict:
    """The stored columns as numpy / lists, read with pyarrow alone (the
    reference's view of the store: nothing of the program is imported)."""
    files = sorted(f for f in os.listdir(path) if f.endswith(".parquet"))
    table = pa.concat_tables(
        [pq.read_table(os.path.join(path, f), columns=list(columns))
         for f in files])
    out = {}
    for name in columns:
        col = table.column(name)
        out[name] = (col.to_pylist() if pa.types.is_binary(col.type)
                     else col.to_numpy())
    return out
