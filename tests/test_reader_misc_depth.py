"""Reader argument/diagnostics, codec encode edges, and benchmark-harness
depth (strategy parity: reference tests/test_reader.py, test_codec_scalar.py,
test_codec_compressed_image.py, test_benchmark.py)."""
from decimal import Decimal

import numpy as np
import pytest

from petastorm_tpu.codecs import (CompressedImageCodec, NdarrayCodec,
                                  ScalarCodec)
from petastorm_tpu.errors import SchemaError
from petastorm_tpu.reader import make_reader
from petastorm_tpu.unischema import UnischemaField


# --------------------------------------------------------------- reader ----

def test_dataset_url_must_be_string():
    with pytest.raises((TypeError, ValueError)):
        make_reader(42)
    with pytest.raises((TypeError, ValueError)):
        make_reader(None)


def test_reader_diagnostics_exposes_pool_state(synthetic_dataset):
    with make_reader(synthetic_dataset.url, reader_pool_type="thread",
                     workers_count=2, shuffle_row_groups=False) as reader:
        next(reader)
        diag = reader.diagnostics
    assert isinstance(diag, dict) and diag


def test_shuffle_drop_composes_with_predicate(synthetic_dataset):
    """Worker-side predicate and drop-partitioning compose: the drop halves
    each already-filtered group."""
    from petastorm_tpu.predicates import in_lambda
    pred = in_lambda(["id2"], lambda v: v["id2"] < 5)
    with make_reader(synthetic_dataset.url, predicate=pred,
                     shuffle_row_drop_partitions=2, seed=3,
                     reader_pool_type="dummy") as reader:
        ids = [row.id for row in reader]
    # The predicate keeps exactly the 50 rows with id2 < 5; the two drop
    # partitions together still cover all of them, just decorrelated.
    assert sorted(ids) == sorted(i for i in range(100) if i % 10 < 5)
    assert [int(i) for i in ids] != sorted(int(i) for i in ids)


def test_shuffle_drop_rejected_for_non_overlapping_ngram(synthetic_dataset):
    from petastorm_tpu.ngram import NGram
    ngram = NGram({0: ["id"], 1: ["id"]}, delta_threshold=1,
                  timestamp_field="id", timestamp_overlap=False)
    with pytest.raises(NotImplementedError):
        make_reader(synthetic_dataset.url, schema_fields=ngram,
                    shuffle_row_drop_partitions=2)


def test_num_epochs_validation(synthetic_dataset):
    with pytest.raises(ValueError):
        make_reader(synthetic_dataset.url, num_epochs=0)
    with pytest.raises(ValueError):
        make_reader(synthetic_dataset.url, num_epochs=-3)


def test_reader_schema_property_reflects_field_selection(synthetic_dataset):
    with make_reader(synthetic_dataset.url, schema_fields=["id", "matrix"],
                     reader_pool_type="dummy") as reader:
        assert set(reader.schema.fields) == {"id", "matrix"}
        row = next(reader)
        assert set(row._fields) == {"id", "matrix"}


# --------------------------------------------------------------- codecs ----

def test_scalar_codec_bool_round_trip():
    f = UnischemaField("b", np.bool_, (), ScalarCodec(np.bool_), False)
    codec = ScalarCodec(np.bool_)
    assert codec.decode(f, codec.encode(f, np.bool_(True))) == True  # noqa: E712
    assert codec.decode(f, codec.encode(f, np.bool_(False))) == False  # noqa: E712


def test_scalar_codec_bytes_round_trip():
    f = UnischemaField("s", bytes, (), ScalarCodec(bytes), False)
    codec = ScalarCodec(bytes)
    assert codec.decode(f, codec.encode(f, b"\x00\xffbin")) == b"\x00\xffbin"


def test_scalar_codec_unicode_round_trip():
    f = UnischemaField("s", str, (), ScalarCodec(str), False)
    codec = ScalarCodec(str)
    assert codec.decode(f, codec.encode(f, "héllo wörld")) == "héllo wörld"


def test_scalar_codec_decimal_round_trip():
    f = UnischemaField("d", Decimal, (), ScalarCodec(Decimal), False)
    codec = ScalarCodec(Decimal)
    out = codec.decode(f, codec.encode(f, Decimal("123.456")))
    assert Decimal(out) == Decimal("123.456")


def test_jpeg_quality_trades_size_for_fidelity():
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (64, 64, 3)).astype(np.uint8)
    f90 = UnischemaField("i", np.uint8, (64, 64, 3), CompressedImageCodec("jpeg", 90), False)
    f20 = UnischemaField("i", np.uint8, (64, 64, 3), CompressedImageCodec("jpeg", 20), False)
    hi = CompressedImageCodec("jpeg", 90).encode(f90, img)
    lo = CompressedImageCodec("jpeg", 20).encode(f20, img)
    assert len(hi) > len(lo)
    hi_dec = CompressedImageCodec("jpeg", 90).decode(f90, hi)
    lo_dec = CompressedImageCodec("jpeg", 20).decode(f20, lo)
    hi_err = np.abs(hi_dec.astype(int) - img.astype(int)).mean()
    lo_err = np.abs(lo_dec.astype(int) - img.astype(int)).mean()
    assert hi_err < lo_err


def test_image_codec_rejects_wrong_shape_on_encode():
    f = UnischemaField("i", np.uint8, (32, 32, 3), CompressedImageCodec("png"), False)
    with pytest.raises(SchemaError):
        CompressedImageCodec("png").encode(f, np.zeros((16, 16, 3), np.uint8))


def test_image_codec_grayscale_2d():
    f = UnischemaField("i", np.uint8, (24, 24), CompressedImageCodec("png"), False)
    codec = CompressedImageCodec("png")
    img = np.random.default_rng(1).integers(0, 255, (24, 24)).astype(np.uint8)
    out = codec.decode(f, codec.encode(f, img))
    np.testing.assert_array_equal(out, img)


def test_ndarray_codec_zero_size_array():
    f = UnischemaField("a", np.float32, (0,), NdarrayCodec(), False)
    codec = NdarrayCodec()
    out = codec.decode(f, codec.encode(f, np.zeros((0,), np.float32)))
    assert out.shape == (0,)


def test_ndarray_codec_fortran_order_survives():
    """F-ordered input round-trips value-exactly (the fast path defers to
    np.load for fortran payloads)."""
    f = UnischemaField("a", np.float64, (4, 5), NdarrayCodec(), False)
    codec = NdarrayCodec()
    arr = np.asfortranarray(np.random.default_rng(2).normal(size=(4, 5)))
    out = codec.decode(f, codec.encode(f, arr))
    np.testing.assert_array_equal(out, arr)


def test_decoded_ndarray_is_writable(synthetic_dataset):
    """Rows must not alias read-only buffers: training code mutates batches."""
    with make_reader(synthetic_dataset.url, schema_fields=["matrix"],
                     reader_pool_type="dummy") as reader:
        row = next(reader)
    row.matrix[0, 0, 0] = 42.0  # must not raise


# ------------------------------------------------------------- benchmark ---

def test_reader_throughput_dummy_pool(synthetic_dataset):
    from petastorm_tpu.benchmark.throughput import reader_throughput
    r = reader_throughput(synthetic_dataset.url, warmup_cycles=5,
                          measure_cycles=20, pool_type="dummy")
    assert r.samples_per_second > 0
    assert r.memory_rss_mb > 0


def test_reader_throughput_field_regex(synthetic_dataset):
    from petastorm_tpu.benchmark.throughput import reader_throughput
    r = reader_throughput(synthetic_dataset.url, field_regex=["id.*"],
                          warmup_cycles=5, measure_cycles=20,
                          pool_type="dummy")
    assert r.samples_per_second > 0


def test_reader_throughput_jax_method_without_step_has_no_stall(synthetic_dataset):
    """read_method='jax' reports stall only when a device step is given —
    a bare loop would measure 100% stall by construction."""
    from petastorm_tpu.benchmark.throughput import reader_throughput
    r = reader_throughput(synthetic_dataset.url, warmup_cycles=2,
                          measure_cycles=6, pool_type="dummy",
                          field_regex=["id", "matrix"], read_method="jax")
    assert r.input_stall_percent is None


def test_user_codec_receives_bytes_not_memoryview(tmp_path):
    """Third-party codecs keep the documented bytes decode contract even on
    the zero-copy read path, and their identity output stays picklable."""
    from petastorm_tpu.codecs import DataframeColumnCodec, register_codec
    from petastorm_tpu.etl.writer import materialize_dataset_local
    from petastorm_tpu.unischema import Unischema

    @register_codec
    class TaggedBlobCodec(DataframeColumnCodec):
        def encode(self, field, value):
            return b"TAG" + value

        def decode(self, field, encoded):
            assert isinstance(encoded, bytes), type(encoded)
            assert encoded.startswith(b"TAG")
            return encoded[3:]

        def arrow_type(self, field):
            import pyarrow as pa
            return pa.binary()

    schema = Unischema("B", [
        UnischemaField("id", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("blob", bytes, (), TaggedBlobCodec(), False),
    ])
    url = f"file://{tmp_path}/ds"
    with materialize_dataset_local(url, schema, rows_per_row_group=5) as w:
        w.write_rows([{"id": i, "blob": bytes([i, i])} for i in range(20)])
    # (spawned process workers can't import codec classes defined in a test
    # module; thread pool still exercises the zero-copy publish path)
    for pool in ("dummy", "thread"):
        with make_reader(url, shuffle_row_groups=False,
                         reader_pool_type=pool, workers_count=2) as reader:
            rows = sorted(reader, key=lambda r: r.id)
        assert [r.blob for r in rows] == [bytes([i, i]) for i in range(20)]
