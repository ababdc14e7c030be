"""TF/torch adapters, CLIs, mocks, batching queue
(strategy parity: reference test_tf_dataset.py / test_pytorch_dataloader.py /
metadata CLI suites)."""
import os
import numpy as np
import pytest

from petastorm_tpu.reader import make_batch_reader, make_reader


# ----------------------------------------------------------------- pytorch
def test_torch_dataloader_row_path(synthetic_dataset):
    import torch
    from petastorm_tpu.pytorch import DataLoader
    with make_reader(synthetic_dataset.url, schema_fields=["id", "matrix"],
                     shuffle_row_groups=False, reader_pool_type="dummy") as reader:
        batches = list(DataLoader(reader, batch_size=10))
    assert len(batches) == 10
    assert isinstance(batches[0]["matrix"], torch.Tensor)
    assert batches[0]["matrix"].shape == (10, 32, 16, 3)
    ids = torch.cat([b["id"] for b in batches])
    assert sorted(ids.tolist()) == list(range(100))


def test_torch_type_promotions(synthetic_dataset):
    import torch
    from petastorm_tpu.pytorch import DataLoader
    with make_reader(synthetic_dataset.url, schema_fields=["id", "matrix_uint16"],
                     shuffle_row_groups=False, reader_pool_type="dummy") as reader:
        b = next(iter(DataLoader(reader, batch_size=5)))
    assert b["matrix_uint16"].dtype == torch.int32  # uint16 promoted


def test_torch_batched_loader(scalar_dataset):
    import torch
    from petastorm_tpu.pytorch import BatchedDataLoader
    with make_batch_reader(scalar_dataset.url, schema_fields=["id", "float_col"],
                           shuffle_row_groups=False, reader_pool_type="dummy") as reader:
        batches = list(BatchedDataLoader(reader, batch_size=32))
    assert [len(b["id"]) for b in batches] == [32, 32, 32]
    assert isinstance(batches[0]["float_col"], torch.Tensor)


# ---------------------------------------------------------------------- tf
def test_tf_dataset_row_path(synthetic_dataset):
    import tensorflow as tf
    from petastorm_tpu.tf_utils import make_petastorm_dataset
    with make_reader(synthetic_dataset.url, schema_fields=["id", "matrix", "decimal_col"],
                     shuffle_row_groups=False, reader_pool_type="dummy") as reader:
        ds = make_petastorm_dataset(reader)
        rows = list(ds.take(5))
    assert rows[0]["matrix"].shape == (32, 16, 3)
    assert rows[0]["id"].dtype == tf.int64
    assert rows[0]["decimal_col"].dtype == tf.string  # Decimal -> str
    assert float(rows[1]["decimal_col"].numpy().decode()) == pytest.approx(0.1)


def test_tf_dataset_batch_path(scalar_dataset):
    import tensorflow as tf  # noqa: F401
    from petastorm_tpu.tf_utils import make_petastorm_dataset
    with make_batch_reader(scalar_dataset.url, schema_fields=["id", "float_col"],
                           shuffle_row_groups=False, reader_pool_type="dummy") as reader:
        ds = make_petastorm_dataset(reader).unbatch().batch(25)
        sizes = [int(b["id"].shape[0]) for b in ds]
    assert sizes == [25, 25, 25, 25]


# -------------------------------------------------------------------- CLIs
def test_copy_dataset_cli(synthetic_dataset, tmp_path):
    from petastorm_tpu.tools.copy_dataset import main
    target = f"file://{tmp_path}/copy"
    assert main([synthetic_dataset.url, target, "--field-regex", "id", "id2",
                 "--rows-per-row-group", "20"]) == 0
    with make_reader(target, shuffle_row_groups=False, reader_pool_type="dummy") as r:
        samples = list(r)
    assert len(samples) == 100
    assert set(samples[0]._fields) == {"id", "id2"}


def test_copy_dataset_not_null_filter(synthetic_dataset, tmp_path):
    from petastorm_tpu.tools.copy_dataset import copy_dataset
    target = f"file://{tmp_path}/copy_nn"
    copied = copy_dataset(synthetic_dataset.url, target,
                          field_regex=["id", "nullable_int"],
                          not_null_fields=["nullable_int"])
    assert copied == 34  # ids divisible by 3


def test_generate_metadata_cli(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = tmp_path / "plain"
    path.mkdir()
    pq.write_table(pa.table({"a": np.arange(50)}), f"{path}/x.parquet",
                   row_group_size=10)
    from petastorm_tpu.etl.generate_metadata import main
    assert main([f"file://{path}"]) == 0
    from petastorm_tpu.etl.dataset_metadata import DatasetContext, get_schema
    schema = get_schema(DatasetContext(f"file://{path}"))
    assert "a" in schema.fields


def test_metadata_util_cli(synthetic_dataset, capsys):
    from petastorm_tpu.etl.metadata_util import main
    assert main([synthetic_dataset.url]) == 0
    out = capsys.readouterr().out
    assert "row groups" in out


# ----------------------------------------------------------------- mocks &c
def test_reader_mock_with_jax_loader():
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.test_util.reader_mock import ReaderMock
    from dataset_utils import TestSchema
    mock = ReaderMock(TestSchema.create_schema_view(["id", "matrix"]), num_rows=50)
    batches = list(DataLoader(mock, batch_size=10))
    assert len(batches) == 5
    assert batches[0]["matrix"].shape == (10, 32, 16, 3)


def test_shuffling_analysis(synthetic_dataset):
    from petastorm_tpu.test_util.shuffling_analysis import compute_correlation_distance
    unshuffled = compute_correlation_distance(
        lambda: make_reader(synthetic_dataset.url, shuffle_row_groups=False,
                            reader_pool_type="dummy", schema_fields=["id"]))
    shuffled = compute_correlation_distance(
        lambda: make_reader(synthetic_dataset.url, shuffle_row_groups=True,
                            shuffle_rows=True, seed=3,
                            reader_pool_type="dummy", schema_fields=["id"]))
    assert unshuffled > 0.99
    assert shuffled < 0.5


def test_batching_table_queue():
    import pyarrow as pa
    from petastorm_tpu.pyarrow_helpers.batching_table_queue import BatchingTableQueue
    q = BatchingTableQueue(batch_size=7)
    assert q.empty()
    q.put(pa.table({"x": list(range(5))}))
    assert q.empty()
    q.put(pa.table({"x": list(range(5, 20))}))
    got = []
    while not q.empty():
        batch = q.get()
        assert batch.num_rows == 7
        got.extend(batch.column("x").to_pylist())
    assert got == list(range(14))  # 20 rows -> 2 full batches, 6 left over
    with pytest.raises(RuntimeError):
        q.get()


def test_dummy_reader_benchmark_smoke():
    from petastorm_tpu.benchmark.dummy_reader import make_dummy_reader
    from petastorm_tpu.jax import DataLoader
    reader = make_dummy_reader(num_rows=100)
    batches = list(DataLoader(reader, batch_size=25))
    assert len(batches) == 4


def test_spark_converter_importable_without_pyspark():
    import petastorm_tpu.spark.spark_dataset_converter as c
    with pytest.raises((ImportError, ValueError)):
        c.make_spark_converter(None)


def test_copy_dataset_overwrite_semantics(synthetic_dataset, tmp_path):
    """Reference parity (tools/copy_dataset.py:104): an existing non-empty
    target errors without --overwrite-output and is replaced with it."""
    from petastorm_tpu.tools.copy_dataset import copy_dataset, main
    target = f"file://{tmp_path}/copy_ow"
    copy_dataset(synthetic_dataset.url, target, field_regex=["id"])
    with pytest.raises(ValueError, match="overwrite"):
        copy_dataset(synthetic_dataset.url, target, field_regex=["id"])
    # CLI flag path + byte-bounded row groups + ignored reference flags
    assert main([synthetic_dataset.url, target, "--field-regex", "id",
                 "--overwrite-output", "--row-group-size-mb", "1",
                 "--partition-count", "8", "--hdfs-driver", "libhdfs3"]) == 0
    with make_reader(target, shuffle_row_groups=False,
                     reader_pool_type="dummy") as r:
        assert len(list(r)) == 100


def test_generate_metadata_reference_cli_spelling(tmp_path):
    """Reference invocations use --dataset_url/--unischema_class (a Spark
    job there, petastorm_generate_metadata.py:119-134); both work here,
    including the ignored Spark flags."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = tmp_path / "plain2"
    path.mkdir()
    pq.write_table(pa.table({"a": np.arange(30)}), f"{path}/x.parquet",
                   row_group_size=10)
    from petastorm_tpu.etl.generate_metadata import main
    assert main(["--dataset_url", f"file://{path}", "--master", "local[2]",
                 "--spark-driver-memory", "2g"]) == 0
    from petastorm_tpu.etl.dataset_metadata import DatasetContext, get_schema
    assert "a" in get_schema(DatasetContext(f"file://{path}")).fields

    # --unischema_class stores the named schema object verbatim
    from dataset_utils import TestSchema  # noqa: F401 - proves importability
    assert main(["--dataset_url", f"file://{path}",
                 "--unischema_class", "dataset_utils.TestSchema"]) == 0
    stored = get_schema(DatasetContext(f"file://{path}"))
    assert set(stored.fields) == set(TestSchema.fields)


def test_copy_dataset_refuses_nested_paths(synthetic_dataset, tmp_path):
    """--overwrite-output recursively removes the target, so a target
    containing (or contained in) the source must refuse up front — either
    nesting direction would delete source data."""
    from petastorm_tpu.tools.copy_dataset import copy_dataset
    src_path = synthetic_dataset.url.replace("file://", "")
    for bad_target in (synthetic_dataset.url,             # identical
                       f"file://{src_path}/sub",          # below the source
                       f"file://{os.path.dirname(src_path)}"):  # above it
        with pytest.raises(ValueError, match="nested|same path"):
            copy_dataset(synthetic_dataset.url, bad_target,
                         overwrite_output=True)
    # sibling with a shared name prefix is fine
    ok_target = f"file://{tmp_path}/copy_sib"
    assert copy_dataset(synthetic_dataset.url, ok_target,
                        field_regex=["id"]) == 100


# ---------------------------------------------------------------------------
# tools/check_columns.py — explicit columns= lint (docs/io.md)
# ---------------------------------------------------------------------------
def _load_tool(name):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.io
class TestCheckColumnsLint:
    @pytest.fixture(scope="class")
    def lint(self):
        return _load_tool("check_columns")

    def _violations(self, lint, tmp_path, code):
        f = tmp_path / "mod.py"
        f.write_text(code)
        return lint.check_file(str(f))

    @pytest.mark.parametrize("code", [
        "pf.read_row_group(0)\n",
        "pf.read_row_groups([0, 1])\n",
        "pf.read_row_group(i, use_threads=False)\n",
    ])
    def test_flags_full_width_reads(self, lint, tmp_path, code):
        assert len(self._violations(lint, tmp_path, code)) == 1

    @pytest.mark.parametrize("code", [
        "pf.read_row_group(0, columns=['a'])\n",
        "pf.read_row_groups([0], columns=cols, use_threads=False)\n",
        "pf.read_row_group(0)  # columns-ok: metadata tool, full width\n",
        "read_row_group(0)\n",           # bare call, not a method
        "pf.read()\n",
    ])
    def test_allows_explicit_columns_and_waivers(self, lint, tmp_path, code):
        assert self._violations(lint, tmp_path, code) == []

    def test_package_is_clean(self, lint):
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "petastorm_tpu")
        assert lint.main([root]) == 0
