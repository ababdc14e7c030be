"""Examples run as tests (strategy parity: reference examples/mnist/tests/ —
the MNIST example trains as part of the suite) plus the reference's
1000-column wide-store fixture (conftest.py:113)."""
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # every example trains a model end-to-end

_EXAMPLES = Path(__file__).resolve().parents[1] / "examples"


def _load_example(name):
    spec = importlib.util.spec_from_file_location(
        f"example_{name}", _EXAMPLES / name / "main.py")
    mod = importlib.util.module_from_spec(spec)
    argv, sys.argv = sys.argv, [name]
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.argv = argv
    return mod


def test_mnist_example_learns(tmp_path):
    """One epoch of the MNIST example on a small synthetic store must reach
    well-above-chance accuracy (reference examples/mnist/tests/)."""
    mnist = _load_example("mnist")
    images, labels = mnist.synthetic_mnist(1024)
    url = f"file://{tmp_path}/mnist"
    mnist.write_dataset(url, images, labels)
    acc = mnist.train(url, epochs=2, batch_size=128)
    assert acc > 0.5  # 10 classes; chance is 0.1


def test_hello_world_example_runs(tmp_path, capsys):
    hw = _load_example("hello_world")
    hw.main(f"file://{tmp_path}/hw")
    out = capsys.readouterr().out
    assert "row sample" in out and "jax batch" in out


def test_spark_converter_to_vit_end_to_end(tmp_path, spark_session):
    """BASELINE config 4 through the REAL converter AND the example's own
    training loop: Spark DataFrame of ML vectors -> make_spark_converter ->
    make_jax_loader -> ViT steps; the example asserts loss falls. Exercises
    vector->array conversion + the loaders' sticky densify of
    undeclared-shape uniform list columns."""
    from pyspark.ml.linalg import Vectors, VectorUDT
    from pyspark.sql.types import IntegerType, StructField, StructType
    from petastorm_tpu.spark.spark_dataset_converter import make_spark_converter

    vit_example = _load_example("spark_to_vit")
    classes, image, rows = 4, 16, 192
    rng = np.random.default_rng(0)
    protos = rng.normal(size=(classes, image * image * 3))
    labels = rng.integers(0, classes, rows)
    feats = protos[labels] + 0.5 * rng.normal(size=(rows, image * image * 3))
    schema = StructType([StructField("features", VectorUDT(), False),
                         StructField("label", IntegerType(), False)])
    df = spark_session.createDataFrame(
        [(Vectors.dense(f), int(l)) for f, l in zip(feats, labels)], schema)
    conv = make_spark_converter(df, parent_cache_dir_url=f"file://{tmp_path}/cache",
                                dtype="float32")
    try:
        losses = vit_example.train(conv.cache_dir_url, steps=15, batch_size=64,
                                   classes=classes, image=image)
        assert len(losses) == 15  # the example itself asserts loss decreased
    finally:
        conv.delete()


@pytest.fixture(scope="module")
def many_columns_dataset(tmp_path_factory):
    """1000 int columns, plain Parquet (reference conftest.py:113)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = tmp_path_factory.mktemp("wide")
    table = pa.table({f"col_{i:04d}": np.arange(20, dtype=np.int64)
                      for i in range(1000)})
    pq.write_table(table, f"{path}/wide.parquet", row_group_size=10)
    return f"file://{path}"


def test_many_columns_batch_reader(many_columns_dataset):
    """A 1000-column store round-trips: schema inference, >255-field
    namedtuples, full column set in batches."""
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(many_columns_dataset, shuffle_row_groups=False,
                           reader_pool_type="dummy") as reader:
        assert len(reader.schema.fields) == 1000
        batch = next(iter(reader))
    assert len(batch._fields) == 1000
    np.testing.assert_array_equal(batch.col_0999, np.arange(10))


def test_many_columns_subset_selection(many_columns_dataset):
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(many_columns_dataset,
                           schema_fields=["col_0001", "col_0500"],
                           shuffle_row_groups=False,
                           reader_pool_type="dummy") as reader:
        batch = next(iter(reader))
    assert sorted(batch._fields) == ["col_0001", "col_0500"]


def test_llm_tokens_example_loss_decreases(tmp_path):
    """The NGram token-window example (BASELINE config 5) trains: loss after
    a few dozen steps is below the initial loss."""
    ex = _load_example("llm_tokens")
    url = f"file://{tmp_path}/tokens"
    ex.write_token_stream(url, n_chunks=2048, vocab=256)
    ex.train(url, steps=25, batch_size=8, window=2, vocab=256)  # asserts loss down


def test_imagenet_example_runs(tmp_path):
    """The ImageNet example runs end to end on a tiny synthetic store and
    reports a positive throughput."""
    ex = _load_example("imagenet")
    url = f"file://{tmp_path}/imgnet"
    ex.write_synthetic_imagenet(url, rows=128, classes=2, rows_per_row_group=32,
                             image_size=48)
    stall, sps = ex.train(url, steps=10, per_device_batch=4, classes=2,
                          learning_rate=0.005)
    assert sps > 0


def test_long_context_example_trains_on_mesh(tmp_path):
    """NGram windows -> dp2 x sp4 mesh -> GQA ring attention: loss
    decreases over a few dozen steps on the virtual 8-device mesh."""
    ex = _load_example("long_context")
    url = f"file://{tmp_path}/lctx"
    ex.write_token_stream(url, n_chunks=2048, vocab=256)
    losses = ex.train(url, steps=25, per_shard_batch=2, window=4,
                      vocab=256, dp=2, sp=4)
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("attn_kind", ["ring-chunked", "ring-flash", "ulysses-flash"])
def test_long_context_example_attention_menu(tmp_path, attn_kind):
    """The example's alternative sequence-parallel attentions (chunked-remat
    ring, Ulysses with the Pallas flash local step) train the same model."""
    ex = _load_example("long_context")
    url = f"file://{tmp_path}/lctx_{attn_kind}"
    ex.write_token_stream(url, n_chunks=512, vocab=256)
    losses = ex.train(url, steps=6, per_shard_batch=2, window=4,
                      vocab=256, dp=2, sp=4, attn_kind=attn_kind)
    assert np.isfinite(losses).all()
