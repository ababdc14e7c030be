"""A ``uint8`` scalar column (a byte-level model's tokens: one byte a token
on disk) through ``make_reader`` + ``NGram(dense=True)`` + ``DataLoader``:
it arrives on the device as ``uint8``, the stored bytes, never widened on
the host; the id offset and the widening belong to the jitted step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.codecs import ScalarCodec
from petastorm_tpu.etl.writer import materialize_dataset_local
from petastorm_tpu.jax import DataLoader
from petastorm_tpu.ngram import NGram
from petastorm_tpu.reader import make_reader
from petastorm_tpu.unischema import Unischema, UnischemaField

ByteSchema = Unischema("ByteSchema", [
    UnischemaField("ts", np.int64, (), ScalarCodec(np.int64), False),
    UnischemaField("token", np.uint8, (), ScalarCodec(np.uint8), False),
])
WINDOW, GROUPS = 16, 6


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    url = f"file://{tmp_path_factory.mktemp('bytes')}/store"
    # Every byte value occurs, the top bit among them (a signed or widened
    # read would show).
    stored = np.random.default_rng(3).permutation(
        np.arange(WINDOW * GROUPS) % 256).astype(np.uint8)
    stored[:3] = (0, 255, 128)
    with materialize_dataset_local(url, ByteSchema,
                                   rows_per_row_group=WINDOW) as w:
        for i, b in enumerate(stored):
            w.write_row({"ts": np.int64(i), "token": b})
    return url, stored


def windows():
    return NGram({o: ["ts", "token"] for o in range(WINDOW)},
                 delta_threshold=1, timestamp_field="ts",
                 timestamp_overlap=False, dense=True)


@pytest.mark.parametrize("pool", ["dummy", "thread"])
def test_dense_windows_keep_the_columns_width(store, pool):
    url, stored = store
    with make_reader(url, schema_fields=windows(), shuffle_row_groups=False,
                     reader_pool_type=pool, workers_count=2) as reader:
        got = list(reader)
    assert len(got) == GROUPS
    for w in got:
        assert w["token"].dtype == np.uint8 and w["token"].shape == (WINDOW,)
        start = int(w["ts"][0])
        np.testing.assert_array_equal(w["token"],
                                      stored[start:start + WINDOW])


@pytest.mark.parametrize("batch", [1, 2])
def test_loader_stages_uint8_and_the_step_widens_on_the_device(store, batch):
    url, stored = store
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    step = jax.jit(lambda tokens: tokens.astype(jnp.int32) + 64)
    with make_reader(url, schema_fields=windows(), shuffle_row_groups=True,
                     seed=5, reader_pool_type="thread", workers_count=2,
                     num_epochs=1) as reader:
        with DataLoader(reader, batch_size=batch, sharding=sharding,
                        prefetch=2) as loader:
            seen = 0
            for staged in loader:
                tokens = staged["token"]
                assert isinstance(tokens, jax.Array)
                assert tokens.dtype == jnp.uint8
                assert tokens.shape == (batch, WINDOW)
                assert tokens.nbytes == batch * WINDOW    # a byte a token
                starts = np.asarray(staged["ts"])[:, 0]
                want = np.stack([stored[s:s + WINDOW] for s in starts])
                np.testing.assert_array_equal(np.asarray(tokens), want)
                ids = step(tokens)
                assert ids.dtype == jnp.int32
                np.testing.assert_array_equal(
                    np.asarray(ids), want.astype(np.int32) + 64)
                seen += batch
    assert seen == GROUPS
