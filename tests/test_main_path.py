"""The main path, through the library and no harness: a Parquet store ->
``make_reader`` (thread pool) -> ``DataLoader(sharding=NamedSharding(mesh,
P("data")))`` -> a jitted, donated train step, on the virtual CPU mesh.

Four things are held: the path trains, what is staged is what was stored,
the kernels asked for are the kernels compiled, and the stores the tests
and the example train on are a function of their seed."""
import functools
import importlib.util
import io
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow.parquet as pq
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dataset_utils import write_token_store
from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import materialize_dataset_local
from petastorm_tpu.jax import DataLoader, DTypePolicy
from petastorm_tpu.models import llama, resnet
from petastorm_tpu.ngram import NGram
from petastorm_tpu.ops.flash_attn import make_flash_attention
from petastorm_tpu.reader import make_reader
from petastorm_tpu.unischema import Unischema, UnischemaField

ROOT = pathlib.Path(__file__).parent.parent
WINDOW = 64          # tokens a window; flash tiles it at block 32 / 64
VOCAB = 128         # the models' vocabulary
STORE_VOCAB = 16    # the ids the token store draws from: a unigram to learn
FLASH_KERNELS = ("flash_fwd", "flash_bwd")


@functools.cache
def imagenet_example():
    spec = importlib.util.spec_from_file_location(
        "example_imagenet_writers", ROOT / "examples" / "imagenet" / "main.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def shardings(n_devices):
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("data",))
    return mesh, NamedSharding(mesh, P("data")), NamedSharding(mesh, P())


def stored_columns(url, names):
    """The store's columns as pyarrow reads its files: no reader, no codec."""
    table = pq.read_table(url[len("file://"):], columns=names)
    return {name: table.column(name).to_pylist() for name in names}


def token_windows():
    return NGram({o: ["ts", "token"] for o in range(WINDOW)},
                 delta_threshold=1, timestamp_field="ts",
                 timestamp_overlap=False, dense=True)


# ----------------------------------------------------------------- stores
@pytest.fixture(scope="module")
def token_store(tmp_path_factory):
    url = f"file://{tmp_path_factory.mktemp('main_path_tokens')}/tokens"
    write_token_store(url, windows=32, window=WINDOW, vocab=STORE_VOCAB, seed=7)
    return url


@pytest.fixture(scope="module")
def class_image_store(tmp_path_factory):
    """The example's class-separable JPEG store: 4 classes, 32 x 32."""
    url = f"file://{tmp_path_factory.mktemp('main_path_images')}/images"
    imagenet_example().write_synthetic_imagenet(
        url, rows=128, classes=4, seed=3, rows_per_row_group=16,
        image_size=32)
    return url


@pytest.fixture(scope="module")
def numbered_image_store(tmp_path_factory):
    """JPEG rows that carry their row number, so a staged row can be traced
    to the stored bytes whatever the order of delivery."""
    url = f"file://{tmp_path_factory.mktemp('main_path_numbered')}/images"
    schema = Unischema("NumberedImages", [
        UnischemaField("id", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("image", np.uint8, (24, 24, 3),
                       CompressedImageCodec("jpeg", 85), False),
        UnischemaField("label", np.int32, (), ScalarCodec(np.int32), False),
    ])
    rng = np.random.default_rng(11)
    with materialize_dataset_local(url, schema, rows_per_row_group=8) as w:
        for i in range(64):
            w.write_row({"id": np.int64(i), "label": np.int32(i % 5),
                         "image": rng.integers(0, 256, (24, 24, 3))
                         .astype(np.uint8)})
    return url


# -------------------------------------------------------- the path trains
def image_job(url, mesh, rows, replicated):
    state = jax.jit(
        lambda key: (lambda p: (p, jax.tree.map(jnp.zeros_like, p)))(
            resnet.init_params(key, num_classes=4)),
        out_shardings=replicated)(jax.random.PRNGKey(0))
    raw = resnet.make_train_step(learning_rate=0.01)

    def step(params, velocity, batch):
        params, velocity, loss, _acc = raw(
            params, velocity,
            {"image": batch["image"].astype(jnp.float32) / 255.0,
             "label": batch["label"]})
        return (params, velocity), loss

    reader = make_reader(url, num_epochs=None, shuffle_row_groups=True,
                         seed=0, reader_pool_type="thread", workers_count=2)
    loader = DataLoader(reader, batch_size=8 * mesh.size, sharding=rows,
                        prefetch=2, dtype_policy=DTypePolicy())
    return state, step, loader


def decoder_job(cfg, url, mesh, rows, replicated, **kernels):
    def sharded(attn):
        fn = jax.shard_map(attn, mesh=mesh, in_specs=(rows.spec,) * 3,
                           out_specs=rows.spec, check_vma=False)
        fn.supports_gqa = True
        return fn

    init_opt, raw = llama.make_train_step(
        cfg, learning_rate=1e-2, shift="roll", xent_chunk=64,
        remat_layers=True,
        **{name: sharded(attn) for name, attn in kernels.items()})
    state = jax.jit(
        lambda key: (lambda p: (p, init_opt(p)))(llama.init_params(key, cfg)),
        out_shardings=replicated)(jax.random.PRNGKey(0))

    def step(params, opt, batch):
        params, opt, loss = raw(params, opt, {"tokens": batch["token"]})
        return (params, opt), loss

    reader = make_reader(url, schema_fields=token_windows(), num_epochs=None,
                         shuffle_row_groups=True, seed=0,
                         reader_pool_type="thread", workers_count=2)
    loader = DataLoader(reader, batch_size=2 * mesh.size, sharding=rows,
                        prefetch=2)
    return state, step, loader


DENSE = llama.LlamaConfig(vocab=VOCAB, dim=32, n_layers=2, n_heads=4,
                          n_kv_heads=2, hidden=64)
# A full layer without positions, then a windowed one with RoPE; top 2 of
# 8 routed experts, of which this share holds two.
SPARSE = llama.LlamaConfig(
    vocab=VOCAB, dim=32, n_layers=2, n_heads=4, n_kv_heads=1, head_dim=8,
    rope_layout=(0, 1), sliding_window_layout=(0, 1), sliding_window=32,
    n_router_outputs=8, top_k=2, experts_held=(0, 2), expert_hidden=16,
    expert_act="relu", router_input="layer_input", embed_std=1.0)


def build_job(model, n_devices, class_image_store, token_store):
    mesh, rows, replicated = shardings(n_devices)
    if model == "resnet_jpeg":
        return image_job(class_image_store, mesh, rows, replicated)
    if model == "llama_dense":
        return decoder_job(DENSE, token_store, mesh, rows, replicated)
    return decoder_job(
        SPARSE, token_store, mesh, rows, replicated,
        attn_fn=make_flash_attention(causal=True, block_q=32, block_k=64),
        window_attn_fn=make_flash_attention(causal=True, window=32,
                                            block_q=32, block_k=64))


@pytest.fixture
def toy_resnet(monkeypatch):
    """ResNet-50's code at one bottleneck a stage and an eighth the width:
    the 50-layer step takes the CPU 20 s to compile."""
    monkeypatch.setattr(resnet, "_RESNET50_STAGES",
                        ((1, 16), (1, 32), (1, 64), (1, 128)))


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("model", ["resnet_jpeg", "llama_dense",
                                   "llama_dropless_window"])
def test_the_path_trains(model, n_devices, class_image_store, token_store,
                         toy_resnet):
    """Four donated steps on batches as the loader stages them: every loss
    finite, the last under the first, and one compilation (state that came
    back from a step laid out otherwise than it went in would make two)."""
    state, step, loader = build_job(model, n_devices, class_image_store,
                                    token_store)
    jitted = jax.jit(lambda state, batch: step(*state, batch),
                     donate_argnums=0)
    losses = []
    with loader:
        batches = iter(loader)
        for _ in range(4):
            state, loss = jitted(state, next(batches))
            losses.append(loss)
    losses = [float(loss) for loss in losses]
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
    assert jitted._cache_size() == 1


# --------------------------------------- what is staged is what was stored
def stored_images(url):
    from PIL import Image
    cols = stored_columns(url, ["id", "image", "label"])
    assert cols["id"] == list(range(len(cols["id"])))
    images = np.stack([np.asarray(Image.open(io.BytesIO(jpeg)).convert("RGB"))
                       for jpeg in cols["image"]])
    # int64 is staged as int32: JAX's 64-bit mode is off
    return {"id": np.asarray(cols["id"], np.int32), "image": images,
            "label": np.asarray(cols["label"], np.int32)}


def stored_tokens(url):
    cols = stored_columns(url, ["ts", "token"])
    order = np.argsort(cols["ts"])
    assert np.array_equal(np.asarray(cols["ts"])[order],
                          np.arange(len(order)))
    return np.asarray(cols["token"], np.int32)[order]


def assert_staged_as_stored(staged, want, sharding, n_devices):
    """``staged``: a global array; ``want``: the same rows decoded apart."""
    assert isinstance(staged, jax.Array)
    assert staged.sharding.is_equivalent_to(sharding, staged.ndim)
    assert staged.shape == want.shape and staged.dtype == want.dtype
    shards = staged.addressable_shards
    assert len(shards) == n_devices
    assert len({shard.device for shard in shards}) == n_devices
    for shard in shards:
        assert shard.data.shape[0] == want.shape[0] // n_devices
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      want[shard.index])


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_staged_image_rows_are_the_stored_rows(n_devices,
                                               numbered_image_store):
    _mesh, rows, _ = shardings(n_devices)
    stored = stored_images(numbered_image_store)
    seen = []
    with make_reader(numbered_image_store, num_epochs=None,
                     shuffle_row_groups=True, seed=5,
                     reader_pool_type="thread", workers_count=2) as reader:
        with DataLoader(reader, batch_size=2 * n_devices, sharding=rows,
                        prefetch=2, dtype_policy=DTypePolicy()) as loader:
            batches = iter(loader)
            for _ in range(3):
                batch = next(batches)
                assert sorted(batch) == ["id", "image", "label"]
                ids = np.asarray(batch["id"])
                seen.extend(ids.tolist())
                for name in ("id", "image", "label"):
                    assert_staged_as_stored(batch[name], stored[name][ids],
                                            rows, n_devices)
    assert len(set(seen)) == len(seen)      # no row twice within an epoch


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_staged_token_windows_are_the_stored_windows(n_devices, token_store):
    _mesh, rows, _ = shardings(n_devices)
    tokens = stored_tokens(token_store)
    firsts = []
    with make_reader(token_store, schema_fields=token_windows(),
                     num_epochs=None, shuffle_row_groups=True, seed=5,
                     reader_pool_type="thread", workers_count=2) as reader:
        with DataLoader(reader, batch_size=2 * n_devices, sharding=rows,
                        prefetch=2) as loader:
            batches = iter(loader)
            for _ in range(2):
                batch = next(batches)
                assert sorted(batch) == ["token", "ts"]
                first = np.asarray(batch["ts"])[:, 0]
                assert (first % WINDOW == 0).all()   # a row group is whole
                firsts.extend(first.tolist())
                stamps = first[:, None] + np.arange(WINDOW, dtype=np.int32)
                assert_staged_as_stored(batch["ts"], stamps, rows, n_devices)
                assert_staged_as_stored(batch["token"], tokens[stamps], rows,
                                        n_devices)
    assert len(set(firsts)) == len(firsts)


# ---------------------- the kernels asked for are the kernels compiled
def decoder_step_lowered(cfg, seq, **kernels):
    """The train step lowered for ``(2, seq)`` tokens: traced, never run."""
    init_opt, raw = llama.make_train_step(cfg, shift="roll", **kernels)
    params = jax.eval_shape(lambda key: llama.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    return jax.jit(raw).lower(params, jax.eval_shape(init_opt, params),
                              {"tokens": tokens})


@pytest.mark.parametrize("cfg, kernels", [
    (DENSE, dict(attn_fn=make_flash_attention(causal=True))),
    # the full layer goes through dense attention: only the window's kernel
    # is left to refuse the sequence
    (SPARSE, dict(window_attn_fn=make_flash_attention(causal=True,
                                                      window=32))),
], ids=["full", "window"])
def test_a_flash_step_on_an_untileable_window_raises_before_tracing_ends(
        cfg, kernels):
    """Whoever passes ``make_flash_attention`` asked for the kernel: a
    sequence its tiles cannot divide is an error while the step is traced,
    never a quiet run through dense attention."""
    with pytest.raises(ValueError, match="cannot tile"):
        decoder_step_lowered(cfg, 100, **kernels)


def test_a_flash_step_lowers_to_the_three_flash_kernels():
    flash = decoder_step_lowered(
        DENSE, WINDOW,
        attn_fn=make_flash_attention(causal=True, block_q=32, block_k=64))
    text = flash.as_text(debug_info=True)
    for name in FLASH_KERNELS:
        assert name in text, name
    assert "_bwd_dq" not in text and "_bwd_dkv" not in text     # one kernel
    dense = decoder_step_lowered(DENSE, WINDOW).as_text(debug_info=True)
    assert not any(name in dense for name in FLASH_KERNELS)


# ----------------------------- the fixtures are a function of the seed
def write_images(url, seed):
    imagenet_example().write_synthetic_imagenet(
        url, rows=24, classes=3, seed=seed, rows_per_row_group=8,
        image_size=16)
    return stored_columns(url, ["image", "label"])


def write_tokens(url, seed):
    write_token_store(url, windows=4, window=16, vocab=VOCAB, seed=seed)
    return stored_columns(url, ["ts", "token"])


@pytest.mark.parametrize("write", [write_images, write_tokens])
def test_a_store_is_a_function_of_its_seed(write, tmp_path):
    first = write(f"file://{tmp_path}/a", seed=5)
    again = write(f"file://{tmp_path}/b", seed=5)
    other = write(f"file://{tmp_path}/c", seed=6)
    assert first == again           # byte-equal columns, JPEG bytes included
    assert first != other
