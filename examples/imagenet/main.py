"""ImageNet-style ResNet-50 training through the petastorm-tpu pipeline
(BASELINE config 3): CompressedImageCodec jpeg decode in reader workers ->
host batches -> HBM staging -> DP over all local devices, with input-stall%
measured against the real device step.

Uses a synthetic class-separable image store so the example is
self-contained; swap ``write_synthetic_imagenet`` for a real ingest job to
train on actual ImageNet.
"""
import argparse
import time

import numpy as np

from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import materialize_dataset_local
from petastorm_tpu.jax import DataLoader, DTypePolicy
from petastorm_tpu.reader import make_reader
from petastorm_tpu.unischema import Unischema, UnischemaField


def make_imagenet_schema(image_size: int = 224) -> Unischema:
    return Unischema("ImagenetSchema", [
        UnischemaField("image", np.uint8, (image_size, image_size, 3),
                       CompressedImageCodec("jpeg", 85), False),
        UnischemaField("label", np.int32, (), ScalarCodec(np.int32), False),
    ])


ImagenetSchema = make_imagenet_schema()


def write_synthetic_imagenet(url: str, rows: int, classes: int = 100,
                             seed: int = 0, rows_per_row_group: int = 64,
                             image_size: int = 224):
    """Class-separable synthetic images: a per-class 8x8 proto upsampled to
    ``image_size`` plus uniform noise — compresses like a photo, trains like
    a toy. ``image_size`` must be a multiple of 8; smaller sizes make the
    ResNet step CPU-feasible for tests (ResNet is fully convolutional)."""
    if image_size % 8:
        raise ValueError("image_size must be a multiple of 8")
    rng = np.random.default_rng(seed)
    protos = rng.integers(60, 195, (classes, 8, 8, 3)).astype(np.uint8)
    up = image_size // 8
    with materialize_dataset_local(url, make_imagenet_schema(image_size),
                                   rows_per_row_group=rows_per_row_group) as w:
        for _ in range(rows):
            label = int(rng.integers(0, classes))
            base = np.kron(protos[label], np.ones((up, up, 1), np.uint8))
            noise = rng.integers(0, 60, (image_size, image_size, 3)).astype(np.uint8)
            w.write_row({"image": np.clip(base + noise, 0, 255).astype(np.uint8),
                         "label": np.int32(label)})


def train(url: str, steps: int = 30, per_device_batch: int = 8,
          classes: int = 100, learning_rate: float = 0.05):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.models import resnet

    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(len(devices)), ("data",))
    batch_sharding = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    batch_size = per_device_batch * len(devices)

    params = jax.device_put(resnet.init_params(jax.random.PRNGKey(0), classes),
                            replicated)
    velocity = jax.device_put(jax.tree.map(lambda p: p * 0, params), replicated)
    raw_step = resnet.make_train_step(learning_rate=learning_rate)

    def preprocess_and_step(params, velocity, batch, key):
        # Device-side augmentation: the host ships compact uint8 batches,
        # flips/crops happen on-chip (petastorm_tpu.ops), keyed per step so
        # replays are deterministic.
        from petastorm_tpu.ops import random_crop, random_flip_horizontal
        k1, k2 = jax.random.split(key)
        images = random_flip_horizontal(k1, batch["image"])
        images = random_crop(k2, images, padding=4)
        images = images.astype(jnp.float32) / 255.0
        return raw_step(params, velocity,
                        {"image": images, "label": batch["label"]})

    step = jax.jit(preprocess_and_step, donate_argnums=(0, 1))
    step_key = jax.random.PRNGKey(42)

    with make_reader(url, num_epochs=None, shuffle_row_groups=True, seed=0,
                     workers_count=4) as reader:
        loader = DataLoader(reader, batch_size=batch_size,
                            sharding=batch_sharding, prefetch=2,
                            dtype_policy=DTypePolicy())
        it = iter(loader)
        # Warm up: first step compiles.
        batch = next(it)
        params, velocity, loss, acc = step(params, velocity, batch, step_key)
        jax.block_until_ready(loss)

        wait_s = compute_s = 0.0
        losses = []
        for i in range(steps):
            t0 = time.perf_counter()
            batch = next(it)
            t1 = time.perf_counter()
            params, velocity, loss, acc = step(
                params, velocity, batch, jax.random.fold_in(step_key, i))
            jax.block_until_ready(loss)
            t2 = time.perf_counter()
            wait_s += t1 - t0
            compute_s += t2 - t1
            losses.append(float(loss))
            if (i + 1) % 10 == 0:
                print(f"step {i+1}: loss={np.mean(losses[-10:]):.3f} "
                      f"acc={float(acc):.3f}")

    total = wait_s + compute_s
    stall = 100.0 * wait_s / total
    sps = steps * batch_size / total
    print(f"devices={len(devices)} global_batch={batch_size} "
          f"throughput={sps:.1f} samples/sec input_stall={stall:.1f}%")
    assert losses[-1] < losses[0] * 1.05, "loss did not trend down"
    return stall, sps


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--url", default="file:///tmp/imagenet_tpu")
    parser.add_argument("--rows", type=int, default=2048)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--per-device-batch", type=int, default=8)
    args = parser.parse_args()
    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import os
    if not os.path.exists(args.url.replace("file://", "") + "/_common_metadata"):
        print("writing synthetic imagenet store...")
        write_synthetic_imagenet(args.url, args.rows)
    train(args.url, steps=args.steps, per_device_batch=args.per_device_batch)


if __name__ == "__main__":
    main()
