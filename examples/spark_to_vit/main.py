"""Dataset-converter -> ViT training (BASELINE config 4).

With pyspark installed this materializes a Spark DataFrame through
``make_spark_converter`` and trains from the cached store; without a JVM
(TPU pods) it builds the same cached Parquet store directly and uses the
identical ``make_batch_reader -> BatchedDataLoader`` consumption path — the
converter's read side is exactly this.
"""
import argparse
import time

import numpy as np


def build_store_sparkless(url: str, rows: int, classes: int, image: int, seed=0):
    import pyarrow as pa
    import pyarrow.parquet as pq
    import os
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(classes, image * image * 3)).astype(np.float32)
    labels = rng.integers(0, classes, rows).astype(np.int32)
    feats = (protos[labels] + 0.7 * rng.normal(size=(rows, image * image * 3))
             ).astype(np.float32)
    path = url[len("file://"):]
    os.makedirs(path, exist_ok=True)
    table = pa.table({
        "features": pa.FixedSizeListArray.from_arrays(pa.array(feats.reshape(-1)),
                                                      image * image * 3),
        "label": labels,
    })
    pq.write_table(table, f"{path}/part-0.parquet", row_group_size=256)
    from petastorm_tpu.etl.dataset_metadata import write_dataset_metadata
    write_dataset_metadata(url, None)


def get_loader(url: str, batch_size: int, image: int):
    """The converter consumption path (identical with or without Spark)."""
    try:
        import pyspark  # noqa: F401
        from petastorm_tpu.spark.spark_dataset_converter import SparkDatasetConverter
        converter = SparkDatasetConverter(url, dataset_size=-1)
        return converter.make_jax_loader(batch_size=batch_size, cur_shard=None,
                                         shuffle_row_groups=True, seed=0)
    except ImportError:
        from petastorm_tpu.jax import BatchedDataLoader
        from petastorm_tpu.reader import make_batch_reader
        reader = make_batch_reader(url, num_epochs=None, shuffle_row_groups=True,
                                   seed=0)
        return BatchedDataLoader(reader, batch_size=batch_size)


def train(url: str, steps: int, batch_size: int, classes: int, image: int):
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.models import vit

    params = vit.init_params(jax.random.PRNGKey(0), image_size=image, patch=8,
                             dim=64, depth=2, heads=4, mlp_dim=128,
                             num_classes=classes)

    def loss_fn(params, batch):
        images = batch["features"].reshape(-1, image, image, 3)
        logits = vit.apply(params, images, patch=8, heads=4)
        logp = jax.nn.log_softmax(logits)
        labels = batch["label"].astype(jnp.int32)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
        return nll, (logits.argmax(-1) == labels).mean()

    @jax.jit
    def step(params, batch):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        return params, loss, acc

    with get_loader(url, batch_size, image) as loader:
        it = iter(loader)
        losses = []
        t0 = time.time()
        for i in range(steps):
            params, loss, acc = step(params, next(it))
            losses.append(float(loss))
            if (i + 1) % 10 == 0:
                print(f"step {i+1}: loss={np.mean(losses[-10:]):.4f} "
                      f"acc={float(acc):.3f}")
    print(f"{steps * batch_size / (time.time() - t0):.0f} samples/sec; "
          f"final loss {losses[-1]:.4f} (random={np.log(10):.2f})")
    assert losses[-1] < losses[0]
    return losses


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--url", default="file:///tmp/converter_vit")
    parser.add_argument("--rows", type=int, default=4096)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--batch-size", type=int, default=64)
    args = parser.parse_args()
    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import os
    classes, image = 10, 16
    if not os.path.exists(args.url.replace("file://", "") + "/_common_metadata"):
        print("building cached store (spark-free path)...")
        build_store_sparkless(args.url, args.rows, classes, image)
    train(args.url, args.steps, args.batch_size, classes, image)


if __name__ == "__main__":
    main()
