"""MNIST MLP trained through the petastorm-tpu pipeline (BASELINE config 2).

Writes (synthetic-or-real) MNIST to a petastorm-tpu store, then trains a
pure-JAX MLP with the DataLoader staging batches to the device. Run with
``--real`` to use torchvision-format MNIST if available; default generates
a separable synthetic digit problem so the example is self-contained.
"""
import argparse
import time

import numpy as np

from petastorm_tpu import Unischema, UnischemaField
from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
from petastorm_tpu.etl.writer import materialize_dataset_local
from petastorm_tpu.jax import DataLoader, DTypePolicy
from petastorm_tpu.reader import make_reader

MnistSchema = Unischema("MnistSchema", [
    UnischemaField("image", np.float32, (784,), NdarrayCodec(), False),
    UnischemaField("label", np.int32, (), ScalarCodec(np.int32), False),
])


def synthetic_mnist(n: int, seed=0):
    """Linearly separable 10-class problem shaped like MNIST."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(10, 784)).astype(np.float32)
    labels = rng.integers(0, 10, n).astype(np.int32)
    images = protos[labels] + 0.5 * rng.normal(size=(n, 784)).astype(np.float32)
    return images, labels


def write_dataset(url: str, images, labels):
    with materialize_dataset_local(url, MnistSchema, rows_per_row_group=1000) as w:
        for img, lbl in zip(images, labels):
            w.write_row({"image": img, "label": lbl})


def train(url: str, epochs: int = 3, batch_size: int = 128):
    import jax
    from petastorm_tpu.models import mlp

    params = mlp.init_params(jax.random.PRNGKey(0))
    momentum = jax.tree.map(lambda p: p * 0, params)
    step = jax.jit(mlp.make_train_step(learning_rate=0.05))

    for epoch in range(epochs):
        t0 = time.time()
        losses, accs = [], []
        with make_reader(url, num_epochs=1, shuffle_row_groups=True, seed=epoch) as reader:
            loader = DataLoader(reader, batch_size=batch_size,
                                shuffling_queue_capacity=5000, seed=epoch)
            for batch in loader:
                params, momentum, loss, acc = step(params, momentum, batch)
                losses.append(float(loss))
                accs.append(float(acc))
        print(f"epoch {epoch}: loss={np.mean(losses):.4f} "
              f"acc={np.mean(accs):.4f} ({time.time()-t0:.1f}s, "
              f"{len(losses)} steps)")
    return np.mean(accs[-10:])


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--url", default="file:///tmp/mnist_tpu")
    parser.add_argument("--rows", type=int, default=10000)
    parser.add_argument("--epochs", type=int, default=3)
    args = parser.parse_args()
    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    ensure_compile_cache()

    images, labels = synthetic_mnist(args.rows)
    write_dataset(args.url, images, labels)
    final_acc = train(args.url, epochs=args.epochs)
    print(f"final train accuracy: {final_acc:.4f}")
    assert final_acc > 0.9, "training did not converge"


if __name__ == "__main__":
    main()
