"""JPEG store -> ``make_reader`` (thread pool, native decode) ->
``DataLoader`` -> ``NamedSharding(mesh, P("data"))`` -> the jitted, donated
step of ``resnet.make_train_step``; and the float32 reference that follows
its first steps from the stored bytes."""
from __future__ import annotations

import io
import os
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np

from chipbench import flops, stores
from chipbench.pipelines import common



class Job:
    unit = "images"

    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 store_path: str):
        self.cfg, self.traffic, self.devices = config, traffic, devices
        self.seed, self.store_path = seed, store_path
        self.global_batch = traffic["per_chip_batch"] * len(devices)
        self.items_per_step = self.global_batch
        self.flops_per_step = self.global_batch * flops.resnet50_train_flops(
            config["image_size"], config["num_classes"])
        cores = len(os.sched_getaffinity(0))
        self.workers = (max(1, cores - 2) if traffic["workers"] == "cores-2"
                        else int(traffic["workers"]))
        self.n_groups = config["store_rows"] // config["rows_per_row_group"]
        self.expected_kernels = ()

    # ------------------------------------------------------------ program
    def write_store(self) -> None:
        c = self.cfg
        stores.write_image_store(
            self.store_path, c["store_rows"], c["num_classes"], self.seed,
            c["image_size"], c["rows_per_row_group"], c["jpeg_quality"],
            threads=self.workers)

    def start(self) -> None:
        """State from the seed in one jitted call, reader and loader up."""
        import jax
        import jax.numpy as jnp
        from petastorm_tpu.jax import DataLoader, DTypePolicy
        from petastorm_tpu.models import resnet
        from petastorm_tpu.reader import make_reader

        opt = self.cfg["optimizer"]
        self.mesh, self.rows, self.replicated = common.mesh_and_shardings(
            self.devices)
        self.params, self.velocity = jax.jit(
            lambda key: (lambda p: (p, jax.tree.map(jnp.zeros_like, p)))(
                resnet.init_params(key, self.cfg["num_classes"])),
            out_shardings=self.replicated)(common.seed_key(self.seed))
        raw = resnet.make_train_step(learning_rate=opt["learning_rate"],
                                     weight_decay=opt["weight_decay"],
                                     momentum=opt["momentum"])

        def step(params, velocity, batch):
            images = batch["image"].astype(jnp.float32) / 255.0
            return raw(params, velocity,
                       {"image": images, "label": batch["label"]})

        self._jitted = jax.jit(step, donate_argnums=(0, 1))
        self._step = None
        t = self.traffic
        self._reader = make_reader(
            f"file://{self.store_path}", num_epochs=None,
            shuffle_row_groups=t["shuffle_row_groups"],
            seed=self.seed % (2 ** 31 - 1),
            reader_pool_type=t["reader_pool_type"],
            workers_count=self.workers)
        self._loader = DataLoader(self._reader, batch_size=self.global_batch,
                                  sharding=self.rows, prefetch=t["prefetch"],
                                  dtype_policy=DTypePolicy(), echo=t["echo"])
        self._it = iter(self._loader)

    def next_batch(self):
        return next(self._it)

    def compile(self, batch):
        """AOT-compile the step for this batch's shapes; the compiled
        object is what set-up and the window both call."""
        args = {"image": batch["image"], "label": batch["label"]}
        self._step = self._jitted.lower(self.params, self.velocity,
                                        args).compile()
        return self._step

    def step(self, batch):
        self.params, self.velocity, loss, _ = self._step(
            self.params, self.velocity,
            {"image": batch["image"], "label": batch["label"]})
        return loss

    def batch_key(self, batch):
        return batch["id"]

    def host_copy(self, batch) -> dict:
        return {k: np.asarray(batch[k]) for k in ("id", "image", "label")}

    def _reference_init(self):
        import jax
        from chipbench.reference import resnet50 as ref
        return jax.jit(ref.init_params, static_argnums=1,
                       out_shardings=self.replicated)(
            common.seed_key(self.seed), self.cfg["num_classes"])

    def grad_leaf_norms(self) -> dict:
        """After step 1 the velocity is g + decay * p0: take the decay off
        with p0 made again from the seed."""
        import jax
        decay = self.cfg["optimizer"]["weight_decay"]
        return common.leaf_norms(jax.tree.map(
            lambda v, p: v - decay * p, self.velocity, self._reference_init()))

    def delta_leaf_norms(self) -> dict:
        import jax
        return common.leaf_norms(jax.tree.map(
            lambda a, b: a - b, self.params, self._reference_init()))

    def stall_report(self) -> dict:
        return self._loader.stall_report()

    def free(self) -> None:
        self._loader.close()
        self._reader.stop()
        self._reader.join()
        self.params = self.velocity = self._step = self._jitted = None
        self._it = None

    # ---------------------------------------------------------- reference
    def _stored(self) -> dict:
        if not hasattr(self, "_stored_cols"):
            self._stored_cols = stores.read_columns(
                self.store_path, ["id", "image", "label"])
        return self._stored_cols

    def stored_batch(self, ids: np.ndarray) -> dict:
        """The rows ``ids`` as the stored bytes give them: JPEG decoded by
        Pillow, not by the program's codec."""
        from PIL import Image
        cols = self._stored()
        if not np.array_equal(cols["id"], np.arange(len(cols["id"]))):
            raise ValueError("stored ids are not the row numbers")

        def decode(i):
            return np.asarray(Image.open(io.BytesIO(cols["image"][i]))
                              .convert("RGB"))

        with ThreadPoolExecutor(self.workers) as pool:
            images = np.stack(list(pool.map(decode, ids.tolist())))
        return {"image": images, "label": cols["label"][ids].astype(np.int32)}

    def reference(self, id_batches, precision=None, rows=None) -> dict:
        """Follow ``id_batches`` with the plain reference -> losses, the
        first gradient's and the total change's leaf norms. ``precision``
        names the control's dtype; ``rows`` keeps the first so many rows of
        each batch (a planted fault)."""
        import jax
        from chipbench.reference import resnet50 as ref
        opt = self.cfg["optimizer"]
        step = jax.jit(partial(ref.train_step, precision=precision,
                               learning_rate=opt["learning_rate"],
                               weight_decay=opt["weight_decay"],
                               momentum=opt["momentum"]),
                       donate_argnums=(0, 1))
        params = self._reference_init()
        velocity = jax.tree.map(lambda p: p * 0, params)
        out = {"losses": []}
        for n, ids in enumerate(id_batches):
            batch = self.stored_batch(np.asarray(ids)[:rows])
            images = jax.device_put(batch["image"], self.rows)
            labels = jax.device_put(batch["label"], self.rows)
            params, velocity, loss, grads = step(params, velocity, images,
                                                 labels)
            out["losses"].append(float(loss))
            if n == 0:
                out["grad_norms"] = common.leaf_norms(grads)
            del grads
        out["delta_norms"] = common.leaf_norms(jax.tree.map(
            lambda a, b: a - b, params, self._reference_init()))
        return out

    # ------------------------------------------------------- data checks
    def delivery(self, keys: list) -> dict:
        """``keys``: every delivered batch's ids, in order."""
        per = self.cfg["rows_per_row_group"]
        chunks = np.concatenate(keys).reshape(-1, per)
        whole = (chunks[:, :1] % per == 0).ravel() & np.all(
            np.diff(chunks, axis=1) == 1, axis=1)
        out = {"rows_out_of_group": int((~whole).sum())}
        out.update(common.delivery_numbers(chunks[:, 0] // per,
                                           self.n_groups))
        return out

    def staged_faults(self, host_batch: dict) -> int:
        """Elements of a staged batch that differ from the stored bytes'
        own decode (lossless decode, unaltered collate and staging)."""
        want = self.stored_batch(host_batch["id"])
        return int((host_batch["image"] != want["image"]).sum()
                   + (host_batch["label"] != want["label"]).sum())
