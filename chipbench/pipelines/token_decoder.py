"""Token store -> ``make_reader`` + ``NGram(dense=True)`` -> ``DataLoader``
-> ``NamedSharding(mesh, P("data"))`` -> the jitted, donated AdamW step of
``llama.make_train_step`` with the Pallas flash kernels under ``shard_map``;
and the float32 dense-attention reference that follows its first steps from
the stored tokens."""
from __future__ import annotations


import numpy as np

from chipbench import flops, stores
from chipbench.pipelines import common

ADAM_B1 = 0.9


class Job:
    unit = "tokens"

    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 store_path: str):
        self.cfg, self.traffic, self.devices = config, traffic, devices
        self.seed, self.store_path = seed, store_path
        self.window = traffic["window"]
        self.global_batch = traffic["per_chip_batch"] * len(devices)
        self.items_per_step = self.global_batch * self.window
        self.flops_per_step = flops.decoder_train_flops(
            config, self.global_batch, self.window)
        self.workers = int(traffic["workers"])
        self.n_groups = traffic["store_windows"]
        self.expected_kernels = ("flash",)    # families, by role

    # ------------------------------------------------------------ program
    def write_store(self) -> None:
        stores.write_token_store(self.store_path, self.n_groups, self.window,
                                 self.cfg["vocab_size"], self.seed)

    def start(self) -> None:
        import jax
        from jax.sharding import PartitionSpec as P
        from petastorm_tpu.jax import DataLoader
        from petastorm_tpu.models import llama
        from petastorm_tpu.ngram import NGram
        from petastorm_tpu.ops.flash_attn import (make_flash_attention,
                                                  require_flash_tiles)
        from petastorm_tpu.reader import make_reader

        c, t = self.cfg, self.traffic
        self.mesh, self.rows, self.replicated = common.mesh_and_shardings(
            self.devices)
        require_flash_tiles(self.window, self.window, causal=True)
        attn = jax.shard_map(make_flash_attention(causal=True),
                             mesh=self.mesh, in_specs=(P("data"),) * 3,
                             out_specs=P("data"), check_vma=False)
        attn.supports_gqa = True
        lcfg = llama.LlamaConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], hidden=c["intermediate_size"],
            rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"])
        if lcfg.head_dim != c["head_dim"]:
            raise ValueError("head_dim is not hidden_size / heads")
        init_opt, raw = llama.make_train_step(
            lcfg, learning_rate=c["optimizer"]["learning_rate"], shift="roll",
            attn_fn=attn, xent_chunk=t["xent_chunk"],
            remat_layers=t["remat_layers"])
        self.params, self.opt = jax.jit(
            lambda key: (lambda p: (p, init_opt(p)))(
                llama.init_params(key, lcfg)),
            out_shardings=self.replicated)(common.seed_key(self.seed))
        self._jitted = jax.jit(
            lambda params, opt, tokens: raw(params, opt, {"tokens": tokens}),
            donate_argnums=(0, 1))
        self._step = None
        ngram = NGram({o: ["ts", "token"] for o in range(self.window)},
                      delta_threshold=1, timestamp_field="ts",
                      timestamp_overlap=False, dense=True)
        self._reader = make_reader(
            f"file://{self.store_path}", schema_fields=ngram, num_epochs=None,
            shuffle_row_groups=t["shuffle_row_groups"],
            seed=self.seed % (2 ** 31 - 1),
            reader_pool_type=t["reader_pool_type"],
            workers_count=self.workers)
        self._loader = DataLoader(self._reader, batch_size=self.global_batch,
                                  sharding=self.rows, prefetch=t["prefetch"],
                                  echo=t["echo"])
        self._it = iter(self._loader)

    def next_batch(self):
        batch = next(self._it)
        if batch["token"].shape != (self.global_batch, self.window):
            raise ValueError(f"staged tokens are {batch['token'].shape}")
        return batch

    def compile(self, batch):
        self._step = self._jitted.lower(self.params, self.opt,
                                        batch["token"]).compile()
        return self._step

    def step(self, batch):
        self.params, self.opt, loss = self._step(self.params, self.opt,
                                                 batch["token"])
        return loss

    def batch_key(self, batch):
        return {"ts": batch["ts"], "token": batch["token"]}

    def host_copy(self, batch) -> dict:
        return {k: np.asarray(batch[k]) for k in ("ts", "token")}

    def _delta_norms(self, params) -> dict:
        """Leaf norms of params - the seed's init, the init made again leaf
        by leaf inside one program, so that no second copy of the weights
        is held."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import decoder as ref

        def norms(p, key):     # the key is an argument: one program for
            init = ref.init_params(key, self.cfg)      # every seed
            return [jnp.sqrt(jnp.sum(jnp.square(a - b))) for a, b in
                    zip(jax.tree.leaves(p), jax.tree.leaves(init))]

        return dict(zip(common.leaf_names(params), map(float, jax.jit(norms)(
            params, common.seed_key(self.seed)))))

    def grad_leaf_norms(self) -> dict:
        """After step 1 Adam's first moment is (1 - b1) * g."""
        return {k: v / (1 - ADAM_B1)
                for k, v in common.leaf_norms(self.opt[0].mu).items()}

    def delta_leaf_norms(self) -> dict:
        return self._delta_norms(self.params)

    def stall_report(self) -> dict:
        return self._loader.stall_report()

    def free(self) -> None:
        self._loader.close()
        self._reader.stop()
        self._reader.join()
        self.params = self.opt = self._step = self._jitted = None
        self._it = None

    # ---------------------------------------------------------- reference
    def _stored(self) -> np.ndarray:
        if not hasattr(self, "_stored_tokens"):
            cols = stores.read_columns(self.store_path, ["ts", "token"])
            if not np.array_equal(cols["ts"], np.arange(len(cols["ts"]))):
                raise ValueError("stored timestamps are not the row numbers")
            self._stored_tokens = cols["token"]
        return self._stored_tokens

    def stored_batch(self, starts: np.ndarray) -> np.ndarray:
        tokens = self._stored()
        return np.stack([tokens[s:s + self.window] for s in starts.tolist()])

    def reference(self, key_batches, precision=None, rows=None) -> dict:
        """Follow the batches (each named by its windows' first timestamps)
        with the plain reference; see ``image_classifier.Job.reference``."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import decoder as ref
        opt = self.cfg["optimizer"]
        def one(params, mu, nu, count, tokens):
            params, mu, nu, count, loss, grads = ref.train_step(
                params, mu, nu, count, tokens, self.cfg, precision=precision,
                learning_rate=opt["learning_rate"],
                weight_decay=opt["weight_decay"])
            norms = [jnp.sqrt(jnp.sum(jnp.square(g)))
                     for g in jax.tree.leaves(grads)]
            return params, mu, nu, count, loss, norms

        step = jax.jit(one, donate_argnums=(0, 1, 2))
        params, mu, nu = jax.jit(
            lambda key: (lambda p: (p, jax.tree.map(jnp.zeros_like, p),
                                    jax.tree.map(jnp.zeros_like, p)))(
                ref.init_params(key, self.cfg)),
            out_shardings=self.replicated)(common.seed_key(self.seed))
        names = common.leaf_names(params)
        count = jnp.zeros((), jnp.int32)
        out = {"losses": []}
        for n, keys in enumerate(key_batches):
            starts = np.asarray(keys["ts"])[:rows, 0]
            tokens = jax.device_put(self.stored_batch(starts), self.rows)
            params, mu, nu, count, loss, norms = step(params, mu, nu, count,
                                                      tokens)
            out["losses"].append(float(loss))
            if n == 0:
                out["grad_norms"] = dict(zip(names, map(float, norms)))
        out["delta_norms"] = self._delta_norms(params)
        return out

    # ------------------------------------------------------- data checks
    def delivery(self, keys: list) -> dict:
        ts = np.concatenate([k["ts"] for k in keys])
        starts = ts[:, 0]
        whole = (starts % self.window == 0) & np.all(
            ts == starts[:, None] + np.arange(self.window), axis=1)
        out = {"rows_out_of_group": int((~whole).sum())}
        out.update(common.delivery_numbers(starts // self.window,
                                           self.n_groups))
        return out

    def staged_faults(self, host_batch: dict) -> int:
        want = self.stored_batch(host_batch["ts"][:, 0])
        return int((host_batch["token"] != want).sum())
