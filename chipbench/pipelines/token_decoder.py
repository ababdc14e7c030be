"""Token store -> ``make_reader`` + ``NGram(dense=True)`` -> ``DataLoader``
-> ``NamedSharding(mesh, P("data"))`` -> the jitted, donated AdamW step of
``llama.make_train_step`` with the Pallas flash kernels under ``shard_map``;
and the float32 dense-attention reference that follows its first steps from
the stored tokens.

``Job`` is every decoder pipeline's: the store, reader, loader, state, step,
the leaf norms and the reference loop. A decoder of another kind subclasses
it and fills in the hooks (the class attributes and methods under the two
``hooks`` headings below): its ``LlamaConfig``, its operations a step, its
kernel families, its attention callables, the step's batch, where AdamW's
first moment sits, its reference module and, where the reference goes a
row at a time, how a row's gradient is taken and counted."""
from __future__ import annotations

import importlib

import numpy as np

from chipbench import flops, stores
from chipbench.pipelines import common

ADAM_B1 = 0.9


def llama_config(c: dict):
    """The program's static configuration from the file's keys."""
    from petastorm_tpu.models import llama
    lcfg = llama.LlamaConfig(
        vocab=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], hidden=c["intermediate_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"])
    if lcfg.head_dim != c["head_dim"]:
        raise ValueError("head_dim is not hidden_size / heads")
    return lcfg


class Job:
    unit = "tokens"
    # ------------------------------------------------------------- hooks
    llama_config = staticmethod(llama_config)
    train_flops = staticmethod(flops.decoder_train_flops)
    expected_kernels = ("flash",)    # families, by role
    with_stats = False               # the step also returns MoE statistics
    ref = "decoder"                  # chipbench.reference.<ref>
    ref_by_rows = False              # the reference takes a row at a time

    def attention_fns(self, lcfg, sharded) -> dict:
        """``make_train_step``'s attention arguments; ``sharded(fn)`` puts
        a kernel under ``shard_map`` over the batch. The causal flash
        kernels, and where the configuration has a window, the windowed
        ones for the layers under it."""
        from petastorm_tpu.ops.flash_attn import (make_flash_attention,
                                                  require_flash_tiles)
        require_flash_tiles(self.window, self.window, causal=True)
        fns = {"attn_fn": sharded(make_flash_attention(causal=True))}
        if lcfg.sliding_window is not None:
            fns["window_attn_fn"] = sharded(make_flash_attention(
                causal=True, window=lcfg.sliding_window))
        return fns

    def step_batch(self, tokens) -> dict:
        """The step's batch from the staged tokens (inside the jit)."""
        return {"tokens": tokens}

    def first_moment(self):
        """AdamW's first moment, a tree named as the parameters are."""
        return self.opt[0].mu

    # ----------------------------------------------------------- program
    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 store_path: str):
        self.cfg, self.traffic, self.devices = config, traffic, devices
        self.seed, self.store_path = seed, store_path
        self.window = traffic["window"]
        self.global_batch = traffic["per_chip_batch"] * len(devices)
        self.items_per_step = self.global_batch * self.window
        self.flops_per_step = self.train_flops(
            config, self.global_batch, self.window)
        self.workers = int(traffic["workers"])
        self.n_groups = traffic["store_windows"]

    def write_store(self) -> None:
        stores.write_token_store(self.store_path, self.n_groups, self.window,
                                 self.cfg["vocab_size"], self.seed)

    def _sharded(self, attn, replicated: int = 0, gqa: bool = True):
        """``attn`` under ``shard_map``: q, k, v split over the batch, then
        ``replicated`` operands held whole."""
        import jax
        from jax.sharding import PartitionSpec as P
        fn = jax.shard_map(attn, mesh=self.mesh,
                           in_specs=(P("data"),) * 3 + (P(),) * replicated,
                           out_specs=P("data"), check_vma=False)
        if gqa:
            fn.supports_gqa = True
        return fn

    def start(self) -> None:
        import jax
        from petastorm_tpu.jax import DataLoader
        from petastorm_tpu.models import llama
        from petastorm_tpu.ngram import NGram
        from petastorm_tpu.reader import make_reader

        c, t = self.cfg, self.traffic
        self.mesh, self.rows, self.replicated = common.mesh_and_shardings(
            self.devices)
        self.lcfg = lcfg = self.llama_config(c)
        init_opt, raw = llama.make_train_step(
            lcfg, learning_rate=c["optimizer"]["learning_rate"], shift="roll",
            xent_chunk=t["xent_chunk"], remat_layers=t["remat_layers"],
            with_stats=self.with_stats,
            **self.attention_fns(lcfg, self._sharded))
        self.params, self.opt = jax.jit(
            lambda key: (lambda p: (p, init_opt(p)))(
                llama.init_params(key, lcfg)),
            out_shardings=self.replicated)(common.seed_key(self.seed))
        self._jitted = jax.jit(
            lambda params, opt, tokens: raw(params, opt,
                                            self.step_batch(tokens)),
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

    def _ref(self):
        return importlib.import_module(f"chipbench.reference.{self.ref}")

    def _delta_norms(self, params) -> dict:
        """Leaf norms of params - the seed's init, the init made again leaf
        by leaf inside one program, so that no second copy of the weights
        is held."""
        import jax
        import jax.numpy as jnp
        ref = self._ref()

        def norms(p, key):     # the key is an argument: one program for
            init = ref.init_params(key, self.cfg)      # every seed
            return [jnp.sqrt(jnp.sum(jnp.square(a - b))) for a, b in
                    zip(jax.tree.leaves(p), jax.tree.leaves(init))]

        return dict(zip(common.leaf_names(params), map(float, jax.jit(norms)(
            params, common.seed_key(self.seed)))))

    def grad_leaf_norms(self) -> dict:
        """After step 1 Adam's first moment is (1 - b1) * g. A leaf that
        has no moment (one held outside AdamW, which no gradient reaches)
        reads 0."""
        norms = common.leaf_norms(self.first_moment())
        return {k: norms.get(k, 0.0) / (1 - ADAM_B1)
                for k in common.leaf_names(self.params)}

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
        with the plain reference; see ``image_classifier.Job.reference``.
        ``rows`` keeps a batch's first rows, the fault ``calibrate`` plants
        as half a batch; at one row a step half a batch is no row, so
        ``rows=0`` reads as **the row's second half of positions left out
        of the loss and of its mean**: the same fault at this batch, half
        the step's tokens missing (a reference that goes a row at a time
        can leave positions out; the whole-batch one cannot)."""
        import jax
        import jax.numpy as jnp
        ref = self._ref()
        positions = self.window // 2 if rows == 0 else None
        take = self._row_steps if self.ref_by_rows else self._batch_steps
        step = take(ref, precision, positions)
        params = jax.jit(lambda key: ref.init_params(key, self.cfg),
                         out_shardings=self.replicated)(
                             common.seed_key(self.seed))
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        mu, nu, count = zeros(params), zeros(params), jnp.zeros((), jnp.int32)
        out = {"losses": []}
        for n, keys in enumerate(key_batches):
            starts = np.asarray(keys["ts"])[:rows or None, 0]
            params, mu, nu, count, loss, norms = step(
                params, mu, nu, count, self.stored_batch(starts), n == 0)
            out["losses"].append(loss)
            if n == 0:
                out["grad_norms"] = norms
        out["delta_norms"] = self._delta_norms(params)
        return out

    def _batch_steps(self, ref, precision, positions):
        """A step as one program over the whole batch (``ref.train_step``),
        the first gradient's leaf norms taken inside it."""
        import jax
        import jax.numpy as jnp
        if positions is not None:
            raise ValueError("the whole-batch reference counts every "
                             "position of a row")
        opt = self.cfg["optimizer"]

        def one(params, mu, nu, count, tokens):
            params, mu, nu, count, loss, grads = ref.train_step(
                params, mu, nu, count, tokens, self.cfg, precision=precision,
                learning_rate=opt["learning_rate"],
                weight_decay=opt["weight_decay"])
            norms = [jnp.sqrt(jnp.sum(jnp.square(g)))
                     for g in jax.tree.leaves(grads)]
            return params, mu, nu, count, loss, norms

        jitted = jax.jit(one, donate_argnums=(0, 1, 2))

        def step(params, mu, nu, count, tokens, first):
            names = common.leaf_names(params)
            params, mu, nu, count, loss, norms = jitted(
                params, mu, nu, count, jax.device_put(tokens, self.rows))
            return (params, mu, nu, count, float(loss),
                    dict(zip(names, map(float, norms))) if first else None)
        return step

    def _row_steps(self, ref, precision, positions):
        """A step as a call a row, each adding its gradient into one sum
        (``row_grads``), then AdamW (``ref.adamw``) as a call of its own:
        a whole step as one program holds more than one gradient and does
        not fit beside float32 weights and the optimizer's state."""
        import jax
        opt = self.cfg["optimizer"]
        add_row = self.row_grads(ref, precision, positions)
        update = jax.jit(
            lambda params, mu, nu, count, grads: ref.adamw(
                params, mu, nu, count, grads,
                learning_rate=opt["learning_rate"],
                weight_decay=opt["weight_decay"]),
            donate_argnums=(0, 1, 2, 4))

        def step(params, mu, nu, count, tokens, first):
            rows = self.reference_rows(tokens)
            scale = 1.0 / (len(rows) * self.counted(ref, positions))
            loss, grads = 0.0, None
            for row in rows:
                value, grads = add_row(params, grads, jax.device_put(
                    row, self.replicated), scale)
                loss += float(value)
            norms = common.leaf_norms(grads) if first else None
            params, mu, nu, count = update(params, mu, nu, count, grads)
            return params, mu, nu, count, loss, norms
        return step

    # ------------------------------------ hooks of a reference row by row
    def row_grads(self, ref, precision, positions):
        """``(params, sum or None, row, scale) -> (scale x the row's summed
        loss, sum + its gradient)``: ``ref.row_grads``, then one donated
        add, so that one gradient is alive when the next is made."""
        import jax
        import jax.numpy as jnp
        grads_of = jax.jit(lambda params, row, scale: ref.row_grads(
            params, row, scale, self.cfg, precision, positions))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=(0, 1))

        def add_row(params, acc, row, scale):
            value, grads = grads_of(params, row, scale)
            return value, grads if acc is None else add(acc, grads)
        return add_row

    def reference_rows(self, tokens: np.ndarray) -> np.ndarray:
        """The reference's rows from the stored tokens."""
        return tokens

    def counted(self, ref, positions) -> int:
        """The targets of one row that the loss counts (its mean's
        divisor)."""
        return ref.counted_positions(self.window, positions)

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
