"""``token_decoder``'s path (store -> ``make_reader`` + ``NGram(dense=True)``
-> ``DataLoader`` -> the donated AdamW step of ``llama.make_train_step``)
for a byte-level decoder with EVA attention and several prediction heads:
the store's ``token`` column is **uint8** (a token is one byte on disk, in
the row group, on the wire and in HBM; the id offset and the widening
happen inside the jitted step), the attention is the three ``eva_*`` Pallas
kernels under ``shard_map``, and the float32 reference is
``reference/evabyte.py``. Overrides what differs: the store, the
``LlamaConfig``, the attention callable, the operations of a step, the
kernels' names and the reference."""
from __future__ import annotations

import numpy as np

from chipbench import flops_eva, stores
from chipbench.pipelines import common, token_decoder


def byte_schema():
    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    return Unischema("ChipbenchBytes", [
        UnischemaField("ts", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("token", np.uint8, (), ScalarCodec(np.uint8), False),
    ])


def llama_config(c: dict):
    """The program's static configuration from the file's keys."""
    from petastorm_tpu.models import llama
    return llama.LlamaConfig(
        vocab=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        hidden=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], attention="eva",
        eva_window=c["window_size"], eva_chunk=c["chunk_size"],
        norm_unit_offset=c["norm_add_unit_offset"],
        n_pred_heads=c["num_pred_heads"], fp32_skip_add=c["fp32_skip_add"])


class Job(token_decoder.Job):
    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 store_path: str):
        super().__init__(config, traffic, devices, seed, store_path)
        self.flops_per_step = flops_eva.train_flops(
            config, self.global_batch, self.window)
        self.expected_kernels = ("eva",)

    # ------------------------------------------------------------ program
    def write_store(self) -> None:
        """Timestamped bytes uniform over 0..255, one window a row group:
        ``stores.write_token_store`` with a uint8 column."""
        import pyarrow as pa
        stores._fresh_dir(self.store_path)
        rng = np.random.default_rng(self.seed)
        n = self.n_groups * self.window
        schema = byte_schema()
        table = pa.Table.from_pydict(
            {"ts": pa.array(np.arange(n, dtype=np.int64)),
             "token": pa.array(rng.integers(0, 256, n, dtype=np.uint8))},
            schema=schema.as_arrow_schema())
        stores._write_parts(self.store_path, table, self.window, schema)

    def start(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from petastorm_tpu.jax import DataLoader
        from petastorm_tpu.models import llama
        from petastorm_tpu.ngram import NGram
        from petastorm_tpu.ops.eva_attn import make_eva_attention
        from petastorm_tpu.reader import make_reader

        c, t = self.cfg, self.traffic
        self.mesh, self.rows, self.replicated = common.mesh_and_shardings(
            self.devices)
        self.lcfg = lcfg = llama_config(c)
        attn = jax.shard_map(
            make_eva_attention(lcfg.eva_window, lcfg.eva_chunk),
            mesh=self.mesh, in_specs=(P("data"),) * 3 + (P(), P()),
            out_specs=P("data"), check_vma=False)
        init_opt, raw = llama.make_train_step(
            lcfg, learning_rate=c["optimizer"]["learning_rate"], shift="roll",
            eva_attn_fn=attn, xent_chunk=t["xent_chunk"],
            remat_layers=t["remat_layers"])
        self.params, self.opt = jax.jit(
            lambda key: (lambda p: (p, init_opt(p)))(
                llama.init_params(key, lcfg)),
            out_shardings=self.replicated)(common.seed_key(self.seed))
        offset = c["token_id_offset"]
        # The staged bytes are widened, and become ids, on the device.
        self._jitted = jax.jit(
            lambda params, opt, tokens: raw(params, opt, {
                "tokens": tokens.astype(jnp.int32) + offset}),
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
        batch = super().next_batch()
        if batch["token"].dtype != np.uint8:
            raise ValueError(f"staged bytes are {batch['token'].dtype}")
        return batch

    def free(self) -> None:
        self.telemetry = self._loader.telemetry
        super().free()

    # ---------------------------------------------------------- reference
    def _delta_norms(self, params) -> dict:
        """Leaf norms of params - the seed's init (see
        ``token_decoder.Job._delta_norms``)."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import evabyte as ref

        def norms(p, key):
            init = ref.init_params(key, self.cfg)
            return [jnp.sqrt(jnp.sum(jnp.square(a - b))) for a, b in
                    zip(jax.tree.leaves(p), jax.tree.leaves(init))]

        return dict(zip(common.leaf_names(params), map(float, jax.jit(norms)(
            params, common.seed_key(self.seed)))))

    def reference(self, key_batches, precision=None, rows=None) -> dict:
        """Follow the batches with ``reference/evabyte.py``; see
        ``token_decoder.Job.reference``. ``rows`` keeps a batch's first
        rows, the fault ``calibrate`` plants as half a batch; at one row a
        step half a batch is no row, so ``rows=0`` reads as **the row's
        second half of positions left out of the loss and of its mean**:
        the same fault at this batch, half the step's tokens missing. A
        row's gradient is a call of its own and AdamW another: a whole
        step as one program does not fit the chip."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import evabyte as ref
        opt, heads = self.cfg["optimizer"], self.cfg["num_pred_heads"]
        positions = self.window // 2 if rows == 0 else None
        row_grads = jax.jit(lambda params, ids, scale: ref.row_grads(
            params, ids, scale, self.cfg, precision, positions))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=(0, 1))
        update = jax.jit(
            lambda params, mu, nu, count, grads: ref.adamw(
                params, mu, nu, count, grads,
                learning_rate=opt["learning_rate"],
                weight_decay=opt["weight_decay"]),
            donate_argnums=(0, 1, 2, 4))
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        params = jax.jit(lambda key: ref.init_params(key, self.cfg),
                         out_shardings=self.replicated)(
                             common.seed_key(self.seed))
        mu, nu, count = zeros(params), zeros(params), jnp.zeros((), jnp.int32)
        out = {"losses": []}
        for n, keys in enumerate(key_batches):
            starts = np.asarray(keys["ts"])[:rows or None, 0]
            ids = self.stored_batch(starts).astype(np.int32) \
                + self.cfg["token_id_offset"]
            scale = 1.0 / (len(ids) * ref.counted_pairs(
                self.window, heads, positions))
            loss, grads = 0.0, None
            for row in ids:
                value, row_grad = row_grads(
                    params, jax.device_put(row, self.replicated), scale)
                loss += float(value)
                grads = row_grad if grads is None else add(grads, row_grad)
                del row_grad    # one gradient alive when the next is made
            out["losses"].append(loss)
            if n == 0:
                out["grad_norms"] = common.leaf_norms(grads)
            params, mu, nu, count = update(params, mu, nu, count, grads)
        out["delta_norms"] = self._delta_norms(params)
        return out
