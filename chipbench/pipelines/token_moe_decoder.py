"""``token_decoder``'s path (token store -> ``make_reader`` +
``NGram(dense=True)`` -> ``DataLoader`` -> the donated AdamW step of
``llama.make_train_step``) for a decoder whose layers mix full and
sliding-window attention and whose FFN is the dropless top-k expert layer
over the experts one tensor-parallel share holds; and the float32 reference
of that share (``reference/smallthinker.py``). Overrides what differs: the
``LlamaConfig``, the two attention callables under ``shard_map``, the
operations of a step, the six kernels' names, the reference and the
expert layers' step statistics."""
from __future__ import annotations

import numpy as np

from chipbench import flops_moe
from chipbench.pipelines import common, token_decoder


def llama_config(c: dict):
    """The program's static configuration from the file's keys."""
    from petastorm_tpu.models import llama
    layers = c["num_hidden_layers"]
    return llama.LlamaConfig(
        vocab=c["vocab_size"], dim=c["hidden_size"], n_layers=layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        rope_layout=tuple(c["rope_layout"][:layers]),
        sliding_window_layout=tuple(c["sliding_window_layout"][:layers]),
        sliding_window=c["sliding_window_size"],
        n_router_outputs=c["moe_router_outputs"],
        top_k=c["moe_num_active_primary_experts"],
        experts_held=(c["moe_experts_held_first"],
                      c["moe_num_primary_experts"]),
        expert_hidden=c["moe_ffn_hidden_size"], expert_act="relu",
        router_input="layer_input", embed_std=c["embed_init_std"])


class Job(token_decoder.Job):
    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 store_path: str):
        # token_decoder.Job.__init__ less its dense FLOP count, which asks
        # for an intermediate_size that this model does not have.
        self.cfg, self.traffic, self.devices = config, traffic, devices
        self.seed, self.store_path = seed, store_path
        self.window = traffic["window"]
        self.global_batch = traffic["per_chip_batch"] * len(devices)
        self.items_per_step = self.global_batch * self.window
        self.workers = int(traffic["workers"])
        self.n_groups = traffic["store_windows"]
        self.flops_per_step = flops_moe.train_flops(
            config, self.global_batch, self.window)
        self.expected_kernels = ("flash", "swa")
        self.moe_stats = []     # one small device tree per dispatched step

    # ------------------------------------------------------------ program
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

        def sharded(attn):
            fn = jax.shard_map(attn, mesh=self.mesh,
                               in_specs=(P("data"),) * 3,
                               out_specs=P("data"), check_vma=False)
            fn.supports_gqa = True
            return fn

        lcfg = llama_config(c)
        init_opt, raw = llama.make_train_step(
            lcfg, learning_rate=c["optimizer"]["learning_rate"], shift="roll",
            attn_fn=sharded(make_flash_attention(causal=True)),
            window_attn_fn=sharded(make_flash_attention(
                causal=True, window=lcfg.sliding_window)),
            xent_chunk=t["xent_chunk"], remat_layers=t["remat_layers"],
            with_stats=True)
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

    def step(self, batch):
        self.params, self.opt, loss, stats = self._step(
            self.params, self.opt, batch["token"])
        self.moe_stats.append(stats)    # read after the window, in free()
        return loss

    def free(self) -> None:
        """Also: the steps' statistics come to the host, and the measured
        window's (the last steps dispatched) go to the loader's registry
        as ``model.moe.*`` counters."""
        import jax
        self.moe_stats = [jax.tree.map(np.asarray, s) for s in self.moe_stats]
        self.telemetry = self._loader.telemetry
        super().free()

    def publish_moe_stats(self, last_steps: int) -> dict:
        """Publish the last ``last_steps`` steps' statistics as the
        program's counters and return the counters' values."""
        from petastorm_tpu.models import llama
        for stats in self.moe_stats[-last_steps:]:
            llama.publish_moe_stats(self.telemetry, stats)
        return {name: self.telemetry.peek_counter(f"model.moe.{name}")
                for name in llama.MOE_STATS}

    # ---------------------------------------------------------- reference
    def _delta_norms(self, params) -> dict:
        """Leaf norms of params - the seed's init (see
        ``token_decoder.Job._delta_norms``)."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import smallthinker as ref

        def norms(p, key):
            init = ref.init_params(key, self.cfg)
            return [jnp.sqrt(jnp.sum(jnp.square(a - b))) for a, b in
                    zip(jax.tree.leaves(p), jax.tree.leaves(init))]

        return dict(zip(common.leaf_names(params), map(float, jax.jit(norms)(
            params, common.seed_key(self.seed)))))

    def reference(self, key_batches, precision=None, rows=None) -> dict:
        """Follow the batches with ``reference/smallthinker.py``; see
        ``token_decoder.Job.reference``. A row's gradient is added into a
        donated sum, one call a row: a whole step as one program holds
        three gradients and does not fit the chip."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import smallthinker as ref
        opt = self.cfg["optimizer"]
        add_row = jax.jit(
            lambda params, acc, row, scale: ref.add_row_grads(
                params, acc, row, scale, self.cfg, precision),
            donate_argnums=(1,))
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
            starts = np.asarray(keys["ts"])[:rows, 0]
            tokens = self.stored_batch(starts)
            scale = 1.0 / (tokens.shape[0] * (tokens.shape[1] - 1))
            loss, grads = 0.0, zeros(params)
            for row in tokens:
                value, grads = add_row(params, grads, jax.device_put(
                    row, self.replicated), scale)
                loss += float(value)
            out["losses"].append(loss)
            if n == 0:
                out["grad_norms"] = common.leaf_norms(grads)
            params, mu, nu, count = update(params, mu, nu, count, grads)
        out["delta_norms"] = self._delta_norms(params)
        return out
