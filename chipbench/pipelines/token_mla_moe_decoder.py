"""``token_moe_decoder``'s path (token store -> ``make_reader`` +
``NGram(dense=True)`` -> ``DataLoader`` -> the donated AdamW step of
``llama.make_train_step``, the expert layers' step statistics published
after the window) for a decoder with latent attention, a leading dense
layer and sigmoid-routed experts beside shared ones, of which one
expert-parallel rank holds a run; and the float32 reference of that share
(``reference/kanana2.py``). Overrides what differs: the ``LlamaConfig``,
the one attention callable under ``shard_map``, the operations of a step,
the kernels' names, where AdamW's first moment sits and the reference."""
from __future__ import annotations

import numpy as np

from chipbench import flops_mla
from chipbench.pipelines import common, token_moe_decoder
from chipbench.pipelines.token_decoder import ADAM_B1


def llama_config(c: dict):
    """The program's static configuration from the file's keys."""
    from petastorm_tpu.models import llama
    if (c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
            or c["q_lora_rank"] is not None or c["rope_scaling"] is not None
            or c["n_group"] != 1 or c["topk_group"] != 1
            or c["moe_layer_freq"] != 1 or c["scoring_func"] != "sigmoid"
            or not c["norm_topk_prob"] or c["hidden_act"] != "silu"):
        raise ValueError("the configuration asks for what this pipeline "
                         "does not build")
    return llama.LlamaConfig(
        vocab=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        hidden=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], attention="mla",
        kv_lora_rank=c["kv_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        rope_interleave=c["rope_interleave"],
        n_dense_layers=c["first_k_dense_replace"],
        n_router_outputs=c["moe_router_outputs"],
        top_k=c["num_experts_per_tok"],
        experts_held=(c["moe_experts_held_first"], c["n_routed_experts"]),
        expert_hidden=c["moe_intermediate_size"], expert_act="silu",
        n_shared_experts=c["n_shared_experts"], router_score="sigmoid",
        router_scale=c["routed_scaling_factor"],
        router_input="mlp_norm", embed_std=c["embed_init_std"])


class Job(token_moe_decoder.Job):
    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 store_path: str):
        # token_moe_decoder.Job.__init__ less its operation count, which
        # asks for keys this model does not have.
        self.cfg, self.traffic, self.devices = config, traffic, devices
        self.seed, self.store_path = seed, store_path
        self.window = traffic["window"]
        self.global_batch = traffic["per_chip_batch"] * len(devices)
        self.items_per_step = self.global_batch * self.window
        self.workers = int(traffic["workers"])
        self.n_groups = traffic["store_windows"]
        self.flops_per_step = flops_mla.train_flops(
            config, self.global_batch, self.window)
        # The flash kernels at a key width of 192 and a value width of 128.
        self.expected_kernels = ("flash",)
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
        attn = jax.shard_map(make_flash_attention(causal=True),
                             mesh=self.mesh, in_specs=(P("data"),) * 3,
                             out_specs=P("data"), check_vma=False)
        attn.supports_gqa = True
        lcfg = llama_config(c)
        init_opt, raw = llama.make_train_step(
            lcfg, learning_rate=c["optimizer"]["learning_rate"], shift="roll",
            attn_fn=attn, xent_chunk=t["xent_chunk"],
            remat_layers=t["remat_layers"], with_stats=True)
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

    # ---------------------------------------------------------- reference
    def grad_leaf_norms(self) -> dict:
        """After step 1 Adam's first moment is (1 - b1) * g. The step holds
        the correction bias outside AdamW (``llama.make_train_step``): the
        weights' moments sit under that transform, and the bias, which no
        gradient reaches and which has no moment, reads 0."""
        mu = self.opt.inner_states["weight"].inner_state[0].mu
        norms = common.leaf_norms(mu)
        return {k: norms.get(k, 0.0) / (1 - ADAM_B1)
                for k in common.leaf_names(self.params)}

    def _delta_norms(self, params) -> dict:
        """Leaf norms of params - the seed's init (see
        ``token_decoder.Job._delta_norms``)."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import kanana2 as ref

        def norms(p, key):
            init = ref.init_params(key, self.cfg)
            return [jnp.sqrt(jnp.sum(jnp.square(a - b))) for a, b in
                    zip(jax.tree.leaves(p), jax.tree.leaves(init))]

        return dict(zip(common.leaf_names(params), map(float, jax.jit(norms)(
            params, common.seed_key(self.seed)))))

    def reference(self, key_batches, precision=None, rows=None) -> dict:
        """Follow the batches with ``reference/kanana2.py``; see
        ``token_decoder.Job.reference``. ``rows`` keeps a batch's first
        rows, the fault ``calibrate`` plants as half a batch; at one row a
        step half a batch is no row, so ``rows=0`` reads as **the row's
        second half of positions left out of the loss and of its mean**
        (as ``byte_eva_decoder`` reads it): the same fault at this batch.
        A row's gradient is a call of its own and AdamW another: a whole
        step as one program does not fit the chip."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import kanana2 as ref
        opt = self.cfg["optimizer"]
        positions = self.window // 2 if rows == 0 else None
        row_grads = jax.jit(lambda params, row, scale: ref.row_grads(
            params, row, scale, self.cfg, precision, positions))
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
            tokens = self.stored_batch(starts)
            scale = 1.0 / (len(tokens) * ref.counted_positions(
                self.window, positions))
            loss, grads = 0.0, None
            for row in tokens:
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
