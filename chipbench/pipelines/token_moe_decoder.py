"""``token_decoder``'s path (token store -> ``make_reader`` +
``NGram(dense=True)`` -> ``DataLoader`` -> the donated AdamW step of
``llama.make_train_step``) for a decoder whose layers mix full and
sliding-window attention and whose FFN is the dropless top-k expert layer
over the experts one tensor-parallel share holds; and the float32 reference
of that share (``reference/smallthinker.py``). Fills in ``token_decoder``'s
hooks (the ``LlamaConfig``, whose window brings the windowed kernels, the
operations of a step, the two kernel families, the reference a row at a
time) and keeps the expert layers' step statistics."""
from __future__ import annotations

import numpy as np

from chipbench import flops_moe
from chipbench.pipelines import token_decoder


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
    llama_config = staticmethod(llama_config)
    train_flops = staticmethod(flops_moe.train_flops)
    expected_kernels = ("flash", "swa")
    with_stats = True
    ref = "smallthinker"
    ref_by_rows = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.moe_stats = []     # one small device tree per dispatched step

    # ------------------------------------------------------------ program
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
    def row_grads(self, ref, precision, positions):
        """``ref.add_row_grads``: a row's gradient added into a donated
        sum in the same call. Every position of a row is counted."""
        import jax
        import jax.numpy as jnp
        if positions is not None:
            raise ValueError("this reference counts every position of a row")
        add_row = jax.jit(
            lambda params, acc, row, scale: ref.add_row_grads(
                params, acc, row, scale, self.cfg, precision),
            donate_argnums=(1,))
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        return lambda params, acc, row, scale: add_row(
            params, zeros(params) if acc is None else acc, row, scale)

    def counted(self, ref, positions) -> int:
        return self.window - 1
