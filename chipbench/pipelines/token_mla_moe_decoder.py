"""``token_moe_decoder``'s path (token store -> ``make_reader`` +
``NGram(dense=True)`` -> ``DataLoader`` -> the donated AdamW step of
``llama.make_train_step``, the expert layers' step statistics published
after the window) for a decoder with latent attention, a leading dense
layer and sigmoid-routed experts beside shared ones, of which one
expert-parallel rank holds a run; and the float32 reference of that share
(``reference/kanana2.py``). Fills in what differs from the expert
decoder: the ``LlamaConfig``, the one attention callable, the operations
of a step, the kernel family, where AdamW's first moment sits and the
reference."""
from __future__ import annotations

from chipbench import flops_mla
from chipbench.pipelines import token_decoder, token_moe_decoder


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
    llama_config = staticmethod(llama_config)
    train_flops = staticmethod(flops_mla.train_flops)
    # The flash kernels at a key width of 192 and a value width of 128.
    expected_kernels = ("flash",)
    ref = "kanana2"
    row_grads = token_decoder.Job.row_grads
    counted = token_decoder.Job.counted

    def first_moment(self):
        """The step holds the correction bias outside AdamW
        (``llama.make_train_step``): the weights' moments sit under that
        transform, and the bias has none."""
        return self.opt.inner_states["weight"].inner_state[0].mu
