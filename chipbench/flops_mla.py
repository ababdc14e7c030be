"""Operations and bytes of the latent-attention, shared-and-routed-expert
decoder (``configs/kanana2-30b-a3b-ep8-d6.json``) and of its attention
kernels' calls, from shapes alone, in ``flops.py``'s terms: multiply-adds
count as two operations, only matrix products are counted, nothing
recomputed under ``remat`` is, and the routed experts are counted at their
expectation (``flops_moe.py``'s rule). Every share of a peak built on these
is an undercount.

Two counts of the attention's backward live here and differ on purpose.
``train_flops`` keeps the harness's rule for a step, ``3 x`` the forward
(as ``flops_moe.train_flops``), so ``step_mfu_pct.tokens`` reads as in the
other decoder cells. ``mla_call`` counts what the mathematics of one call
needs: where scores are wider than values the backward is ``dv`` and ``dp``
over the value width and ``s``, ``dq``, ``dk`` over the key width, 2.6
forwards at 192 | 128, not 2.5.
"""
from __future__ import annotations


def pairs(seq: int) -> int:
    """(query, key) pairs under the causal mask."""
    return seq * (seq + 1) // 2


def attention_matrix_params(sizes: dict) -> int:
    """Wq, Wkv_a, Wkv_b, Wo of one layer."""
    d, nh = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rot = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, vd = sizes["kv_lora_rank"], sizes["v_head_dim"]
    return (d * nh * (nope + rot) + d * (rank + rot)
            + rank * nh * (nope + vd) + nh * vd * d)


def ffn_matrix_params(sizes: dict, layer_idx: int) -> float:
    """Matrix parameters one token meets in layer ``layer_idx``'s FFN: a
    leading layer's dense gate, up and down; else the router at its full
    width, the shared experts, and gate, up and down of the expected
    number of held experts it is routed to."""
    d = sizes["hidden_size"]
    if layer_idx < sizes["first_k_dense_replace"]:
        return 3 * d * sizes["intermediate_size"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    held_per_token = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
                      / sizes["moe_router_outputs"])
    return (d * sizes["moe_router_outputs"]
            + sizes["n_shared_experts"] * expert + held_per_token * expert)


def attention_forward_flops(sizes: dict, batch: int, seq: int) -> int:
    """One layer's attention, forward: scores over the key width
    (position-free + rotary), ``P v`` over the value width."""
    width = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
             + sizes["v_head_dim"])
    return 2 * batch * sizes["num_attention_heads"] * width * pairs(seq)


def train_flops(sizes: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 per matrix parameter per token
    (embedding lookups cost none, the untied head over the share's slice
    counts), and each layer's attention with its backward at twice its
    forward (the harness's rule for a step: see the module's note)."""
    layers = sizes["num_hidden_layers"]
    matmul = layers * attention_matrix_params(sizes) \
        + sum(ffn_matrix_params(sizes, li) for li in range(layers)) \
        + sizes["hidden_size"] * sizes["vocab_size"]
    return (6 * matmul * batch * seq
            + 3 * layers * attention_forward_flops(sizes, batch, seq))


def mla_call(kernel: str, batch: int, heads: int, seq: int, nope: int,
             rot: int, v_dim: int, itemsize: int = 2) -> dict:
    """Operations and HBM bytes of one layer's attention call, whatever
    implements it. ``fwd``: scores over ``nope + rot`` and ``P v`` over
    ``v_dim``; reads q, each head's position-free key, the ONE rotary key,
    v, writes o and the float32 row statistics. ``bwd`` (the dQ and the
    dK/dV kernel together): ``s`` again, ``dq`` and ``dk`` over ``nope +
    rot``, ``dp`` and ``dv`` over ``v_dim`` (the second recomputation of
    ``s`` and ``dp`` in the two-kernel split is not counted); reads q, the
    keys, v, o, do and the statistics, writes dq, the keys' gradients and
    dv. A rotary key repeated for every head in HBM, or a padded operand,
    is the kernel's cost, not the call's."""
    dk = nope + rot
    unit = 2 * batch * heads * pairs(seq)
    q = batch * seq * heads * dk * itemsize
    keys = batch * seq * (heads * nope + rot) * itemsize
    v = batch * seq * heads * v_dim * itemsize
    stats = batch * heads * seq * 4
    if kernel == "fwd":
        return {"flops": unit * (dk + v_dim),
                "bytes": q + keys + 2 * v + stats}
    if kernel == "bwd":
        return {"flops": unit * (3 * dk + 2 * v_dim),
                "bytes": 2 * q + 2 * keys + 4 * v + 2 * stats}
    raise ValueError(f"unknown kernel {kernel!r}")
