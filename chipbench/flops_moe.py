"""Operations and bytes of the sparse-expert, window/full-attention decoder
(``configs/smallthinker21b-tp4-d4.json``) and of its windowed flash kernels,
from shapes alone, in ``flops.py``'s terms: multiply-adds count as two
operations, only matrix products are counted, nothing recomputed under
``remat`` is, and the experts are counted at their expectation (of a
token's ``top_k`` assignments the share ``held / router outputs`` lands on
experts held here), not over the rows of the buffer the products are
issued over: every share of a peak built on these is an undercount.
"""
from __future__ import annotations


def band_pairs(seq: int, window=None) -> int:
    """(query, key) pairs under the mask ``j <= i`` and, with a window,
    ``i - j < window``: the causal triangle ``s (s + 1) / 2``, or the band
    ``w (w + 1) / 2 + (s - w) w`` where the window is shorter than the
    sequence."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_matrix_params(sizes: dict) -> float:
    """Matrix parameters one token meets in one layer of the share: q, k,
    v, o, the router at its full width, and gate, up and down of the
    expected number of held experts it is routed to."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    held_per_token = (sizes["moe_num_active_primary_experts"]
                      * sizes["moe_num_primary_experts"]
                      / sizes["moe_router_outputs"])
    return (2 * d * nh * hd + 2 * d * nkv * hd
            + d * sizes["moe_router_outputs"]
            + held_per_token * 3 * d * sizes["moe_ffn_hidden_size"])


def attention_forward_flops(batch: int, heads: int, seq: int, head_dim: int,
                            window=None) -> int:
    """QK^T and PV over the pairs the mask keeps."""
    return 4 * batch * heads * head_dim * band_pairs(seq, window)


def train_flops(sizes: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 per matrix parameter per token
    (embedding lookups cost none, the untied head over the share's slice
    counts), and each layer's attention, over the triangle or the band,
    with its backward at twice its forward."""
    layers = sizes["num_hidden_layers"]
    matmul = layers * layer_matrix_params(sizes) \
        + sizes["hidden_size"] * sizes["vocab_size"]
    attn = sum(attention_forward_flops(
        batch, sizes["num_attention_heads"], seq, sizes["head_dim"],
        sizes["sliding_window_size"] if windowed else None)
        for windowed in sizes["sliding_window_layout"][:layers])
    return 6 * matmul * batch * seq + 3 * attn


def swa_call(kernel: str, batch: int, heads: int, kv_heads: int, seq: int,
             head_dim: int, window: int, itemsize: int = 2) -> dict:
    """Operations and HBM bytes of one call of a sliding-window flash
    kernel, as ``flops.flash_call`` counts the causal ones: ``fwd`` two
    products over the band; ``bwd`` (``swa_bwd_dq`` and ``swa_bwd_dkv``
    together) five. Bytes are each operand once: a key tile read again for
    every query tile that sees it is the kernel's cost, not the call's."""
    unit = 2 * batch * heads * head_dim * band_pairs(seq, window)
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    stats = batch * heads * seq * 4
    if kernel == "fwd":
        return {"flops": 2 * unit, "bytes": 2 * q + 2 * kv + stats}
    if kernel == "bwd":
        return {"flops": 5 * unit, "bytes": 4 * q + 4 * kv + 2 * stats}
    raise ValueError(f"unknown kernel {kernel!r}")
