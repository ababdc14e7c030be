"""Operations and bytes that the mathematics of a step or a kernel call
needs, from shapes alone. Multiply-adds count as two operations; only
convolutions and matrix products are counted (norms, activations and the
optimizer are left out), and nothing recomputed under ``remat`` is: every
share of a peak built on these is an undercount, never an overcount.
"""
from __future__ import annotations

import json
import os

RESNET50_STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}: add "
                       f"it to chipbench/peaks.json with its source")
    return table[device_kind]


def conv_flops(out_hw: int, kernel: int, cin: int, cout: int) -> int:
    """Forward operations of one square convolution on one image."""
    return 2 * out_hw * out_hw * kernel * kernel * cin * cout


def bottleneck_forward_flops(in_hw: int, cin: int, mid: int, stride: int,
                             project: bool) -> int:
    """1x1 -> 3x3 (carries the stride) -> 1x1, plus the 1x1 projection on a
    stage's first block; one image."""
    out_hw = in_hw // stride
    total = (conv_flops(in_hw, 1, cin, mid) + conv_flops(out_hw, 3, mid, mid)
             + conv_flops(out_hw, 1, mid, 4 * mid))
    if project:
        total += conv_flops(out_hw, 1, cin, 4 * mid)
    return total


def resnet50_train_flops(image_size: int, classes: int) -> int:
    """Forward and backward of one image. Backward is twice the forward
    (one product for the input's gradient, one for the weights'), except in
    the stem, whose input is the image and needs no gradient."""
    stem = conv_flops(image_size // 2, 7, 3, 64)
    body, hw, cin = 0, image_size // 4, 64
    for stage, (blocks, mid) in enumerate(RESNET50_STAGES):
        for block in range(blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            body += bottleneck_forward_flops(hw, cin, mid, stride, block == 0)
            hw, cin = hw // stride, 4 * mid
    body += 2 * cin * classes
    return 2 * stem + 3 * body


def decoder_layer_params(sizes: dict) -> int:
    """Matrix parameters of one decoder layer (q, k, v, o, gate, up, down)."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return (2 * d * nh * hd + 2 * d * nkv * hd
            + 3 * d * sizes["intermediate_size"])


def causal_attention_forward_flops(batch: int, heads: int, seq: int,
                                   head_dim: int) -> int:
    """QK^T and PV over the lower triangle: half of 2 x 2 x s^2 x d."""
    return 2 * batch * heads * seq * seq * head_dim


def decoder_train_flops(sizes: dict, batch: int, seq: int) -> int:
    """Forward and backward of one step: 6 per matrix parameter per token
    (embedding lookups cost none, the untied head counts), and attention's
    backward at twice its forward (dV, dP, dQ, dK)."""
    layers = sizes["num_hidden_layers"]
    matmul = layers * decoder_layer_params(sizes) \
        + sizes["hidden_size"] * sizes["vocab_size"]
    attn = causal_attention_forward_flops(
        batch, sizes["num_attention_heads"], seq, sizes["head_dim"])
    return 6 * matmul * batch * seq + 3 * layers * attn


def flash_call(kernel: str, batch: int, heads: int, kv_heads: int, seq: int,
               head_dim: int, itemsize: int = 2) -> dict:
    """Operations and HBM bytes of one call of a causal flash kernel.
    ``fwd``: two products, reads q, k, v, writes o and the float32 row
    statistics. ``bwd`` is the backward pass as a whole (``flash_bwd_dq``
    and ``flash_bwd_dkv`` together): five products (S again, dP, dV, dQ,
    dK; the second recomputation of S and dP in the two-kernel split is not
    counted), reads q, k, v, o, do and the statistics, writes dq, dk, dv."""
    unit = causal_attention_forward_flops(batch, heads, seq, head_dim) // 2
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    stats = batch * heads * seq * 4
    if kernel == "fwd":
        return {"flops": 2 * unit, "bytes": 2 * q + 2 * kv + stats}
    if kernel == "bwd":
        return {"flops": 5 * unit, "bytes": 4 * q + 4 * kv + 2 * stats}
    raise ValueError(f"unknown flash kernel {kernel!r}")


def least_seconds(call: dict, peak: dict) -> tuple:
    """The least time the chip could take for ``call`` and which of the two
    peaks bounds it: ``(seconds, "compute" | "memory")``."""
    compute = call["flops"] / peak["bf16_flops_per_s"]
    memory = call["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
