"""Operations and bytes of the EVA decoder (``configs/evabyte-6.5b-d4.json``)
and of its three attention kernels, from shapes alone, in ``flops.py``'s
terms: multiply-adds count as two operations, only matrix products are
counted, nothing recomputed under ``remat`` is. Every share of a peak built
on these is an undercount.
"""
from __future__ import annotations


def pairs(seq: int, window: int, chunk: int) -> tuple:
    """``(local, summary)`` (query, key) pairs one head scores over one row:
    each of the ``n = seq / window`` blocks scores its causal triangle,
    ``W (W + 1) / 2``, and block ``w`` the ``(W / C) w`` summaries of the
    blocks before it from each of its ``W`` queries: ``W (W / C) n (n - 1)
    / 2`` in all."""
    n = seq // window
    return (n * window * (window + 1) // 2,
            window * (window // chunk) * n * (n - 1) // 2)


def layer_matrix_params(sizes: dict) -> int:
    """Matrix parameters of one layer: q, k, v, o (one KV head a query
    head), gate, up, down."""
    d = sizes["hidden_size"]
    return (4 * d * sizes["num_attention_heads"] * sizes["head_dim"]
            + 3 * d * sizes["intermediate_size"])


def attention_forward_flops(sizes: dict, batch: int, seq: int) -> int:
    """One layer's attention, forward: QK^T and PV over the local and the
    summary pairs."""
    return 4 * batch * sizes["num_attention_heads"] * sizes["head_dim"] \
        * sum(pairs(seq, sizes["window_size"], sizes["chunk_size"]))


def summary_forward_flops(sizes: dict, batch: int, seq: int) -> int:
    """One layer's chunk summaries, forward: per key the score ``k . phi``
    and the two pooled sums, three products of ``head_dim``."""
    return 2 * 3 * batch * seq * sizes["num_attention_heads"] \
        * sizes["head_dim"]


def train_flops(sizes: dict, batch: int, seq: int) -> int:
    """Forward and backward of one step: 6 per matrix parameter per token
    (embedding lookups cost none; the eight untied heads count), each
    layer's attention with its backward at twice its forward, and the
    summaries' products likewise."""
    layers = sizes["num_hidden_layers"]
    matmul = layers * layer_matrix_params(sizes) + sizes["hidden_size"] \
        * sizes["num_pred_heads"] * sizes["vocab_size"]
    return (6 * matmul * batch * seq
            + 3 * layers * (attention_forward_flops(sizes, batch, seq)
                            + summary_forward_flops(sizes, batch, seq)))


def eva_call(kernel: str, batch: int, heads: int, seq: int, head_dim: int,
             window: int, chunk: int, itemsize: int = 2) -> dict:
    """Operations and HBM bytes of one call, as ``flops.flash_call`` counts
    the causal kernels: ``fwd`` (``eva_fwd``) two products a pair, reads q,
    k, v and the summaries seen (the first ``n - 1`` blocks'), writes o and
    the float32 row statistics; ``bwd`` (``eva_bwd_dq`` and ``eva_bwd_dkv``
    together) five products a pair (S again, dP, dV, dQ, dK; the second
    recomputation of S and dP in the two-kernel split is not counted),
    reads q, k, v, the summaries, o's row sums, do and the statistics,
    writes dq, dk, dv and the summaries' gradients. Bytes are each operand
    and result once."""
    unit = 2 * batch * heads * head_dim * sum(pairs(seq, window, chunk))
    rows = batch * seq * heads * head_dim * itemsize
    summaries = 2 * batch * (seq - window) // chunk * heads * head_dim \
        * itemsize                                  # kbar and vbar
    stats = batch * heads * seq * 4
    if kernel == "fwd":
        return {"flops": 2 * unit, "bytes": 4 * rows + summaries + stats}
    if kernel == "bwd":
        return {"flops": 5 * unit,
                "bytes": 7 * rows + 2 * summaries + 2 * stats}
    raise ValueError(f"unknown kernel {kernel!r}")
