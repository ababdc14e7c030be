"""Shared plain softmax attention — the single-device kernel used by the
Llama model (no SP) and as the per-head-shard local step of Ulysses
sequence parallelism. One copy so numerics tweaks (score dtype, mask
handling) never diverge between consumers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dense_attention(q, k, v, causal: bool = False, window=None):
    """Softmax attention on full tensors; q is (b, seq, heads, dim) and
    k/v are (b, seq, kv_heads, dim) (v may have a width of its own, which
    is then the output's) with ``heads % kv_heads == 0`` —
    grouped-query attention runs natively (each K/V head serves
    ``heads/kv_heads`` query heads via einsum broadcasting, no repeat).

    Scores accumulate in float32 regardless of input dtype; the causal mask
    is position-based so it also holds for lq != lk. ``window`` (with
    ``causal``) keeps of each query's keys its own and the ``window - 1``
    before it: sliding-window attention.

    q and k may be pairs ``(position-free part, rotary part)``, the key's
    rotary part one head for every query head (latent attention's split
    form, as :func:`petastorm_tpu.ops.flash_attn.flash_attention` takes
    it): the parts are joined here, the rotary key repeated over the key
    heads."""
    if isinstance(q, tuple):
        (q_nope, q_rope), (k_nope, k_rope) = q, k
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, k_nope.shape[:3] + k_rope.shape[3:])], axis=-1)
    b, lq, h, d = q.shape
    kv_h = k.shape[2]
    if window is not None and not causal:
        raise ValueError("window needs causal=True")
    if h == kv_h:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    else:
        if h % kv_h:
            raise ValueError(f"heads ({h}) must be a multiple of kv_heads ({kv_h})")
        qg = q.reshape(b, lq, kv_h, h // kv_h, d)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
        scores = scores.reshape(b, h, lq, k.shape[1])
    scores = scores / jnp.sqrt(jnp.float32(d))
    if causal:
        lk = k.shape[1]
        behind = jnp.arange(lq)[:, None] - jnp.arange(lk)[None, :]
        mask = behind >= 0 if window is None else (
            (behind >= 0) & (behind < window))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    if h == kv_h:
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)
    wg = w.reshape(b, kv_h, h // kv_h, lq, k.shape[1])
    return jnp.einsum("bgrqk,bkgd->bqgrd", wg, v).reshape(
        b, lq, h, v.shape[-1])
