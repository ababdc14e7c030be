"""Sliding-window attention: the three Pallas kernels under a static
``window`` (interpret mode) and the dense fallback, against plain masked
attention written out here."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.ops import flash_attn
from petastorm_tpu.ops.flash_attn import flash_attention
from petastorm_tpu.parallel.attention import dense_attention


def masked_scores(q, k, causal, window):
    """Scaled scores (b, h, sq, sk), heads repeated, float32, -inf outside
    ``j <= i`` (``causal``) and ``i - j < window``."""
    k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    behind = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None]
    keep = jnp.ones_like(behind, bool)
    if causal:
        keep &= behind >= 0
    if window is not None:
        keep &= behind < window
    return jnp.where(keep, scores, -jnp.inf)


def masked_attention(q, k, v, window):
    """Softmax attention under ``j <= i`` and ``i - j < window``."""
    probs = jax.nn.softmax(masked_scores(q, k, True, window), axis=-1)
    v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def inputs(seq, heads=4, kv_heads=2, dim=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (2, seq, h, dim)  # noqa: E731
    return (jax.random.normal(keys[0], shape(heads)),
            jax.random.normal(keys[1], shape(kv_heads)),
            jax.random.normal(keys[2], shape(kv_heads)),
            jax.random.normal(keys[3], shape(heads)))


# (seq, window, block_q, block_k): shorter than, equal to and longer than
# the sequence; not a multiple of either block; one key; blocks either way.
CASES = [(128, 40, 32, 64), (128, 128, 32, 64), (128, 200, 32, 64),
         (128, 1, 32, 64), (256, 50, 16, 64), (256, 100, 64, 32),
         (256, 33, 32, 32), (256, 64, 32, 128), (256, 1000, 32, 64)]


@pytest.mark.parametrize("seq,window,block_q,block_k", CASES)
def test_windowed_kernels_match_masked_attention(seq, window, block_q,
                                                 block_k):
    q, k, v, g = inputs(seq, seed=seq + window)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_k=block_k, window=window)

    want, pull = jax.vjp(lambda q, k, v: masked_attention(q, k, v, window),
                         q, k, v)
    got, pull_kernel = jax.vjp(kernel, q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for mine, theirs in zip(pull_kernel(g), pull(g)):
        np.testing.assert_allclose(mine, theirs, atol=2e-5)


@pytest.mark.parametrize("window", [1, 7, 64, 100, 1000])
def test_dense_fallback_takes_the_same_window(window):
    q, k, v, _ = inputs(64, seed=window)
    np.testing.assert_allclose(
        dense_attention(q, k, v, causal=True, window=window),
        masked_attention(q, k, v, window), atol=2e-6)
    # A shape the tiles cannot divide takes the dense route, window and all.
    q, k, v, _ = inputs(100, seed=window)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, window=window),
        masked_attention(q, k, v, window), atol=2e-6)


def test_a_window_needs_the_causal_mask():
    q, k, v, _ = inputs(64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError):
        dense_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True, window=0)


# (heads, kv_heads, causal, window): every group width the cells run (the
# dense decoder's 4, the sparse one's 7, one head a group), with no mask,
# the causal one, a band, and a band whose last key tiles' queries run past
# the sequence's end.
GROUPS = [(2, 2, False, None), (4, 1, False, None), (2, 2, True, None),
          (4, 1, True, None), (7, 1, True, None), (4, 1, True, 40),
          (7, 1, True, 100), (2, 2, True, 250), (7, 1, True, 1000)]


@pytest.mark.parametrize("heads,kv_heads,causal,window", GROUPS)
def test_kernels_match_dense_attention_for_every_group_width(
        heads, kv_heads, causal, window):
    """o, lse, dq, dk, dv of the scheduled kernels against the dense route,
    tiles of 32 x 64 over 256 positions."""
    q, k, v, g = inputs(256, heads=heads, kv_heads=kv_heads, seed=heads)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=32,
                               block_k=64, window=window)

    want, pull = jax.vjp(lambda q, k, v: dense_attention(
        q, k, v, causal=causal, window=window), q, k, v)
    got, pull_kernel = jax.vjp(kernel, q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for mine, theirs in zip(pull_kernel(g), pull(g)):
        np.testing.assert_allclose(mine, theirs, atol=2e-5)
    # lse: the rows' logsumexp of the masked scores.
    _, lse = flash_attn._flash_forward_lse(q, k, v, causal, 32, 64, True,
                                           window)
    np.testing.assert_allclose(
        lse[..., 0],
        jax.nn.logsumexp(masked_scores(q, k, causal, window), axis=-1),
        atol=2e-5)


def test_windowed_calls_carry_names_of_their_own():
    """``chipbench/trace_reduce.kernel_seconds`` matches by substring: the
    windowed calls must not read as the causal ones."""
    q, k, v, _ = inputs(128)

    def names(window):
        text = jax.jit(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=32, block_k=64, window=window,
            interpret=True).sum(), (0, 1, 2))).lower(q, k, v).as_text(
                debug_info=True)
        return set(re.findall(r"\b(?:flash|swa)_(?:fwd|bwd\w*)\b", text))

    assert names(None) == {"flash_fwd", "flash_bwd"}
    assert names(32) == {"swa_fwd", "swa_bwd"}
