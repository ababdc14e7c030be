"""The flash kernels at a key width and a value width of their own
(``ops/flash_attn.py``; latent attention scores over 192 columns and
weighs values of 128): ``o``, ``lse``, ``dq``, ``dk``, ``dv`` against dense
float32 attention on the interpreter, the launched grids against
``grid_steps``, and the tie to the equal-width kernels, bit for bit, at the
cells' tiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.ops import flash_attn
from petastorm_tpu.ops.flash_attn import flash_attention, grid_steps


def dense(q, k, v, window=None):
    """Float32 causal attention, heads grouped by hand -> (o, lse)."""
    rep = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, rep, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    behind = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None, :]
    keep = behind >= 0 if window is None else (behind >= 0) & (behind < window)
    s = jnp.where(keep, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision="highest")
    return o, jax.nn.logsumexp(s, axis=-1)


def operands(seed, b, s, h, kv_h, dk, dv, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (b, s, h, dk), dtype),
            jax.random.normal(keys[1], (b, s, kv_h, dk), dtype),
            jax.random.normal(keys[2], (b, s, kv_h, dv), dtype),
            jax.random.normal(keys[3], (b, s, h, dv), dtype))


# (heads, kv heads, key width, value width, window, block_q, block_k): the
# cell's 192 | 128 in small (24 | 16: one and a half of the value's width),
# values wider than keys, grouped heads, a band.
WIDTHS = [(4, 4, 24, 16, None, 32, 64), (4, 4, 24, 16, None, 64, 32),
          (2, 2, 8, 32, None, 32, 32), (6, 2, 24, 16, None, 32, 64),
          (4, 1, 12, 8, 40, 32, 32), (3, 3, 192, 128, None, 64, 64)]


@pytest.mark.parametrize("h,kv_h,dk,dv,window,block_q,block_k", WIDTHS)
def test_unequal_widths_against_dense_float32(h, kv_h, dk, dv, window,
                                              block_q, block_k):
    q, k, v, do = operands(dk + dv, 2, 128, h, kv_h, dk, dv)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_k=block_k, window=window)

    o, pull = jax.vjp(kernel, q, k, v)
    want, want_pull = jax.vjp(lambda *a: dense(*a, window=window)[0], q, k, v)
    assert o.shape == (2, 128, h, dv)
    np.testing.assert_allclose(o, want, atol=2e-5, rtol=2e-5)
    for got, ref, width in zip(pull(do), want_pull(do), (dk, dk, dv)):
        assert got.shape[-1] == width
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5)
    _, lse = flash_attn._flash_forward_lse(q, k, v, True, block_q, block_k,
                                           True, window)
    np.testing.assert_allclose(lse[..., 0], dense(q, k, v, window)[1],
                               atol=2e-5, rtol=2e-5)


def test_the_scale_is_the_key_widths():
    """Scores over 24 columns are scaled by 1 / sqrt(24), whatever the
    values' 16: a kernel that took the value width would read 22% off."""
    q, k, v, _ = operands(0, 1, 64, 2, 2, 24, 16)
    o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(o, dense(q, k, v)[0], atol=2e-5, rtol=2e-5)
    wrong = dense(q * np.sqrt(24 / 16), k, v)[0]
    assert float(jnp.abs(o - wrong).max()) > 1e-2


def pallas_calls(jaxpr, found=None):
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn.params["grid_mapping"].grid
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                pallas_calls(inner, found)
    return found


@pytest.mark.parametrize("window,prefix", [(None, "flash"), (100, "swa")])
def test_the_launched_grids_are_the_schedules_at_unequal_widths(window,
                                                                prefix):
    q, k, v, _ = operands(1, 2, 256, 4, 2, 24, 16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=64, window=window).sum(),
        (0, 1, 2)))(q, k, v)
    steps = grid_steps(256, 256, 32, 64, True, window, rep=2)
    assert pallas_calls(jaxpr.jaxpr) == {
        f"{prefix}_fwd": (2, 4, steps["fwd"]),
        f"{prefix}_bwd": (2, 2, steps["bwd"])}


# (seq, heads, kv heads, width, window, dtype) at the launch tiles, 1024 x
# 1024: the dense cell's call, the sparse one's full and windowed layers',
# one head a key/value head. bfloat16 as in the cells (a float32 product's
# sums are ordered by the CPU library's blocking, which follows the width).
EQUAL = [(2048, 4, 1, 128, None, jnp.bfloat16),
         (2048, 7, 1, 128, 1500, jnp.bfloat16),
         (2048, 2, 2, 64, None, jnp.bfloat16)]


@pytest.mark.parametrize("seq,h,kv_h,d,window,dtype", EQUAL)
def test_equal_widths_are_the_unequal_kernels_columns_bit_for_bit(
        seq, h, kv_h, d, window, dtype):
    """Values of half the key width give, bit for bit, the first columns
    of the equal-width call on the same values padded with zeros (the
    scores do not see the values; every value column is its own sum): the
    two widths run one program, and the equal-width call is the one the
    dense and sparse cells launch (checked against the parent's kernels
    bit for bit when the widths were split, PERF.md section 6, PR 35)."""
    assert flash_attn._tiles(seq, seq, True, flash_attn._DEFAULT_BLOCK_Q,
                             flash_attn._DEFAULT_BLOCK_K) == (1024, 1024)
    q, k, v, do = operands(seq + h, 1, seq, h, kv_h, d, d // 2, dtype)
    pad = [(0, 0)] * 3 + [(0, d // 2)]

    def run(v, do):
        o, pull = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window), q, k, v)
        return (o, *pull(do))

    o, dq, dk, dv = run(v, do)
    o_eq, dq_eq, dk_eq, dv_eq = run(jnp.pad(v, pad), jnp.pad(do, pad))
    assert o.shape[-1] == d // 2 and o_eq.shape[-1] == d
    np.testing.assert_array_equal(o, o_eq[..., :d // 2])
    np.testing.assert_array_equal(dv, dv_eq[..., :d // 2])
    np.testing.assert_array_equal(dq, dq_eq)
    np.testing.assert_array_equal(dk, dk_eq)
    assert not np.any(np.asarray(o_eq[..., d // 2:], np.float32))


def test_untileable_unequal_widths_take_the_dense_route():
    q, k, v, _ = operands(2, 1, 100, 4, 2, 24, 16)
    o = flash_attention(q, k, v, causal=True)
    assert o.shape == (1, 100, 4, 16)
    np.testing.assert_allclose(o, dense(q, k, v)[0], atol=2e-5, rtol=2e-5)
