"""The attention backward as one kernel a family (``flash_bwd`` /
``swa_bwd``: S, P, dP and dS once a live tile, dQ, dK and dV from them)
against the pair it replaces (``*_bwd_dq`` + ``*_bwd_dkv``), on the Pallas
interpreter: the same gradients bit for bit, the pair taken exactly where
the reckoning says a K/V head's accumulators do not fit VMEM."""
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.ops import flash_attn
from petastorm_tpu.ops.flash_attn import flash_attention, grid_steps
from petastorm_tpu.parallel.attention import dense_attention

MiB = 1 << 20


def operands(b, sq, sk, h, kv_h, d, vd, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (b, sq, h, d), dtype),
            jax.random.normal(keys[1], (b, sk, kv_h, d), dtype),
            jax.random.normal(keys[2], (b, sk, kv_h, vd), dtype),
            jax.random.normal(keys[3], (b, sq, h, vd), dtype))


def backward(q, k, v, do, causal, window, block_q, block_k):
    o, lse = flash_attn._flash_forward_lse(q, k, v, causal, block_q, block_k,
                                           True, window)
    return flash_attn._flash_backward(q, k, v, o, lse, do, causal, block_q,
                                      block_k, True, window)


# (b, sq, sk, heads, kv heads, key width, value width, causal, window, tiles,
# dtype): the cells' kinds of call in small.
CALLS = {
    "causal_rep4": (2, 256, 256, 8, 2, 32, 32, True, None, (64, 64),
                    jnp.bfloat16),
    "window_rep7": (1, 256, 256, 7, 1, 32, 32, True, 96, (32, 64),
                    jnp.bfloat16),
    "window_of_one_tile": (1, 256, 256, 4, 2, 16, 16, True, 64, (64, 64),
                           jnp.bfloat16),
    "latent_widths_rep1": (1, 256, 256, 4, 4, 192, 128, True, None,
                           (128, 128), jnp.bfloat16),
    "non_causal_sq_ne_sk": (1, 128, 256, 4, 1, 32, 32, False, None,
                            (64, 32), jnp.bfloat16),
    "float32": (1, 128, 128, 4, 2, 64, 64, True, None, (32, 32),
                jnp.float32),
    "one_q_tile": (1, 64, 64, 6, 2, 16, 16, True, None, (64, 32),
                   jnp.bfloat16),
}


@pytest.mark.parametrize("call", CALLS)
def test_one_kernel_gives_the_pairs_gradients_bit_for_bit(call, monkeypatch):
    """Every K/V tile meets its (head, q tile) pairs in the dK/dV pass's
    order and every q tile its K/V tiles in the dQ pass's, through the same
    tile math: no sum changes order."""
    b, sq, sk, h, kv_h, d, vd, causal, window, tiles, dtype = CALLS[call]
    q, k, v, do = operands(b, sq, sk, h, kv_h, d, vd, dtype)
    one = backward(q, k, v, do, causal, window, *tiles)
    monkeypatch.setattr(flash_attn, "_bwd_vmem_limit", lambda *a: None)
    pair = backward(q, k, v, do, causal, window, *tiles)
    for name, got, want in zip(("dq", "dk", "dv"), one, pair):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.asarray(jnp.abs(want.astype(jnp.float32)).max()) > 0
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32), name)


@pytest.mark.parametrize("call", ["causal_rep4", "window_rep7",
                                  "latent_widths_rep1", "float32"])
def test_one_kernel_against_the_dense_reference(call):
    b, sq, sk, h, kv_h, d, vd, causal, window, tiles, dtype = CALLS[call]
    q, k, v, do = (x.astype(jnp.float32) for x in operands(
        b, sq, sk, h, kv_h, d, vd, dtype))

    def grads(attn):
        return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) * do),
                        (0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=tiles[0], block_k=tiles[1],
        window=window, interpret=True))
    want = grads(lambda q, k, v: dense_attention(q, k, v, causal=causal,
                                                 window=window))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def kernel_names(fn, *args) -> set:
    text = str(jax.make_jaxpr(fn)(*args))
    return set(re.findall(r"\bname=((?:flash|swa)_(?:fwd|bwd)\w*)", text))


def grad_fn(window=None):
    return jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64, window=window,
        interpret=True).astype(jnp.float32).sum(), (0, 1, 2))


@pytest.mark.parametrize("window,prefix", [(None, "flash"), (96, "swa")])
def test_the_pair_is_taken_exactly_where_the_head_does_not_fit(
        window, prefix, monkeypatch):
    """The choice is a static function of the call's shapes: at a ceiling
    of what the reckoning asks for the backward is one kernel, a byte under
    it the pair, and both give the same gradients."""
    q, k, v, _ = operands(1, 256, 256, 4, 2, 32, 32, jnp.bfloat16)
    monkeypatch.setattr(flash_attn, "_VMEM_LIMIT", 0)   # the reckoning bare
    need = flash_attn._bwd_vmem_limit(256, 32, 32, 2, 64, 64)
    assert need is not None and need > 256 * 256 * (4 + 2 * 2)

    monkeypatch.setattr(flash_attn, "_VMEM_CEILING", need)
    assert kernel_names(grad_fn(window), q, k, v) == {
        f"{prefix}_fwd", f"{prefix}_bwd"}
    one = grad_fn(window)(q, k, v)

    monkeypatch.setattr(flash_attn, "_VMEM_CEILING", need - 1)
    assert flash_attn._bwd_vmem_limit(256, 32, 32, 2, 64, 64) is None
    assert kernel_names(grad_fn(window), q, k, v) == {
        f"{prefix}_fwd", f"{prefix}_bwd_dq", f"{prefix}_bwd_dkv"}
    pair = grad_fn(window)(q, k, v)
    for got, want in zip(one, pair):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


# (positions, key width, value width, bytes an element) at the launch tiles
# -> MiB the call asks for, None where the backward stays the pair.
RECKONED = [
    ((4096, 128, 128, 2), 32),      # mistral7b-tok4k-1chip: the standing limit
    ((16384, 128, 128, 2), 54),     # smallthinker21b-tok16k-1chip, both kinds
    ((16384, 192, 128, 2), 72),     # kanana2-tok16k-1chip: 192 pads to 256
    ((4096, 256, 256, 4), 56),      # float32 at head 256 (test_eva_aot.py)
    ((32768, 128, 128, 2), 86),     # bfloat16 at head 128 fits to 32k
    ((32768, 256, 256, 4), None),   # float32 at head 256 does not
    ((65536, 128, 128, 2), None),   # nor any head at 64k and beyond
    ((65536, 64, 64, 2), None),     # (64 columns pad to a lane tile of 128)
    ((131072, 128, 128, 2), None),
]


@pytest.mark.parametrize("shape,mib", RECKONED)
def test_the_scoped_limit_is_reckoned_from_the_calls_shapes(shape, mib):
    limit = flash_attn._bwd_vmem_limit(*shape, 1024, 1024)
    assert limit == (None if mib is None else mib * MiB)
    if limit is not None:
        assert flash_attn._VMEM_LIMIT <= limit <= flash_attn._VMEM_CEILING


# (positions, window, rep) at the launch tiles -> items a (batch, kv head).
CELLS_BWD_STEPS = [((4096, None, 4), 4 * 10),       # dense: 10 of 16 a head
                   ((16384, None, 7), 7 * 136),     # sparse, full layer
                   ((16384, 4096, 7), 7 * 70),      # sparse, windowed layers
                   ((16384, None, 1), 136)]         # latent: 32 kv heads


@pytest.mark.parametrize("call,items", CELLS_BWD_STEPS)
def test_bwd_grid_steps_of_the_cells_calls_by_hand(call, items):
    seq, window, rep = call
    steps = grid_steps(seq, seq, 1024, 1024, True, window, rep)
    assert steps["bwd"] == items == rep * steps["dq"] == steps["dkv"]


@pytest.mark.parametrize("causal,window,rep", [(True, None, 1), (True, None, 4),
                                               (True, 96, 7), (False, None, 2)])
def test_the_one_walk_keeps_both_passes_orders(causal, window, rep):
    """What makes the gradients bit-equal: restricted to a K/V tile the
    walk is :func:`_kv_schedule`'s, restricted to a (head, q tile) it is
    :func:`_q_schedule`'s with its first and last flags."""
    live = flash_attn._live_tiles(256, 256, 32, 64, causal, window)
    kt, head, qt, flags = flash_attn._bwd_schedule(live, rep)
    assert all(a.dtype == np.int32 for a in (kt, head, qt, flags))
    assert len(kt) == rep * live.sum() and live[qt, kt].all()
    kv_kt, kv_head, kv_qt, _ = flash_attn._kv_schedule(live, rep)
    for tile in range(live.shape[1]):
        np.testing.assert_array_equal(head[kt == tile], kv_head[kv_kt == tile])
        np.testing.assert_array_equal(qt[kt == tile], kv_qt[kv_kt == tile])
    q_qt, q_kt, q_flags = flash_attn._q_schedule(live)
    for r in range(rep):
        np.testing.assert_array_equal(qt[head == r], q_qt)
        np.testing.assert_array_equal(kt[head == r], q_kt)
        np.testing.assert_array_equal(flags[head == r], q_flags)
    assert (np.diff(head) >= 0).all()               # heads outermost


def test_s_and_dp_are_made_in_one_place_a_tile():
    """The kernel the cells run calls the shared tile math once; the pair's
    two kernels, kept as the fallback, once each."""
    for kernel in (flash_attn._flash_bwd_kernel,
                   flash_attn._flash_bwd_dq_kernel,
                   flash_attn._flash_bwd_dkv_kernel):
        assert inspect.getsource(kernel).count("_bwd_p_ds(") == 1
    assert inspect.getsource(flash_attn._bwd_p_ds).count("_p_ds_tile(") == 1


def test_ulysses_attention_differentiates_through_the_one_kernel():
    """``ulysses_attention(local_attn="flash")`` runs the exchanged
    full-sequence attention through ``flash_attention``: its backward is
    the one kernel too."""
    from jax.sharding import Mesh, PartitionSpec as P
    from petastorm_tpu.parallel.ulysses_attention import ulysses_attention
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    q, k, v, _ = operands(1, 256, 256, 4, 4, 16, 16, jnp.float32)
    attn = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq",
                                          causal=True, local_attn="flash"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False)
    names = kernel_names(jax.grad(lambda q, k, v: attn(q, k, v).sum(),
                                  (0, 1, 2)), q, k, v)
    assert names == {"flash_fwd", "flash_bwd"}
