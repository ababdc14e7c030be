"""The static tile schedule of the six flash / sliding-window kernels
(``ops/flash_attn.py``): every tile that holds a pair inside the mask is
walked exactly once and no other, counted by hand at the benchmark cells'
shapes and checked against the mask written out element by element."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.ops.flash_attn import (_FIRST, _LAST, _kv_schedule,
                                          _live_tiles, _q_schedule,
                                          flash_attention, grid_steps)


# (seq, block_q, block_k, causal, window, rep) -> live (q tile, K/V tile)
# pairs a head, of the rectangle's n_q x n_k. The first three are the
# benchmark cells' calls at the launch tiles (the dense decoder's, the
# sparse one's full layer, its windowed layers), the next three the same in
# the 256 x 1024 tiles of the rectangular grid this replaced: 40 of 64, 544
# of 1,024 and 280 (the band's constant walk took 384 a head forward and
# 336 in dK/dV).
HAND_COUNTS = [
    (4096, 1024, 1024, True, None, 4, 10, 16),
    (16384, 1024, 1024, True, None, 7, 136, 256),
    (16384, 1024, 1024, True, 4096, 7, 70, 256),
    (4096, 256, 1024, True, None, 4, 40, 64),
    (16384, 256, 1024, True, None, 7, 544, 1024),
    (16384, 256, 1024, True, 4096, 7, 280, 1024),
    (32768, 256, 1024, True, None, 4, 2112, 4096),
    # square tiles: the triangle with its diagonal
    (4096, 512, 512, True, None, 1, 36, 64),
    # a window of one tile sees its own and the one before
    (4096, 512, 512, True, 512, 1, 15, 64),
    # a window past the sequence is the causal mask
    (4096, 256, 1024, True, 1 << 20, 4, 40, 64),
    # no mask: every tile
    (4096, 256, 1024, False, None, 4, 64, 64),
]


@pytest.mark.parametrize("seq,block_q,block_k,causal,window,rep,live,rect",
                         HAND_COUNTS)
def test_grid_steps_by_hand(seq, block_q, block_k, causal, window, rep, live,
                            rect):
    assert (seq // block_q) * (seq // block_k) == rect
    assert grid_steps(seq, seq, block_q, block_k, causal, window, rep) == {
        "fwd": live, "bwd": live * rep, "dq": live, "dkv": live * rep}


def pairs_inside(seq_q, seq_k, causal, window):
    """The mask itself, (seq_q, seq_k) booleans."""
    behind = np.arange(seq_q)[:, None] - np.arange(seq_k)[None, :]
    keep = np.ones((seq_q, seq_k), bool)
    if causal:
        keep &= behind >= 0
    if window is not None:
        keep &= behind < window
    return keep


# (seq, block_q, block_k, causal, window, rep): windows shorter than a
# tile, off every tile edge, longer than the sequence; tiles either way
# round; key tiles whose band of queries runs past the sequence's end.
SCHEDULES = [
    (256, 32, 64, True, None, 1), (256, 64, 32, True, None, 4),
    (256, 32, 32, True, None, 7), (256, 32, 128, True, 1, 2),
    (256, 32, 64, True, 7, 1), (256, 64, 32, True, 64, 4),
    (256, 16, 64, True, 100, 7), (256, 128, 16, True, 33, 1),
    (256, 32, 64, True, 1000, 2), (512, 8, 256, True, 200, 1),
    (256, 32, 64, False, None, 4), (512, 512, 512, True, 5, 3),
]


@pytest.mark.parametrize("seq,block_q,block_k,causal,window,rep", SCHEDULES)
def test_schedules_walk_every_live_tile_once_and_no_other(
        seq, block_q, block_k, causal, window, rep):
    keep = pairs_inside(seq, seq, causal, window)
    n_q, n_k = seq // block_q, seq // block_k
    want = keep.reshape(n_q, block_q, n_k, block_k).any(axis=(1, 3))
    live = _live_tiles(seq, seq, block_q, block_k, causal, window)
    np.testing.assert_array_equal(live, want)

    qt, kt, flags = _q_schedule(live)
    assert sorted(zip(qt, kt)) == sorted(zip(*np.nonzero(want)))
    assert len(set(zip(qt, kt))) == len(qt)
    assert all(a.dtype == np.int32 for a in (qt, kt, flags))
    # q tiles ascend, a q tile's K/V tiles ascend, and the flags bracket
    # each q tile's run: what carries the online softmax across it.
    for i in range(len(qt)):
        new_run = i == 0 or qt[i] != qt[i - 1]
        ends_run = i == len(qt) - 1 or qt[i] != qt[i + 1]
        assert bool(flags[i] & _FIRST) == new_run
        assert bool(flags[i] & _LAST) == ends_run
        if not new_run:
            assert kt[i] > kt[i - 1]
        elif i:
            assert qt[i] > qt[i - 1]
    assert set(qt) == set(range(n_q))       # every output tile is written

    kt, head, qt, flags = _kv_schedule(live, rep)
    assert sorted(zip(kt, head, qt)) == sorted(
        (k, r, q) for q, k in zip(*np.nonzero(want)) for r in range(rep))
    assert len(set(zip(kt, head, qt))) == len(kt)
    for i in range(len(kt)):
        new_run = i == 0 or kt[i] != kt[i - 1]
        ends_run = i == len(kt) - 1 or kt[i] != kt[i + 1]
        assert bool(flags[i] & _FIRST) == new_run
        assert bool(flags[i] & _LAST) == ends_run
        if not new_run:     # heads outermost, q tiles ascending in a head
            assert (head[i], qt[i]) > (head[i - 1], qt[i - 1])
    assert set(kt) == set(range(n_k))


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


@pytest.mark.parametrize("causal,window,prefix", [(True, None, "flash"),
                                                  (True, 100, "swa"),
                                                  (False, None, "flash")])
def test_the_launched_grids_are_the_schedules(causal, window, prefix):
    """What :func:`grid_steps` counts is what the three calls launch: no
    grid step above the diagonal or outside the band."""
    q = jnp.zeros((2, 256, 4, 16))
    kv = jnp.zeros((2, 256, 2, 16))
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=64,
        window=window).sum(), (0, 1, 2)))(q, kv, kv)
    steps = grid_steps(256, 256, 32, 64, causal, window, rep=2)
    assert steps["fwd"] < 8 * 4 or not causal
    assert pallas_calls(jaxpr.jaxpr) == {
        f"{prefix}_fwd": (2, 4, steps["fwd"]),
        f"{prefix}_bwd": (2, 2, steps["bwd"])}
