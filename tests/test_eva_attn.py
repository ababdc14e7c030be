"""EVA attention (``ops/eva_attn.py``, Pallas kernels interpreted on the
CPU) against the plain reference's attention
(``chipbench/reference/evabyte.py``, dense float32 scores per block):
outputs and the gradients of q, k, v, phi and mu, the schedules against a
brute-force walk of the mask, and what the forward names for a checkpoint
policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import evabyte as ref
from petastorm_tpu.ops import eva_attn
from petastorm_tpu.ops.eva_attn import eva_attention

W, C, H, D, B = 32, 4, 2, 8, 2      # window, chunk, heads, head width, rows
# Small tiles walk several key and summary tiles a query tile; the defaults
# clamp to one tile a window.
TILINGS = {"default": {}, "small": dict(_tiles=(8, 16, 16))}


@pytest.fixture(autouse=True)
def full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def operands(n_blocks: int, seed: int = 0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (B, n_blocks * W, H, D)
    q, k, v = (jax.random.normal(keys[i], shape, jnp.float32)
               for i in range(3))
    phi, mu = (0.3 * jax.random.normal(keys[3 + i], (H, D)) for i in (0, 1))
    return (q, k, v, phi, mu), jax.random.normal(keys[5], shape)


def reference_attention(q, k, v, phi, mu):
    """The reference's one-head attention over rows and heads."""
    one = lambda q, k, v, phi, mu: ref.head_attention(q, k, v, phi, mu, W, C)
    heads = jax.vmap(one, in_axes=(1, 1, 1, 0, 0), out_axes=1)
    return jax.vmap(heads, in_axes=(0, 0, 0, None, None))(q, k, v, phi, mu)


@pytest.mark.parametrize("tiling", TILINGS)
@pytest.mark.parametrize("n_blocks", [1, 2, 5])
def test_output_and_gradients_match_the_reference(n_blocks, tiling):
    args, weight = operands(n_blocks, seed=n_blocks)
    attn = lambda *a: eva_attention(*a, window=W, chunk=C, **TILINGS[tiling])
    out, want = attn(*args), reference_attention(*args)
    # float32 on both sides: what is left is the order of the sums (online
    # softmax over tiles against one dense softmax a block).
    np.testing.assert_allclose(out, want, atol=2e-6)
    # A query of the first block sees no summary: its rows equal plain
    # causal attention within the block.
    first = eva_attention(*(a[:, :W] for a in args[:3]), *args[3:],
                          window=W, chunk=C, **TILINGS[tiling])
    np.testing.assert_allclose(out[:, :W], first, atol=2e-6)
    grads = jax.grad(lambda *a: jnp.sum(attn(*a) * weight),
                     argnums=(0, 1, 2, 3, 4))(*args)
    wants = jax.grad(lambda *a: jnp.sum(reference_attention(*a) * weight),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, got, want in zip(("q", "k", "v", "phi", "mu"), grads, wants):
        scale = max(float(jnp.abs(want).max()), 1e-6)
        np.testing.assert_allclose(got / scale, want / scale, atol=3e-6,
                                   err_msg=name)
    if n_blocks == 1:       # no summary is seen: phi and mu do not matter
        assert not np.any(grads[3]) and not np.any(grads[4])


@pytest.mark.parametrize("n_blocks", [2, 5])
def test_the_last_blocks_queries_see_every_earlier_summary(n_blocks):
    """Moving mu moves the last block's rows and not the first's; moving a
    key of the last block moves no earlier row."""
    args, _ = operands(n_blocks)
    attn = lambda *a: eva_attention(*a, window=W, chunk=C,
                                    _tiles=(8, 16, 16))
    base = attn(*args)
    moved = attn(*args[:4], args[4] + 1.0)
    assert np.array_equal(base[:, :W], moved[:, :W])
    assert np.abs(base[:, -W:] - moved[:, -W:]).max() > 1e-3
    k2 = args[1].at[:, -1].add(1.0)
    assert np.array_equal(base[:, :-1], attn(args[0], k2, *args[2:])[:, :-1])


@pytest.mark.parametrize("blocks", [(8, 16, 16), (32, 32, 8), (16, 8, 24)])
@pytest.mark.parametrize("n_blocks", [1, 3, 5])
def test_schedules_cover_the_mask_and_nothing_dead(n_blocks, blocks):
    """Every (query tile, key or summary tile) pair with a live element is
    walked exactly once by both schedules, and no other; first and last
    flags bracket each walk; an unread source keeps its tile index."""
    t = eva_attn._shape(n_blocks * W, W, C, *blocks)
    seq = n_blocks * W
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    local = (i // W == j // W) & (j <= i)
    n_sum = t.n_s_tiles * t.bs
    c = np.arange(n_sum)[None, :]
    remote = (c < t.n_seen) & ((c * C) // W < i // W)

    def live_tiles(mask, rows, cols):
        return {(a, b) for a in range(mask.shape[0] // rows)
                for b in range(mask.shape[1] // cols)
                if mask[a * rows:(a + 1) * rows, b * cols:(b + 1) * cols].any()}

    want = ({(0, q, kt) for q, kt in live_tiles(local, t.bq, t.bk)}
            | {(1, q, st) for q, st in live_tiles(remote, t.bq, t.bs)})
    qt, kt, st, flags = eva_attn._q_schedule(t)
    walked = [(f & 1, q, s if f & 1 else k)
              for q, k, s, f in zip(qt, kt, st, flags)]
    assert len(walked) == len(set(walked)) and set(walked) == want
    # One walk a query tile, in order, local tiles first.
    assert [q for q, f in zip(qt, flags) if f & 2] == list(range(seq // t.bq))
    assert [q for q, f in zip(qt, flags) if f & 4] == list(range(seq // t.bq))
    assert np.all(np.diff(qt) >= 0)
    kt2, st2, qt2, flags2 = eva_attn._kv_schedule(t)
    walked2 = [(f & 1, q, s if f & 1 else k)
               for k, s, q, f in zip(kt2, st2, qt2, flags2)]
    assert len(walked2) == len(set(walked2)) and set(walked2) == want
    assert sum(bool(f & 2) for f in flags2) == sum(bool(f & 4) for f in flags2)
    # While the summaries are walked the key tile's index stays put (its
    # block is not fetched or written again), and the other way round.
    local_items = (flags2 & 1) == 0
    assert np.all(st2[local_items] == 0)
    assert np.all(kt2[~local_items] == seq // t.bk - 1)


def test_shapes_that_cannot_tile_raise():
    args, _ = operands(2)
    with pytest.raises(ValueError, match="chunk"):
        eva_attention(*args, window=W, chunk=5)
    with pytest.raises(ValueError, match="chunk"):
        eva_attention(*(a[:, :W + 8] for a in args[:3]), *args[3:],
                      window=W, chunk=C)
    with pytest.raises(ValueError, match="cannot tile"):
        eva_attention(*args, window=W, chunk=C, _tiles=(12, 16, 16))
    with pytest.raises(ValueError, match="one key/value head"):
        eva_attention(args[0], args[1][:, :, :1], args[2][:, :, :1],
                      *args[3:], window=W, chunk=C)


def test_forward_names_its_output_and_statistics_for_a_checkpoint():
    """Under a checkpoint whose policy saves the flash kernels' names the
    gradient launches ``eva_fwd`` once; under a bare checkpoint twice."""
    from petastorm_tpu.ops.flash_attn import SAVED_NAMES
    args, weight = operands(2)

    def loss(policy):
        attn = jax.checkpoint(
            lambda *a: eva_attention(*a, window=W, chunk=C), policy=policy)
        return lambda *a: jnp.sum(attn(*a) * weight)

    def launches(policy):
        text = str(jax.make_jaxpr(jax.grad(loss(policy)))(*args))
        return text.count("name=eva_fwd"), text.count("name=eva_bwd_dkv")

    keep = jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)
    assert launches(keep) == (1, 1)
    assert launches(None) == (2, 1)
    np.testing.assert_allclose(jax.grad(loss(keep))(*args),
                               jax.grad(loss(None))(*args), atol=1e-6)
