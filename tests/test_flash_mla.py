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


# --- The split form: q = (q_nope, q_rope), k = (k_nope, k_rope), the
# rotary key one head for every query head (latent attention's operands as
# ``models/llama._latent_qkv`` makes them).

def split_operands(seed, b, s, h, dn, dr, dv, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return ((jax.random.normal(keys[0], (b, s, h, dn), dtype),
             jax.random.normal(keys[1], (b, s, h, dr), dtype)),
            (jax.random.normal(keys[2], (b, s, h, dn), dtype),
             jax.random.normal(keys[3], (b, s, 1, dr), dtype)),
            jax.random.normal(keys[4], (b, s, h, dv), dtype),
            jax.random.normal(keys[5], (b, s, h, dv), dtype))


def joined(q, k):
    """The split operands as one q and one k: parts concatenated, the
    rotary key repeated over the heads."""
    (qn, qr), (kn, kr) = q, k
    return (jnp.concatenate([qn, qr], -1), jnp.concatenate(
        [kn, jnp.broadcast_to(kr, kn.shape[:3] + kr.shape[3:])], -1))


# (heads, position-free width, rotary width, value width, block_q,
# block_k): toy widths, then the latent cell's (128 + 64 scored, 128
# valued, 32 query heads over one rotary key), each over two q tiles.
SPLIT = [(4, 16, 8, 16, 32, 64), (32, 128, 64, 128, 128, 128)]


@pytest.mark.parametrize("pair", [False, True], ids=["one_kernel", "pair"])
@pytest.mark.parametrize("h,dn,dr,dv,block_q,block_k", SPLIT)
def test_split_operands_against_dense_float32(h, dn, dr, dv, block_q,
                                              block_k, pair, monkeypatch):
    """o, lse and the five gradients of the split call against dense
    float32 attention on the joined form, the one-kernel backward and the
    pair it falls back to; the calls keep the family's names."""
    if pair:
        monkeypatch.setattr(flash_attn, "_bwd_vmem_limit", lambda *a: None)
    s = 2 * block_q
    q, k, v, do = split_operands(h + dn, 1, s, h, dn, dr, dv)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_k=block_k)

    o, pull = jax.vjp(kernel, q, k, v)
    want, want_pull = jax.vjp(
        lambda q, k, v: dense(*joined(q, k), v)[0], q, k, v)
    assert o.shape == (1, s, h, dv)
    np.testing.assert_allclose(o, want, atol=2e-5, rtol=2e-5)
    got, ref = pull(do), want_pull(do)
    assert jax.tree.structure(got) == jax.tree.structure((q, k, v))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)
    _, lse = flash_attn._flash_forward_lse(q, k, v, True, block_q, block_k,
                                           True)
    np.testing.assert_allclose(lse[..., 0], dense(*joined(q, k), v)[1],
                               atol=2e-5, rtol=2e-5)
    names = pallas_calls(jax.make_jaxpr(jax.grad(
        lambda q, k, v: kernel(q, k, v).sum(), (0, 1, 2)))(q, k, v).jaxpr)
    assert set(names) == ({"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
                          if pair else {"flash_fwd", "flash_bwd"})


@pytest.mark.parametrize("pair", [False, True], ids=["one_kernel", "pair"])
def test_the_rotary_keys_gradient_is_the_heads_sum(pair, monkeypatch):
    """The one rotary key's gradient is the sum over the query heads of
    the rotary columns of dK of the joined call (the same kernels on the
    repeated key), and the output and the other gradients are the joined
    call's, bit for bit: the kernels join a split tile before its
    products."""
    if pair:
        monkeypatch.setattr(flash_attn, "_bwd_vmem_limit", lambda *a: None)
    q, k, v, do = split_operands(7, 2, 128, 8, 16, 8, 16)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=64)

    o, pull = jax.vjp(kernel, q, k, v)
    (dqn, dqr), (dkn, dkr), dv = pull(do)
    o_joined, pull = jax.vjp(kernel, *joined(q, k), v)
    dq, dk, dv_joined = pull(do)
    assert dkr.shape == (2, 128, 1, 8)
    np.testing.assert_allclose(dkr, dk[..., 16:].sum(2, keepdims=True),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(o, o_joined)
    np.testing.assert_array_equal(dkn, dk[..., :16])
    np.testing.assert_array_equal(jnp.concatenate([dqn, dqr], -1), dq)
    np.testing.assert_array_equal(dv, dv_joined)


def test_split_operands_that_do_not_pair_are_refused():
    q, k, v, _ = split_operands(1, 1, 64, 2, 8, 4, 8)
    with pytest.raises(ValueError, match="split operands"):
        flash_attention(q, k[0], v, causal=True)
    with pytest.raises(ValueError, match="split operands"):
        flash_attention(q, (k[0], jnp.repeat(k[1], 2, axis=2)), v,
                        causal=True)


def test_untileable_split_operands_take_the_dense_route():
    q, k, v, _ = split_operands(2, 1, 100, 4, 16, 8, 16)
    o = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(o, dense(*joined(q, k), v)[0], atol=2e-5,
                               rtol=2e-5)


def mosaic_modules(fn, *args) -> str:
    """The Mosaic modules of ``fn(*args)`` lowered for a TPU (no chip is
    needed to lower), as text without source locations, sorted."""
    import base64
    import json
    import re
    from jax._src.lib.mlir import ir
    from jax._src.tpu_custom_call import tpu

    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    found = []
    for m in re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"', text):
        config = json.loads(re.sub(r"\\([0-9A-Fa-f]{2})",
                                   lambda g: chr(int(g.group(1), 16)),
                                   m.group(1)))
        body = base64.b64decode(config["custom_call_config"]["body"])
        with ir.Context() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True      # stable_mosaic
            found.append(ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False))
    return "\n".join(sorted(found))


# sha256 of the Mosaic modules (forward and backward) of each family's
# array call, read on the commit before the split form was added
# (e89fb74), by the construction below: the other cells' kernels, whose
# tile bodies and launchers the split form shares, are the same programs.
MOSAIC = {
    "flash": "2fe76cb7c040d2c7add2abd39dfd6e4b4587aa0785ce49698393ec6306792712",
    "swa": "f9fbc83e7e03796957dcf3162a62d0852fd013e07204d0ceec789f2bed2e19cb",
    "eva": "86c61844662b766f93b02912127838446413c45244e050628e1ed94cd75af045",
    "flash_pair":
        "d686870130c88a4bde3c755ab503d5ee9106d1a6f65342c0caf29ba6f65c633a",
    "swa_pair":
        "9e52f95eea523793a1070d591ef2cd335e2476df81688919ca3d139ea960c4bc",
}


@pytest.mark.parametrize("family", sorted(MOSAIC))
def test_array_calls_lower_to_the_mosaic_they_lowered_to(family,
                                                         monkeypatch):
    import hashlib
    from petastorm_tpu.ops.eva_attn import make_eva_attention

    def grad_of(attn, n):
        return jax.grad(lambda *a: attn(*a).astype(jnp.float32).sum(),
                        tuple(range(n)))

    def rows(h):
        return jax.ShapeDtypeStruct((1, 512, h, 128), jnp.bfloat16)

    if family.endswith("_pair"):
        monkeypatch.setattr(flash_attn, "_bwd_vmem_limit", lambda *a: None)
    if family == "eva":
        per_head = jax.ShapeDtypeStruct((2, 128), jnp.float32)
        fn = grad_of(make_eva_attention(128, 16, interpret=False), 5)
        args = (rows(2),) * 3 + (per_head,) * 2
    else:
        window = 200 if family.startswith("swa") else None
        fn = grad_of(flash_attn.make_flash_attention(
            window=window, block_q=128, block_k=256, interpret=False), 3)
        args = (rows(4), rows(2), rows(2))
    digest = hashlib.sha256(mosaic_modules(fn, *args).encode()).hexdigest()
    assert digest == MOSAIC[family]
