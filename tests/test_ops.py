"""Device-op tests (pallas kernel in interpret mode on the CPU mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.ops.image_ops import normalize_images


def test_normalize_xla_path_correctness():
    imgs = np.random.default_rng(0).integers(0, 255, (2, 8, 8, 3)).astype(np.uint8)
    out = normalize_images(jnp.asarray(imgs), use_pallas=False)
    assert out.dtype == jnp.bfloat16
    expected = (imgs / 255.0 - np.array([0.485, 0.456, 0.406])) / np.array(
        [0.229, 0.224, 0.225])
    np.testing.assert_allclose(np.asarray(out, np.float32), expected, atol=1e-2)


def test_normalize_pallas_interpret_matches_xla():
    # 8*224*224*3 flattens to (9408, 128); block picks lcm(3,32)*k rows.
    imgs = np.random.default_rng(1).integers(0, 255, (8, 224, 224, 3)).astype(np.uint8)
    x = jnp.asarray(imgs)
    out_pallas = normalize_images(x, use_pallas=True)   # interpret on CPU
    out_xla = normalize_images(x, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(out_pallas, np.float32),
                                  np.asarray(out_xla, np.float32))


def test_normalize_pallas_rejects_untileable():
    imgs = jnp.zeros((1, 5, 5, 3), jnp.uint8)
    with pytest.raises(ValueError, match="tile"):
        normalize_images(imgs, use_pallas=True)


def test_normalize_custom_mean_std_and_dtype():
    imgs = np.full((4, 32, 32, 3), 128, np.uint8)
    out = normalize_images(jnp.asarray(imgs), mean=(0.5, 0.5, 0.5),
                           std=(0.5, 0.5, 0.5), out_dtype=jnp.float32,
                           use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), (128 / 255 - 0.5) / 0.5, atol=1e-6)


# ------------------------------------------------------------ augmentation ---

def test_random_flip_horizontal_flips_some():
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import random_flip_horizontal
    imgs = jnp.arange(4 * 2 * 3 * 1, dtype=jnp.float32).reshape(4, 2, 3, 1)
    out = random_flip_horizontal(jax.random.PRNGKey(0), imgs)
    flipped = imgs[:, :, ::-1, :]
    per_sample_flipped = [bool(jnp.all(out[i] == flipped[i])) for i in range(4)]
    per_sample_same = [bool(jnp.all(out[i] == imgs[i])) for i in range(4)]
    assert all(f or s for f, s in zip(per_sample_flipped, per_sample_same))
    # p=1 / p=0 are deterministic
    assert bool(jnp.all(random_flip_horizontal(jax.random.PRNGKey(1), imgs, p=1.0)
                        == flipped))
    assert bool(jnp.all(random_flip_horizontal(jax.random.PRNGKey(1), imgs, p=0.0)
                        == imgs))


def test_random_crop_shape_and_content():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petastorm_tpu.ops import random_crop
    imgs = jnp.ones((3, 8, 8, 2), jnp.uint8) * 7
    out = random_crop(jax.random.PRNGKey(0), imgs, padding=2)
    assert out.shape == imgs.shape and out.dtype == imgs.dtype
    vals = np.unique(np.asarray(out))
    assert set(vals.tolist()) <= {0, 7}  # original content or zero padding
    # determinism
    again = random_crop(jax.random.PRNGKey(0), imgs, padding=2)
    assert bool(jnp.all(out == again))


def test_cutout_masks_expected_area():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petastorm_tpu.ops import cutout
    imgs = jnp.ones((2, 16, 16, 3), jnp.float32)
    out = np.asarray(cutout(jax.random.PRNGKey(3), imgs, size=4))
    zeros_per_sample = (out == 0).all(axis=-1).sum(axis=(1, 2))
    assert (zeros_per_sample > 0).all()
    assert (zeros_per_sample <= 16).all()  # at most size^2 (clipped at edges)


def test_mixup_mixes_images_and_labels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from petastorm_tpu.ops import mixup
    imgs = jnp.stack([jnp.zeros((4, 4, 1)), jnp.ones((4, 4, 1))]).astype(jnp.float32)
    labels = jnp.asarray([0, 1], jnp.int32)
    mixed, soft = mixup(jax.random.PRNGKey(0), imgs, labels, alpha=0.4,
                        num_classes=2)
    assert mixed.shape == imgs.shape and soft.shape == (2, 2)
    np.testing.assert_allclose(np.asarray(soft).sum(axis=1), 1.0, rtol=1e-5)
    # lam >= 0.5 keeps each sample dominated by its own content
    assert float(mixed[0].mean()) <= 0.5 and float(mixed[1].mean()) >= 0.5
    import pytest
    with pytest.raises(ValueError):
        mixup(jax.random.PRNGKey(0), imgs, labels)  # int labels, no num_classes
    with pytest.raises(ValueError):
        mixup(jax.random.PRNGKey(0), imgs.astype(jnp.uint8), labels, num_classes=2)


# ------------------------------------------------------- flash attention
def _attn_inputs(s=256, h=4, kvh=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(2, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(2, s, kvh, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, s, kvh, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kvh", [4, 2])  # MHA and grouped-query
def test_flash_attention_matches_dense(causal, kvh):
    from petastorm_tpu.ops.flash_attn import flash_attention
    from petastorm_tpu.parallel.attention import dense_attention

    q, k, v = _attn_inputs(kvh=kvh)
    out = flash_attention(q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grads_match_dense():
    from petastorm_tpu.ops.flash_attn import flash_attention
    from petastorm_tpu.parallel.attention import dense_attention

    q, k, v = _attn_inputs(s=128)
    gf = jax.grad(lambda *a: (flash_attention(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: (dense_attention(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_attention_bf16():
    from petastorm_tpu.ops.flash_attn import flash_attention
    from petastorm_tpu.parallel.attention import dense_attention

    q, k, v = _attn_inputs(s=128, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_attention_untileable_falls_back(monkeypatch):
    """seq=100 clamps the block to 100, which is not 8-aligned: the dense
    fallback must kick in WITHOUT touching the kernel (a 100-wide tile
    would fail Mosaic's second-minor granule on real hardware even though
    interpret mode happily runs it)."""
    import importlib

    from petastorm_tpu.parallel.attention import dense_attention

    # The package re-export shadows the submodule attribute; resolve the
    # module itself to patch its internals.
    fa_mod = importlib.import_module("petastorm_tpu.ops.flash_attn")

    def _boom(*a, **kw):
        raise AssertionError("kernel must not run for untileable shapes")

    monkeypatch.setattr(fa_mod, "_flash_forward", _boom)
    q, k, v = _attn_inputs(s=100)
    out = fa_mod.flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    # causal cross-attention (sq != sk) must also avoid the kernel
    out2 = fa_mod.flash_attention(q[:, :96], k[:, :64], v[:, :64], causal=True)
    assert out2.shape == (2, 96, 4, 64)


def test_flash_attention_block_fallback_keeps_kernel_path(monkeypatch):
    """seq=1280 divides the 128 granule but not the 1024 x 1024 launch
    defaults: _pick_block must halve the blocks down to 256 and stay on
    the kernel path (regression: raising the defaults silently pushed
    these seqs onto the O(seq^2) dense fallback)."""
    import importlib

    fa_mod = importlib.import_module("petastorm_tpu.ops.flash_attn")
    assert fa_mod._pick_block(fa_mod._DEFAULT_BLOCK_K, 1280) == 256
    assert fa_mod._pick_block(fa_mod._DEFAULT_BLOCK_K, 1152) == 128
    assert fa_mod._pick_block(96, 256) == 128     # no power of two: granule
    assert fa_mod._pick_block(fa_mod._DEFAULT_BLOCK_K, 4096) \
        == fa_mod._DEFAULT_BLOCK_K  # divides: launch default stays
    assert fa_mod._pick_block(fa_mod._DEFAULT_BLOCK_Q, 100) == 100  # -> dense

    calls = {}
    real = fa_mod._flash_forward

    def spy(q, k, v, causal, block_q, block_k, interpret, window=None):
        calls["blocks"] = (block_q, block_k)
        return real(q, k, v, causal, block_q, block_k, interpret, window)

    monkeypatch.setattr(fa_mod, "_flash_forward", spy)
    q, k, v = _attn_inputs(s=1280)
    out = fa_mod.flash_attention(q, k, v, causal=True)
    # 1280 % 1024 != 0: both blocks halve 1024 -> 512 -> 256
    assert calls["blocks"] == (256, 256)
    from petastorm_tpu.parallel.attention import dense_attention
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_attention_in_llama():
    """make_flash_attention drops into llama.apply as attn_fn (GQA-native)
    and reproduces the dense-attention loss."""
    from petastorm_tpu.models import llama
    from petastorm_tpu.ops.flash_attn import make_flash_attention

    cfg = llama.LlamaConfig(vocab=64, dim=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, hidden=96)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (2, 129)), jnp.int32)}
    base = float(llama.loss_fn(params, batch, cfg))
    flash = float(llama.loss_fn(params, batch, cfg,
                                attn_fn=make_flash_attention(causal=True)))
    assert flash == pytest.approx(base, abs=5e-3)


def test_flash_attention_multichunk_grads_match_dense():
    """s=256 with block 128 -> two q chunks through the checkpointed
    backward; grads must still equal the dense path's."""
    from petastorm_tpu.ops.flash_attn import flash_attention
    from petastorm_tpu.parallel.attention import dense_attention

    q, k, v = _attn_inputs(s=256)
    for causal in (False, True):
        gf = jax.grad(lambda *a: (flash_attention(*a, causal=causal) ** 2).sum(),  # noqa: B023
                      argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda *a: (dense_attention(*a, causal=causal) ** 2).sum(),  # noqa: B023
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# ---------------------------------------------------------- flash stats ----

def test_flash_stats_match_dense_stats():
    """flash_attention_stats emits the ring-merge contract (unnormalized o,
    m, l): must equal the chunked dense stats bit-for-tolerance, causal and
    not, GQA included (kernel in interpret mode off-TPU)."""
    import numpy as np
    from petastorm_tpu.ops.flash_attn import (_dense_stats,
                                              flash_attention_stats)

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    for causal in (False, True):
        o_f, m_f, l_f = flash_attention_stats(q, k, v, causal=causal,
                                              block_q=16, block_k=16,
                                              interpret=True)
        o_d, m_d, l_d = _dense_stats(q, k, v, causal, block_q=16)
        # m differs by the blockwise running max ONLY when a later block
        # raises it; both are valid online-softmax states — compare the
        # normalized outputs and the recombined normalizers instead.
        np.testing.assert_allclose(
            np.asarray(o_f / l_f[..., None]),
            np.asarray(o_d / l_d[..., None]), atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(m_f + jnp.log(l_f)),   # logsumexp is state-invariant
            np.asarray(m_d + jnp.log(l_d)), atol=2e-5)
        assert o_f.shape == q.shape


def test_flash_stats_fallback_non_tiling():
    """Shapes the kernel can't tile (seq 20 -> block 20 not 8-aligned) fall
    back to the dense stats transparently."""
    import numpy as np
    from petastorm_tpu.ops.flash_attn import (_dense_stats,
                                              flash_attention_stats)

    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 20, 2, 8)), jnp.float32)
               for _ in range(3))
    o_f, m_f, l_f = flash_attention_stats(q, k, v, causal=True)
    o_d, m_d, l_d = _dense_stats(q, k, v, True, block_q=20)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_d), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_f), np.asarray(m_d), atol=1e-6)


def test_flash_stats_grad_matches_dense_stats_grad():
    """The custom_vjp recomputes through the dense stats: gradients of a
    loss touching ALL THREE outputs (o, m, l) must match the dense path."""
    import numpy as np
    from petastorm_tpu.ops.flash_attn import (_dense_stats,
                                              flash_attention_stats)

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)

    def loss_flash(q, k, v):
        o, m, l = flash_attention_stats(q, k, v, causal=True, block_q=16,
                                        block_k=16, interpret=True)
        return jnp.sum(o / l[..., None]) + jnp.sum(m + jnp.log(l))

    def loss_dense(q, k, v):
        o, m, l = _dense_stats(q, k, v, True, block_q=16)
        return jnp.sum(o / l[..., None]) + jnp.sum(m + jnp.log(l))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_flash_stats_fallback_large_non_multiple_seq():
    """Regression: sq > default block and not a multiple of it (e.g. 200)
    must fall back to ONE dense block, not crash in the chunked reshape."""
    import numpy as np
    from petastorm_tpu.ops.flash_attn import (_dense_stats,
                                              flash_attention_stats)

    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 200, 2, 8)), jnp.float32)
               for _ in range(3))
    o_f, m_f, l_f = flash_attention_stats(q, k, v, causal=True)
    o_d, m_d, l_d = _dense_stats(q, k, v, True, block_q=200)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_d), atol=1e-5)


def test_flash_backward_gqa_group_accumulation_matches_dense():
    """The Pallas backward's dK/dV pass sums grouped-query head gradients
    in-kernel (grid walks every (group head, q block) pair per K/V tile);
    with rep=4 and multiple blocks in both dims the accumulated grads
    must equal the dense path's."""
    from petastorm_tpu.ops.flash_attn import flash_attention
    from petastorm_tpu.parallel.attention import dense_attention

    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (2, 128, 8, 32))
    k = jax.random.normal(keys[1], (2, 128, 2, 32))
    v = jax.random.normal(keys[2], (2, 128, 2, 32))
    for causal in (False, True):
        gf = jax.grad(lambda *a: (flash_attention(  # noqa: B023
            *a, causal=causal, block_q=32, block_k=64) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda *a: (dense_attention(  # noqa: B023
            *a, causal=causal) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, err_msg=f"d{name}")


def test_make_flash_attention_raises_where_flash_attention_falls_back():
    import jax.numpy as jnp

    from petastorm_tpu.ops.flash_attn import (flash_attention,
                                              make_flash_attention)

    q = jnp.zeros((1, 100, 2, 8), jnp.float32)
    assert flash_attention(q, q, q, causal=True).shape == q.shape  # dense
    with pytest.raises(ValueError, match="cannot tile"):
        make_flash_attention(causal=True)(q, q, q)


def test_pallas_interpret_is_chosen_on_cpu_only(monkeypatch):
    import jax

    from petastorm_tpu.ops import flash_attn

    assert flash_attn._resolve_interpret(None) is True       # tests: cpu
    assert flash_attn._resolve_interpret(False) is False
    for backend in ("tpu", "gpu", "some_plugin"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert flash_attn._resolve_interpret(None) is False
        assert flash_attn._resolve_interpret(False) is False
        with pytest.raises(ValueError, match="cpu only"):
            flash_attn._resolve_interpret(True)


def test_flash_launch_tiles_hold_at_the_smoke_windows():
    """4k and 32k — the chip legs — tile at the launch defaults, 1024x1024."""
    from petastorm_tpu.ops.flash_attn import require_flash_tiles

    assert require_flash_tiles(4096, 4096, causal=True) == (1024, 1024)
    assert require_flash_tiles(32768, 32768, causal=True) == (1024, 1024)
    with pytest.raises(ValueError, match="cannot tile"):
        require_flash_tiles(4096, 2048, causal=True)   # causal needs sq == sk
