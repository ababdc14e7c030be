"""The EVA decoder through ``models/llama.py`` (``attention="eva"``,
``norm_unit_offset``, ``n_pred_heads``) against the plain reference
(``chipbench/reference/evabyte.py``) on seeded weights: the init, the loss,
the gradient's leaf norms; the eight-head loss against a loop over heads;
the float32 residual add; and that the checkpoint policy runs the kernel
once."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import evabyte as ref
from petastorm_tpu.models import llama

SIZES = {"hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 2,
         "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
         "vocab_size": 320, "num_pred_heads": 8, "window_size": 32,
         "chunk_size": 4, "rope_theta": 100000, "rms_norm_eps": 1e-5}
CFG = llama.LlamaConfig(
    vocab=320, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, head_dim=16,
    hidden=64, rope_theta=1e5, norm_eps=1e-5, attention="eva", eva_window=32,
    eva_chunk=4, norm_unit_offset=True, n_pred_heads=8)
SEQ = 96        # three blocks


@pytest.fixture(autouse=True)
def full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def ids(batch: int = 2, seed: int = 1):
    return 64 + jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0,
                                   256)


def program_loss(params, tokens, **kw):
    return llama.loss_fn(params, {"tokens": tokens}, CFG, shift="roll",
                         compute_dtype=jnp.float32, **kw)


def test_init_is_the_references_draw_for_draw():
    key = jax.random.PRNGKey(7)
    program, reference = llama.init_params(key, CFG), ref.init_params(
        key, SIZES)
    assert (jax.tree.structure(program) == jax.tree.structure(reference))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(program)[0],
                            jax.tree.leaves(reference)):
        assert a.dtype == jnp.float32 and np.array_equal(a, b), path
    layer = program["layers"][0]
    assert layer["eva_phi"].shape == layer["eva_mu"].shape == (2, 16)
    assert float(jnp.abs(layer["eva_phi"]).max()) <= 16 ** -0.5
    assert not np.any(layer["attn_norm"]) and not np.any(program["norm_out"])
    assert program["lm_head"].shape == (32, 8 * 320)


@pytest.mark.parametrize("how", [
    {"xent_chunk": 192}, {"xent_chunk": 64},
    {"xent_chunk": 64, "remat_layers": True}],
    ids=["one-chunk", "chunked", "chunked-remat"])
def test_loss_and_gradient_leaf_norms_match_the_reference(how):
    params = llama.init_params(jax.random.PRNGKey(3), CFG)
    # Norm offsets and mu away from their init, so that a wrong reading of
    # either shows.
    params = jax.tree.map(lambda x: x + 0.05 if x.ndim == 1 else x, params)
    tokens = ids()
    loss, grads = jax.value_and_grad(
        lambda p: program_loss(p, tokens, **how))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, SIZES))(params)
    # Both float32 at highest precision: the gap is the order of the sums
    # (online softmax over tiles; one-pass head; bf16 nowhere).
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    for (path, got), exp in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(want_grads)):
        norm = float(jnp.linalg.norm(exp))
        assert float(jnp.linalg.norm(got - exp)) < 2e-5 * max(norm, 1e-3), path


def test_eight_head_loss_is_the_mean_over_a_loop_of_heads():
    params = llama.init_params(jax.random.PRNGKey(5), CFG)
    tokens = ids(seed=2)
    logits = llama.apply(params, tokens, CFG, compute_dtype=jnp.float32)
    assert logits.shape == (2, SEQ, 8 * 320)
    total, count = 0.0, 0
    for m in range(8):
        head = logits[..., m * 320:(m + 1) * 320]
        logp = jax.nn.log_softmax(head[:, :SEQ - 1 - m], axis=-1)
        target = tokens[:, 1 + m:]
        total += float(-jnp.take_along_axis(
            logp, target[..., None], axis=-1).sum())
        count += target.size
    for chunk in (192, 48):
        assert float(program_loss(params, tokens, xent_chunk=chunk)) == \
            pytest.approx(total / count, rel=2e-6)
    assert count == 2 * ref.counted_pairs(SEQ, 8)


@pytest.mark.parametrize("how", [dict(shift="split", xent_chunk=48),
                                 dict(shift="roll")],
                         ids=["split", "no-xent-chunk"])
def test_eight_heads_need_the_full_window_and_the_chunked_head(how):
    params = llama.init_params(jax.random.PRNGKey(5), CFG)
    with pytest.raises(ValueError, match="shift='roll'.*xent_chunk"):
        llama.loss_fn(params, {"tokens": ids()}, CFG, **how)


def test_left_out_half_is_the_rows_second_half_of_positions():
    """The fault ``calibrate`` plants at one row a step
    (``reference(rows=0)``): positions from the middle on are out of the
    loss and of its mean."""
    params = ref.init_params(jax.random.PRNGKey(9), SIZES)
    tokens = ids(batch=1, seed=4)
    half = float(ref.loss(params, tokens, SIZES, positions=SEQ // 2))
    assert ref.counted_pairs(SEQ, 8, SEQ // 2) == 8 * (SEQ // 2)
    targets, counted = ref.targets_and_counted(tokens[0], 8, SEQ // 2)
    assert not np.any(counted[SEQ // 2:]) and np.all(counted[:SEQ // 2])
    assert np.array_equal(targets[:5, 2], tokens[0, 3:8])
    whole = float(ref.loss(params, tokens, SIZES))
    assert abs(half - whole) > 1e-4 * whole


def test_unit_offset_norm_scales_by_one_plus_g():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 32))
    g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    np.testing.assert_allclose(llama._rmsnorm(x, g, 1e-5, True),
                               llama._rmsnorm(x, 1.0 + g, 1e-5), rtol=1e-6)


def test_config_refuses_what_eva_cannot_run():
    base = dict(vocab=320, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
                head_dim=16, hidden=64, attention="eva", eva_window=32,
                eva_chunk=4)
    llama.LlamaConfig(**base)
    for bad, match in ((dict(eva_chunk=5), "multiple"),
                       (dict(eva_chunk=0), "multiple"),
                       (dict(n_kv_heads=1), "one KV head"),
                       (dict(attention="linear"), "unknown attention"),
                       (dict(n_pred_heads=0), "n_pred_heads")):
        with pytest.raises(ValueError, match=match):
            llama.LlamaConfig(**{**base, **bad})


def test_param_shardings_carry_the_new_leaves():
    specs = llama._param_pspec_tuples(CFG, "model")
    assert specs["layers"][0]["eva_phi"] == ("model", None)
    assert specs["layers"][1]["eva_mu"] == ("model", None)
    assert (jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, tuple))
            == jax.tree.structure(llama.init_params(jax.random.PRNGKey(0),
                                                    CFG)))
    assert "eva_phi" not in llama._param_pspec_tuples(
        llama.TINY, "model")["layers"][0]


def test_remat_layers_runs_each_layers_kernel_once():
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    tokens = ids()

    def launches(**how):
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: program_loss(p, tokens, xent_chunk=64, **how)))(params))
        return text.count("name=eva_fwd"), text.count("name=eva_bwd_dq")

    assert launches() == (2, 2)
    assert launches(remat_layers=True) == (2, 2)    # not 4: o and lse kept


def test_fp32_skip_add_rounds_the_sum_once():
    """``fp32_skip_add``: the branch's product is not rounded before the
    add, so the sum is the float32 sum rounded once to the stream's dtype;
    without it the product is rounded to bfloat16 first."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(keys[0], (64, 32)).astype(jnp.bfloat16)
    h = jax.random.normal(keys[1], (64, 48)).astype(jnp.bfloat16)
    w = jax.random.normal(keys[2], (48, 32)) / 7
    w16 = w.astype(jnp.bfloat16).astype(jnp.float32)
    exact = x.astype(jnp.float32) + h.astype(jnp.float32) @ w16
    once = llama._skip_add(x, h, w, fp32=True)
    assert once.dtype == jnp.bfloat16
    assert np.array_equal(once, exact.astype(jnp.bfloat16))
    twice = llama._skip_add(x, h, w)
    assert twice.dtype == jnp.bfloat16 and not np.array_equal(once, twice)
    err = lambda y: float(jnp.abs(y.astype(jnp.float32) - exact).mean())
    assert err(once) < err(twice)


@pytest.mark.parametrize("fp32", [False, True], ids=["compute-dtype", "fp32"])
def test_skip_add_gradients_are_the_plain_sums(fp32):
    """In float32 either path's gradients are those of ``x + h @ w``."""
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    x, h = jax.random.normal(keys[0], (8, 6)), jax.random.normal(keys[1], (8, 5))
    w = jax.random.normal(keys[2], (5, 6))
    loss = lambda add: lambda *a: jnp.sum(jnp.sin(add(*a)))
    got = jax.grad(loss(lambda x, h, w: llama._skip_add(x, h, w, fp32)),
                   argnums=(0, 1, 2))(x, h, w)
    want = jax.grad(loss(lambda x, h, w: x + h @ w), argnums=(0, 1, 2))(x, h, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_fp32_skip_add_is_a_static_choice_of_the_dense_block():
    """In float32 compute the flag changes nothing; off, the block traces
    the program it did; the expert layers refuse it."""
    import dataclasses
    params = llama.init_params(jax.random.PRNGKey(3), CFG)
    tokens = ids()
    on = dataclasses.replace(CFG, fp32_skip_add=True)
    loss = lambda cfg, dtype: llama.loss_fn(
        params, {"tokens": tokens}, cfg, shift="roll", xent_chunk=64,
        compute_dtype=dtype)
    assert float(loss(on, jnp.float32)) == float(loss(CFG, jnp.float32))
    assert float(loss(on, jnp.bfloat16)) != float(loss(CFG, jnp.bfloat16))
    text = str(jax.make_jaxpr(lambda x, h, w: llama._skip_add(x, h, w))(
        jnp.ones((4, 8), jnp.bfloat16), jnp.ones((4, 6), jnp.bfloat16),
        jnp.ones((6, 8))))
    assert "preferred_element_type=bfloat16" in text and "f32[4,8]" not in text
    with pytest.raises(ValueError, match="fp32_skip_add"):
        llama.LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=2,
                          n_kv_heads=2, hidden=64, n_experts=4,
                          fp32_skip_add=True)
