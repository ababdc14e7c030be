"""Model smoke tests: shapes, finite grads, one train step (CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models import llama, mlp, resnet, vit


def _finite(tree):
    return all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tree))


def test_mlp_train_step_reduces_loss():
    params = mlp.init_params(jax.random.PRNGKey(0), hidden=64)
    momentum = jax.tree.map(lambda p: p * 0, params)
    step = jax.jit(mlp.make_train_step(0.1))
    rng = np.random.default_rng(0)
    batch = {"image": jnp.asarray(rng.normal(size=(32, 784)), jnp.float32),
             "label": jnp.asarray(rng.integers(0, 10, 32), jnp.int32)}
    losses = []
    for _ in range(5):
        params, momentum, loss, _ = step(params, momentum, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert _finite(params)


@pytest.mark.slow
def test_resnet50_forward_and_grads():
    params = resnet.init_params(jax.random.PRNGKey(0), num_classes=10)
    images = jnp.asarray(np.random.default_rng(0).random((2, 64, 64, 3)), jnp.float32)
    logits, _ = resnet.apply(params, images, train=False)
    assert logits.shape == (2, 10)
    batch = {"image": images, "label": jnp.asarray([1, 2], jnp.int32)}
    (loss, (acc, stats)), grads = jax.value_and_grad(
        resnet.loss_fn, has_aux=True)(params, batch)
    assert np.isfinite(float(loss))
    assert _finite(grads)
    # train step folds bn stats back
    step = resnet.make_train_step(0.1)
    velocity = jax.tree.map(lambda p: p * 0, params)
    new_params, _, loss2, _ = step(params, velocity, batch)
    assert not np.allclose(np.asarray(new_params["head"]["w"]),
                           np.asarray(params["head"]["w"]))
    # moving stats moved away from init
    assert float(jnp.abs(new_params["stem"]["bn"]["mean"]).sum()) > 0


@pytest.mark.slow
def test_vit_forward():
    params = vit.init_params(jax.random.PRNGKey(0), image_size=32, patch=8,
                             dim=64, depth=2, heads=4, mlp_dim=128, num_classes=10)
    images = jnp.asarray(np.random.default_rng(0).random((2, 32, 32, 3)), jnp.float32)
    logits = vit.apply(params, images, patch=8, heads=4)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.slow
def test_llama_tiny_loss_and_grads():
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (2, 17)),
                         jnp.int32)
    loss, grads = jax.value_and_grad(llama.loss_fn)(params, {"tokens": tokens},
                                                    cfg=cfg)
    assert np.isfinite(float(loss))
    assert float(loss) == pytest.approx(np.log(cfg.vocab), rel=0.3)  # random init
    assert _finite(grads)


@pytest.mark.slow
def test_llama_causality():
    """Changing a future token must not change past logits."""
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (1, 16))
    toks2 = toks.copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % cfg.vocab
    out1 = llama.apply(params, jnp.asarray(toks, jnp.int32), cfg)
    out2 = llama.apply(params, jnp.asarray(toks2, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]),
                               atol=1e-5)
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


@pytest.mark.slow
def test_resnet_remat_matches_no_remat():
    """jax.checkpoint remat recomputes activations without changing math:
    loss and grads must match the stored-activation path bitwise-close."""
    params = resnet.init_params(jax.random.PRNGKey(0), 5)
    rng = np.random.default_rng(0)
    batch = {"image": jnp.asarray(rng.random((2, 32, 32, 3)), jnp.float32),
             "label": jnp.asarray([1, 3], jnp.int32)}
    outs = {}
    for remat in (False, True):
        (loss, _), grads = jax.value_and_grad(
            lambda p: resnet.loss_fn(p, batch, remat=remat),  # noqa: B023
            has_aux=True)(params)
        outs[remat] = (float(loss), grads)
    assert abs(outs[True][0] - outs[False][0]) < 1e-5
    flat_a = jax.tree.leaves(outs[False][1])
    flat_b = jax.tree.leaves(outs[True][1])
    for a, b in zip(flat_a, flat_b):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_llama_embed_onehot_matches_gather():
    """The one-hot embedding contraction (used when the table is
    vocab-sharded) is numerically identical to the gather: products are
    exactly 0 or the embedding value and accumulation adds only zeros."""
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (2, 17)), jnp.int32)}
    losses = {mode: float(llama.loss_fn(params, batch, cfg, embed_lookup=mode))
              for mode in ("gather", "onehot")}
    assert losses["gather"] == pytest.approx(losses["onehot"], abs=1e-6)
    with pytest.raises(ValueError, match="embed_lookup"):
        llama.loss_fn(params, batch, cfg, embed_lookup="typo")


def test_llama_roll_shift_loss_matches_manual_mask():
    """shift="roll" feeds the FULL window and masks the wraparound target:
    the loss must equal the hand-computed mean of -logp[target] over
    positions 0..S-2 of the same logits (sharding-friendly layout used by
    the store-fed dryrun; llama.loss_fn docstring)."""
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.models import llama

    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    loss = float(llama.loss_fn(params, {"tokens": tokens}, cfg,
                               shift="roll", aux_weight=0.0))

    logits = llama.apply(params, tokens, cfg)            # (2, 8, vocab) f32
    logp = jax.nn.log_softmax(logits)
    expected = -float(jnp.mean(jnp.take_along_axis(
        logp[:, :-1], tokens[:, 1:, None], axis=-1)))
    assert loss == pytest.approx(expected, rel=1e-6)

    with pytest.raises(ValueError, match="shift"):
        llama.loss_fn(params, {"tokens": tokens}, cfg, shift="typo")


def test_llama_split_shift_loss_matches_log_softmax_reference():
    """The fused nll (logsumexp - target logit; models/llama.py loss_fn)
    must equal the textbook log_softmax + gather form in split mode too
    (roll mode is pinned above)."""
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.models import llama

    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0, cfg.vocab)
    loss = float(llama.loss_fn(params, {"tokens": tokens}, cfg,
                               shift="split", aux_weight=0.0))

    logits = llama.apply(params, tokens[:, :-1], cfg)
    logp = jax.nn.log_softmax(logits)
    expected = -float(jnp.mean(jnp.take_along_axis(
        logp, tokens[:, 1:, None], axis=-1)))
    assert loss == pytest.approx(expected, rel=1e-6)


def test_llama_chunked_xent_matches_full_loss():
    """xent_chunk computes the lm_head matmul + logsumexp per token chunk
    under jax.checkpoint (never materializing (b, s, V) logits) — loss
    and grads must match the full path at bf16-reassociation tolerance
    in both shift modes, and indivisible chunking must raise."""
    import jax
    import numpy as np
    from petastorm_tpu.models import llama

    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab)
    for shift, ck in (("roll", 6), ("split", 8)):
        full = float(llama.loss_fn(params, {"tokens": tokens}, cfg,
                                   shift=shift, aux_weight=0.0))
        chunked = float(llama.loss_fn(params, {"tokens": tokens}, cfg,
                                      shift=shift, aux_weight=0.0,
                                      xent_chunk=ck))
        assert chunked == pytest.approx(full, rel=1e-3)

    g1 = jax.grad(lambda p: llama.loss_fn(
        p, {"tokens": tokens}, cfg, shift="roll", aux_weight=0.0))(params)
    g2 = jax.grad(lambda p: llama.loss_fn(
        p, {"tokens": tokens}, cfg, shift="roll", aux_weight=0.0,
        xent_chunk=6))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
        assert rel < 3e-2  # bf16 cotangent reassociation

    with pytest.raises(ValueError, match="must divide"):
        llama.loss_fn(params, {"tokens": tokens}, cfg, shift="roll",
                      xent_chunk=5)


def _llama_grads(params, tokens, cfg, scale=1.0, **kw):
    return jax.grad(lambda p: scale * llama.loss_fn(
        p, {"tokens": tokens}, cfg, **kw))(params)


def _max_rel_gap(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9))


# (shift, tokens per row, chunk): 2 rows, so model seq is 8 either way; a
# chunk of 4 splits each row in two, a chunk of 16 is all tokens at once.
@pytest.mark.parametrize("compute_dtype,tol", [("float32", 1e-5),
                                               ("bfloat16", 3e-2)])
@pytest.mark.parametrize("shift,window,chunk", [("roll", 8, 4), ("split", 9, 4),
                                                ("roll", 8, 16)])
def test_llama_chunked_xent_grads_match_full_logits(shift, window, chunk,
                                                    compute_dtype, tol):
    """Every leaf's gradient through the one-pass chunked head (its own
    dx / dhead, no autodiff) against autodiff of the full-logits path."""
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, window), 0, cfg.vocab)
    kw = dict(shift=shift, aux_weight=0.0, compute_dtype=jnp.dtype(compute_dtype))
    full = _llama_grads(params, tokens, cfg, **kw)
    chunked = _llama_grads(params, tokens, cfg, xent_chunk=chunk, **kw)
    paths = jax.tree_util.tree_flatten_with_path(full)[0]
    assert len(paths) == len(jax.tree.leaves(chunked))
    for (path, a), b in zip(paths, jax.tree.leaves(chunked)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert _max_rel_gap(a, b) < tol, jax.tree_util.keystr(path)


def test_llama_chunked_xent_roll_mask_position_gets_zero_gradient():
    """shift="roll" masks the wraparound target out of the mean: the hidden
    state at each row's last position gets no gradient from the head, every
    other position does, and dhead is the sum over the unmasked ones only."""
    rng = np.random.default_rng(0)
    b, s, dm, vocab = 2, 6, 16, 48
    x = jnp.asarray(rng.normal(size=(b, s, dm)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(dm, vocab)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, vocab, (b, s)), jnp.int32)
    mask = (jnp.arange(s) < s - 1).astype(jnp.float32)
    weights = jnp.broadcast_to(mask / (mask.sum() * b), (b, s))

    def chunked(x, head):
        return llama._chunked_xent(x.reshape(3, 4, dm), head,
                                   targets.reshape(3, 4), weights.reshape(3, 4))

    def plain(x, head):
        logp = jax.nn.log_softmax(x @ head)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (nll * weights).sum()

    dx, dhead = jax.grad(chunked, argnums=(0, 1))(x, head)
    assert not np.any(np.asarray(dx[:, -1]))
    assert np.all(np.any(np.asarray(dx[:, :-1]) != 0, axis=-1))
    dx_ref, dhead_ref = jax.grad(plain, argnums=(0, 1))(x, head)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dhead, dhead_ref, rtol=1e-5, atol=1e-7)
    assert float(chunked(x, head)) == pytest.approx(float(plain(x, head)),
                                                    rel=1e-6)


def test_llama_chunked_xent_scales_with_the_cotangent():
    """The backward rule scales the stored (dx, dhead) by the incoming
    cotangent: grad of 2.5 * loss is 2.5 * grad of loss on every leaf."""
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    kw = dict(shift="roll", aux_weight=0.0, compute_dtype=jnp.float32,
              xent_chunk=4)
    g1 = _llama_grads(params, tokens, cfg, **kw)
    g2 = _llama_grads(params, tokens, cfg, scale=2.5, **kw)
    assert float(jnp.max(jnp.abs(g1["lm_head"]))) > 0
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        assert _max_rel_gap(2.5 * np.asarray(a), b) < 1e-5


def _count_vocab_products_and_loops(jaxpr, vocab):
    """(dot_generals with the vocabulary axis on an operand or the result,
    scan/while loops) over a jaxpr and every jaxpr nested in its equations."""
    products = loops = 0
    for eqn in jaxpr.eqns:
        products += eqn.primitive.name == "dot_general" and any(
            vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
        loops += eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            p, l = _count_vocab_products_and_loops(sub, vocab)
            products, loops = products + p, loops + l
    return products, loops


@pytest.mark.parametrize("shift,window", [("roll", 8), ("split", 9)])
def test_llama_chunked_xent_grad_is_one_loop_of_three_head_products(shift,
                                                                    window):
    """The mechanism engages at trace time: under grad the head is one loop
    holding logits, dx and dhead (the checkpointed lax.map it replaces
    traced two loops and four products); outside grad the loop holds the
    logits product alone and returns the same value."""
    cfg = llama.TINY  # vocab 256: no other axis of the model has that size
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, window),
                                          0, cfg.vocab)}

    def loss(p):
        return llama.loss_fn(p, batch, cfg, shift=shift, xent_chunk=4)

    assert _count_vocab_products_and_loops(
        jax.make_jaxpr(jax.grad(loss))(params).jaxpr, cfg.vocab) == (3, 1)
    assert _count_vocab_products_and_loops(
        jax.make_jaxpr(loss)(params).jaxpr, cfg.vocab) == (1, 1)
    value, _ = jax.value_and_grad(loss)(params)
    assert float(loss(params)) == pytest.approx(float(value), rel=1e-6)


def test_llama_chunked_xent_keeps_the_moe_aux_term():
    """A switch-dispatch MoE config has aux > 0: the chunked loss still adds
    aux_weight * aux, and the router gets its gradient through it."""
    import dataclasses

    cfg = dataclasses.replace(llama.TINY, n_experts=4, moe_every=1,
                              moe_dispatch="switch")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          cfg.vocab)}
    kw = dict(shift="roll", compute_dtype=jnp.float32)
    _, aux = llama.apply(params, batch["tokens"], cfg, with_aux=True,
                         compute_dtype=jnp.float32)
    assert float(aux) > 0
    bare = float(llama.loss_fn(params, batch, cfg, aux_weight=0.0,
                               xent_chunk=4, **kw))
    with_aux = float(llama.loss_fn(params, batch, cfg, aux_weight=0.5,
                                   xent_chunk=4, **kw))
    assert with_aux == pytest.approx(bare + 0.5 * float(aux), rel=1e-6)
    full = _llama_grads(params, batch["tokens"], cfg, aux_weight=0.5, **kw)
    chunked = _llama_grads(params, batch["tokens"], cfg, aux_weight=0.5,
                           xent_chunk=4, **kw)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(chunked)):
        assert _max_rel_gap(a, b) < 1e-5


def test_llama_remat_layers_matches_no_remat():
    """remat_layers wraps each block in jax.checkpoint — the long-context
    memory lever; loss and grads must be identical (checkpoint recompute
    is exact)."""
    import jax
    import numpy as np
    from petastorm_tpu.models import llama

    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    f = lambda p, r: llama.loss_fn(p, {"tokens": tokens}, cfg,
                                   aux_weight=0.0, remat_layers=r)
    assert float(f(params, True)) == float(f(params, False))
    g1 = jax.grad(lambda p: f(p, False))(params)
    g2 = jax.grad(lambda p: f(p, True))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
