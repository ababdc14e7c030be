"""Latent attention, a leading dense layer, shared experts and the
sigmoid router through ``models/llama.py`` against the float32 reference of
``chipbench/reference/kanana2.py`` on seeded weights, at toy sizes on the
CPU: loss and every leaf's gradient, the shares of the expert-parallel cut
adding up to the uncut layer, and what a wrong score scale, a missing
latent norm or a bias that weighs would read."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import kanana2 as ref
from petastorm_tpu.models import llama
from petastorm_tpu.ops.flash_attn import make_flash_attention


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def toy_sizes(**over):
    sizes = dict(
        hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=96, moe_intermediate_size=16, n_routed_experts=4,
        moe_router_outputs=8, moe_experts_held_first=0, num_experts_per_tok=3,
        n_shared_experts=2, first_k_dense_replace=1,
        routed_scaling_factor=2.448, rope_theta=1e6, rms_norm_eps=1e-6,
        vocab_size=128, num_hidden_layers=3, embed_init_std=1.0)
    return {**sizes, **over}


def program_config(sizes):
    """As ``chipbench/pipelines/token_mla_moe_decoder.py`` builds it."""
    from chipbench.pipelines.token_mla_moe_decoder import llama_config
    published = dict(qk_head_dim=sizes["qk_nope_head_dim"]
                     + sizes["qk_rope_head_dim"], q_lora_rank=None,
                     rope_scaling=None, n_group=1, topk_group=1,
                     moe_layer_freq=1, scoring_func="sigmoid",
                     norm_topk_prob=True, hidden_act="silu",
                     rope_interleave=True)
    return llama_config({**published, **sizes})


def with_bias(params, scale=0.05):
    """A correction bias that is not nought: of the size of the gaps
    between neighbouring scores, so that it changes who is selected."""
    for n, layer in enumerate(params["layers"]):
        if "router_bias" in layer:
            layer["router_bias"] = scale * jax.random.normal(
                jax.random.PRNGKey(90 + n), layer["router_bias"].shape)
    return params


def assert_close(got, want, tol):
    scale = float(jnp.abs(want).max()) or 1.0
    assert float(jnp.abs(got - want).max()) <= tol * scale


def test_init_is_the_references_leaf_for_leaf():
    sizes = toy_sizes()
    cfg = program_config(sizes)
    got = llama.init_params(jax.random.PRNGKey(3), cfg)
    want = ref.init_params(jax.random.PRNGKey(3), sizes)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    # The dense layer first, then the expert layers with their shared ones.
    assert "w1" in got["layers"][0] and "router" not in got["layers"][0]
    assert {"router", "router_bias", "ew1", "sw1"} <= set(got["layers"][1])
    assert got["layers"][1]["wq"].shape == (64, 4 * 12)
    assert got["layers"][1]["wkv_a"].shape == (64, 16 + 4)
    assert got["layers"][1]["wkv_b"].shape == (16, 4 * 16)
    assert got["layers"][1]["wo"].shape == (4 * 8, 64)
    assert got["layers"][1]["sw1"].shape == (64, 32)
    specs = llama._param_pspec_tuples(cfg, "model")
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree.structure(got)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_loss_and_every_gradient_against_the_reference(attention):
    """Float32 compute, a correction bias that is not nought, two rows of
    64 tokens; the flash kernels interpreted at 32 x 32 tiles."""
    sizes = toy_sizes()
    cfg = program_config(sizes)
    params = with_bias(llama.init_params(jax.random.PRNGKey(3), cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    attn_fn = None if attention == "dense" else make_flash_attention(
        causal=True, block_q=32, block_k=32)
    loss, grads = jax.value_and_grad(lambda p: llama.loss_fn(
        p, {"tokens": tokens}, cfg, shift="roll", attn_fn=attn_fn,
        compute_dtype=jnp.float32, xent_chunk=32, remat_layers=True))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, sizes))(params)
    assert abs(float(loss) - float(want)) <= 2e-6 * float(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            assert not np.any(np.asarray(g)), name   # no gradient reaches it
            continue
        assert float(jnp.linalg.norm(g - w)) <= 2e-5 * float(
            jnp.linalg.norm(w)), name


@pytest.mark.parametrize("bias", ["zeros", "from_a_checkpoint"])
def test_the_step_hands_the_bias_back_as_it_came_and_counts_its_rows(bias):
    """The correction bias is outside the optimizer: no moments, no weight
    decay (AdamW's would shrink one that is not nought by lr * 0.1 a
    step), so two steps return it bit-equal."""
    sizes = toy_sizes()
    cfg = program_config(sizes)
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    if bias == "from_a_checkpoint":
        params = with_bias(params)
    init_opt, step = llama.make_train_step(
        cfg, learning_rate=1e-3, shift="roll", xent_chunk=32,
        remat_layers=True, with_stats=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    opt_state = init_opt(params)
    moments = [jax.tree_util.keystr(path) for path, _ in
               jax.tree_util.tree_flatten_with_path(opt_state)[0]]
    assert any("['router']" in m for m in moments)
    assert not any("['router_bias']" in m for m in moments)
    new, opt_state, loss, stats = jax.jit(step)(params, opt_state,
                                                {"tokens": tokens})
    new, _, loss, stats = jax.jit(step)(new, opt_state, {"tokens": tokens})
    assert np.isfinite(float(loss))
    for before, after in zip(params["layers"][1:], new["layers"][1:]):
        assert np.any(np.asarray(before["router_bias"])) == (bias != "zeros")
        np.testing.assert_array_equal(
            np.asarray(after["router_bias"]).view(np.uint32),
            np.asarray(before["router_bias"]).view(np.uint32))
        assert np.any(np.asarray(after["router"] != before["router"]))
    # The dense layer reports nothing; each expert layer its rows of the
    # 128 x 3 assignments (4 of 8 experts held).
    assert stats["rows_held"].shape == (3,)
    assert int(stats["rows_held"][0]) == 0 == int(stats["rows_buffer"][0])
    assert all(0 < int(r) <= 384 for r in stats["rows_held"][1:])
    assert all(int(m) <= int(r) for m, r in zip(stats["load_max"][1:],
                                                stats["rows_held"][1:]))


def test_the_references_step_hands_the_bias_back_too():
    sizes = toy_sizes()
    params = with_bias(ref.init_params(jax.random.PRNGKey(3), sizes))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    grads = jax.grad(lambda p: ref.loss(p, tokens, sizes))(params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    new, mu, _, count = ref.adamw(params, zeros, zeros, jnp.zeros((), int),
                                  grads, learning_rate=1e-3, weight_decay=0.1)
    assert int(count) == 1
    for before, after in zip(params["layers"][1:], new["layers"][1:]):
        np.testing.assert_array_equal(after["router_bias"],
                                      before["router_bias"])
        assert np.any(np.asarray(after["router"] != before["router"]))
    # Every weight decays and steps as the other decoders' reference has it.
    lr, g, p = 1e-3, grads["norm_out"], params["norm_out"]
    np.testing.assert_allclose(
        new["norm_out"], p - lr * (g / (jnp.abs(g) + 1e-8) + 0.1 * p),
        rtol=1e-6)


def one_layer(sizes, seed=9):
    """An expert layer's leaves, its input and its normed FFN input."""
    layer = with_bias(ref.init_params(jax.random.PRNGKey(seed), sizes))[
        "layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, 64))
    return layer, x


def test_the_bias_selects_and_does_not_weigh():
    """``s + b`` picks the experts, ``s`` weighs them: the program agrees
    with the reference, a bias of nought selects others, and a bias that
    also weighed would read far off."""
    sizes = toy_sizes(n_routed_experts=8)       # all held: the whole sum
    cfg = program_config(sizes)
    layer, x = one_layer(sizes)
    got, stats = llama._dropless_moe_block(x, x, layer, cfg)
    assert int(stats["rows_held"]) == 64 * 3
    assert_close(got[0], ref.routed_part(layer, x[0], sizes, None), 1e-5)

    weights, ids = ref.route(x[0], layer["router"], layer["router_bias"],
                             sizes)
    np.testing.assert_allclose(weights.sum(-1), 2.448, rtol=1e-6)
    _, plain_ids = ref.route(x[0], layer["router"], 0.0, sizes)
    assert np.any(np.sort(ids, -1) != np.sort(plain_ids, -1))

    unbiased = {**layer, "router_bias": jnp.zeros_like(layer["router_bias"])}
    other, _ = llama._dropless_moe_block(x, x, unbiased, cfg)
    assert float(jnp.abs(other - got).max()) > 1e-2 * float(
        jnp.abs(got).max())
    # Weights from the biased scores: the same experts, another sum.
    scores = jax.nn.sigmoid(x[0] @ layer["router"]) + layer["router_bias"]
    top = jnp.take_along_axis(scores, ids, axis=-1)
    wrong = top / top.sum(-1, keepdims=True) * 2.448
    assert float(jnp.abs(wrong - weights).max()) > 1e-2


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test: from one input, the routed parts of the eight
    shares (experts 2c, 2c + 1 of 16; the router, its bias, attention and
    the shared experts whole on each) plus attention and the shared
    experts counted once add up to the uncut reference's layer."""
    shares = 8
    whole = toy_sizes(n_routed_experts=16, moe_router_outputs=16,
                      num_hidden_layers=2)
    full, x = one_layer(whole)
    want = ref._layer(full, x[0], whole, None)
    alike = x[0] + ref.attention_part(full, x[0], whole, None)
    h = ref._rmsnorm(alike, full["mlp_norm"], whole["rms_norm_eps"])
    alike = alike + ref.shared_part(full, h, None)

    routed = 0.0
    for c in range(shares):
        sizes = {**whole, "n_routed_experts": 2,
                 "moe_experts_held_first": 2 * c}
        cfg = program_config(sizes)
        assert cfg.experts_held == (2 * c, 2) and cfg.n_router_outputs == 16
        e = slice(2 * c, 2 * c + 2)
        layer = {**full, "ew1": full["ew1"][e], "ew3": full["ew3"][e],
                 "ew2": full["ew2"][e]}
        out, _ = llama.apply_block(layer, x, cfg, layer_idx=1)
        # Each share computes attention and the shared experts alike.
        routed = routed + (out[0] - alike)
        assert_close(out[0], ref._layer(layer, x[0], sizes, None), 1e-5)
    assert float(jnp.abs(routed).max()) > 0.1
    assert_close(alike + routed, want, 1e-5)


def test_a_wrong_scale_or_a_missing_latent_norm_would_show(monkeypatch):
    """The attention branch against the reference, and against the
    reference with the scores scaled by 1 / sqrt(nope) or the latent left
    unnormed: the program is the first and far from the others."""
    sizes = toy_sizes()
    cfg = program_config(sizes)
    layer, x = one_layer(sizes)
    # Make the latent's scale matter: a norm that is not one.
    layer = {**layer, "kv_norm": 1.0 + 0.5 * jax.random.normal(
        jax.random.PRNGKey(4), layer["kv_norm"].shape)}
    (q_nope, q_rope), (k_nope, k_rope), v = llama._latent_qkv(
        layer, llama._rmsnorm(x, layer["attn_norm"], cfg.norm_eps), cfg, True)
    assert q_nope.shape == k_nope.shape == v.shape == (1, 64, 4, 8)
    assert q_rope.shape == (1, 64, 4, 4)
    # One rotary key for every head: one head, never repeated.
    assert k_rope.shape == (1, 64, 1, 4)

    silent = {**layer, "ew2": jnp.zeros_like(layer["ew2"]),
              "sw2": jnp.zeros_like(layer["sw2"])}
    got = (llama.apply_block(silent, x, cfg, layer_idx=1)[0] - x)[0]
    want = ref.attention_part(layer, x[0], sizes, None)
    assert_close(got, want, 1e-5)

    narrow = {**layer, "wq": layer["wq"] * np.sqrt(12 / 8)}
    off = ref.attention_part(narrow, x[0], sizes, None)
    assert float(jnp.abs(off - want).max()) > 1e-2 * float(
        jnp.abs(want).max())

    plain = ref._rmsnorm
    monkeypatch.setattr(ref, "_rmsnorm", lambda x, scale, eps: (
        x if scale.shape == (sizes["kv_lora_rank"],)
        else plain(x, scale, eps)))
    unnormed = ref.attention_part(layer, x[0], sizes, None)
    assert float(jnp.abs(unnormed - want).max()) > 1e-2 * float(
        jnp.abs(want).max())


def test_adjacent_pairs_rotate_to_the_same_scores():
    """``rope_interleave``: the program de-interleaves and rotates halves,
    the reference rotates the pairs in place; every product of a rotated
    query with a rotated key is the same."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 3, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 3, 8))
    got = jnp.einsum("bqhd,bkhd->bhqk",
                     llama._rope(llama._pairs_to_halves(x), 1e4),
                     llama._rope(llama._pairs_to_halves(y), 1e4))
    want = jnp.einsum("qhd,khd->hqk", ref._rope_pairs(x[0], 1e4),
                      ref._rope_pairs(y[0], 1e4))
    assert_close(got[0], want, 1e-5)
    halves = jnp.einsum("bqhd,bkhd->bhqk", llama._rope(x, 1e4),
                        llama._rope(y, 1e4))
    assert float(jnp.abs(halves[0] - want).max()) > 1e-2


@pytest.mark.parametrize("over,match", [
    (dict(attention="mla"), "latent attention needs"),
    (dict(attention="mla", kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=3,
          v_dim=8), "even qk_rope_dim"),
    (dict(n_dense_layers=1), "belong to the dropless"),
    (dict(n_shared_experts=2), "belong to the dropless"),
    (dict(n_router_outputs=8, expert_hidden=16, n_dense_layers=2),
     "n_dense_layers"),
    (dict(n_router_outputs=8, expert_hidden=16, router_score="softmax"),
     "unknown router_score"),
])
def test_config_refuses_what_it_cannot_build(over, match):
    with pytest.raises(ValueError, match=match):
        llama.LlamaConfig(vocab=128, dim=64, n_layers=2, n_heads=4,
                          n_kv_heads=4, hidden=96, **over)


def test_the_new_scopes_name_their_parts():
    """``petastorm_tpu.mla_latent`` and ``petastorm_tpu.moe_shared`` reach
    the lowered program's locations, beside the scopes that were there."""
    sizes = toy_sizes(num_hidden_layers=2)
    cfg = program_config(sizes)
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.zeros((1, 64), jnp.int32)
    text = jax.jit(partial(llama.apply, cfg=cfg)).lower(
        params, tokens).as_text(debug_info=True)
    for scope in ("mla_latent", "attn_full", "moe_route", "moe_experts",
                  "moe_shared"):
        assert f"petastorm_tpu.{scope}" in text, scope


# --- Latent attention's operands in the kernels' split form.

def joined_latent_qkv(layer, h, cfg, rope):
    """The latent path as it was before the split form: q and k of
    qk_nope_dim + qk_rope_dim columns a head, sliced from and joined back
    into one product, the rotary key repeated over the heads, adjacent
    pairs put in halves order on the product, not on the weights."""
    b, s, _ = h.shape
    nh, nope, rot = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = (h @ layer["wq"].astype(h.dtype)).reshape(b, s, nh, nope + rot)
    down = h @ layer["wkv_a"].astype(h.dtype)
    latent = llama._rmsnorm(down[..., :cfg.kv_lora_rank], layer["kv_norm"],
                            cfg.norm_eps, cfg.norm_unit_offset)
    up = (latent @ layer["wkv_b"].astype(h.dtype)).reshape(
        b, s, nh, nope + cfg.v_dim)
    q_rot = q[..., nope:]
    k_rot = down[..., cfg.kv_lora_rank:].reshape(b, s, 1, rot)
    if rope:
        q_rot = llama._rope(llama._pairs_to_halves(q_rot), cfg.rope_theta)
        k_rot = llama._rope(llama._pairs_to_halves(k_rot), cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rot, (b, s, nh, rot))], axis=-1)
    return q, k, up[..., nope:]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_weight_side_deinterleave_rotates_the_pairs_bit_for_bit(dtype):
    """The rotary columns of ``wq`` and ``wkv_a`` put in halves order and
    rotated as halves are, column for column and bit for bit, the sliced
    products de-interleaved and rotated as halves: a column gather of a
    product's weights is the same gather of the product."""
    sizes = toy_sizes()
    cfg = program_config(sizes)
    assert cfg.rope_interleave
    layer, x = one_layer(sizes)
    layer = jax.tree.map(lambda a: a.astype(dtype), layer)
    h = x.astype(dtype)
    (_, q_rope), (_, k_rope), _ = llama._latent_qkv(layer, h, cfg, True)
    q_want, k_want, _ = joined_latent_qkv(layer, h, cfg, True)
    assert q_rope.dtype == dtype
    np.testing.assert_array_equal(q_rope, q_want[..., 8:])
    np.testing.assert_array_equal(k_rope, k_want[:, :, :1, 8:])
    # Unrotated layers hand over the columns in halves order: the scores
    # do not see the order of the pairs.
    (_, q_rope), (_, k_rope), _ = llama._latent_qkv(layer, h, cfg, False)
    q_want, k_want, _ = joined_latent_qkv(layer, h, cfg, False)
    np.testing.assert_array_equal(
        q_rope, llama._pairs_to_halves(q_want[..., 8:]))
    np.testing.assert_array_equal(
        k_rope, llama._pairs_to_halves(k_want[:, :, :1, 8:]))


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_a_latent_layer_is_the_joined_forms(attention, monkeypatch):
    """One expert layer's output and every parameter's gradient through
    the split operands against the same layer with the joined operands,
    through ``dense_attention`` and through the interpreted kernels."""
    sizes = toy_sizes()
    cfg = program_config(sizes)
    layer, x = one_layer(sizes)
    attn_fn = None if attention == "dense" else make_flash_attention(
        causal=True, block_q=32, block_k=32)

    def run():
        def out(layer):
            y, _ = llama.apply_block(layer, x, cfg, attn_fn=attn_fn,
                                     layer_idx=1)
            return y
        y, pull = jax.vjp(out, layer)
        return y, pull(jnp.cos(y))[0]

    got, got_grads = run()
    monkeypatch.setattr(llama, "_latent_qkv", joined_latent_qkv)
    want, want_grads = run()
    assert_close(got, want, 1e-5)
    for name in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "attn_norm"):
        assert float(jnp.linalg.norm(got_grads[name] - want_grads[name])) \
            <= 1e-5 * float(jnp.linalg.norm(want_grads[name])), name


def test_the_latent_step_holds_no_joined_head_and_no_repeated_rotary_key():
    """The lowered train step (kernels interpreted) holds no array of
    qk_nope_dim + qk_rope_dim columns over the positions, and no broadcast
    of the one rotary key over the heads; the joined form held both."""
    import re
    sizes = toy_sizes(hidden_size=80, num_hidden_layers=2)
    cfg = program_config(sizes)
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.zeros((1, 64), jnp.int32)
    attn_fn = make_flash_attention(causal=True, block_q=32, block_k=32)

    def lowered():
        return jax.jit(jax.grad(lambda p: llama.loss_fn(
            p, {"tokens": tokens}, cfg, shift="roll", attn_fn=attn_fn,
            xent_chunk=32, remat_layers=True))).lower(params).as_text()

    def joined_heads(text):     # (.., 64 positions, .., 12 columns)
        return re.findall(r"tensor<(?:\d+x)*64x(?:\d+x)*12x(?:f32|bf16)>",
                          text)

    def repeated_key(text):     # (1, 64, 1, 4) -> (1, 64, 4, 4)
        return re.findall(r"broadcast_in_dim[^\n]*tensor<1x64x1x4x\w+>\)"
                          r" -> tensor<1x64x4x4x", text)

    text = lowered()
    assert joined_heads(text) == [] and repeated_key(text) == []
    kernels = str(jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(
        p, {"tokens": tokens}, cfg, shift="roll", attn_fn=attn_fn,
        xent_chunk=32, remat_layers=True)))(params))
    assert "name=flash_fwd" in kernels and "name=flash_bwd" in kernels
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(llama, "_latent_qkv", joined_latent_qkv)
        text = lowered()
    assert joined_heads(text) and repeated_key(text)


# sha256 of the jaxpr text (object addresses dropped) of the toy train
# step's gradient of the dense, sparse and EVA cells' constructions, read
# on the commit before the split form was added (e89fb74): the latent path
# is the only one the split form changes.
OTHER_CELLS = {
    "dense": "ea9aa52a26ac2650060468c13d13a5b3454355f0840183aed4261bd6c8f673da",
    "sparse": "d9d290c35057b91b3d38a0dfecb7289bfc68b00616b5524a1237e3a8571d9b77",
    "eva": "709d72a88bbc9872a05130456f2fbbad68b0cb22f764747f47a78af1b7310053",
}


def other_cells_toy_step(cell: str) -> str:
    """The jaxpr of the toy train step's gradient of ``cell`` as its
    pipeline builds the configuration (the rehearsal sizes of
    ``chipbench/configs``) and its attention callables (kernels
    interpreted), object addresses dropped."""
    import json
    import os
    import re
    from petastorm_tpu.ops.eva_attn import make_eva_attention

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def sizes(name):
        with open(os.path.join(root, "chipbench", "configs",
                               f"{name}.json")) as f:
            c = json.load(f)
        return {**c, **c["rehearsal"]}

    if cell == "dense":
        c = sizes("mistral7b-v03-d2")
        cfg = llama.LlamaConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            hidden=c["intermediate_size"], rope_theta=c["rope_theta"],
            norm_eps=c["rms_norm_eps"])
        kw = dict(attn_fn=make_flash_attention(block_q=32, block_k=64))
    elif cell == "sparse":
        from chipbench.pipelines.token_moe_decoder import llama_config
        cfg = llama_config(sizes("smallthinker21b-tp4-d4"))
        kw = dict(attn_fn=make_flash_attention(block_q=32, block_k=64),
                  window_attn_fn=make_flash_attention(
                      window=cfg.sliding_window, block_q=32, block_k=64))
    else:
        from chipbench.pipelines.byte_eva_decoder import llama_config
        cfg = llama_config(sizes("evabyte-6.5b-d4"))
        kw = dict(eva_attn_fn=make_eva_attention(cfg.eva_window,
                                                 cfg.eva_chunk))
    params = jax.eval_shape(lambda key: llama.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    loss = partial(llama.loss_fn, cfg=cfg, shift="roll", xent_chunk=64,
                   remat_layers=True, **kw)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, t: loss(p, {"tokens": t})))(params, tokens))
    return re.sub(r" at 0x[0-9a-f]+", "", text)


@pytest.mark.parametrize("cell", sorted(OTHER_CELLS))
def test_the_other_cells_toy_steps_are_what_they_were(cell):
    import hashlib
    with jax.default_matmul_precision(None):    # as the cells run
        text = other_cells_toy_step(cell)
    assert hashlib.sha256(text.encode()).hexdigest() == OTHER_CELLS[cell]
