"""``models/llama.py``'s block built from (attention kind x FFN kind): the
dropless held-experts layer and window/full attention against the plain
reference of ``chipbench/reference/smallthinker.py``, the share test that
ties one tensor-parallel share to the uncut layer, and the dense path's
numbers held bit for bit through the refactor."""
import hashlib
import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models import llama
from petastorm_tpu.ops.flash_attn import make_flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def toy_sizes(**over):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "smallthinker21b-tp4-d4.json")) as f:
        sizes = json.load(f)
    return {**sizes, **sizes["rehearsal"], **over}


def program_config(sizes):
    from chipbench.pipelines.token_moe_decoder import llama_config
    return llama_config(sizes)


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def assert_close(got, want, tol, what=""):
    scale = float(jnp.abs(want).max()) + 1e-30
    assert float(jnp.abs(got - want).max()) <= tol * scale, what


@pytest.fixture(params=[512, 8], ids=["one_path", "short_path"])
def row_tile(request, monkeypatch):
    """The chip's row tile of 512 rounds the toy shapes' short buffer up to
    every row (one path, no ``cond``); a tile of 8 leaves it 192 of 512
    rows (96 of 256), so the toy sizes really take the short path."""
    monkeypatch.setattr(llama, "_ROW_TILE", request.param)
    return request.param


@pytest.fixture
def short_tile(monkeypatch):
    monkeypatch.setattr(llama, "_ROW_TILE", 8)


def loss_and_grads(sizes, params, tokens, **kw):
    return jax.jit(jax.value_and_grad(partial(
        llama.loss_fn, cfg=program_config(sizes), shift="roll", xent_chunk=64,
        remat_layers=True, compute_dtype=jnp.float32, with_stats=True, **kw),
        has_aux=True))(params, {"tokens": tokens})


@pytest.mark.parametrize("kernels", ["dense", "flash"])
def test_program_matches_the_plain_reference(kernels, row_tile):
    """Loss and every gradient leaf, seeded random weights, toy widths: 4
    layers (full without positions, then three windowed with RoPE), 2 of 8
    experts held, top 2, window 32 in a sequence of 128; through the one
    full buffer, and with every layer on the short one."""
    from chipbench.reference import smallthinker as ref
    sizes = toy_sizes()
    key = jax.random.PRNGKey(3)
    params = llama.init_params(key, program_config(sizes))
    mine, theirs = leaves(params), leaves(ref.init_params(key, sizes))
    assert list(mine) == list(theirs)       # leaf for leaf, in order
    for name in mine:
        np.testing.assert_array_equal(mine[name], theirs[name], name)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 0,
                                sizes["vocab_size"], jnp.int32)
    kw = {} if kernels == "dense" else dict(
        attn_fn=make_flash_attention(block_q=32, block_k=64),
        window_attn_fn=make_flash_attention(window=32, block_q=32,
                                            block_k=64))
    (loss, stats), grads = loss_and_grads(sizes, params, tokens, **kw)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, sizes)))(params)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    want_grads = leaves(want_grads)
    for name, got in leaves(grads).items():
        assert_close(got, want_grads[name], 1e-4, name)
    # The rows of the buffer taken: all 512, or the short one's 192.
    short = row_tile == 8
    assert stats["rows_buffer"].tolist() == [192 if short else 512] * 4
    assert stats["short_buffer"].tolist() == [int(short)] * 4
    assert all(0 < held < 192 for held in stats["rows_held"].tolist())
    assert all(m <= h for m, h in zip(stats["load_max"].tolist(),
                                      stats["rows_held"].tolist()))


def test_every_assignment_to_a_held_expert_is_computed():
    """The dropless test: a router that sends every token's choices to the
    held experts fills the buffer to its last row, and the result is still
    the reference's: nothing was dropped for want of room."""
    from chipbench.reference import smallthinker as ref
    sizes = toy_sizes(num_hidden_layers=1)
    cfg = program_config(sizes)
    layer = llama.init_params(jax.random.PRNGKey(1), cfg)["layers"][0]
    first, held = cfg.experts_held
    # Feature 0 is a large constant and only held experts weigh it.
    layer["router"] = layer["router"].at[0].set(
        jnp.where((jnp.arange(cfg.n_router_outputs) >= first)
                  & (jnp.arange(cfg.n_router_outputs) < first + held),
                  100.0, 0.0))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, cfg.dim))
    x = x.at[..., 0].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 128, cfg.dim))
    out, stats = llama._dropless_moe_block(x, h, layer, cfg)
    assert int(stats["rows_held"]) == int(stats["rows_buffer"]) == 2 * 128 * 2
    for row in range(2):
        weights, ids = ref.route(x[row], layer["router"], cfg.top_k)
        want = ref.experts_part(layer, h[row], weights, ids, sizes, None)
        assert_close(out[row], want, 1e-5)


def test_rows_that_no_group_owns_never_reach_a_result(monkeypatch, row_tile):
    """On the chip XLA's grouped product skips the tiles past the last
    group: those rows of its result, and of its transpose for dx, are
    never written (first chip run, PR 29: every gradient upstream of an
    expert layer read 1e4 to 1e8 times too large). Here they are poisoned,
    and loss-side values and every gradient must not notice: past the held
    rows of the full buffer, and between them and the short buffer's end."""
    from chipbench.reference import smallthinker as ref
    real = jax.lax.ragged_dot

    def poison_tail(x, group_sizes):
        tail = jnp.arange(x.shape[0]) >= group_sizes.sum()
        return jnp.where(tail[:, None], 1e4, x)

    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        return poison_tail(real(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return poisoned(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(residuals, g):
        lhs, rhs, group_sizes = residuals
        tail = jnp.arange(g.shape[0]) >= group_sizes.sum()
        _, pull = jax.vjp(lambda a, b: real(a, b, group_sizes), lhs, rhs)
        dlhs, drhs = pull(jnp.where(tail[:, None], 0, g))
        return poison_tail(dlhs, group_sizes), drhs, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)

    sizes = toy_sizes(num_hidden_layers=1)
    cfg = program_config(sizes)
    layer = llama.init_params(jax.random.PRNGKey(1), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (128, cfg.dim))
    h = jax.random.normal(jax.random.PRNGKey(4), (128, cfg.dim))
    g = jax.random.normal(jax.random.PRNGKey(8), (128, cfg.dim))

    def mine(h, layer):
        out, stats = llama._dropless_moe_block(x[None], h[None], layer, cfg)
        return jnp.sum(out[0] * g), stats

    def theirs(h, layer):
        weights, ids = ref.route(x, layer["router"], cfg.top_k)
        return jnp.sum(ref.experts_part(layer, h, weights, ids, sizes, None)
                       * g)

    (value, stats), grads = jax.value_and_grad(mine, (0, 1), has_aux=True)(
        h, layer)
    assert int(stats["rows_held"]) < int(stats["rows_buffer"])  # a tail
    assert int(stats["rows_buffer"]) == (96 if row_tile == 8 else 256)
    want, want_grads = jax.value_and_grad(theirs, (0, 1))(h, layer)
    assert abs(float(value) - float(want)) <= 1e-5 * abs(float(want))
    assert_close(grads[0], want_grads[0], 1e-5, "dh")
    for name in ("ew1", "ew3", "ew2", "router"):
        assert_close(grads[1][name], want_grads[1][name], 1e-5, name)


def routed(cfg, n_tok: int, rows_held: int):
    """``(route_x, router)`` under which exactly ``rows_held`` of the
    ``n_tok x 2`` assignments go to the two held experts: feature ``e`` of
    a token is large where expert ``e`` is its first or second choice and
    the router reads feature ``e`` into logit ``e``; small noise on both
    keeps the router's gradient a full matrix."""
    first, held = cfg.experts_held
    assert held == 2 and cfg.top_k == 2 and 0 <= rows_held <= 2 * n_tok
    away = [e for e in range(cfg.n_router_outputs)
            if not first <= e < first + held]
    both = max(0, rows_held - n_tok)
    one = rows_held - 2 * both
    choices = np.empty((n_tok, 2), np.int64)
    for t in range(n_tok):
        far = (away[t % len(away)], away[(t + 1) % len(away)])
        if t < both:
            pair = (first + t % 2, first + (t + 1) % 2)
        elif t < both + one:
            pair = (first + t % 2, far[0])[::1 if t % 3 else -1]
        else:
            pair = far
        choices[t] = pair
    x = 0.1 * np.asarray(jax.random.normal(jax.random.PRNGKey(12),
                                           (n_tok, cfg.dim)))
    x[np.arange(n_tok), choices[:, 0]] = 10.0
    x[np.arange(n_tok), choices[:, 1]] = 9.0
    router = 0.01 * np.asarray(jax.random.normal(
        jax.random.PRNGKey(13), (cfg.dim, cfg.n_router_outputs)))
    router[np.arange(cfg.n_router_outputs),
           np.arange(cfg.n_router_outputs)] = 1.0
    return jnp.asarray(x, jnp.float32), jnp.asarray(router, jnp.float32)


@pytest.mark.parametrize("rows_held,short", [
    (100, True),        # under the short buffer's 192 rows
    (192, True),        # the boundary: its last row is held
    (193, False),       # one more: the full buffer, in the same call
    (400, False),
    (512, False),       # every assignment held
], ids=["short", "short_to_its_last_row", "one_row_over", "fallback",
        "all_held"])
def test_either_buffer_gives_the_references_answer(short_tile, rows_held,
                                                    short):
    """The short buffer (192 of the toy shapes' 512 rows) where the held
    rows fit it and the full one where they do not, chosen inside one
    jitted program from the traced count: value, dh, the three expert
    matrices' and the router's gradient are the plain reference's."""
    from chipbench.reference import smallthinker as ref
    sizes = toy_sizes(num_hidden_layers=1)
    cfg = program_config(sizes)
    layer = llama.init_params(jax.random.PRNGKey(1), cfg)["layers"][0]
    x, layer["router"] = routed(cfg, 256, rows_held)
    h = jax.random.normal(jax.random.PRNGKey(4), (256, cfg.dim))
    g = jax.random.normal(jax.random.PRNGKey(8), (256, cfg.dim))

    def mine(h, layer):
        out, stats = llama._dropless_moe_block(
            x.reshape(2, 128, -1), h.reshape(2, 128, -1), layer, cfg)
        return jnp.sum(out.reshape(256, -1) * g), stats

    def theirs(h, layer):
        weights, ids = ref.route(x, layer["router"], cfg.top_k)
        return jnp.sum(ref.experts_part(layer, h, weights, ids, sizes, None)
                       * g)

    (value, stats), grads = jax.jit(jax.value_and_grad(
        mine, (0, 1), has_aux=True))(h, layer)
    assert {k: int(v) for k, v in stats.items() if k != "load_max"} == {
        "rows_held": rows_held, "short_buffer": int(short),
        "rows_buffer": 192 if short else 512}
    want, want_grads = jax.jit(jax.value_and_grad(theirs, (0, 1)))(h, layer)
    assert abs(float(value) - float(want)) <= 1e-5 * abs(float(want))
    assert_close(grads[0], want_grads[0], 1e-5, "dh")
    for name in ("ew1", "ew3", "ew2", "router"):
        assert_close(grads[1][name], want_grads[1][name], 1e-5, name)


def test_a_shard_that_holds_every_expert_keeps_one_path(short_tile):
    """``count == n_router_outputs``: the short size is the full one, so
    nothing is chosen: no ``cond`` is traced and the buffer is ``T x k``.
    (With 2 of 8 held the same call does trace one.)"""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))

    def traced(held):
        cfg = program_config(toy_sizes(num_hidden_layers=1,
                                       moe_num_primary_experts=held))
        assert cfg.experts_held == (0, held)
        layer = llama.init_params(jax.random.PRNGKey(1), cfg)["layers"][0]
        block = partial(llama._dropless_moe_block, cfg=cfg)
        _, stats = block(x, x, layer)
        return str(jax.make_jaxpr(jax.grad(
            lambda h: block(x, h, layer)[0].sum()))(x)), stats

    text, stats = traced(8)
    assert " cond[" not in text and "ragged_dot" in text
    assert (int(stats["rows_buffer"]), int(stats["short_buffer"])) == (512, 0)
    assert int(stats["rows_held"]) == 512
    text, stats = traced(2)
    assert " cond[" in text
    assert (int(stats["rows_buffer"]), int(stats["short_buffer"])) == (192, 1)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: from one input, the four shares' ``a_c Wo_c`` and
    ``y_c`` of one layer (heads 2c, 2c+1 with KV head c; experts 2c, 2c+1
    of 8; the router whole on each) add up to the uncut reference's
    attention and expert outputs."""
    from chipbench.reference import smallthinker as ref
    shares, hd = 4, 8
    whole = toy_sizes(num_hidden_layers=2, num_attention_heads=8,
                      num_key_value_heads=4, moe_num_primary_experts=8,
                      moe_experts_held_first=0)
    full = ref.init_params(jax.random.PRNGKey(9), whole)["layers"][1]
    kind = ref.layer_kinds(whole)[1]        # windowed, with RoPE
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 128, 64))
    h2 = jax.random.normal(jax.random.PRNGKey(7), (1, 128, 64))
    want_attn = ref.attention_part(full, x[0], kind, whole, None)
    weights, ids = ref.route(x[0], full["router"], 2)
    want_experts = ref.experts_part(full, h2[0], weights, ids, whole, None)

    attn_sum, experts_sum = 0.0, 0.0
    for c in range(shares):
        sizes = toy_sizes(num_hidden_layers=2, num_attention_heads=2,
                          moe_experts_held_first=2 * c)
        cfg = program_config(sizes)
        q = slice(2 * hd * c, 2 * hd * (c + 1))
        kv = slice(hd * c, hd * (c + 1))
        e = slice(2 * c, 2 * c + 2)
        layer = {"attn_norm": full["attn_norm"], "mlp_norm": full["mlp_norm"],
                 "router": full["router"], "wq": full["wq"][:, q],
                 "wk": full["wk"][:, kv], "wv": full["wv"][:, kv],
                 "wo": full["wo"][q], "ew1": full["ew1"][e],
                 "ew3": full["ew3"][e], "ew2": full["ew2"][e]}
        # With the down projections nought the block is x + a_c Wo_c.
        silent = {**layer, "ew2": jnp.zeros_like(layer["ew2"])}
        out, _ = llama.apply_block(silent, x, cfg, layer_idx=1)
        attn_sum = attn_sum + (out - x)[0]
        y, _ = llama._dropless_moe_block(x, h2, layer, cfg)
        experts_sum = experts_sum + y[0]
    assert_close(attn_sum, want_attn, 1e-5)
    assert_close(experts_sum, want_experts, 1e-5)


# sha256 over the gradient leaves' bytes and the loss as a hex float, read
# on the commit before the block was rebuilt (04b7841), same construction.
# The flash digest is of the program whose checkpoint keeps the kernel's
# output (PR 32): the backward reads a saved array where it read a
# recomputed one, and XLA's CPU backend then fuses the bf16 arithmetic
# around it otherwise and rounds in other places (in float32 the gradients
# stayed bit-equal; the dense program, which names nothing, did not move).
MISTRAL_TOY = {
    "dense": ("0x1.7d6c0e0000000p+2", "83b2d19b59573ebdcec3e1ef7c8b4a26"
              "043b297a259855c9564642a1928e8b93"),
    "flash": ("0x1.7d6bd40000000p+2", "7cef7d142f1ff124d664e75095e27541"
              "30b6844181bd5ffd72260d34073bb947"),
}


@pytest.mark.parametrize("kernels", ["dense", "flash"])
def test_mistrals_construction_is_bit_equal_through_the_refactor(kernels):
    """``LlamaConfig`` built as ``chipbench/pipelines/token_decoder.py``
    builds it (no ``head_dim``, no layouts, dense MLP) gives the loss and
    the gradients it gave before, to the last bit (default precision, as
    the cell runs)."""
    with jax.default_matmul_precision("default"):
        cfg = llama.LlamaConfig(vocab=256, dim=64, n_layers=2, n_heads=8,
                                n_kv_heads=4, hidden=128,
                                rope_theta=1000000.0, norm_eps=1e-5)
        assert cfg.head_dim == 8 and cfg.attention_kind(1) == (True, None)
        params = llama.init_params(jax.random.PRNGKey(7), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(11), (2, 128), 0, 256,
                                    jnp.int32)
        attn = make_flash_attention(causal=True) if kernels == "flash" \
            else None
        loss, grads = jax.jit(jax.value_and_grad(partial(
            llama.loss_fn, cfg=cfg, attn_fn=attn, shift="roll", xent_chunk=64,
            remat_layers=True)))(params, {"tokens": tokens})
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves(grads):
        digest.update(np.asarray(leaf).tobytes())
    assert (float(loss).hex(), digest.hexdigest()) == MISTRAL_TOY[kernels]


def test_head_dim_is_a_field_with_the_old_default():
    assert llama.LlamaConfig(dim=64, n_heads=8).head_dim == 8
    cfg = llama.LlamaConfig(vocab=64, dim=40, n_layers=1, n_heads=2,
                            n_kv_heads=1, hidden=32, head_dim=16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert params["layers"][0]["wq"].shape == (40, 32)
    assert params["layers"][0]["wo"].shape == (32, 40)
    logits = llama.apply(params, jnp.zeros((1, 8), jnp.int32), cfg)
    assert logits.shape == (1, 8, 64)


@pytest.mark.parametrize("bad", [
    dict(rope_layout=(1, 0, 1)),
    dict(sliding_window_layout=(0, 1)),                  # no window given
    dict(n_router_outputs=8, top_k=2, expert_hidden=16, n_experts=4),
    dict(n_router_outputs=8, top_k=2, expert_hidden=16, experts_held=(6, 4)),
    dict(n_router_outputs=8, top_k=9, expert_hidden=16),
    dict(n_router_outputs=8, top_k=2, expert_hidden=16, expert_act="gelu"),
    dict(n_router_outputs=8, top_k=2, expert_hidden=16, router_input="x"),
])
def test_a_configuration_that_cannot_be_built_is_refused(bad):
    with pytest.raises(ValueError):
        llama.LlamaConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                          n_kv_heads=2, hidden=64, **bad)


def test_attention_kind_follows_the_two_layouts():
    cfg = llama.LlamaConfig(vocab=64, dim=32, n_layers=4, n_heads=4,
                            n_kv_heads=2, hidden=64,
                            rope_layout=(0, 1, 1, 1),
                            sliding_window_layout=[0, 1, 1, 1],
                            sliding_window=16)
    assert [cfg.attention_kind(i) for i in range(4)] == [
        (False, None), (True, 16), (True, 16), (True, 16)]
    hash(cfg)       # static under jit: the layouts are tuples


def test_step_statistics_become_the_registrys_counters(row_tile):
    from petastorm_tpu.telemetry.registry import TelemetryRegistry
    sizes = toy_sizes()
    cfg = program_config(sizes)
    init_opt, step = llama.make_train_step(cfg, shift="roll", xent_chunk=64,
                                           with_stats=True)
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 0, 256,
                                jnp.int32)
    _, _, loss, stats = jax.jit(step)(params, init_opt(params),
                                      {"tokens": tokens})
    assert np.isfinite(float(loss))
    assert {k: v.shape for k, v in stats.items()} == {
        name: (4,) for name in llama.MOE_STATS}
    registry = TelemetryRegistry()
    llama.publish_moe_stats(registry, stats)
    llama.publish_moe_stats(registry, stats)
    counters = registry.metrics_view()["counters"]
    # Two publications of four layers: the rows of the buffer each took,
    # and the layer-steps that took the short one.
    short = row_tile == 8
    assert counters["model.moe.rows_buffer"] == 2 * 4 * (192 if short else 512)
    assert counters["model.moe.short_buffer"] == (2 * 4 if short else 0)
    assert counters["model.moe.rows_held"] == 2 * int(stats["rows_held"].sum())
    assert counters["model.moe.load_max"] == 2 * int(stats["load_max"].sum())
    # A model without the expert layer reports noughts, not an error.
    _, _, _, none = jax.jit(llama.make_train_step(
        llama.TINY, with_stats=True)[1])(
        *(lambda p: (p, llama.make_train_step(llama.TINY)[0](p)))(
            llama.init_params(jax.random.PRNGKey(0), llama.TINY)),
        {"tokens": tokens})
    assert int(none["rows_buffer"].sum()) == 0


# What ``remat_layers`` keeps across a block's checkpoint: (attention kind x
# FFN kind) at toy sizes, two layers, the Pallas kernels interpreted.
@pytest.fixture(params=[(attention, ffn) for attention in ("full", "window")
                        for ffn in ("dense_ffn", "experts")],
                ids="-".join)
def remat_case(request, short_tile):
    """``(loss(params, remat_layers, flash=True) -> scalar, params, the
    kernels' name prefix)`` of one kind of block."""
    attention, ffn = request.param
    windowed = int(attention == "window")
    if ffn == "experts":
        cfg = program_config(toy_sizes(
            num_hidden_layers=2, sliding_window_layout=[windowed] * 2))
    else:
        cfg = llama.LlamaConfig(
            vocab=256, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
            hidden=128, sliding_window_layout=(windowed,) * 2,
            sliding_window=32)
    kernels = dict(
        attn_fn=make_flash_attention(block_q=32, block_k=64),
        window_attn_fn=make_flash_attention(window=32, block_q=32,
                                            block_k=64))
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 0, 256,
                                jnp.int32)

    def loss(p, remat_layers, flash=True):
        return llama.loss_fn(p, {"tokens": tokens}, cfg, shift="roll",
                             xent_chunk=64, compute_dtype=jnp.float32,
                             remat_layers=remat_layers,
                             **(kernels if flash else {}))
    return loss, params, ("swa" if windowed else "flash")


def grad_program(loss, params, **kw) -> str:
    return str(jax.make_jaxpr(jax.grad(partial(loss, **kw)))(params))


def kernel_calls(program: str, name: str) -> int:
    return len(re.findall(rf"\bname={name}\b", program))


def test_remat_launches_each_forward_attention_kernel_once(remat_case,
                                                           monkeypatch):
    """The block's checkpoint keeps what the kernel's forward rule names, so
    the gradient program holds one forward call a layer; the bare checkpoint
    (the program before PR 32) holds a second one for the recomputation."""
    loss, params, kernel = remat_case
    kept = grad_program(loss, params, remat_layers=True)
    assert kernel_calls(kept, f"{kernel}_fwd") == 2
    assert kernel_calls(kept, f"{kernel}_bwd") == 2
    # Without a checkpoint the names are identities: one call a layer.
    assert kernel_calls(grad_program(loss, params, remat_layers=False),
                        f"{kernel}_fwd") == 2
    monkeypatch.setattr(llama, "_SAVE_ATTENTION", None)
    bare = grad_program(loss, params, remat_layers=True)
    assert kernel_calls(bare, f"{kernel}_fwd") == 4
    assert kernel_calls(bare, f"{kernel}_bwd") == 2


def test_remat_with_the_kept_output_gives_the_same_gradients(remat_case):
    loss, params, _ = remat_case
    want, want_grads = jax.jit(jax.value_and_grad(
        partial(loss, remat_layers=False)))(params)
    got, grads = jax.jit(jax.value_and_grad(
        partial(loss, remat_layers=True)))(params)
    assert float(got) == float(want)
    want_grads = leaves(want_grads)
    for name, leaf in leaves(grads).items():
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(want_grads[name]), atol=1e-6,
                                   err_msg=name)


def test_remat_of_dense_attention_is_the_program_it_was(remat_case,
                                                        monkeypatch):
    """Dense attention names nothing, so the policy has nothing to keep:
    the gradient program is the bare checkpoint's, equation for equation."""
    loss, params, _ = remat_case

    def program():
        return re.sub(r"policy=[^\n]*", "", grad_program(
            loss, params, remat_layers=True, flash=False))

    with_policy = program()
    assert "pallas_call" not in with_policy
    monkeypatch.setattr(llama, "_SAVE_ATTENTION", None)
    assert with_policy == program()
