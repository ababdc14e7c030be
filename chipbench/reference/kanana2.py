"""One expert-parallel rank's share of kanana-2-30b-a3b's decoder layers
(HF ``deepseek_v3`` with ``q_lora_rank: null``;
``configs/kanana2-30b-a3b-ep8-d6.json`` gives the cut and what is assumed).
Float32 and plain ``jax.numpy``: no kernels, no sorting, no buffer. With
``n()`` RMSNorm under its own scale and ``x`` the stream, a layer is

* attention: ``h = n(x)``; ``q = h Wq`` as (s, heads, nope + rope);
  ``h Wkv_a`` as ``[c (kv_lora_rank) | k_rope (rope)]``, ``k_rope`` one
  head for all query heads; ``c' = n_kv(c)``; ``c' Wkv_b`` as (s, heads,
  nope + v) = ``[k_nope | v]``; rotary positions on ``q_rope`` and
  ``k_rope`` alone, pair ``i`` the columns ``(2i, 2i + 1)``
  (``rope_interleave``); ``score = (q_nope . k_nope + q_rope . k_rope) /
  sqrt(nope + rope)``, causal softmax, ``o = P v``; ``x += o Wo``;
* the first ``first_k_dense_replace`` layers: ``x += SwiGLU(n(x))`` of
  ``intermediate_size``;
* the others: ``s = sigmoid(n(x) Wr)`` over ``moe_router_outputs``
  experts, the ``num_experts_per_tok`` selected are the largest of ``s +
  b`` (``b`` the correction bias; one group, so no group limit), weights
  ``w = s[selected] / (sum + 1e-20) * routed_scaling_factor``; ``x +=
  sum_e w_e SwiGLU_e(n(x)) + SwiGLU_shared(n(x))``, the shared experts as
  one of ``n_shared_experts`` times the width,

then the final norm, an untied head and next-token cross-entropy, AdamW on
every weight. The correction bias ``b`` is no weight: no gradient reaches
it, and the update hands it back as it came (no decay, no moments' step).

Departures from the published model, all of the share and none of the
equations: of the routed sum only the experts ``moe_experts_held_first ..
+ n_routed_experts - 1`` are held here and what the others would add is
left out, in the program alike (the partial sum is what goes on to the next
layer); the head and the loss are over this rank's slice of the
vocabulary. The router's arithmetic is float32 whatever the control's
precision: the configuration states the router so.

Memory, not mathematics, shapes the code: one row at a time, attention
four heads at a time and inside them one block of query rows at a time,
the MLPs, the experts and
the head one block of tokens or one expert at a time, each under
``jax.checkpoint``, so that float32 at 16,384 positions fits beside the
optimizer's state. None of it changes a value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.decoder import HI, _cast, _mm, _rmsnorm
from chipbench.reference import smallthinker

HEAD_GROUP = 4          # heads whose attention is one step
QUERY_BLOCK = 256       # rows of one block of a group's attention scores
TOKEN_BLOCK = 2048      # tokens of one block of an MLP and of the head


def init_params(seed_key, sizes: dict):
    """N(0, embed_init_std) embedding, N(0, 0.02) router, a correction
    bias of zeros, N(0, 1/fan_in) matrices, unit norms: the
    configuration's init, drawn key by key in its stated order (per layer
    Wq, Wkv_a, Wkv_b, Wo; then gate, up, down of a dense layer, or one key
    split in four for the router and the held experts' gate, up and down
    and the next split in three for the shared experts')."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    nh, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rot = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    vd, ff = sizes["v_head_dim"], sizes["moe_intermediate_size"]
    held, shared = sizes["n_routed_experts"], sizes["n_shared_experts"] * ff
    layers = sizes["num_hidden_layers"]
    keys = iter(jax.random.split(seed_key, 4 + layers * 8))

    def mat(fan_in, fan_out, key=None):
        return jax.random.normal(next(keys) if key is None else key,
                                 (fan_in, fan_out),
                                 jnp.float32) / np.sqrt(fan_in)

    def stack(key, fan_in, fan_out):
        return jax.random.normal(key, (held, fan_in, fan_out),
                                 jnp.float32) / np.sqrt(fan_in)

    params = {"embed": jax.random.normal(next(keys), (v, d), jnp.float32)
              * sizes["embed_init_std"],
              "lm_head": mat(d, v), "norm_out": jnp.ones((d,)), "layers": []}
    for li in range(layers):
        layer = {"attn_norm": jnp.ones((d,)),
                 "wq": mat(d, nh * (nope + rot)), "wkv_a": mat(d, rank + rot),
                 "kv_norm": jnp.ones((rank,)),
                 "wkv_b": mat(rank, nh * (nope + vd)), "wo": mat(nh * vd, d),
                 "mlp_norm": jnp.ones((d,))}
        if li >= sizes["first_k_dense_replace"]:
            k1, k2, k3, k4 = jax.random.split(next(keys), 4)
            layer["router"] = jax.random.normal(
                k1, (d, sizes["moe_router_outputs"]), jnp.float32) * 0.02
            layer["router_bias"] = jnp.zeros((sizes["moe_router_outputs"],))
            layer["ew1"], layer["ew3"] = stack(k2, d, ff), stack(k3, d, ff)
            layer["ew2"] = stack(k4, ff, d)
            k1, k2, k3 = jax.random.split(next(keys), 3)
            layer["sw1"], layer["sw3"] = mat(d, shared, k1), mat(d, shared, k2)
            layer["sw2"] = mat(shared, d, k3)
        else:
            ffd = sizes["intermediate_size"]
            layer["w1"], layer["w3"] = mat(d, ffd), mat(d, ffd)
            layer["w2"] = mat(ffd, d)
        params["layers"].append(layer)
    return params


def _rope_pairs(x, theta):
    """x: (s, heads, d): pair ``i`` is columns ``(2i, 2i + 1)``, rotated by
    ``position * theta ** (-2i / d)``, positions 0..s-1."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attention(q_nope, q_rope, k_nope, k_rope, v, precision):
    """Causal softmax attention of a group of heads, a block of query rows
    at a time. q_nope, k_nope: (heads, s, nope); q_rope: (heads, s, rope);
    k_rope: (s, rope), the one rotary key; v: (heads, s, v) -> (heads, s,
    v)."""
    heads, s, nope = q_nope.shape
    scale = 1.0 / np.sqrt(nope + q_rope.shape[-1])
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions do not divide into blocks of {block}")
    kpos = jnp.arange(s)
    k_nope, k_rope, v = (_cast(a, precision) for a in (k_nope, k_rope, v))

    def rows(args):
        qn, qr, start = args                          # (heads, block, .)
        scores = (jnp.einsum("hqd,hkd->hqk", _cast(qn, precision), k_nope,
                             precision=HI)
                  + jnp.einsum("hqd,kd->hqk", _cast(qr, precision), k_rope,
                               precision=HI)) * scale
        keep = (start + jnp.arange(block))[:, None] >= kpos[None, :]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", _cast(probs, precision), v,
                          precision=HI)

    def blocks(a):
        return a.reshape(heads, s // block, block, -1).transpose(1, 0, 2, 3)

    out = jax.lax.map(jax.checkpoint(rows), (
        blocks(q_nope), blocks(q_rope), jnp.arange(0, s, block)))
    return out.transpose(1, 0, 2, 3).reshape(heads, s, -1)


def attention_part(layer, x, sizes, precision):
    """``o Wo`` for one row of tokens (s, d): the heads in groups of
    ``HEAD_GROUP``, each group's queries, keys, values and its rows of
    ``Wo`` a step of their own (the latent and the rotary key are every
    group's alike)."""
    nh, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, vd = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    s, d = x.shape
    group = min(HEAD_GROUP, nh)
    if nh % group:
        raise ValueError(f"{nh} heads do not divide into groups of {group}")
    h = _rmsnorm(x, layer["attn_norm"], eps)
    down = _mm(h, layer["wkv_a"], precision)
    latent = _rmsnorm(down[:, :rank], layer["kv_norm"], eps)
    k_rope = _rope_pairs(down[:, None, rank:], theta)[:, 0]

    def one(wq, wkv_b, wo):
        q = _mm(h, wq, precision).reshape(s, group, -1)
        up = _mm(latent, wkv_b, precision).reshape(s, group, nope + vd)
        out = attention(q[..., :nope].transpose(1, 0, 2),
                        _rope_pairs(q[..., nope:], theta).transpose(1, 0, 2),
                        up[..., :nope].transpose(1, 0, 2), k_rope,
                        up[..., nope:].transpose(1, 0, 2), precision)
        return _mm(out.transpose(1, 0, 2).reshape(s, group * vd), wo,
                   precision)

    def body(acc, weights):
        return acc + jax.checkpoint(one)(*weights), None

    def columns(w):         # (rows, heads * width) -> a group's columns
        return w.reshape(w.shape[0], nh // group, -1).transpose(1, 0, 2)

    return jax.lax.scan(body, jnp.zeros_like(x), (
        columns(layer["wq"]), columns(layer["wkv_b"]),
        layer["wo"].reshape(nh // group, group * vd, d)))[0]


def _token_blocks(x):
    block = min(TOKEN_BLOCK, x.shape[0])
    return x.reshape(x.shape[0] // block, block, *x.shape[1:])


def swiglu(h, gate, up, down, precision):
    """``(silu(h Wg) * (h Wu)) Wd``, a block of tokens at a time."""
    def one(hb):
        return _mm(jax.nn.silu(_mm(hb, gate, precision))
                   * _mm(hb, up, precision), down, precision)
    return jax.lax.map(jax.checkpoint(one), _token_blocks(h)).reshape(h.shape)


def route(h, router, bias, sizes):
    """``(weights, ids)`` of each token's selected experts: sigmoid scores
    over all the router's outputs, the selected the largest of score +
    bias, the weights the selected's unbiased scores over their sum, times
    the scaling factor."""
    scores = jax.nn.sigmoid(jnp.dot(h, router, precision=HI))
    _, ids = jax.lax.top_k(scores + bias, sizes["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return (top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
            * sizes["routed_scaling_factor"], ids)


def routed_part(layer, h, sizes, precision):
    """``sum over held e chosen of w_e SwiGLU_e(h)``: a plain loop over the
    held experts, each over every token under the weight of the tokens
    that chose it (nought for the others)."""
    weights, ids = route(h, layer["router"], layer["router_bias"], sizes)
    first = sizes["moe_experts_held_first"]

    def one(ew1, ew3, ew2, w_e):
        return w_e[:, None] * _mm(
            jax.nn.silu(_mm(h, ew1, precision)) * _mm(h, ew3, precision),
            ew2, precision)

    def body(acc, args):
        ew1, ew3, ew2, e = args
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return acc + jax.checkpoint(one)(ew1, ew3, ew2, w_e), None

    held = layer["ew1"].shape[0]
    return jax.lax.scan(body, jnp.zeros_like(h), (
        layer["ew1"], layer["ew3"], layer["ew2"], jnp.arange(held)))[0]


def shared_part(layer, h, precision):
    return swiglu(h, layer["sw1"], layer["sw3"], layer["sw2"], precision)


def _layer(layer, x, sizes, precision):
    x = x + attention_part(layer, x, sizes, precision)
    h = _rmsnorm(x, layer["mlp_norm"], sizes["rms_norm_eps"])
    if "router" not in layer:
        return x + swiglu(h, layer["w1"], layer["w3"], layer["w2"], precision)
    return x + routed_part(layer, h, sizes, precision) \
        + shared_part(layer, h, precision)


def counted_positions(seq: int, positions=None) -> int:
    """Positions of one row that the loss counts: all but the last, which
    has no target, and of them the first ``positions`` (None = all)."""
    return min(seq - 1, seq if positions is None else positions)


def row_nll_sum(params, row, sizes, precision=None, positions=None):
    """Summed next-token negative log-likelihood of one row of tokens over
    this share's slice of the vocabulary, over the counted positions."""
    x = params["embed"][row]
    for layer in params["layers"]:
        x = jax.checkpoint(
            lambda lyr, xx: _layer(lyr, xx, sizes, precision))(layer, x)
    x = _rmsnorm(x, params["norm_out"], sizes["rms_norm_eps"])
    s = x.shape[0]
    targets = jnp.roll(row, -1)
    counted = (jnp.arange(s) < counted_positions(s, positions)).astype(
        jnp.float32)

    def head(xb, tb, wb):
        logits = _mm(xb, params["lm_head"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        target = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(wb * (lse - target))

    def body(acc, args):
        return acc + jax.checkpoint(head)(*args), None

    return jax.lax.scan(body, jnp.zeros(()), (
        _token_blocks(x), _token_blocks(targets), _token_blocks(counted)))[0]


def loss(params, tokens, sizes, precision=None, positions=None):
    """Mean next-token cross-entropy of a (batch, seq) int32 array."""
    total = sum(row_nll_sum(params, row, sizes, precision, positions)
                for row in tokens)
    return total / (tokens.shape[0] * counted_positions(tokens.shape[1],
                                                        positions))


def adamw(params, mu, nu, count, grads, **rates):
    """A step's end: ``smallthinker.adamw`` on the weights -> (params, mu,
    nu, count), each layer's correction bias handed back as it came."""
    new, mu, nu, count = smallthinker.adamw(params, mu, nu, count, grads,
                                            **rates)
    for layer, old in zip(new["layers"], params["layers"]):
        if "router_bias" in old:
            layer["router_bias"] = old["router_bias"]
    return new, mu, nu, count


def row_grads(params, row, scale, sizes, precision=None, positions=None):
    """``(scale x the row's summed loss, its gradient)``. One call a row
    (and a step's AdamW update, ``adamw``, as a call of its own): a whole
    step as one program holds more than one gradient and does not fit
    beside float32 weights and the optimizer's state."""
    return jax.value_and_grad(lambda p: scale * row_nll_sum(
        p, row, sizes, precision, positions))(params)
