"""One tensor-parallel share of SmallThinker-21BA3B's decoder layers
(``configs/smallthinker21b-tp4-d4.json`` gives the equations and the cut):
pre-norm blocks whose router reads the layer's input before attention and
softmaxes the six selected of 64 logits, grouped-query attention that is
full and position-free in every fourth layer and windowed with rotary
positions in the others, ReGLU experts of which this share holds a run, an
untied head over this share's slice of the vocabulary, next-token
cross-entropy and AdamW. Float32 and plain ``jax.numpy``: no kernels, no
sorting, no buffer.

What the absent shares would add (their heads' rows of ``Wo``, their
experts) is left out here as in the program: the partial sums are what
goes on to the next layer.

Memory, not mathematics, shapes the code: rows of the batch go through one
at a time, attention one block of query rows at a time, the experts one at
a time and the head one block of tokens at a time, each under
``jax.checkpoint``, so that float32 at 16,384 positions fits beside the
optimizer's state. None of it changes a value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.decoder import HI, _cast, _mm, _rmsnorm, _rope

QUERY_BLOCK = 256       # rows of one block of attention scores
HEAD_BLOCK = 512        # tokens of one block of logits


def layer_kinds(sizes: dict) -> list:
    """``[(rope, window or None)]`` of the layers that are kept: the
    source's two layouts, read from their start."""
    return [(bool(rope), sizes["sliding_window_size"] if windowed else None)
            for rope, windowed in zip(
                sizes["rope_layout"][:sizes["num_hidden_layers"]],
                sizes["sliding_window_layout"])]


def init_params(seed_key, sizes: dict):
    """N(0, embed_init_std) embedding, N(0, 0.02) router, N(0, 1/fan_in)
    matrices, unit norms: the configuration's init, drawn key by key in
    its stated order (per layer q, k, v, o, then one key split in four for
    the router and the held experts' gate, up and down)."""
    d, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    ff, held = sizes["moe_ffn_hidden_size"], sizes["moe_num_primary_experts"]
    layers = sizes["num_hidden_layers"]
    keys = iter(jax.random.split(seed_key, 4 + layers * 8))

    def mat(fan_in, fan_out):
        return jax.random.normal(next(keys), (fan_in, fan_out),
                                 jnp.float32) / np.sqrt(fan_in)

    def stack(key, fan_in, fan_out):
        return jax.random.normal(key, (held, fan_in, fan_out),
                                 jnp.float32) / np.sqrt(fan_in)

    params = {"embed": jax.random.normal(next(keys), (v, d), jnp.float32)
              * sizes["embed_init_std"],
              "lm_head": mat(d, v), "norm_out": jnp.ones((d,)), "layers": []}
    for _ in range(layers):
        layer = {"attn_norm": jnp.ones((d,)),
                 "wq": mat(d, nh * hd), "wk": mat(d, nkv * hd),
                 "wv": mat(d, nkv * hd), "wo": mat(nh * hd, d),
                 "mlp_norm": jnp.ones((d,))}
        k1, k2, k3, k4 = jax.random.split(next(keys), 4)
        layer["router"] = jax.random.normal(
            k1, (d, sizes["moe_router_outputs"]), jnp.float32) * 0.02
        layer["ew1"], layer["ew3"] = stack(k2, d, ff), stack(k3, d, ff)
        layer["ew2"] = stack(k4, ff, d)
        params["layers"].append(layer)
    return params


def route(x, router, top_k: int):
    """The six selected experts of each token and their weights: softmax
    over the selected logits, so the weights sum to 1. Float32 whatever
    the control's precision: the configuration states the router so."""
    logits = jnp.dot(x, router, precision=HI)
    top, ids = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), ids


def attention(q, k, v, window, precision):
    """Softmax attention of the query heads that share one key/value head
    under the mask ``j <= i`` and, with a window, ``i - j < window``, a
    block of query rows at a time. q: (rep, s, hd); k, v: (s, hd)."""
    s, hd = k.shape
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions do not divide into blocks of {block}")
    kpos = jnp.arange(s)

    def rows(args):
        qb, start = args                                  # (rep, block, hd)
        scores = jnp.einsum("rqd,kd->rqk", _cast(qb, precision),
                            _cast(k, precision), precision=HI) / np.sqrt(hd)
        behind = (start + jnp.arange(block))[:, None] - kpos[None, :]
        keep = behind >= 0
        if window is not None:
            keep &= behind < window
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->rqd", _cast(probs, precision),
                          _cast(v, precision), precision=HI)

    blocks = q.reshape(q.shape[0], s // block, block, hd).transpose(1, 0, 2, 3)
    out = jax.lax.map(jax.checkpoint(rows),
                      (blocks, jnp.arange(0, s, block)))
    return out.transpose(1, 0, 2, 3).reshape(q.shape)


def attention_part(layer, x, kind, sizes, precision):
    """``a Wo`` of the heads held here, for one row of tokens (s, d)."""
    rope, window = kind
    hd = sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    s = x.shape[0]
    h = _rmsnorm(x, layer["attn_norm"], sizes["rms_norm_eps"])
    q = _mm(h, layer["wq"], precision).reshape(s, nh, hd)
    k = _mm(h, layer["wk"], precision).reshape(s, nkv, hd)
    v = _mm(h, layer["wv"], precision).reshape(s, nkv, hd)
    if rope:
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    q = q.reshape(s, nkv, nh // nkv, hd).transpose(1, 2, 0, 3)
    out = jnp.stack([attention(q[g], k[:, g], v[:, g], window, precision)
                     for g in range(nkv)])
    out = out.transpose(2, 0, 1, 3).reshape(s, nh * hd)
    return _mm(out, layer["wo"], precision)


def experts_part(layer, h, weights, ids, sizes, precision):
    """``sum over held e chosen of w_e (relu(h Wg_e) * (h Wu_e)) Wd_e``:
    a plain loop over the held experts, each over every token under the
    mask of the tokens that chose it."""
    first = sizes["moe_experts_held_first"]

    def one(ew1, ew3, ew2, w_e):
        gate = jax.nn.relu(_mm(h, ew1, precision))
        return w_e[:, None] * _mm(gate * _mm(h, ew3, precision), ew2,
                                  precision)

    def body(acc, args):
        ew1, ew3, ew2, e = args
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return acc + jax.checkpoint(one)(ew1, ew3, ew2, w_e), None

    held = layer["ew1"].shape[0]
    return jax.lax.scan(body, jnp.zeros_like(h), (
        layer["ew1"], layer["ew3"], layer["ew2"], jnp.arange(held)))[0]


def _layer(layer, x, kind, sizes, precision):
    weights, ids = route(x, layer["router"],
                         sizes["moe_num_active_primary_experts"])
    x = x + attention_part(layer, x, kind, sizes, precision)
    h = _rmsnorm(x, layer["mlp_norm"], sizes["rms_norm_eps"])
    return x + experts_part(layer, h, weights, ids, sizes, precision)


def _row_nll_sum(params, row, sizes, precision):
    """Summed next-token negative log-likelihood of one row of tokens over
    this share's slice of the vocabulary; the last position has no target
    and is left out."""
    x = params["embed"][row]
    for layer, kind in zip(params["layers"], layer_kinds(sizes)):
        x = jax.checkpoint(
            lambda lyr, xx, kind=kind: _layer(lyr, xx, kind, sizes,
                                              precision))(layer, x)
    x = _rmsnorm(x, params["norm_out"], sizes["rms_norm_eps"])
    s = x.shape[0]
    block = min(HEAD_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions do not divide into blocks of {block}")
    targets = jnp.roll(row, -1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)

    def head(xb, tb, wb):
        logits = _mm(xb, params["lm_head"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        target = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(wb * (lse - target))

    def body(acc, args):
        return acc + jax.checkpoint(head)(*args), None

    return jax.lax.scan(body, jnp.zeros(()), (
        x.reshape(s // block, block, -1), targets.reshape(-1, block),
        counted.reshape(-1, block)))[0]


def loss(params, tokens, sizes, precision=None):
    """Mean next-token cross-entropy of a (batch, seq) int32 array."""
    b, s = tokens.shape
    row = jax.checkpoint(
        lambda p, r: _row_nll_sum(p, r, sizes, precision))
    total = jax.lax.scan(lambda acc, r: (acc + row(params, r), None),
                         jnp.zeros(()), tokens)[0]
    return total / (b * (s - 1))


def add_row_grads(params, acc, row, scale, sizes, precision=None):
    """``(scale x the row's summed loss, acc + its gradient)``. One call a
    row, ``acc`` donated: two gradients side by side (or the gradient of a
    scan over rows, which holds the sum several times over) do not fit
    beside float32 weights and the optimizer's state."""
    value, grads = jax.value_and_grad(
        lambda p: scale * _row_nll_sum(p, row, sizes, precision))(params)
    return value, jax.tree.map(jnp.add, acc, grads)


def adamw(params, mu, nu, count, grads, *, learning_rate, weight_decay,
          b1=0.9, b2=0.999, eps=1e-8):
    """One AdamW update, written as ``reference/decoder.py`` writes it
    (decoupled decay on every leaf) -> (params, mu, nu, count). A step is
    ``add_row_grads`` over the batch's rows, then this."""
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, n: p - learning_rate * (
            (m / c1) / (jnp.sqrt(n / c2) + eps) + weight_decay * p),
        params, mu, nu)
    return params, mu, nu, count
