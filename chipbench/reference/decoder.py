"""Pre-norm decoder-only transformer (RMSNorm, rotary positions, grouped-query
causal attention, SwiGLU, untied output head: the equations of
Mistral-7B's ``modeling`` and of the Llama paper, arXiv:2302.13971 section
2) with next-token cross-entropy and AdamW, as the configuration states
them.

Memory, not mathematics, shapes the code: rows of the batch go through one
at a time and attention one group of heads at a time, under
``jax.checkpoint``, so that float32 at 4096 x 4096 fits beside the
optimizer's state. Neither changes a value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _cast(x, precision):
    return x if precision is None else x.astype(precision).astype(jnp.float32)


def _mm(a, b, precision):
    return jnp.dot(_cast(a, precision), _cast(b, precision), precision=HI)


def init_params(seed_key, sizes: dict):
    """N(0, 0.02) embedding, N(0, 1/fan_in) matrices, unit norms: the
    configuration's init, drawn key by key in its stated order."""
    d, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    ff, layers = sizes["intermediate_size"], sizes["num_hidden_layers"]
    keys = iter(jax.random.split(seed_key, 4 + layers * 8))

    def mat(fan_in, fan_out):
        return jax.random.normal(next(keys), (fan_in, fan_out),
                                 jnp.float32) / np.sqrt(fan_in)

    params = {"embed": jax.random.normal(next(keys), (v, d), jnp.float32)
              * 0.02,
              "lm_head": mat(d, v), "norm_out": jnp.ones((d,)), "layers": []}
    for _ in range(layers):
        params["layers"].append({
            "attn_norm": jnp.ones((d,)),
            "wq": mat(d, nh * hd), "wk": mat(d, nkv * hd),
            "wv": mat(d, nkv * hd), "wo": mat(nh * hd, d),
            "mlp_norm": jnp.ones((d,)),
            "w1": mat(d, ff), "w3": mat(d, ff), "w2": mat(ff, d)})
    return params


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (s, heads, hd), rotate-half convention, positions 0..s-1."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _group_attention(qkv, precision):
    """Dense causal softmax attention of the query heads that share one
    key/value head. q: (rep, s, hd); k, v: (s, hd)."""
    q, k, v = qkv
    s, hd = k.shape
    scores = jnp.einsum("rqd,kd->rqk", _cast(q, precision),
                        _cast(k, precision), precision=HI) / np.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("rqk,kd->rqd", _cast(probs, precision),
                      _cast(v, precision), precision=HI)


def _layer(layer, x, sizes, precision):
    hd = sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    s = x.shape[0]
    h = _rmsnorm(x, layer["attn_norm"], sizes["rms_norm_eps"])
    q = _rope(_mm(h, layer["wq"], precision).reshape(s, nh, hd),
              sizes["rope_theta"])
    k = _rope(_mm(h, layer["wk"], precision).reshape(s, nkv, hd),
              sizes["rope_theta"])
    v = _mm(h, layer["wv"], precision).reshape(s, nkv, hd)
    q = q.reshape(s, nkv, nh // nkv, hd).transpose(1, 2, 0, 3)
    out = jax.lax.map(
        jax.checkpoint(lambda qkv: _group_attention(qkv, precision)),
        (q, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(2, 0, 1, 3).reshape(s, nh * hd)
    x = x + _mm(out, layer["wo"], precision)
    h = _rmsnorm(x, layer["mlp_norm"], sizes["rms_norm_eps"])
    gate = jax.nn.silu(_mm(h, layer["w1"], precision))
    return x + _mm(gate * _mm(h, layer["w3"], precision), layer["w2"],
                   precision)


def _row_nll_sum(params, row, sizes, precision):
    """Summed next-token negative log-likelihood of one row of tokens; the
    last position has no target and is left out."""
    x = params["embed"][row]
    for layer in params["layers"]:
        x = jax.checkpoint(
            lambda lyr, xx: _layer(lyr, xx, sizes, precision))(layer, x)
    x = _rmsnorm(x, params["norm_out"], sizes["rms_norm_eps"])
    logits = _mm(x, params["lm_head"], precision)
    lse = jax.nn.logsumexp(logits[:-1], axis=-1)
    target = jnp.take_along_axis(logits[:-1], row[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - target)


def loss(params, tokens, sizes, precision=None):
    """Mean next-token cross-entropy of a (batch, seq) int32 array."""
    b, s = tokens.shape
    row = jax.checkpoint(
        lambda p, r: _row_nll_sum(p, r, sizes, precision))
    total = jax.lax.scan(lambda acc, r: (acc + row(params, r), None),
                         jnp.zeros(()), tokens)[0]
    return total / (b * (s - 1))


def train_step(params, mu, nu, count, tokens, sizes, *, learning_rate,
               weight_decay, b1=0.9, b2=0.999, eps=1e-8, precision=None):
    """One AdamW step (Loshchilov & Hutter, decoupled decay on every leaf)
    -> (params, mu, nu, count, loss, grads)."""
    value, grads = jax.value_and_grad(loss)(params, tokens, sizes, precision)
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, n: p - learning_rate * (
            (m / c1) / (jnp.sqrt(n / c2) + eps) + weight_decay * p),
        params, mu, nu)
    return params, mu, nu, count, value, grads
