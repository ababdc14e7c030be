"""EvaByte's decoder layers (``configs/evabyte-6.5b-d4.json`` gives the
equations, the cut and what is assumed): pre-norm blocks with RMSNorm
scales stored as offsets from one, rotary positions, EVA attention, SwiGLU,
eight untied prediction heads and AdamW. Float32 and plain ``jax.numpy``:
no kernels.

EVA attention of one head (``d`` = head width, ``tau = d ** -0.5``, blocks
of ``W`` positions, chunks of ``C``): with the head's learned ``phi``,
``mu``, chunk ``c`` pools its rotated keys and its values by ``a_cj =
softmax_j(k_j . phi)`` into ``kbar_c = sum_j a_cj k_j + mu`` and ``vbar_c =
sum_j a_cj v_j``. A query at ``i`` sees, in ONE softmax, the keys ``j <= i``
of its own block and the summaries of every chunk of every EARLIER block:
per block a dense ``[W, W + (W / C) w]`` score array.

The loss is the mean, over the eight heads ``m`` and the positions ``t``
with ``t + 1 + m`` inside the window, of the cross-entropy of head ``m``'s
logits at ``t`` against the id at ``t + 1 + m``.

Memory, not mathematics, shapes the code: one row at a time, attention one
head at a time and inside it one block of queries at a time, the MLP and
the heads one block of tokens at a time, each under ``jax.checkpoint``, so
that float32 at 16,384 positions fits beside the optimizer's state. None of
it changes a value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.decoder import HI, _cast, _mm, _rmsnorm, _rope
from chipbench.reference.smallthinker import adamw  # noqa: F401 (a step's end)

TOKEN_BLOCK = 2048      # tokens of one block of the MLP and of the heads


def init_params(seed_key, sizes: dict):
    """N(0, 0.02) embedding, N(0, 1/fan_in) matrices, norm offsets of
    nought, ``phi`` and ``mu`` as clip(N(0, 1), -1, 1) / sqrt(head_dim):
    the configuration's init, drawn key by key in its stated order (the
    embedding, the eight heads as one matrix, then per layer q, k, v, o,
    gate, up, down and one key split in two for phi and mu)."""
    d, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    nh, ff = sizes["num_attention_heads"], sizes["intermediate_size"]
    layers, heads = sizes["num_hidden_layers"], sizes["num_pred_heads"]
    keys = iter(jax.random.split(seed_key, 4 + layers * 8))

    def mat(fan_in, fan_out):
        return jax.random.normal(next(keys), (fan_in, fan_out),
                                 jnp.float32) / np.sqrt(fan_in)

    def per_head(key):
        return jnp.clip(jax.random.normal(key, (nh, hd), jnp.float32),
                        -1, 1) / np.sqrt(hd)

    params = {"embed": jax.random.normal(next(keys), (v, d), jnp.float32)
              * 0.02,
              "lm_head": mat(d, heads * v), "norm_out": jnp.zeros((d,)),
              "layers": []}
    for _ in range(layers):
        layer = {"attn_norm": jnp.zeros((d,)),
                 "wq": mat(d, nh * hd), "wk": mat(d, nh * hd),
                 "wv": mat(d, nh * hd), "wo": mat(nh * hd, d),
                 "mlp_norm": jnp.zeros((d,)),
                 "w1": mat(d, ff), "w3": mat(d, ff), "w2": mat(ff, d)}
        k_phi, k_mu = jax.random.split(next(keys))
        layer["eva_phi"], layer["eva_mu"] = per_head(k_phi), per_head(k_mu)
        params["layers"].append(layer)
    return params


def _norm(x, offset, sizes):
    return _rmsnorm(x, 1.0 + offset, sizes["rms_norm_eps"])


def summaries(k, v, phi, mu, chunk: int, precision=None):
    """One head's chunk summaries. k (rotated), v: (s, d); phi, mu: (d,)
    -> kbar, vbar (s / chunk, d)."""
    kc = k.reshape(-1, chunk, k.shape[-1])
    vc = v.reshape(-1, chunk, v.shape[-1])
    a = jax.nn.softmax(jnp.einsum("ncd,d->nc", _cast(kc, precision),
                                  _cast(phi, precision), precision=HI), -1)
    a = _cast(a, precision)
    kbar = jnp.einsum("nc,ncd->nd", a, _cast(kc, precision), precision=HI)
    vbar = jnp.einsum("nc,ncd->nd", a, _cast(vc, precision), precision=HI)
    return kbar + mu, vbar


def head_attention(q, k, v, phi, mu, window: int, chunk: int,
                   precision=None):
    """EVA attention of one head. q, k (both rotated), v: (s, d) ->
    (s, d). Block ``w``'s queries score their own block's keys (causal)
    and the ``(window / chunk) w`` summaries before it, one softmax over
    both."""
    s, d = q.shape
    kbar, vbar = summaries(k, v, phi, mu, chunk, precision)
    per_block = window // chunk
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None, :]
    out = []
    for w in range(s // window):
        rows = slice(w * window, (w + 1) * window)
        keys = jnp.concatenate([k[rows], kbar[:w * per_block]])
        values = jnp.concatenate([v[rows], vbar[:w * per_block]])
        seen = jnp.concatenate(
            [causal, jnp.ones((window, w * per_block), bool)], axis=1)
        scores = jnp.einsum("qd,kd->qk", _cast(q[rows], precision),
                            _cast(keys, precision), precision=HI) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("qk,kd->qd", _cast(probs, precision),
                              _cast(values, precision), precision=HI))
    return jnp.concatenate(out)


def attention_part(layer, h, sizes, precision):
    """``concat_heads(o) Wo`` of the normed stream ``h`` (s, hidden), one
    head at a time: its columns of Wq, Wk, Wv, its attention, its rows of
    Wo, added up."""
    s, dm = h.shape
    nh, hd = sizes["num_attention_heads"], sizes["head_dim"]
    theta = sizes["rope_theta"]

    def columns(w):                     # (hidden, heads * hd) -> per head
        return w.reshape(dm, nh, hd).transpose(1, 0, 2)

    def one(wq, wk, wv, wo, phi, mu):
        q = _rope(_mm(h, wq, precision)[:, None, :], theta)[:, 0]
        k = _rope(_mm(h, wk, precision)[:, None, :], theta)[:, 0]
        o = head_attention(q, k, _mm(h, wv, precision), phi, mu,
                           sizes["window_size"], sizes["chunk_size"],
                           precision)
        return _mm(o, wo, precision)

    def body(acc, args):
        return acc + jax.checkpoint(one)(*args), None

    return jax.lax.scan(body, jnp.zeros_like(h), (
        columns(layer["wq"]), columns(layer["wk"]), columns(layer["wv"]),
        layer["wo"].reshape(nh, hd, dm), layer["eva_phi"],
        layer["eva_mu"]))[0]


def _token_blocks(x):
    block = min(TOKEN_BLOCK, x.shape[0])
    return x.reshape(x.shape[0] // block, block, *x.shape[1:])


def mlp_part(layer, h, precision):
    def one(hb):
        gate = jax.nn.silu(_mm(hb, layer["w1"], precision))
        return _mm(gate * _mm(hb, layer["w3"], precision), layer["w2"],
                   precision)
    return jax.lax.map(jax.checkpoint(one), _token_blocks(h)).reshape(h.shape)


def _layer(layer, x, sizes, precision):
    x = x + attention_part(layer, _norm(x, layer["attn_norm"], sizes), sizes,
                           precision)
    return x + mlp_part(layer, _norm(x, layer["mlp_norm"], sizes), precision)


def targets_and_counted(ids, heads: int, positions=None):
    """``targets[t, m] = ids[t + 1 + m]`` and ``counted[t, m]``: 1 where
    that lies inside the window and ``t < positions`` (None = every
    position)."""
    s = ids.shape[0]
    t = np.arange(s)[:, None]
    ahead = t + 1 + np.arange(heads)[None, :]
    counted = (ahead < s) & (t < (s if positions is None else positions))
    return ids[np.minimum(ahead, s - 1)], jnp.asarray(counted, jnp.float32)


def row_nll_sum(params, ids, sizes, precision=None, positions=None):
    """Summed cross-entropy of one row of ids (s,) over the heads and the
    positions counted (:func:`targets_and_counted`)."""
    heads, vocab = sizes["num_pred_heads"], sizes["vocab_size"]
    x = params["embed"][ids]
    for layer in params["layers"]:
        x = jax.checkpoint(
            lambda lyr, xx: _layer(lyr, xx, sizes, precision))(layer, x)
    x = _norm(x, params["norm_out"], sizes)
    targets, counted = targets_and_counted(ids, heads, positions)

    def head(xb, tb, cb):
        logits = _mm(xb, params["lm_head"], precision).reshape(
            -1, heads, vocab)
        lse = jax.nn.logsumexp(logits, axis=-1)
        target = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum(cb * (lse - target))

    def body(acc, args):
        return acc + jax.checkpoint(head)(*args), None

    return jax.lax.scan(body, jnp.zeros(()), (
        _token_blocks(x), _token_blocks(targets), _token_blocks(counted)))[0]


def counted_pairs(seq: int, heads: int, positions=None) -> int:
    """(position, head) pairs of one row that the loss counts."""
    return int(targets_and_counted(np.zeros(seq, np.int32), heads,
                                   positions)[1].sum())


def loss(params, ids, sizes, precision=None, positions=None):
    """The mean cross-entropy of a (batch, seq) array of ids."""
    total = sum(row_nll_sum(params, row, sizes, precision, positions)
                for row in ids)
    return total / (ids.shape[0] * counted_pairs(
        ids.shape[1], sizes["num_pred_heads"], positions))


def row_grads(params, ids, scale, sizes, precision=None, positions=None):
    """``(scale x the row's summed loss, its gradient)``. One call a row
    (and a step's AdamW update, ``adamw``, as a call of its own): a whole
    step as one program holds more than one gradient and does not fit
    beside float32 weights and the optimizer's state."""
    return jax.value_and_grad(lambda p: scale * row_nll_sum(
        p, ids, sizes, precision, positions))(params)
