"""ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1, 50-layer column)
with its SGD-momentum training step, as the configuration states it.

Departures from the paper, all the configuration's own: stride 2 sits on the
3x3 convolution of a stage's first block ("v1.5", as MLPerf's reference),
"SAME" padding, no bias in convolutions, batch-norm statistics over the
whole global batch, weight decay on every leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))
BN_MOMENTUM, BN_EPS = 0.9, 1e-5


def _cast(x, precision):
    """``precision`` None is the reference (float32); a dtype name is the
    control: operands of every convolution and matmul rounded to it."""
    return x if precision is None else x.astype(precision).astype(jnp.float32)


def init_params(seed_key, num_classes: int):
    """He-normal convolutions, unit batch norms, N(0, 0.01) head: the
    configuration's init, drawn key by key in its stated order."""
    keys = iter(jax.random.split(seed_key, 256))

    def conv(kh, kw, cin, cout):
        return jax.random.normal(next(keys), (kh, kw, cin, cout),
                                 jnp.float32) * np.sqrt(2.0 / (kh * kw * cin))

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,)),
                "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}

    params = {"stem": {"conv": conv(7, 7, 3, 64), "bn": bn(64)}}
    cin = 64
    for si, (blocks, mid) in enumerate(STAGES):
        stage = []
        for bi in range(blocks):
            cout = mid * 4
            block = {"conv1": conv(1, 1, cin, mid), "bn1": bn(mid),
                     "conv2": conv(3, 3, mid, mid), "bn2": bn(mid),
                     "conv3": conv(1, 1, mid, cout), "bn3": bn(cout)}
            if bi == 0:
                block["proj"] = conv(1, 1, cin, cout)
                block["proj_bn"] = bn(cout)
            stage.append(block)
            cin = cout
        params[f"stage{si}"] = stage
    params["head"] = {"w": jax.random.normal(next(keys), (cin, num_classes),
                                             jnp.float32) * 0.01,
                      "b": jnp.zeros((num_classes,))}
    return params


def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        _cast(x, precision), _cast(w, precision), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, bn):
    """Training-mode batch norm -> (normalised, new moving statistics)."""
    axes = (0, 1, 2)
    mean, var = jnp.mean(x, axes), jnp.var(x, axes)
    out = (x - mean) * (jax.lax.rsqrt(var + BN_EPS) * bn["scale"]) + bn["bias"]
    return out, {"mean": BN_MOMENTUM * bn["mean"] + (1 - BN_MOMENTUM) * mean,
                 "var": BN_MOMENTUM * bn["var"] + (1 - BN_MOMENTUM) * var}


def _block(x, block, stride, precision):
    stats = {}
    h, stats["bn1"] = _bn(_conv(x, block["conv1"], 1, precision), block["bn1"])
    h, stats["bn2"] = _bn(_conv(jax.nn.relu(h), block["conv2"], stride,
                                precision), block["bn2"])
    h, stats["bn3"] = _bn(_conv(jax.nn.relu(h), block["conv3"], 1, precision),
                          block["bn3"])
    if "proj" in block:
        x, stats["proj_bn"] = _bn(_conv(x, block["proj"], stride, precision),
                                  block["proj_bn"])
    return jax.nn.relu(h + x), stats


def loss_and_stats(params, images_u8, labels, precision=None):
    """Mean cross-entropy of a uint8 NHWC batch, and the batch norms' new
    moving statistics. Blocks are checkpointed so float32 activations of a
    full batch fit the chip; that changes no value."""
    x = images_u8.astype(jnp.float32) / 255.0
    stats = {"stem": {}}
    x, stats["stem"]["bn"] = _bn(_conv(x, params["stem"]["conv"], 2,
                                       precision), params["stem"]["bn"])
    x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                              (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    block = jax.checkpoint(_block, static_argnums=(2, 3))
    for si, (blocks, _) in enumerate(STAGES):
        stage_stats = []
        for bi in range(blocks):
            x, s = block(x, params[f"stage{si}"][bi],
                         2 if (bi == 0 and si > 0) else 1, precision)
            stage_stats.append(s)
        stats[f"stage{si}"] = stage_stats
    pooled = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(_cast(pooled, precision),
                     _cast(params["head"]["w"], precision),
                     precision=jax.lax.Precision.HIGHEST) + params["head"]["b"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    return nll, stats


def _merge_stats(params, stats):
    if isinstance(stats, list):
        return [_merge_stats(p, s) for p, s in zip(params, stats)]
    out = dict(params)
    for k, v in stats.items():
        out[k] = ({**params[k], **v} if isinstance(v, dict) and "mean" in v
                  else _merge_stats(params[k], v))
    return out


def train_step(params, velocity, images_u8, labels, *, learning_rate,
               weight_decay, momentum, precision=None):
    """One SGD-momentum step -> (params, velocity, loss, grads)."""
    (loss, stats), grads = jax.value_and_grad(loss_and_stats, has_aux=True)(
        params, images_u8, labels, precision)
    velocity = jax.tree.map(lambda v, g, p: momentum * v + g + weight_decay * p,
                            velocity, grads, params)
    params = jax.tree.map(lambda p, v: p - learning_rate * v, params, velocity)
    return _merge_stats(params, stats), velocity, loss, grads
