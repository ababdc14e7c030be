"""ResNet-50 in pure JAX — the ImageNet consumer (BASELINE config 3).

Design notes for TPU: NHWC layout (XLA's native conv layout on TPU),
bfloat16 activations with float32 batch-norm statistics and float32 master
params, ``lax.conv_general_dilated`` so the MXU gets large fused convs.
Batch norm runs in inference *or* training mode (returning updated moving
stats) without python branching inside jit.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from petastorm_tpu import device_scopes as scopes

Params = Dict[str, Any]

# (blocks per stage, bottleneck mid-channels per stage)
_RESNET50_STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * np.sqrt(2.0 / fan_in)


def _bn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}


def init_params(rng_key, num_classes: int = 1000) -> Params:
    keys = iter(jax.random.split(rng_key, 256))
    params: Params = {"stem": {"conv": _conv_init(next(keys), 7, 7, 3, 64),
                               "bn": _bn_init(64)}}
    cin = 64
    for stage_idx, (blocks, mid) in enumerate(_RESNET50_STAGES):
        stage = []
        for block_idx in range(blocks):
            cout = mid * 4
            block = {
                "conv1": _conv_init(next(keys), 1, 1, cin, mid), "bn1": _bn_init(mid),
                "conv2": _conv_init(next(keys), 3, 3, mid, mid), "bn2": _bn_init(mid),
                "conv3": _conv_init(next(keys), 1, 1, mid, cout), "bn3": _bn_init(cout),
            }
            if block_idx == 0:
                block["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
                block["proj_bn"] = _bn_init(cout)
            stage.append(block)
            cin = cout
        params[f"stage{stage_idx}"] = stage
    params["head"] = {"w": jax.random.normal(next(keys), (cin, num_classes),
                                             jnp.float32) * 0.01,
                      "b": jnp.zeros((num_classes,), jnp.float32)}
    return params


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, bn, train: bool, momentum=0.9, eps=1e-5):
    if train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x.astype(jnp.float32), axes)
        var = jnp.var(x.astype(jnp.float32), axes)
        new_stats = {"mean": momentum * bn["mean"] + (1 - momentum) * mean,
                     "var": momentum * bn["var"] + (1 - momentum) * var}
    else:
        mean, var = bn["mean"], bn["var"]
        new_stats = {"mean": bn["mean"], "var": bn["var"]}
    inv = jax.lax.rsqrt(var + eps) * bn["scale"]
    out = (x.astype(jnp.float32) - mean) * inv + bn["bias"]
    return out.astype(x.dtype), new_stats


def _bottleneck(x, block, stride, train):
    stats = {}
    h, stats["bn1"] = _batch_norm(_conv(x, block["conv1"]), block["bn1"], train)
    h = jax.nn.relu(h)
    h, stats["bn2"] = _batch_norm(_conv(h, block["conv2"], stride), block["bn2"], train)
    h = jax.nn.relu(h)
    h, stats["bn3"] = _batch_norm(_conv(h, block["conv3"]), block["bn3"], train)
    if "proj" in block:
        shortcut, stats["proj_bn"] = _batch_norm(_conv(x, block["proj"], stride),
                                                 block["proj_bn"], train)
    else:
        shortcut = x
    return jax.nn.relu(h + shortcut), stats


def apply(params: Params, images, train: bool = False, compute_dtype=jnp.bfloat16,
          remat: bool = False):
    """images: (N, H, W, 3) float32 in [0, 1] -> (logits, new_bn_stats).

    ``remat=True`` wraps each bottleneck in :func:`jax.checkpoint` so the
    backward pass recomputes block activations instead of storing them —
    the standard FLOPs-for-HBM trade: the train step's temp memory scales
    with the batch (every benchmark result carries XLA's own account as
    ``compiled_hbm_bytes``), and remat keeps per-device batches that would
    overflow a 16 GiB-class chip inside HBM.
    """
    block_fn = jax.checkpoint(_bottleneck, static_argnums=(2, 3)) if remat \
        else _bottleneck
    new_stats: Params = {"stem": {}}
    with jax.named_scope(scopes.STEM):
        x = images.astype(compute_dtype)
        x, new_stats["stem"]["bn"] = _batch_norm(
            _conv(x, params["stem"]["conv"], 2), params["stem"]["bn"], train)
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
    for stage_idx, (blocks, _) in enumerate(_RESNET50_STAGES):
        stage_stats = []
        with jax.named_scope(scopes.STAGES[stage_idx]):
            for block_idx in range(blocks):
                stride = 2 if (block_idx == 0 and stage_idx > 0) else 1
                x, s = block_fn(x, params[f"stage{stage_idx}"][block_idx],
                                stride, train)
                stage_stats.append(s)
        new_stats[f"stage{stage_idx}"] = stage_stats
    with jax.named_scope(scopes.HEAD):
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        logits = x @ params["head"]["w"] + params["head"]["b"]
    return logits, new_stats


def merge_bn_stats(params: Params, new_stats: Params) -> Params:
    """Fold updated moving statistics back into the param tree."""
    def merge(p, path_stats):
        out = dict(p)
        for k, v in path_stats.items():
            if isinstance(v, dict) and "mean" in v:
                out[k] = {**p[k], **v}
            elif isinstance(v, list):
                out[k] = [merge(pb, sb) for pb, sb in zip(p[k], v)]
            elif isinstance(v, dict):
                out[k] = merge(p[k], v)
        return out
    return merge(params, new_stats)


def loss_fn(params, batch, train: bool = True, remat: bool = False):
    logits, new_stats = apply(params, batch["image"], train=train, remat=remat)
    labels = batch["label"]
    with jax.named_scope(scopes.HEAD):
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
        acc = (logits.argmax(-1) == labels).mean()
    return nll, (acc, new_stats)


def make_train_step(learning_rate: float = 0.1, weight_decay: float = 1e-4,
                    momentum: float = 0.9, remat: bool = False):
    """SGD momentum + weight decay train step (standard ImageNet recipe).
    ``remat`` rematerializes bottleneck activations in the backward pass
    (see :func:`apply`)."""
    def train_step(params, velocity, batch):
        (loss, (acc, new_stats)), grads = jax.value_and_grad(
            partial(loss_fn, remat=remat), has_aux=True)(params, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            velocity = jax.tree.map(
                lambda v, g, p: momentum * v + g + weight_decay * p,
                velocity, grads, params)
            params = jax.tree.map(lambda p, v: p - learning_rate * v,
                                  params, velocity)
            params = merge_bn_stats(params, new_stats)
        return params, velocity, loss, acc
    return train_step
