"""Device-side image ops with a Pallas TPU fast path.

``normalize_images`` fuses the standard input-pipeline tail — uint8 ->
float, scale to [0,1], normalize by mean/std, cast to bfloat16 — into one
VPU pass over VMEM tiles, so the staged uint8 batch (4x smaller on the wire
than float32) is expanded only on-chip. Falls back to plain XLA (which also
fuses this well) off-TPU or when shapes don't tile.

Kernel layout: the flattened batch is viewed as (rows, 128) lanes. The
channel of element (row, lane) is ``(row*128 + lane) % C``, which is
periodic in the row index with period ``lcm(C,128)/128``; per-channel
scale/bias are pre-expanded into one such periodic block so the kernel body
is a single elementwise FMA (no gather or modulo on the VPU).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_LANES = 128


def _normalize_kernel(x_ref, scale_ref, bias_ref, out_ref):
    # Mosaic has no direct uint8->f32 cast on some TPU gens; hop via int32.
    x = x_ref[:].astype(jnp.int32).astype(jnp.float32)
    out_ref[:] = (x * scale_ref[:] + bias_ref[:]).astype(out_ref.dtype)


def _pick_block_rows(rows: int, period_rows: int) -> Optional[int]:
    """Block height: a multiple of the channel period AND of 32 (uint8
    sublane tile) that divides the row count; prefer larger blocks."""
    base = int(np.lcm(period_rows, 32))
    for mult in (16, 8, 4, 2, 1):
        br = base * mult
        if br <= rows and rows % br == 0:
            return br
    return None


@partial(jax.jit, static_argnames=("mean", "std", "out_dtype", "use_pallas"))
def normalize_images(images, mean: tuple = (0.485, 0.456, 0.406),
                     std: tuple = (0.229, 0.224, 0.225),
                     out_dtype=jnp.bfloat16, use_pallas: Optional[bool] = None):
    """(..., C) uint8 images -> normalized ``out_dtype``: ``(x/255 - mean)/std``.

    Pallas kernel on TPU when the flattened size tiles cleanly; XLA
    otherwise (numerically identical at float32 accuracy).
    """
    channels = images.shape[-1]
    if len(mean) < channels or len(std) < channels:
        raise ValueError(f"images have {channels} channels but mean/std supply "
                         f"{len(mean)}/{len(std)} values")
    mean_arr = jnp.asarray(mean, jnp.float32)[:channels]
    std_arr = jnp.asarray(std, jnp.float32)[:channels]
    # (x/255 - mean)/std  ==  x * scale + bias
    scale = 1.0 / (255.0 * std_arr)
    bias = -mean_arr / std_arr

    total = int(np.prod(images.shape))
    rows = total // _LANES if total % _LANES == 0 else 0
    period_rows = int(np.lcm(channels, _LANES)) // _LANES
    block_rows = _pick_block_rows(rows, period_rows) if rows else None

    if use_pallas is None:
        # Measured on v5e: XLA's automatic fusion wins for this purely
        # memory-bound elementwise op (~0.9ms vs ~1.4ms per 8x224x224x3
        # batch), so the kernel is opt-in; it exists as the template for
        # fused ops XLA cannot express (e.g. decode+normalize+augment).
        use_pallas = False
    if use_pallas and block_rows is None:
        raise ValueError(f"image batch of {total} elements does not tile into "
                         f"(k*lcm({period_rows},32), 128) blocks")

    if not use_pallas:
        x = images.astype(jnp.float32)
        return (x * scale + bias).astype(out_dtype)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    flat = images.reshape(rows, _LANES)
    lane_idx = (jnp.arange(block_rows * _LANES) % channels).reshape(block_rows, _LANES)
    scale_tile = scale[lane_idx]
    bias_tile = bias[lane_idx]

    out = pl.pallas_call(
        _normalize_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), out_dtype),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, _LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, _LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        # The Pallas interpreter is for the cpu backend only; any other
        # backend compiles the kernel or raises.
        interpret=(jax.default_backend() == "cpu"),
    )(flat, scale_tile, bias_tile)
    return out.reshape(images.shape)
