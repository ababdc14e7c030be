"""Device-side ops: input-pipeline tail kernels and flash attention.

Normalize/augment run inside the jitted step so the host ships compact
uint8 batches; ``flash_attention`` is the Pallas O(seq)-memory attention
kernel and ``eva_attention`` its chunked linear-attention sibling (a block's
own keys and the earlier blocks' chunk summaries in one softmax).
"""
from petastorm_tpu.ops.augment import (cutout, mixup, random_crop,
                                       random_flip_horizontal)
from petastorm_tpu.ops.eva_attn import eva_attention, make_eva_attention
from petastorm_tpu.ops.flash_attn import (flash_attention,
                                               make_flash_attention)
from petastorm_tpu.ops.image_ops import normalize_images

__all__ = ["normalize_images", "random_flip_horizontal", "random_crop",
           "cutout", "mixup", "flash_attention", "make_flash_attention",
           "eva_attention", "make_eva_attention"]
