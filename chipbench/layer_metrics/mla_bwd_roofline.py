"""The device time of the flash family's backward kernels, one or two, in the
latent-attention cell against the least the chip could take for the
backward pass's operations (2.6 forwards at 192 | 128) and bytes
(chipbench/flops_mla.py)."""
from chipbench.layer_metrics._kernels import mla_call, roofline_pct


def read(run):
    return roofline_pct(run, "flash", "bwd", mla_call)
