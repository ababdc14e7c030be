"""The device time of the flash family's backward kernels, one or two
(flash_bwd, or flash_bwd_dq + flash_bwd_dkv), against the least the chip
could take for the backward pass of their calls (chipbench/flops.py)."""
from chipbench.layer_metrics._kernels import flash_call, roofline_pct


def read(run):
    return roofline_pct(run, "flash", "bwd", flash_call)
