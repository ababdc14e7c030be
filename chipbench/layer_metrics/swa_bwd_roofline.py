"""The device time of the swa family's backward kernels, one or two (swa_bwd,
or swa_bwd_dq + swa_bwd_dkv), against the least the chip could take for the
backward pass over the band (chipbench/flops_moe.py)."""
from chipbench.layer_metrics._kernels import roofline_pct, swa_call


def read(run):
    return roofline_pct(run, "swa", "bwd", swa_call)
