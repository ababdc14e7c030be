"""The device time of the eva family's backward kernels, one or two (eva_bwd,
or eva_bwd_dq + eva_bwd_dkv), against the least the chip could take for the
backward pass's operations and bytes (chipbench/flops_eva.py)."""
from chipbench.layer_metrics._kernels import eva_call, roofline_pct


def read(run):
    return roofline_pct(run, "eva", "bwd", eva_call)
