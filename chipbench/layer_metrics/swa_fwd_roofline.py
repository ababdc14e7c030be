"""swa_fwd's device time against the least the chip could take for the
band's operations and bytes of its calls (chipbench/flops_moe.py)."""
from chipbench.layer_metrics._kernels import roofline_pct, swa_call


def read(run):
    return roofline_pct(run, "swa", "fwd", swa_call)
