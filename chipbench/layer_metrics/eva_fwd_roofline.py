"""eva_fwd's device time against the least the chip could take for the
local and summary pairs' operations and the bytes of its calls
(chipbench/flops_eva.py)."""
from chipbench.layer_metrics._kernels import eva_call, roofline_pct


def read(run):
    return roofline_pct(run, "eva", "fwd", eva_call)
