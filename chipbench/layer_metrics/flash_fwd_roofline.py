"""flash_fwd's device time against the least the chip could take for its
calls (compute-bound at these shapes: chipbench/flops.py says which)."""
from chipbench.layer_metrics._kernels import flash_call, roofline_pct


def read(run):
    return roofline_pct(run, "flash", "fwd", flash_call)
