"""flash_fwd's device time against the least the chip could take for its
calls (compute-bound at these shapes: chipbench/flops.py says which)."""
from chipbench.layer_metrics._flash import roofline_pct


def read(run):
    return roofline_pct(run, "fwd", ("flash_fwd",))
