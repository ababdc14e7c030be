"""flash_bwd_dq + flash_bwd_dkv's device time against the least the chip
could take for the backward pass of their calls (chipbench/flops.py)."""
from chipbench.layer_metrics._flash import roofline_pct


def read(run):
    return roofline_pct(run, "bwd", ("flash_bwd_dq", "flash_bwd_dkv"))
