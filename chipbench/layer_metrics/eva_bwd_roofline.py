"""eva_bwd_dq + eva_bwd_dkv's device time against the least the chip could
take for the backward pass's operations and bytes (chipbench/flops_eva.py)."""
from chipbench.layer_metrics._eva import roofline_pct


def read(run):
    return roofline_pct(run, "bwd", ("eva_bwd_dq", "eva_bwd_dkv"))
