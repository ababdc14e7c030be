"""swa_bwd_dq + swa_bwd_dkv's device time against the least the chip could
take for the backward pass over the band (chipbench/flops_moe.py)."""
from chipbench.layer_metrics._swa import roofline_pct


def read(run):
    return roofline_pct(run, "bwd", ("swa_bwd_dq", "swa_bwd_dkv"))
