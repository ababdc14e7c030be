"""The dQ and dK/dV kernels' device time in the latent-attention cell
against the least the chip could take for the backward pass's operations
(2.6 forwards at 192 | 128) and bytes (chipbench/flops_mla.py)."""
from chipbench.layer_metrics._mla import roofline_pct


def read(run):
    return roofline_pct(run, "bwd", ("flash_bwd_dq", "flash_bwd_dkv"))
