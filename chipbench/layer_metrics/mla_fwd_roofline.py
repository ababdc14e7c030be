"""The forward attention kernel's device time in the latent-attention cell
against the least the chip could take for one call's operations (scores
over 192, values over 128) and bytes, the rotary key counted as one head
(chipbench/flops_mla.py)."""
from chipbench.layer_metrics._kernels import mla_call, roofline_pct


def read(run):
    return roofline_pct(run, "flash", "fwd", mla_call)
