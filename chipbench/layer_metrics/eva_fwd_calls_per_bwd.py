"""``eva_fwd`` calls per backward pass of the eva family in the traced
window: 1 is what the EVA cell's step relies on (body and meaning:
_kernels.fwd_calls_per_bwd)."""
from chipbench.layer_metrics._kernels import fwd_calls_per_bwd


def read(run):
    return fwd_calls_per_bwd(run, "eva")
