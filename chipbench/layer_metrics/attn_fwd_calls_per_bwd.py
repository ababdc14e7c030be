"""Forward calls of the flash and swa families per backward pass in the
traced window (body and meaning: _kernels.fwd_calls_per_bwd)."""
from chipbench.layer_metrics._kernels import fwd_calls_per_bwd


def read(run):
    return fwd_calls_per_bwd(run, "flash", "swa")
