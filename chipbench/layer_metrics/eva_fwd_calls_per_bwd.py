"""``eva_fwd`` calls per ``eva_bwd_dq`` call in the traced window: 1 where
the block's checkpoint kept the forward kernel's output and row statistics
(what the EVA cell's step relies on), 2 where the backward pass's
recomputation launches the forward kernel again."""
from chipbench import trace_reduce


def read(run):
    if not run["trace"]:
        return None
    fwd, bwd = (trace_reduce.kernel_seconds(run["trace"], kernel)[1]
                for kernel in ("eva_fwd", "eva_bwd_dq"))
    return fwd / bwd if bwd else None
