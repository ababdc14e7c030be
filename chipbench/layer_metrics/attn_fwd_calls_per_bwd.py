"""Forward attention kernel calls per backward call in the traced window:
2 where every layer's forward kernel runs again in the backward pass's
recomputation, 1 where its output and row statistics were kept."""
from chipbench import trace_reduce


def calls(trace, *kernels) -> int:
    return sum(trace_reduce.kernel_seconds(trace, k)[1] for k in kernels)


def read(run):
    if not run["trace"]:
        return None
    bwd = calls(run["trace"], "flash_bwd_dq", "swa_bwd_dq")
    return calls(run["trace"], "flash_fwd", "swa_fwd") / bwd if bwd else None
