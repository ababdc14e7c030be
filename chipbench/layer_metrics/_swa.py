"""Shared by the sliding-window kernels' readers: the per-chip call's
shapes, and its least time from ``flops_moe.swa_call``."""
from chipbench import flops, flops_moe, trace_reduce


def roofline_pct(run, kernel: str, names: tuple):
    if not run["trace"] or run["peak"] is None:
        return None
    seconds, calls = zip(*(trace_reduce.kernel_seconds(run["trace"], n)
                           for n in names))
    cfg, traffic = run["job"].cfg, run["job"].traffic
    if not calls[0] or "sliding_window_size" not in cfg:
        return None
    call = flops_moe.swa_call(kernel, traffic["per_chip_batch"],
                              cfg["num_attention_heads"],
                              cfg["num_key_value_heads"], traffic["window"],
                              cfg["head_dim"], cfg["sliding_window_size"])
    least, _ = flops.least_seconds(call, run["peak"])
    return 100.0 * least * calls[0] / sum(seconds)
