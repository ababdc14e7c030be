"""Shared by the flash kernels' readers: the per-chip call's shapes."""
from chipbench import flops, trace_reduce


def roofline_pct(run, kernel: str, names: tuple):
    if not run["trace"] or run["peak"] is None:
        return None
    seconds, calls = zip(*(trace_reduce.kernel_seconds(run["trace"], n)
                           for n in names))
    if not calls[0]:
        return None
    cfg, traffic = run["job"].cfg, run["job"].traffic
    call = flops.flash_call(kernel, traffic["per_chip_batch"],
                            cfg["num_attention_heads"],
                            cfg["num_key_value_heads"], traffic["window"],
                            cfg["head_dim"])
    least, _ = flops.least_seconds(call, run["peak"])
    return 100.0 * least * calls[0] / sum(seconds)
