"""Shared by the EVA kernels' readers: the per-chip call's shapes, its
least time from ``flops_eva.eva_call``, and the kernels' device seconds."""
from chipbench import flops, flops_eva, trace_reduce

KERNELS = ("eva_fwd", "eva_bwd_dq", "eva_bwd_dkv")


def roofline_pct(run, kernel: str, names: tuple):
    if not run["trace"] or run["peak"] is None:
        return None
    seconds, calls = zip(*(trace_reduce.kernel_seconds(run["trace"], n)
                           for n in names))
    cfg, traffic = run["job"].cfg, run["job"].traffic
    if not calls[0] or "chunk_size" not in cfg:
        return None
    call = flops_eva.eva_call(kernel, traffic["per_chip_batch"],
                              cfg["num_attention_heads"], traffic["window"],
                              cfg["head_dim"], cfg["window_size"],
                              cfg["chunk_size"])
    least, _ = flops.least_seconds(call, run["peak"])
    return 100.0 * least * calls[0] / sum(seconds)


def kernel_share_pct(run):
    """The three kernels' device seconds over the traced window's busy
    seconds: how much of the step the mechanism is."""
    if not run["trace"]:
        return None
    seconds = sum(trace_reduce.kernel_seconds(run["trace"], n)[0]
                  for n in KERNELS)
    busy = trace_reduce.busy_seconds(run["trace"])
    return 100.0 * seconds / busy if seconds and busy else None
