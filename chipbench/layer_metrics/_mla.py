"""Shared by the latent-attention readers: the per-chip call's shapes, its
least time from ``flops_mla.mla_call``, and the kernels' device seconds.
The call runs the three flash kernels at a key width and a value width of
their own, under the kernels' names."""
from chipbench import flops, flops_mla, trace_reduce

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def roofline_pct(run, kernel: str, names: tuple):
    if not run["trace"] or run["peak"] is None:
        return None
    seconds, calls = zip(*(trace_reduce.kernel_seconds(run["trace"], n)
                           for n in names))
    cfg, traffic = run["job"].cfg, run["job"].traffic
    if not calls[0] or "kv_lora_rank" not in cfg:
        return None
    call = flops_mla.mla_call(kernel, traffic["per_chip_batch"],
                              cfg["num_attention_heads"], traffic["window"],
                              cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    least, _ = flops.least_seconds(call, run["peak"])
    return 100.0 * least * calls[0] / sum(seconds)


def kernel_share_pct(run):
    """The three kernels' device seconds over the traced window's busy
    seconds: how much of the step latent attention's kernels are."""
    if not run["trace"] or "kv_lora_rank" not in run["job"].cfg:
        return None
    seconds = sum(trace_reduce.kernel_seconds(run["trace"], n)[0]
                  for n in KERNELS)
    busy = trace_reduce.busy_seconds(run["trace"])
    return 100.0 * seconds / busy if seconds and busy else None
