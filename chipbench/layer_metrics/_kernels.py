"""Shared by the twelve readers of the attention kernels. They find a
family's kernels by role (``trace_reduce.forward_seconds`` and
``backward_seconds``), so a backward of one kernel reads like a backward of
two; what differs between them is the call a roofline is read against:
``<name>_call(direction, cfg, batch, seq)``, the ``flops*.py`` count of one
per-chip call, or None where the configuration has no such mechanism."""
from chipbench import flops, flops_eva, flops_mla, flops_moe, trace_reduce

SECONDS = {"fwd": trace_reduce.forward_seconds,
           "bwd": trace_reduce.backward_seconds}


def flash_call(direction, cfg, batch, seq):
    return flops.flash_call(direction, batch, cfg["num_attention_heads"],
                            cfg["num_key_value_heads"], seq, cfg["head_dim"])


def swa_call(direction, cfg, batch, seq):
    if "sliding_window_size" in cfg:
        return flops_moe.swa_call(
            direction, batch, cfg["num_attention_heads"],
            cfg["num_key_value_heads"], seq, cfg["head_dim"],
            cfg["sliding_window_size"])


def mla_call(direction, cfg, batch, seq):
    """Latent attention runs the ``flash`` kernels at a key width and a
    value width of their own, under the kernels' names."""
    if "kv_lora_rank" in cfg:
        return flops_mla.mla_call(
            direction, batch, cfg["num_attention_heads"], seq,
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def eva_call(direction, cfg, batch, seq):
    if "chunk_size" in cfg:
        return flops_eva.eva_call(
            direction, batch, cfg["num_attention_heads"], seq,
            cfg["head_dim"], cfg["window_size"], cfg["chunk_size"])


def roofline_pct(run, family: str, direction: str, call):
    """The least the chip could take for the passes the traced window made,
    over the device seconds of the family's kernels that made them."""
    if not run["trace"] or run["peak"] is None:
        return None
    seconds, passes = SECONDS[direction](run["trace"], family)
    traffic = run["job"].traffic
    one = passes and call(direction, run["job"].cfg,
                          traffic["per_chip_batch"], traffic["window"])
    if not one:
        return None
    least, _ = flops.least_seconds(one, run["peak"])
    return 100.0 * least * passes / seconds


def kernel_share_pct(run, family: str, key: str):
    """The family's kernels' device seconds over the traced window's busy
    seconds: how much of the step the mechanism (the configuration's
    ``key``) is."""
    if not run["trace"] or key not in run["job"].cfg:
        return None
    seconds = sum(SECONDS[d](run["trace"], family)[0] for d in SECONDS)
    busy = trace_reduce.busy_seconds(run["trace"])
    return 100.0 * seconds / busy if seconds and busy else None


def fwd_calls_per_bwd(run, *families):
    """Forward kernel calls per backward pass in the traced window: 1 where
    each block's checkpoint kept the forward kernel's output and row
    statistics, 2 where the backward pass's recomputation launches it
    again."""
    if not run["trace"]:
        return None
    fwd, bwd = (sum(SECONDS[d](run["trace"], f)[1] for f in families)
                for d in ("fwd", "bwd"))
    return fwd / bwd if bwd else None
