"""Bodies that the split readers (``<metric>.image`` / ``<metric>.tokens``)
share: which cells report a metric is the manifest's ``workloads`` list, so
a reader only has to say when there is nothing to read."""
from chipbench import trace_reduce, window


def host_cpu_s_per_item(run):
    """Process CPU seconds (all threads) per item delivered in the window."""
    return run["cpu_s"] / (run["log"].steps * run["job"].items_per_step)


def input_stall_pct(run):
    """Share of the window's wall time spent blocked in next(loader)."""
    return window.input_stall_pct(run["log"])


def step_mfu_pct(run):
    """The whole step's share of the chips' bf16 peak, fed: operations the
    forward and backward passes need (chipbench/flops.py; nothing recomputed
    is counted) over the window's wall time."""
    if run["peak"] is None:
        return None
    log = run["log"]
    achieved = run["job"].flops_per_step * log.steps / log.wall_s
    return 100.0 * achieved / (run["chips"] * run["peak"]["bf16_flops_per_s"])


def resident_step_ms(run):
    """The compiled step re-run on one staged batch, loader out of the
    loop: the ceiling the fed rate is read against."""
    log = run["resident_log"]
    return 1e3 * log.wall_s / log.steps if log and log.steps else None


def device_idle_pct(run):
    """Share of the traced window in which no instruction ran."""
    if not run["trace"] or not run["trace"]["devices"]:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_seconds(run["trace"])
                    / run["traced_log"].wall_s)
