"""Process CPU time (all threads) per image delivered in the window."""
from chipbench.layer_metrics._shared import host_cpu_s_per_item


def read(run):
    return 1e3 * host_cpu_s_per_item(run)
