"""The flash family's kernels' share of the latent-attention cell's busy
seconds (body: _kernels.kernel_share_pct)."""
from chipbench.layer_metrics._kernels import kernel_share_pct


def read(run):
    return kernel_share_pct(run, "flash", "kv_lora_rank")
