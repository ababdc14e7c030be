"""`mla_kernel_share_pct` (body and meaning: _mla.kernel_share_pct)."""
from chipbench.layer_metrics._mla import kernel_share_pct as read  # noqa: F401
