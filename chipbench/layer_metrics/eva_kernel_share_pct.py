"""`eva_kernel_share_pct` (body and meaning: _eva.kernel_share_pct)."""
from chipbench.layer_metrics._eva import kernel_share_pct as read  # noqa: F401
