"""`input_stall_pct` of the image cells (body and meaning: _shared.input_stall_pct)."""
from chipbench.layer_metrics._shared import input_stall_pct as read  # noqa: F401
