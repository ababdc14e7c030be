"""`device_idle_pct` of the tokens cells (body and meaning: _shared.device_idle_pct)."""
from chipbench.layer_metrics._shared import device_idle_pct as read  # noqa: F401
