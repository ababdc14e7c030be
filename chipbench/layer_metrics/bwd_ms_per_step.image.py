"""`bwd_ms_per_step` of the image cell (body and meaning: _scopes.bwd_ms_per_step)."""
from chipbench.layer_metrics._scopes import bwd_ms_per_step as read  # noqa: F401
