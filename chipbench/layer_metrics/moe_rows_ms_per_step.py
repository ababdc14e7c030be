"""`moe_rows_ms_per_step` (body and meaning: _scopes.moe_rows_ms_per_step)."""
from chipbench.layer_metrics._scopes import moe_rows_ms_per_step as read  # noqa: F401
