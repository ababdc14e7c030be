"""`optimizer_ms_per_step` of the image cell (body and meaning: _scopes.optimizer_ms_per_step)."""
from chipbench.layer_metrics._scopes import optimizer_ms_per_step as read  # noqa: F401
