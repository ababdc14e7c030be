"""`eva_prep_ms_per_step` (body and meaning: _scopes.eva_prep_ms_per_step)."""
from chipbench.layer_metrics._scopes import eva_prep_ms_per_step as read  # noqa: F401
