"""`fwd_ms_per_step` of the tokens cells (body and meaning: _scopes.fwd_ms_per_step)."""
from chipbench.layer_metrics._scopes import fwd_ms_per_step as read  # noqa: F401
