"""`attn_glue_ms_per_step` (body and meaning: _scopes.attn_glue_ms_per_step)."""
from chipbench.layer_metrics._scopes import attn_glue_ms_per_step as read  # noqa: F401
