"""`scope_coverage_pct` of the tokens cells (body and meaning: _scopes.scope_coverage_pct)."""
from chipbench.layer_metrics._scopes import scope_coverage_pct as read  # noqa: F401
