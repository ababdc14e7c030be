"""`step_mfu_pct` of the tokens cells (body and meaning: _shared.step_mfu_pct)."""
from chipbench.layer_metrics._shared import step_mfu_pct as read  # noqa: F401
