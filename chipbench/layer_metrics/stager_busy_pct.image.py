"""`stager_busy_pct` of the image cells (body and meaning: _spans.stager_busy_pct)."""
from chipbench.layer_metrics._spans import stager_busy_pct as read  # noqa: F401
