"""`delivery_wait_pct` of the image cells (body and meaning: _spans.delivery_wait_pct)."""
from chipbench.layer_metrics._spans import delivery_wait_pct as read  # noqa: F401
