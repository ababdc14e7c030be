"""`h2d_ms_per_step` of the image cells (body and meaning: _spans.h2d_ms)."""
from chipbench.layer_metrics._spans import h2d_ms as read  # noqa: F401
