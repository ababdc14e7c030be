"""`queue_depth_mean` of the tokens cells (body and meaning: _spans.queue_depth_mean)."""
from chipbench.layer_metrics._spans import queue_depth_mean as read  # noqa: F401
