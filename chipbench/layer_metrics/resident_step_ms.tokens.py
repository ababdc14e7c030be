"""`resident_step_ms` of the tokens cells (body and meaning: _shared.resident_step_ms)."""
from chipbench.layer_metrics._shared import resident_step_ms as read  # noqa: F401
