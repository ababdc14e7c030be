"""`mla_latent_ms_per_step` (body and meaning: _scopes.mla_latent_ms_per_step)."""
from chipbench.layer_metrics._scopes import mla_latent_ms_per_step as read  # noqa: F401
