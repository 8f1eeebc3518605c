"""Per-layer metric ``mla_latent_gb_per_step_pangu``: mean of ``gen:step``'s ``latent_bytes`` (the live tokens' latent rows over every latent layer), in GB."""
from perfbench.harness.mla import mla_latent_gb_per_step as read  # noqa: F401
