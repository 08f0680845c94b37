"""Oracle for the selective-scan kernels: the model's step-by-step
``lax.scan`` (``models.ssm.ssm_scan_ref``), and its ``jax.grad``."""
from repro.models.ssm import ssm_scan_ref

__all__ = ["ssm_scan_ref"]
