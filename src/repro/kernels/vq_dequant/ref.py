"""Oracle of the VQ dequantization kernel: ``core.vq.dequantize``."""
from repro.core.vq import dequantize as vq_dequant_ref  # noqa: F401
