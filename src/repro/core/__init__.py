"""Core quantization + execution-planning package: weight/KV vector
quantization (vq), fused ops, and the matmul planner (plan).
"""
from repro.core.vq import VQWeight, fit_vq, dequantize, synthetic_vq, vq_specs
from repro.core.ops import (
    eva_matmul, dequant_matmul, fp_matmul, int8_matmul, vq_matmul,
    compute_output_codebook, compute_collapse_ratio,
)
from repro.core.plan import (
    LinearSpec, MatmulPlan, PlanPolicy, Planner, default_planner,
    register_backend, registered_backends,
)
# repro.core.quantize imports repro.models (circular via this __init__);
# import it directly: `from repro.core.quantize import quantize_params`.
