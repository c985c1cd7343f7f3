"""Pallas TPU kernels for EVA's compute hot-spots.

  vq_gemm         — Step 1: output-codebook GEMM  O = X·B
  oc_lookup       — Step 2: conflict-free OC lookup + add-only reduction
  fused_vq_matmul — flagship: both steps fused, OC resident in VMEM
  dequant_gemv    — conventional-VQ baseline (centroid gather + GEMV)
  vq_dequant      — rebuild W from indices + codebooks (MLA's wkv_b in decode)
  int8_gemm       — prefill int8 GEMM (reconfigurable-PE INT8 mode)

All kernels are TPU-targeted (pl.pallas_call + BlockSpec VMEM tiling) and
validated against pure-jnp oracles in interpret mode on CPU.
"""
