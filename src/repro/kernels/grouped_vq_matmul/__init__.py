from repro.kernels.grouped_vq_matmul.ops import grouped_vq_matmul
