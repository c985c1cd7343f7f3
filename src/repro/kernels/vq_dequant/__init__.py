from repro.kernels.vq_dequant.ops import vq_dequant
from repro.kernels.vq_dequant.ref import vq_dequant_ref
