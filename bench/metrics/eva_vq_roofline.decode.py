"""eva_vq_roofline.decode: least time of the EVA VQ linears of the decode
steps in the trace (M = num_slots rows each; bench/lib/work.py) over the
device time of the EVA kernels inside the decode program, in percent."""
from bench.lib import work
from bench.lib.report import DECODE_PROGRAMS, EVA_KERNELS


def read(w):
    if w.trace is None:
        return None
    kernel = w.trace.kernel_s(EVA_KERNELS, DECODE_PROGRAMS)
    steps = w.trace.program_count(DECODE_PROGRAMS)
    if kernel <= 0 or steps <= 0:
        return None
    least = work.vq_least_seconds(w.shape, w.mix["engine"]["num_slots"],
                                  w.peak)
    return 100.0 * steps * least / kernel
