"""eva_moe_roofline.decode: least time of the routed-expert linears of
the window's decode steps (bench/lib/moe_work.py, from the engine's
counters ``moe_expert_visits`` and ``moe_routed_rows``) over the own
device time of the grouped EVA kernel inside the decode program, in
percent. None where the run holds no such counters or kernel."""
from bench.lib import moe_work
from bench.lib.report import DECODE_PROGRAMS

GROUPED_KERNELS = ("grouped_vq_matmul",)


def read(w):
    if w.trace is None:
        return None
    try:
        visits = w.run.delta("moe_expert_visits")
        rows = w.run.delta("moe_routed_rows")
    except KeyError:
        return None
    kernel = w.trace.kernel_s(GROUPED_KERNELS, DECODE_PROGRAMS)
    if kernel <= 0 or visits <= 0:
        return None
    least = moe_work.least_seconds(moe_work.Experts(w.conf), visits, rows,
                                   w.peak)
    return 100.0 * least / kernel
