"""decode_mfu: dense-equivalent FLOPs of the decode steps in the window
(every linear and the LM head per active row, attention over each row's
context) over the engine's decode wall time in the window times the
chip's peak bf16 FLOP/s, in percent."""
from bench.lib import work


def read(w):
    secs = w.run.delta("decode_s")
    ctx = [c for s in w.run.steps for c in s.decode_contexts]
    if secs <= 0 or not ctx:
        return None
    flops = work.decode_flops(w.shape, ctx)
    return 100.0 * flops / (secs * w.peak["bf16_flop_per_s"])
