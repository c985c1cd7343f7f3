"""Work of a mixture-of-experts layer's routed experts, counted from the
configuration's shapes and the routing the engine counted.

Nothing here reads the program's kernels: the shapes come from the
configuration file as it is run (``n_routed_experts``, ``hidden_size``,
``moe_intermediate_size``, the VQ geometry of ``serving``), and the
routing from two counters of the window: ``moe_expert_visits`` (experts
with at least one row, summed over the steps' MoE layers) and
``moe_routed_rows`` (rows x top-k x MoE layers). Each routed expert is
two EVA linears, gate|up (D x 2 Fe) and down (Fe x D), as in
``bench/lib/work.py``:

    bytes = visits x sum over the two linears of (K*N*C*n/d/8 indices
            + C*d*2^n*4 codebooks + N*4 scales)
            + rows x sum of (K*2 + N*2) (bf16 activations in and out)
    FLOPs = rows x sum of EVA's per-row count (2*K*2^n*C + N*(K/d)*C)

so the least time reads the routing the model defines, whatever
implements it: an implementation that computes every expert for every
row reads lower against it, as it should.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple


class Experts:
    """The routed-expert sizes of a configuration file."""

    def __init__(self, conf: Dict[str, Any]):
        c, s = conf["config"], conf["serving"]
        self.d = int(c["hidden_size"])
        self.ff = int(c["moe_intermediate_size"])
        self.C, self.vd, self.n = int(s["vq_C"]), int(s["vq_d"]), \
            int(s["vq_n"])

    def linears(self) -> List[Tuple[str, int, int]]:
        """(name, K, N) of one expert's EVA linears."""
        return [("gate_up", self.d, 2 * self.ff), ("down", self.ff, self.d)]

    def visit_bytes(self) -> int:
        """Weight bytes one expert's linears read: indices, codebooks,
        scales."""
        return sum(K * N * self.C * self.n // self.vd // 8
                   + self.C * self.vd * 2 ** self.n * 4 + N * 4
                   for _, K, N in self.linears())

    def row_bytes(self) -> int:
        """bf16 activations in and out of one routed row."""
        return sum(2 * K + 2 * N for _, K, N in self.linears())

    def row_flops(self) -> int:
        """EVA's operations of one routed row: the output codebook and
        one lookup-add per index."""
        return sum(2 * K * 2 ** self.n * self.C + N * (K // self.vd) * self.C
                   for _, K, N in self.linears())


def least_seconds(ex: Experts, visits: float, rows: float,
                  pk: Dict[str, Any]) -> float:
    """Least time of the routed-expert linears behind ``visits`` expert
    visits and ``rows`` routed rows: bytes over peak bandwidth or FLOPs
    over peak FLOP/s, whichever is larger."""
    return max((visits * ex.visit_bytes() + rows * ex.row_bytes())
               / pk["hbm_bytes_per_s"],
               rows * ex.row_flops() / pk["bf16_flop_per_s"])
