"""Work the model defines, counted from its shapes, and the chip's peaks.

Nothing here reads the program: the shapes come from the configuration
file as it is run, and the peaks from `peaks.json`, keyed by JAX's
``device_kind``. A device the table lacks is an error, never a default.

Dense-equivalent FLOPs count the work the model defines, whatever
implements it: 2 per multiply-add of every linear and of the LM head,
and of attention's two products over each row's live context.

The least time of one EVA VQ linear (M rows, a K x N weight held as C
codebooks of 2^n entries over d-element groups) is the larger of its
bytes over peak bandwidth and its FLOPs over peak FLOP/s, where

    bytes = K*N*C*n/d/8 (packed indices) + C*d*2^n*4 (codebooks)
            + N*4 (scales) + M*K*2 + M*N*2 (bf16 x and y)
    FLOPs = min(2*M*K*N, 2*M*K*2^n*C + M*N*(K/d)*C)

the second term of the minimum being EVA's own count: the output
codebook X.B for every codebook, and one lookup-add per index and row.
Linears that share their input (q/k/v, gate/up) share one output
codebook, so they count as one linear here, which keeps the least time
a lower bound whatever the program groups.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Iterable, List, Tuple

PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def peak(device_kind: str) -> Dict[str, Any]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/lib/peaks.json") from None


class Shape:
    """The sizes of a dense decoder that the work functions need, read
    from a configuration file's ``config`` and ``serving`` sections."""

    def __init__(self, conf: Dict[str, Any]):
        c, s = conf["config"], conf["serving"]
        self.layers = int(c["num_hidden_layers"])
        self.d = int(c["hidden_size"])
        self.ff = int(c["intermediate_size"])
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.head_dim = int(c["head_dim"])
        self.vocab = int(c["vocab_size"])
        self.C, self.vd, self.n = int(s["vq_C"]), int(s["vq_d"]), \
            int(s["vq_n"])

    def vq_linears(self) -> List[Tuple[str, int, int]]:
        """(name, K, N) of one layer's VQ linears, same-input linears
        taken together."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return [("qkv", self.d, q + 2 * kv), ("o", q, self.d),
                ("gate_up", self.d, 2 * self.ff), ("down", self.ff, self.d)]

    def layer_params(self) -> int:
        return sum(K * N for _, K, N in self.vq_linears())

    def head_params(self) -> int:
        return self.d * self.vocab

    def index_bytes(self) -> int:
        """Packed 2-bit (C*n/d bits per weight) indices of every layer."""
        return self.layers * sum(K * N * self.C * self.n // self.vd // 8
                                 for _, K, N in self.vq_linears())

    def head_bytes(self) -> int:
        return 2 * self.head_params()


def vq_bytes(M: int, K: int, N: int, C: int, n: int, d: int) -> int:
    return (K * N * C * n // d // 8 + C * d * 2 ** n * 4 + N * 4
            + 2 * M * K + 2 * M * N)


def vq_flops(M: int, K: int, N: int, C: int, n: int, d: int) -> int:
    eva = 2 * M * K * 2 ** n * C + M * N * (K // d) * C
    return min(2 * M * K * N, eva)


def vq_least_seconds(shape: Shape, M: int, pk: Dict[str, Any]) -> float:
    """Least time of every VQ linear of one model step at M rows."""
    per_layer = sum(
        max(vq_bytes(M, K, N, shape.C, shape.n, shape.vd)
            / pk["hbm_bytes_per_s"],
            vq_flops(M, K, N, shape.C, shape.n, shape.vd)
            / pk["bf16_flop_per_s"])
        for _, K, N in shape.vq_linears())
    return shape.layers * per_layer


def decode_flops(shape: Shape, contexts: Iterable[int]) -> float:
    """Dense-equivalent FLOPs of one decode row per context length in
    ``contexts`` (the positions each row attends, itself included)."""
    per_row = 2 * (shape.layers * shape.layer_params() + shape.head_params())
    attn = 4 * shape.layers * shape.heads * shape.head_dim
    return float(sum(per_row + attn * int(ctx) for ctx in contexts))
