"""One general generator for every traffic mix file.

Requests are numbered in the order they are sent. Request ``j`` belongs
to wave ``j // clients``. Every wave holds the same kind of spread of
lengths whatever the seed: its ``clients`` prompt and output lengths
are stratified quantiles of the mix's length distributions, at an
offset fixed per wave. The seed only shuffles the order of the
requests inside each wave and draws their token ids, their greedy or
sampled decoding and their sampling seeds. So every seed gives the
same sizes and arrivals in another order, and runs with different
seeds do the same work.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np

_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    greedy: bool
    temperature: float
    top_p: float
    top_k: int
    seed: int


def _quantile(dist: Dict[str, Any], u: float) -> int:
    lo, hi = float(dist["min"]), float(dist["max"])
    if dist["dist"] == "log_uniform":
        return int(round(lo * (hi / lo) ** u))
    if dist["dist"] == "uniform":
        return int(round(lo + (hi - lo) * u))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def wave_lengths(mix: Dict[str, Any], wave: int) -> List[tuple]:
    """(prompt_len, output_len) of the requests of one wave, before the
    seed's shuffle. Output quantiles run in the opposite order of prompt
    quantiles, offset by half a stratum, so lengths are not paired
    short with short."""
    n = int(mix["clients"])
    off = (wave * _GOLDEN) % 1.0
    out = []
    for i in range(n):
        up = (i + off) / n
        uo = ((n - 1 - i) + (off + 0.5) % 1.0) / n
        out.append((_quantile(mix["prompt_len"], up),
                    _quantile(mix["output_len"], uo)))
    return out


class Traffic:
    """The requests of one mix under one seed, generated on demand in
    the order they are sent."""

    def __init__(self, mix: Dict[str, Any], seed: int, vocab_size: int):
        self.mix = mix
        self.vocab_size = int(vocab_size)
        self.clients = int(mix["clients"])
        self.rng = np.random.default_rng(seed)
        self._wave: List[Request] = []
        self.sent = 0

    def _fill_wave(self) -> None:
        w = self.sent // self.clients
        lengths = wave_lengths(self.mix, w)
        order = self.rng.permutation(self.clients)
        greedy_n = int(round(self.mix["greedy_share"] * self.clients))
        sp = self.mix["sampling"]
        reqs = []
        for pos, i in enumerate(order):
            p_len, o_len = lengths[int(i)]
            reqs.append(Request(
                index=w * self.clients + pos,
                prompt=self.rng.integers(0, self.vocab_size, p_len,
                                         dtype=np.int64).astype(np.int32),
                max_new_tokens=o_len,
                greedy=pos < greedy_n,
                temperature=float(sp["temperature"]),
                top_p=float(sp["top_p"]), top_k=int(sp["top_k"]),
                seed=int(self.rng.integers(0, 2 ** 31 - 1))))
        self._wave = reqs

    def next(self) -> Request:
        if self.sent % self.clients == 0:
            self._fill_wave()
        req = self._wave[self.sent % self.clients]
        self.sent += 1
        return req

    def buckets(self, bucket_for) -> List[int]:
        """Every prefill bucket a prompt of this mix can fall in."""
        lo, hi = int(self.mix["prompt_len"]["min"]), \
            int(self.mix["prompt_len"]["max"])
        return sorted({bucket_for(n) for n in range(lo, hi + 1)})
