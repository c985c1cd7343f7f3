"""The check of `correct`: what the timed path served, against the plain
reference.

Once the window has closed and the program's state is freed, a sample
of the greedy requests the window served, drawn from the seed and always
holding the one with the most served tokens, is run through the
reference once, over each prompt followed by its served tokens. For
every served token the reference gives the gap by which that token's
logit lies below the reference's best at its position; a greedy token
computed correctly lies within rounding of the best. The widest gap, or
a high percentile of the gaps, is compared with the cell's limit
(``bench/limits/<cell>.json``), which was set between the program's
readings and the control's.

The control (``control=True``) is the reference itself computed one
precision below the configuration's (``reference.logits(...,
control=True)``); it reads, at each of the same positions, the
reference's gap of the token the control puts first, and is judged by
the same limits (``judge``), which it has to fail.

Sampled requests are not compared: their tokens are draws, and a
served draw has no single right answer to be held against.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from bench.lib.loop import Run, Sent


def pick(run: Run, seed: int, max_requests: int) -> List[Sent]:
    """Greedy requests with served tokens, at most ``max_requests``: the
    one with the most tokens, then others in an order drawn from the
    seed."""
    greedy = [s for s in run.sent.values() if s.req.greedy and s.tokens]
    if not greedy:
        return []
    greedy.sort(key=lambda s: (-len(s.tokens), s.uid))
    rest = greedy[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    return [greedy[0]] + [rest[int(i)] for i in order[:max_requests - 1]]


def _layout(picked: List[Sent], shape: Tuple[int, int]
            ) -> Tuple[np.ndarray, List[int], List[int], np.ndarray]:
    """Each picked request's prompt and served tokens as one row of a
    (rows, length) array of the fixed ``shape``, and the positions whose
    next token was served."""
    seqs = [np.concatenate([s.req.prompt, np.asarray(s.tokens[:-1], np.int32)])
            for s in picked]
    R, T = shape
    if len(seqs) > R or max(len(q) for q in seqs) > T:
        raise ValueError(f"{len(seqs)} requests of up to "
                         f"{max(len(q) for q in seqs)} tokens exceed {shape}")
    tokens = np.zeros((R, T), np.int32)
    rows, cols, served = [], [], []
    for r, (s, q) in enumerate(zip(picked, seqs)):
        tokens[r, :len(q)] = q
        p = s.req.prompt.size
        for i, t in enumerate(s.tokens):
            rows.append(r)
            cols.append(p - 1 + i)
            served.append(t)
    return tokens, rows, cols, np.asarray(served, np.int32)


def gaps(ref, conf: Dict[str, Any], seed: int, picked: List[Sent],
         shape: Tuple[int, int], *, control: bool = False) -> np.ndarray:
    """Per compared token, the reference's best logit minus its logit of
    the served token (or, with ``control``, of the control's first
    token at that position). ``shape`` is the cell's fixed (rows,
    length) of the reference's input."""
    if not picked:
        return np.zeros((0,), np.float32)
    tokens, rows, cols, served = _layout(picked, shape)
    want = np.asarray(ref.logits(conf, seed, tokens, rows, cols), np.float64)
    if control:
        got = np.asarray(ref.logits(conf, seed, tokens, rows, cols,
                                    control=True))
        chosen = np.argmax(got, axis=-1)
    else:
        chosen = served
    picked_logit = np.take_along_axis(want, chosen[:, None], axis=-1)[:, 0]
    return want.max(axis=-1) - picked_logit


def gap_readings(gaps: np.ndarray) -> Dict[str, float]:
    """The widest gap and the gap at three percentiles over the compared
    tokens (the widest is inf when nothing was compared)."""
    if not gaps.size:
        return {"max_logit_gap": float("inf")}
    out = {"max_logit_gap": float(gaps.max())}
    for q in (99, 95, 90):
        out[f"p{q}_logit_gap"] = float(np.percentile(gaps, q))
    return out


def judge(readings: Dict[str, float], limits: Dict[str, Any]
          ) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """Every number compared beside its limit; ``limits`` maps a name to
    ``{"max": x}`` or ``{"min": x}``."""
    out, ok = {}, True
    for name, lim in limits.items():
        v = readings[name]
        if "max" in lim:
            good = v <= lim["max"]
            out[name] = {"value": v, "limit": lim["max"], "rule": "<="}
        else:
            good = v >= lim["min"]
            out[name] = {"value": v, "limit": lim["min"], "rule": ">="}
        ok = ok and bool(good)
    return ok, out
