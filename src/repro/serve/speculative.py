"""Self-drafting speculative decoding: multi-token verify inside the
one-trace jitted decode step.

One engine decode step proposes K draft tokens per slot from a
per-slot successor table (device state, like the RNG keys), feeds
``[t0, d1..dK]`` through ONE ``model.decode`` call (the KV layer
appends all K+1 entries), then replays the exact non-speculative
sampling epilogue over the K+1 logit rows and keeps the longest prefix
the acceptance rule proves identical to what the non-speculative
engine would have emitted. Everything here is per-slot vectorized
device math — there is no host-side per-draft loop, and the decode
step still traces exactly once.

Why the streams are provably identical
--------------------------------------
The non-speculative engine is a deterministic map: given the committed
context and the slot's PRNG key, ``api.sample_tokens`` fixes the next
token (argmax for greedy slots; one key split + ``categorical`` over
the masked, temperature-scaled logits for sampled slots). Logit row j
of the verify window is conditioned on ``[context, t0, d1..dj]``, so
row j equals the baseline's step-(j+1) logits IFF every draft before
it matched the baseline emission: ``d_i == s_{i-1}`` for i <= j. The
verify scan samples ``s_j`` from row j advancing the key once per row
— the same key trajectory the baseline would follow — and the emit
mask keeps exactly the rows whose conditioning prefix matched (plus
the first mismatch row, whose sample IS the baseline's correction).
The slot's key is then rolled back to "after e splits" where e is the
number of emitted tokens, so the next step resumes the identical
PRNG stream. Acceptance is by token equality, not distribution
overlap, so this holds for greedy and seeded sampling alike.

Rejected drafts are rolled back WITHOUT retracing: the model wrote
K+1 cache entries and advanced every ``len`` leaf by K+1, and
``truncate_cache_len`` walks the returned cache tree adding ``e -
(K+1)`` — stale entries beyond ``len`` are invisible to the
``pos < len`` attention validity mask and are overwritten in place by
the next step's writes at the same slots.

The drafter is prompt-lookup style self-drafting (no extra model): a
``(B, V) int32`` successor table mapping token -> the token that last
followed it in this slot's own stream, primed from the prompt at
prefill and updated in-jit from emitted transitions. -1 means "never
seen": the draft chain self-terminates and shorter windows simply
verify fewer rows.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import api


def prime_successors(succ: np.ndarray, slot: int, tokens) -> None:
    """Host-side (re)prime of one slot's successor row from its token
    history (prompt + any already-emitted tokens): ``succ[slot, t_i] =
    t_{i+1}``, later transitions winning. Called at prefill activation,
    outside the jitted step."""
    toks = np.asarray(tokens, np.int64).ravel()
    vocab = succ.shape[1]
    succ[slot, :] = -1
    if toks.size < 2:
        return
    src, dst = toks[:-1], toks[1:]
    ok = (src >= 0) & (src < vocab) & (dst >= 0) & (dst < vocab)
    # np fancy-index assignment applies duplicates in order: later wins
    succ[slot, src[ok]] = dst[ok].astype(np.int32)


def propose_drafts(succ: jax.Array, last_token: jax.Array,
                   k: int) -> jax.Array:
    """Chain k successor lookups from each slot's last committed token.
    succ (B, V) int32, last_token (B,) int32 -> drafts (B, k) int32
    with -1 past the end of the known chain."""
    B, vocab = succ.shape
    rows = jnp.arange(B)

    def step(tok, _):
        nxt = succ[rows, jnp.clip(tok, 0, vocab - 1)]
        nxt = jnp.where(tok >= 0, nxt, -1)
        return nxt, nxt

    _, chain = jax.lax.scan(step, last_token, None, length=k)
    return jnp.moveaxis(chain, 0, 1)                     # (B, k)


def update_successors(succ: jax.Array, prevs: jax.Array, nexts: jax.Array,
                      emit: jax.Array) -> jax.Array:
    """Record the emitted transitions ``prevs[:, j] -> nexts[:, j]`` for
    every j with ``emit[:, j]`` — sequentially, so within one window the
    latest transition wins, matching the host priming order."""
    B, S = prevs.shape
    vocab = succ.shape[1]
    rows = jnp.arange(B)

    def body(j, table):
        pv = jnp.clip(prevs[:, j], 0, vocab - 1)
        cur = table[rows, pv]
        new = jnp.where(emit[:, j], nexts[:, j], cur)
        return table.at[rows, pv].set(new)

    return jax.lax.fori_loop(0, S, body, succ)


def truncate_cache_len(caches: Any, delta: jax.Array) -> Any:
    """Roll back every ``len`` leaf of a decode-cache tree by ``delta``
    (B,) — the rejected-draft rollback. ``len`` leaves carry batch on
    the LAST axis ((L, B) after the per-layer vmap stack), so delta
    broadcasts from the right. Trees without ``len`` (stub models) pass
    through untouched; block tables are never modified."""

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for key, val in node.items():
                if key == "len" and hasattr(val, "dtype"):
                    d = delta.astype(val.dtype)
                    out[key] = val + d.reshape((1,) * (val.ndim - 1) + (-1,))
                else:
                    out[key] = walk(val)
            return out
        return node

    return walk(caches)


def sample_window(logits: jax.Array, keys: jax.Array, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array, greedy: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Replay the baseline sampling epilogue over each of the S logit
    rows in order, advancing the PRNG keys exactly once per row — the
    identical key trajectory the non-speculative engine walks across S
    consecutive steps.

    logits (B, S, V) -> (tokens (B, S) i32, logprobs (B, S) f32,
    keys_after (B, S, 2): the key state after sampling row j)."""

    def step(ks, row):
        tok, nk = api.sample_tokens(row, ks, temperature, top_k, top_p,
                                    greedy)
        lp = api.token_logprobs(row, tok)
        return nk, (tok, lp, nk)

    _, (toks, lps, ktraj) = jax.lax.scan(
        step, keys, jnp.moveaxis(logits, 1, 0))
    return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(lps, 0, 1),
            jnp.moveaxis(ktraj, 0, 1))


def accept_window(toks: jax.Array, drafts: jax.Array, finite: jax.Array,
                  stop_ids: jax.Array, remaining: jax.Array,
                  active: jax.Array, spec_on: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                             jax.Array]:
    """The acceptance rule. All conjuncts of the emit mask are monotone
    non-increasing in j, so the mask is a prefix and ``e = sum(emit)``.

    Emission j (the sample from logit row j) is kept iff:
      * every draft before it matched the corresponding emission
        (``drafts[:, i] == toks[:, i]`` for i < j) — row j's
        conditioning equals the baseline context;
      * no earlier emission hit a stop token (baseline would have
        finished the stream);
      * every row up to j is finite (a poisoned/NaN row invalidates
        itself and everything after, exactly like the baseline's
        ``bad`` short-circuit — row 0 non-finite marks the slot bad);
      * j < remaining (never emit past the token budget);
      * j == 0 or the slot opted into speculation.

    Returns (emit (B, S) bool, e (B,) i32, accepted (B,) i32 drafts
    kept, done (B,) bool, bad (B,) bool)."""
    B, S = toks.shape
    K = S - 1
    bad = active & ~finite[:, 0]

    ones = jnp.ones((B, 1), bool)
    if K:
        mismatch = jnp.cumsum(drafts != toks[:, :K], axis=1) > 0   # (B, K)
        prefix = jnp.concatenate([ones, ~mismatch], axis=1)
    else:
        prefix = ones
    hit_stop = jnp.any(toks[..., None] == stop_ids[:, None, :], axis=-1)
    stopped = jnp.cumsum(hit_stop, axis=1) > 0                     # (B, S)
    nostop_before = jnp.concatenate([ones, ~stopped[:, :K]], axis=1)
    finite_prefix = jnp.cumsum(~finite, axis=1) == 0               # (B, S)
    j = jnp.arange(S)[None, :]
    emit = (prefix & nostop_before & finite_prefix
            & (j < remaining[:, None])
            & (spec_on[:, None] | (j == 0))
            & active[:, None] & ~bad[:, None])
    e = jnp.sum(emit, axis=1).astype(jnp.int32)
    if K:
        accepted = jnp.sum(emit[:, :K] & (drafts == toks[:, :K]),
                           axis=1).astype(jnp.int32)
    else:
        accepted = jnp.zeros((B,), jnp.int32)
    last = jnp.clip(e - 1, 0, S - 1)
    stop_last = jnp.take_along_axis(hit_stop, last[:, None], axis=1)[:, 0]
    done = active & ~bad & (e > 0) & (stop_last | (e >= remaining))
    return emit, e, accepted, done, bad


def spec_decode_step(model, params, caches, tokens, positions, succ, keys,
                     temperature, top_k, top_p, greedy, stop_ids, remaining,
                     active, spec_on, poison, *, rc, k: int):
    """One speculative decode step — the jitted body the engine traces
    ONCE (all K+1 positions ride fixed shapes; per-slot variability is
    data, never shape).

    Returns (tokens (B, K+1) emitted-or-zero, logprobs (B, K+1),
    e (B,) emitted counts, accepted (B,) draft hits, done, bad,
    new_keys (B, 2), new_succ, new_caches)."""
    B = tokens.shape[0]
    S = k + 1
    vocab = model.cfg.vocab_size
    t0 = jnp.where(active, tokens, 0)
    drafts = propose_drafts(succ, t0, k)                 # (B, k)
    feed = jnp.concatenate(
        [t0[:, None], jnp.clip(drafts, 0, vocab - 1)], axis=1)
    pos = positions[:, None] + jnp.arange(S, dtype=positions.dtype)[None, :]
    logits, new_caches = model.decode(params, feed, pos, caches, rc)
    with jax.named_scope("lm_head"):
        logits = logits[:, :, :vocab].astype(jnp.float32)
    with jax.named_scope("sample"):
        logits = logits + poison[:, None, None]
        finite = jnp.all(jnp.isfinite(logits), axis=-1)  # (B, S)
        toks, lps, ktraj = sample_window(logits, keys, temperature, top_k,
                                         top_p, greedy)
        emit, e, accepted, done, bad = accept_window(
            toks, drafts, finite, stop_ids, remaining, active, spec_on)
    # key rollback: after this step the slot must sit e splits ahead,
    # exactly where the baseline would be after emitting e tokens
    last = jnp.clip(e - 1, 0, S - 1)
    new_keys = jnp.take_along_axis(
        ktraj, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    new_keys = jnp.where((e > 0)[:, None], new_keys, keys)
    new_caches = truncate_cache_len(new_caches, e - S)
    prevs = jnp.concatenate([t0[:, None], toks[:, :k]], axis=1)
    new_succ = update_successors(succ, prevs, toks, emit)
    out_toks = jnp.where(emit, toks, 0)
    return (out_toks, lps, e, accepted, done, bad, new_keys, new_succ,
            new_caches)
