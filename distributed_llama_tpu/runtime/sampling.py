"""Host side of summary sampling (ops/sharded_vocab.py).

The device half ships a tiny summary of a step's logits: the global
argmax and k candidates per vocab shard with an exactness guard (one
shard on an engine without a mesh, whose two slot step programs compute
the summary themselves: ops/sharded_vocab.sample_summary); this module
turns it into tokens with the host Sampler's exact semantics:

  * :func:`sample_candidates` — the oracle's top-p nucleus walk run on
    the merged candidates, EXACT whenever the truncation point provably
    sits above the guard (the candidate set contains every token at or
    above it); returns None when exactness cannot be proven and the
    caller must fall back.
  * :class:`FullLogitsView` / :class:`ShardedLogitsView` — the one
    sampling surface the scheduler and the batch generator consume:
    ``argmax(row, n_vocab)`` and ``sample(sampler, row)``. The full view
    is the replicated parity oracle (host Sampler on fetched logits,
    exactly the pre-sharding path); the sharded view serves greedy rows
    BIT-IDENTICALLY from the device argmax, sampled rows from the
    candidate scheme, and falls back for anything unprovable: to ONE
    replicated (vocab,) row fetch — never the (B, vocab) array — where
    the vocab is sharded, to ONE fetch of the whole array for all of a
    step's unproven rows on one shard (what every step paid before the
    summary).

Docs: docs/parallelism.md ("Vocab sharding") carries the exactness
argument in full.
"""

from __future__ import annotations

import numpy as np


def draw_coin(sampler) -> float:
    """Advance the sampler's xorshift stream one step and return the
    uniform — the same coin `Sampler.sample` would have flipped on the
    full logits (works for both the python and native backends via the
    rng_state property)."""
    from ..utils.rng import xorshift_f32

    s, v = xorshift_f32(sampler.rng_state)
    sampler.rng_state = s
    return v


def sample_candidates(sampler, cand_p: np.ndarray, cand_id: np.ndarray,
                      guard: np.ndarray, argmax_tok: int,
                      ordered: bool = False) -> int | None:
    """Sample one token from the per-shard top-k candidate summary,
    EXACTLY distributed as ``sampler.sample`` on the full logits — or
    return None when exactness cannot be proven from the candidates
    alone (the caller then falls back to the replicated row fetch).

    Exactness argument (docs/parallelism.md "Vocab sharding" carries the
    long form): every token NOT in the candidate set has prob <=
    v_guard = max over shards of that shard's k-th-largest prob. The
    oracle (sampler._sample_topp / topp_nucleus) walks tokens with
    prob >= cutoff in (prob desc, id asc) order and truncates at the
    first index whose cumulative mass crosses topp (inclusive). If the
    crossing element's prob is STRICTLY above v_guard, every token at
    or above it — ties included — is a candidate and ordered exactly as
    the oracle orders it, so the truncated set, its cumulative masses,
    and the draw within it are the oracle's. If the walk never crosses
    (the nucleus is the whole cutoff-filtered set), exactness instead
    needs v_guard < cutoff (no non-candidate passes the filter). The
    probabilities themselves are the device softmax's f32 values — the
    same real quantity the oracle computes, to rounding.

    Only the nucleus mode (0 < topp < 1) is candidate-exact; pure
    multinomial (topp <= 0 or >= 1) needs the full CDF and always
    falls back. Temperature 0 never lands here (the caller returns the
    sharded argmax, bit-identical to np.argmax).

    ordered: the candidates arrive in the oracle's (prob desc, id asc)
    order already (one shard's: ops/sharded_vocab.top_candidates sorts
    them so), and the sort here, most of a walk's time, is left out."""
    topp = float(sampler.topp)
    if topp <= 0.0 or topp >= 1.0:
        return None
    n = int(sampler.vocab_size)
    v_guard = float(guard.max())
    cutoff = (1.0 - topp) / (n - 1)
    keep = cand_p >= cutoff
    if ordered:     # the kept ones are a prefix: views, no copies, no sort
        n_keep = int(np.count_nonzero(keep))
        p, ids = cand_p[:n_keep], cand_id[:n_keep]
    else:
        p, ids = cand_p[keep], cand_id[keep]
        # the oracle's stable descending sort == (prob desc, id asc)
        order = np.lexsort((ids, -p))
        p, ids = p[order], ids[order]
    if p.size == 0:
        # the oracle's empty-nucleus branch keeps the (first) argmax —
        # which the sharded argmax already pinned; exact only when no
        # hidden token passes the cutoff either
        if v_guard >= cutoff:
            return None
        draw_coin(sampler)  # the oracle consumes its coin here too
        return int(argmax_tok)
    cum = np.cumsum(p, dtype=np.float64)
    # the first index whose cumulative mass is over topp (cum never falls)
    last = int(np.searchsorted(cum, topp, side="right"))
    exact_all = v_guard < cutoff
    if last < len(cum):
        if not exact_all and not (p[last] > v_guard):
            return None  # truncation point at/below the guard: a hidden
            # token could belong above it
    else:
        if not exact_all:
            return None  # nucleus = the whole filtered set, but the
            # tail past the candidates is unknown
        last = len(ids) - 1
    coin = draw_coin(sampler)
    r = coin * cum[last]
    idx = int(np.searchsorted(cum[: last + 1], r, side="right"))
    idx = min(idx, last)
    return int(ids[idx])


def unpack_summary(packed: np.ndarray):
    """(argmax (B,), cand_p (B, k), cand_id (B, k), guard (B, 1)) from the
    one leaf ops/sharded_vocab.sample_summary packs: the k-th candidate is
    the guard (one shard: every token that is no candidate lies at or
    below it)."""
    k = (packed.shape[1] - 1) // 2
    cand_p = packed[:, 1:1 + k].view(np.float32)
    return packed[:, 0], cand_p, packed[:, 1 + k:], cand_p[:, k - 1:]


class _CountedView:
    """What both views count: `window` is the scheduler's ServeStats (or
    None), whose `sampled_rows` counts every row a view sampled or took
    the argmax for and `sampled_rows_summary` those served from the
    summary alone."""

    window = None

    def _count_row(self, summary: bool) -> None:
        w = self.window
        if w is not None:
            w.sampled_rows += 1
            w.sampled_rows_summary += summary


class FullLogitsView(_CountedView):
    """The replicated parity oracle: full (B, vocab) logits on host,
    every row sampled by the host Sampler exactly as before vocab
    sharding existed."""

    sharded = False

    def __init__(self, logits_np: np.ndarray):
        self.lg = logits_np

    def argmax(self, row: int, n_vocab: int) -> int:
        self._count_row(False)
        return int(np.argmax(self.lg[row, :n_vocab]))

    def sample(self, sampler, row: int) -> int:
        self._count_row(False)
        return int(sampler.sample(self.lg[row]))

    def row(self, row: int) -> np.ndarray:
        return self.lg[row]


class ShardedLogitsView(_CountedView):
    """Sampling access to one step's logits WITHOUT the (B, vocab)
    fetch: greedy rows read the device argmax, sampled rows run the
    candidate scheme, and anything the candidates cannot prove exact —
    guard failures, pure-multinomial requests, foreign sampler vocabs, a
    temperature other than the one the candidates were computed at —
    reads the row through `fetch_row` (the parity oracle: a warmed
    (vocab,) row gather on a sharded vocab, one cached fetch of the
    whole array on one shard) and samples the oracle way. `stats` is a
    plain dict the engine owns: {"sharded", "fallback"} counters.
    `temps`: the (B,) float32 temperatures the candidates were computed
    at, where the caller of `sample` did not choose them itself (the
    step programs' own summary, computed at the dispatch); such
    candidates, one shard's, also arrive in the oracle's order."""

    sharded = True

    def __init__(self, amax: np.ndarray, cand_p: np.ndarray,
                 cand_id: np.ndarray, guard: np.ndarray, n_vocab: int,
                 fetch_row, stats: dict | None = None,
                 temps: np.ndarray | None = None):
        self.amax = amax
        self.cand_p = cand_p
        self.cand_id = cand_id
        self.guard = guard
        self.n_vocab = int(n_vocab)
        self._fetch_row = fetch_row
        self.stats = stats if stats is not None else {}
        self.temps = temps

    def _count(self, key: str) -> None:
        self.stats[key] = self.stats.get(key, 0) + 1
        self._count_row(key == "sharded")

    def argmax(self, row: int, n_vocab: int) -> int:
        if n_vocab == self.n_vocab:
            self._count("sharded")
            return int(self.amax[row])
        self._count("fallback")
        return int(np.argmax(self._fetch_row(row)[:n_vocab]))

    def row(self, row: int) -> np.ndarray:
        return self._fetch_row(row)

    def sample(self, sampler, row: int) -> int:
        if getattr(sampler, "vocab_size", None) == self.n_vocab:
            if sampler.temperature == 0.0:
                # np.argmax parity: the device argmax is masked at the
                # same vocab and tie-breaks to the lowest index (ONE
                # greedy implementation — argmax() above)
                return self.argmax(row, self.n_vocab)
            tok = None
            if (self.temps is None or self.temps[row]
                    == np.float32(sampler.temperature)):
                tok = sample_candidates(sampler, self.cand_p[row],
                                        self.cand_id[row], self.guard[row],
                                        int(self.amax[row]),
                                        ordered=self.temps is not None)
            if tok is not None:
                self._count("sharded")
                return tok
        self._count("fallback")
        return int(sampler.sample(self._fetch_row(row)))
