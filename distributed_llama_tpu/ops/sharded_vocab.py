"""Vocab-dim sharding: tp-split embedding gather + sharded sampling.

The reference kept the embedding and classifier head root-only
(ref: src/transformer.cpp:639,663-673) and early revisions of this repo
replicated them per device — 533 MB/chip at 70B widths, blowing the
README's own 2.42 GB/chip budget (VERDICT weak #3), plus a serialized
~0.36 ms/token full-logit head read. Megatron-LM's parallel vocab
embedding + sharded cross-entropy (PAPERS.md) is the standard fix; this
module is its inference-side analogue:

  * **Embedding** (:func:`embed_tokens_sharded`) — ``tok_emb`` lives as a
    local ``(vocab/S, dim)`` shard per device (S = the product of the
    vocab mesh axes, normally tp; under pp the table additionally splits
    over pp since the gather runs outside the manual region). The lookup
    is a masked LOCAL gather — out-of-shard token rows contribute exact
    zeros — followed by one all-reduce of the (B, T, dim) activations.
    Zeros + one real contribution add exactly in any float dtype, so the
    result is BIT-IDENTICAL to the replicated ``emb[tokens]`` gather.
  * **Head / sampling** (:func:`sharded_sample_prep`) — the logits stay
    vocab-sharded on device (wcls is row-split already); what crosses to
    the host is a tiny per-shard summary instead of the (B, vocab)
    logits:

      - greedy: local argmax + local max per shard, a (S, B) pair
        gather, and a global pick with the SAME deterministic
        lowest-index tie-break ``np.argmax`` implies (within a shard the
        local argmax picks the lowest local index; across shards the
        lowest global id among max-attaining shards wins — and any
        equal value in a lower shard has the lower global id).
      - sampled: local top-k probabilities (exact — the softmax
        denominator is a psum over shards of the per-shard masses) with
        global ids, plus each shard's k-th-largest prob as the
        EXACTNESS GUARD. The merged k·S candidates provably contain the
        global top-k: the global i-th largest value (i <= k) is within
        the top-i <= top-k of whatever shard holds it. Host-side
        (runtime/sampling.sample_candidates) the oracle's nucleus walk runs on
        the merged candidates and is EXACT whenever the truncation
        point lands strictly above the guard (every token above the
        guard is a candidate, in oracle order); otherwise the caller
        falls back to a single replicated row fetch (the parity
        oracle), so the distribution is exact in every case.

Everything traced here is a module-level body so analysis/entrypoints.py
fingerprints the SAME programs the engine jits (the
seed_rows_from_blocks discipline). Docs: docs/parallelism.md
("Vocab sharding").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from ..parallel.mesh import DP_AXIS


def vocab_shard_axes(mesh, vocab_size: int) -> tuple[str, ...]:
    """The mesh axes the vocab dim can row-split over: tp always (when it
    divides), pp too when present (the embedding gather and head matmul
    run OUTSIDE the manual pp region, so the table may split over both —
    each pp stage would otherwise hold a full copy it never reads for
    the other stages' tokens). Returns () when the vocab cannot split
    evenly — the caller keeps the replicated path."""
    if mesh is None:
        return ()
    tp = mesh.shape.get("tp", 1)
    pp = mesh.shape.get("pp", 1)
    if tp <= 1:
        return ()
    if pp > 1 and vocab_size % (pp * tp) == 0:
        return ("pp", "tp")
    if vocab_size % tp != 0:
        return ()
    return ("tp",)


def _axes_size(mesh, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _shard_index(axes: tuple[str, ...], sizes: tuple[int, ...]):
    """Linear shard index along `axes` inside a manual region, matching
    PartitionSpec((axes,)) layout order (major-to-minor as listed)."""
    idx = jnp.int32(0)
    for a, s in zip(axes, sizes):
        idx = idx * s + lax.axis_index(a).astype(jnp.int32)
    return idx


def embed_tokens_local(emb_local, tokens, base, compute_dtype, axes):
    """The per-shard embedding body: masked local gather + all-reduce.
    Token ids outside [base, base + vocab/S) contribute exact zeros; the
    psum then adds zeros to the one shard's real rows — exact in any
    float dtype, so sharded == replicated bit-for-bit. Module-level so
    the audit fingerprints the program the engine runs."""
    vloc = emb_local.shape[0]
    loc = tokens.astype(jnp.int32) - base
    ok = (loc >= 0) & (loc < vloc)
    safe = jnp.clip(loc, 0, vloc - 1)
    x = emb_local[safe].astype(compute_dtype)
    x = jnp.where(ok[..., None], x, jnp.zeros((), compute_dtype))
    return lax.psum(x, axes)


def embed_tokens_sharded(emb, tokens, mesh, axes: tuple[str, ...],
                         compute_dtype):
    """(B, T) int32 tokens -> (B, T, dim) activations from a vocab-
    sharded embedding table (emb placed P(axes, None)). The output is
    replicated over the vocab axes (each shard contributed its rows);
    GSPMD reshards downstream as the consumer needs."""
    sizes = tuple(mesh.shape[a] for a in axes)
    vloc = emb.shape[0] // _axes_size(mesh, axes)

    def body(emb_local, tok):
        base = _shard_index(axes, sizes) * vloc
        return embed_tokens_local(emb_local, tok, base, compute_dtype,
                                  axes)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), P(DP_AXIS, None)),
        out_specs=P(DP_AXIS, None, None),
        check_vma=False,
    )(emb, tokens)


# -- sharded sampling prep ---------------------------------------------------

# consecutive ids a block of top_candidates: 16 read fastest on a v5e of 8,
# 16 and strided blocks, at 8 x 32,000 to 16 x 65,536 (PERF.md section 6,
# PR 53)
_BLOCK = 16


def top_candidates(p, k: int):
    """The k largest of each row of p (B, V), EXACT, in (value descending,
    id ascending) order: (values (B, k), ids (B, k) int32). What
    `lax.top_k(p, k)` returns, by two sorts of a few thousand values a row
    where the TPU's TopK makes k passes over the vocabulary or sorts it
    whole, whichever its compiler picks (11 ms and 0.4 ms at k = 512 over
    8 x 50,176; this 0.1 ms: PERF.md section 6, PR 53):

      1. the maximum of every block of _BLOCK consecutive ids; the k blocks
         of the largest maxima, ties to the lower block;
      2. those blocks' k * _BLOCK values, sorted by (value, id).

    Every one of the k largest lies in those blocks: a value v at id i in a
    block b that was not taken has k blocks before b in (maximum
    descending, block ascending) order, each with a maximum >= b's >= v,
    and one that only equals v is a LOWER block, all of whose ids are under
    i; so k values precede (v, i) and it is not among the k largest. A row
    too short for that to prune (under 2k blocks) is sorted whole. Both
    keys are compared and no two pairs are equal, so the sorts need not be
    stable (a stable sort of these widths takes the TPU's compiler twice
    as long: 27 s against 12-16 s, and its time is a cold boot's)."""
    b, v = p.shape
    ids = jnp.arange(v, dtype=jnp.int32)

    def largest(vals, idx):
        neg, idx = lax.sort((-vals, idx), dimension=1, is_stable=False,
                            num_keys=2)
        return -neg[:, :k], idx[:, :k]

    n_blocks = -(-v // _BLOCK)
    if n_blocks < 2 * k:
        return largest(p, jnp.broadcast_to(ids, (b, v)))
    pad = n_blocks * _BLOCK - v          # under every probability
    blocks = jnp.pad(p, ((0, 0), (0, pad)), constant_values=-1.0).reshape(
        b, n_blocks, _BLOCK)
    _, taken = largest(
        jnp.max(blocks, axis=-1),
        jnp.broadcast_to(jnp.arange(n_blocks, dtype=jnp.int32),
                         (b, n_blocks)))
    held = jnp.take_along_axis(blocks, taken[:, :, None], axis=1)
    held_ids = taken[:, :, None] * _BLOCK + ids[:_BLOCK]
    return largest(held.reshape(b, k * _BLOCK),
                   held_ids.reshape(b, k * _BLOCK))


def sample_prep_local(l_local, temps, base, n_vocab, k, axes):
    """Per-shard sampling summary over a (B, vocab/S) logits shard:

      * greedy half: (local max, local argmax as a GLOBAL id), both over
        the tokenizer vocab only (ids >= n_vocab mask to -inf — the host
        Sampler's truncation, sampler.py:69);
      * sampled half: the local top-k EXACT probabilities (softmax over
        the FULL vocab: global max by pmax, denominator by psum) with
        global ids, plus the shard's k-th-largest prob — the host-side
        exactness guard.

    temps is a traced (B,) float32 (per-row temperature — requests in a
    batch sample at different temperatures without new compile keys);
    rows with temperature 0 pass 1.0 and ignore the sampled half.
    n_vocab may be traced too. axes == () is the ONE-shard form
    (sample_summary below): the collectives over no axis are the
    identity, and the body is the same."""
    vloc = l_local.shape[-1]
    gid = base + jnp.arange(vloc, dtype=jnp.int32)
    valid = gid < n_vocab
    neg = jnp.asarray(-jnp.inf, jnp.float32)
    lm = jnp.where(valid[None, :], l_local.astype(jnp.float32), neg)

    loc_max = jnp.max(lm, axis=-1)                        # (B,)
    loc_arg = base + jnp.argmax(lm, axis=-1).astype(jnp.int32)

    t = jnp.maximum(temps.astype(jnp.float32), 1e-6)[:, None]
    x = lm / t
    gmax = lax.pmax(jnp.max(x, axis=-1), axes)            # (B,)
    e = jnp.where(valid[None, :], jnp.exp(x - gmax[:, None]), 0.0)
    z = lax.psum(jnp.sum(e, axis=-1), axes)               # (B,)
    p = e / z[:, None]
    top_p, top_i = top_candidates(p, k)                   # (B, k) desc
    top_id = base + top_i
    guard = top_p[:, k - 1]                               # k-th largest
    return (loc_max[:, None], loc_arg[:, None], top_p, top_id,
            guard[:, None])


def sharded_sample_prep(logits, temps, mesh, axes: tuple[str, ...],
                        n_vocab: int, k: int):
    """(B, V) vocab-sharded logits -> the host-fetchable sampling
    summary, with the full logits NEVER gathered:

      argmax  (B,)        — the global greedy token (tie-break pinned)
      cand_p  (B, S*k)    — exact candidate probs, per-shard top-k
      cand_id (B, S*k)    — their global token ids
      guard   (B, S)      — each shard's k-th-largest prob

    The cross-shard greedy pick happens on the tiny (B, S) gathered
    pair: lowest global id among the max-attaining shards — exactly
    np.argmax's first-max rule, since ids increase with shard index."""
    sizes = tuple(mesh.shape[a] for a in axes)
    n_shards = _axes_size(mesh, axes)
    vloc = logits.shape[-1] // n_shards

    def body(l_local, t):
        base = _shard_index(axes, sizes) * vloc
        return sample_prep_local(l_local, t, base, n_vocab, k, axes)

    spec_b = P(DP_AXIS, axes)
    lmax, larg, cand_p, cand_id, guard = shard_map(
        body, mesh=mesh,
        in_specs=(P(DP_AXIS, axes), P(DP_AXIS)),
        out_specs=(spec_b, spec_b, spec_b, spec_b, spec_b),
        check_vma=False,
    )(logits, temps)
    # global greedy pick over the (B, S) summaries — GSPMD land, the
    # gather here is S values per row, not the vocab
    best = jnp.max(lmax, axis=1, keepdims=True)
    amax = jnp.min(jnp.where(lmax == best, larg, jnp.int32(2**31 - 1)),
                   axis=1).astype(jnp.int32)
    return amax, cand_p, cand_id, guard


# -- the summary every served step returns -----------------------------------

# candidates a row of the one-shard summary: the 0.9 nucleus of a head with
# a trained model's ~3-nat logits holds 50-70 tokens at the median and under
# 300 at the most over a vocabulary of 32k-65k (PERF.md section 6, PR 53)
SUMMARY_TOPK = 512


def step_summary(logits, sample):
    """The LAST lines of a slot step program of an engine without a mesh
    (runtime/engine.py; the audit's entry points and the compile
    rehearsal trace this same body): the step's sampling summary, or
    zeros of its shape from a step no row of which will be sampled (a
    mid-prompt chunk, the benchmark's check; a real conditional: such a
    step pays none of the device work), under the `head` scope
    (models/scopes.py), whose logits it reads.

    sample (B + 2,) float32, ONE traced operand and so one small transfer
    a dispatch: the rows' temperatures, then the tokenizer's vocabulary
    (exact in float32 under 2**24) and whether to compute the summary
    (Engine._sample_operands: whether the caller gave temperatures)."""
    b, v = logits.shape
    k = min(SUMMARY_TOPK, v)
    with jax.named_scope("head"):
        return lax.cond(
            sample[b + 1] > 0,
            lambda: sample_summary(logits, sample[:b],
                                   sample[b].astype(jnp.int32), k),
            lambda: jnp.zeros((b, 1 + 2 * k), jnp.int32))


def sample_summary(logits, temps, n_vocab, k: int):
    """The one-shard form of the sampling summary, as step_summary above
    puts it at the end of both slot step programs: (B, vocab) logits ->
    (B, 1 + 2k) int32, one leaf and one transfer:

      [:, 0]        the argmax over the tokenizer's vocabulary
      [:, 1:1+k]    the bits of the top-k float32 probabilities at each
                    row's temperature, descending (the k-th is the guard)
      [:, 1+k:]     their token ids

    runtime/sampling.unpack_summary is the host's half."""
    _, amax, top_p, top_id, _ = sample_prep_local(
        logits, temps, jnp.int32(0), n_vocab, k, ())
    return jnp.concatenate(
        [amax, lax.bitcast_convert_type(top_p, jnp.int32), top_id], axis=1)
