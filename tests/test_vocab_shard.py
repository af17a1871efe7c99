"""Vocab sharding (ops/sharded_vocab.py, ISSUE-15): tp-split embedding +
logits head with sharded sampling.

The contract under test, against the replicated full-logit ORACLE:

  * forward logits are BIT-IDENTICAL sharded vs replicated (the masked
    local gather + all-reduce adds zeros + one real contribution —
    exact in any float dtype) across tp=2/4, prefill and decode;
  * the sharded argmax equals np.argmax including the deterministic
    lowest-index tie-break, and masks at the tokenizer vocab;
  * the merged per-shard top-k candidates provably contain the global
    top-k, and candidate top-p sampling matches the host Sampler
    token-for-token on the same coin stream whenever the exactness
    guard holds — with the guard FAILING OVER to the replicated row
    fetch on flat distributions (never a wrong distribution);
  * the slot scheduler serves greedy requests BIT-IDENTICALLY sharded
    vs replicated through every path — chunked prefill, plain decode,
    the seeded-prefix-cache path, and the speculative verify/accept
    path — with ZERO post-warmup compiles under a frozen ledger;
  * the HBM ledger's `vocab` category shows the freed bytes and
    `--serve-batch auto` / `--prefix-blocks auto` actually BANK them
    (larger resolved values, not just a smaller number in a report).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.models.spec import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.parallel.mesh import make_mesh
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.profiler import COMPILES, hbm_ledger
from distributed_llama_tpu.runtime.sampling import (draw_coin,
                                                    sample_candidates)
from distributed_llama_tpu.sampler import Sampler

SEQ = 96


def _spec(vocab=288, layers=2, seq=SEQ):
    return ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=layers, n_heads=4, n_kv_heads=2,
                     vocab_size=vocab, seq_len=seq,
                     hidden_act=HiddenAct.SILU)


@pytest.fixture(scope="module")
def tiny():
    spec = _spec()
    host = random_tensors(spec, seed=11, scale=0.5)  # peaked logits —
    # the sampled tests need a nucleus narrower than the candidate set
    return spec, load_params(spec, host, mode="dense", dtype=jnp.float32)


def _engine(tiny, tp, shard, batch=1):
    spec, params = tiny
    mesh = make_mesh(tp=tp, dp=1)
    return Engine(spec, dict(params), mesh, batch=batch,
                  compute_dtype=jnp.float32, cache_dtype=jnp.float32,
                  shard_vocab=shard)


def _prep(eng, logits, temps, n_vocab):
    view = eng.sample_view(logits, temps, n_vocab)
    assert view.sharded
    return view


# -- forward parity ----------------------------------------------------------


@pytest.mark.parametrize("tp", [2, 4])
def test_logits_bit_identical_sharded_vs_replicated(tiny, tp):
    """The tentpole invariant: the vocab-sharded embedding gather and
    head change NOTHING numerically — prefill and decode logits are
    bit-for-bit the replicated engine's."""
    prompt = [1, 5, 7, 9, 200, 31, 287, 2]
    on = _engine(tiny, tp, True)
    off = _engine(tiny, tp, False)
    assert on.shard_vocab and not off.shard_vocab
    a = on.fetch_logits(on.prefill(prompt))
    b = off.fetch_logits(off.prefill(prompt))
    assert np.array_equal(a, b)
    for tok in (3, 250):
        a = on.fetch_logits(on.step(np.asarray([[tok]], np.int32), on.pos))
        b = off.fetch_logits(off.step(np.asarray([[tok]], np.int32),
                                      off.pos))
        assert np.array_equal(a, b)


# -- sharded argmax ----------------------------------------------------------


@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_argmax_parity_and_pinned_tiebreak(tiny, tp):
    """Device argmax == np.argmax over the tokenizer vocab, with the
    tie-break rule pinned EXPLICITLY: the lowest global index among
    max-attaining tokens wins — within a shard via the local argmax's
    first-max rule, across shards because lower shards hold lower ids."""
    spec, _ = tiny
    eng = _engine(tiny, tp, True)
    v = spec.vocab_size
    rng = np.random.default_rng(0)
    rows = []
    r = rng.standard_normal(v).astype(np.float32)
    rows.append(r)
    # exact tie ACROSS shards: same max value planted in shard 0 and the
    # last shard — index 7 (shard 0) must win
    t = rng.standard_normal(v).astype(np.float32)
    t[7] = t[v - 5] = np.float32(9.5)
    rows.append(t)
    # exact tie WITHIN one shard: first occurrence wins
    w = rng.standard_normal(v).astype(np.float32)
    w[40] = w[41] = np.float32(8.25)
    rows.append(w)
    # tokenizer-vocab mask: a huge logit beyond n_vocab is ignored
    n_vocab = v - 30
    m = rng.standard_normal(v).astype(np.float32)
    m[v - 2] = np.float32(99.0)
    rows.append(m)
    lg = jnp.asarray(np.stack(rows))
    # pad the batch? sample_view takes (B, V) of any B — fine as-is
    view = _prep(eng, lg, None, n_vocab)
    for i, row in enumerate(rows):
        assert view.argmax(i, n_vocab) == int(np.argmax(row[:n_vocab]))
    assert view.argmax(1, n_vocab) == 7      # cross-shard tie: lowest id
    assert view.argmax(2, n_vocab) == 40     # in-shard tie: first max


# -- candidate top-k ---------------------------------------------------------


def test_candidates_contain_global_topk(tiny):
    """The distribution-exactness precondition, proven directly: the
    merged k·S candidate set contains the global top-k (the global i-th
    largest, i <= k, is within the top-i <= top-k of its own shard)."""
    spec, _ = tiny
    eng = _engine(tiny, 4, True)
    v, k = spec.vocab_size, eng.vocab_topk
    rng = np.random.default_rng(1)
    lg = jnp.asarray(rng.standard_normal((3, v)).astype(np.float32))
    view = _prep(eng, lg, np.full((3,), 0.8, np.float32), v)
    for i in range(3):
        top = np.argsort(-np.asarray(lg[i]), kind="stable")[:k]
        assert set(top.tolist()) <= set(view.cand_id[i].tolist())


@pytest.mark.parametrize("tp", [2, 4])
def test_topp_candidate_sampling_matches_oracle(tiny, tp):
    """Peaked logits: the guard holds, and the candidate scheme draws
    the SAME token as the host Sampler on the SAME coin stream —
    token-for-token over many seeds (the probabilities are the same
    real quantity to f32 rounding; the nucleus set and order are the
    oracle's exactly)."""
    spec, _ = tiny
    v = spec.vocab_size
    eng = _engine(tiny, tp, True)
    # robustly peaked: ~12-token nucleus spread across both shards —
    # well inside the per-shard top-k, so the guard provably holds
    rng = np.random.default_rng(3)
    row = (rng.standard_normal(v) * 0.5).astype(np.float32)
    for j, gid in enumerate((3, 17, 150, 160, 201, 44, 260, 9, 99, 180)):
        row[gid] += np.float32(6.0 - 0.2 * j)
    lg_dev = jnp.asarray(row[None, :])
    view = _prep(eng, lg_dev, np.asarray([0.8], np.float32), v)
    agree = 0
    for seed in range(200):
        s_sh = Sampler(v, 0.8, 0.9, seed=seed, backend="python")
        s_or = Sampler(v, 0.8, 0.9, seed=seed, backend="python")
        t_sh = view.sample(s_sh, 0)
        t_or = s_or.sample(row)
        assert t_sh == t_or, (seed, t_sh, t_or)
        agree += 1
    assert agree == 200
    assert eng.vocab_sample_stats["fallback"] == 0  # guard held — the
    # fast path served every draw


def test_flat_distribution_falls_back_exactly(tiny):
    """FLAT logits (high temperature): the nucleus outgrows the
    candidates, the guard refuses, and the view serves the draw from
    the replicated row fetch — still the oracle's exact token on the
    same coin (sample_candidates itself returns None, never a wrong
    distribution)."""
    spec, _ = tiny
    v = spec.vocab_size
    eng = _engine(tiny, 2, True)
    rng = np.random.default_rng(5)
    flat = rng.standard_normal((1, v)).astype(np.float32) * 0.01
    lg = jnp.asarray(flat)
    view = _prep(eng, lg, np.asarray([5.0], np.float32), v)
    # the raw candidate scheme must refuse (guard fails on a ~full-vocab
    # nucleus at k*S << nucleus size)
    s_probe = Sampler(v, 5.0, 0.97, seed=1, backend="python")
    assert sample_candidates(s_probe, view.cand_p[0], view.cand_id[0],
                             view.guard[0], int(view.amax[0])) is None
    for seed in range(20):
        s_sh = Sampler(v, 5.0, 0.97, seed=seed, backend="python")
        s_or = Sampler(v, 5.0, 0.97, seed=seed, backend="python")
        assert view.sample(s_sh, 0) == s_or.sample(flat[0])
    assert eng.vocab_sample_stats["fallback"] >= 20


def test_pure_multinomial_and_foreign_vocab_fall_back_exactly(tiny):
    """topp >= 1 (full multinomial) and a sampler truncating at a
    DIFFERENT vocab both take the per-row oracle fallback — exact
    parity with the host Sampler on the full row, same coins."""
    spec, _ = tiny
    v = spec.vocab_size
    eng = _engine(tiny, 2, True)
    rng = np.random.default_rng(9)
    row = rng.standard_normal(v).astype(np.float32)
    lg = jnp.asarray(row[None, :])
    view = _prep(eng, lg, np.asarray([0.8], np.float32), v)
    s_sh = Sampler(v, 0.8, 1.0, seed=3, backend="python")   # topp >= 1
    s_or = Sampler(v, 0.8, 1.0, seed=3, backend="python")
    assert view.sample(s_sh, 0) == s_or.sample(row)
    s2_sh = Sampler(200, 0.8, 0.9, seed=4, backend="python")  # vocab 200
    s2_or = Sampler(200, 0.8, 0.9, seed=4, backend="python")
    assert view.sample(s2_sh, 0) == s2_or.sample(row)
    assert view.argmax(0, 200) == int(np.argmax(row[:200]))


def test_draw_coin_matches_sampler_stream(tiny):
    """draw_coin consumes exactly the sampler's next xorshift uniform —
    the candidate path's one coin is the oracle's one coin."""
    a = Sampler(288, 0.8, 0.9, seed=77, backend="python")
    b = Sampler(288, 0.8, 0.9, seed=77, backend="python")
    c1 = draw_coin(a)
    c2 = b._coin()
    assert c1 == c2 and a.rng_state == b.rng_state


# -- serving paths -----------------------------------------------------------


def _serve(tiny, shard, temps, *, draft=True, prefix=True, freeze=False):
    from distributed_llama_tpu.runtime.prefix_cache import PrefixCache
    from distributed_llama_tpu.runtime.scheduler import Scheduler

    spec, _ = tiny
    eng = _engine(tiny, 2, shard, batch=2)
    pc = PrefixCache(eng, num_blocks=16, block_len=8) if prefix else None
    draft_factory = None
    if draft:
        from distributed_llama_tpu.runtime.draft import build_draft

        draft_factory = lambda e: build_draft(e, "self:1")  # noqa: E731
    sched = Scheduler(eng, chunk=16, prefix_cache=pc,
                      draft_factory=draft_factory,
                      draft_len=4 if draft else 0,
                      draft_vocab=spec.vocab_size)
    sched.warmup()
    frozen_before = COMPILES.after_warmup
    if freeze:
        COMPILES.freeze = True
    try:
        sys_prefix = list(range(40, 72))  # shared prefix: seeds the
        # radix cache for later requests (the seeded-prefix-cache path)
        prompts = [sys_prefix + [5 + i, 9, 3 + i] for i in range(6)]
        reqs = []
        for i, p in enumerate(prompts):
            smp = Sampler(spec.vocab_size, temps[i % len(temps)], 0.9,
                          seed=1000 + i, backend="python")
            reqs.append(sched.submit(p, 10, smp))
        while sched.step():
            pass
        outs = [list(r.tokens()) for r in reqs]
        frozen_delta = COMPILES.after_warmup - frozen_before
    finally:
        COMPILES.freeze = False
        sched.close()
    return outs, frozen_delta, dict(eng.vocab_sample_stats)


def test_scheduler_greedy_bit_identical_all_paths(tiny):
    """Greedy serving through the slot scheduler — chunked prefill,
    decode, the SEEDED-prefix-cache path (requests 2+ hit the shared
    prefix), and the speculative verify/accept path (self-draft armed)
    — emits BIT-IDENTICAL tokens sharded vs replicated, and the sharded
    run mints ZERO post-warmup compiles with the ledger FROZEN."""
    a, frozen, stats = _serve(tiny, True, [0.0], freeze=True)
    b, _, _ = _serve(tiny, False, [0.0])
    assert a == b
    assert frozen == 0
    assert stats.get("fallback", 0) == 0 and stats.get("sharded", 0) > 0


def test_scheduler_mixed_sampled_rows_deterministic(tiny):
    """Mixed greedy/sampled traffic: greedy rows stay bit-identical to
    the replicated engine; sampled rows are DETERMINISTIC across two
    sharded runs (fixed seeds — the candidate path consumes the same
    coins) and come from the candidate scheme, not the fallback."""
    a, frozen, stats = _serve(tiny, True, [0.0, 0.8], freeze=True)
    a2, _, _ = _serve(tiny, True, [0.0, 0.8])
    b, _, _ = _serve(tiny, False, [0.0, 0.8])
    assert a == a2                       # sampled determinism
    assert frozen == 0
    for i in range(0, 6, 2):
        assert a[i] == b[i]              # greedy rows: exact parity
    assert stats.get("sharded", 0) > 0


def test_generate_batch_stream_parity(tiny):
    """The batch-generate serving entry point: greedy batch rows are
    bit-identical sharded vs replicated (device argmax == np.argmax per
    row, same stop semantics)."""
    spec, _ = tiny
    prompts = [[1, 5, 9], [7, 2, 200, 31], [287, 3, 4]]

    def run(shard):
        eng = _engine(tiny, 2, shard, batch=3)
        smp = Sampler(spec.vocab_size, 0.0, 0.9, seed=5,
                      backend="python")
        return eng.generate_batch(prompts, 8, smp)

    assert run(True) == run(False)


def test_supervisor_tier_serves_on_tp_mesh(tiny):
    """The CLI-reachable path (PR-15 review finding): `dllama api
    --serve-batch N --tp T` builds the single-supervisor tier over the
    LAUNCHED mesh engine — build_front_door's engine factory must carry
    the mesh and the template's resolved shard_vocab decision through
    (rebuilds included), and the warmed sharded-sampling executables
    must serve greedy requests bit-identically to a replicated
    supervisor."""
    from distributed_llama_tpu.runtime.router import build_front_door

    spec, _ = tiny

    def run(shard):
        template = _engine(tiny, 2, shard, batch=1)
        sup = build_front_door(template, serve_batch=2, serve_chunk=16,
                               stall_timeout=60.0)
        try:
            eng = sup.engine
            assert eng.shard_vocab is shard  # the template's RESOLVED
            assert eng.mesh is template.mesh  # decision + mesh carried
            reqs = [sup.submit([1 + i, 5, 9], 8,
                               Sampler(spec.vocab_size, 0.0, 0.9,
                                       seed=50 + i, backend="python"))
                    for i in range(3)]
            return [list(r.tokens()) for r in reqs]
        finally:
            sup.close()

    assert run(True) == run(False)


# -- HBM ledger + auto-sizing ------------------------------------------------


def test_vocab_category_and_headroom_banked():
    """The freed bytes are REAL and BANKED: the ledger's `vocab`
    category shrinks under sharding (embedding per-chip = 1/tp), and
    `--serve-batch auto` / `--prefix-blocks auto` resolve to LARGER
    values for the sharded engine under the same byte budget."""
    from distributed_llama_tpu.runtime.profiler import resolve_auto_shape

    spec = _spec(vocab=2048, seq=64)
    host = random_tensors(spec, seed=2, scale=0.1)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    mesh = make_mesh(tp=2, dp=1)
    on = Engine(spec, dict(params), mesh, batch=1,
                compute_dtype=jnp.float32, cache_dtype=jnp.float32,
                shard_vocab=True)
    off = Engine(spec, dict(params), mesh, batch=1,
                 compute_dtype=jnp.float32, cache_dtype=jnp.float32,
                 shard_vocab=False)
    led_on = hbm_ledger(on, device_stats=False)
    led_off = hbm_ledger(off, device_stats=False)
    emb = spec.vocab_size * spec.dim * 4
    # off: full embedding + the (already row-split) head's half;
    # on: both halved — the embedding shard is exactly 1/tp
    assert led_off["vocab_bytes"] == emb + emb // 2
    assert led_on["vocab_bytes"] == emb // 2 + emb // 2
    assert led_on["weights_bytes"] == led_off["weights_bytes"]

    # bank the freed bytes: same byte budget, larger resolved shapes.
    # {"bytes_limit": L} without in_use -> the ledger models in_use as
    # its accounted bytes, so the sharded engine's smaller footprint IS
    # the headroom difference
    budget = led_off["accounted_bytes"] + 4 * led_off["per_slot_bytes"]
    dec_on = resolve_auto_shape(on, serve_batch="auto",
                                prefix_blocks="auto", prefix_block_len=8,
                                device_stats={"bytes_limit": budget})
    dec_off = resolve_auto_shape(off, serve_batch="auto",
                                 prefix_blocks="auto", prefix_block_len=8,
                                 device_stats={"bytes_limit": budget})
    assert dec_on["serve_batch"] > dec_off["serve_batch"]
    assert dec_on["prefix_blocks"] > dec_off["prefix_blocks"]


def test_shard_vocab_refuses_indivisible_mesh():
    """Explicit shard_vocab=True with a mesh that cannot split the
    vocab is a clear construction error (the dead-flag discipline)."""
    spec = _spec(vocab=289)  # prime-ish: not divisible by 2
    host = random_tensors(spec, seed=2, scale=0.1)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    mesh = make_mesh(tp=2, dp=1)
    with pytest.raises(AssertionError, match="shard_vocab"):
        Engine(spec, params, mesh, compute_dtype=jnp.float32,
               cache_dtype=jnp.float32, shard_vocab=True)
    # auto on a tp-less mesh simply stays off (dp-only: nothing to
    # split over — the replicated oracle serves)
    spec2 = _spec()
    host2 = random_tensors(spec2, seed=2, scale=0.1)
    params2 = load_params(spec2, host2, mode="dense", dtype=jnp.float32)
    eng = Engine(spec2, params2, make_mesh(tp=1, dp=2), batch=2,
                 compute_dtype=jnp.float32, cache_dtype=jnp.float32)
    assert not eng.shard_vocab
