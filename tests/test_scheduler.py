"""Continuous-batching scheduler parity (runtime/scheduler.py).

The contract under test: greedy continuous-batching output for N staggered
requests is TOKEN-IDENTICAL to N sequential Engine.generate runs — through
mid-decode joins, early finishes that hand a slot to a queued request, and
chunked prefill with padded tail chunks. f32 on the CPU mesh so the
batched scatter-write paths compare bit-exactly against the single-row
oracle (same discipline as tests/test_apps.py's batch fixtures).
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime.draft import DraftModel
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.scheduler import PromptTooLong, Scheduler
from distributed_llama_tpu.runtime.stats import WINDOW_COUNTERS
from distributed_llama_tpu.sampler import Sampler

SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=SEQ,
                     hidden_act=HiddenAct.SILU)
    host = random_tensors(spec, seed=3, scale=0.05)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    return spec, params


def _oracle(spec, params, prompt, max_tokens, eos_id=None):
    """Sequential single-row reference: a fresh batch=1 Engine.generate."""
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    r = eng.generate(prompt, max_tokens,
                     Sampler(spec.vocab_size, temperature=0.0, topp=0.9,
                             seed=1), eos_id=eos_id)
    return r.tokens


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)


def _drain(req):
    return list(req.tokens(timeout=5.0))


def _run_until_done(sched, reqs, limit=500):
    for _ in range(limit):
        if all(r.finished.is_set() for r in reqs):
            return
        sched.step()
    raise AssertionError("scheduler did not drain within the step limit")


def test_parity_staggered_joins_and_slot_reuse(tiny):
    """Three requests through a 2-slot scheduler: r1 joins mid-decode of
    r0, r2 queues until r1's early finish frees its slot — every output
    must equal the sequential oracle."""
    spec, params = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=4)

    p0 = [1, 9, 23, 54, 7, 88, 101, 5, 61, 17, 3]   # 3 padded chunks
    p1 = [2, 40, 77, 12, 9]
    p2 = [5, 66, 31, 90, 14, 8, 55]

    r0 = sched.submit(p0, 10, _greedy(spec))
    for _ in range(5):  # r0 prefills (3 chunks) and starts decoding
        sched.step()
    assert not r0.finished.is_set()

    r1 = sched.submit(p1, 4, _greedy(spec))   # joins mid-decode of r0
    r2 = sched.submit(p2, 6, _greedy(spec))   # queued: both slots busy
    _run_until_done(sched, [r0, r1, r2])

    assert _drain(r0) == _oracle(spec, params, p0, 10)
    assert _drain(r1) == _oracle(spec, params, p1, 4)
    assert _drain(r2) == _oracle(spec, params, p2, 6)
    assert r0.finish_reason == r1.finish_reason == r2.finish_reason == "length"
    # the batch never overflowed its slots and r2 really waited in queue
    assert max(sched.stats.occupancy) <= 2
    assert max(sched.stats.queue_depth) >= 1
    s = sched.stats.summary()
    assert s["requests_finished"] == 3
    assert s["tokens_out"] == 20
    assert s["ttft_p50_ms"] is not None and s["ttft_p50_ms"] >= 0


def test_parity_eos_early_finish(tiny):
    """A request whose greedy stream hits its stop token finishes early
    (stop token INCLUDED — Engine.generate parity) and frees the slot to
    a queued request whose output stays oracle-identical."""
    spec, params = tiny
    p0 = [1, 9, 23, 54, 7]
    p1 = [2, 40, 77, 12, 9, 31]
    base = _oracle(spec, params, p0, 8)
    eos = base[2]  # force an early stop three tokens in
    want0 = _oracle(spec, params, p0, 8, eos_id=eos)
    assert want0 == base[:3] and want0[-1] == eos

    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8)  # batch=1: p1 MUST wait for p0's slot
    r0 = sched.submit(p0, 8, _greedy(spec), eos_id=eos)
    r1 = sched.submit(p1, 5, _greedy(spec))
    _run_until_done(sched, [r0, r1])

    assert _drain(r0) == want0
    assert r0.finish_reason == "stop"
    assert _drain(r1) == _oracle(spec, params, p1, 5)
    assert max(sched.stats.occupancy) == 1


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "verify"])
def test_gated_rows_counts_the_slots_outside_every_program(tiny, draft):
    """`gated_rows`: rows passed at pos == seq_len, over every prefill
    chunk and decode (or verify) program — batch x programs less the rows
    that took part — and monotonic like every window counter."""
    spec, params = tiny
    batch, chunk = 4, 4
    eng = Engine(spec, params, batch=batch, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    kw = dict(draft_factory=lambda e: DraftModel.self_draft(e, 1),
              draft_len=2, draft_vocab=spec.vocab_size) if draft else {}
    sched = Scheduler(eng, chunk=chunk, **kw)
    assert "gated_rows" in WINDOW_COUNTERS
    p0 = [1, 9, 23, 54, 7, 88, 101, 5, 61, 17, 3]   # 3 chunks
    p1 = [2, 40, 77, 12, 9]                          # 2 chunks
    r0 = sched.submit(p0, 10, _greedy(spec))
    snaps = [sched.stats.summary()]
    for _ in range(5):
        sched.step()
        snaps.append(sched.stats.summary())
    r1 = sched.submit(p1, 4, _greedy(spec))          # joins mid-decode
    for _ in range(500):
        if r0.finished.is_set() and r1.finished.is_set():
            break
        sched.step()
        snaps.append(sched.stats.summary())
    assert _drain(r0) == _oracle(spec, params, p0, 10)
    assert _drain(r1) == _oracle(spec, params, p1, 4)

    s = snaps[-1]
    # a prompt takes one program ROW per chunk-wide segment: r0, alone,
    # all three in one program (chained); r1, beside a decoding row, too
    segments = sum(-(-len(p) // chunk) for p in (p0, p1))
    assert s["prefill_segments"] == segments
    assert s["prefill_rows"] == s["prefill_steps"] == 2
    programs = s["prefill_steps"] + s["decode_steps"]
    assert s["gated_rows"] == batch * programs - segments - s["decode_rows"]
    assert s["gated_rows"] >= programs              # never more than 3 live
    if not draft:
        assert s["decode_rows"] == s["tokens_out"] - s["admitted"] == 12
        # the first five iterations held r0 alone: 1 chunk program of 3
        # live rows, 4 decode steps
        assert snaps[5]["gated_rows"] == (batch - 3) + 4 * (batch - 1)
    for a, b in zip(snaps, snaps[1:]):
        assert b["gated_rows"] >= a["gated_rows"]
    sched.close()


def test_prompt_too_long_and_empty_rejected(tiny):
    spec, params = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng)
    with pytest.raises(PromptTooLong):
        sched.submit(list(range(1, SEQ + 1)), 4, _greedy(spec))
    with pytest.raises(ValueError):
        sched.submit([], 4, _greedy(spec))
    assert not sched.step()  # nothing was queued: no work


def test_budget_zero_prefills_and_emits_nothing(tiny):
    """max_tokens <= 0: prefill runs, nothing is emitted — the same
    hard-cap contract as Engine.generate."""
    spec, params = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=4)
    r = sched.submit([1, 9, 23], 0, _greedy(spec))
    _run_until_done(sched, [r])
    assert _drain(r) == []
    assert r.finish_reason == "length"


def test_threaded_loop_and_cancellation(tiny):
    """The background thread drains submissions; cancel() retires a
    request mid-stream and frees its slot to the next one."""
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8)
    sched.start()
    try:
        r0 = sched.submit([1, 9, 23, 54], 30, _greedy(spec))
        it = r0.tokens(timeout=60.0)
        got = [next(it), next(it)]
        r0.cancel()
        rest = list(it)
        assert got + rest == _oracle(spec, params, [1, 9, 23, 54], 30)[
            : len(got) + len(rest)]
        assert r0.finished.wait(60.0)
        assert r0.finish_reason == "cancelled"
        # the freed slot serves the next request with full parity
        r1 = sched.submit([2, 40, 77], 4, _greedy(spec))
        assert r1.finished.wait(60.0)
        assert _drain(r1) == _oracle(spec, params, [2, 40, 77], 4)
    finally:
        sched.close()


def test_exclusive_drains_then_lends_engine(tiny):
    """exclusive() finishes all in-flight work, then the borrower owns the
    engine (the legacy batch endpoint's path to the single live batched
    cache)."""
    spec, params = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8)
    r = sched.submit([1, 9, 23], 3, _greedy(spec))
    with sched.exclusive() as borrowed:
        assert borrowed is eng
        assert r.finished.is_set()
        borrowed.reset()  # all slots free: a reset cannot hurt anyone
    assert _drain(r) == _oracle(spec, params, [1, 9, 23], 3)


# -- a slot that prefills alone takes the chunk program's other rows ---------

CHUNK, ROWS, LONG = 32, 8, 1024


class Unchained(Engine):
    """An engine that gives a slot one row of a chunk program, as the engine
    of a model with a state layer, the latent cache or sharded rows does:
    the scheduler packs as it always did and passes no map. (This one's
    program is still the one with the map, fed the identity, so that a
    comparison with chaining is one of packings, in one executable: XLA's
    CPU code for the map-less program rounds the attention over more than
    256 cached positions differently.)"""

    prefill_rows_per_slot = 1


def _wide(arch=ArchType.LLAMA):
    if arch == ArchType.GRANITE_HYBRID:     # SSM x 2, attention, twice over
        from distributed_llama_tpu.testing import tiny_granite_spec

        spec = tiny_granite_spec(seq_len=LONG, vocab_size=128)
    else:
        moe = dict(n_experts=4, n_active_experts=2) \
            if arch == ArchType.MIXTRAL else {}
        spec = ModelSpec(arch=arch, dim=64, hidden_dim=128, n_layers=2,
                         n_heads=4, n_kv_heads=2, vocab_size=128,
                         seq_len=LONG, hidden_act=HiddenAct.SILU, **moe)
    host = random_tensors(spec, seed=5, scale=0.05)
    return spec, load_params(spec, host, mode="dense", dtype=jnp.float32)


@pytest.fixture(scope="module")
def wide():
    return {a: _wide(a) for a in (ArchType.LLAMA, ArchType.MIXTRAL,
                                  ArchType.GRANITE_HYBRID)}


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, 128, size=n)]


def _todays_arrays(batch, chunk, seq_len, rows):
    """The chunk program's arrays as the scheduler built them before rows
    could be chained: row == slot, one segment a slot. rows: (slot index,
    prompt, offset)."""
    tok = np.zeros((batch, chunk), np.int32)
    pos = np.full((batch,), seq_len, np.int32)
    lidx = np.zeros((batch,), np.int32)
    for idx, prompt, off in rows:
        n = min(chunk, len(prompt) - off)
        tok[idx, :n] = prompt[off:off + n]
        pos[idx] = off
        lidx[idx] = n - 1
    return tok, pos, lidx


# (prompts, offset of the first, engine, SLO rung, tokens of each program)
PACKING = {
    "alone_300_is_cut_evenly": ([300], 0, Engine, 0, [160, 140]),
    "alone_256_is_one_program": ([256], 0, Engine, 0, [256]),
    "alone_1000_is_four": ([1000], 0, Engine, 0, [256, 256, 256, 232]),
    "alone_33": ([33], 0, Engine, 0, [33]),
    "alone_32_is_one_segment": ([32], 0, Engine, 0, [32]),
    "alone_after_a_hit_of_whole_chunks": ([400], 64, Engine, 0, [192, 144]),
    "off_the_chunk_is_one_segment": ([100], 16, Engine, 0, [32, 32, 20]),
    "two_prefilling_keep_todays_arrays": ([70, 40], 0, Engine, 0,
                                          [64, 40, 6]),
    "an_engine_that_takes_no_map": ([100], 0, Unchained, 0, [32, 32, 32, 4]),
    "an_slo_rung_below_the_top": ([70], 0, Engine, 1, [16] * 4 + [6]),
}


@pytest.mark.parametrize("case", PACKING)
def test_prefill_packing(wide, case):
    """What `_prefill_chunk` hands the engine, program by program. One slot
    alone on an engine that chains: up to 8 consecutive segments, what is
    left cut EVENLY over the fewest programs, rows 0..k-1 live, the rest
    gated on the other slots. Otherwise (several slots, a start off the
    chunk, an engine without the map, an SLO rung below the top) exactly
    the arrays of before and no map. And the counters after it."""
    from distributed_llama_tpu.runtime.profiler import COMPILES
    from distributed_llama_tpu.runtime.scheduler import chain_map

    lens, off0, engine, rung, want_tokens = PACKING[case]
    spec, params = wide[ArchType.LLAMA]
    eng = engine(spec, params, batch=ROWS, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=CHUNK,
                      **({"slo_itl_ms": 1e9} if rung else {}))
    sched.warmup()
    compiles = COMPILES.after_warmup
    calls = []
    real = eng.slot_prefill_chunk

    def spy(tok, pos, lidx, slots=None, **sample):
        calls.append((tok.copy(), pos.copy(), lidx.copy(), slots))
        return real(tok, pos, lidx, slots, **sample)

    eng.slot_prefill_chunk = spy
    prompts = [_prompt(n, seed=n) for n in lens]
    reqs = [sched.submit(p, 1, _greedy(spec)) for p in prompts]
    sched._admit()
    sched.slots[0].off = off0           # as after a prefix hit of off0 tokens
    if rung:
        sched.admission._rung, sched.admission.cooldown = rung, 10 ** 9
    width = CHUNK >> rung
    offs = [off0] + [0] * (len(lens) - 1)
    chains = (engine is Engine and len(lens) == 1 and not rung
              and off0 % CHUNK == 0)
    _run_until_done(sched, reqs)
    assert [int((c[1] < LONG).sum() and sum(
        c[2][c[1] < LONG] + 1)) for c in calls] == want_tokens
    segments = 0
    for (tok, pos, lidx, slots), n_tok in zip(calls, want_tokens):
        live = [(i, p, o) for i, (p, o) in enumerate(zip(prompts, offs))
                if o < len(p)]
        k = -(-n_tok // width) if chains else len(live)
        segments += k
        if chains and k > 1:
            p, o = prompts[0], offs[0]
            np.testing.assert_array_equal(slots, chain_map(0, k, ROWS))
            np.testing.assert_array_equal(pos[:k], o + CHUNK * np.arange(k))
            assert (pos[k:] == LONG).all()
            flat = tok[:k].reshape(-1)
            np.testing.assert_array_equal(flat[:n_tok], p[o:o + n_tok])
            assert not flat[n_tok:].any() and not tok[k:].any()
            np.testing.assert_array_equal(
                lidx[:k], [CHUNK - 1] * (k - 1) + [n_tok - CHUNK * (k - 1) - 1])
            offs[0] += n_tok
        else:
            assert slots is None
            for got, want in zip((tok, pos, lidx), _todays_arrays(
                    ROWS, width, LONG, live)):
                np.testing.assert_array_equal(got, want)
            offs = [min(o + width, len(p)) for p, o in zip(prompts, offs)]
    s = sched.stats.summary()
    assert s["prefill_steps"] == len(want_tokens)
    assert s["prefill_segments"] == segments
    # slots that took part, a program: a chained slot once, however many
    # rows it took
    assert s["prefill_rows"] == (len(want_tokens) if chains else sum(
        -(-(len(p) - o) // width)
        for p, o in zip(prompts, [off0] + [0] * len(lens))))
    assert s["gated_rows"] == ROWS * len(want_tokens) - segments
    assert s["decode_steps"] == 0       # max_tokens 1: the prefill's token
    assert s["prefill_tokens"] == sum(want_tokens)
    assert s["attn_pairs_prefill"] == sum(
        len(p) * (len(p) + 1) // 2 - o * (o + 1) // 2
        for p, o in zip(prompts, [off0] + [0] * len(lens)))
    assert COMPILES.after_warmup == compiles
    assert len([k for k in eng._steps if k[0] == "slot_prefill"]) == (
        4 if rung else 1)
    sched.close()


def _serve(engine, spec, params, prompt, *, shared=0, n_out=4, kernels=False):
    """Serve `prompt` greedily on slot 0 of a fresh engine (after a request
    that publishes its first `shared` tokens, where shared > 0): the tokens,
    the logits behind each of them (the first: the last prompt token's), the
    counters, and the bits of every OTHER slot's cache leaves (rows, states
    and tails) before and after."""
    from distributed_llama_tpu.runtime.prefix_cache import PrefixCache

    eng = engine(spec, params, batch=ROWS, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32, use_pallas=kernels,
                 pallas_interpret=kernels)
    pc = (PrefixCache(eng, num_blocks=32, block_len=CHUNK) if shared
          else None)
    sched = Scheduler(eng, chunk=CHUNK, prefix_cache=pc)
    if shared:
        first = sched.submit(prompt[:shared] + _prompt(40, seed=99), 1,
                             _greedy(spec))
        _run_until_done(sched, [first])
    # the other slots hold something to lose
    noise = [tuple(jnp.asarray(np.random.default_rng(i).standard_normal(
        x.shape).astype(np.float32)).at[0].set(x[0])
        for i, x in enumerate(leaf)) for leaf in eng.cache]
    eng.cache = type(eng.cache)(*noise)
    before = [np.asarray(x[1:]) for leaf in noise for x in leaf]
    logits = []
    view = sched._sample_view

    def spy(lg, rows, at=None):
        logits.append(np.asarray(lg)[(at or [s.idx for s in rows])[0]])
        return view(lg, rows, at=at)

    sched._sample_view = spy
    base = sched.stats.summary()
    req = sched.submit(prompt, n_out, _greedy(spec))
    _run_until_done(sched, [req])
    assert req.stats.n_out == n_out
    after = [np.asarray(x[1:]) for leaf in eng.cache for x in leaf]
    s = sched.stats.summary()
    moved = {k: s[k] - base[k] for k in ("prefill_steps", "prefill_rows",
                                         "prefill_segments",
                                         "prefill_tokens")}
    sched.close()
    return _drain(req), logits, moved, before, after


_LENGTHS = [(31, 0), (32, 0), (33, 0), (33, 32), (256, 0), (256, 64),
            (300, 0), (300, 64), (700, 0), (700, 64)]   # (n, a prefix hit of)


@pytest.mark.parametrize("arch,n,shared,kernels", [
    *((a, n, sh, False) for a in (ArchType.LLAMA, ArchType.MIXTRAL)
      for n, sh in _LENGTHS),
    # a state has no prefix arena; 210: seven segments, the last a tail
    *((ArchType.GRANITE_HYBRID, n, 0, False)
      for n in (31, 33, 210, 256, 300, 700)),
    (ArchType.GRANITE_HYBRID, 210, 0, True),
], ids=lambda v: v.name.lower() if isinstance(v, ArchType) else
    "interpret" if v is True else "" if v is False else str(v))
def test_chained_prefill_is_bit_equal_to_a_segment_an_iteration(
        wide, arch, n, shared, kernels):
    """Every op of the chunk program is independent across its token rows
    but attention over the cache, and a chained row attends exactly what
    the rows before it wrote; in a state layer (granite's SSM mixers) it
    starts from the final state and the convolution's tail of the row it
    continues. A prompt prefilled up to eight segments a program gives, BIT
    FOR BIT (float32, CPU; the XLA twins, and for one length the kernels
    interpreted), the logits at its last token and after every decode
    step, and so the tokens, of the same prompt prefilled one segment an
    iteration, with the same `/stats` `prefill_segments`; and no other
    slot's cache, state or tail changes by a bit."""
    spec, params = wide[arch]
    prompt = _prompt(n, seed=n + shared)
    got = _serve(Engine, spec, params, prompt, shared=shared, kernels=kernels)
    want = _serve(Unchained, spec, params, prompt, shared=shared,
                  kernels=kernels)
    assert got[0] == want[0]
    assert len(got[1]) == len(want[1]) == 4
    for a, b in zip(got[1], want[1]):
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    left = n - shared
    segments = -(-left // CHUNK)
    assert want[2] == {"prefill_steps": segments, "prefill_rows": segments,
                       "prefill_segments": segments, "prefill_tokens": left}
    programs = -(-segments // ROWS)
    assert got[2] == {"prefill_steps": programs, "prefill_rows": programs,
                      "prefill_segments": segments, "prefill_tokens": left}
    for run in (got, want):
        for x, y in zip(run[3], run[4]):
            np.testing.assert_array_equal(x.view(np.uint32),
                                          y.view(np.uint32))


@pytest.mark.parametrize("model,mesh,rows", [
    ("llama", None, ROWS), ("mixtral", None, ROWS),
    ("granite_hybrid", None, ROWS), ("olmo_hybrid", None, 1),
    ("sarvam_mla", None, 1), ("llama", {"dp": 2}, 1), ("llama", {"tp": 2}, 1),
], ids=lambda v: "-".join(f"{k}{n}" for k, n in v.items())
    if isinstance(v, dict) else None)
def test_who_may_chain_is_decided_from_the_model_and_the_mesh(model, mesh,
                                                              rows):
    """`Engine.prefill_rows_per_slot`: the whole batch where the rows are
    not sharded, every cache leaf is the dense K/V cache and every state
    layer is of a kind whose mixer chains (SSM: granite); 1 for a model
    with a delta-rule layer or the latent cache and on any mesh, whose
    chunk program takes no map at all
    (`models/transformer.takes_slot_map`)."""
    from distributed_llama_tpu import testing
    from distributed_llama_tpu.parallel.mesh import make_mesh

    spec = {"llama": testing.tiny_spec,
            "mixtral": lambda **kw: testing.tiny_spec(
                arch=ArchType.MIXTRAL, n_experts=4, n_active_experts=2, **kw),
            "olmo_hybrid": testing.tiny_hybrid_spec,
            "granite_hybrid": testing.tiny_granite_spec,
            "sarvam_mla": testing.tiny_mla_spec}[model](seq_len=SEQ)
    params = load_params(spec, random_tensors(spec, seed=2, scale=0.05),
                         mode="dense", dtype=jnp.float32)
    eng = Engine(spec, params, batch=ROWS, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32,
                 mesh=make_mesh(**mesh) if mesh else None)
    assert eng.prefill_rows_per_slot == rows
    gate = np.full((ROWS,), SEQ, np.int32)
    call = (np.zeros((ROWS, 8), np.int32), gate, np.zeros((ROWS,), np.int32))
    eng.slot_prefill_chunk(*call)
    if rows == 1:
        with pytest.raises(AssertionError, match="takes no map"):
            eng.slot_prefill_chunk(*call, np.arange(ROWS, dtype=np.int32))
    else:
        eng.slot_prefill_chunk(*call, np.arange(ROWS, dtype=np.int32)[::-1])
    # one chunk executable an engine, with or without the map
    assert [k for k in eng._steps if k != "cache_maker"] == [
        ("slot_prefill", 8)]
