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
    # a prompt's row takes part in one chunk program per chunk
    prefill_rows = sum(-(-len(p) // chunk) for p in (p0, p1))
    programs = s["prefill_steps"] + s["decode_steps"]
    assert s["gated_rows"] == batch * programs - prefill_rows - s["decode_rows"]
    assert s["gated_rows"] >= 2 * programs          # never more than 2 live
    if not draft:
        assert s["decode_rows"] == s["tokens_out"] - s["admitted"] == 12
        # the first five iterations held r0 alone: 3 chunks, 2 decode steps
        assert snaps[5]["gated_rows"] == (3 + 2) * (batch - 1)
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
