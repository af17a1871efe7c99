"""The sampling summary of the slot step programs (PR 53).

An engine without a mesh ends both slot step programs with
ops/sharded_vocab.sample_summary over the logits they hold: the argmax,
the 512 exact top probabilities a row at the row's temperature and their
ids, one packed leaf. Engine.sample_view serves the step's rows from that
leaf alone where the candidates prove the row, and from ONE fetch of the
whole (B, vocab) array for every row they cannot.

The contract under test, against the parity oracle (FullLogitsView on the
fetched logits: the host Sampler, a row at a time, as every step sampled
before):

  * token AND rng_state equal the oracle's, row by row, over peaked, flat
    and tied logits, greedy and sampled, nucleus and pure multinomial;
  * `sampled_rows_summary` counts a row only where the summary proved it,
    by a proof written here independently of the view;
  * a step with several unproven rows fetches the array once and
    dispatches nothing a row; a step dispatched without temperatures (a
    mid-prompt chunk, the benchmark's check: no row of it is sampled)
    computes no summary, in the same executable;
  * a served run with the compile ledger FROZEN, sampled and greedy
    requests mixed, mints no key after warm-up; a chained finishing chunk
    samples from its own program row.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.ops.sharded_vocab import SUMMARY_TOPK
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.profiler import COMPILES
from distributed_llama_tpu.runtime.sampling import FullLogitsView
from distributed_llama_tpu.runtime.scheduler import Scheduler
from distributed_llama_tpu.runtime.stats import WINDOW_COUNTERS, ServeStats
from distributed_llama_tpu.sampler import Sampler

B, SEQ, VOCAB = 8, 64, 8192
N_VOCAB = VOCAB - 9         # the tokenizer's: the head's last rows are padding
STEPS = 40                  # x 8 rows: a few hundred rows a case
STD = {"peaked": 4.0, "flat": 1.5, "ties": 4.0}


@pytest.fixture(scope="module", autouse=True)
def _drop_programs():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def engines():
    """An engine a shape of logits: the tiny model's head scaled so that
    a decode step's logits have the standard deviation of a trained head
    (~4: a 0.9 nucleus of tens of tokens), of a drawn one (~1.5: a
    nucleus wider than any summary), or hold every value twice (ties at
    the top, at the crossing and at the guard)."""
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=VOCAB, seq_len=SEQ,
                     hidden_act=HiddenAct.SILU)
    host = random_tensors(spec, seed=5, scale=0.05)
    made = {}

    def make(shape: str) -> Engine:
        if shape not in made:
            params = load_params(spec, host, mode="dense", dtype=jnp.float32)
            eng = Engine(spec, dict(params), batch=B,
                         compute_dtype=jnp.float32, cache_dtype=jnp.float32)
            lg = eng.fetch_logits(eng.slot_decode_step(
                np.arange(B, dtype=np.int32)[:, None] + 1,
                np.zeros((B,), np.int32)))
            w = np.asarray(params["wcls"]) * (STD[shape] / lg.std())
            if shape == "ties":
                w[1::2] = w[0::2]
            eng.params = dict(eng.params, wcls=jnp.asarray(w))
            made[shape] = eng
        return made[shape]

    return spec, make


def _fresh(eng: Engine) -> Engine:
    """A new cache."""
    eng.cache = eng._new_cache()
    return eng


def _provable(row: np.ndarray, temp: float, topp: float) -> bool:
    """Whether SUMMARY_TOPK candidates prove this row's nucleus, written
    from the argument and not from the view: every token that is no
    candidate lies at or below the k-th largest probability (the guard),
    so the walk is the oracle's if the element at which the cumulative
    mass crosses topp lies strictly above the guard, or if the guard lies
    under the cut-off and no hidden token passes the filter at all."""
    if temp == 0.0:
        return True                     # the device argmax
    if not 0.0 < topp < 1.0:
        return False                    # the whole CDF
    x = row.astype(np.float32) / np.float32(temp)
    x[N_VOCAB:] = -np.inf
    e = np.exp(x - x.max())
    p = np.sort((e / e.sum()).astype(np.float32))[::-1]
    guard = p[SUMMARY_TOPK - 1]
    cutoff = (1.0 - topp) / (N_VOCAB - 1)
    if guard < cutoff:
        return True
    cand = p[:SUMMARY_TOPK]
    cand = cand[cand >= cutoff]
    over = np.nonzero(np.cumsum(cand.astype(np.float64)) > topp)[0]
    return bool(over.size) and bool(cand[over[0]] > guard)


@pytest.mark.parametrize("topp", [0.5, 0.9, 1.0])
@pytest.mark.parametrize("temp", [0.0, 0.8, 1.3])
@pytest.mark.parametrize("shape", ["peaked", "flat", "ties"])
def test_the_summary_view_gives_the_oracles_token_and_rng_state(
        engines, shape, temp, topp):
    """Every row of STEPS decode steps: the view built from the step's own
    summary against FullLogitsView on the same step's fetched logits, two
    samplers of one seed a row. The counters: every row counted, a row
    counted as served from the summary exactly where `_provable` says the
    candidates prove it."""
    spec, make = engines
    eng = _fresh(make(shape))
    mine = [Sampler(N_VOCAB, temp, topp, seed=100 + r, backend="python")
            for r in range(B)]
    oracle = [Sampler(N_VOCAB, temp, topp, seed=100 + r, backend="python")
              for r in range(B)]
    temps = np.full((B,), temp or 1.0, np.float32)
    window = ServeStats()
    tok = np.arange(B, dtype=np.int32)[:, None] + 1
    proven = 0
    for step in range(STEPS):
        pos = np.full((B,), step % SEQ, np.int32)
        lg = eng.slot_decode_step(tok, pos, temps=temps, n_vocab=N_VOCAB)
        view = eng.sample_view(lg, temps, N_VOCAB)
        assert view.sharded
        view.window = window
        full = eng.fetch_logits(lg)
        ref = FullLogitsView(full)
        for r in range(B):
            got = view.sample(mine[r], r)
            assert got == ref.sample(oracle[r], r), (step, r)
            assert mine[r].rng_state == oracle[r].rng_state, (step, r)
            proven += _provable(full[r], temp, topp)
            tok[r, 0] = got
    assert window.sampled_rows == STEPS * B
    assert window.sampled_rows_summary == proven
    if temp == 0.0 or (shape != "flat" and topp < 1.0 and temp < 1.0):
        assert proven >= 0.95 * STEPS * B       # the mechanism engages
    if temp and (topp == 1.0 or (shape == "flat" and topp == 0.9)):
        assert proven == 0                      # and says when it cannot


def test_a_temperature_the_step_was_not_dispatched_with_is_not_proven(
        engines):
    """The candidates stand at the temperatures of the dispatch: a sampler
    of another temperature reads the fetched logits, as the oracle does."""
    spec, make = engines
    eng = _fresh(make("peaked"))
    tok = np.ones((B, 1), np.int32)
    pos = np.zeros((B,), np.int32)
    temps = np.full((B,), 0.8, np.float32)
    lg = eng.slot_decode_step(tok, pos, temps=temps, n_vocab=N_VOCAB)
    view = eng.sample_view(lg, temps, N_VOCAB)
    view.window = window = ServeStats()
    a, b = (Sampler(N_VOCAB, 0.5, 0.9, seed=3, backend="python")
            for _ in range(2))
    assert view.sample(a, 0) == FullLogitsView(
        eng.fetch_logits(lg)).sample(b, 0)
    assert a.rng_state == b.rng_state
    assert (window.sampled_rows, window.sampled_rows_summary) == (1, 0)


def test_unproven_rows_share_one_fetch_of_the_whole_array(engines,
                                                          monkeypatch):
    """Eight rows no summary proves (flat logits, a 0.9 nucleus): the
    first fetches the (B, vocab) array, the others read it; no executable
    is minted or run for a row (no `vrow` key), and a step whose rows are
    all proven fetches nothing but the summary."""
    spec, make = engines
    fetched = []
    for shape, n_fetch in (("flat", 1), ("peaked", 0)):
        eng = _fresh(make(shape))
        real = eng.fetch_logits
        monkeypatch.setattr(
            eng, "fetch_logits",
            lambda lg, real=real: fetched.append(lg.shape) or real(lg))
        keys = set(eng._steps)
        temps = np.full((B,), 0.8, np.float32)
        lg = eng.slot_decode_step(np.ones((B, 1), np.int32),
                                  np.zeros((B,), np.int32), temps=temps,
                                  n_vocab=N_VOCAB)
        view = eng.sample_view(lg, temps, N_VOCAB)
        fetched.clear()
        for r in range(B):
            view.sample(Sampler(N_VOCAB, 0.8, 0.9, seed=r,
                                backend="python"), r)
        assert fetched == [(B, VOCAB)] * n_fetch, shape
        assert set(eng._steps) == keys


def test_a_step_without_temperatures_computes_no_summary(engines):
    """No temperatures at the dispatch says that no row of the step will
    be sampled: the step leaves no summary (its device work sits behind a
    traced flag of the SAME executable, and nothing is fetched) and its
    logits, if someone samples them after all, take the full view."""
    spec, make = engines
    eng = _fresh(make("peaked"))
    tok, pos = np.ones((B, 1), np.int32), np.zeros((B,), np.int32)
    temps = np.full((B,), 0.8, np.float32)
    with_it = eng.slot_decode_step(tok, pos, temps=temps, n_vocab=N_VOCAB)
    assert eng._step_summary[0] is with_it
    steps = eng._steps["slot_decode"]
    without = eng.slot_decode_step(tok, pos)
    assert eng._step_summary is None
    assert not eng.sample_view(without, temps, N_VOCAB).sharded
    np.testing.assert_array_equal(eng.fetch_logits(with_it),
                                  eng.fetch_logits(without))
    assert eng._steps["slot_decode"] is steps and steps._cache_size() == 1


def test_only_a_finishing_chunk_is_dispatched_with_temperatures(engines):
    """A prompt of four dispatches (each chains the engine's rows): the
    three mid-prompt ones sample no row and are dispatched without
    temperatures (no summary, no fetch), the finishing one with the
    request's at its row."""
    spec, make = engines
    eng = _fresh(make("peaked"))
    sched = Scheduler(eng, chunk=2, sample_vocab=N_VOCAB)
    seen = []
    real = eng.slot_prefill_chunk

    def spy(tok, pos, lidx, *slots, **sample):
        seen.append(sample.get("temps"))
        return real(tok, pos, lidx, *slots, **sample)

    eng.slot_prefill_chunk = spy
    rows = eng.prefill_rows_per_slot
    prompt = [int(t) for t in np.random.default_rng(2).integers(
        1, 99, 2 * rows * 3 + 5)]
    req = sched.submit(prompt, 1, Sampler(N_VOCAB, 0.8, 0.9, seed=4,
                                          backend="python"))
    while sched.step():
        pass
    sched.close()
    assert len(list(req.tokens(timeout=5.0))) == 1
    assert len(seen) == 4 and seen[:3] == [None] * 3
    assert np.count_nonzero(seen[3] != 1.0) == 1
    assert sched.stats.sampled_rows_summary == 1


def test_logits_without_a_summary_take_the_full_view(engines):
    """A step's summary belongs to that step's logits: other logits (a
    verify step's position 0, an older step's) and another vocabulary are
    sampled from the fetched array, as before."""
    spec, make = engines
    eng = _fresh(make("peaked"))
    tok, pos = np.ones((B, 1), np.int32), np.zeros((B,), np.int32)
    temps = np.ones((B,), np.float32)
    old = eng.slot_decode_step(tok, pos, temps=temps, n_vocab=N_VOCAB)
    new = eng.slot_decode_step(tok, pos + 1, temps=temps, n_vocab=N_VOCAB)
    assert not eng.sample_view(old, None, N_VOCAB).sharded
    assert not eng.sample_view(new, None, N_VOCAB - 1).sharded
    view = eng.sample_view(new, None, N_VOCAB)
    assert view.sharded
    full = eng.fetch_logits(new)
    assert [view.argmax(r, N_VOCAB) for r in range(B)] == [
        int(np.argmax(full[r, :N_VOCAB])) for r in range(B)]


def test_the_check_and_the_scheduler_enter_one_executable(engines):
    """The benchmark's check calls both programs without temperatures or a
    vocabulary, the scheduler with both: traced operands, one executable a
    program, and the methods return the logits array alone."""
    spec, make = engines
    eng = make("peaked")
    tok, pos = np.ones((B, 1), np.int32), np.zeros((B,), np.int32)
    chunk = np.ones((B, 8), np.int32)
    eng.slot_decode_step(tok, pos)
    eng.slot_prefill_chunk(chunk, pos, np.zeros((B,), np.int32))
    steps = (eng._steps["slot_decode"], eng._steps["slot_prefill", 8])
    lg = eng.slot_decode_step(tok, pos, temps=np.full((B,), 0.7, np.float32),
                              n_vocab=N_VOCAB)
    lp = eng.slot_prefill_chunk(chunk, pos, np.full((B,), 7, np.int32),
                                temps=np.full((B,), 1.3, np.float32),
                                n_vocab=N_VOCAB - 1)
    assert [fn._cache_size() for fn in steps] == [1, 1]
    assert lg.shape == lp.shape == (B, VOCAB)
    assert eng.fetch_logits(lg).dtype == np.float32


@pytest.mark.parametrize("shape", ["peaked", "flat"])
def test_a_frozen_served_run_mixes_sampled_and_greedy_requests(engines,
                                                                shape):
    """Scheduler.warmup, then the ledger FROZEN: greedy and sampled
    requests mixed, prompts long enough that one prefills alone and its
    segments are chained (the finishing row is then not its slot's), mint
    no key; every emitted token is counted in `sampled_rows`; /stats
    carries both counters."""
    spec, make = engines
    eng = _fresh(make(shape))
    sched = Scheduler(eng, chunk=8, sample_vocab=N_VOCAB)
    sched.warmup()
    before = COMPILES.after_warmup
    COMPILES.freeze = True
    try:
        rng = np.random.default_rng(0)
        reqs = []
        for i, n in enumerate((30, 5, 17, 9)):
            smp = Sampler(N_VOCAB, (0.0, 0.8)[i % 2], 0.9, seed=40 + i,
                          backend="python")
            reqs.append(sched.submit(
                [int(t) for t in rng.integers(1, N_VOCAB, n)], 6, smp))
            for _ in range(3):      # the first prefills alone: chained
                sched.step()
        while sched.step():
            pass
        outs = [list(r.tokens(timeout=5.0)) for r in reqs]
    finally:
        COMPILES.freeze = False
        sched.close()
    assert COMPILES.after_warmup == before
    assert [len(o) for o in outs] == [6] * 4
    s = sched.stats.summary()
    assert {"sampled_rows", "sampled_rows_summary"} <= set(WINDOW_COUNTERS)
    assert s["sampled_rows"] == s["tokens_out"] == 24
    # greedy rows are the device argmax whatever the logits; sampled rows
    # are proven on a trained head's logits and not on flat ones
    assert s["sampled_rows_summary"] == (24 if shape == "peaked" else 12)
    assert sched.stats.prefill_segments > sched.stats.prefill_rows


def test_a_chained_finishing_chunk_samples_from_its_own_program_row(engines):
    """A prompt that prefills alone in chained segments finishes in program
    row k - 1, not in its slot's row: the token is the oracle's on THAT
    row of the chunk's logits, sampled at the request's temperature (the
    dispatch put it at that row of `temps`)."""
    spec, make = engines
    eng = _fresh(make("peaked"))
    sched = Scheduler(eng, chunk=8, sample_vocab=N_VOCAB)
    seen = []
    real = eng.slot_prefill_chunk

    def spy(tok, pos, lidx, slots=None, **sample):
        lg = real(tok, pos, lidx, slots, **sample)
        seen.append((sample["temps"].copy(), slots, eng.fetch_logits(lg)))
        return lg

    eng.slot_prefill_chunk = spy
    prompt = [int(t) for t in np.random.default_rng(1).integers(1, 99, 20)]
    req = sched.submit(prompt, 1, Sampler(N_VOCAB, 0.8, 0.9, seed=9,
                                          backend="python"))
    while sched.step():
        pass
    sched.close()
    (temps, slots, logits), = seen
    assert slots is not None                    # three chained segments
    assert list(np.nonzero(temps != 1.0)[0]) == [2]
    assert temps[2] == np.float32(0.8)
    want = Sampler(N_VOCAB, 0.8, 0.9, seed=9, backend="python").sample(
        logits[2])
    assert list(req.tokens(timeout=5.0)) == [int(want)]
    assert sched.stats.sampled_rows_summary == 1


def test_the_manifest_ends_with_the_share_as_a_data_file():
    """What PR 53 appended to the benchmark: ONE per-layer metric, last in
    its list, whose file names a reader the benchmark had
    (`stats_delta_opt`, which leaves the metric out where a server has no
    such counters: the parent) over the two new window counters; every
    cell reports `itl_p50_ms` and so lists it."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = m["per_layer"][-2]  # PR 54's counter metric came after it
    with open(os.path.join(repo, "benchmark", "layer_metrics",
                           "sample_summary_share.json")) as f:
        spec = json.load(f)
    assert entry == {**{k: spec[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")},
        "workloads": [w["name"] for w in m["workloads"]]}
    assert (entry["name"], entry["unit"], entry["better"], entry["moves"]) == (
        "sample_summary_share", "%", "higher", "itl_p50_ms")
    assert entry["layer"] == "scheduler (runtime/scheduler.py)"
    assert spec["reader"] == "stats_delta_opt"
    assert spec["args"] == {"num": "sampled_rows_summary",
                            "den": "sampled_rows", "scale": 100}
    assert {spec["args"]["num"], spec["args"]["den"]} <= set(WINDOW_COUNTERS)
