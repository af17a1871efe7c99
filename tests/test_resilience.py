"""Serving resilience (runtime/faults.py + runtime/resilience.py).

The chaos contract under test: with an injected step crash mid-decode,
in-flight requests receive STRUCTURED error frames, the supervisor
rebuilds the engine, readiness flips unready -> ready, and a subsequent
request completes with output token-identical to a sequential
Engine.generate run. The watchdog detects an injected stall within its
configured bound (seconds — not the 600 s client timeout); queue overflow
and deadlines return fast structured rejections. Everything runs on CPU
with count-deterministic fault injection, so every failure shape the TPU
platform has produced (crash, hang, slow step) is reproducible in CI.

f32 on the CPU mesh so the parity assertions compare bit-exactly against
the single-row oracle (same discipline as tests/test_scheduler.py).
"""

import threading
import time

import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.faults import FAULTS, FaultError, FaultRegistry
from distributed_llama_tpu.runtime.resilience import (
    BROKEN, READY, RECOVERING, EngineSupervisor, EngineUnready)
from distributed_llama_tpu.runtime.scheduler import (
    QueueFull, RequestError, Scheduler, SchedulerClosed)
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


@pytest.fixture(autouse=True)
def clean_faults():
    FAULTS.clear()
    yield
    FAULTS.clear()


def _factory(tiny, batch=2):
    spec, params = tiny

    def make():
        return Engine(spec, params, batch=batch, compute_dtype=jnp.float32,
                      cache_dtype=jnp.float32)

    return make


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)


def _oracle(spec, params, prompt, max_tokens):
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    return eng.generate(prompt, max_tokens,
                        Sampler(spec.vocab_size, temperature=0.0, topp=0.9,
                                seed=1)).tokens


def _wait(pred, timeout=30.0, poll=0.01):
    end = time.perf_counter() + timeout
    while time.perf_counter() < end:
        if pred():
            return True
        time.sleep(poll)
    return False


# -- the fault registry itself ------------------------------------------


def test_fault_registry_count_deterministic():
    r = FaultRegistry()
    r.arm("step_raise", after=2, times=2)
    r.fire("step_raise")  # hit 1: skipped
    r.fire("step_raise")  # hit 2: skipped
    with pytest.raises(FaultError):
        r.fire("step_raise")  # hit 3: fires
    with pytest.raises(FaultError):
        r.fire("step_raise")  # hit 4: fires (times=2 spent)
    r.fire("step_raise")  # hit 5: disarmed by times
    assert r.fired("step_raise") == 2
    r.clear()
    r.fire("step_raise")  # cleared: no-op


def test_fault_registry_env_parsing():
    r = FaultRegistry()
    r.load_env({"DLLAMA_FAULTS": "step_raise:after=1;times=3, slow_step:ms=5;times=0"})
    assert r.armed("step_raise") and r.armed("slow_step")
    r.fire("step_raise")  # after=1: first hit skipped
    with pytest.raises(FaultError):
        r.fire("step_raise")
    t0 = time.perf_counter()
    r.fire("slow_step")
    assert time.perf_counter() - t0 >= 0.004
    with pytest.raises(ValueError):
        FaultRegistry().load_env({"DLLAMA_FAULTS": "step_raise:bogus=1"})
    with pytest.raises(ValueError):
        FaultRegistry().load_env({"DLLAMA_FAULTS": "no_such_site"})


def test_fault_stall_releasable():
    r = FaultRegistry()
    r.arm("step_stall", ms=60_000)
    done = threading.Event()

    def stallee():
        r.fire("step_stall")
        done.set()

    t = threading.Thread(target=stallee, daemon=True)
    t.start()
    assert not done.wait(0.1)
    r.release()
    assert done.wait(5.0)


# -- scheduler-level: close(), deadlines, queue bound -------------------


def test_scheduler_close_fails_queued_waiters(tiny):
    """Regression (ISSUE 3 satellite): close() must fail queued AND
    in-flight requests so no waiter outlives close — pre-fix, queued
    submitters hung in tokens() until the 600 s timeout."""
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8)
    FAULTS.arm("slow_step", times=0, ms=30.0)  # keep work in flight so
    # close() provably lands while requests are live
    sched.start()
    # one in-flight + two queued beyond the single slot
    reqs = [sched.submit([1, 9, 23], 200, _greedy(spec)) for _ in range(3)]
    results: dict = {}

    def waiter(i, req):
        try:
            results[i] = ("ok", list(req.tokens(timeout=30.0)))
        except RequestError as e:
            results[i] = ("error", e.code)

    threads = [threading.Thread(target=waiter, args=(i, r), daemon=True)
               for i, r in enumerate(reqs)]
    for t in threads:
        t.start()
    _wait(lambda: any(s.req is not None for s in sched.slots), 30.0)
    t0 = time.perf_counter()
    sched.close()
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive(), "a waiter outlived close()"
    assert time.perf_counter() - t0 < 10.0
    assert len(results) == 3
    for i, req in enumerate(reqs):
        assert req.finished.is_set()
        assert results[i][0] == "error" and req.finish_reason == "error", \
            (i, results[i])
    with pytest.raises(SchedulerClosed):
        sched.submit([1], 1, _greedy(spec))


def test_scheduler_queue_bound_rejects_fast(tiny):
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8, max_queue=2)
    # no step loop running: everything stays queued
    sched.submit([1, 2], 4, _greedy(spec))
    sched.submit([1, 3], 4, _greedy(spec))
    with pytest.raises(QueueFull) as ei:
        sched.submit([1, 4], 4, _greedy(spec))
    assert ei.value.retry_after > 0
    assert sched.stats.requests_rejected == 1
    sched.close()


def test_scheduler_request_deadline_structured_frame(tiny):
    """A request over its deadline is failed mid-decode with the
    structured 'deadline' frame instead of draining its whole budget
    (the step loop is slowed so the deadline provably lands mid-run)."""
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8)
    FAULTS.arm("slow_step", times=0, ms=30.0)
    sched.start()
    req = sched.submit([1, 9, 23], 10_000, _greedy(spec),
                       deadline=time.perf_counter() + 0.3)
    got = []
    with pytest.raises(RequestError) as ei:
        for t in req.tokens(timeout=30.0):
            got.append(t)
    assert ei.value.code == "deadline" and not ei.value.retryable
    assert req.finish_reason == "error"
    assert sched.stats.requests_expired == 1
    assert len(got) < 60  # it did NOT drain the budget/context
    sched.close()


def test_scheduler_queue_timeout_expires_queued(tiny):
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8, queue_timeout=0.25)
    r0 = sched.submit([1, 9], 2, _greedy(spec))
    sched.step()  # admits r0 (inside its queue budget) — slot now busy
    r1 = sched.submit([1, 8], 2, _greedy(spec))  # queued behind it
    time.sleep(0.3)  # r1's queue-time budget expires while waiting
    for _ in range(100):
        if r0.finished.is_set() and r1.finished.is_set():
            break
        sched.step()
    assert r0.finish_reason == "length"  # admitted in time, unaffected
    with pytest.raises(RequestError) as ei:
        list(r1.tokens(timeout=5.0))
    assert ei.value.code == "queue_timeout"
    sched.close()


# -- supervisor: crash recovery, watchdog, breaker ----------------------


def test_step_crash_recovers_and_stays_token_identical(tiny):
    """The headline chaos test: a crash mid-decode fails in-flight
    requests with structured frames, the supervisor rebuilds, readiness
    flips unready -> ready, and the next request is oracle-identical."""
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0,
                           backoff_base=0.01, breaker_threshold=5)
    try:
        p = [1, 9, 23, 54]
        # pace the loop so the request provably cannot FINISH before the
        # crash is armed (a warm compile cache makes bare steps sub-ms)
        FAULTS.arm("slow_step", times=0, ms=25.0)
        req = sup.submit(p, 40, _greedy(spec))
        it = req.tokens(timeout=30.0)
        got = [next(it)]  # decoding is live
        FAULTS.arm("step_raise")  # next step crashes mid-decode
        with pytest.raises(RequestError) as ei:
            for t in it:
                got.append(t)
        assert ei.value.code == "engine_error"
        assert "injected step_raise" in str(ei.value)
        assert req.finish_reason == "error"
        # recovery: unready (briefly) then ready again
        assert _wait(lambda: sup.ready, 30.0), sup.state
        assert sup.sup_stats.crashes == 1
        assert sup.sup_stats.recoveries == 1
        # the rebuilt engine serves the SAME prompt oracle-identically
        FAULTS.clear()
        req2 = sup.submit(p, 6, _greedy(spec))
        assert list(req2.tokens(timeout=60.0)) == _oracle(spec, params, p, 6)
        s = sup.summary()
        assert s["state"] == READY
        assert s["requests_failed"] >= 1  # carried across the rebuild
        assert s["resilience"]["recoveries"] == 1
    finally:
        sup.close()


def test_watchdog_detects_stall_within_bound(tiny):
    """An injected step stall (a silent stall raises nothing) is
    detected by the watchdog within the configured bound and recovery
    proceeds: frames delivered, engine rebuilt, ready again — in seconds,
    not the 600 s client timeout."""
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=0.5,
                           backoff_base=0.01, breaker_threshold=5)
    try:
        FAULTS.arm("slow_step", times=0, ms=25.0)  # pace: see crash test
        req = sup.submit([1, 9, 23], 40, _greedy(spec))
        FAULTS.arm("step_stall", ms=60_000)  # next step wedges "forever"
        t0 = time.perf_counter()
        with pytest.raises(RequestError) as ei:
            list(req.tokens(timeout=30.0))
        detected = time.perf_counter() - t0
        assert detected < 10.0, f"stall took {detected:.1f}s to surface"
        assert "stalled" in str(ei.value)
        assert sup.sup_stats.watchdog_trips == 1
        assert _wait(lambda: sup.ready, 30.0), sup.state
        # the wedged generation is abandoned; release it so its thread
        # exits now rather than at the 60 s stall bound
        FAULTS.clear()
        req2 = sup.submit([2, 40, 77], 4, _greedy(spec))
        assert list(req2.tokens(timeout=60.0)) == _oracle(
            spec, params, [2, 40, 77], 4)
    finally:
        FAULTS.clear()
        sup.close()


def test_supervisor_unready_rejects_submit(tiny):
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0,
                           backoff_base=0.5, breaker_threshold=5)
    try:
        FAULTS.arm("slow_step", times=0, ms=25.0)  # pace: see crash test
        req = sup.submit([1, 9], 40, _greedy(spec))
        FAULTS.arm("step_raise")  # after submit: EngineUnready must not
        # race the enqueue — the crash lands on a later iteration
        with pytest.raises(RequestError):
            list(req.tokens(timeout=30.0))
        # backoff_base 0.5 leaves a visible RECOVERING window
        assert _wait(lambda: sup.state == RECOVERING, 10.0)
        with pytest.raises(EngineUnready) as ei:
            sup.submit([1, 9], 4, _greedy(spec))
        assert ei.value.retry_after > 0
        assert sup.sup_stats.rejected_unready == 1
        assert _wait(lambda: sup.ready, 30.0)
    finally:
        sup.close()


def test_circuit_breaker_opens_and_resets(tiny):
    """N consecutive failures open the breaker: the supervisor STAYS
    unready (no rebuild churn against a dead backend) until an operator
    reset, which restores service when the fault is gone."""
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0,
                           backoff_base=0.01, breaker_threshold=2)
    try:
        # every WORKING step crashes (idle iterations never fire a fault
        # site): each submitted request crashes its generation, and the
        # second consecutive failure opens the breaker
        FAULTS.arm("step_raise", times=0)
        for _ in range(6):
            if sup.state == BROKEN:
                break
            assert _wait(lambda: sup.state in (READY, BROKEN), 30.0)
            try:
                req = sup.submit([1, 9], 8, _greedy(spec))
                with pytest.raises(RequestError):
                    list(req.tokens(timeout=30.0))
            except EngineUnready:
                time.sleep(0.05)  # raced a recovery window; try again
        assert sup.state == BROKEN, sup.state
        assert not sup.ready
        with pytest.raises(EngineUnready) as ei:
            sup.submit([1, 9], 4, _greedy(spec))
        assert ei.value.retry_after >= 30.0  # "come back much later"
        trips = sup.sup_stats.consecutive_failures
        assert trips >= 2
        time.sleep(0.2)  # breaker open: NO further rebuild attempts
        assert sup.sup_stats.consecutive_failures == trips
        FAULTS.clear()  # fault gone; operator closes the breaker
        sup.reset_breaker()
        assert _wait(lambda: sup.ready, 30.0), sup.state
        req2 = sup.submit([2, 40, 77], 4, _greedy(spec))
        assert list(req2.tokens(timeout=60.0)) == _oracle(
            spec, params, [2, 40, 77], 4)
    finally:
        FAULTS.clear()
        sup.close()


def test_supervisor_drain_finishes_inflight_then_refuses(tiny):
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0)
    try:
        req = sup.submit([1, 9, 23], 5, _greedy(spec))
        assert sup.drain(timeout=60.0)  # in-flight work completes
        assert list(req.tokens(timeout=5.0)) == _oracle(
            spec, params, [1, 9, 23], 5)
        with pytest.raises(EngineUnready):  # admissions stopped
            sup.submit([1, 9], 2, _greedy(spec))
        assert not sup.ready
    finally:
        sup.close()


def test_supervisor_exclusive_borrows_current_engine(tiny):
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0)
    try:
        r = sup.submit([1, 9, 23], 3, _greedy(spec))
        with sup.exclusive() as eng:
            assert eng is sup.engine
            assert r.finished.is_set()  # exclusive() drained it first
        assert list(r.tokens(timeout=5.0)) == _oracle(
            spec, params, [1, 9, 23], 3)
    finally:
        sup.close()


def test_prefill_raise_site_recovers(tiny):
    """The engine-entry fault site: a crash during slot prefill (not
    decode) takes the same recovery path."""
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=4, stall_timeout=60.0,
                           backoff_base=0.01, breaker_threshold=5)
    try:
        FAULTS.arm("prefill_raise")
        req = sup.submit([1, 9, 23, 54, 7], 4, _greedy(spec))
        with pytest.raises(RequestError) as ei:
            list(req.tokens(timeout=30.0))
        assert "injected prefill_raise" in str(ei.value)
        assert _wait(lambda: sup.ready, 30.0)
        req2 = sup.submit([1, 9, 23, 54, 7], 4, _greedy(spec))
        assert list(req2.tokens(timeout=60.0)) == _oracle(
            spec, params, [1, 9, 23, 54, 7], 4)
    finally:
        sup.close()


def test_slow_step_still_serves_under_deadline_pressure(tiny):
    """slow_step degrades throughput without failing; requests with
    generous deadlines complete, requests with tight deadlines get the
    fast structured 'deadline' frame instead of waiting."""
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0)
    try:
        FAULTS.arm("slow_step", times=0, ms=30.0)
        tight = sup.submit([1, 9], 10_000, _greedy(spec),
                           deadline=time.perf_counter() + 0.25)
        with pytest.raises(RequestError) as ei:
            list(tight.tokens(timeout=30.0))
        assert ei.value.code == "deadline"
        FAULTS.clear()
        ok = sup.submit([2, 40, 77], 4, _greedy(spec))
        assert list(ok.tokens(timeout=60.0)) == _oracle(
            spec, params, [2, 40, 77], 4)
        assert sup.ready  # slowness is not a failure: no recovery churn
        assert sup.sup_stats.recoveries == 0
    finally:
        FAULTS.clear()
        sup.close()


def test_terminal_delivery_exactly_once(tiny):
    """Concurrent failure paths (a dying generation's abort racing the
    supervisor's failed-during-submit fallback) may both try to finish a
    request: exactly ONE terminal event is delivered and counted."""
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=8)
    req = sched.submit([1, 2], 2, _greedy(spec))
    frame = {"code": "engine_error", "message": "x", "retryable": True}
    assert sched._fail_req(req, frame) is True
    assert sched._fail_req(req, frame) is False  # second claim loses
    assert sched.stats.requests_failed == 1
    assert sched.stats.requests_finished == 1
    with pytest.raises(RequestError):
        list(req.tokens(timeout=5.0))
    assert req.events.empty()  # ONE error event, not two
    sched.close()


def test_exclusive_borrow_crash_triggers_recovery(tiny):
    """A crash inside the exclusive() borrow must not bypass supervision:
    the same recovery as a step crash (rebuild, ready again) runs and the
    exception still reaches the borrower."""
    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0,
                           backoff_base=0.01, breaker_threshold=5)
    try:
        with pytest.raises(RuntimeError, match="boom"):
            with sup.exclusive():
                raise RuntimeError("boom")
        assert _wait(lambda: sup.ready, 30.0), sup.state
        assert sup.sup_stats.crashes == 1
        assert sup.sup_stats.recoveries == 1
        req = sup.submit([1, 9, 23], 4, _greedy(spec))
        assert list(req.tokens(timeout=60.0)) == _oracle(
            spec, params, [1, 9, 23], 4)
    finally:
        sup.close()


def test_trip_cluster_opens_breaker_with_structured_frames(tiny):
    """EngineSupervisor.trip_cluster (the api-mode mapping of a multihost
    ClusterPeerLost): in-flight requests get a NON-retryable
    cluster_peer_lost frame immediately — never a hang to their deadline
    in an orphaned collective — and the circuit opens without burning
    rebuild attempts (a local rebuild cannot resurrect a remote worker).
    reset_breaker() stays the operator's half-open."""
    from distributed_llama_tpu.parallel.multihost import ClusterPeerLost

    spec, params = tiny
    sup = EngineSupervisor(_factory(tiny), chunk=8, stall_timeout=60.0,
                           backoff_base=0.01)
    try:
        req = sup.submit([1, 2, 3], 48, _greedy(spec))
        assert _wait(lambda: req.stats.t_first is not None, 30.0)
        recoveries_before = sup.sup_stats.recoveries
        sup.trip_cluster(ClusterPeerLost(2, 10.1, "run", "timeout"))
        assert sup.state == BROKEN
        assert sup.sup_stats.cluster_losses == 1
        with pytest.raises(RequestError) as ei:
            list(req.tokens(timeout=10.0))
        assert ei.value.code == "cluster_peer_lost"
        assert ei.value.retryable is False
        assert "node 2" in str(ei.value)
        # no rebuild was attempted: BROKEN means operator intervention
        time.sleep(0.2)
        assert sup.sup_stats.recoveries == recoveries_before
        # idempotent: a second detection does not double-count
        sup.trip_cluster(ClusterPeerLost(2, 11.0, "run", "timeout"))
        assert sup.sup_stats.cluster_losses == 1
        # admission while broken is a structured fast rejection
        with pytest.raises(EngineUnready):
            sup.submit([1, 2, 3], 4, _greedy(spec))
        # operator half-open: the replica recovers once reset
        sup.reset_breaker()
        assert _wait(lambda: sup.ready, 60.0), sup.state
        req2 = sup.submit([1, 9, 23], 4, _greedy(spec))
        assert list(req2.tokens(timeout=60.0)) == _oracle(
            spec, params, [1, 9, 23], 4)
        assert sup.summary()["resilience"]["cluster_losses"] == 1
    finally:
        sup.close()
