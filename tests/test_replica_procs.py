"""Process-isolated replicas (runtime/replica_worker.py + the
RemoteReplicaHandle process supervision in runtime/router.py).

The chaos contract under test is the ISSUE 7 acceptance bar — the
strongest kill the repo can deliver, upgraded from "injected exception"
to a REAL ``SIGKILL -9`` of a live replica OS process mid-stream:

  * zero unstreamed request failures — a request whose worker dies
    before its first token fails over to a sibling replica within the
    retry budget and returns greedy tokens BIT-IDENTICAL to the
    single-engine oracle (the connection EOF surfaces as a structured
    RETRYABLE ``replica_lost`` frame, feeding the PR-6 failover
    machinery unchanged);
  * a request that already streamed tokens gets the structured
    NON-retryable frame (never a silent replay);
  * the process supervisor classifies the death (``signal:SIGKILL``),
    respawns the worker under backoff, and the replica is ROUTABLE
    again within the configured bound;
  * /stats counter totals carry across the respawn — never reset,
    never double-counted (the ``SupervisorStats`` contract, now across
    a process boundary);
  * a crash-looping worker (spawns that die young) trips the per-replica
    spawn breaker instead of respawning forever; ``reset_breaker`` is
    the operator half-open.

Every worker is a REAL subprocess running single-process CPU JAX over a
deterministic ``test_spec`` (same spec/seed as the in-test oracle, so
params are bit-identical across the process boundary) — the same
subprocess discipline as tests/test_cluster_chaos.py, so these run
wherever the cluster chaos tests do (the CI ``chaos`` job; the main
matrix ignores them).
"""

import os
import signal
import threading
import time

import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.replica_worker import (EXIT_WORKER_FAULT,
                                                          WorkerClient,
                                                          WorkerProc,
                                                          classify_exit)
from distributed_llama_tpu.runtime.resilience import EngineUnready
from distributed_llama_tpu.runtime.router import RemoteReplicaHandle, Router
from distributed_llama_tpu.runtime.scheduler import (PromptTooLong,
                                                     RequestError)
from distributed_llama_tpu.runtime.trace import TRACER
from distributed_llama_tpu.sampler import Sampler

SEQ = 64
SPEC_FIELDS = dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                   n_kv_heads=2, vocab_size=128, seq_len=SEQ)
SEED, SCALE = 3, 0.05

# the worker config every test ships: deterministic synthetic weights
# (same spec/seed/scale as the oracle below — bit-identical params in
# both processes), f32 so greedy parity compares bit-exactly. Workers
# run their own flight recorder (runtime/trace.py) so surviving
# requests ship worker-side spans back over RMSG_TRACE; with the
# parent's tracer off (every test but the SIGKILL one) the shipped
# frames are simply skipped
CFG = {"test_spec": SPEC_FIELDS, "seed": SEED, "scale": SCALE,
       "compute_dtype": "f32", "batch": 2,
       "serve": {"stall_timeout": 60.0},
       "trace": {"capacity": 2048}}

# the worker subprocess environment: CPU jax. Workers place their XLA
# compilation cache with the same helper as this suite
# (utils/compile_cache.py), so repeat spawns skip the compile cost
WORKER_ENV = {"JAX_PLATFORMS": "cpu"}

SPAWN_TIMEOUT = 120.0   # worker startup bound (import + build + warmup)
# _wait's give-up ceiling. Nothing below asserts elapsed time against
# it: the chaos tests wait on OBSERVABLE monitor transitions (exit
# classified -> respawn counted -> routable) and this bound only
# decides when a wait that will never succeed stops burning CI time.
# A respawn is a full interpreter + jax import + engine build + warmup
# in a fresh subprocess, so the ceiling is generous by construction.
RESPAWN_BOUND = 180.0


@pytest.fixture(scope="module")
def oracle_bits():
    spec = ModelSpec(arch=ArchType.LLAMA, hidden_act=HiddenAct.SILU,
                     **SPEC_FIELDS)
    host = random_tensors(spec, seed=SEED, scale=SCALE)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    return spec, params


def _greedy():
    return Sampler(SPEC_FIELDS["vocab_size"], temperature=0.0, topp=0.9,
                   seed=1)


def _oracle(oracle_bits, prompt, max_tokens):
    spec, params = oracle_bits
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    return eng.generate(prompt, max_tokens, _greedy()).tokens


def _proc(rid, workdir, faults=""):
    return WorkerProc(rid, dict(CFG, fault_key=f"r{rid}"),
                      workdir=str(workdir), env=WORKER_ENV,
                      faults=faults or None)


def _handle(rid, workdir, faults="", **kw):
    kw.setdefault("poll_interval", 0.1)
    kw.setdefault("spawn_backoff_base", 0.05)
    kw.setdefault("spawn_timeout", SPAWN_TIMEOUT)
    kw.setdefault("respawn_timeout", SPAWN_TIMEOUT)
    return RemoteReplicaHandle(rid, proc=_proc(rid, workdir, faults), **kw)


def _wait(pred, timeout=RESPAWN_BOUND, poll=0.02):
    end = time.perf_counter() + timeout
    while time.perf_counter() < end:
        if pred():
            return True
        time.sleep(poll)
    return False


def _two_replica_router(mk, **router_kw):
    """Spawn two worker handles CONCURRENTLY (construction blocks on the
    port handshake — import + build + warmup; the shared compilation
    cache makes the second compile-free but not import-free), then hand
    Router prebuilt handles. Keeps the two-replica chaos tests inside
    the fast tier's time budget."""
    handles = [None, None]

    def build(i):
        handles[i] = mk(i)

    threads = [threading.Thread(target=build, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if not all(h is not None for h in handles):
        for h in handles:
            if h is not None:
                h.close()  # don't orphan the sibling that DID come up
        raise AssertionError("worker spawn failed")
    return Router(None, handle_factories=[lambda: handles[0],
                                          lambda: handles[1]], **router_kw)


# -- the framed protocol, one worker --------------------------------------


def test_worker_roundtrip_parity_refusals_and_admin_verbs(tmp_path,
                                                          oracle_bits):
    """One worker process over the framed codec: greedy tokens are
    bit-identical to the in-process oracle (the sampler spec rides the
    submit frame and is reconstructed worker-side), door refusals
    re-raise the SAME exception types the in-process supervisor uses,
    RMSG_REBUILD swaps the supervisor while counters carry, and a
    graceful shutdown is exit 0 / ``clean``."""
    proc = _proc(0, tmp_path)
    proc.spawn()
    try:
        port = proc.wait_ready(timeout=SPAWN_TIMEOUT)
        client = WorkerClient("127.0.0.1", port)
        h = client.ping()
        assert h is not None and h["ready"] and h["state"] == "ready"

        p = [1, 9, 23, 54, 7]
        rs = client.submit(p, 6, _greedy())
        assert list(rs.tokens(timeout=60.0)) == _oracle(oracle_bits, p, 6)
        assert rs.finish_reason == "length"
        # the HELLO ack cached the shape template (the handlers' slice)
        assert client.batch == 2 and client.seq_len == SEQ

        # door refusal types survive the wire
        with pytest.raises(PromptTooLong):
            client.submit(list(range(1, SEQ + 2)), 2, _greedy())

        # rolling-restart verb: fresh supervisor, counters carry
        before = client.stats_summary()
        assert before["requests_finished"] == 1
        assert client.rebuild(timeout=SPAWN_TIMEOUT)
        after = client.stats_summary()
        assert after["requests_finished"] == 1      # carried, not reset
        assert after["tokens_out"] == before["tokens_out"]
        rs = client.submit(p, 6, _greedy())          # and it still serves
        assert list(rs.tokens(timeout=60.0)) == _oracle(oracle_bits, p, 6)
        assert client.stats_summary()["requests_finished"] == 2

        assert client.shutdown()
        rc = proc.stop(timeout=20.0)
        assert rc == 0 and classify_exit(rc) == "clean"
    finally:
        proc.stop(timeout=10.0)


def test_worker_exit_fault_is_retryable_eof_pre_token(tmp_path):
    """The ``worker_exit`` site (the in-process SIGKILL/OOM stand-in):
    armed with key=r0 in the worker's OWN environment, the worker
    os._exits immediately before its first token frame — the client
    sees a mid-request EOF with ZERO tokens streamed and raises the
    structured RETRYABLE ``replica_lost`` frame (exactly what the
    router's failover machinery consumes), and the corpse classifies as
    ``fault_exit``."""
    proc = _proc(0, tmp_path, faults="worker_exit:key=r0")
    proc.spawn()
    try:
        port = proc.wait_ready(timeout=SPAWN_TIMEOUT)
        client = WorkerClient("127.0.0.1", port)
        rs = client.submit([1, 9, 23], 4, _greedy())
        got = []
        with pytest.raises(RequestError) as ei:
            for t in rs.tokens(timeout=60.0):
                got.append(t)
        assert got == []                      # pre-first-token, always
        assert ei.value.code == "replica_lost"
        assert ei.value.retryable is True
        assert _wait(lambda: proc.poll() is not None, 30.0)
        assert proc.poll() == EXIT_WORKER_FAULT
        assert classify_exit(proc.poll()) == "fault_exit"
    finally:
        proc.stop(timeout=10.0)


# -- the acceptance chaos test: real SIGKILL mid-stream --------------------


def test_sigkill_mid_stream_zero_unstreamed_failures_and_respawn(
        tmp_path, oracle_bits):
    """ISSUE 7 acceptance: ``kill -9`` a live replica worker process
    while it serves a mid-stream request AND holds a not-yet-streamed
    one. The streamed request gets the structured NON-retryable frame
    (partial output is never silently replayed); the unstreamed one
    fails over to the sibling replica and returns BIT-IDENTICAL greedy
    tokens; the service stays ready throughout; and the supervisor
    classifies the SIGKILL and respawns the worker to routable within
    the bound.

    ISSUE 9 rides the same kill: the flight recorder must link the
    casualty span, the classified exit, and the bit-identical sibling
    retry as ONE cross-process timeline (the trace id travels in the
    submit frame; the parent records the casualty itself because a
    SIGKILLed worker can never ship its span)."""
    TRACER.configure(capacity=8192)
    # worker-side slow_step paces decode (80 ms/step) so the kill
    # provably lands while streams are in flight
    router = _two_replica_router(
        lambda i: _handle(i, tmp_path, faults="slow_step:times=0;ms=80"),
        policy="round_robin", retry_budget=1)
    h0, h1 = router.replicas
    p = [1, 9, 23, 54, 7]
    want6 = _oracle(oracle_bits, p, 6)
    ready_gaps = []
    sampling = threading.Event()
    sampling.set()

    def sample_ready():
        while sampling.is_set():
            if not router.ready:
                ready_gaps.append(time.perf_counter())
            time.sleep(0.005)

    try:
        samp = threading.Thread(target=sample_ready, daemon=True)
        samp.start()
        # round_robin placement is deterministic: A -> r0, B -> r1,
        # C -> r0
        req_a = router.submit(p, 6, _greedy())
        req_b = router.submit(p, 6, _greedy())
        it_a = req_a.tokens(timeout=120.0)
        got_a = [next(it_a)]              # A is LIVE mid-stream on r0...
        # ...and C joins r0 only NOW, after A's first token: its own
        # first token is at least one paced prefill + one paced decode
        # step away (>= 160 ms), so the kill provably lands before C
        # streams anything
        req_c = router.submit(p, 6, _greedy())
        assert (req_a.replica_id, req_b.replica_id,
                req_c.replica_id) == (0, 1, 0)
        os.kill(h0._proc.proc.pid, signal.SIGKILL)

        # A: already streamed -> structured NON-retryable frame
        with pytest.raises(RequestError) as ei:
            for t in it_a:
                got_a.append(t)
        assert ei.value.retryable is False
        assert "already streamed" in str(ei.value)
        assert len(got_a) >= 1
        assert got_a == want6[:len(got_a)]  # the partial stream was real

        # C: zero tokens streamed -> bounded failover to r1, parity
        got_c = list(req_c.tokens(timeout=120.0))
        assert got_c == want6, "failover lost greedy parity"
        assert req_c.retries == 1 and req_c.replica_id == 1

        # B (on the surviving replica) never noticed
        assert list(req_b.tokens(timeout=120.0)) == want6

        # supervised respawn, event-driven: wait on each observable state
        # transition of the monitor in order — exit CLASSIFIED, respawn
        # COUNTED, worker routable. RESPAWN_BOUND is only _wait's
        # give-up ceiling; no assertion does wall-clock arithmetic.
        assert _wait(lambda: h0.proc_stats.exit_classes
                     .get("signal:SIGKILL", 0) >= 1), \
            "monitor never classified the SIGKILL"
        assert _wait(lambda: h0.proc_stats.respawns >= 1), \
            "monitor never completed a respawn"
        assert _wait(lambda: h0.ready), \
            "respawned worker never became routable"
        ps = h0.proc_stats.summary()
        assert ps["exit_classes"].get("signal:SIGKILL") == 1
        assert ps["respawns"] == 1
        assert ps["respawn_p50_ms"] is not None

        # the respawned worker SERVES (fresh process, same weights)
        req_d = router.submit(p, 4, _greedy())
        assert list(req_d.tokens(timeout=120.0)) == want6[:4]

        # the single-replica outage was invisible at the service level
        assert not ready_gaps, f"router went unready at {ready_gaps}"

        # -- the flight-recorder story of the kill (ISSUE 9) ----------
        # C's span: ONE trace id links route->r0, the replica_lost
        # casualty (zero tokens), the failover, and the route->r1 retry
        span_c = TRACER.by_id(req_c.trace_id)
        kinds_c = [e["kind"] for e in span_c]
        routes = [e for e in span_c if e["kind"] == "route"]
        assert [r["replica"] for r in routes] == [0, 1]
        err_c = next(e for e in span_c if e["kind"] == "error")
        assert err_c["code"] == "replica_lost" and err_c["n_out"] == 0
        fo = next(e for e in span_c if e["kind"] == "failover")
        assert fo["replica"] == 0 and fo["attempt"] == 1
        assert (kinds_c.index("error") < kinds_c.index("failover")
                < len(kinds_c) - kinds_c[::-1].index("route"))
        # A's span: the mid-stream casualty — it streamed (client-side
        # first_token), then lost its worker mid-request
        span_a = TRACER.by_id(req_a.trace_id)
        assert any(e["kind"] == "first_token" for e in span_a)
        err_a = next(e for e in span_a if e["kind"] == "error"
                     and e["code"] == "replica_lost")
        assert err_a["n_out"] >= 1
        # the kill itself, classified, on the same timeline
        exits = [e for e in TRACER.recent(0) if e["kind"] == "worker_exit"]
        assert exits and exits[0]["replica"] == 0
        assert exits[0]["cls"] == "signal:SIGKILL"
        # B survived on r1: its worker shipped its span over RMSG_TRACE
        # — worker-side events (origin worker@...) merged onto the
        # parent timeline, the cross-process half of the contract
        span_b = TRACER.by_id(req_b.trace_id)
        worker_evs = [e for e in span_b if str(e.get("origin",
                                                     "")).startswith("worker@")]
        assert any(e["kind"] == "finish" for e in worker_evs)
        assert any(e["kind"] == "admit" for e in worker_evs)

        assert router.stats.midstream_failures == 1
        assert router.stats.retries == 1
        assert router.stats.failovers_ok == 1

        # -- /metrics over the PROCESS tier (the third serving tier of
        # the ISSUE 9 acceptance bar): the real HTTP handler over this
        # very router serves valid Prometheus text with the per-replica
        # process series — including the classified SIGKILL
        import http.client

        from distributed_llama_tpu.apps.api_server import (ApiState,
                                                           make_handler)
        from http.server import ThreadingHTTPServer

        state = ApiState(None, None, None, model_name="procs",
                         serve_batch=2, replica_procs=2)
        state._scheduler = router
        srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            conn = http.client.HTTPConnection(*srv.server_address,
                                              timeout=60)
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            body = resp.read().decode()
            assert resp.status == 200
            assert resp.getheader("Content-Type").startswith("text/plain")
            assert 'dllama_up{model="procs",mode="router"} 1' in body
            assert ('dllama_replica_proc_exit_class_total'
                    '{replica="0",class="signal:SIGKILL"} 1') in body
            assert 'dllama_replica_up{replica="1"} 1' in body
            assert "dllama_router_retries_total 1" in body
            conn.close()
        finally:
            srv.shutdown()
    finally:
        sampling.clear()
        router.close()
        TRACER.reset()


# -- /stats aggregation across a respawn (satellite) -----------------------


def test_stats_totals_carry_across_respawn_no_reset_no_double_count(
        tmp_path, oracle_bits):
    """Counter totals in the router's /stats aggregation must behave
    across a worker respawn exactly like SupervisorStats does across an
    engine rebuild: carried, never reset, never double-counted. The
    parent folds the dead process's last-polled counters into a carry;
    with the monitor given one quiet poll interval before the kill, the
    fold is exact."""
    router = Router(None, policy="least_loaded", retry_budget=1,
                    handle_factories=[lambda: _handle(0, tmp_path)])
    h0 = router.replicas[0]
    p = [2, 40, 77, 5]
    try:
        for _ in range(2):
            req = router.submit(p, 3, _greedy())
            assert list(req.tokens(timeout=120.0)) == _oracle(
                oracle_bits, p, 3)
        # let the monitor's PONG poll capture the finished counters so
        # the carry across the kill is exact, not a lower bound
        assert _wait(lambda: h0._last_counters["requests_finished"] == 2,
                     10.0)
        s1 = router.summary()
        assert s1["requests_finished"] == 2
        assert s1["tokens_out"] == 6

        os.kill(h0._proc.proc.pid, signal.SIGKILL)
        assert _wait(lambda: h0.proc_stats.respawns == 1, RESPAWN_BOUND)
        # mid-restart reads never went backwards or forward-jumped
        s2 = router.summary()
        assert s2["requests_finished"] == 2      # carried, not reset
        assert s2["tokens_out"] == 6             # and not double-counted

        assert _wait(lambda: h0.ready, RESPAWN_BOUND)
        req = router.submit(p, 3, _greedy())
        assert list(req.tokens(timeout=120.0)) == _oracle(
            oracle_bits, p, 3)
        s3 = router.summary()
        assert s3["requests_finished"] == 3      # old 2 + new 1
        assert s3["tokens_out"] == 9
        reps = s3["replicas"]
        assert reps[0]["proc"]["mode"] == "spawn"
        assert reps[0]["proc"]["exit_classes"].get("signal:SIGKILL") == 1
    finally:
        router.close()


# -- spawn breaker on a crash loop ----------------------------------------


def test_crash_loop_trips_spawn_breaker_and_reset_recovers(tmp_path):
    """A worker whose respawns keep dying young (here: config file
    corrupted after a healthy start -> every respawn is a fast exit 2
    ``config_error``) must trip the per-replica spawn breaker instead of
    respawning forever; ``reset_breaker`` after restoring the config is
    the operator half-open that resumes supervision."""
    h0 = _handle(0, tmp_path, min_uptime=5.0, spawn_breaker=3,
                 spawn_backoff_max=0.2)
    try:
        assert h0.ready
        good = open(h0._proc.config_path).read()
        with open(h0._proc.config_path, "w") as f:
            f.write("{not json")
        os.kill(h0._proc.proc.pid, signal.SIGKILL)
        assert _wait(lambda: h0.state == "broken", RESPAWN_BOUND), \
            f"breaker never tripped (state {h0.state})"
        assert not h0.ready
        with pytest.raises(EngineUnready):
            h0.submit([1, 2, 3], 2, _greedy())
        assert h0.proc_stats.spawn_failures >= 1
        assert h0.proc_stats.exit_classes.get("config_error", 0) >= 1

        # operator half-open: fix the config, reset, supervision resumes
        with open(h0._proc.config_path, "w") as f:
            f.write(good)
        h0.reset_breaker()
        assert _wait(lambda: h0.ready, RESPAWN_BOUND), \
            "reset_breaker did not resume respawning"
        rs = h0.submit([1, 9, 23], 2, _greedy())
        assert len(list(rs.tokens(timeout=60.0))) == 2
    finally:
        h0.close()


# -- shadow prefix index placement (process-mode cache awareness) ----------


def test_shadow_index_routes_cache_aware_and_clears_on_respawn(
        tmp_path, oracle_bits):
    """Cache-aware placement across the process boundary: the router's
    shadow radix index records what it ROUTED (no RPC on the hot path),
    so a repeat prompt is placed on the replica that already served its
    prefix; a worker death clears that replica's shadow (the respawned
    process holds an empty real tree)."""
    cfg_pc = dict(CFG, prefix_cache=True, prefix_blocks=32,
                  prefix_block_len=4)

    def mk(i):
        proc = WorkerProc(i, dict(cfg_pc, fault_key=f"r{i}"),
                          workdir=str(tmp_path), env=WORKER_ENV)
        return RemoteReplicaHandle(i, proc=proc, block_len=4,
                                   poll_interval=0.1,
                                   spawn_backoff_base=0.05,
                                   spawn_timeout=SPAWN_TIMEOUT,
                                   respawn_timeout=SPAWN_TIMEOUT)

    router = _two_replica_router(mk, policy="cache_aware", retry_budget=1)
    h0 = router.replicas[0]
    p = [1, 9, 23, 54, 7, 11, 40, 3, 15]   # two whole 4-token blocks
    try:
        want = _oracle(oracle_bits, p, 3)
        r1 = router.submit(p, 3, _greedy())
        assert list(r1.tokens(timeout=120.0)) == want
        assert r1.replica_id == 0           # idle tie-break: lowest id
        assert h0.match_len(p) >= 4         # the shadow recorded it
        # repeat prompt: placed by SHADOW match, not fallback
        r2 = router.submit(p, 3, _greedy())
        assert list(r2.tokens(timeout=120.0)) == want
        assert r2.replica_id == 0
        assert router.stats.routed_cache_hit >= 1

        os.kill(h0._proc.proc.pid, signal.SIGKILL)
        assert _wait(lambda: h0.proc_stats.respawns == 1, RESPAWN_BOUND)
        assert h0.match_len(p) == 0         # shadow cleared with the corpse
    finally:
        router.close()


# -- /admin/profile over the process tier (ISSUE 10) ------------------------


def test_admin_profile_guarded_and_rmsg_profile_roundtrips(tmp_path,
                                                           oracle_bits,
                                                           monkeypatch):
    """The chaos-job half of the ISSUE 10 capture satellite: the
    RMSG_PROFILE verb round-trips to a REAL worker process — the capture
    lands in that worker's own per-worker dir — and, over HTTP on the
    process tier, POST /admin/profile is admin-guarded off-loopback
    exactly like every other /admin/* verb (403 bare, 200 + per-worker
    dirs with the --admin-token bearer)."""
    import http.client
    import json as _json
    from http.server import ThreadingHTTPServer

    import distributed_llama_tpu.apps.api_server as api_mod
    from distributed_llama_tpu.apps.api_server import (ApiState,
                                                       make_handler)

    cfg = dict(CFG, profile_dir=str(tmp_path / "prof"), fault_key="r0")
    proc = WorkerProc(0, cfg, workdir=str(tmp_path), env=WORKER_ENV)
    h0 = RemoteReplicaHandle(0, proc=proc, poll_interval=0.1,
                             spawn_backoff_base=0.05,
                             spawn_timeout=SPAWN_TIMEOUT,
                             respawn_timeout=SPAWN_TIMEOUT)
    router = Router(None, policy="least_loaded", retry_budget=1,
                    handle_factories=[lambda: h0])
    try:
        # the verb itself, straight through the framed codec: the 200
        # (RMSG_OK) is synchronous with the capture, so the per-worker
        # dir exists the moment the reply lands
        out = h0.profile(40)
        assert out is not None, "RMSG_PROFILE failed"
        want_prefix = os.path.join(str(tmp_path), "prof", "worker-r0")
        assert out["dir"].startswith(want_prefix), out
        assert os.path.isdir(out["dir"])

        # HTTP relay + the off-loopback guard
        state = ApiState(None, None, None, model_name="procs",
                         serve_batch=2, replica_procs=1)
        state._scheduler = router
        srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            def post(headers=None):
                conn = http.client.HTTPConnection(*srv.server_address,
                                                  timeout=120)
                conn.request("POST", "/admin/profile?ms=40", b"{}",
                             {"Content-Type": "application/json",
                              **(headers or {})})
                resp = conn.getresponse()
                return resp.status, _json.loads(resp.read())

            monkeypatch.setattr(api_mod, "_is_loopback", lambda a: False)
            status, body = post()
            assert status == 403 and "admin" in body["error"]
            state.admin_token = "tok-prof"
            status, body = post({"Authorization": "Bearer tok-prof"})
            assert status == 200, body
            w = body["workers"]["r0"]
            assert w is not None and w["dir"].startswith(want_prefix)
            assert os.path.isdir(w["dir"])
            assert w["dir"] != out["dir"]  # a fresh capture, not a replay
        finally:
            srv.shutdown()
    finally:
        router.close()


# -- one process per chip (ISSUE 22) ----------------------------------------


def test_front_door_serves_without_touching_a_jax_backend(tmp_path,
                                                          monkeypatch):
    """The process tiers' front door must hold no device: with every way
    of reaching a JAX backend in THIS process made to raise, the real
    ApiState + HTTP handler still boot a worker, serve a completion, and
    report the WORKER's backend/device facts on /healthz (relayed from
    its health PONG — the front door may not ask JAX)."""
    import http.client
    import json
    from http.server import ThreadingHTTPServer

    from jax._src import xla_bridge

    from distributed_llama_tpu.apps.api_server import ApiState, make_handler
    from distributed_llama_tpu.apps.dllama import FrontDoorTemplate
    from distributed_llama_tpu.testing import tiny_spec, write_fixture
    from distributed_llama_tpu.tokenizer import Tokenizer

    def boom(*a, **kw):
        raise AssertionError("the front door touched a JAX backend")

    for name in ("get_backend", "backends", "_get_backend_uncached"):
        monkeypatch.setattr(xla_bridge, name, boom)
    _, tpath = write_fixture(tmp_path)
    spec = tiny_spec()
    tok = Tokenizer.from_file(tpath)
    fields = dict(dim=spec.dim, hidden_dim=spec.hidden_dim,
                  n_layers=spec.n_layers, n_heads=spec.n_heads,
                  n_kv_heads=spec.n_kv_heads, vocab_size=spec.vocab_size,
                  seq_len=spec.seq_len)
    cfg = {"test_spec": fields, "seed": SEED, "scale": SCALE,
           "compute_dtype": "f32", "batch": 2,
           "serve": {"stall_timeout": 60.0}}
    state = ApiState(FrontDoorTemplate(spec), tok,
                     Sampler(tok.vocab_size, 0.0, 0.9, 1),
                     model_name="procs", serve_batch=2, replica_procs=1,
                     worker_config=cfg)
    try:
        assert state.build_info()["backend"] == "uninitialized"
        srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            conn = http.client.HTTPConnection(*srv.server_address,
                                              timeout=SPAWN_TIMEOUT)
            conn.request("POST", "/v1/completions", body=json.dumps(
                {"prompt": "hello", "max_tokens": 4,
                 "temperature": 0.0}).encode(),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 200, body
            assert 1 <= body["usage"]["completion_tokens"] <= 4
            conn.request("GET", "/healthz")
            build = json.loads(conn.getresponse().read())["build"]
            conn.close()
        finally:
            srv.shutdown()
        # the worker's facts, not this process's (which may not be asked)
        assert build["backend"] == "cpu" and build["device_kind"] == "cpu"
        assert build["device_count"] >= 1 and build["mesh"] == "front-door"
    finally:
        if state._fleet is not None:
            state._fleet.close()
        if state._scheduler is not None:
            state._scheduler.close()


def test_worker_environment_carries_its_chip_assignment(tmp_path,
                                                        monkeypatch):
    """Worker i is given exactly chip i (in the variables libtpu honours);
    without them N workers on an N-chip host would each try to open every
    chip."""
    import subprocess

    seen = {}

    class FakePopen:
        def __init__(self, cmd, env=None, **kw):
            seen["env"] = env

    monkeypatch.setattr(subprocess, "Popen", FakePopen)
    for rid in (0, 3):
        WorkerProc(rid, dict(CFG), workdir=str(tmp_path)).spawn()
        env = seen["env"]
        assert env["TPU_VISIBLE_CHIPS"] == str(rid)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # an explicit per-worker env still wins (tests pin workers to the CPU)
    WorkerProc(1, dict(CFG), workdir=str(tmp_path),
               env={"TPU_VISIBLE_CHIPS": "7"}).spawn()
    assert seen["env"]["TPU_VISIBLE_CHIPS"] == "7"


def test_more_workers_than_chips_is_a_startup_error(tmp_path, monkeypatch):
    """`--replica-procs N` with N > the host's TPU chips can never come
    up (one chip per worker): refused at start-up on a TPU host, before
    any engine work; a host without a TPU is exempt."""
    from distributed_llama_tpu.apps import api_server
    from distributed_llama_tpu.apps.dllama import build_argparser
    from distributed_llama_tpu.runtime import replica_worker
    from distributed_llama_tpu.testing import write_fixture

    mpath, tpath = write_fixture(tmp_path)
    args = build_argparser().parse_args(
        ["api", "--model", mpath, "--tokenizer", tpath, "--serve-batch",
         "2", "--replica-procs", "2"])
    monkeypatch.setattr(replica_worker, "local_tpu_chips", lambda: 1)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit) as e:
        api_server.serve(args)
    assert "exceeds the 1 TPU chip" in str(e.value)
