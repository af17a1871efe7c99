"""Cross-replica KV block transfer (runtime/kv_transfer.py): cache fill
on miss, prefill/decode disaggregation, and the chaos bars.

The contract under test is the ISSUE 14 acceptance set:

  * greedy outputs are BIT-IDENTICAL with transfer on vs off: the
    shipped K/V *is* a sibling prefill's writes (same executable, same
    params), so a filled-and-seeded request must emit exactly the cold
    oracle's tokens — pinned over both transports (thread-tier local
    fill and the RMSG_BLOCK_* wire path);
  * every transfer failure — donor death mid-``RMSG_BLOCK_DATA`` (a
    REAL ``SIGKILL -9`` of a stalled donor worker process, plus the
    count-deterministic ``kvx_exit`` hard-exit), a client-side
    ``recv_stall`` past the per-transfer deadline, a ``frame_truncate``
    torn frame — degrades to a plain local re-prefill with ZERO
    unstreamed request failures and the same bit-identical output;
  * the measured block-frame wire ledger reconciles EXACTLY (drift 0.0)
    with the frame-size arithmetic (``multihost.frame_bytes``);
  * donor-side eviction cannot strand the router fetching dead blocks:
    a ``RMSG_BLOCK_QUERY`` miss answer clears the stale shadow entry
    (the ISSUE 14 staleness regression);
  * ``--tier prefill|decode`` routes the prompt pass to the prefill
    worker, the decode worker admits already-seeded, and the mixed path
    serves when no prefill worker is routable.

Wire tests run REAL TCP against in-process ``ReplicaServer``s (connect-
mode ``RemoteReplicaHandle``s — every frame crosses a real socket, no
subprocess spawn cost); the donor-death chaos test spawns REAL worker
subprocesses like tests/test_replica_procs.py and runs in the CI chaos
job (the main matrix ignores this file).
"""

import threading
import time

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime import kv_transfer as kvx
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.faults import FAULTS
from distributed_llama_tpu.runtime.profiler import COMPILES
from distributed_llama_tpu.runtime.replica_worker import (
    REPLICA_PROTOCOL_VERSION, ReplicaServer)
from distributed_llama_tpu.runtime.resilience import EngineSupervisor
from distributed_llama_tpu.runtime.router import (RemoteReplicaHandle,
                                                  Router,
                                                  ShadowPrefixIndex)
from distributed_llama_tpu.runtime.stats import KVTransferStats
from distributed_llama_tpu.sampler import Sampler

SEQ = 64
BL = 8  # block length: prompts below are a few whole blocks + remainder
SPEC_FIELDS = dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                   n_kv_heads=2, vocab_size=128, seq_len=SEQ)
SEED, SCALE = 3, 0.05


@pytest.fixture(scope="module")
def tiny():
    spec = ModelSpec(arch=ArchType.LLAMA, hidden_act=HiddenAct.SILU,
                     **SPEC_FIELDS)
    host = random_tensors(spec, seed=SEED, scale=SCALE)
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
        return Engine(spec, params, batch=batch,
                      compute_dtype=jnp.float32, cache_dtype=jnp.float32)

    return make


def _greedy():
    return Sampler(SPEC_FIELDS["vocab_size"], temperature=0.0, topp=0.9,
                   seed=1)


def _oracle(tiny, prompt, max_tokens):
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    return eng.generate(prompt, max_tokens, _greedy()).tokens


def _sup(tiny, *, blocks=16, transfer=True, key=None):
    return EngineSupervisor(_factory(tiny), prefix_blocks=blocks,
                            prefix_block_len=BL, kv_transfer=transfer,
                            stall_timeout=60.0, fault_key=key)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, SPEC_FIELDS["vocab_size"], n).astype(
        np.int64).tolist()


class _Cluster:
    """Two in-process ReplicaServers behind a connect-mode Router: real
    TCP, real frames, zero subprocess spawns."""

    def __init__(self, tiny, *, tiers=("mixed", "mixed"), blocks=16,
                 io_timeout=30.0, policy="round_robin",
                 kv_transfer=True):
        self.servers = [
            ReplicaServer(
                (lambda k: (lambda: _sup(tiny, blocks=blocks,
                                         key=k)))(f"r{i}"),
                kv_transfer=kv_transfer, tier=tiers[i],
                io_timeout=io_timeout)
            for i in range(2)]
        self.ports = [s.start() for s in self.servers]
        self.handles = [
            RemoteReplicaHandle(i, address=("127.0.0.1", self.ports[i]),
                                block_len=BL, poll_interval=0.1)
            for i in range(2)]
        hs = self.handles
        self.router = Router(None, policy=policy,
                             handle_factories=[lambda: hs[0],
                                               lambda: hs[1]],
                             kv_transfer=kv_transfer,
                             fill_min_tokens=BL)

    def close(self):
        self.router.close()
        for s in self.servers:
            s.shutdown()


# -- thread-tier local fill -------------------------------------------------


def test_local_fill_parity_miss_and_zero_postwarmup_compiles(tiny):
    """The in-process transport: a warm donor's blocks import into a
    cold sibling, the seeded serve emits the cold oracle's exact tokens,
    a donor that cannot help answers a MISS (no import, no failure), and
    the whole exchange (export/import warmed by PrefixCache.warmup)
    mints ZERO post-warmup compile keys."""
    sup0, sup1 = _sup(tiny, key="r0"), _sup(tiny, key="r1")
    try:
        warm_baseline = COMPILES.after_warmup
        prompt = _prompt(3 * BL + 3, seed=0)
        oracle = _oracle(tiny, prompt, 8)
        got = list(sup0.submit(prompt, 8, _greedy()).tokens(timeout=60))
        assert got == oracle

        st = KVTransferStats(enabled=True)
        ans = kvx.local_fill(sup0, sup1, prompt, stats=st)
        assert ans == 3 * BL  # the donor's whole-block answer
        assert st.fills_ok == 1 and st.tokens_filled == 3 * BL
        assert st.blocks_filled == 3 and st.fill_fallbacks == 0

        got1 = list(sup1.submit(prompt, 8, _greedy()).tokens(timeout=60))
        assert got1 == oracle, "transfer-seeded output diverged"
        pcs = sup1.prefix_cache.stats
        assert pcs.hits == 1 and pcs.tokens_saved == 3 * BL

        # a prefix neither side caches: donor answers a miss, nothing
        # imports, nothing fails
        other = _prompt(2 * BL + 1, seed=9)
        ans2 = kvx.local_fill(sup0, sup1, other, stats=st)
        assert ans2 == 0 and st.fill_misses == 1 and st.fills_ok == 1

        # donor-side pins all released (eviction-safe): every node in
        # the donor tree is unreferenced again
        def all_unpinned(node):
            return node.refs == 0 and all(
                all_unpinned(c) for c in node.children.values())
        assert all(all_unpinned(c) for c in
                   sup0.prefix_cache._root.children.values())
        assert COMPILES.after_warmup == warm_baseline, \
            "transfer minted a post-warmup compile key"
    finally:
        sup0.close()
        sup1.close()


# -- the wire path ----------------------------------------------------------


def test_wire_fill_parity_ledger_reconciles_exactly(tiny):
    """Real frames end to end: round-robin lands the repeat request on
    the cold replica, which fetches the donor's blocks over RMSG_BLOCK_*
    and emits the oracle's exact tokens. The importer's dlwire ledger
    entry for BLOCK_DATA reconciles with the frame-size arithmetic at
    drift 0.0 (multihost.frame_bytes), and the donor's tree holds no
    leaked pins."""
    from distributed_llama_tpu.parallel.multihost import frame_bytes
    from distributed_llama_tpu.runtime.netstats import reconcile_wire

    spec, _ = tiny
    c = _Cluster(tiny)
    try:
        prompt = _prompt(4 * BL + 1, seed=1)
        oracle = _oracle(tiny, prompt, 8)
        r0 = c.router.submit(prompt, 8, _greedy())
        assert list(r0.tokens(timeout=60)) == oracle
        r1 = c.router.submit(prompt, 8, _greedy())
        assert list(r1.tokens(timeout=60)) == oracle, \
            "wire-filled output diverged"
        assert r1.replica_id != r0.replica_id

        tgt = c.servers[r1.replica_id].kvx_stats
        don = c.servers[r0.replica_id].kvx_stats
        assert tgt.fills_ok == 1 and tgt.tokens_filled == 4 * BL
        assert don.queries_served == 1 and don.blocks_exported == 4

        per_block = kvx.block_payload_bytes(
            spec.n_layers, spec.n_kv_heads, BL, spec.head_size,
            jnp.float32)
        measured = tgt.wire.summary()["peers"][str(r0.replica_id)][
            "rx"]["BLOCK_DATA"]["bytes"]
        rec = reconcile_wire(measured, 4 * frame_bytes(1, per_block))
        assert rec["drift_frac"] == 0.0, rec
        # donor's pins all released after the connection closed
        pc0 = c.servers[r0.replica_id].sup.prefix_cache

        def all_unpinned(node):
            return node.refs == 0 and all(
                all_unpinned(ch) for ch in node.children.values())
        assert all(all_unpinned(ch)
                   for ch in pc0._root.children.values())

        # the router aggregate + /metrics family render the record
        summ = c.router.summary()
        agg = summ["kv_transfer"]
        assert agg["enabled"] and agg["fills_ok"] == 1, agg
        from distributed_llama_tpu.runtime.trace import render_prometheus
        text = render_prometheus(summ)
        assert "dllama_kv_transfer_fills_total 1" in text
        assert "dllama_replica_kv_transfer_blocks_exported_total" in text
    finally:
        c.close()


# -- chaos: faults + donor death at the transfer sites ----------------------
#
# These spawn REAL worker subprocesses (the test_replica_procs
# discipline): the donor's codec calls then live in ANOTHER process, so
# arming the global recv_stall/frame_truncate sites here counts ONLY the
# test-side transfer calls — deterministic `after=` placement.

_WORKER_ENV = {"JAX_PLATFORMS": "cpu"}
_WORKER_CFG = {"test_spec": SPEC_FIELDS, "seed": SEED, "scale": SCALE,
               "compute_dtype": "f32", "batch": 2,
               "prefix_cache": True, "prefix_blocks": 16,
               "prefix_block_len": BL, "kv_transfer": True,
               "serve": {"stall_timeout": 60.0}}
_SPAWN_TIMEOUT = 120.0


def _worker_proc(rid, workdir, faults=""):
    from distributed_llama_tpu.runtime.replica_worker import WorkerProc

    return WorkerProc(rid, dict(_WORKER_CFG, fault_key=f"r{rid}"),
                      workdir=str(workdir), env=_WORKER_ENV,
                      faults=faults or None)


def _spawned_donor(workdir, faults=""):
    proc = _worker_proc(0, workdir, faults)
    proc.spawn()
    try:
        port = proc.wait_ready(timeout=_SPAWN_TIMEOUT)
    except BaseException:
        proc.stop(timeout=5.0)
        raise
    return proc, port


def test_client_codec_faults_degrade_to_reprefill(tiny, tmp_path):
    """``recv_stall``/``frame_truncate`` AT THE TRANSFER SITES: a stall
    past the per-transfer deadline and a torn QUERY frame both surface
    as a degraded fill (fallback counted, no exception), and the request
    still serves bit-identically via plain re-prefill — zero unstreamed
    failures."""
    from distributed_llama_tpu.runtime.replica_worker import WorkerClient

    proc, port = _spawned_donor(tmp_path)
    sup1 = _sup(tiny, key="r1")
    try:
        prompt = _prompt(3 * BL + 2, seed=2)
        oracle = _oracle(tiny, prompt, 8)
        wc = WorkerClient("127.0.0.1", port)
        warm = wc.submit(prompt, 8, _greedy())
        assert list(warm.tokens(timeout=60)) == oracle

        st = KVTransferStats(enabled=True)
        # transfer-side recv sequence (the ONLY codec recvs in this
        # process): HELLO_ACK(1), BLOCK_ACK(2), DATA(3) -> after=2
        # stalls the first BLOCK_DATA recv; the 1 s transfer deadline
        # fires and the fill degrades
        FAULTS.arm("recv_stall", after=2, times=1, ms=5000.0)
        t0 = time.perf_counter()
        ans = kvx.fill_from_wire(
            sup1._sched, prompt, "127.0.0.1", port, 3 * BL, stats=st,
            protocol_version=REPLICA_PROTOCOL_VERSION, io_timeout=1.0,
            deadline_s=1.0)
        FAULTS.clear()
        FAULTS.release()
        assert time.perf_counter() - t0 < 10.0, "deadline did not bound"
        assert st.fill_fallbacks == 1 and st.fills_ok == 0
        # the donor ANSWERED the query before the stall: the verdict is
        # its real match (shadow stays truthful), only the data was lost
        assert ans == 3 * BL

        # transfer-side send sequence: HELLO(1), QUERY(2) -> after=1
        # tears the QUERY mid-write; the donor sees a torn frame, the
        # client an EOF — no verdict, degrade
        FAULTS.arm("frame_truncate", after=1, times=1)
        ans2 = kvx.fill_from_wire(
            sup1._sched, prompt, "127.0.0.1", port, 3 * BL, stats=st,
            protocol_version=REPLICA_PROTOCOL_VERSION, io_timeout=2.0,
            deadline_s=2.0)
        FAULTS.clear()
        assert ans2 == -1, "a torn handshake must yield NO verdict"
        assert st.fill_fallbacks == 2

        # both failures degraded: the request itself serves cold,
        # bit-identically, with zero failures
        got = list(sup1.submit(prompt, 8, _greedy()).tokens(timeout=60))
        assert got == oracle
        assert sup1._sched.stats.requests_failed == 0
    finally:
        FAULTS.clear()
        FAULTS.release()
        sup1.close()
        proc.stop(timeout=5.0)


def test_donor_hard_exit_mid_block_data_degrades(tiny, tmp_path):
    """``kvx_exit`` lands an ``os._exit`` EXACTLY between the donor's
    first and second BLOCK_DATA frames (the count-deterministic
    SIGKILL/OOM shape): the importer sees a mid-transfer EOF, degrades
    to re-prefill, and the request's greedy output stays bit-identical
    — never a request failure."""
    from distributed_llama_tpu.runtime.replica_worker import WorkerClient

    proc, port = _spawned_donor(
        tmp_path, faults="kvx_exit:after=1;times=1;key=r0")
    sup1 = _sup(tiny, key="r1")
    try:
        prompt = _prompt(3 * BL + 2, seed=4)
        oracle = _oracle(tiny, prompt, 8)
        wc = WorkerClient("127.0.0.1", port)
        assert list(wc.submit(prompt, 8,
                              _greedy()).tokens(timeout=60)) == oracle

        st = KVTransferStats(enabled=True)
        ans = kvx.fill_from_wire(
            sup1._sched, prompt, "127.0.0.1", port, 3 * BL, stats=st,
            protocol_version=REPLICA_PROTOCOL_VERSION, io_timeout=5.0,
            deadline_s=5.0)
        # the donor died between DATA #1 and #2: partial data must be
        # discarded (a half path would still be correct, but the torn
        # stream yields no import), the fill degrades
        assert st.fills_ok == 0 and st.fill_fallbacks == 1
        assert ans in (-1, 3 * BL)  # EOF may land before or after ACK
        # the EOF can reach us a moment before the dying donor is
        # waitable (its sockets close during exit): bounded wait
        proc.proc.wait(timeout=10.0)
        assert proc.poll() is not None
        from distributed_llama_tpu.runtime.replica_worker import \
            classify_exit
        assert classify_exit(proc.poll()) == "fault_exit"

        got = list(sup1.submit(prompt, 8, _greedy()).tokens(timeout=60))
        assert got == oracle
        assert sup1._sched.stats.requests_failed == 0
    finally:
        sup1.close()
        proc.stop(timeout=5.0)


def test_sigkill_mid_transfer_holds_availability_and_parity(tiny,
                                                            tmp_path):
    """THE acceptance chaos bar: a REAL ``kill -9`` of the donor worker
    while a transfer is in flight (the donor is wedged inside its
    BLOCK_DATA loop by ``kvx_stall``, so the kill provably lands
    mid-transfer). The placed replica's fill degrades to a local
    re-prefill, the request completes with greedy tokens BIT-IDENTICAL
    to the oracle, zero unstreamed failures, the service stays ready
    throughout, and the dead donor is classified + respawned."""
    import os
    import signal

    procs = [_worker_proc(0, tmp_path,
                          faults="kvx_stall:key=r0;ms=60000;times=1"),
             _worker_proc(1, tmp_path)]
    handles = [None, None]

    def build(i):
        handles[i] = RemoteReplicaHandle(
            i, proc=procs[i], block_len=BL, poll_interval=0.1,
            spawn_timeout=_SPAWN_TIMEOUT, respawn_timeout=_SPAWN_TIMEOUT,
            spawn_backoff_base=0.05)

    threads = [threading.Thread(target=build, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(h is not None for h in handles), "worker spawn failed"
    hs = handles
    router = Router(None, policy="round_robin",
                    handle_factories=[lambda: hs[0], lambda: hs[1]],
                    kv_transfer=True, fill_min_tokens=BL)
    try:
        prompt = _prompt(4 * BL + 1, seed=5)
        oracle = _oracle(tiny, prompt, 8)
        r0 = router.submit(prompt, 8, _greedy())
        assert list(r0.tokens(timeout=60)) == oracle
        donor = hs[r0.replica_id]
        donor_pid = donor._proc.pid
        assert donor_pid

        # the fill for the NEXT request wedges inside the donor's
        # BLOCK_DATA loop (kvx_stall); this timer delivers the real -9
        # while it is wedged — provably mid-transfer
        killer = threading.Timer(
            0.7, lambda: os.kill(donor_pid, signal.SIGKILL))
        killer.start()
        t0 = time.perf_counter()
        r1 = router.submit(prompt, 8, _greedy())
        toks = list(r1.tokens(timeout=120))
        killer.join()
        assert toks == oracle, "post-kill output diverged"
        assert r1.replica_id != donor.id
        # the survivor stayed routable the whole time
        assert router.ready
        # the fill degraded, the request never failed
        survivor = hs[r1.replica_id]
        summ = survivor.summary()
        tgt_kvx = summ.get("kv_transfer") or {}
        assert tgt_kvx.get("fill_fallbacks", 0) >= 1, tgt_kvx
        assert summ.get("requests_failed", 0) == 0, summ
        # the dead donor is classified and respawned to routable
        end = time.perf_counter() + 180.0
        while time.perf_counter() < end:
            if donor.proc_stats.exit_classes.get("signal:SIGKILL"):
                break
            time.sleep(0.05)
        assert donor.proc_stats.exit_classes.get("signal:SIGKILL"), \
            donor.proc_stats.exit_classes
        while time.perf_counter() < end and not donor.ready:
            time.sleep(0.05)
        assert donor.ready, "donor did not respawn to routable"
        assert time.perf_counter() - t0 < 180.0
    finally:
        router.close()


# -- shadow-index staleness (the ISSUE 14 regression) -----------------------


def test_shadow_index_unit_truncate():
    sh = ShadowPrefixIndex(block_len=BL)
    toks = list(range(4 * BL + 1))
    sh.publish(toks)
    assert sh.match_len(toks) == 4 * BL
    assert sh.truncate(toks, 2 * BL) == 2  # two stale paths dropped
    assert sh.match_len(toks) == 2 * BL
    assert sh.truncate(toks, 2 * BL) == 0  # idempotent


def test_query_miss_clears_stale_shadow_entry(tiny):
    """Donor-side eviction of a transferred path must not leave the
    router fetching dead blocks: the donor's RMSG_BLOCK_QUERY miss
    answer (echoed on the ACCEPT frame) truncates the stale shadow
    entry, so the path stops attracting fetches — and the request that
    hit the miss still serves bit-identically via re-prefill."""
    from distributed_llama_tpu.runtime.replica_worker import WorkerClient

    c = _Cluster(tiny, blocks=4)  # tiny donor arena: 4 blocks total
    try:
        fam_a = _prompt(2 * BL + 1, seed=10)
        oracle_a = _oracle(tiny, fam_a, 6)
        # request A routes to r0 (round-robin first pick) and publishes
        # its 2 blocks there; the router's shadow records the path
        ra = c.router.submit(fam_a, 6, _greedy())
        assert list(ra.tokens(timeout=60)) == oracle_a
        donor = c.handles[ra.replica_id]
        assert donor.shadow.match_len(fam_a) == 2 * BL

        # evict A donor-side BEHIND the router's back: two more 2-block
        # families through a direct WorkerClient fill the 4-block pool
        # and LRU-evict A's path (the shadow still promises it)
        wc = WorkerClient("127.0.0.1", c.ports[donor.id])
        for s in (11, 12):
            fam = _prompt(2 * BL + 1, seed=s)
            rs = wc.submit(fam, 4, _greedy())
            for _ in rs.tokens(timeout=60):
                pass
        pc = c.servers[donor.id].sup.prefix_cache
        assert pc.match_len(fam_a) == 0, "eviction setup failed"
        assert donor.shadow.match_len(fam_a) == 2 * BL  # stale!

        # request A again: round-robin places it on the OTHER replica,
        # the fill targets the (stale) donor, the donor answers a MISS,
        # the shadow truncates, and the request re-prefills bit-exactly
        rb = c.router.submit(fam_a, 6, _greedy())
        assert list(rb.tokens(timeout=60)) == oracle_a
        assert rb.replica_id != donor.id
        tgt = c.servers[rb.replica_id].kvx_stats
        assert tgt.fills_requested == 1 and tgt.fills_ok == 0
        assert tgt.fill_misses == 1
        assert donor.shadow.match_len(fam_a) == 0, \
            "stale shadow entry survived the QUERY miss answer"
        assert c.router.kvx.shadow_truncates >= 1
    finally:
        c.close()


# -- prefill/decode disaggregation ------------------------------------------


def test_disaggregated_tiers_route_fill_and_fall_back(tiny):
    """--tier prefill|decode: the prompt runs on the prefill worker
    (max_tokens=0 pass, publishes blocks), the decode worker admits
    already-seeded via a fill from that donor, output is bit-identical
    to the unified oracle; prefill-tier replicas never serve requests;
    with the prefill worker drained the mixed path serves unchanged."""
    c = _Cluster(tiny, tiers=("prefill", "decode"))
    try:
        assert c.handles[0].tier == "prefill"
        assert c.handles[1].tier == "decode"
        prompt = _prompt(3 * BL + 3, seed=3)
        oracle = _oracle(tiny, prompt, 8)
        r = c.router.submit(prompt, 8, _greedy())
        assert list(r.tokens(timeout=60)) == oracle
        assert r.replica_id == 1, "prefill-tier replica served a request"
        assert c.router.kvx.prefill_passes == 1
        tgt = c.servers[1].kvx_stats
        assert tgt.fills_ok == 1 and tgt.tokens_filled == 3 * BL
        # the decode worker prefilled ONLY the suffix
        pcs = c.servers[1].sup.prefix_cache.stats
        assert pcs.tokens_saved == 3 * BL
        assert pcs.tokens_prefilled == len(prompt) - 3 * BL

        # no prefill worker routable -> unified mixed path, no failure
        c.handles[0].draining = True
        r2 = c.router.submit(prompt, 8, _greedy())
        assert list(r2.tokens(timeout=60)) == oracle
        assert c.router.kvx.prefill_pass_fallbacks == 1
    finally:
        c.close()


# -- /stats + CLI surface ---------------------------------------------------


def test_kv_transfer_block_present_in_every_tier(tiny):
    """The family must not vanish off a launch flag: a transfer-less
    supervisor summary gains an enabled=False block at the API layer
    (render path), and a router tier's aggregate block is real."""
    from distributed_llama_tpu.runtime.trace import render_prometheus

    off = KVTransferStats().summary()
    assert off["enabled"] is False
    text = render_prometheus({"kv_transfer": off})
    assert 'dllama_kv_transfer_info' in text
    assert 'enabled="False"' in text


def test_cli_dead_flag_validation(tiny, monkeypatch):
    """--kv-transfer/--tier dead-flag discipline at parse time (the
    api_server.serve validation block), in-process for speed."""
    from distributed_llama_tpu.apps import api_server
    from distributed_llama_tpu.apps.dllama import build_argparser

    def run(argv):
        args = build_argparser().parse_args(argv)
        with pytest.raises(SystemExit) as e:
            api_server.serve(args)
        return str(e.value)

    base = ["api", "--serve-batch", "2"]
    assert "--prefix-cache" in run(base + ["--kv-transfer",
                                           "--replicas", "2"])
    assert ">= 2 replicas" in run(base + ["--kv-transfer",
                                          "--prefix-cache"])
    # a ONE-replica process tier is still sibling-less (review-found:
    # process_tier truthiness must not stand in for a real fleet count)
    assert ">= 2 replicas" in run(base + ["--kv-transfer",
                                          "--prefix-cache",
                                          "--replica-procs", "1"])
    assert "--kv-transfer" in run(base + ["--prefix-cache",
                                          "--replicas", "2",
                                          "--tier", "prefill,decode"])
    assert "at least one decode" in run(
        base + ["--prefix-cache", "--replicas", "2", "--kv-transfer",
                "--tier", "prefill"])
    assert "2 roles for 3" in run(
        base + ["--prefix-cache", "--replicas", "3", "--kv-transfer",
                "--tier", "prefill,decode"])
    assert "prefill|decode|mixed" in run(
        base + ["--prefix-cache", "--replicas", "2", "--kv-transfer",
                "--tier", "prefill,bogus"])
    assert "--replica-hosts" in run(
        ["api", "--serve-batch", "2", "--prefix-cache", "--kv-transfer",
         "--replica-hosts", "h:1,h:2", "--tier", "prefill,decode"])
