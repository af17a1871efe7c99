"""Startup auto-sizing (``--serve-batch auto`` / ``--prefix-blocks auto``
via runtime/profiler.resolve_auto_shape) and the SLO-aware self-tuning
admission policy (runtime/scheduler.AdmissionPolicy).

The contracts under test:

  * auto-sizing NEVER exceeds what the HBM ledger says fits
    (headroom-capped), never exceeds the knee (knee-capped), and refuses
    a ledger-less engine with a clear error instead of crashing;
  * the adaptive chunk width converges to the ladder floor under a
    synthetic slow-step fault (the ``slow_step`` site) and recovers;
  * greedy outputs are BIT-IDENTICAL adaptive-vs-static (chunk
    boundaries must never change tokens — the scheduler parity contract
    extended to a moving width);
  * an adaptive run mints ZERO post-warmup compile keys (warmup warms
    the whole ladder, so ``--freeze-compiles`` stays green while the
    width moves);
  * the CLI sentinels and SLO flags validate at parse time (dead-flag
    rules), before any model load.
"""

import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.apps import dllama
from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.faults import FAULTS
from distributed_llama_tpu.runtime.profiler import (COMPILES,
                                                    DEFAULT_KNEE_ROWS,
                                                    resolve_auto_shape)
from distributed_llama_tpu.runtime.scheduler import (AdmissionPolicy,
                                                     Scheduler, chunk_ladder)
from distributed_llama_tpu.sampler import Sampler

SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
                     seq_len=SEQ, hidden_act=HiddenAct.SILU)
    host = random_tensors(spec, seed=3, scale=0.05)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    return spec, params


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)


# -- auto-sizing ------------------------------------------------------------


def test_auto_batch_headroom_capped(tiny):
    """`--serve-batch auto` never exceeds slots_addable: with a fake
    device limit worth 5 slots, a knee of 32 resolves to 5."""
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    per_slot = int(sum(x.nbytes for x in
                       __import__("jax").tree_util.tree_leaves(eng.cache)))
    dec = resolve_auto_shape(
        eng, serve_batch="auto", default_knee=32,
        device_stats={"bytes_in_use": 0, "bytes_limit": 5 * per_slot})
    assert dec["serve_batch"] == 5
    assert dec["serve_batch_basis"] == "hbm_cap"
    assert dec["inputs"]["slots_addable"] == 5
    # replicas split the same headroom
    dec2 = resolve_auto_shape(
        eng, serve_batch="auto", replicas=2, default_knee=32,
        device_stats={"bytes_in_use": 0, "bytes_limit": 5 * per_slot})
    assert dec2["serve_batch"] == 2


def test_auto_batch_knee_capped(tiny):
    """With ample headroom the knee is the cap; unset, it is the
    conservative default."""
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    dec = resolve_auto_shape(
        eng, serve_batch="auto", default_knee=4,
        device_stats={"bytes_in_use": 0, "bytes_limit": 1 << 40})
    assert dec["serve_batch"] == 4
    assert dec["serve_batch_basis"] == "default_heuristic"
    assert dec["inputs"]["knee_rows"] == 4
    dec2 = resolve_auto_shape(eng, serve_batch="auto", device_stats=None)
    assert dec2["serve_batch"] == DEFAULT_KNEE_ROWS
    assert dec2["serve_batch_basis"] == "default_heuristic"
    # a static serve_batch passes through untouched, the SLO recorded
    static = resolve_auto_shape(eng, serve_batch=6, slo_itl_ms=80.0,
                                device_stats=None)
    assert (static["serve_batch"], static["serve_batch_basis"]) == (
        6, "static")
    assert static["prefix_blocks_basis"] == "static"
    assert static["inputs"]["slo_itl_ms"] == 80.0


def test_auto_prefix_blocks_capped(tiny):
    """`--prefix-blocks auto`: the 2xBxcontext target, capped at HALF
    the blocks the free HBM could hold."""
    spec, params = tiny
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    bl = 16
    per_block = (2 * spec.n_layers * spec.n_kv_heads * bl
                 * spec.head_size * 4)
    dec = resolve_auto_shape(
        eng, serve_batch=2, prefix_blocks="auto", prefix_block_len=bl,
        device_stats={"bytes_in_use": 0, "bytes_limit": 8 * per_block})
    assert dec["prefix_blocks"] == 4  # 8 addable // 2
    assert dec["prefix_blocks_basis"] == "hbm_cap"
    dec2 = resolve_auto_shape(eng, serve_batch=2, prefix_blocks="auto",
                              prefix_block_len=bl, device_stats=None)
    assert dec2["prefix_blocks"] == 2 * 2 * SEQ // bl  # context heuristic
    assert dec2["prefix_blocks_basis"] == "context_heuristic"


def test_auto_refuses_ledgerless_engine():
    """A weightless front-door template (the process tier's parent)
    cannot be auto-sized: a clear ValueError, not a crash mid-build."""
    from distributed_llama_tpu.apps.dllama import FrontDoorTemplate

    class _Spec:
        seq_len = 64

    with pytest.raises(ValueError, match="ledger-capable"):
        resolve_auto_shape(FrontDoorTemplate(_Spec()), serve_batch="auto")


# -- the SLO-aware admission policy -----------------------------------------


def test_chunk_ladder_shape():
    assert chunk_ladder(32) == [32, 16, 8, 4]
    assert chunk_ladder(8) == [8, 4, 2, 1]
    assert chunk_ladder(2) == [2, 1]
    assert chunk_ladder(1) == [1]


def test_admission_policy_unit():
    """Shrink on ITL pressure (decode + prefill present), widen when
    decode idles or ITL is comfortable, cooldown-gated, ladder-bounded."""
    p = AdmissionPolicy(32, slo_itl_ms=10.0, cooldown=2)
    assert p.width == 32
    # pressure: EWMA above 0.85 * 10 with mixed work -> shrink one rung
    p.observe_step(20.0, decode_rows=2, prefill_rows=1)
    assert p.width == 16 and p.shrinks == 1
    # cooldown: the very next pressured step must NOT shrink again
    p.observe_step(20.0, decode_rows=2, prefill_rows=1)
    assert p.width == 16
    p.observe_step(20.0, decode_rows=2, prefill_rows=1)
    assert p.width == 8 and p.shrinks == 2
    # floor: pressure can never leave the ladder
    for _ in range(10):
        p.observe_step(50.0, decode_rows=2, prefill_rows=1)
    assert p.width == chunk_ladder(32)[-1]
    # recovery: comfortable ITL (< 0.5 * SLO EWMA) widens back up
    for _ in range(40):
        p.observe_step(1.0, decode_rows=2, prefill_rows=0)
    assert p.width == 32 and p.widens >= 3
    # pure-prefill iterations (decode idle) widen even with no samples
    p2 = AdmissionPolicy(32, slo_itl_ms=10.0, cooldown=1)
    p2._rung = 2
    p2.observe_step(30.0, decode_rows=0, prefill_rows=3)
    assert p2.width == 16 and p2.widens == 1
    # TTFT pressure with ITL headroom widens; without headroom it must
    # not (the ITL SLO wins the conflict)
    p3 = AdmissionPolicy(32, slo_ttft_ms=100.0, slo_itl_ms=10.0,
                         cooldown=1)
    p3._rung = 1
    p3.observe_ttft(95.0)
    p3.observe_step(6.0, decode_rows=2, prefill_rows=1)  # itl ewma 6.0
    assert p3.width == 32 and p3.widens == 1
    p3._rung = 1
    p3.itl_ewma_ms = 9.0  # near its own SLO: TTFT pressure is blocked
    p3.observe_step(9.0, decode_rows=2, prefill_rows=0)
    assert p3.width == 16


def test_adaptive_chunk_converges_under_slow_steps(tiny):
    """The acceptance shape: a synthetic slow-step fault (the
    ``slow_step`` site) drags every working step over the ITL SLO while
    prompts keep prefilling — the policy must walk the width down to the
    ladder floor (and the run must still produce correct tokens)."""
    spec, params = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=16, slo_itl_ms=30.0)
    sched.warmup()
    floor = sched.admission.ladder[-1]
    FAULTS.arm("slow_step", times=0, ms=40.0)  # every step > the SLO
    try:
        # one decode-heavy stream plus a SUPPLY of long prompts cycling
        # through the second slot: prefill_rows stays > 0 for many mixed
        # iterations — the composition the shrink rule requires — long
        # enough to walk the whole ladder down
        reqs = [sched.submit([1, 9, 23, 54], 24, _greedy(spec))]
        reqs += [sched.submit(list(range(1, 49)), 2, _greedy(spec))
                 for _ in range(3)]
        min_width = sched.admission.width
        for _ in range(800):
            if all(r.finished.is_set() for r in reqs):
                break
            sched.step()
            min_width = min(min_width, sched.admission.width)
        assert all(r.finished.is_set() for r in reqs)
    finally:
        FAULTS.clear()
        sched.close()
    adm = sched.stats.summary()["admission"]
    # the width walked the WHOLE ladder down while the fault held every
    # mixed step over the SLO (once decode idles at the trace tail, the
    # policy legitimately widens back — that recovery is also asserted)
    assert min_width == floor, (min_width, adm)
    assert adm["shrinks"] >= len(sched.admission.ladder) - 1
    assert adm["widens"] >= 1, adm
    assert adm["itl_ewma_ms"] > 30.0  # the signal it converged on


def test_greedy_parity_adaptive_vs_static(tiny):
    """Greedy outputs must be BIT-IDENTICAL whether the chunk width is
    pinned or adapting mid-run (an impossibly tight ITL SLO forces
    transitions): chunk boundaries never change tokens."""
    spec, params = tiny
    prompts = [[1, 9, 23, 54, 7, 88, 101, 5, 61, 17, 3] * 3,
               [2, 40, 77, 12, 9],
               list(range(1, 40))]
    budgets = [10, 8, 6]

    def serve(slo_itl):
        eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                     cache_dtype=jnp.float32)
        sched = Scheduler(eng, chunk=16, slo_itl_ms=slo_itl)
        sched.warmup()
        reqs = [sched.submit(p, k, _greedy(spec))
                for p, k in zip(prompts, budgets)]
        for _ in range(600):
            if all(r.finished.is_set() for r in reqs):
                break
            sched.step()
        outs = [list(r.tokens(timeout=5.0)) for r in reqs]
        adm = sched.admission.summary() if sched.admission else None
        sched.close()
        return outs, adm

    static_outs, _ = serve(None)
    adaptive_outs, adm = serve(0.0001)  # every step "violates" -> shrink
    assert adm["shrinks"] >= 1, adm  # the width really moved
    assert adaptive_outs == static_outs


def test_zero_compiles_after_warmup_adaptive(tiny):
    """Warmup compiles EVERY ladder rung, so an adaptive run — width
    transitions included — mints zero post-warmup keys, and the same
    run is clean under the --freeze-compiles refusal."""
    spec, params = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=16, slo_itl_ms=0.0001)  # always shrink
    sched.warmup()  # warms 16/8/4/2 + decode + arms the sentinel
    before = COMPILES.after_warmup
    prev_freeze = COMPILES.freeze
    COMPILES.freeze = True
    try:
        reqs = [sched.submit(list(range(1, 34)), 6, _greedy(spec)),
                sched.submit([2, 40, 77], 8, _greedy(spec))]
        for _ in range(400):
            if all(r.finished.is_set() for r in reqs):
                break
            sched.step()
        assert all(r.finished.is_set() for r in reqs)
        for r in reqs:
            assert r.finish_reason == "length"  # no frozen refusal
    finally:
        COMPILES.freeze = prev_freeze
        sched.close()
    assert sched.admission.shrinks >= 1  # widths genuinely moved
    assert COMPILES.after_warmup == before


# -- CLI validation (dead-flag rules, parse time) ---------------------------


def test_admission_metrics_render_in_both_tiers():
    """The dllama_admission_* family must ride /metrics on the
    single-supervisor tier AND, replica-labelled, on router tiers whose
    aggregate summary carries no top-level admission block (a tier must
    not lose a metric family to a launch flag — the PR-8 rule)."""
    from distributed_llama_tpu.runtime.trace import render_prometheus

    adm = AdmissionPolicy(32, slo_itl_ms=50.0).summary()
    top = render_prometheus({"admission": adm})
    assert "dllama_admission_chunk_width 32" in top
    assert 'dllama_admission_chunk_changes_total{direction="shrink"}' \
        in top
    routed = render_prometheus({"replicas": [
        {"replica": 0, "state": "ready", "admission": adm},
        {"replica": 1, "state": "ready"}]})
    assert ('dllama_replica_admission_chunk_width{replica="0"} 32'
            in routed)
    assert "dllama_admission_chunk_width" not in routed.replace(
        "dllama_replica_admission", "")


def test_slo_flags_rejected_on_replica_hosts_tier():
    """Pre-started --replica-hosts workers own their configs — the
    parent cannot arm their policies, so SLO flags there are the silent
    dead configuration the parse-time rules exist to refuse."""
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--serve-batch", "2",
                     "--replica-hosts", "h1:9001,h2:9001",
                     "--slo-itl-ms", "80"])
    assert "--replica-hosts" in str(ei.value)


def test_slo_flags_rejected_without_serve_batch():
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--slo-itl-ms", "50"])
    assert "--serve-batch" in str(ei.value)
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--slo-ttft-ms", "500"])
    assert "--serve-batch" in str(ei.value)
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--serve-batch", "2", "--slo-itl-ms", "-5"])
    assert "> 0" in str(ei.value)


def test_auto_sentinels_validate_at_parse_time():
    """'auto' parses (argparse type), garbage does not; auto on the
    process tier is a clear error (no ledger-capable local engine)."""
    ap = dllama.build_argparser()
    args = ap.parse_args(["api", "--serve-batch", "auto",
                          "--prefix-blocks", "AUTO"])
    assert args.serve_batch == "auto" and args.prefix_blocks == "auto"
    with pytest.raises(SystemExit):
        ap.parse_args(["api", "--serve-batch", "many"])

    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--serve-batch", "auto", "--replica-procs", "2"])
    assert "ledger-capable" in str(ei.value)
