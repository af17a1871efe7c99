"""Device-tier observability (runtime/profiler.py): the compile ledger +
recompile sentinel, the HBM ledger, on-demand capture, and build info —
the ISSUE 10 acceptance bars:

  * ZERO post-warmup compiles across the legacy / supervisor / router
    serving paths on the existing traffic shapes (the runtime twin of
    dlgrind's static fingerprint gate), including across a supervisor
    crash-recovery rebuild;
  * a deliberately minted NEW compile key (an unregistered prefill
    chunk width) fires ``compile_after_warmup`` — and, under
    ``--freeze-compiles``, a structured ``RequestError`` BEFORE the
    compile runs;
  * the HBM ledger's slot/arena byte counts match the engine's
    allocated shapes EXACTLY on CPU-tiny (they are real ``nbytes``);
  * with no capture running the span guard is allocation-free on the
    hot path (guard-before-call, the tracer's <50-blocks discipline).
"""

import json
import os
import sys
import time

import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.profiler import (COMPILES, PROFILER,
                                                    build_info,
                                                    compile_key_str,
                                                    hbm_ledger)
from distributed_llama_tpu.runtime.scheduler import RequestError, Scheduler
from distributed_llama_tpu.runtime.trace import TRACER
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
def clean_ledgers():
    COMPILES.reset()
    PROFILER.reset()
    TRACER.reset()
    yield
    COMPILES.reset()
    PROFILER.reset()
    TRACER.reset()


def _engine(tiny, batch=2):
    spec, params = tiny
    return Engine(spec, params, batch=batch, compute_dtype=jnp.float32,
                  cache_dtype=jnp.float32)


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)


# -- compile ledger ---------------------------------------------------------


def test_ledger_records_every_mint_with_key_and_ms(tiny):
    spec, _ = tiny
    eng = _engine(tiny, batch=1)
    before = COMPILES.total  # 0 on CPU: an unsharded engine's cache is
    # built eagerly, the jitted cache maker exists only on meshes
    eng.generate([1, 9, 23, 54, 7], 3, _greedy(spec))
    s = COMPILES.summary()
    assert s["total"] > before          # prefill seg + decode step minted
    assert s["after_warmup"] == 0       # nothing marked warm yet
    assert s["total_ms"] > 0.0
    assert "seg:1" in s["by_key"]       # the decode step's key
    rec = s["by_key"]["seg:1"]
    assert rec["count"] == 1 and rec["ms"] > 0.0
    # steady state restored: the raw jitted callable is back in _steps
    # (the watch swapped itself out after the first call)
    from distributed_llama_tpu.runtime.profiler import _CompileWatch
    assert not isinstance(eng._steps[1], _CompileWatch)


def test_key_strings_are_label_safe():
    assert compile_key_str(1) == "seg:1"
    assert compile_key_str("slot_decode") == "slot_decode"
    assert compile_key_str(("slot_prefill", 16)) == "slot_prefill:16"
    ks = compile_key_str(("prefix_arena", (16, 2, 2, 4, 16)))
    assert ks == "prefix_arena:16x2x2x4x16"
    assert all(c.isalnum() or c in "_:.x-" for c in ks)


def test_zero_post_warmup_compiles_supervisor_traffic(tiny):
    """The supervisor tier acceptance bar: warmup compiles the serving
    set; the existing traffic shapes then mint NOTHING — every request
    rides slot_prefill_chunk_C + slot_decode_step."""
    from distributed_llama_tpu.runtime.resilience import EngineSupervisor

    spec, params = tiny
    sup = EngineSupervisor(lambda: Engine(spec, params, batch=2,
                                          compute_dtype=jnp.float32,
                                          cache_dtype=jnp.float32),
                           chunk=8, stall_timeout=60.0)
    try:
        assert COMPILES.after_warmup == 0
        for n in (3, 5, 9, 12):  # varied lengths: same chunked shapes
            req = sup.submit(list(range(1, n + 1)), 4, _greedy(spec))
            assert len(list(req.tokens(timeout=60.0))) >= 1
        assert COMPILES.after_warmup == 0, COMPILES.summary()
    finally:
        sup.close()


def test_zero_post_warmup_compiles_across_recovery(tiny):
    """A crash-recovery rebuild mints a FRESH engine whose own warmup
    legitimately recompiles the serving set — the sentinel must not
    misread those (the warm flag is per engine), and post-recovery
    traffic still mints nothing."""
    from distributed_llama_tpu.runtime.faults import FAULTS
    from distributed_llama_tpu.runtime.resilience import EngineSupervisor

    spec, params = tiny
    sup = EngineSupervisor(lambda: Engine(spec, params, batch=2,
                                          compute_dtype=jnp.float32,
                                          cache_dtype=jnp.float32),
                           chunk=8, stall_timeout=60.0,
                           backoff_base=0.01)
    try:
        FAULTS.arm("step_raise", after=0, times=1)
        req = sup.submit([1, 2, 3], 4, _greedy(spec))
        with pytest.raises(RequestError):
            list(req.tokens(timeout=60.0))
        deadline = time.perf_counter() + 60.0
        while not sup.ready and time.perf_counter() < deadline:
            time.sleep(0.02)
        assert sup.ready
        req = sup.submit([1, 9, 23, 54, 7], 4, _greedy(spec))
        assert len(list(req.tokens(timeout=60.0))) == 4
        assert COMPILES.after_warmup == 0, COMPILES.summary()
        assert sup.sup_stats.recoveries == 1
    finally:
        FAULTS.clear()
        sup.close()


def test_zero_post_warmup_compiles_router_traffic(tiny):
    """The thread-router tier: two warmed replicas over shared weights;
    routed traffic on the existing shapes mints nothing anywhere."""
    from distributed_llama_tpu.runtime.router import Router

    spec, params = tiny
    router = Router(lambda: Engine(spec, params, batch=2,
                                   compute_dtype=jnp.float32,
                                   cache_dtype=jnp.float32),
                    replicas=2, policy="round_robin", chunk=8,
                    stall_timeout=60.0)
    try:
        assert COMPILES.after_warmup == 0
        for _ in range(4):  # both replicas serve
            req = router.submit([1, 9, 23, 54, 7], 3, _greedy(spec))
            assert len(list(req.tokens(timeout=60.0))) == 3
        assert COMPILES.after_warmup == 0, COMPILES.summary()
        assert router.summary()["compiles"]["after_warmup"] == 0
    finally:
        router.close()


def test_legacy_repeat_shapes_mint_nothing(tiny):
    """The legacy tier's version of the bar: the first serve of a shape
    compiles; repeating the SAME shapes mints zero new executables."""
    spec, _ = tiny
    eng = _engine(tiny, batch=1)
    eng.generate([1, 9, 23, 54, 7], 3, _greedy(spec))
    before = COMPILES.total
    eng.reset()
    eng.generate([2, 8, 22, 50, 9], 3, _greedy(spec))  # same lengths
    assert COMPILES.total == before, COMPILES.summary()


def test_new_key_fires_sentinel_and_freeze_refuses(tiny):
    """The sentinel proven both ways: an unregistered chunk width on a
    WARM engine (1) records compile_after_warmup (event + counter), and
    (2) under freeze raises the structured error BEFORE compiling —
    unfreezing then compiles the same key fine (nothing was poisoned)."""
    import numpy as np

    spec, _ = tiny
    TRACER.configure(capacity=256)
    eng = _engine(tiny)
    sched = Scheduler(eng, chunk=8)
    sched.warmup()  # arms the sentinel (engine._compile_warm)
    assert eng._compile_warm

    gate = np.full((eng.batch,), eng.seq_len, np.int32)
    tok16 = np.zeros((eng.batch, 16), np.int32)  # unregistered width
    lidx = np.zeros((eng.batch,), np.int32)

    COMPILES.freeze = True
    with pytest.raises(RequestError) as ei:
        eng.slot_prefill_chunk(tok16, gate, lidx)
    assert ei.value.code == "compile_after_warmup"
    assert ei.value.retryable is False
    assert "slot_prefill:16" in str(ei.value)
    assert COMPILES.after_warmup == 1
    # refused BEFORE the compile: no record of the key was minted
    assert "slot_prefill:16" not in COMPILES.summary()["by_key"]

    COMPILES.freeze = False
    eng.slot_prefill_chunk(tok16, gate, lidx)  # now compiles (sentinel
    assert COMPILES.after_warmup == 2          # still counts the event)
    assert "slot_prefill:16" in COMPILES.summary()["by_key"]
    evs = [e for e in TRACER.recent(0)
           if e["kind"] == "compile_after_warmup"]
    assert len(evs) == 2
    assert evs[0]["key"] == "slot_prefill:16" and evs[0]["frozen"] is True
    sched.close()


# -- HBM ledger -------------------------------------------------------------


def test_hbm_ledger_matches_allocated_shapes_exactly(tiny):
    """The acceptance bar: slot/arena byte counts equal the engine's
    REAL allocated shapes on CPU-tiny (nbytes, not estimates)."""
    from distributed_llama_tpu.runtime.prefix_cache import PrefixCache

    spec, _ = tiny
    eng = _engine(tiny, batch=2)
    pc = PrefixCache(eng, num_blocks=16, block_len=4)
    led = hbm_ledger(eng, pc, device_stats=False)
    # KV slots: 2 (K+V) x layers x (B, KVH, S, HS) f32
    want_kv = 2 * spec.n_layers * 2 * spec.n_kv_heads * SEQ * \
        spec.head_size * 4
    assert led["kv_slot_bytes"] == want_kv
    assert led["kv_slot_bytes"] == sum(
        leaf.nbytes for leaf in list(eng.cache.k) + list(eng.cache.v))
    # arena: 2 x (16, layers, KVH, 4, HS) f32 — the real arrays
    want_arena = 2 * 16 * spec.n_layers * spec.n_kv_heads * 4 * \
        spec.head_size * 4
    assert led["prefix_arena_bytes"] == want_arena
    assert led["prefix_arena_bytes"] == (pc.arena_k.nbytes
                                         + pc.arena_v.nbytes)
    assert led["per_slot_bytes"] * eng.batch == led["kv_slot_bytes"]
    assert led["per_block_bytes"] * 16 == led["prefix_arena_bytes"]
    assert led["weights_bytes"] > 0
    # the vocab split-out (ISSUE-15): tok_emb + wcls land in their own
    # category and the accounted identity carries all five
    assert led["vocab_bytes"] > 0
    assert led["accounted_bytes"] == (
        led["weights_bytes"] + led["vocab_bytes"] + led["kv_slot_bytes"]
        + led["prefix_arena_bytes"] + led["logits_workspace_bytes"])
    # CPU backend: no allocator stats — nulls, never fabricated numbers
    cpu_led = hbm_ledger(eng, pc)
    if cpu_led["device_bytes_in_use"] is None:
        assert cpu_led["slots_addable"] is None
    json.dumps(led)  # /stats- and BENCH-ready


def test_hbm_block_rides_supervisor_stats(tiny):
    from distributed_llama_tpu.runtime.resilience import EngineSupervisor

    spec, params = tiny
    sup = EngineSupervisor(lambda: Engine(spec, params, batch=2,
                                          compute_dtype=jnp.float32,
                                          cache_dtype=jnp.float32),
                           chunk=8, stall_timeout=60.0,
                           prefix_blocks=8, prefix_block_len=4)
    try:
        s = sup.summary()
        assert s["hbm"]["kv_slot_bytes"] > 0
        assert s["hbm"]["prefix_arena_bytes"] > 0
        assert s["compiles"]["total"] >= 2  # the warmed serving set
    finally:
        sup.close()


# -- disabled-path allocation + overhead bars -------------------------------


def test_profiler_disabled_is_allocation_free():
    """No capture running and no --trace: the guard every span site uses
    (`if TRACER.spans:`) is one attribute read and allocates nothing."""
    assert not TRACER.capturing and not TRACER.spans

    def guarded_loop(n):
        for _ in range(n):
            if TRACER.spans:  # the scheduler's guard pattern
                TRACER.end(TRACER.span("sched.step"))

    guarded_loop(10)  # warm code object/locals
    before = sys.getallocatedblocks()
    guarded_loop(10_000)
    grew = sys.getallocatedblocks() - before
    assert grew < 50, f"disabled guard allocated {grew} blocks"


# -- capture ----------------------------------------------------------------


def test_capture_writes_a_trace_and_refuses_concurrent(tmp_path):
    d = str(tmp_path / "cap")
    t0 = time.perf_counter()
    out = PROFILER.capture(d, ms=20)
    assert out["dir"] == d and os.path.isdir(d)
    assert PROFILER.captures == 1
    # the instants of the trace on the ring's clock (perf_counter)
    assert t0 <= out["t_start_mono"] < out["t_stop_mono"] <= (
        time.perf_counter())
    assert out["t_stop_mono"] - out["t_start_mono"] >= 0.02
    assert out["stop_ms"] >= 0.0 and not TRACER.capturing
    # the busy refusal: hold the slot, expect the structured error
    PROFILER._busy = True
    with pytest.raises(RuntimeError, match="busy"):
        PROFILER.capture(str(tmp_path / "cap2"), ms=10)
    PROFILER._busy = False


# -- build info -------------------------------------------------------------


def test_build_info_shape(tiny):
    eng = _engine(tiny, batch=1)
    b = build_info(eng)
    assert set(b) == {"version", "jax", "backend", "device_kind",
                      "device_count", "mesh"}
    assert b["mesh"] == "single" and b["backend"] == "cpu"
    # the device as JAX reports it — chip_smoke.py's last line comes from
    # this block (conftest pins 8 virtual CPU devices)
    assert b["device_kind"] == "cpu" and b["device_count"] == 8
    assert b["version"] and b["jax"]
    assert build_info(None)["mesh"] == "single"


def test_ledger_records_kernels_per_executable(tiny):
    """A silent-fallback guard (ISSUE 22): the compile ledger carries,
    per minted executable, which Pallas kernels are in it (None = not
    inspected: this CPU engine compiles no kernels)."""
    import numpy as np

    from distributed_llama_tpu.runtime.profiler import _kernels_in

    COMPILES.reset()
    eng = _engine(tiny, batch=2)
    eng.slot_decode_step(np.zeros((2, 1), np.int32),
                         np.full((2,), eng.seq_len, np.int32))
    rec = COMPILES.summary()["by_key"]["slot_decode"]
    assert rec["count"] == 1 and rec["kernels"] is None
    assert not eng.use_pallas and _kernels_in(eng, None, ()) is None
    COMPILES.reset()
