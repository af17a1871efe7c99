"""Flight recorder (runtime/trace.py): ring semantics, span recording
through the real scheduler, the /metrics Prometheus plane across all
three serving tiers, /admin/trace JSONL export, cross-process span
rebase, and the two acceptance bars the ISSUE pins:

  * tracing-enabled overhead <= 2% of a decode step (measured against
    the REAL slot_decode_step on the tiny model — the tracer's per-step
    cost is microseconds against a multi-millisecond step);
  * the disabled path is an allocation-free no-op (the call-site
    ``if TRACER.enabled:`` guard runs before any kwargs dict exists).

The HTTP tier tests drive the real ThreadingHTTPServer handlers, same
discipline as tests/test_apps.py; a tiny Prometheus text parser
validates exposition-format invariants (one HELP/TYPE per metric,
sample lines parse, labels quoted) instead of eyeballing strings.
"""

import http.client
import json
import math
import re
import threading
import time

import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.scheduler import Scheduler
from distributed_llama_tpu.runtime.trace import (TRACER, Tracer, _sampled,
                                                 render_prometheus)
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
def clean_tracer():
    TRACER.reset()
    yield
    TRACER.reset()


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)


def _engine(tiny, batch=2):
    spec, params = tiny
    return Engine(spec, params, batch=batch, compute_dtype=jnp.float32,
                  cache_dtype=jnp.float32)


# -- ring + core semantics --------------------------------------------------


def test_ring_is_bounded_and_keeps_newest():
    TRACER.configure(capacity=32)
    for i in range(100):
        TRACER.event("enqueue", i + 1, seq=i)
    evs = TRACER.recent(0)
    assert len(evs) == 32
    assert [e["seq"] for e in evs] == list(range(68, 100))
    assert TRACER.recent(5) == evs[-5:]


def test_by_id_selects_one_span():
    TRACER.configure(capacity=128)
    a, b = TRACER.new_id(), TRACER.new_id()
    TRACER.event("enqueue", a)
    TRACER.event("enqueue", b)
    TRACER.event("finish", a, reason="stop")
    span = TRACER.by_id(a)
    assert [e["kind"] for e in span] == ["enqueue", "finish"]
    assert all(e["tid"] == a for e in span)


def test_disabled_records_nothing_and_is_allocation_free():
    """The off path: no ring growth, and the call-site guard pattern
    (`if TRACER.enabled:`) allocates nothing — conftest disables
    automatic GC, so getallocatedblocks deltas are deterministic."""
    import sys

    assert not TRACER.enabled
    TRACER.event("enqueue", 1, n_prompt=5)   # direct call: still a no-op
    TRACER.step(decode_rows=1, prefill_rows=0, chunk=0, queue_depth=0,
                wall_ms=1.0)
    assert TRACER.recent(0) == []
    assert TRACER.step_timeline() == {}

    def guarded_loop(n):
        for _ in range(n):
            if TRACER.enabled:  # the pattern every hot call site uses
                TRACER.event("decode", 1, n_out=1)

    guarded_loop(10)  # warm the code object/locals
    before = sys.getallocatedblocks()
    guarded_loop(10_000)
    grew = sys.getallocatedblocks() - before
    assert grew < 50, f"disabled guard allocated {grew} blocks"


def test_sampling_is_deterministic_per_id():
    assert _sampled(123, 1.0) and not _sampled(123, 0.0)
    picks = {tid: _sampled(tid, 0.3) for tid in range(1, 2000)}
    assert picks == {tid: _sampled(tid, 0.3) for tid in range(1, 2000)}
    frac = sum(picks.values()) / len(picks)
    assert 0.2 < frac < 0.4  # hash spreads sequential ids


def test_sink_rotation_and_jsonl(tmp_path):
    sink_dir = str(tmp_path / "traces")
    t = Tracer()
    t.configure(capacity=64, sink_dir=sink_dir, sink_max_bytes=2000,
                sink_max_files=3)
    for i in range(200):
        t.event("enqueue", i + 1, n_prompt=4)
    files = sorted((tmp_path / "traces").glob("trace-*.jsonl"))
    assert 1 < len(files) <= 3  # rotated AND bounded
    for f in files:
        for line in f.read_text().splitlines():
            rec = json.loads(line)
            assert rec["kind"] == "enqueue" and "ts_wall" in rec
    t.reset()


def test_sink_sampling_drops_unsampled_spans(tmp_path):
    sink_dir = str(tmp_path / "traces")
    t = Tracer()
    t.configure(capacity=4096, sink_dir=sink_dir, sample=0.0)
    t.event("enqueue", 7, n_prompt=4)      # span event: sampled out
    t.event("fault", 0, site="step_raise")  # tid 0 infra: always kept
    t.reset()  # closes the sink, flushing
    lines = []
    for f in (tmp_path / "traces").glob("trace-*.jsonl"):
        lines += f.read_text().splitlines()
    kinds = [json.loads(ln)["kind"] for ln in lines]
    assert kinds == ["fault"]
    assert len(t.by_id(7)) == 0  # reset cleared the ring too


def test_step_timeline_tool_reads_what_the_sink_writes(tmp_path):
    """tools/step_timeline.py over a sink's files (sample 0: the step
    records stay): medians and means a span over the iterations that ran
    both programs inside the window, the host's ms (wall less sched.wait)
    and its median by decoding rows; decode-only iterations and those
    before `skip_s` are left out."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import step_timeline

    t = Tracer()
    t.configure(capacity=64, sink_dir=str(tmp_path / "tr"), sample=0.0)
    t.event("enqueue", 9, n_prompt=4)               # sampled out
    for i in range(60):
        dec = 0 if i % 10 == 9 else 1 + i % 2       # every tenth: chunk only
        t.step(decode_rows=dec, prefill_rows=3, chunk=16, queue_depth=0,
               wall_ms=30.0 + dec, n=i, ts0=0.0,
               phases={"sched.wait": 24.0, "sched.sample_emit": 2.0 * dec,
                       "sched.dispatch.prefill": 3.0})
    t.reset()
    steps = step_timeline.steps_of(str(tmp_path / "tr"))
    assert len(steps) == 54 and all(r["dec"] and r["pre"] for r in steps)
    row = step_timeline.summary(steps, skip_s=0.0, window_s=60.0)
    assert row["n"] == 54 and row["wait"] == [24.0, 24.0]
    assert row["dispatch.prefill"] == [3.0, 3.0]
    assert row["host_by_dec"] == {1: 7.0, 2: 8.0}
    assert row["host"][0] in (7.0, 7.5, 8.0) and row["pre_mean"] == 3.0
    assert step_timeline.summary(steps, skip_s=3600.0, window_s=1.0) == {
        "n": 0}
    # --decode-only: the iterations that ran a decode step and no chunk
    t.configure(capacity=64, sink_dir=str(tmp_path / "dec"), sample=0.0)
    for i in range(30):
        t.step(decode_rows=8, prefill_rows=i % 3 == 0, chunk=32,
               queue_depth=0, wall_ms=36.0, n=i, ts0=0.0,
               phases={"sched.wait": 29.0, "sched.sample_emit": 3.0})
    t.reset()
    alone = step_timeline.steps_of(str(tmp_path / "dec"), decode_only=True)
    assert len(alone) == 20 and not any(r["pre"] for r in alone)
    row = step_timeline.summary(alone, skip_s=0.0, window_s=60.0)
    assert row["host"] == [7.0, 7.0] and row["host_by_dec"] == {8: 7.0}


def test_span_reads_survive_concurrent_appends():
    """by_id/export_span run on pump/HTTP threads while step threads
    append lock-free: they must snapshot the deque first — iterating it
    live raises "deque mutated during iteration" (review-found: the
    worker's _ship_trace would then drop the terminal frame and fabricate
    a replica_lost failover for a healthy worker)."""
    TRACER.configure(capacity=4096)
    stop = threading.Event()
    errs = []

    def writer():
        i = 0
        while not stop.is_set():
            TRACER.event("decode", (i % 7) + 1, n_out=i)
            i += 1

    def reader():
        try:
            for _ in range(3000):
                TRACER.by_id(3)
                TRACER.export_span(4)
        except RuntimeError as e:  # pragma: no cover — the regression
            errs.append(e)

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    try:
        reader()
    finally:
        stop.set()
        w.join(timeout=10)
    assert not errs, errs


def test_export_span_ingest_rebases_cross_process():
    """Worker -> parent span shipping: the wall-stamped export lands on
    the ingesting tracer's monotonic timeline in event order."""
    worker = Tracer()
    worker.configure(capacity=64)
    tid = 42
    worker.event("enqueue", tid, n_prompt=3)
    worker.event("finish", tid, reason="stop")
    shipped = worker.export_span(tid)
    assert all("ts_wall" in e for e in shipped)

    TRACER.configure(capacity=64)
    TRACER.event("route", tid, replica=0, reason="fallback")
    TRACER.ingest(shipped, origin="worker@x:1")
    span = TRACER.by_id(tid)
    assert [e["kind"] for e in span] == ["route", "enqueue", "finish"]
    assert span[1]["origin"] == "worker@x:1"
    # rebased timestamps are on THIS tracer's clock: within a second of
    # now, and ordered
    now = time.perf_counter()
    assert all(abs(e["ts"] - now) < 5.0 for e in span)
    assert span[1]["ts"] <= span[2]["ts"]
    worker.reset()


# -- span + step timeline through the real scheduler ------------------------


def test_scheduler_records_span_and_step_timeline(tiny):
    spec, _ = tiny
    TRACER.configure(capacity=4096, decode_every=2)
    eng = _engine(tiny)
    sched = Scheduler(eng, chunk=8)
    req = sched.submit([1, 9, 23, 54, 7, 11, 40, 3, 15, 2, 8, 31, 5, 77, 6,
                        19, 4], 6, _greedy(spec))
    while not req.finished.is_set():
        sched.step()
    sched.close()

    assert req.trace_id > 0
    span = TRACER.by_id(req.trace_id)
    kinds = [e["kind"] for e in span]
    # lifecycle order: enqueue -> admit -> prefill chunks -> first token
    # -> decode progress -> finish
    assert kinds[0] == "enqueue"
    assert "admit" in kinds and "prefill" in kinds
    assert kinds.index("admit") < kinds.index("prefill")
    assert "first_token" in kinds
    assert kinds[-1] == "finish"
    fin = span[-1]
    assert fin["reason"] == "length" and fin["n_out"] == 6
    # 17-token prompt at chunk 8, alone on 2 rows: 3 segments, chained
    # 2 + 1 = exactly 2 prefill events, one a chunk program
    assert kinds.count("prefill") == 2
    assert [(e["off"], e["n"]) for e in span
            if e["kind"] == "prefill"] == [(0, 16), (16, 1)]
    assert kinds.count("decode") >= 1  # cadence 2 over 6 tokens
    # timestamps are monotonic within the span (one clock domain)
    assert all(a["ts"] <= b["ts"] for a, b in zip(span, span[1:]))

    tl = TRACER.step_timeline()
    assert tl, "no step records"
    assert any(k[1] > 0 for k in tl)  # a prefill composition
    assert any(k[0] > 0 and k[1] == 0 for k in tl)  # a pure-decode one
    assert all(v["p50_ms"] >= 0 and v["n"] > 0 for v in tl.values())


def test_attn_grid_steps_are_exported_and_a_dispatch_adds_the_kernels_grid(
        tiny, monkeypatch):
    """/stats attn_grid_steps_decode and _prefill: a dispatch adds the grid
    the program's flash_attention calls RUN (read off the pallas_calls the
    programs trace), times the layers that attend; both reach /metrics."""
    from distributed_llama_tpu.ops import pallas_attention as pa
    from distributed_llama_tpu.runtime.stats import WINDOW_COUNTERS

    spec, params = tiny
    seen, real = [], pa.pl.pallas_call

    def spy(kernel, *a, **kw):
        if kw.get("name") == "flash_attention":
            seen.append(tuple(kw["grid_spec"].grid))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    pa.flash_attention.clear_cache()  # the programs below trace it anew
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32, pallas_interpret=True)
    sched = Scheduler(eng, chunk=8)
    req = sched.submit(list(range(1, 18)), 6, _greedy(spec))
    while not req.finished.is_set():
        sched.step()
    sched.close()
    stats = sched.stats
    hs = spec.dim // spec.n_heads
    grid = {t: pa.flash_grid(2, t, spec.n_heads, spec.n_kv_heads, SEQ, hs,
                             jnp.float32, jnp.float32) for t in (1, 8)}
    assert grid[1] == grid[8] == (2, 1, 1)  # both KV heads in one tile
    assert set(seen) == set(grid.values()) and len(seen) >= 2
    steps = {t: spec.n_layers * math.prod(g) for t, g in grid.items()}
    assert [eng.attn_grid_steps(t) for t in (1, 8)] == [steps[1], steps[8]]
    assert stats.decode_steps > 0 and stats.prefill_steps == 2
    assert stats.attn_grid_steps_decode == stats.decode_steps * steps[1]
    assert stats.attn_grid_steps_prefill == stats.prefill_steps * steps[8]
    # an engine on the XLA path holds no such kernel and counts none
    assert _engine(tiny).attn_grid_steps(1) == 0
    assert {"attn_grid_steps_decode",
            "attn_grid_steps_prefill"} <= set(WINDOW_COUNTERS)
    m = _parse_prometheus(render_prometheus(stats.summary(), model="x"))
    for program in ("decode", "prefill"):
        (_, v), = m[f"dllama_attn_grid_steps_{program}_total"]
        assert v == getattr(stats, f"attn_grid_steps_{program}")


def test_prefix_seed_event_records_hit_length(tiny):
    """The span's `seed` event carries the prefix-cache hit length: 0 on
    the cold serve, the whole-block match on the warm repeat (the same
    len-1-capped rule PrefixCache.lookup_pin applies)."""
    from distributed_llama_tpu.runtime.prefix_cache import PrefixCache

    spec, _ = tiny
    TRACER.configure(capacity=2048)
    eng = _engine(tiny)
    pc = PrefixCache(eng, num_blocks=16, block_len=4)
    sched = Scheduler(eng, chunk=8, prefix_cache=pc)
    sched.warmup()
    p = [1, 9, 23, 54, 7, 11, 40, 3, 15]  # two whole 4-token blocks
    outs = []
    reqs = []
    for _ in range(2):
        req = sched.submit(p, 3, _greedy(spec))
        while not req.finished.is_set():
            sched.step()
        outs.append(list(req.tokens(timeout=5.0)))
        reqs.append(req)
    sched.close()
    assert outs[0] == outs[1]  # seeded == cold (the parity guarantee)
    seeds = [next(e for e in TRACER.by_id(r.trace_id)
                  if e["kind"] == "seed") for r in reqs]
    assert seeds[0]["hit"] == 0      # cold
    assert seeds[1]["hit"] == 8      # two published whole blocks
    assert all(s["n_prompt"] == len(p) for s in seeds)


def test_error_frames_record_error_events(tiny):
    spec, _ = tiny
    TRACER.configure(capacity=1024)
    eng = _engine(tiny)
    sched = Scheduler(eng, chunk=8)
    req = sched.submit([1, 2, 3], 4, _greedy(spec))
    sched.close()  # fails queued work with structured shutdown frames
    span = TRACER.by_id(req.trace_id)
    err = [e for e in span if e["kind"] == "error"]
    assert err and err[-1]["code"] == "shutdown"
    assert err[-1]["retryable"] is False


def test_fired_fault_sites_land_on_timeline(tiny):
    from distributed_llama_tpu.runtime.faults import FAULTS, FaultError

    spec, _ = tiny
    TRACER.configure(capacity=1024)
    eng = _engine(tiny)
    sched = Scheduler(eng, chunk=8)
    FAULTS.arm("step_raise", after=0, times=1)
    try:
        sched.submit([1, 2, 3], 2, _greedy(spec))
        with pytest.raises(FaultError):
            sched.step()
    finally:
        FAULTS.clear()
        sched.close()
    fired = [e for e in TRACER.recent(0) if e["kind"] == "fault"]
    assert fired and fired[0]["site"] == "step_raise"


# -- the <= 2% overhead acceptance bar --------------------------------------


def test_tracing_overhead_at_most_two_percent_of_decode_step(tiny):
    """ISSUE 9 acceptance: enabled tracing costs <= 2% of the decode-step
    microbench. Measured composition: per-iteration cost = one step()
    record + the per-token span events a worst-case step emits (every
    row at the decode_every cadence), timed tightly over many
    iterations; the decode step is the REAL slot_decode_step on the tiny
    model (the smallest — i.e. least favorable — denominator; real
    models are 10-1000x slower per step, the tracer cost is constant)."""
    spec, _ = tiny
    eng = _engine(tiny)
    sched = Scheduler(eng, chunk=8)
    sched.warmup()
    # median UNTRACED decode-step wall over a live request
    req = sched.submit([1, 9, 23], 200, _greedy(spec))
    times = []
    sched.step()  # prefill + first token
    for _ in range(30):
        t0 = time.perf_counter()
        sched.step()
        times.append(time.perf_counter() - t0)
    req.cancel()
    sched.step()
    sched.close()
    step_ms = sorted(times)[len(times) // 2] * 1e3

    # per-iteration tracer cost, tightly measured (enabled path)
    TRACER.configure(capacity=8192, decode_every=1)
    n = 2000
    b = eng.batch
    t0 = time.perf_counter()
    for i in range(n):
        for row in range(b):  # worst case: every row emits an event
            TRACER.event("decode", row + 1, n_out=i)
        TRACER.step(decode_rows=b, prefill_rows=0, chunk=0,
                    queue_depth=0, wall_ms=1.0)
    per_step_ms = (time.perf_counter() - t0) / n * 1e3
    overhead = per_step_ms / step_ms
    assert overhead <= 0.02, (
        f"tracing costs {per_step_ms * 1e3:.1f} us/step = "
        f"{overhead * 100:.2f}% of a {step_ms:.2f} ms decode step")


# -- Prometheus exposition --------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? ([0-9eE.+-]+|NaN)$')
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"$')


def _parse_prometheus(text: str) -> dict:
    """Minimal exposition-format validator: returns {metric: [(labels,
    value)]}; raises AssertionError on format violations scrapers
    reject (sample before HELP/TYPE, duplicate headers, bad labels)."""
    metrics: dict = {}
    seen_meta: dict = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            _, what, name, rest = line.split(" ", 3)
            key = (what, name)
            assert key not in seen_meta, f"duplicate {key}"
            seen_meta[key] = rest
            if what == "TYPE":
                assert rest in ("counter", "gauge", "histogram", "summary")
            continue
        assert not line.startswith("#"), f"stray comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, _, labels, value = m.groups()
        base = name
        assert ("TYPE", base) in seen_meta, f"sample before TYPE: {name}"
        for lab in filter(None, (labels or "").split(",")):
            assert _LABEL_RE.match(lab), f"bad label: {lab!r} in {line!r}"
        metrics.setdefault(name, []).append((labels, float(value)))
    return metrics


def test_render_prometheus_supervisor_shape_valid():
    TRACER.configure(capacity=64)
    TRACER.step(decode_rows=2, prefill_rows=1, chunk=8, queue_depth=0,
                wall_ms=3.0)
    summary = {"requests_submitted": 5, "requests_finished": 4,
               "tokens_out": 40, "steps": 33, "state": "ready",
               "ttft_p50_ms": 12.0, "itl_p99_ms": 4.5,
               "mean_slot_occupancy": 1.5, "max_queue_depth": 2,
               "prefix_cache": {"lookups": 4, "hits": 2,
                                "blocks_in_use": 7},
               "resilience": {"crashes": 1, "recoveries": 1,
                              "recovery_p50_ms": 88.0}}
    m = _parse_prometheus(render_prometheus(summary, tracer=TRACER,
                                            model="tiny"))
    assert m["dllama_requests_submitted_total"] == [(None, 5.0)]
    assert m["dllama_prefix_cache_hits_total"] == [(None, 2.0)]
    assert m["dllama_supervisor_crashes_total"] == [(None, 1.0)]
    assert ('state="ready"', 1.0) in m["dllama_state"]
    assert ('state="broken"', 0.0) in m["dllama_state"]
    step = dict(m["dllama_step_ms"])
    assert step[
        'decode_rows="2",prefill_rows="1",chunk="8",quantile="0.5"'] == 3.0


def test_render_prometheus_router_shape_valid():
    summary = {
        "requests_submitted": 9, "state": "ready",
        "router": {"routed": 9, "retries": 1, "failovers_ok": 1,
                   "breaker_trips": 2},
        "replicas": [
            {"replica": 0, "state": "ready", "draining": False,
             "breaker_open": False, "requests_finished": 5,
             "proc": {"exits": 1, "respawns": 1, "spawn_failures": 0,
                      "exit_classes": {"signal:SIGKILL": 1},
                      "respawn_p50_ms": 4300.0}},
            {"replica": 1, "state": "recovering", "draining": True,
             "breaker_open": False, "requests_finished": 4},
        ],
        "cluster": {"pings_sent": 7, "pongs_received": 7,
                    "peers_lost": [{"node_id": 1}]},
    }
    m = _parse_prometheus(render_prometheus(summary, model="tiny",
                                            mode="router"))
    assert dict(m["dllama_replica_up"]) == {'replica="0"': 1.0,
                                            'replica="1"': 0.0}
    assert dict(m["dllama_replica_requests_finished_total"]) == {
        'replica="0"': 5.0, 'replica="1"': 4.0}
    assert m["dllama_router_retries_total"] == [(None, 1.0)]
    assert dict(m["dllama_replica_proc_exit_class_total"]) == {
        'replica="0",class="signal:SIGKILL"': 1.0}
    assert m["dllama_cluster_peers_lost_total"] == [(None, 1.0)]


def test_render_prometheus_cluster_wire_families():
    """dlwire (ISSUE 12): the FULL ClusterStats counter set renders as
    tier-invariant dllama_cluster_* families (the old renderer exported
    only 3 of them), the measured wire ledger as
    dllama_wire_{bytes,frames}_total{peer,kind,dir} +
    dllama_heartbeat_rtt_ms{peer} + the clock offset, and the startup
    broadcast timings."""
    summary = {
        "requests_submitted": 1, "state": "ready",
        "cluster": {
            "nnodes": 2, "phase": "decode", "connect_retries": 3,
            "pings_sent": 7, "pongs_received": 6, "pongs_sent": 0,
            "frames_sent": 9, "frames_received": 15,
            "bcast_spec_ms": 12.5, "bcast_tensors_ms": 830.0,
            "bcast_tensors_bytes": 1 << 20,
            "peers_lost": [],
            "wire": {"peers": {"1": {
                "tx": {"PING": {"frames": 7, "bytes": 168},
                       "RUN": {"frames": 2, "bytes": 250}},
                "rx": {"PONG": {"frames": 6, "bytes": 192}},
                "rtt_ms": {"n": 6, "p50_ms": 0.9, "p99_ms": 1.8,
                           "mean_ms": 1.1, "recent": [0.9]},
                "clock_offset_ms": 0.07, "best_rtt_ms": 0.7}}},
        },
    }
    m = _parse_prometheus(render_prometheus(summary, model="tiny"))
    # the tier-invariant cluster counter set (satellite: a tier must not
    # lose a family to a launch flag — these were /stats-only before)
    assert m["dllama_cluster_pings_sent_total"] == [(None, 7.0)]
    assert m["dllama_cluster_pongs_received_total"] == [(None, 6.0)]
    assert m["dllama_cluster_pongs_sent_total"] == [(None, 0.0)]
    assert m["dllama_cluster_frames_sent_total"] == [(None, 9.0)]
    assert m["dllama_cluster_frames_received_total"] == [(None, 15.0)]
    assert m["dllama_cluster_connect_retries_total"] == [(None, 3.0)]
    assert m["dllama_cluster_peers_lost_total"] == [(None, 0.0)]
    assert m["dllama_cluster_nnodes"] == [(None, 2.0)]
    assert m["dllama_cluster_phase"] == [('phase="decode"', 1.0)]
    assert dict(m["dllama_cluster_bcast_ms"]) == {'what="spec"': 12.5,
                                                  'what="tensors"': 830.0}
    assert m["dllama_cluster_bcast_bytes_total"] == [('what="tensors"',
                                                      float(1 << 20))]
    # the wire ledger families
    wire = dict(m["dllama_wire_bytes_total"])
    assert wire['peer="1",kind="PING",dir="tx"'] == 168.0
    assert wire['peer="1",kind="RUN",dir="tx"'] == 250.0
    assert wire['peer="1",kind="PONG",dir="rx"'] == 192.0
    frames = dict(m["dllama_wire_frames_total"])
    assert frames['peer="1",kind="PING",dir="tx"'] == 7.0
    rtt = dict(m["dllama_heartbeat_rtt_ms"])
    assert rtt['peer="1",quantile="0.5"'] == 0.9
    assert rtt['peer="1",quantile="0.99"'] == 1.8
    assert m["dllama_cluster_clock_offset_ms"] == [('peer="1"', 0.07)]


def test_ingest_rebases_cluster_node_spans_onto_one_timeline():
    """A multihost worker's MSG_TRACE span (wall-stamped, shifted by the
    clock-offset estimate at the link layer) merges under the SAME trace
    id as the root's events — by_id serves the linked span the way
    /admin/trace?id= would."""
    TRACER.configure(capacity=256)
    tid = TRACER.new_id()
    TRACER.event("cluster_tick", tid, phase="run", role="root", rank=0)
    # a worker span as multihost._ingest_trace hands it over: ts_wall
    # stamps in the (already offset-corrected) local wall domain
    now_wall = TRACER.to_wall(__import__("time").perf_counter())
    TRACER.ingest([
        {"ts_wall": now_wall + 0.001, "kind": "cluster_tick", "tid": tid,
         "phase": "run", "role": "worker", "rank": 1},
        {"ts_wall": now_wall + 0.050, "kind": "cluster_tick", "tid": tid,
         "phase": "run_done", "role": "worker", "rank": 1, "ms": 49.0},
    ], origin="node1")
    TRACER.event("cluster_lost", tid, node=1, reason="eof", phase="run")
    span = TRACER.by_id(tid)
    assert [e["kind"] for e in span] == ["cluster_tick", "cluster_tick",
                                        "cluster_tick", "cluster_lost"]
    origins = [e.get("origin") for e in span]
    assert origins == [None, "node1", "node1", None]
    # the ingested pair rebased into the LOCAL monotonic domain with
    # their relative spacing preserved (49 ms apart, near "now")
    w0, w1 = span[1]["ts"], span[2]["ts"]
    assert abs((w1 - w0) - 0.049) < 1e-6, (w0, w1)
    local_now = span[3]["ts"]
    assert abs(w0 - local_now) < 1.0, (w0, local_now)


def test_render_prometheus_handles_none_and_idle():
    # legacy / unbuilt tiers: still a valid, scrapeable document
    for mode, st in (("legacy", "off"), ("scheduler", "idle")):
        m = _parse_prometheus(render_prometheus(None, model="x",
                                                mode=mode, state=st))
        assert m["dllama_up"] == [(f'model="x",mode="{mode}"', 1.0)]
        assert (f'state="{st}"', 1.0) in m["dllama_state"]


# -- the HTTP plane: /metrics + /admin/trace across tiers -------------------


def _serve(state):
    from http.server import ThreadingHTTPServer

    from distributed_llama_tpu.apps.api_server import make_handler

    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server


def _get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=120)
    conn.request("GET", path)
    r = conn.getresponse()
    return r.status, r.getheader("Content-Type") or "", r.read().decode()


@pytest.fixture
def api_state(tiny, tmp_path):
    """ApiState over the synthetic tiny engine (no model file — the
    /metrics plane needs an engine + tokenizer-ish surface only)."""
    from distributed_llama_tpu.apps.api_server import ApiState
    from distributed_llama_tpu.testing import write_fixture
    from distributed_llama_tpu.tokenizer import Tokenizer

    _, tpath = write_fixture(tmp_path, seed=5)
    tokenizer = Tokenizer.from_file(tpath)

    def make(**kw):
        spec, params = tiny
        eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                     cache_dtype=jnp.float32)
        sampler = Sampler(spec.vocab_size, 0.0, 0.9, 3)
        return ApiState(eng, tokenizer, sampler, model_name="tiny", **kw)

    return make


def test_metrics_and_trace_endpoints_all_tiers(api_state, tiny):
    """/metrics answers VALID Prometheus text in the legacy tier, the
    single-supervisor tier, and the thread-router tier (the process
    tier's renderer path is pinned by test_render_prometheus_router_
    shape_valid + the chaos-job test in tests/test_replica_procs.py);
    /admin/trace serves the ring as JSONL behind the admin guard."""
    spec, _ = tiny
    TRACER.configure(capacity=1024)

    # -- legacy tier (no scheduler): process-level series only
    state = api_state()
    srv = _serve(state)
    try:
        code, ctype, body = _get(srv.server_address, "/metrics")
        assert code == 200 and ctype.startswith("text/plain")
        m = _parse_prometheus(body)
        assert ('model="tiny",mode="legacy"', 1.0) in m["dllama_up"]
    finally:
        srv.shutdown()

    # -- supervisor tier: drive one real request, then scrape
    state = api_state(serve_batch=2, serve_chunk=16)
    srv = _serve(state)
    try:
        # idle (front door unbuilt): still valid, mode=scheduler
        m = _parse_prometheus(_get(srv.server_address, "/metrics")[2])
        assert ('state="idle"', 1.0) in m["dllama_state"]

        conn = http.client.HTTPConnection(*srv.server_address, timeout=240)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": "ab", "max_tokens": 4,
                                 "temperature": 0}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        resp.read()
        conn.close()
        code, _, body = _get(srv.server_address, "/metrics")
        m = _parse_prometheus(body)
        assert m["dllama_requests_submitted_total"][0][1] >= 1.0
        assert m["dllama_tokens_out_total"][0][1] >= 1.0
        assert "dllama_step_ms" in m  # the tracer families rode along
        assert ('state="ready"', 1.0) in m["dllama_state"]

        # /admin/trace: loopback passes the guard; JSONL parses; the
        # span view filters by id
        code, ctype, body = _get(srv.server_address, "/admin/trace?n=50")
        assert code == 200 and ctype == "application/x-ndjson"
        lines = [json.loads(ln) for ln in body.splitlines()]
        assert "anchor_wall" in lines[0]
        kinds = {e["kind"] for e in lines[1:]}
        assert {"enqueue", "first_token", "finish", "step"} <= kinds
        tid = next(e["tid"] for e in lines[1:] if e["kind"] == "finish")
        code, _, body = _get(srv.server_address, f"/admin/trace?id={tid}")
        span = [json.loads(ln) for ln in body.splitlines()][1:]
        assert span and all(e["tid"] == tid for e in span)
        assert all("ts_wall" in e for e in span)

        code, _, _ = _get(srv.server_address, "/admin/trace?id=zzz")
        assert code == 400
        # negative n would slice the wrong end of the ring (evs[-n:]
        # == evs[n:] — a near-full dump); it must be a 400 instead
        code, _, _ = _get(srv.server_address, "/admin/trace?n=-5")
        assert code == 400
    finally:
        srv.shutdown()
        if state._scheduler is not None:
            state._scheduler.close()

    # -- thread-router tier: per-replica series
    state = api_state(serve_batch=2, serve_chunk=16, replicas=2)
    srv = _serve(state)
    try:
        # idle scrape BEFORE any traffic: mode comes from the config,
        # not the lazily-built front door — the label must not flip
        # from "scheduler" to "router" after the first request
        m = _parse_prometheus(_get(srv.server_address, "/metrics")[2])
        assert ('model="tiny",mode="router"', 1.0) in m["dllama_up"]
        assert ('state="idle"', 1.0) in m["dllama_state"]
        conn = http.client.HTTPConnection(*srv.server_address, timeout=240)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": "ab", "max_tokens": 3,
                                 "temperature": 0}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        resp.read()
        conn.close()
        code, _, body = _get(srv.server_address, "/metrics")
        m = _parse_prometheus(body)
        assert dict(m["dllama_replica_up"]) == {'replica="0"': 1.0,
                                                'replica="1"': 1.0}
        assert m["dllama_router_routed_total"][0][1] >= 1.0
        assert ('model="tiny",mode="router"', 1.0) in m["dllama_up"]
    finally:
        srv.shutdown()
        if state._scheduler is not None:
            state._scheduler.close()


def test_stats_and_metrics_carry_wire_plane_with_live_link(api_state):
    """With a cluster link installed, /stats hoists the measured wire
    ledger as its own `wire` block and /metrics renders the
    dllama_cluster_* + dllama_wire_* families — in the LEGACY tier too
    (tier-invariance satellite: the cluster plane must not vanish off a
    launch flag)."""
    from distributed_llama_tpu.parallel import multihost as mh

    link = mh.WorkerLink("127.0.0.1", 1, 1, 2)
    link._init_stats(connect_retries=2)
    link.stats.pongs_sent = 5
    link.stats.wire.account(0, "PING", "rx", 160, frames=5)
    link.stats.wire.account(0, "PONG", "tx", 160, frames=5)
    old = mh.get_link()
    mh.set_link(link)
    state = api_state()  # legacy tier: serve_batch off
    srv = _serve(state)
    try:
        code, _, body = _get(srv.server_address, "/stats")
        assert code == 200
        payload = json.loads(body)
        assert payload["cluster"]["pongs_sent"] == 5
        wire = payload["wire"]  # the hoisted block
        assert wire["peers"]["0"]["tx"]["PONG"]["bytes"] == 160
        assert wire["rx_bytes"] == 160

        m = _parse_prometheus(_get(srv.server_address, "/metrics")[2])
        assert m["dllama_cluster_pongs_sent_total"] == [(None, 5.0)]
        assert m["dllama_cluster_connect_retries_total"] == [(None, 2.0)]
        assert dict(m["dllama_wire_bytes_total"])[
            'peer="0",kind="PONG",dir="tx"'] == 160.0
    finally:
        srv.shutdown()
        mh.set_link(old)


def test_admin_trace_404_when_tracing_off(api_state):
    assert not TRACER.enabled
    state = api_state(serve_batch=2)
    srv = _serve(state)
    try:
        code, _, body = _get(srv.server_address, "/admin/trace")
        assert code == 404 and "--trace" in body
        # /metrics still answers without the tracer families
        code, _, body = _get(srv.server_address, "/metrics")
        assert code == 200
        assert "dllama_step_ms" not in body
    finally:
        srv.shutdown()
        if state._scheduler is not None:
            state._scheduler.close()


def test_admin_trace_kind_and_since_filters(api_state, tiny):
    """ISSUE 10 satellite: GET /admin/trace grows kind= and since_ms=
    filters alongside n=/id= — validated (400 on garbage), and the kind
    filter scans the WHOLE ring before tailing n (a sparse kind must not
    vanish behind n pre-filter events)."""
    TRACER.configure(capacity=2048)
    state = api_state(serve_batch=2, serve_chunk=16)
    srv = _serve(state)
    try:
        conn = http.client.HTTPConnection(*srv.server_address, timeout=240)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": "ab", "max_tokens": 4,
                                 "temperature": 0}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        resp.read()
        conn.close()

        # kind=: only that kind comes back — here 'finish', which sits
        # behind many decode/step events (n=3 unfiltered would miss it)
        code, _, body = _get(srv.server_address,
                             "/admin/trace?kind=finish&n=3")
        assert code == 200
        evs = [json.loads(ln) for ln in body.splitlines()][1:]
        assert evs and all(e["kind"] == "finish" for e in evs)

        # since_ms=: a large window keeps everything, a zero window
        # keeps (effectively) nothing
        code, _, body = _get(srv.server_address,
                             "/admin/trace?since_ms=600000")
        assert code == 200
        recent = [json.loads(ln) for ln in body.splitlines()][1:]
        assert recent
        code, _, body = _get(srv.server_address, "/admin/trace?since_ms=0")
        assert code == 200
        assert len([json.loads(ln) for ln in body.splitlines()][1:]) <= 1

        # filters compose with id=
        tid = next(e["tid"] for e in recent if e["kind"] == "finish")
        code, _, body = _get(srv.server_address,
                             f"/admin/trace?id={tid}&kind=prefill")
        span = [json.loads(ln) for ln in body.splitlines()][1:]
        assert span and all(e["kind"] == "prefill" and e["tid"] == tid
                            for e in span)

        # validation: garbage is a 400, never an empty-but-200 dump
        for q in ("kind=notakind", "kind=", "since_ms=abc",
                  "since_ms=-1", "since_ms=nan"):
            code, _, _ = _get(srv.server_address, f"/admin/trace?{q}")
            assert code == 400, q
    finally:
        srv.shutdown()
        if state._scheduler is not None:
            state._scheduler.close()
