"""Scheduler spans and window counters (ISSUE 25): one span primitive on
the tracer feeding two sinks — `jax.profiler.TraceAnnotation`s while a
device capture runs (server started WITHOUT --trace), `phases` on the
ring's `step` records with --trace — and the monotonic window counters in
/stats that the benchmark's per-layer metrics difference:

  * counter identities over a tiny served run, through HTTP;
  * a capture around scheduler steps holds the `sched.*` names nested in
    `sched.step` and no Python-frame event;
  * neither sink on: the step path builds no Span and no annotation;
  * the `step` record's n / ts0 / phases and the `step` field of the
    request events it caused;
  * `sched.idle_wait` is the one wait of both step loops;
  * the HBM ledger walks an engine's trees once, not on every read;
  * the counters survive a supervisor recovery and render on /metrics.
"""

import glob
import http.client
import json
import threading
import time

import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime import profiler as profiler_mod
from distributed_llama_tpu.runtime import trace as trace_mod
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.profiler import (COMPILES, PROFILER,
                                                    hbm_ledger)
from distributed_llama_tpu.runtime.scheduler import RequestError, Scheduler
from distributed_llama_tpu.runtime.stats import WINDOW_COUNTERS
from distributed_llama_tpu.runtime.trace import (SPAN_NAMES, TRACER,
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
def clean():
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


def _drain(sched, reqs):
    while not all(r.finished.is_set() for r in reqs):
        sched.step()


def _host_events(directory):
    """[(line, name, start_ns, end_ns)] of the capture's host planes. A
    line is one thread; every Python thread's line is NAMED "python", so
    a line is told from the others by its place in its plane."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    assert files, f"no .xplane.pb under {directory}"
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            for e in ln.events:
                out.append((f"{plane.name}#{i}", e.name, e.start_ns,
                            e.start_ns + e.duration_ns))
    return out


class _HeldClock:
    """`time` as runtime/profiler.py sees it during a test's capture:
    the window's sleep lasts until the test's work is done, however slow
    the machine, so no span of the work straddles the stop."""

    def __init__(self, done):
        self._done = done

    def sleep(self, seconds):
        self._done.wait(timeout=30.0)

    def __getattr__(self, name):
        return getattr(time, name)


def _capture_while(tmp_path, ms, work):
    """Run `work()` on this thread while a capture runs on another
    (capture sleeps out its window on the caller's thread, as the HTTP
    handler's does). Returns (reply, host events)."""
    d = str(tmp_path / "cap")
    box = {}
    done = threading.Event()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(profiler_mod, "time", _HeldClock(done))
        t = threading.Thread(
            target=lambda: box.update(PROFILER.capture(d, ms)), daemon=True)
        t.start()
        end = time.perf_counter() + 30.0
        while not TRACER.capturing and time.perf_counter() < end:
            time.sleep(0.001)
        assert TRACER.capturing
        try:
            work()
        finally:
            done.set()
        t.join(timeout=60.0)
    assert not t.is_alive() and box.get("dir") == d
    return box, _host_events(d)


# -- served run: the window counters ------------------------------------------


@pytest.fixture
def served(tmp_path, rng):
    """The scheduler path of `dllama api` over HTTP, prefix cache on,
    started WITHOUT --trace."""
    from http.server import ThreadingHTTPServer

    from distributed_llama_tpu.apps import dllama
    from distributed_llama_tpu.apps.api_server import ApiState, make_handler
    from distributed_llama_tpu.testing import write_fixture

    mpath, tpath = write_fixture(tmp_path, rng=rng, seq_len=192)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3",
        "--compute-dtype", "f32", "--cache-dtype", "f32"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny",
                     serve_batch=2, serve_chunk=16, prefix_cache=True,
                     prefix_blocks=16, prefix_block_len=4)
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield server.server_address, state
    server.shutdown()
    if state._scheduler is not None:
        state._scheduler.close()


def _http(addr, method, path, body=None):
    conn = http.client.HTTPConnection(*addr, timeout=240)
    conn.request(method, path, json.dumps(body) if body else None,
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, (resp.status, resp.read())
    return json.loads(resp.read())


def test_window_counter_identities_over_a_served_run(served):
    """Every counter is incremented where the work happens, so over a
    run: admitted = requests; prefill_tokens = prompt tokens less the
    seeded ones (and equals the prefix cache's own count); every output
    token is a first token or a decode row; busy >= host >= 0; and no
    counter ever falls between two /stats reads."""
    addr, state = served
    assert not TRACER.enabled          # the cells' servers pass no --trace
    long_prompt = "the quick brown fox jumps over the lazy dog " * 2
    snaps = []
    prompt_tokens = completion_tokens = 0
    for prompt, n in ((long_prompt, 5), (long_prompt, 4), ("ab", 6)):
        out = _http(addr, "POST", "/v1/completions",
                    {"prompt": prompt, "max_tokens": n, "temperature": 0})
        prompt_tokens += out["usage"]["prompt_tokens"]
        completion_tokens += out["usage"]["completion_tokens"]
        snaps.append(_http(addr, "GET", "/stats"))
    s = snaps[-1]
    assert s["admitted"] == 3 == s["requests_submitted"]
    assert s["queue_wait_ms_sum"] >= 0.0
    seeded = s["prefix_cache"]["tokens_saved"]
    assert seeded > 0                  # the second long prompt was seeded
    assert s["prefill_tokens"] == prompt_tokens - seeded
    assert s["prefill_tokens"] == (
        state._scheduler._sched.prefix_cache.stats.tokens_prefilled)
    assert s["prefill_steps"] >= 3
    assert s["prefill_steps"] + s["decode_steps"] >= s["steps"]
    # each request's first token comes out of its finishing prefill
    # chunk; every other token is one row of one decode step
    assert s["decode_rows"] + s["admitted"] == s["tokens_out"]
    assert s["tokens_out"] >= completion_tokens
    assert s["busy_ms"] >= s["host_ms"] >= 0.0
    assert s["wait_ms"] >= 0.0
    assert s["busy_ms"] == pytest.approx(s["host_ms"] + s["wait_ms"],
                                         abs=0.05)
    fd = s["frontdoor"]
    assert fd["requests"] == 3
    assert set(fd) == {"requests", "pre_submit_ms_sum"}
    assert fd["pre_submit_ms_sum"] > 0.0
    for a, b in zip(snaps, snaps[1:]):
        for k in WINDOW_COUNTERS + ("steps", "tokens_out"):
            assert b[k] >= a[k], (k, a[k], b[k])
        for k in fd:
            assert b["frontdoor"][k] >= a["frontdoor"][k], k
    # an observer must not stall what it observes: nothing was recorded
    # and no span was left open by the reads
    assert TRACER.recent(0) == [] and not TRACER.spans


def test_capture_on_a_server_without_trace_holds_the_front_door_span(
        served, tmp_path):
    """POST-time work of the front door is `api.pre_submit`, on the
    handler's thread; the scheduler's spans come from the supervised
    loop's thread, in the same capture, with --trace off."""
    addr, _ = served
    _http(addr, "POST", "/v1/completions",          # builds the scheduler
          {"prompt": "ab", "max_tokens": 2, "temperature": 0})

    def work():
        _http(addr, "POST", "/v1/completions",
              {"prompt": "the quick brown fox", "max_tokens": 4,
               "temperature": 0})
        time.sleep(0.15)     # the loop's next idle wait opens AND closes

    _, events = _capture_while(tmp_path, 300, work)
    by_name = {}
    for ln, name, s, e in events:
        by_name.setdefault(name, []).append((ln, s, e))
    assert len(by_name["api.pre_submit"]) == 1
    assert {"sched.step", "sched.wait", "sched.idle_wait"} <= set(by_name)
    assert not TRACER.enabled and TRACER.recent(0) == []


# -- sink 1: a device capture --------------------------------------------------


def test_capture_holds_sched_spans_nested_in_step_and_no_python_frames(
        tiny, tmp_path):
    spec, _ = tiny
    sched = Scheduler(_engine(tiny), chunk=8)
    sched.warmup()

    def work():
        reqs = [sched.submit(list(range(1, n + 1)), 5, _greedy(spec))
                for n in (11, 3)]
        _drain(sched, reqs)
        sched.idle_wait(timeout=0.002)

    reply, events = _capture_while(tmp_path, 400, work)
    sched.close()
    assert reply["t_stop_mono"] > reply["t_start_mono"]
    assert not TRACER.enabled and TRACER.recent(0) == []   # no --trace
    names = {name for _, name, _, _ in events}
    for want in ("sched.step", "sched.admit", "sched.dispatch.prefill",
                 "sched.dispatch.decode", "sched.wait", "sched.sample_emit",
                 "sched.idle_wait"):
        assert want in names, (want, sorted(names))
    assert {n for n in names if n.startswith(("sched.", "api."))} <= set(
        SPAN_NAMES)
    # the Python tracer is off: its events are named "$file:line func"
    assert not [n for n in names if n.startswith("$")]
    steps = [(ln, s, e) for ln, n, s, e in events if n == "sched.step"]
    assert len(steps) >= 5
    for ln, name, s, e in events:
        if name.startswith("sched.") and name not in ("sched.step",
                                                      "sched.idle_wait"):
            assert any(l2 == ln and s2 <= s and e <= e2
                       for l2, s2, e2 in steps), (name, s, e)
    # the idle wait is no part of a working iteration
    for ln, name, s, e in events:
        if name == "sched.idle_wait":
            assert not any(l2 == ln and s2 < e and s < e2
                           for l2, s2, e2 in steps)


def test_capture_names_the_supervised_loops_idle_wait(tiny, tmp_path):
    """Both step loops wait in Scheduler.idle_wait: an idle supervised
    server under capture shows `sched.idle_wait` and no `sched.step`."""
    from distributed_llama_tpu.runtime.resilience import EngineSupervisor

    sup = EngineSupervisor(lambda: _engine(tiny), chunk=8,
                           stall_timeout=60.0)
    try:
        _, events = _capture_while(tmp_path, 200, lambda: time.sleep(0.4))
    finally:
        sup.close()
    names = [name for _, name, _, _ in events]
    assert names.count("sched.idle_wait") >= 2
    assert "sched.step" not in names
    assert not [n for n in names if n.startswith("$")]


# -- both sinks off -------------------------------------------------------------


def test_no_capture_and_no_trace_builds_no_span_or_annotation(
        tiny, monkeypatch):
    import jax.profiler

    def boom(*a, **k):
        raise AssertionError("a span was built with both sinks off")

    monkeypatch.setattr(trace_mod.Span, "__init__", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    spec, _ = tiny
    assert not TRACER.enabled and not TRACER.capturing and not TRACER.spans
    sched = Scheduler(_engine(tiny), chunk=8)
    sched.warmup()
    reqs = [sched.submit(list(range(1, n + 1)), 4, _greedy(spec))
            for n in (9, 2)]
    _drain(sched, reqs)
    sched.idle_wait(timeout=0.001)
    st = sched.stats
    sched.close()
    assert sched._span is None and TRACER.recent(0) == []
    # the counters are on regardless
    assert st.admitted == 2 and st.prefill_tokens == 11
    assert st.decode_rows + 2 == st.tokens_out == 8
    assert st.busy_ms >= st.host_ms > 0.0


# -- sink 2: the ring, with --trace -----------------------------------------------


def test_step_record_carries_n_ts0_phases_and_events_name_their_step(tiny):
    spec, _ = tiny
    TRACER.configure(capacity=4096, decode_every=2)
    assert TRACER.spans
    sched = Scheduler(_engine(tiny), chunk=8)
    sched.warmup()
    req = sched.submit(list(range(1, 20)), 6, _greedy(spec))
    _drain(sched, [req])
    sched.close()
    evs = TRACER.recent(0)
    steps = [e for e in evs if e["kind"] == "step"]
    # a slot alone takes both rows of a chunk program: 2 segments, then 1
    assert [(e["pre"], e["seg"]) for e in steps[:3]] == [(1, 2), (1, 1),
                                                         (0, 0)]
    assert [e["n"] for e in steps] == list(range(1, len(steps) + 1))
    for e in steps:
        assert e["ts0"] <= e["ts"]
        assert (e["ts"] - e["ts0"]) * 1e3 >= e["ms"] - 0.01
        ph = e["phases"]
        assert set(ph) <= set(SPAN_NAMES) and "sched.admit" in ph
        assert sum(ph.values()) <= e["ms"] + 0.01   # self time >= 0
    # the first iteration prefills 16 of 19 tokens (no fetch), the second
    # finishes the prompt and samples, later ones decode
    assert "sched.dispatch.prefill" in steps[0]["phases"]
    assert "sched.wait" not in steps[0]["phases"]
    assert {"sched.dispatch.prefill", "sched.wait",
            "sched.sample_emit"} <= set(steps[1]["phases"])
    assert {"sched.dispatch.decode", "sched.wait",
            "sched.sample_emit"} <= set(steps[-1]["phases"])
    by_kind = {}
    for e in TRACER.by_id(req.trace_id):
        by_kind.setdefault(e["kind"], []).append(e)
    assert [e["step"] for e in by_kind["prefill"]] == [1, 2]
    assert by_kind["first_token"][0]["step"] == 2
    known = {e["n"] for e in steps}
    assert by_kind["decode"] and all(e["step"] in known and e["step"] > 2
                                     for e in by_kind["decode"])


def test_spans_on_and_off_make_the_same_calls_on_the_logits(tiny):
    """The captured path is the served path: with spans on the scheduler
    fetches the logits exactly as with spans off — one copy to the host a
    decode step, on the REAL engine's path (Engine.sample_view), and no
    wait of its own before it that untraced serving does not make."""
    import numpy as np

    spec, _ = tiny
    calls = []

    class Probe:
        def __init__(self, arr):
            self.arr, self.shape = arr, arr.shape

        def block_until_ready(self):
            calls.append("ready")

        def __array__(self, dtype=None, copy=None):
            calls.append("copy")
            return np.asarray(self.arr)

    eng = _engine(tiny)
    real = eng.slot_decode_step
    eng.slot_decode_step = lambda tok, pos, **kw: Probe(real(tok, pos, **kw))
    sched = Scheduler(eng, chunk=8)
    sched.warmup()
    calls.clear()
    _drain(sched, [sched.submit([1, 2, 3], 3, _greedy(spec))])
    assert calls == ["copy", "copy"]            # two decode steps, spans off
    calls.clear()
    TRACER.configure(capacity=256)
    _drain(sched, [sched.submit([4, 5, 6], 3, _greedy(spec))])
    sched.close()
    assert calls == ["copy", "copy"]            # and the same with them on
    waits = [e["phases"]["sched.wait"] for e in TRACER.recent(0)
             if e["kind"] == "step" and "sched.wait" in e["phases"]]
    assert len(waits) == 3      # the finishing prefill and two decodes


def test_span_primitive_parent_phases_and_end():
    """Tracer.span/phase/end: children follow each other under a parent,
    a closed child's ms are summed by name, end() closes an open child."""
    TRACER.configure(capacity=64)
    root = TRACER.span("sched.step")
    TRACER.phase(root, "sched.admit")
    first = root._child
    assert first.parent is root and first.name == "sched.admit"
    time.sleep(0.002)
    TRACER.phase(root, "sched.wait")
    assert root._child is not first and root.phases["sched.admit"] >= 1.0
    TRACER.phase(root, "sched.admit")       # the same name again: summed
    before = root.phases["sched.admit"]
    time.sleep(0.001)
    ms = TRACER.end(root)                   # closes the open child too
    assert root._child is None and root.phases["sched.admit"] > before
    assert ms >= sum(root.phases.values()) - 0.01
    assert root.parent is None and root.t0 > 0


# -- satellites ---------------------------------------------------------------------


def test_hbm_ledger_walks_an_engine_once(tiny, monkeypatch):
    """GET /stats and /metrics call hbm_ledger on every read; the walk
    over the weights and the slot cache runs once per engine object."""
    calls = []
    real = profiler_mod._tree_bytes

    def counting(tree):
        calls.append(1)
        return real(tree)

    monkeypatch.setattr(profiler_mod, "_tree_bytes", counting)
    eng = _engine(tiny)
    first = hbm_ledger(eng, device_stats=False)
    walked = len(calls)
    assert walked >= 2 and first["kv_slot_bytes"] > 0
    for _ in range(3):
        assert hbm_ledger(eng, device_stats=False) == first
    assert len(calls) == walked
    other = _engine(tiny, batch=1)          # another engine: walked anew
    assert hbm_ledger(other, device_stats=False)["kv_slot_bytes"] * 2 == (
        first["kv_slot_bytes"])
    assert len(calls) > walked


def test_window_counters_survive_a_recovery(tiny):
    """A rebuilt generation starts its ServeStats at zero; the /stats
    counters stay monotonic because the supervisor carries the dead
    generations' window counters like its other totals."""
    from distributed_llama_tpu.runtime.faults import FAULTS
    from distributed_llama_tpu.runtime.resilience import EngineSupervisor

    spec, _ = tiny
    sup = EngineSupervisor(lambda: _engine(tiny), chunk=8,
                           stall_timeout=60.0, backoff_base=0.01)
    try:
        req = sup.submit([1, 2, 3, 4, 5], 4, _greedy(spec))
        assert len(list(req.tokens(timeout=60.0))) == 4
        before = sup.summary()
        assert before["admitted"] == 1 and before["prefill_tokens"] == 5
        FAULTS.arm("step_raise", after=0, times=1)
        req = sup.submit([1, 2, 3], 4, _greedy(spec))
        with pytest.raises(RequestError):
            list(req.tokens(timeout=60.0))
        end = time.perf_counter() + 60.0
        while not sup.ready and time.perf_counter() < end:
            time.sleep(0.02)
        assert sup.ready
        mid = sup.summary()
        req = sup.submit([1, 9, 23, 54], 3, _greedy(spec))
        assert len(list(req.tokens(timeout=60.0))) == 3
        after = sup.summary()
        for a, b in ((before, mid), (mid, after)):
            for k in WINDOW_COUNTERS:
                assert b[k] >= a[k], (k, a[k], b[k])
        assert after["admitted"] == 2
        assert after["prefill_tokens"] == 9
        assert after["decode_rows"] == 3 + 2
    finally:
        FAULTS.clear()
        sup.close()


def test_window_counters_render_as_prometheus_counters():
    summary = {"state": "ready", "steps": 9, "admitted": 3,
               "queue_wait_ms_sum": 12.5, "prefill_steps": 4,
               "prefill_tokens": 57, "decode_steps": 7, "decode_rows": 11,
               "busy_ms": 410.25, "wait_ms": 380.0, "host_ms": 30.25}
    text = render_prometheus(summary, model="tiny")
    for name, val in (("dllama_admitted_total", "3"),
                      ("dllama_queue_wait_ms_total", "12.5"),
                      ("dllama_prefill_tokens_total", "57"),
                      ("dllama_decode_rows_total", "11"),
                      ("dllama_scheduler_host_ms_total", "30.25")):
        assert f"# TYPE {name} counter" in text
        assert f"\n{name} {val}\n" in text
    assert "device_ms" not in text and "step_sync" not in text
