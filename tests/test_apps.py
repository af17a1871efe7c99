"""CLI + API server tests over a tiny end-to-end fixture model.

Exercises the app layer the reference never tested (SURVEY.md §4 notes the
absence of API-server tests): dllama generate/inference modes and the
OpenAI-compatible /v1/chat/completions route incl. SSE streaming
(ref: src/apps/dllama/dllama.cpp, src/apps/dllama-api/dllama-api.cpp).
"""

import http.client
import json
import threading

import numpy as np
import pytest

from distributed_llama_tpu.apps import dllama
from distributed_llama_tpu.apps.api_server import ApiState, make_handler
from distributed_llama_tpu.io import (
    TokenizerData, model_tensor_plan, write_model, write_tokenizer_file,
)
from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.quants import FloatType


def _fixture(tmp_path, rng, wt=FloatType.Q40):
    from distributed_llama_tpu.testing import write_fixture

    return write_fixture(tmp_path, rng=rng, weights_float_type=wt,
                         seq_len=192)


def test_cli_mesh_flags_end_to_end(tmp_path, rng, capsys):
    """--tp/--pp/--dp compose through the CLI on the virtual 8-device mesh:
    a dp-batched generation over tp-split weights in pp stages must produce
    the same tokens as the single-device run (greedy, fixed seed)."""
    mpath, tpath = _fixture(tmp_path, rng)
    # f32 buffers on both runs: the pp run force-disables q80, so the
    # baseline must not use it either or the comparison is approximate
    base_args = ["generate", "--model", mpath, "--tokenizer", tpath,
                 "--prompt", "ab", "--steps", "3", "--seed", "7",
                 "--temperature", "0", "--buffer-float-type", "f32"]
    dllama.main(base_args)
    want = capsys.readouterr().out
    dllama.main(base_args + ["--tp", "2", "--pp", "2", "--dp", "2"])
    got = capsys.readouterr().out
    # same generated text; the batched run reports its sequence count
    assert want.splitlines()[-1] in got


def test_cli_inference_mode(tmp_path, rng, capsys):
    mpath, tpath = _fixture(tmp_path, rng)
    dllama.main([
        "inference", "--model", mpath, "--tokenizer", tpath,
        "--prompt", "ab", "--steps", "4", "--seed", "7", "--temperature", "0",
    ])
    out = capsys.readouterr().out
    assert "Generated tokens:    4" in out
    assert "Avg generation time:" in out
    assert "🔶 G" in out  # per-token benchmark lines (ref: dllama.cpp:74-79)


def test_cli_inference_tp_trace_t_column(tmp_path, rng, capsys):
    """Benchmark mode on a multi-device mesh captures a trace for the
    per-step T column; on CPU the trace has no device plane, so the
    microbench fallback must keep the output intact (the TPU path is the
    same code with real per-step values — netstats.per_step_op_ms)."""
    mpath, tpath = _fixture(tmp_path, rng)
    dllama.main([
        "inference", "--model", mpath, "--tokenizer", tpath, "--tp", "2",
        "--prompt", "ab", "--steps", "3", "--seed", "7", "--temperature", "0",
    ])
    out = capsys.readouterr().out
    assert "🔶 G" in out and " T " in out
    assert "Avg transfer" in out


def test_per_step_op_ms_empty_trace(tmp_path):
    from distributed_llama_tpu.runtime.netstats import per_step_op_ms

    assert per_step_op_ms(str(tmp_path)) == []


def test_cli_worker_mode_rejected(tmp_path, rng):
    with pytest.raises(SystemExit):
        dllama.main(["worker", "--port", "9998"])


@pytest.fixture
def api_server(tmp_path, rng):
    mpath, tpath = _fixture(tmp_path, rng)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny")
    from http.server import HTTPServer
    server = HTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield server.server_address
    server.shutdown()


def test_api_models_route(api_server):
    host, port = api_server
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/v1/models")
    resp = conn.getresponse()
    assert resp.status == 200
    body = json.loads(resp.read())
    assert body["data"][0]["id"] == "tiny"


def test_api_chat_completion(api_server):
    host, port = api_server
    conn = http.client.HTTPConnection(host, port, timeout=120)
    req = {"messages": [{"role": "user", "content": "ab"}],
           "max_tokens": 4, "temperature": 0}
    conn.request("POST", "/v1/chat/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    body = json.loads(resp.read())
    assert body["object"] == "chat.completion"
    choice = body["choices"][0]
    assert choice["message"]["role"] == "assistant"
    assert body["usage"]["completion_tokens"] <= 4
    assert body["usage"]["total_tokens"] == (
        body["usage"]["prompt_tokens"] + body["usage"]["completion_tokens"])


def test_api_chat_completion_streaming(api_server):
    host, port = api_server
    conn = http.client.HTTPConnection(host, port, timeout=120)
    req = {"messages": [{"role": "user", "content": "ab"}],
           "max_tokens": 3, "temperature": 0, "stream": True}
    conn.request("POST", "/v1/chat/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    raw = resp.read().decode()
    events = [line[len("data: "):] for line in raw.splitlines()
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events[:-1]]
    assert parsed[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    deltas = [p["choices"][0]["delta"].get("content", "") for p in parsed[:-1]]
    assert all(isinstance(d, str) for d in deltas)


def test_api_prefix_reuse_matches_stateless(tmp_path, rng):
    """Session/prefix reuse (VERDICT r2 #6): two chat requests sharing a
    system prompt — the second request must prefill only the suffix beyond
    the longest common token prefix, and its response must be byte-identical
    to a stateless (fresh-engine) handling of the same request."""
    from distributed_llama_tpu.apps.api_server import _completion_chunks

    mpath, tpath = _fixture(tmp_path, rng)

    def build_state():
        args = dllama.build_argparser().parse_args([
            "api", "--model", mpath, "--tokenizer", tpath,
            "--steps", "8", "--temperature", "0", "--seed", "3"])
        engine, tokenizer, sampler = dllama.build_engine(args)
        return ApiState(engine, tokenizer, sampler, model_name="tiny")

    def run(state, user):
        body = {"messages": [
            {"role": "system", "content": "abba"},
            {"role": "user", "content": user}],
            "max_tokens": 4, "temperature": 0}
        return list(_completion_chunks(state, body))

    # stateless oracle: fresh engine per request
    want_1 = run(build_state(), "ab")
    want_2 = run(build_state(), "ba")

    # shared-session path: one state across both requests; record how many
    # tokens each request actually prefilled
    state = build_state()
    prefills = []
    orig_prefill = state.engine.prefill

    def spy(suffix):
        prefills.append(len(suffix))
        return orig_prefill(suffix)

    state.engine.prefill = spy
    got_1 = run(state, "ab")
    full_len = prefills[0]
    got_2 = run(state, "ba")
    assert got_1 == want_1
    assert got_2 == want_2  # byte-identical responses
    # the shared system-prompt prefix was NOT re-prefilled
    assert len(prefills) == 2 and 0 < prefills[1] < full_len, prefills


def test_api_lookup_negative_temp_keeps_prefix_cache_aligned(tmp_path, rng):
    """ADVICE r4 (medium): with --lookup-decode on, a request carrying a
    NEGATIVE temperature falls through to the plain sampled loop; history
    bookkeeping must not double-append there, or cached_tokens drifts from
    the real K/V positions and every later prefix-reuse request decodes
    against wrong cache contents. Serve (negative-temp, then greedy) on one
    state and require the greedy follow-up byte-identical to stateless."""
    from distributed_llama_tpu.apps.api_server import _completion_chunks

    mpath, tpath = _fixture(tmp_path, rng)

    def build_state():
        args = dllama.build_argparser().parse_args([
            "api", "--model", mpath, "--tokenizer", tpath,
            "--steps", "8", "--temperature", "0", "--seed", "3",
            "--lookup-decode", "4"])
        engine, tokenizer, sampler = dllama.build_engine(args)
        return ApiState(engine, tokenizer, sampler, model_name="tiny",
                        lookup_decode=4)

    def run(state, user, temp):
        body = {"messages": [
            {"role": "system", "content": "abba"},
            {"role": "user", "content": user}],
            "max_tokens": 4, "temperature": temp}
        return list(_completion_chunks(state, body))

    want_2 = run(build_state(), "ba", 0)  # stateless oracle for request 2

    state = build_state()
    run(state, "ab", -1.0)  # negative temp: plain loop despite lookup on
    # the cache map must exactly mirror the engine's written K/V positions
    assert len(state.cached_tokens) == state.engine.pos
    got_2 = run(state, "ba", 0)
    assert got_2 == want_2


def test_api_session_survives_restart(tmp_path, rng):
    """API session persistence (VERDICT r3 weak #6): serve request A, save
    the session (the server's shutdown path), rebuild the server process
    state, load the session, then serve A + a follow-up — the follow-up
    must prefill ONLY the suffix beyond the restored prefix and its
    response must be byte-identical to the no-restart path."""
    from distributed_llama_tpu.apps.api_server import (
        _completion_chunks, build_chat_prompt, load_server_session,
        save_server_session)

    from distributed_llama_tpu.testing import write_fixture

    # the two-turn conversation runs ~272 prompt tokens — needs more
    # context than the shared 192-token fixture
    mpath, tpath = write_fixture(tmp_path, rng=rng, seq_len=384)
    spath = str(tmp_path / "api_session.npz")

    def build_state():
        args = dllama.build_argparser().parse_args([
            "api", "--model", mpath, "--tokenizer", tpath,
            "--steps", "8", "--temperature", "0", "--seed", "3"])
        engine, tokenizer, sampler = dllama.build_engine(args)
        return ApiState(engine, tokenizer, sampler, model_name="tiny")

    def body(messages):
        return {"messages": messages, "max_tokens": 4, "temperature": 0}

    msgs_a = [{"role": "system", "content": "abba"},
              {"role": "user", "content": "ab"}]
    # the follow-up extends the same conversation (assistant turn + new
    # user turn share the A prefix)
    msgs_b = msgs_a + [{"role": "assistant", "content": "x"},
                       {"role": "user", "content": "ba"}]

    # no-restart oracle: one state serves A then the follow-up
    ref = build_state()
    want_a = list(_completion_chunks(ref, body(msgs_a)))
    want_b = list(_completion_chunks(ref, body(msgs_b)))

    # restart path: serve A, save (shutdown), new process state, load
    s1 = build_state()
    got_a = list(_completion_chunks(s1, body(msgs_a)))
    assert got_a == want_a
    save_server_session(s1, spath)

    s2 = build_state()
    load_server_session(s2, spath)
    assert s2.engine.pos == s1.engine.pos
    prefills = []
    orig = s2.engine.prefill

    def spy(suffix):
        prefills.append(len(suffix))
        return orig(suffix)

    s2.engine.prefill = spy
    got_b = list(_completion_chunks(s2, body(msgs_b)))
    assert got_b == want_b  # byte-identical to the no-restart path
    # only the suffix beyond the restored prefix was prefilled
    n_full = len(s2.tokenizer.encode(build_chat_prompt(msgs_b)))
    assert len(prefills) == 1 and 0 < prefills[0] < n_full, (prefills, n_full)


def test_api_bad_json(api_server):
    host, port = api_server
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("POST", "/v1/chat/completions", "{not json",
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 400


def test_cli_profile_flag(tmp_path, rng, capsys):
    """--profile DIR writes a jax.profiler trace of the generation
    (net-new observability; the reference has no profiler hooks)."""
    import os

    mpath, tpath = _fixture(tmp_path, rng)
    pdir = str(tmp_path / "trace")
    dllama.main(["generate", "--model", mpath, "--tokenizer", tpath,
                 "--prompt", "ab", "--steps", "2", "--seed", "7",
                 "--temperature", "0", "--profile", pdir])
    out = capsys.readouterr().out
    assert "profiler trace written" in out
    found = [f for _, _, fs in os.walk(pdir) for f in fs]
    assert any(f.endswith((".pb", ".json.gz", ".xplane.pb")) for f in found), found


@pytest.fixture
def api_batch_server(tmp_path, rng):
    mpath, tpath = _fixture(tmp_path, rng)
    # f32: the batched step paths ("bpre"/"bvec") contain a bf16 dot
    # XLA's CPU thunks cannot execute (real target is TPU; the non-batch
    # API fixture keeps the bf16 default)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3",
        "--compute-dtype", "f32", "--cache-dtype", "f32"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny",
                     serve_batch=3)
    from http.server import HTTPServer
    server = HTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield server.server_address, state
    server.shutdown()
    if state._scheduler is not None:
        # a leaked supervisor keeps its loop thread stepping forever —
        # later fault-injection tests would race it for armed faults
        state._scheduler.close()


def test_api_batch_completions_greedy_matches_singles(api_batch_server,
                                                      tmp_path, rng):
    """POST /v1/batch/completions: each row's greedy completion must be
    byte-identical to a fresh single-request server answering that prompt
    alone (ragged lengths — right-padded batch prefill per-row parity)."""
    (host, port), state = api_batch_server
    msgs = [[{"role": "user", "content": c}] for c in ("ab", "abab x", "b")]

    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"messages_list": msgs, "max_tokens": 5, "temperature": 0}
    conn.request("POST", "/v1/batch/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    body = json.loads(resp.read())
    assert body["object"] == "chat.completion"
    assert [c["index"] for c in body["choices"]] == [0, 1, 2]

    from distributed_llama_tpu.apps.api_server import _completion_chunks
    for i, m in enumerate(msgs):
        st = ApiState(state.engine, state.tokenizer, state.sampler)
        st.engine.reset()
        st.cached_tokens = []
        single = "".join(
            p for kind, p in _completion_chunks(
                st, {"messages": m, "max_tokens": 5, "temperature": 0})
            if kind == "piece")
        assert body["choices"][i]["message"]["content"] == single, i
    state.engine.reset()
    state.cached_tokens = []


def test_api_batch_completions_streaming_and_validation(api_batch_server):
    """SSE chunks carry per-row indices; oversized batches 400 cleanly."""
    (host, port), state = api_batch_server
    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"messages_list": [[{"role": "user", "content": "ab"}]] * 2,
           "max_tokens": 3, "temperature": 0, "stream": True}
    conn.request("POST", "/v1/batch/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    raw = resp.read().decode()
    events = [line[len("data: "):] for line in raw.splitlines()
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events[:-1]]
    assert {p["choices"][0]["index"] for p in parsed} == {0, 1}
    finals = [p for p in parsed if p["choices"][0]["finish_reason"]]
    assert len(finals) == 2

    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"messages_list": [[{"role": "user", "content": "x"}]] * 4,
           "max_tokens": 2, "temperature": 0}
    conn.request("POST", "/v1/batch/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 400


def test_api_batch_speculative_matches_plain_batch(tmp_path, rng):
    """Batched speculation on the batch endpoint (round 5): with
    --lookup-decode on, a greedy batch request must return byte-identical
    choices to the plain batch path — sub-batch padding rows stay silent
    and per-row eos/stop handling is unchanged."""
    from distributed_llama_tpu.apps.api_server import (
        _batch_completion_chunks)

    mpath, tpath = _fixture(tmp_path, rng)

    def build_state(lookup):
        args = dllama.build_argparser().parse_args([
            "api", "--model", mpath, "--tokenizer", tpath,
            "--steps", "8", "--temperature", "0", "--seed", "3",
            "--compute-dtype", "f32", "--cache-dtype", "f32"])
        engine, tokenizer, sampler = dllama.build_engine(args)
        return ApiState(engine, tokenizer, sampler, model_name="tiny",
                        serve_batch=3, lookup_decode=lookup)

    # a 2-row request on a serve_batch=3 server: one padding row
    body = {"prompts": ["abab", "ba"], "max_tokens": 6, "temperature": 0}

    def collect(state):
        rows = {0: "", 1: ""}
        done = None
        for kind, payload in _batch_completion_chunks(state, dict(body)):
            if kind == "piece":
                i, piece = payload
                rows[i] += piece
            else:
                done = payload
        return rows, done

    # the lookup path bursts per row while the step loop interleaves, so
    # compare per-row text + the done envelope, not raw event order
    want_rows, want_done = collect(build_state(0))
    got_rows, got_done = collect(build_state(4))
    assert got_rows == want_rows
    assert got_done == want_done


def test_api_batch_max_tokens_zero_means_unlimited(api_batch_server):
    """ADVICE r4 (low): max_tokens: 0 on the batch endpoint must mean
    'generate to the context limit' like the single endpoint — not silently
    return one token per row."""
    (host, port), state = api_batch_server
    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"prompts": ["ab", "ba"], "max_tokens": 0, "temperature": 0}
    conn.request("POST", "/v1/batch/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    body = json.loads(resp.read())
    # every row must run past a single token (to eos or the context limit)
    for c in body["choices"]:
        assert c["finish_reason"] in ("stop", "length")
    assert body["usage"]["completion_tokens"] > 2
    state.engine.reset()
    state.cached_tokens = []


def test_api_batch_endpoint_off_by_default(api_server):
    host, port = api_server
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("POST", "/v1/batch/completions",
                 json.dumps({"prompts": ["x"]}),
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 404


def test_cli_dp_lookup_matches_plain(tmp_path, rng, capsys):
    """--dp + --lookup-decode (round 5, Engine.generate_batch_lookup):
    the replicated-prompt batch must stream row 0's EXACT greedy tokens,
    same as the plain --dp run and the single-sequence run."""
    from distributed_llama_tpu.testing import write_fixture

    mpath, tpath = write_fixture(tmp_path, seed=23)
    base = ["generate", "--model", mpath, "--tokenizer", tpath,
            "--prompt", "abab", "--steps", "6", "--seed", "7",
            "--temperature", "0", "--compute-dtype", "f32",
            "--cache-dtype", "f32"]

    def run(args):
        dllama.main(args)
        return [ln for ln in capsys.readouterr().out.splitlines()
                if ln.strip()][-1]

    single = run(list(base))
    plain = run(base + ["--dp", "2"])
    spec = run(base + ["--dp", "2", "--lookup-decode", "4"])
    assert plain == spec == single


@pytest.fixture
def sched_api_server(tmp_path, rng):
    """Threaded server with the continuous-batching scheduler on:
    /v1/completions and /v1/chat/completions enqueue onto the shared slot
    scheduler (f32 — the batched step paths contain bf16 dots XLA's CPU
    thunks cannot execute, same as the batch fixture)."""
    mpath, tpath = _fixture(tmp_path, rng)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3",
        "--compute-dtype", "f32", "--cache-dtype", "f32"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny",
                     serve_batch=2, serve_chunk=16)
    from http.server import ThreadingHTTPServer
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield server.server_address, state
    server.shutdown()
    if state._scheduler is not None:
        state._scheduler.close()


def _sse_events(raw: str) -> list:
    events = [line[len("data: "):] for line in raw.splitlines()
              if line.startswith("data: ")]
    assert events and events[-1] == "[DONE]"
    return [json.loads(e) for e in events[:-1]]


def test_api_threaded_concurrent_streaming_clients(sched_api_server):
    """Two concurrent streaming clients with different prompt lengths both
    complete through the shared scheduler, each with well-formed SSE."""
    (host, port), state = sched_api_server
    results = {}

    def client(key, content, n):
        conn = http.client.HTTPConnection(host, port, timeout=240)
        req = {"messages": [{"role": "user", "content": content}],
               "max_tokens": n, "temperature": 0, "stream": True}
        conn.request("POST", "/v1/chat/completions", json.dumps(req),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        results[key] = (resp.status, resp.getheader("Content-Type"),
                        resp.read().decode())

    threads = [threading.Thread(target=client, args=("a", "ab", 6)),
               threading.Thread(target=client,
                                args=("b", "abab baba abba x", 9))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
        assert not t.is_alive()

    for key in ("a", "b"):
        status, ctype, raw = results[key]
        assert status == 200
        assert ctype.startswith("text/event-stream")
        parsed = _sse_events(raw)
        # every chunk is a well-formed per-request envelope; exactly one
        # terminal chunk carries the finish_reason
        assert all(p["object"] == "chat.completion.chunk" for p in parsed)
        assert all(p["choices"][0]["index"] == 0 for p in parsed)
        finals = [p for p in parsed if p["choices"][0]["finish_reason"]]
        assert len(finals) == 1
        assert finals[0]["choices"][0]["finish_reason"] in ("stop", "length")
    assert len(state.scheduler().stats.requests) == 2


def test_api_sched_greedy_matches_legacy_single(sched_api_server, tmp_path,
                                                rng):
    """A greedy chat request served through the scheduler must be
    byte-identical to the legacy single-engine path answering it alone
    (continuous batching is a scheduling change, not a sampling one)."""
    from distributed_llama_tpu.apps.api_server import _completion_chunks

    (host, port), state = sched_api_server
    body = {"messages": [{"role": "user", "content": "abba"}],
            "max_tokens": 6, "temperature": 0}
    conn = http.client.HTTPConnection(host, port, timeout=240)
    conn.request("POST", "/v1/chat/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    got = json.loads(resp.read())["choices"][0]["message"]["content"]

    legacy = ApiState(state.engine, state.tokenizer, state.sampler)
    legacy.engine.reset()
    want = "".join(p for kind, p in _completion_chunks(legacy, dict(body))
                   if kind == "piece")
    assert got == want


def test_api_completions_route_scheduler(sched_api_server):
    """POST /v1/completions (raw prompt, no chat template) through the
    scheduler: valid text_completion envelope, consistent usage."""
    (host, port), state = sched_api_server
    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"prompt": "ab", "max_tokens": 5, "temperature": 0}
    conn.request("POST", "/v1/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    body = json.loads(resp.read())
    assert body["object"] == "text_completion"
    choice = body["choices"][0]
    assert isinstance(choice["text"], str)
    assert choice["finish_reason"] in ("stop", "length")
    assert body["usage"]["completion_tokens"] <= 5
    assert body["usage"]["total_tokens"] == (
        body["usage"]["prompt_tokens"] + body["usage"]["completion_tokens"])


def test_api_completions_route_legacy(api_server):
    """The raw /v1/completions route also works without the scheduler
    (single engine behind the lock) — including SSE streaming."""
    host, port = api_server
    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"prompt": "ab", "max_tokens": 3, "temperature": 0,
           "stream": True}
    conn.request("POST", "/v1/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    parsed = _sse_events(resp.read().decode())
    assert all(p["object"] == "text_completion" for p in parsed)
    assert parsed[-1]["choices"][0]["finish_reason"] in ("stop", "length")


def test_api_sched_prompt_too_long_clean_400(sched_api_server):
    """A prompt larger than seq_len must return a clean 400 through the
    queued/threaded scheduler path (PromptTooLong from submit), and the
    server must keep serving afterwards."""
    (host, port), state = sched_api_server
    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"messages": [{"role": "user", "content": "x" * 400}],
           "max_tokens": 2, "temperature": 0}
    conn.request("POST", "/v1/chat/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 400
    assert "tokens" in json.loads(resp.read())["error"]

    conn = http.client.HTTPConnection(host, port, timeout=240)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt": "ab", "max_tokens": 2,
                             "temperature": 0}),
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 200


def test_api_stats_route(sched_api_server):
    """GET /stats exposes the scheduler's serving counters after a
    request has been served."""
    (host, port), state = sched_api_server
    conn = http.client.HTTPConnection(host, port, timeout=240)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt": "ab", "max_tokens": 3,
                             "temperature": 0}),
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 200
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/stats")
    resp = conn.getresponse()
    assert resp.status == 200
    s = json.loads(resp.read())
    assert s["requests_finished"] >= 1
    assert s["tokens_out"] >= 1
    assert s["ttft_p50_ms"] is not None and s["ttft_p50_ms"] >= 0


@pytest.mark.parametrize("wt", [FloatType.F32, FloatType.Q80])
def test_cli_runs_f32_and_q80_weight_files(tmp_path, rng, capsys, wt):
    """The reference converts/serves q40, q80 AND f32 weight files
    (ref: converter/writer.py); q40 has dedicated kernels here, while q80/
    f32 run through the dense load path — pin that both actually DECODE
    (inference mode's stats line counts the generated tokens, so a load
    path that serves but silently emits nothing fails here)."""
    mpath, tpath = _fixture(tmp_path, rng, wt=wt)
    dllama.main(["inference", "--model", mpath, "--tokenizer", tpath,
                 "--prompt", "ab", "--steps", "4", "--seed", "7",
                 "--temperature", "0"])
    out = capsys.readouterr().out
    assert "Generated tokens:    4" in out, wt


# -- serving resilience at the HTTP layer (ISSUE 3) -------------------------


def test_api_healthz_readyz_routes(api_server):
    """Liveness and readiness on the legacy (scheduler-off) server:
    /healthz is the process-up probe, /readyz the routing signal."""
    host, port = api_server
    for path, key, want in (("/healthz", "status", "ok"),
                            ("/readyz", "status", "ready")):
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", path)
        resp = conn.getresponse()
        assert resp.status == 200, path
        assert json.loads(resp.read())[key] == want


def test_api_readyz_scheduler_states(sched_api_server):
    """/readyz with the supervisor: 'idle' before the first request builds
    it, 'ready' with the supervisor state once live."""
    (host, port), state = sched_api_server
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/readyz")
    body = json.loads(conn.getresponse().read())
    assert body == {"status": "ready", "scheduler": "idle"}
    conn = http.client.HTTPConnection(host, port, timeout=240)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt": "ab", "max_tokens": 2,
                             "temperature": 0}),
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 200
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/readyz")
    resp = conn.getresponse()
    assert resp.status == 200
    assert json.loads(resp.read())["state"] == "ready"
    # /stats now carries the resilience block too
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/stats")
    s = json.loads(conn.getresponse().read())
    assert s["state"] == "ready"
    assert s["resilience"]["recoveries"] == 0


def test_api_draining_rejects_posts_but_stays_alive(sched_api_server):
    """Graceful drain: POSTs 503 with Retry-After, /readyz goes unready,
    /healthz stays 200 (a liveness restart would cut the drain short)."""
    (host, port), state = sched_api_server
    state.draining = True
    try:
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": "ab", "max_tokens": 2}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 503
        assert resp.getheader("Retry-After") is not None
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/readyz")
        resp = conn.getresponse()
        assert resp.status == 503
        assert json.loads(resp.read())["status"] == "draining"
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        assert resp.status == 200
        assert json.loads(resp.read())["status"] == "draining"
    finally:
        state.draining = False


def test_api_sse_midstream_error_frame(sched_api_server):
    """ISSUE 3 satellite: an SSE client already streaming tokens when the
    step loop crashes must receive a structured error event and a
    terminated stream ([DONE]) — never a silent hang."""
    from distributed_llama_tpu.runtime.faults import FAULTS

    (host, port), state = sched_api_server
    try:
        # pace the step loop so the stream provably cannot COMPLETE before
        # the crash is armed below (warm caches make bare steps sub-ms)
        FAULTS.arm("slow_step", times=0, ms=25.0)
        conn = http.client.HTTPConnection(host, port, timeout=240)
        req = {"prompt": "abab", "max_tokens": 5000, "temperature": 0,
               "stream": True}
        conn.request("POST", "/v1/completions", json.dumps(req),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        # read until the first token chunk arrives — the stream is LIVE
        first = b""
        while not first.strip():
            first = resp.fp.readline()
        FAULTS.arm("step_raise")  # the next scheduler step crashes
        raw = first.decode() + resp.read().decode()
        events = [line[len("data: "):] for line in raw.splitlines()
                  if line.startswith("data: ")]
        assert events[-1] == "[DONE]"  # the stream TERMINATED cleanly
        parsed = [json.loads(e) for e in events[:-1]]
        errs = [p for p in parsed if "error" in p]
        assert len(errs) == 1, raw[-500:]
        assert errs[0]["error"]["code"] == "engine_error"
        assert "injected step_raise" in errs[0]["error"]["message"]
        finals = [p for p in parsed if p.get("choices")
                  and p["choices"][0]["finish_reason"]]
        assert finals and finals[-1]["choices"][0]["finish_reason"] == "error"
        # the supervisor recovers and the server keeps serving
        sup = state._scheduler
        deadline = 30.0
        import time as _time
        t0 = _time.perf_counter()
        while not sup.ready and _time.perf_counter() - t0 < deadline:
            _time.sleep(0.05)
        assert sup.ready, sup.state
        conn = http.client.HTTPConnection(host, port, timeout=240)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": "ab", "max_tokens": 2,
                                 "temperature": 0}),
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 200
        assert sup.sup_stats.recoveries == 1
    finally:
        FAULTS.clear()


@pytest.fixture
def tight_queue_server(tmp_path, rng):
    """serve_batch=1 + queue_depth=1: one running slot, one queue seat —
    the third concurrent request must be REJECTED, not queued."""
    mpath, tpath = _fixture(tmp_path, rng)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3",
        "--compute-dtype", "f32", "--cache-dtype", "f32"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny",
                     serve_batch=1, serve_chunk=16, queue_depth=1)
    from http.server import ThreadingHTTPServer
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield server.server_address, state
    server.shutdown()
    if state._scheduler is not None:
        state._scheduler.close()


def test_api_queue_overflow_429_retry_after(tight_queue_server):
    """ISSUE 3: queue overflow returns a fast 429 + Retry-After instead of
    queueing unboundedly, and /readyz reports the saturated queue."""
    import time as _time

    from distributed_llama_tpu.runtime.faults import FAULTS

    (host, port), state = tight_queue_server
    results = {}

    def client(key, n):
        conn = http.client.HTTPConnection(host, port, timeout=240)
        req = {"prompt": "abab", "max_tokens": n, "temperature": 0,
               "stream": True}
        conn.request("POST", "/v1/completions", json.dumps(req),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        results[key] = (resp.status, resp.read().decode())

    try:
        FAULTS.arm("slow_step", times=0, ms=60.0)  # hold the slot busy
        a = threading.Thread(target=client, args=("a", 30), daemon=True)
        a.start()
        # wait until A occupies the slot
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < 30.0:
            sup = state._scheduler
            if sup is not None and any(
                    s.req is not None for s in sup._sched.slots):
                break
            _time.sleep(0.02)
        b = threading.Thread(target=client, args=("b", 2), daemon=True)
        b.start()  # takes the single queue seat
        t0 = _time.perf_counter()
        while len(state._scheduler._sched._queue) < 1:
            assert _time.perf_counter() - t0 < 30.0, "B never queued"
            _time.sleep(0.02)
        # C: queue full -> fast 429 with Retry-After
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": "ab", "max_tokens": 2,
                                 "temperature": 0}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 429
        assert int(resp.getheader("Retry-After")) >= 1
        assert "queue full" in json.loads(resp.read())["error"]
        # readiness = engine healthy AND queue under bound
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/readyz")
        assert conn.getresponse().status == 503
        FAULTS.clear()  # let A and B finish normally
        a.join(timeout=240)
        b.join(timeout=240)
        assert not a.is_alive() and not b.is_alive()
        assert results["a"][0] == 200 and results["b"][0] == 200
        assert state._scheduler.stats.requests_rejected == 1
    finally:
        FAULTS.clear()


def test_api_batch_bad_temperature_is_400(api_batch_server):
    """A malformed request field on the batch endpoint is a deterministic
    client error: 400, never a retryable 503 'engine failure'."""
    (host, port), state = api_batch_server
    conn = http.client.HTTPConnection(host, port, timeout=240)
    req = {"prompts": ["ab"], "max_tokens": 2, "temperature": "hot"}
    conn.request("POST", "/v1/batch/completions", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 400
    assert resp.getheader("Retry-After") is None
    assert "ValueError" in json.loads(resp.read())["error"]


def test_api_batch_borrow_crash_triggers_recovery(api_batch_server):
    """A crash inside the exclusive borrow (the whole-batch generation
    itself) must reach the supervisor: recovery runs, the engine is
    rebuilt, and the endpoint serves again."""
    import time as _time

    from distributed_llama_tpu.apps.api_server import (
        _batch_completion_chunks)

    (host, port), state = api_batch_server
    sup = state.scheduler()

    def boom(*a, **k):
        raise RuntimeError("borrowed engine crashed")
        yield  # pragma: no cover — generator shape

    sup.engine.generate_batch_stream = boom
    body = {"prompts": ["ab", "ba"], "max_tokens": 3, "temperature": 0}
    with pytest.raises(RuntimeError, match="borrowed engine crashed"):
        list(_batch_completion_chunks(state, dict(body)))
    t0 = _time.perf_counter()
    while not sup.ready and _time.perf_counter() - t0 < 30.0:
        _time.sleep(0.05)
    assert sup.ready, sup.state
    assert sup.sup_stats.crashes == 1
    assert sup.sup_stats.recoveries == 1
    # the rebuilt engine serves the endpoint again, end to end
    conn = http.client.HTTPConnection(host, port, timeout=240)
    conn.request("POST", "/v1/batch/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    out = json.loads(resp.read())
    assert all(c["finish_reason"] in ("stop", "length")
               for c in out["choices"])


def test_session_pp_contract_rejected_at_parse():
    """VERDICT pp contract holes: --session with --pp > 1 (stage-stacked
    caches are not host-fetchable) and with --nnodes > 1 must be refused
    at PARSE time with a clear message — before any model load, cluster
    connect, or silent ignore."""
    with pytest.raises(SystemExit) as ei:
        dllama.main(["generate", "--model", "m", "--tokenizer", "t",
                     "--session", "s.bin", "--pp", "2"])
    assert "--session" in str(ei.value) and "--pp" in str(ei.value)
    with pytest.raises(SystemExit) as ei:
        dllama.main(["chat", "--model", "m", "--tokenizer", "t",
                     "--session", "s.bin", "--pp", "4"])
    assert "--pp" in str(ei.value)
    with pytest.raises(SystemExit) as ei:
        dllama.main(["generate", "--model", "m", "--tokenizer", "t",
                     "--session", "s.bin", "--nnodes", "2",
                     "--coordinator", "127.0.0.1:1"])
    assert "--nnodes" in str(ei.value)


def test_help_surfaces_q80_pp_exclusion():
    """The q80+pp collective exclusion must be discoverable from --help,
    not only from a runtime notice mid-run."""
    text = " ".join(dllama.build_argparser().format_help().split())
    # --buffer-float-type documents that q80 is ignored under --pp
    assert "q80 is ignored there" in text, text
    assert "quantized exchange cannot nest" in text.lower()
    # --pp documents both of its contract exclusions
    assert "--session is refused" in text
    # and the new cluster-resilience flags are documented
    for flag in ("--connect-timeout", "--heartbeat-interval",
                 "--worker-timeout"):
        assert flag in text, flag


def test_api_batch_lookup_streams_keepalives_before_completion(tmp_path,
                                                               rng,
                                                               monkeypatch):
    """ADVICE r5 low: the batch endpoint's greedy+lookup path buffers all
    rows (generate_batch_lookup) before the first data event — SSE
    keepalive comment frames must flow WHILE it collects, so bytes reach
    the client well before completion (no proxy/client idle timeout on
    long generations)."""
    import time as _time

    from distributed_llama_tpu.apps import api_server

    monkeypatch.setattr(api_server, "KEEPALIVE_SECS", 0.01)
    mpath, tpath = _fixture(tmp_path, rng)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3",
        "--compute-dtype", "f32", "--cache-dtype", "f32"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny",
                     serve_batch=2, lookup_decode=4)
    from http.server import HTTPServer
    server = HTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    host, port = server.server_address
    try:
        conn = http.client.HTTPConnection(host, port, timeout=240)
        req = {"prompts": ["abab", "ba"], "max_tokens": 6,
               "temperature": 0, "stream": True}
        conn.request("POST", "/v1/batch/completions", json.dumps(req),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        first_byte_at = None
        lines = []
        while True:
            line = resp.fp.readline()
            if first_byte_at is None and line:
                first_byte_at = _time.monotonic()
            lines.append(line.decode())
            if line.strip() == b"data: [DONE]":
                done_at = _time.monotonic()
                break
            assert line, lines  # EOF before [DONE] = broken stream
        # keepalive comments arrived, and BEFORE the first data event
        # (the collected path yields no piece until the whole batch is
        # done, so any earlier keepalive proves first-byte << completion)
        first_data = next(i for i, ln in enumerate(lines)
                          if ln.startswith("data: "))
        keepalives = [i for i, ln in enumerate(lines)
                      if ln.startswith(": keepalive")]
        assert keepalives, lines
        assert keepalives[0] < first_data, lines
        assert first_byte_at < done_at
        # the stream still ends with per-row finish chunks + [DONE]
        datas = [json.loads(ln[len("data: "):]) for ln in lines
                 if ln.startswith("data: ") and "[DONE]" not in ln]
        finals = [d for d in datas if d["choices"][0]["finish_reason"]]
        assert len(finals) == 2
    finally:
        server.shutdown()
        state.engine.reset()


def test_api_batch_lookup_stream_crash_yields_structured_error(tmp_path,
                                                               rng,
                                                               monkeypatch):
    """An engine crash surfacing BEHIND the keepalives (after the 200/SSE
    start) must follow the mid-stream error contract: an explicit
    {"error": ...} event then [DONE] — never a dropped connection."""
    import time as _time

    from distributed_llama_tpu.apps import api_server

    monkeypatch.setattr(api_server, "KEEPALIVE_SECS", 0.01)
    mpath, tpath = _fixture(tmp_path, rng)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3",
        "--compute-dtype", "f32", "--cache-dtype", "f32"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny",
                     serve_batch=2, lookup_decode=4)
    sup = state.scheduler()  # build the supervisor, then wound its engine

    def boom(*a, **k):
        _time.sleep(0.05)  # long enough for a keepalive to have flowed
        raise RuntimeError("injected lookup crash")

    sup.engine.generate_batch_lookup = boom
    from http.server import HTTPServer
    server = HTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    host, port = server.server_address
    try:
        conn = http.client.HTTPConnection(host, port, timeout=240)
        req = {"prompts": ["abab", "ba"], "max_tokens": 6,
               "temperature": 0, "stream": True}
        conn.request("POST", "/v1/batch/completions", json.dumps(req),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200  # SSE already started when it crashed
        raw = resp.read().decode()
        datas = [ln[len("data: "):] for ln in raw.splitlines()
                 if ln.startswith("data: ")]
        assert datas[-1] == "[DONE]", raw
        err_events = [json.loads(d) for d in datas[:-1]
                      if "error" in json.loads(d)]
        assert err_events and "injected lookup crash" in \
            err_events[0]["error"], raw
    finally:
        server.shutdown()
        if state._scheduler is not None:
            state._scheduler.close()


# -- multi-replica router tier at the HTTP layer (ISSUE 6) ------------------


def test_is_loopback_guard_shapes():
    """The /admin/* guard: the whole IPv4 loopback block, ::1, and the
    IPv6-mapped form pass; anything routable does not."""
    from distributed_llama_tpu.apps.api_server import _is_loopback

    for ok in ("127.0.0.1", "127.1.2.3", "::1", "::ffff:127.0.0.1"):
        assert _is_loopback(ok), ok
    for bad in ("10.0.0.1", "192.168.1.9", "0.0.0.0", "::ffff:10.0.0.1",
                "2001:db8::1", "128.0.0.1"):
        assert not _is_loopback(bad), bad


@pytest.fixture
def router_api_server(tmp_path, rng):
    """Threaded server with the 2-replica failover router in front of the
    continuous-batching scheduler (f32 for the same CPU-thunk reason as
    the other scheduler fixtures)."""
    mpath, tpath = _fixture(tmp_path, rng)
    args = dllama.build_argparser().parse_args([
        "api", "--model", mpath, "--tokenizer", tpath,
        "--steps", "8", "--temperature", "0", "--seed", "3",
        "--compute-dtype", "f32", "--cache-dtype", "f32"])
    engine, tokenizer, sampler = dllama.build_engine(args)
    state = ApiState(engine, tokenizer, sampler, model_name="tiny",
                     serve_batch=2, serve_chunk=16, replicas=2,
                     retry_budget=1)
    from http.server import ThreadingHTTPServer
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield server.server_address, state
    server.shutdown()
    if state._scheduler is not None:
        state._scheduler.close()


def test_api_router_serves_and_reports_replicas(router_api_server):
    """The SAME handlers serve N replicas: a chat completion routes
    through the Router, /readyz carries per-replica states, and /stats
    aggregates counters with a `replicas` list + `router` block."""
    (host, port), state = router_api_server
    body = {"messages": [{"role": "user", "content": "ab"}],
            "max_tokens": 4, "temperature": 0}
    conn = http.client.HTTPConnection(host, port, timeout=240)
    conn.request("POST", "/v1/chat/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    out = json.loads(resp.read())
    assert out["choices"][0]["finish_reason"] in ("stop", "length")

    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/readyz")
    resp = conn.getresponse()
    assert resp.status == 200
    ready = json.loads(resp.read())
    assert ready["status"] == "ready"
    assert set(ready["replicas"]) == {"r0", "r1"}

    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/stats")
    s = json.loads(conn.getresponse().read())
    assert s["requests_finished"] >= 1
    assert s["router"]["replicas"] == 2
    assert s["router"]["routed"] >= 1
    assert len(s["replicas"]) == 2
    assert all("resilience" in r for r in s["replicas"])


def test_api_router_replica_failure_invisible_to_client(router_api_server):
    """Kill replica 0 mid-trace at the HTTP layer: the in-flight
    not-yet-streamed request retries on replica 1 and the client sees a
    clean 200 — byte-identical to the healthy answer — while /readyz
    stays 200 throughout."""
    from distributed_llama_tpu.runtime.faults import FAULTS

    (host, port), state = router_api_server
    body = {"messages": [{"role": "user", "content": "abba"}],
            "max_tokens": 5, "temperature": 0}

    def ask():
        conn = http.client.HTTPConnection(host, port, timeout=240)
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    status, healthy = ask()  # also builds the router
    assert status == 200
    try:
        FAULTS.arm("replica_raise", key="r0", times=1)
        # the idle tie routes to r0 (its cache has no radix tree here, so
        # no cache bias): it dies pre-first-token, the router fails over
        status, failover = ask()
        assert status == 200
        assert failover["choices"][0]["message"]["content"] == \
            healthy["choices"][0]["message"]["content"]
        sup = state._scheduler
        assert sup.stats.retries >= 1 or FAULTS.fired("replica_raise") == 0
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/readyz")
        assert conn.getresponse().status == 200
    finally:
        FAULTS.clear()


def test_api_admin_reset_breaker_restores_broken_service(sched_api_server):
    """ISSUE 6 satellite: a BROKEN supervisor in api mode used to be an
    outage only a Python REPL could end — POST /admin/reset_breaker is
    the operator's HTTP half-open, and service resumes once the fault is
    gone."""
    import time as _time

    from distributed_llama_tpu.runtime.faults import FAULTS
    from distributed_llama_tpu.runtime.resilience import BROKEN, READY

    (host, port), state = sched_api_server

    def post(path, body):
        conn = http.client.HTTPConnection(host, port, timeout=240)
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    status, _ = post("/v1/completions", {"prompt": "ab", "max_tokens": 2,
                                         "temperature": 0})
    assert status == 200
    sup = state._scheduler
    try:
        FAULTS.arm("step_raise", times=0)  # every working step crashes
        t0 = _time.perf_counter()
        while sup.state != BROKEN and _time.perf_counter() - t0 < 60.0:
            try:
                post("/v1/completions", {"prompt": "ab", "max_tokens": 4,
                                         "temperature": 0})
            except Exception:  # noqa: BLE001 — a 503 path mid-recovery
                pass
            _time.sleep(0.05)
        assert sup.state == BROKEN, sup.state
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/readyz")
        assert conn.getresponse().status == 503
        FAULTS.clear()  # the fault is gone; the operator closes the circuit
        status, body = post("/admin/reset_breaker", {})
        assert status == 200 and body["status"] == "ok"
        t0 = _time.perf_counter()
        while sup.state != READY and _time.perf_counter() - t0 < 30.0:
            _time.sleep(0.05)
        status, _ = post("/v1/completions", {"prompt": "ab",
                                             "max_tokens": 2,
                                             "temperature": 0})
        assert status == 200
    finally:
        FAULTS.clear()


def test_api_admin_replica_ops_rolling_restart(router_api_server):
    """The rolling-restart recipe over HTTP: drain replica 0 (service
    stays ready on replica 1), restart it, repeat for replica 1 — the
    operator path docs/operations.md documents."""
    (host, port), state = router_api_server

    def post(path, body):
        conn = http.client.HTTPConnection(host, port, timeout=240)
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    status, _ = post("/v1/completions", {"prompt": "ab", "max_tokens": 2,
                                         "temperature": 0})
    assert status == 200  # router built
    for rid in (0, 1):
        status, body = post("/admin/drain_replica", {"replica": rid})
        assert status == 200 and body["status"] == "drained"
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/readyz")
        resp = conn.getresponse()
        assert resp.status == 200  # the sibling keeps the service ready
        assert json.loads(resp.read())["replicas"][f"r{rid}"].endswith(
            "/draining")
        status, body = post("/admin/restart_replica", {"replica": rid})
        assert status == 200 and body["status"] == "restarted"
        status, _ = post("/v1/completions", {"prompt": "ab",
                                             "max_tokens": 2,
                                             "temperature": 0})
        assert status == 200
    assert state._scheduler.stats.restarts == 2
    # replica index validation is a clean 400
    status, body = post("/admin/restart_replica", {"replica": 9})
    assert status == 400 and "replica" in body["error"]


def test_api_admin_on_single_replica_and_legacy(api_server):
    """Admin endpoints never 404 by surprise: the legacy (no
    --serve-batch) server answers with a clear 404 + remedy; replica ops
    on a 1-replica server are a clean 400 (see the router fixture for the
    happy path)."""
    host, port = api_server
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("POST", "/admin/reset_breaker", json.dumps({}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 404
    assert "--serve-batch" in json.loads(resp.read())["error"]


def test_admin_authorized_token_paths():
    """ISSUE 7 satellite (unit): loopback always passes; off-loopback
    needs an exact --admin-token bearer (constant-time compare) — no
    token configured means off-box is always refused, and a configured
    token never opens the door to a wrong or missing header."""
    from types import SimpleNamespace

    from distributed_llama_tpu.apps.api_server import _admin_authorized

    s = SimpleNamespace(admin_token="s3cret-tok")
    assert _admin_authorized(s, "127.0.0.1", None)          # loopback
    assert _admin_authorized(s, "::1", "Bearer wrong")      # still loopback
    assert _admin_authorized(s, "10.0.0.1", "Bearer s3cret-tok")
    assert not _admin_authorized(s, "10.0.0.1", None)
    assert not _admin_authorized(s, "10.0.0.1", "Bearer nope")
    assert not _admin_authorized(s, "10.0.0.1", "s3cret-tok")  # no scheme
    assert not _admin_authorized(s, "10.0.0.1", "bearer s3cret-tok")
    no_tok = SimpleNamespace(admin_token=None)
    assert not _admin_authorized(no_tok, "10.0.0.1", "Bearer s3cret-tok")
    assert _admin_authorized(no_tok, "127.0.0.1", None)


def test_api_admin_token_403_and_200_off_loopback(sched_api_server,
                                                  monkeypatch):
    """ISSUE 7 satellite (HTTP): with the caller simulated off-loopback,
    /admin/* is 403 without (or with a wrong) bearer and 200 with the
    configured --admin-token — the operator path for remote-replica
    deployments where loopback-only was an outage."""
    import distributed_llama_tpu.apps.api_server as api_mod

    (host, port), state = sched_api_server
    monkeypatch.setattr(api_mod, "_is_loopback", lambda addr: False)
    monkeypatch.setattr(state, "admin_token", "tok-123")

    def post(headers):
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", "/admin/reset_breaker", json.dumps({}),
                     {"Content-Type": "application/json", **headers})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    status, body = post({})
    assert status == 403 and "admin-token" in body["error"]
    status, _ = post({"Authorization": "Bearer wrong"})
    assert status == 403
    status, _ = post({"Authorization": "Bearer tok-123"})
    assert status == 200


def test_api_healthz_readyz_all_modes_never_404(api_server,
                                                sched_api_server,
                                                router_api_server):
    """ISSUE 6 satellite: a probe must never 404 depending on launch
    flags — /healthz and /readyz answer on the legacy single-engine
    server, the scheduler server, and the router server alike."""
    targets = [api_server, sched_api_server[0], router_api_server[0]]
    for host, port in targets:
        for path in ("/healthz", "/health", "/readyz"):
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("GET", path)
            resp = conn.getresponse()
            assert resp.status in (200, 503), (host, port, path)
            assert resp.status != 404, (host, port, path)
            json.loads(resp.read())  # machine-readable either way


def test_replica_flags_rejected_without_serve_batch():
    """--replicas/--retry-budget/--route-policy are loud errors without
    --serve-batch (and retry/policy without --replicas), same dead-flag
    principle as the prefix-cache knobs — checked before any model
    load."""
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--replicas", "2"])
    assert "--serve-batch" in str(ei.value)
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--serve-batch", "2", "--retry-budget", "3"])
    assert "--replicas" in str(ei.value)
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--serve-batch", "2", "--route-policy",
                     "round_robin"])
    assert "--replicas" in str(ei.value)
    # an explicit 0 must hit the >= 1 error, not silently coerce to 1
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--serve-batch", "2", "--replicas", "0"])
    assert ">= 1" in str(ei.value)


def test_api_healthz_build_block_all_modes(api_server, sched_api_server,
                                           router_api_server):
    """ISSUE 10 satellite: /healthz carries the build-identity block —
    {version, jax, backend, mesh} — in every tier (never gated on a
    launch flag, the same rule as /metrics): version skew across a
    replica fleet must show on the probe everyone already scrapes."""
    import jax

    import distributed_llama_tpu as pkg

    targets = [api_server, sched_api_server[0], router_api_server[0]]
    for host, port in targets:
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, (host, port)
        b = body["build"]
        assert b["version"] == pkg.__version__
        assert b["jax"] == jax.__version__
        assert b["backend"] == "cpu" and b["mesh"] == "single"


def test_api_metrics_build_info_series(sched_api_server):
    """dllama_build_info rides /metrics as the constant-1 info idiom."""
    (host, port), _state = sched_api_server
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    body = resp.read().decode()
    assert resp.status == 200
    line = next(ln for ln in body.splitlines()
                if ln.startswith("dllama_build_info{"))
    assert 'backend="cpu"' in line and 'mesh="single"' in line
    assert line.endswith(" 1")


def test_api_admin_profile_captures_and_validates(sched_api_server,
                                                  tmp_path, monkeypatch):
    """POST /admin/profile?ms=N: loopback 200 with the trace dir in the
    body (the capture ran synchronously), garbage ms a clean 400 —
    and off-loopback it is guarded exactly like every /admin/* verb."""
    import distributed_llama_tpu.apps.api_server as api_mod

    (host, port), state = sched_api_server
    monkeypatch.setattr(state, "profile_dir", str(tmp_path / "prof"))

    def post(path, headers=None):
        conn = http.client.HTTPConnection(host, port, timeout=120)
        conn.request("POST", path, json.dumps({}),
                     {"Content-Type": "application/json", **(headers or {})})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    status, body = post("/admin/profile?ms=20")
    assert status == 200, body
    assert body["status"] == "ok" and body["ms"] == 20.0
    assert body["dir"].startswith(str(tmp_path / "prof"))
    import os
    assert os.path.isdir(body["dir"])
    # the capture's report of itself: on this CPU there is no device plane,
    # which it says, in the reply, in the file and in /stats `capture`
    assert body["report"]["error"] and body["report_ms"] >= 0
    with open(os.path.join(body["dir"], "report.json")) as f:
        assert json.load(f) == body["report"]
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/stats")
    capture = json.loads(conn.getresponse().read())["capture"]
    assert capture["report"] == body["report"]
    assert set(capture) == {"start", "stop", "report"}

    for bad in ("ms=zz", "ms=-5", "ms=0", "ms=900000"):
        status, body = post(f"/admin/profile?{bad}")
        assert status == 400, (bad, body)

    # off-loopback: same guard as every admin verb (the chaos job pins
    # the process-tier variant in tests/test_replica_procs.py)
    monkeypatch.setattr(api_mod, "_is_loopback", lambda addr: False)
    status, body = post("/admin/profile?ms=10")
    assert status == 403 and "admin" in body["error"]
    monkeypatch.setattr(state, "admin_token", "tok-9")
    status, _ = post("/admin/profile?ms=10",
                     {"Authorization": "Bearer tok-9"})
    assert status == 200


def test_profiler_flags_rejected_without_serve_batch():
    """--freeze-compiles hangs off the slot scheduler (warmup arms the
    sentinel) — a dead flag without --serve-batch, same principle as the
    router/trace knobs."""
    with pytest.raises(SystemExit) as ei:
        dllama.main(["api", "--model", "m", "--tokenizer", "t",
                     "--freeze-compiles"])
    assert "--serve-batch" in str(ei.value)


def test_api_engine_that_cannot_be_built_exits_nonzero(tmp_path):
    """`dllama api --serve-batch` builds and warms its engine BEFORE it
    listens: an engine that cannot be built (here: the injected
    prefill_raise fault fires inside the warm-up; on a chip, an
    out-of-memory or a refused compile) must end the server with a
    traceback and a non-zero exit — not a process that looks ready and
    500s, nor a rebuild loop that never becomes ready."""
    import os
    import subprocess
    import sys

    from distributed_llama_tpu.testing import write_fixture

    mpath, tpath = write_fixture(tmp_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               DLLAMA_FAULTS="prefill_raise:times=0")
    r = subprocess.run(
        [sys.executable, "-m", "distributed_llama_tpu.apps.dllama", "api",
         "--model", mpath, "--tokenizer", tpath, "--serve-batch", "2",
         "--host", "127.0.0.1", "--port", "0"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=300)
    assert r.returncode not in (0, None), r.stdout[-500:]
    assert "FaultError" in r.stderr
    assert "listening" not in r.stdout
