"""Chip smoke: the serving path, end to end, on one TPU v5e chip.

    python chip_smoke.py              # one chip — what the driver runs
    python chip_smoke.py --chips 4    # the tp=4 path and its one-chip twin

Drives `.m`/`.t` files -> streamed loader -> Engine -> Scheduler -> HTTP
through the entry points a user calls (`python -m
distributed_llama_tpu.apps.dllama api ...`) at the full width AND depth of
Llama-2-7B Q40 (random but valid weights from --seed), checks the answers
and the device-side records, and prints ONE JSON object as the last stdout
line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed phase exits non-zero and prints no such line. Without an
accelerator it fails: every chip-holding child runs with JAX_PLATFORMS=tpu,
and the device facts in the last line are the ones the SERVER reported.

This parent process never imports JAX: a process that touched JAX holds the
chip, and the children need it. Every step that compiles or loads a model
is a child, one at a time. Timings printed here are smoke timings — one
reading each, compile included where stated — never benchmark metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Llama-2-7B (the reference's headline model): published widths, full depth
LLAMA2_7B = dict(arch="LLAMA", dim=4096, hidden_dim=11008, n_layers=32,
                 n_heads=32, n_kv_heads=32, vocab_size=32000, seq_len=2048,
                 hidden_act="SILU", rope_theta=10000.0)


@dataclasses.dataclass
class Plan:
    """Everything a phase needs. `main()` builds the real one (7B widths,
    JAX_PLATFORMS=tpu); the CPU rehearsal (tests/test_chip_smoke.py)
    builds a tiny one — the script itself has no option that lets it
    pass without a chip."""
    spec: dict
    workdir: str
    seed: int = 0
    child_env: dict = dataclasses.field(default_factory=dict)
    want_backend: str = "tpu"
    serve_batch: int = 8
    # B x C = 256 rows = pallas_q40.MAX_T: the widest serving prefill that
    # still takes the fused Q40 kernel (wider segments are the designed
    # XLA-dequant fallback, which this smoke must not silently measure)
    serve_chunk: int = 32
    max_seq_len: int = 1024
    prefix_blocks: int = 128
    prefix_block_len: int = 32
    max_tokens: int = 12
    interpret: bool = False     # parity child: Pallas interpret mode (CPU)
    # logits compared as relative L2 (||a-b|| / ||b||) over the vocab. bf16
    # compute through 32 layers measured 0.004-0.02 between the kernel and
    # XLA paths on the chip; 0.05 is the bound tests/test_tp_kernels.py's
    # q80 check uses (atol 0.05 on unit-range logits), applied to both
    logit_tol: float = 0.05     # kernels vs use_pallas=False
    tp_logit_tol: float = 0.05  # tp q80 vs one chip
    synth_timeout: float = 600.0
    boot_timeout: float = 900.0
    traffic_timeout: float = 300.0
    child_timeout: float = 900.0
    drain_timeout: float = 90.0

    @property
    def model(self) -> str:
        return os.path.join(self.workdir, "model.m")

    @property
    def tokenizer(self) -> str:
        return os.path.join(self.workdir, "tok.t")


class SmokeFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def sizing_note(plan: Plan) -> str:
    s = plan.spec
    per_tok = 2 * s["n_layers"] * s["n_kv_heads"] * (
        s["dim"] // s["n_heads"]) * 2  # k+v, bf16
    slots = plan.serve_batch * plan.max_seq_len * per_tok
    arena = plan.prefix_blocks * plan.prefix_block_len * per_tok
    vals = s["n_layers"] * (2 * s["dim"] ** 2
                            + 2 * s["dim"] * s["dim"] * s["n_kv_heads"]
                            // s["n_heads"]
                            + 3 * s["dim"] * s["hidden_dim"]) \
        + s["vocab_size"] * s["dim"]
    weights = vals * 18 // 32 + s["vocab_size"] * s["dim"] * 2
    return (f"sizing: weights ~{weights / 2**30:.1f} GiB + "
            f"{plan.serve_batch} slots x {plan.max_seq_len} tokens "
            f"~{slots / 2**30:.1f} GiB (--max-seq-len {plan.max_seq_len}; "
            f"the model's 2048 would need {2 * slots / 2**30:.1f}) + arena "
            f"{plan.prefix_blocks} x {plan.prefix_block_len}-token blocks "
            f"~{arena / 2**30:.1f} GiB (--prefix-blocks "
            f"{plan.prefix_blocks}; the default 2*B*seq/block_len = "
            f"{2 * plan.serve_batch * plan.max_seq_len // plan.prefix_block_len}"
            f" blocks would be {2 * slots / 2**30:.1f} GiB) = "
            f"~{(weights + slots + arena) / 2**30:.1f} GiB of the chip's "
            "15.75 GiB")


# -- child processes ---------------------------------------------------------


def _child_env(plan: Plan) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(plan.child_env)
    return env


def _kill_group(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)


def _tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return "<no log>"


def run_child(plan: Plan, name: str, payload: dict, timeout: float,
              env: dict | None = None) -> dict:
    """Run one library-surface child (`child_main` below) to completion;
    returns the JSON object it printed last. Its process group dies at
    the time limit."""
    log = os.path.join(plan.workdir, f"{name}.log")
    code = "import chip_smoke; chip_smoke.child_main()"
    with open(log, "wb") as lf:
        proc = subprocess.Popen(
            [sys.executable, "-c", code, name, json.dumps(payload)],
            cwd=REPO, env=env or _child_env(plan), stdout=lf, stderr=lf,
            start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            raise SmokeFailure(f"{name}: no result within {timeout:.0f}s\n"
                               + _tail(log))
    check(rc == 0, f"{name}: child exited {rc}\n{_tail(log)}")
    for line in reversed(_tail(log, 1 << 16).splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure(f"{name}: child printed no result\n{_tail(log)}")


def child_main() -> None:
    """Entry of every library-surface child: `python -c "import
    chip_smoke; chip_smoke.child_main()" <name> <json payload>`."""
    name, payload = sys.argv[1], json.loads(sys.argv[2])
    out = {"synth": _child_synth, "parity": _child_parity,
           "tp_logits": _child_tp_logits}[name](payload)
    print(json.dumps(out), flush=True)


def _child_synth(p: dict) -> dict:
    """A random-but-valid Q40 `.m` streamed in plan order (the 7B file is
    ~4.2 GB; one tensor resident at a time) and a byte-fallback `.t`."""
    from distributed_llama_tpu.io.tokenizer_file import (
        TokenizerData, write_tokenizer_file)
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType
    from distributed_llama_tpu.testing import (byte_fallback_vocab,
                                               write_synthetic_model)

    s = dict(p["spec"])
    s["arch"] = ArchType[s["arch"]]
    s["hidden_act"] = HiddenAct[s["hidden_act"]]
    spec = ModelSpec(weights_float_type=FloatType.Q40, **s)
    t0 = time.time()
    size = write_synthetic_model(p["model"], spec, p["seed"])
    write_tokenizer_file(p["tokenizer"], TokenizerData(
        vocab=byte_fallback_vocab(spec.vocab_size),
        scores=[0.0] * spec.vocab_size, bos_id=1, eos_id=2))
    return {"bytes": size, "seconds": round(time.time() - t0, 1)}


def _cli_args(p: dict, extra: list[str]):
    from distributed_llama_tpu.apps.dllama import build_argparser

    return build_argparser().parse_args(
        ["inference", "--model", p["model"], "--tokenizer", p["tokenizer"],
         "--max-seq-len", str(p["max_seq_len"]), "--seed", str(p["seed"]),
         "--temperature", "0"] + extra)


def _device_block() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _child_parity(p: dict) -> dict:
    """Kernels-on engine vs the plain XLA (use_pallas=False) engine over
    the SAME loaded params, built by the CLI's own build_engine: logits of
    one prefill and of a few decode steps. The reference's argmax feeds
    both engines each step, so they always see identical inputs."""
    import numpy as np

    from distributed_llama_tpu.apps.dllama import build_engine
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.utils.compile_cache import \
        ensure_compile_cache

    ensure_compile_cache()
    built, _tok, _ = build_engine(_cli_args(p, []))

    def twin(use_pallas: bool) -> Engine:
        return Engine(built.spec, built.params, max_seq_len=built.seq_len,
                      compute_dtype=built.compute_dtype,
                      cache_dtype=built.cache_dtype,
                      activation_q80=built.activation_q80,
                      use_pallas=use_pallas,
                      pallas_interpret=p["interpret"] and use_pallas)

    if p["interpret"]:      # CPU rehearsal: kernels only run interpreted
        kern = twin(True)
    else:                   # the chip: the engine the CLI built IS kernels-on
        assert built.use_pallas, "CLI engine came up without the kernels"
        kern = built
    ref = twin(False)
    rng = np.random.default_rng(p["seed"] + 1)
    prompt = rng.integers(3, built.spec.vocab_size,
                          (1, p["prompt_len"])).astype(np.int32)
    rows = []
    tok, pos = prompt, 0
    for step in range(1 + p["decode_steps"]):
        lk = np.asarray(kern.fetch_logits(kern.step(tok, pos)), np.float32)
        lr = np.asarray(ref.fetch_logits(ref.step(tok, pos)), np.float32)
        assert lk.shape == lr.shape == (1, built.spec.vocab_size), lk.shape
        rows.append({
            "step": "prefill" if step == 0 else f"decode{step}",
            "finite": bool(np.isfinite(lk).all() and np.isfinite(lr).all()),
            "rel_l2": _rel_l2(lk, lr),
            "max_abs": float(np.abs(lk - lr).max()),
            "ref_absmax": float(np.abs(lr).max()),
            "argmax_agree": bool(lk.argmax() == lr.argmax())})
        pos += tok.shape[1]
        tok = lr.argmax(-1).astype(np.int32).reshape(1, 1)
    return {"rows": rows, "device": _device_block()}


def _child_tp_logits(p: dict) -> dict:
    """tp=N q80 engine vs a mesh-less one-chip engine in ONE process (one
    process may drive every chip of the host): first-step logits, plus the
    placement the tp engine really got — per-device live bytes and the
    vocab sharding of tok_emb/wcls."""
    import numpy as np

    from distributed_llama_tpu.apps.dllama import build_engine
    from distributed_llama_tpu.runtime.profiler import hbm_ledger
    from distributed_llama_tpu.utils.compile_cache import \
        ensure_compile_cache

    ensure_compile_cache()
    tp = p["tp"]
    eng_tp, _, _ = build_engine(_cli_args(
        p, ["--tp", str(tp), "--buffer-float-type", "q80"]))
    rng = np.random.default_rng(p["seed"] + 2)
    prompt = rng.integers(3, eng_tp.spec.vocab_size,
                          (1, p["prompt_len"])).astype(np.int32)
    l_tp = np.asarray(eng_tp.fetch_logits(eng_tp.step(prompt, 0)),
                      np.float32)
    ledger = hbm_ledger(eng_tp)

    def spec_of(leaf):
        while hasattr(leaf, "w"):
            leaf = leaf.w
        arr = getattr(leaf, "packed", leaf)
        return str(arr.sharding.spec), list(
            arr.sharding.shard_shape(arr.shape))

    placement = {k: spec_of(eng_tp.params[k]) for k in ("tok_emb", "wcls")}
    out = {"tp": tp, "shard_vocab": bool(eng_tp.shard_vocab),
           "placement": placement,
           "per_device_bytes": ledger["per_device_bytes_in_use"],
           "finite_tp": bool(np.isfinite(l_tp).all())}
    eng_1, _, _ = build_engine(_cli_args(p, []))
    l_1 = np.asarray(eng_1.fetch_logits(eng_1.step(prompt, 0)), np.float32)
    out.update(rel_l2=_rel_l2(l_tp, l_1),
               max_abs=float(np.abs(l_tp - l_1).max()),
               ref_absmax=float(np.abs(l_1).max()),
               argmax_agree=bool(l_tp.argmax() == l_1.argmax()),
               device=_device_block())
    return out


# -- the served phases -------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 120.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, raw
    finally:
        conn.close()


class Server:
    """One `dllama api` child in its own process group."""

    def __init__(self, plan: Plan, name: str, extra: list[str]):
        self.plan, self.name = plan, name
        self.port = free_port()
        self.log = os.path.join(plan.workdir, f"{name}.log")
        cmd = [sys.executable, "-m", "distributed_llama_tpu.apps.dllama",
               "api", "--model", plan.model, "--tokenizer", plan.tokenizer,
               "--host", "127.0.0.1", "--port", str(self.port),
               "--serve-batch", str(plan.serve_batch),
               "--serve-chunk", str(plan.serve_chunk),
               "--max-seq-len", str(plan.max_seq_len),
               "--freeze-compiles", "--seed", str(plan.seed),
               "--drain-timeout", "30"] + extra
        say(f"{name}: " + " ".join(cmd[1:]))
        self._lf = open(self.log, "wb")
        self.t0 = time.time()
        self.proc = subprocess.Popen(cmd, cwd=REPO, env=_child_env(plan),
                                     stdout=self._lf, stderr=self._lf,
                                     start_new_session=True)

    def wait_ready(self) -> float:
        end = self.t0 + self.plan.boot_timeout
        while time.time() < end:
            rc = self.proc.poll()
            check(rc is None, f"{self.name}: server exited {rc} during "
                              f"start-up\n{_tail(self.log)}")
            try:
                st, raw = http_json(self.port, "GET", "/readyz", timeout=5)
                if st == 200 and json.loads(raw).get("status") == "ready":
                    return time.time() - self.t0
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"{self.name}: not ready within "
                           f"{self.plan.boot_timeout:.0f}s\n{_tail(self.log)}")

    def get(self, path: str) -> dict:
        st, raw = http_json(self.port, "GET", path)
        check(st == 200, f"{self.name}: GET {path} -> {st} {raw[:300]!r}")
        return json.loads(raw)

    def metrics(self) -> str:
        st, raw = http_json(self.port, "GET", "/metrics")
        check(st == 200, f"{self.name}: GET /metrics -> {st}")
        return raw.decode()

    def stop(self) -> None:
        """SIGTERM -> graceful drain -> exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=self.plan.drain_timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name}: no exit within "
                               f"{self.plan.drain_timeout:.0f}s of SIGTERM\n"
                               + _tail(self.log))
        check(rc == 0, f"{self.name}: exit code {rc} after SIGTERM\n"
                       + _tail(self.log))

    def close(self) -> None:
        _kill_group(self.proc)
        self._lf.close()


def _text(n: int, salt: int) -> str:
    """n printable bytes (byte-fallback tokenizer: one token per byte)."""
    words = ("the chip answers ", "a packed block ", "of four bit weights ",
             "streams through ", "the matrix unit ", "row by row ")
    out = ""
    i = salt
    while len(out) < n:
        out += words[i % len(words)]
        i += 1
    return out[:n]


def complete(server: Server, prompt: str, *, max_tokens: int,
             temperature: float, seed: int) -> dict:
    st, raw = http_json(server.port, "POST", "/v1/completions",
                        {"prompt": prompt, "max_tokens": max_tokens,
                         "temperature": temperature, "seed": seed},
                        timeout=server.plan.traffic_timeout)
    check(st == 200, f"{server.name}: /v1/completions -> {st} {raw[:300]!r}")
    body = json.loads(raw)
    n = body["usage"]["completion_tokens"]
    check(1 <= n <= max_tokens,
          f"{server.name}: completion_tokens {n} outside 1..{max_tokens}")
    check(body["usage"]["prompt_tokens"] >= len(prompt),
          f"{server.name}: prompt_tokens {body['usage']['prompt_tokens']} "
          f"< {len(prompt)} prompt bytes")
    return body


def chat_stream(server: Server, content: str, *, max_tokens: int) -> int:
    """One streaming /v1/chat/completions; returns the SSE chunk count."""
    conn = http.client.HTTPConnection(
        "127.0.0.1", server.port, timeout=server.plan.traffic_timeout)
    try:
        conn.request("POST", "/v1/chat/completions", body=json.dumps({
            "messages": [{"role": "user", "content": content}],
            "max_tokens": max_tokens, "temperature": 0.0,
            "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        check(resp.status == 200, f"{server.name}: chat stream -> "
                                  f"{resp.status}")
        events = [ln[6:] for ln in resp.read().decode().splitlines()
                  if ln.startswith("data: ")]
    finally:
        conn.close()
    check(events and events[-1].strip() == "[DONE]",
          f"{server.name}: chat stream did not end with [DONE]: "
          f"{events[-2:]}")
    chunks = [json.loads(e) for e in events[:-1]]
    check(len(chunks) >= 2, f"{server.name}: empty chat stream")
    return len(chunks)


def _concurrently(jobs) -> list:
    out: list = [None] * len(jobs)

    def run(i, fn):
        try:
            out[i] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            out[i] = e

    threads = [threading.Thread(target=run, args=(i, fn))
               for i, fn in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in out:
        if isinstance(r, BaseException):
            raise r
    return out


def _check_build(server: Server, plan: Plan) -> dict:
    build = server.get("/healthz")["build"]
    say(f"{server.name}: build {json.dumps(build)}")
    check(build["backend"] == plan.want_backend,
          f"{server.name}: backend is {build['backend']!r}, not "
          f"{plan.want_backend!r}")
    return {"platform": build["backend"], "kind": build["device_kind"],
            "count": build["device_count"]}


def phase_serve(plan: Plan) -> dict:
    """The real CLI, single-supervisor tier: boot, >= 10 requests the way
    users send them, device-side records, graceful exit."""
    srv = Server(plan, "serve", [
        "--prefix-cache", "--prefix-blocks", str(plan.prefix_blocks),
        "--prefix-block-len", str(plan.prefix_block_len)])
    try:
        warm_s = srv.wait_ready()
        device = _check_build(srv, plan)
        comp0 = srv.get("/stats")["compiles"]
        say(f"serve: smoke timing: ready after {warm_s:.1f}s (load + "
            f"warm-up); {comp0['total']} executables minted in "
            f"{comp0['total_ms'] / 1e3:.1f}s; persistent cache "
            f"{comp0['persistent_cache_hits']} hits / "
            f"{comp0['persistent_cache_misses']} misses")

        chunk = plan.serve_chunk
        room = plan.max_seq_len - plan.max_tokens - 2
        # mixed lengths: under one chunk, several chunks, and two that
        # share a long prefix (sent in separate waves so the first has
        # published its blocks before the second is admitted)
        shared = _text(min(6 * chunk, room - 40), 7)
        lens = [max(chunk // 4, 2), chunk - 1, chunk + 9, 3 * chunk + 5,
                min(9 * chunk + 3, room), 2 * chunk, chunk // 2]
        wave1 = [_text(n, i) for i, n in enumerate(lens)] + [
            shared + _text(17, 3)]
        t0 = time.time()
        bodies = _concurrently([
            (lambda pr=pr, i=i: complete(
                srv, pr, max_tokens=plan.max_tokens,
                temperature=0.0 if i % 2 == 0 else 0.8, seed=plan.seed + i))
            for i, pr in enumerate(wave1)])
        wave2 = _concurrently([
            lambda: complete(srv, shared + _text(23, 5),
                             max_tokens=plan.max_tokens, temperature=0.0,
                             seed=plan.seed),
            lambda: chat_stream(srv, _text(chunk + 3, 2),
                                max_tokens=plan.max_tokens),
            lambda: complete(srv, _text(chunk, 4),
                             max_tokens=plan.max_tokens, temperature=0.8,
                             seed=plan.seed + 99)])
        n_req = len(bodies) + len(wave2)
        say(f"serve: {n_req} requests answered ({len(wave1)} concurrent "
            f"completions, prompts {min(map(len, wave1))}-"
            f"{max(map(len, wave1))} tokens, greedy and sampled; then a "
            f"shared-prefix completion, a streaming chat "
            f"({wave2[1]} SSE chunks) and a sampled completion) in "
            f"{time.time() - t0:.1f}s wall (smoke timing)")

        stats = srv.get("/stats")
        comp = stats["compiles"]
        check(comp["after_warmup"] == 0 and comp["total"] == comp0["total"],
              f"serve: compiles after warm-up: {comp['after_warmup']} "
              f"(total {comp0['total']} -> {comp['total']})")
        check(comp["frozen"], "serve: --freeze-compiles not armed")
        pc = stats["prefix_cache"]
        check(pc["hits"] > 0 and pc["tokens_saved"] > 0,
              f"serve: no prefix-cache hit: {json.dumps(pc)[:400]}")
        hbm = stats["hbm"]
        if plan.want_backend == "tpu":
            check(hbm["device_bytes_in_use"] is not None,
                  "serve: HBM ledger has no device_bytes_in_use")
            for key in ("slot_decode", f"slot_prefill:{chunk}"):
                ks = (comp["by_key"].get(key) or {}).get("kernels")
                check(ks and all(ks.get(k, 0) > 0 for k in (
                          "q40_matmul", "flash_attention", "kv_cache_write")),
                      f"serve: executable {key!r} lacks the Pallas "
                      f"kernels: {ks}")
                say(f"serve: {key}: kernels {json.dumps(ks)}")
        say("serve: hbm " + json.dumps({k: hbm[k] for k in (
            "weights_bytes", "vocab_bytes", "kv_slot_bytes",
            "prefix_arena_bytes", "device_bytes_in_use",
            "device_bytes_limit")}))
        say(f"serve: prefix cache {pc['hits']} hits, {pc['tokens_saved']} "
            "prompt tokens served from the arena")
        say("serve: smoke timings (one run, not metrics): "
            + json.dumps({k: stats.get(k) for k in (
                "ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms", "itl_p99_ms")}))
        text = srv.metrics()
        for family in ("dllama_compiles_after_warmup", "dllama_hbm_bytes",
                       "dllama_build_info"):
            check(family in text, f"serve: /metrics lacks {family}")
        srv.stop()
        say("serve: SIGTERM -> drained -> exit 0")
        return device
    finally:
        srv.close()


def phase_procs(plan: Plan) -> dict:
    """The process tier: a front door that holds no chip and one worker
    process that does. The device facts on /healthz must come from the
    worker; the worker boots the shapes `serve` compiled, so the
    persistent cache must show hits."""
    srv = Server(plan, "procs", [
        "--replica-procs", "1", "--prefix-cache",
        "--prefix-blocks", str(plan.prefix_blocks),
        "--prefix-block-len", str(plan.prefix_block_len)])
    try:
        warm_s = srv.wait_ready()
        deadline = time.time() + 30
        while True:  # the build block arrives with the first health PONG
            build = srv.get("/healthz")["build"]
            if build["backend"] != "uninitialized" or time.time() > deadline:
                break
            time.sleep(0.5)
        device = _check_build(srv, plan)
        with open(f"/proc/{srv.proc.pid}/maps") as f:
            has_tpu_lib = "libtpu" in f.read()
        say(f"procs: front door pid {srv.proc.pid} maps libtpu: "
            f"{has_tpu_lib} (it must hold no chip; its worker does)")
        check(not has_tpu_lib or plan.want_backend != "tpu",
              "procs: the front door loaded libtpu")
        _concurrently([
            lambda: complete(srv, _text(plan.serve_chunk + 5, 1),
                             max_tokens=plan.max_tokens, temperature=0.0,
                             seed=plan.seed),
            lambda: complete(srv, _text(11, 2), max_tokens=plan.max_tokens,
                             temperature=0.8, seed=plan.seed + 1)])
        stats = srv.get("/stats")
        comp = next((r["compiles"] for r in stats.get("replicas") or ()
                     if "compiles" in r), None)
        check(comp is not None,
              f"procs: no worker compile ledger on /stats: "
              f"{list(stats)[:12]}")
        say(f"procs: smoke timing: ready after {warm_s:.1f}s; worker minted "
            f"{comp['total']} executables in {comp['total_ms'] / 1e3:.1f}s; "
            f"persistent cache {comp['persistent_cache_hits']} hits / "
            f"{comp['persistent_cache_misses']} misses")
        check(comp["after_warmup"] == 0,
              f"procs: compiles after warm-up: {comp['after_warmup']}")
        check(comp["persistent_cache_hits"] > 0,
              "procs: the worker booted the shapes `serve` compiled and "
              "hit nothing in the persistent compile cache")
        srv.stop()
        say("procs: 2 requests answered; SIGTERM -> exit 0")
        return device
    finally:
        srv.close()


def phase_synth(plan: Plan) -> None:
    env = _child_env(plan)
    env["JAX_PLATFORMS"] = "cpu"  # numpy only; must never reach for a chip
    out = run_child(plan, "synth", {
        "spec": plan.spec, "seed": plan.seed, "model": plan.model,
        "tokenizer": plan.tokenizer}, plan.synth_timeout, env=env)
    s = plan.spec
    say(f"synth: {out['bytes'] / 1e9:.2f} GB Q40 .m (dim {s['dim']}, hidden "
        f"{s['hidden_dim']}, {s['n_layers']} layers, vocab "
        f"{s['vocab_size']}) + byte-fallback .t in {out['seconds']}s "
        f"under {plan.workdir}")


def _lib_payload(plan: Plan, **kw) -> dict:
    return {"model": plan.model, "tokenizer": plan.tokenizer,
            "max_seq_len": plan.max_seq_len, "seed": plan.seed, **kw}


def phase_parity(plan: Plan) -> dict:
    out = run_child(plan, "parity", _lib_payload(
        plan, interpret=plan.interpret, prompt_len=48, decode_steps=3),
        plan.child_timeout)
    for r in out["rows"]:
        say(f"parity: {r['step']}: rel_l2 {r['rel_l2']:.4f} max_abs "
            f"{r['max_abs']:.4f} (ref absmax {r['ref_absmax']:.2f}) "
            f"argmax_agree {r['argmax_agree']}")
        check(r["finite"], f"parity: non-finite logits at {r['step']}")
        check(r["rel_l2"] <= plan.logit_tol,
              f"parity: {r['step']} logits differ by rel_l2 "
              f"{r['rel_l2']:.4f} > {plan.logit_tol} (kernels vs XLA "
              "dequant path, same params)")
    say(f"parity: kernels-on vs use_pallas=False logits within rel_l2 "
        f"{plan.logit_tol} on {len(out['rows'])} steps")
    return out["device"]


def phase_tp(plan: Plan, tp: int) -> dict:
    """--chips 4: the same .m served tp=4 q80 and by a one-chip child,
    then first-step logits + placement on the library surface."""
    prompts = [_text(plan.serve_chunk + 7, 1), _text(19, 2),
               _text(3 * plan.serve_chunk, 3)]

    def served(name: str, extra: list[str]):
        srv = Server(plan, name, extra)
        try:
            warm_s = srv.wait_ready()
            device = _check_build(srv, plan)
            outs = _concurrently([
                (lambda pr=pr: complete(srv, pr, max_tokens=plan.max_tokens,
                                        temperature=0.0, seed=plan.seed))
                for pr in prompts])
            stats = srv.get("/stats")
            check(stats["compiles"]["after_warmup"] == 0,
                  f"{name}: compiles after warm-up")
            say(f"{name}: smoke timing: ready after {warm_s:.1f}s; "
                f"{len(outs)} greedy requests answered")
            srv.stop()
            return device, [o["choices"][0]["text"] for o in outs], stats
        finally:
            srv.close()

    device, texts_tp, stats_tp = served(
        f"tp{tp}", ["--tp", str(tp), "--buffer-float-type", "q80"])
    check(device["count"] == tp,
          f"tp{tp}: server saw {device['count']} devices")
    per_dev = stats_tp["hbm"]["per_device_bytes_in_use"]
    say(f"tp{tp}: served HBM ledger per-device bytes in use: {per_dev}")
    _, texts_1, _ = served("one_chip", [])
    agree = sum(a == b for a, b in zip(texts_tp, texts_1))
    say(f"tp{tp}: greedy texts equal to the one-chip server's on "
        f"{agree}/{len(prompts)} prompts (reported, not asserted: bf16 "
        "near-ties flip under the q80 exchange)")

    out = run_child(plan, "tp_logits", _lib_payload(
        plan, tp=tp, prompt_len=48), plan.child_timeout)
    say(f"tp{tp}: logits vs one chip: rel_l2 {out['rel_l2']:.4f} max_abs "
        f"{out['max_abs']:.4f} (ref absmax {out['ref_absmax']:.2f}) "
        f"argmax_agree {out['argmax_agree']}")
    say(f"tp{tp}: placement {json.dumps(out['placement'])} shard_vocab "
        f"{out['shard_vocab']}; per-device bytes {out['per_device_bytes']}")
    check(out["finite_tp"], f"tp{tp}: non-finite logits")
    check(out["rel_l2"] <= plan.tp_logit_tol,
          f"tp{tp}: logits differ from one chip by rel_l2 "
          f"{out['rel_l2']:.4f} > {plan.tp_logit_tol}")
    check(out["shard_vocab"], f"tp{tp}: vocab is not sharded")
    vocab = plan.spec["vocab_size"]
    for k, (spec_s, shard) in out["placement"].items():
        check(shard[0] == vocab // tp,
              f"tp{tp}: {k} shard holds {shard[0]} vocab rows, not "
              f"{vocab // tp} ({spec_s})")
    if plan.want_backend == "tpu":
        for name, sizes in (("served", per_dev),
                            ("library", out["per_device_bytes"])):
            check(sizes and len(sizes) == tp and all(sizes),
                  f"tp{tp}: {name} ledger lacks per-device bytes: {sizes}")
            mean = sum(sizes) / tp
            check(max(sizes) <= 1.15 * mean and min(sizes) >= 0.85 * mean,
                  f"tp{tp}: {name} per-device bytes are not ~total/{tp} "
                  f"each: {sizes}")
    return device


# -- main --------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the tp=4 path and its one-chip twin")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    plan = Plan(spec=LLAMA2_7B, workdir=workdir, seed=args.seed,
                child_env={"JAX_PLATFORMS": "tpu"})
    t0 = time.time()
    try:
        say(sizing_note(plan))
        phase_synth(plan)
        if args.chips == 4:
            device = phase_tp(plan, 4)
        else:
            device = phase_serve(plan)
            dev_parity = phase_parity(plan)
            dev_procs = phase_procs(plan)
            check(device == dev_parity == dev_procs,
                  f"phases disagree on the device: {device} {dev_parity} "
                  f"{dev_procs}")
        check(device["platform"] == "tpu" and device["count"] == args.chips,
              f"ran on {device}, wanted {args.chips} tpu chip(s)")
        from distributed_llama_tpu import native  # numpy + ctypes only

        say("tokenizer/sampler implementation: "
            + ("native (" + native.LIB_PATH + ")" if native.available()
               else "pure Python (native/libdllama_native.so not built)"))
        assert "jax" not in sys.modules, "the parent must never import jax"
        say(f"all phases passed in {time.time() - t0:.0f}s (smoke timing)")
    except SmokeFailure as e:
        say(f"FAILED after {time.time() - t0:.0f}s: {e}")
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
