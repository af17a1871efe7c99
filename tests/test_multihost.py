"""Multi-host cluster tests: a REAL two-process jax.distributed run.

The reference's only multi-node testing was a manual screen-session script
(ref: examples/n-workers.sh; SURVEY.md §4 notes the gap). Here the root +
worker protocol (parallel/multihost.py, apps/dllama.py cmd_worker) runs as
two actual OS processes, 1 virtual CPU device each, forming one global
2-device tp mesh over the jax.distributed coordinator — and the cluster's
greedy transcript must equal a single-process run of the same model.
"""

import os
import subprocess
import sys

import pytest

from distributed_llama_tpu.testing import write_fixture

# compile-heavy SPMD meshes / subprocess clusters: the slow tier (pytest.ini)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# pins the CPU platform before any backend init and runs the real CLI main
WRAPPER = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
           "import sys; from distributed_llama_tpu.apps.dllama import main; "
           "main(sys.argv[1:])")


def _fixture(tmp_path):
    return write_fixture(tmp_path, seed=77)


def _run(cli_args, n_local_devices=1, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_local_devices}")
    env.pop("JAX_PLATFORMS", None)  # the wrapper pins cpu via jax.config
    return subprocess.Popen(
        [sys.executable, "-c", WRAPPER, *cli_args],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True), timeout


def _free_port() -> int:
    from distributed_llama_tpu.testing import free_port

    return free_port()


def _gen_line(out: str) -> str:
    """The generated-text line: last non-empty stdout line."""
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert lines, out
    return lines[-1]


def test_two_process_cluster_matches_single(tmp_path):
    mpath, tpath = _fixture(tmp_path)
    base = ["--model", mpath, "--tokenizer", tpath, "--prompt", "ab",
            "--steps", "6", "--seed", "7", "--temperature", "0",
            "--buffer-float-type", "f32"]

    # single-process reference transcript (1 virtual device, no mesh)
    p, t = _run(["generate", *base])
    out_single, err = p.communicate(timeout=t)
    assert p.returncode == 0, err

    # two-process cluster: rank 0 root (generate) + rank 1 worker, 1 device
    # each -> a global 2-device tp mesh over the coordinator
    port = _free_port()
    cluster = ["--nnodes", "2", "--coordinator", f"127.0.0.1:{port}"]
    root, t = _run(["generate", *base, *cluster, "--node-rank", "0"])
    worker, _ = _run(["worker", "--model", mpath, "--tokenizer", tpath,
                      "--temperature", "0", "--buffer-float-type", "f32",
                      *cluster, "--node-rank", "1"])
    out_root, err_root = root.communicate(timeout=t)
    out_worker, err_worker = worker.communicate(timeout=t)
    assert root.returncode == 0, (out_root, err_root)
    assert worker.returncode == 0, (out_worker, err_worker)

    assert _gen_line(out_root) == _gen_line(out_single), (
        out_root, out_single)
    assert "worker rank 1 of 2 ready" in out_worker
    assert "root shut down" in out_worker


def test_two_process_cluster_push_weights_fileless_worker(tmp_path):
    """Root-push weight distribution (VERDICT r4 #8): the worker starts
    with NO model file — rank 0 broadcasts the spec + every tensor's raw
    bytes (parallel/multihost.bcast_spec / bcast_model_tensors, the
    reference's per-worker TCP weight push, transformer.cpp:562-591) and
    the cluster transcript must still equal the single-process run."""
    mpath, tpath = _fixture(tmp_path)
    base = ["--model", mpath, "--tokenizer", tpath, "--prompt", "ab",
            "--steps", "6", "--seed", "7", "--temperature", "0",
            "--buffer-float-type", "f32"]

    p, t = _run(["generate", *base])
    out_single, err = p.communicate(timeout=t)
    assert p.returncode == 0, err

    port = _free_port()
    cluster = ["--nnodes", "2", "--coordinator", f"127.0.0.1:{port}",
               "--push-weights"]
    root, t = _run(["generate", *base, *cluster, "--node-rank", "0"])
    # the worker gets NO --model flag at all — spec and weights arrive
    # over the broadcast protocol
    worker, _ = _run(["worker", "--tokenizer", tpath,
                      "--temperature", "0", "--buffer-float-type", "f32",
                      *cluster, "--node-rank", "1"])
    out_root, err_root = root.communicate(timeout=t)
    out_worker, err_worker = worker.communicate(timeout=t)
    assert root.returncode == 0, (out_root, err_root)
    assert worker.returncode == 0, (out_worker, err_worker)
    assert _gen_line(out_root) == _gen_line(out_single), (
        out_root, out_single)
    assert "<pushed>" in out_worker  # the worker really had no file
    assert "root shut down" in out_worker


def test_two_process_cluster_lookup_decode(tmp_path):
    """--lookup-decode over a 2-process cluster: drafts are mined from the
    replicated token stream, so both processes compute the same verify
    widths in lock-step and the transcript matches the single-process
    speculative run (the worker replays via the MSG_RUN lookup field)."""
    mpath, tpath = _fixture(tmp_path)
    base = ["--model", mpath, "--tokenizer", tpath, "--prompt", "abab",
            "--steps", "8", "--seed", "7", "--temperature", "0",
            "--buffer-float-type", "f32", "--lookup-decode", "5"]

    p, t = _run(["generate", *base])
    out_single, err = p.communicate(timeout=t)
    assert p.returncode == 0, err

    port = _free_port()
    cluster = ["--nnodes", "2", "--coordinator", f"127.0.0.1:{port}"]
    root, t = _run(["generate", *base, *cluster, "--node-rank", "0"])
    # --lookup-decode is part of the cluster config fingerprint (API mode
    # needs flag parity), so the worker passes it too; the RUN header's
    # draft length is still what the replay uses
    worker, _ = _run(["worker", "--model", mpath, "--tokenizer", tpath,
                      "--temperature", "0", "--buffer-float-type", "f32",
                      "--lookup-decode", "5",
                      *cluster, "--node-rank", "1"])
    out_root, err_root = root.communicate(timeout=t)
    out_worker, err_worker = worker.communicate(timeout=t)
    assert root.returncode == 0, (out_root, err_root)
    assert worker.returncode == 0, (out_worker, err_worker)
    assert _gen_line(out_root) == _gen_line(out_single), (
        out_root, out_single)


def _post_completion(port: int, body: dict, deadline: float = 240.0) -> dict:
    """POST /v1/chat/completions, retrying until the server accepts."""
    import http.client
    import json
    import time

    t0 = time.monotonic()
    last = None
    while time.monotonic() - t0 < deadline:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            conn.request("POST", "/v1/chat/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = json.loads(resp.read())
            conn.close()
            return data
        except (ConnectionRefusedError, OSError) as e:
            last = e
            time.sleep(1.0)
    raise TimeoutError(f"server never came up: {last}")


def _stop(proc) -> tuple[str, str]:
    """Terminate a server/worker subprocess, escalating to SIGKILL (the api
    root blocks in serve_forever; workers may be blocked in a collective).
    Drains and returns (stdout, stderr) so failures carry diagnostics and
    the pipes can't fill up or leak."""
    proc.terminate()
    try:
        return proc.communicate(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        return proc.communicate(timeout=10)


@pytest.mark.parametrize("lookup", [0, 5])
def test_two_process_cluster_api_mode(tmp_path, lookup):
    """api mode over a 2-process cluster: the worker replays each request
    from its broadcast JSON body; the completion must equal the
    single-process server's. lookup=5 exercises speculative replay — both
    processes must carry the same --lookup-decode (it is in the cluster
    config fingerprint) and mine identical drafts from the replayed
    request, keeping the verify widths in lock-step."""
    mpath, tpath = _fixture(tmp_path)
    body = {"messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 5, "temperature": 0}
    lk = ["--lookup-decode", str(lookup)] if lookup else []

    def run_api(extra, http_port):
        # f32 buffers: default q80 would give the tp=2 cluster lossy
        # quantized reduces vs the single run's exact ones (same pinning as
        # test_two_process_cluster_matches_single)
        return _run(["api", "--model", mpath, "--tokenizer", tpath,
                     "--temperature", "0", "--seed", "11",
                     "--buffer-float-type", "f32", *lk,
                     "--port", str(http_port), "--host", "127.0.0.1", *extra])

    # single-process reference completion
    port1 = _free_port()
    single, _ = run_api([], port1)
    try:
        want = _post_completion(port1, body)
    finally:
        _, err = _stop(single)
        print("single server stderr:", err[-2000:])  # shown on failure

    # two-process cluster (root api + worker)
    port2, cport = _free_port(), _free_port()
    cluster = ["--nnodes", "2", "--coordinator", f"127.0.0.1:{cport}"]
    root, _ = run_api([*cluster, "--node-rank", "0"], port2)
    worker, _ = _run(["worker", "--model", mpath, "--tokenizer", tpath,
                      "--temperature", "0", "--seed", "11",
                      "--buffer-float-type", "f32", *lk,
                      *cluster, "--node-rank", "1"])
    try:
        got = _post_completion(port2, body)
        # same completion text and token accounting as the single server
        assert (got["choices"][0]["message"]["content"]
                == want["choices"][0]["message"]["content"]), (got, want)
        assert got["usage"] == want["usage"], (got, want)
    finally:
        # the api server runs until killed; the worker exits via coordinator
        # teardown when the root dies (or the SIGKILL escalation)
        _, r_err = _stop(root)
        _, w_err = _stop(worker)
        print("root stderr:", r_err[-2000:])    # shown on failure
        print("worker stderr:", w_err[-2000:])


def test_two_process_benchmark_completes(tmp_path):
    """ADVICE r5 HIGH regression: `inference` (--benchmark) over a
    2-process cluster must COMPLETE. The root's _print_benchmark runs
    measure_transfer_ms AND measure_prefill_transfer_ms(n_prompt) —
    real collectives over the global mesh — so the MSG_XFER_BENCH header
    now carries n_prompt and workers run the IDENTICAL sequence; before
    the fix the root's prefill microbench had no worker counterpart and
    the cluster deadlocked here (this test timed out)."""
    mpath, tpath = _fixture(tmp_path)
    base = ["--model", mpath, "--tokenizer", tpath, "--prompt", "ab",
            "--steps", "4", "--seed", "7", "--temperature", "0",
            "--buffer-float-type", "f32"]
    port = _free_port()
    cluster = ["--nnodes", "2", "--coordinator", f"127.0.0.1:{port}"]
    root, t = _run(["inference", *base, *cluster, "--node-rank", "0"])
    worker, _ = _run(["worker", "--model", mpath, "--tokenizer", tpath,
                      "--temperature", "0", "--buffer-float-type", "f32",
                      *cluster, "--node-rank", "1"])
    out_root, err_root = root.communicate(timeout=t)
    out_worker, err_worker = worker.communicate(timeout=t)
    assert root.returncode == 0, (out_root, err_root)
    assert worker.returncode == 0, (out_worker, err_worker)
    # the benchmark epilogue only prints after BOTH microbenches complete
    assert "Avg tokens / second:" in out_root, out_root
    assert "Avg transfer" in out_root, out_root
    assert "root shut down" in out_worker


def test_worker_mode_requires_cluster_flags():
    from distributed_llama_tpu.apps import dllama

    with pytest.raises(SystemExit):
        dllama.main(["worker", "--port", "9998"])
    with pytest.raises(SystemExit):  # nnodes without coordinator
        dllama.main(["generate", "--nnodes", "2"])
    with pytest.raises(SystemExit):  # non-root rank must be a worker
        dllama.main(["generate", "--nnodes", "2", "--node-rank", "1",
                     "--coordinator", "127.0.0.1:1"])
    with pytest.raises(SystemExit):  # root rank cannot be a worker
        dllama.main(["worker", "--nnodes", "2", "--node-rank", "0",
                     "--coordinator", "127.0.0.1:1"])


def test_single_process_protocol_helpers():
    """is_multihost/fetch_logits degrade to no-ops off-cluster."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.parallel.multihost import is_multihost
    from distributed_llama_tpu.parallel.mesh import make_mesh

    assert not is_multihost(None)
    assert not is_multihost(make_mesh(tp=2, devices=jax.devices()[:2]))
