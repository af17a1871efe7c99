"""Child processes: the `dllama api` server under test and the benchmark's
own helper children. Process handling copied from chip_smoke.py (PR 22:
own process group, log file, SIGTERM -> exit 0, kill on the way out).

The parent never imports JAX: a process that has touched JAX holds the chip.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

from client import http_json

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class BenchFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def child_env(**extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, HERE, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return "<no log>"


def kill_group(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)


def run_child(name: str, payload: dict, log: str, env: dict,
              timeout: float) -> dict:
    """Run `children.py <name> <payload>` to its end; returns the JSON
    object it printed last. Its process group dies at the time limit."""
    with open(log, "wb") as lf:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "children.py"), name,
             json.dumps(payload)], cwd=REPO, env=env, stdout=lf, stderr=lf,
            start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            kill_group(proc)
            raise BenchFailure(f"{name}: no result within {timeout:.0f}s\n"
                               + tail(log))
        finally:
            kill_group(proc)
    check(rc == 0, f"{name}: child exited {rc}\n{tail(log)}")
    for line in reversed(tail(log, 1 << 20).splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise BenchFailure(f"{name}: child printed no result\n{tail(log)}")


class Server:
    """One `python -m distributed_llama_tpu.apps.dllama api` child."""

    def __init__(self, model: str, tokenizer: str, flags: list[str],
                 log: str, env: dict, boot_timeout: float = 900.0):
        self.port = free_port()
        self.log, self.boot_timeout = log, boot_timeout
        cmd = [sys.executable, "-m", "distributed_llama_tpu.apps.dllama",
               "api", "--model", model, "--tokenizer", tokenizer,
               "--host", "127.0.0.1", "--port", str(self.port),
               "--freeze-compiles", "--seed", "0",
               "--drain-timeout", "30"] + list(flags)
        say("server: " + " ".join(cmd[1:]))
        self._lf = open(log, "wb")
        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=self._lf,
                                     stderr=self._lf, start_new_session=True)

    def wait_ready(self) -> float:
        end = self.t0 + self.boot_timeout
        while time.perf_counter() < end:
            rc = self.proc.poll()
            check(rc is None, f"server exited {rc} during start-up\n"
                              + tail(self.log))
            try:
                st, raw = http_json(self.port, "GET", "/readyz", timeout=5)
                if st == 200 and json.loads(raw).get("status") == "ready":
                    return time.perf_counter() - self.t0
            except (OSError, ValueError):
                pass
            time.sleep(0.25)
        raise BenchFailure(f"server not ready within {self.boot_timeout:.0f}s"
                           f"\n{tail(self.log)}")

    def get(self, path: str) -> dict:
        st, raw = http_json(self.port, "GET", path)
        check(st == 200, f"GET {path} -> {st} {raw[:300]!r}")
        return json.loads(raw)

    def post(self, path: str, body: dict | None = None,
             timeout: float = 120.0) -> dict:
        st, raw = http_json(self.port, "POST", path, body or {},
                            timeout=timeout)
        check(st == 200, f"POST {path} -> {st} {raw[:300]!r}")
        return json.loads(raw)

    def stop(self, timeout: float = 90.0) -> None:
        """SIGTERM -> graceful drain -> exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise BenchFailure(f"server: no exit within {timeout:.0f}s of "
                               f"SIGTERM\n{tail(self.log)}")
        check(rc == 0, f"server: exit code {rc} after SIGTERM\n"
                       + tail(self.log))

    def close(self) -> None:
        kill_group(self.proc)
        self._lf.close()
