"""The load generator: sends a schedule over HTTP with streaming on and
keeps one record a request. Pure standard library; never imports JAX."""

from __future__ import annotations

import http.client
import json
import threading
import time


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 120.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def stream_completion(port: int, req, temperature: float, due: float,
                      timeout: float) -> dict:
    """One streamed /v1/completions. Returns the record: `tokens_at` holds
    the host-clock instant at which each token's SSE event was read."""
    rec = {"index": req.index, "phase": req.phase, "due": due,
           "prompt_tokens": req.prompt_tokens, "max_tokens": req.max_tokens,
           "tokens_at": [], "finish": None, "ok": False, "error": None,
           "status": None}
    body = json.dumps({"prompt": req.prompt(), "max_tokens": req.max_tokens,
                       "temperature": temperature, "seed": req.seed,
                       "stream": True}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        rec["sent"] = time.perf_counter()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read()[:200].decode("utf-8", "replace")
            return rec
        done = False
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.perf_counter()
            payload = line[6:].strip()
            if payload == b"[DONE]":
                done = True
                break
            event = json.loads(payload)
            if "error" in event:
                rec["error"] = json.dumps(event["error"])[:200]
                continue
            choice = event["choices"][0]
            if choice["finish_reason"] is None:
                rec["tokens_at"].append(now)
            else:
                rec["finish"] = choice["finish_reason"]
        n = len(rec["tokens_at"])
        # well formed: the stream ended, with the tokens asked for or a
        # stop (an end-of-sequence token the sampler drew)
        rec["ok"] = bool(done and rec["error"] is None and (
            (rec["finish"] == "length" and n == req.max_tokens)
            or (rec["finish"] == "stop" and n < req.max_tokens)))
        if not rec["ok"] and rec["error"] is None:
            rec["error"] = (f"ill-formed stream: done={done} "
                            f"finish={rec['finish']} tokens={n}/"
                            f"{req.max_tokens}")
    except (OSError, ValueError, KeyError, http.client.HTTPException) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec.setdefault("sent", time.perf_counter())
        rec["ended"] = time.perf_counter()
        conn.close()
    return rec


class Load:
    """Runs one schedule against one port. `start()` returns the host-clock
    instant of the ramp's start; the window is [t0 + ramp, t0 + ramp +
    seconds). `finish()` waits for what was sent, up to the drain limit;
    what has not ended by then stays in `records` as failed."""

    def __init__(self, port: int, mix: dict, cell: dict, schedule: list,
                 seconds: float):
        self.port, self.mix, self.cell = port, mix, cell
        self.schedule, self.seconds = schedule, float(seconds)
        self.ramp = float(cell["ramp_s"])
        self.timeout = self.ramp + self.seconds + float(cell["drain_s"])
        self.records: list[dict] = []
        self.sent_log: list[str] = []   # phase of every request sent
        self._lock = threading.Lock()
        self._threads: list[threading.Thread] = []
        self.t0 = 0.0

    # one request, on its own thread; the record is kept even if it fails
    def _send(self, req, due: float) -> dict:
        with self._lock:
            self.sent_log.append(req.phase)
        rec = stream_completion(self.port, req, self.mix["temperature"], due,
                                self.timeout)
        with self._lock:
            self.records.append(rec)
        return rec

    def _open_loop(self) -> None:
        for req in self.schedule:
            due = self.t0 + req.due
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=self._send, args=(req, due),
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def _closed_client(self, mine: list) -> None:
        end = self.t0 + self.ramp + self.seconds
        i = 0
        while True:
            due = time.perf_counter()  # due the instant the client is free
            if due >= end:
                return
            req = mine[i % len(mine)]
            i += 1
            req.phase = "ramp" if due < self.t0 + self.ramp else "window"
            self._send(req, due)

    def start(self) -> float:
        self.t0 = time.perf_counter()
        if self.mix["loop"] == "open":
            self._main = [threading.Thread(target=self._open_loop,
                                           daemon=True)]
        else:
            n = int(self.mix["clients"])
            self._main = [threading.Thread(
                target=self._closed_client,
                args=([r for r in self.schedule if r.client == c],),
                daemon=True) for c in range(n)]
        for t in self._main:
            t.start()
        return self.t0

    def finish(self) -> list[dict]:
        deadline = self.t0 + self.timeout
        for t in self._main:
            t.join(max(deadline - time.perf_counter(), 0.0))
        for t in list(self._threads):
            t.join(max(deadline - time.perf_counter(), 0.0))
        with self._lock:
            return list(self.records)

    def in_flight(self) -> int:
        """Requests sent and not yet ended (the sweep reads the backlog)."""
        with self._lock:
            return len(self.sent_log) - len(self.records)
