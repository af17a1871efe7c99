"""Continuous batching: a slot-based KV scheduler over the batched Engine.

Iteration-level scheduling in the Orca style (Yu et al., OSDI '22) with the
slot-reuse KV management popularized by vLLM (Kwon et al., SOSP '23),
adapted to the fixed-shape compilation discipline of this engine: the KV
cache is ONE batch=B allocation whose rows ("slots") are leased to requests,
requests join and leave the running decode batch every step, and every
device program is one of exactly two executables —

  * ``slot_prefill_chunk_C`` — a (B, C) segment forward writing each
    prefilling row's chunk at its own offset (tail chunks pad to C, so C is
    the only prefill compilation key),
  * ``slot_decode_step``     — a (B, 1) decode step at per-row positions.

Rows not participating in a call are gated off by passing position ==
seq_len: their cache writes drop out of bounds (models/transformer's
per-row write: the in-place kv_cache_write kernel on the chip, the
drop-mode scatter without the kernels), their attention reads one cache
block (ops/pallas_attention._last_attended) and their logits are never
read; `gated_rows` counts them. This replaces the
static batch endpoint's regime — all prompts in one request, serial
prefill, every slot held until the slowest row drains — with
iteration-level admission: a finished row's slot is handed to the next
queued request IMMEDIATELY (no cache zeroing or reallocation; the new
request overwrites each position before any of its queries can attend it,
the same invariant decode overruns rely on everywhere in the engine).

Chunked-prefill interleave: each scheduler iteration runs at most ONE
prefill-chunk forward and ONE decode step, so a newly admitted prompt adds
at most one chunk's latency to in-flight requests' inter-token gap while
its own time-to-first-token stays bounded by ceil(len/C) iterations.

Per-slot sampling state is the request's own host ``Sampler`` (its
xorshift stream IS the per-slot RNG state); greedy requests therefore
yield EXACTLY the tokens of a sequential ``Engine.generate`` run
(tests/test_scheduler.py pins token-identical parity, including mid-decode
joins and early-finish slot handoffs).

Cross-request KV reuse: with a ``runtime/prefix_cache.PrefixCache``
attached, ``_admit`` looks up the longest cached token prefix, seeds the
slot's cache rows from arena blocks (``Engine.slot_seed_prefix``) and
prefills only the uncached suffix; a slot publishes its PROMPT's K/V
back into the radix tree when the prompt finishes prefilling (prefill-
written blocks only — decode-step K/V is not guaranteed bitwise-equal
to a cold prefill's, and publishing it would void the exact-parity
guarantee). The matched path stays PINNED for the slot's lifetime so
eviction can never free a block an in-flight slot came from, and the
whole tree is invalidated whenever the engine generation dies
(``_abort_all`` — the arena dies with the engine).

Thread model: ``submit()`` is thread-safe; the step loop runs either on
the ``start()`` background thread or synchronously via ``step()``
(tests). ``exclusive()`` drains all in-flight work and lends the
batched engine to a legacy whole-batch caller (apps/api_server's
/v1/batch/completions), so one process never holds two live batched
caches.
"""

from __future__ import annotations

import contextlib
import queue as _queue
import threading
import time
from collections import deque
from typing import Iterator

import numpy as np

from .faults import FAULTS
from .stats import RequestStats, ServeStats
from .trace import TRACER


class PromptTooLong(ValueError):
    """Prompt does not fit the engine's context window."""


class QueueFull(RuntimeError):
    """Admission refused: the request queue is at its configured bound.
    Overload must surface as a FAST structured rejection (HTTP 429 with
    Retry-After at the API layer), never as unbounded queue latency."""

    def __init__(self, depth: int, bound: int, retry_after: float = 1.0):
        super().__init__(f"queue full ({depth} waiting, bound {bound})")
        self.retry_after = retry_after


class SchedulerClosed(RuntimeError):
    """Submission after close(): the step loop is gone, so queueing the
    request would hang its waiter forever."""


class RequestError(RuntimeError):
    """Structured terminal failure of one request — the payload every
    error frame carries: a machine-readable ``code`` plus whether a
    client retry is expected to succeed (``retryable``). Raised out of
    ``ServeRequest.tokens()`` so stream consumers see one exception type
    with the frame attached."""

    def __init__(self, code: str, message: str, retryable: bool = True):
        super().__init__(message)
        self.code = code
        self.retryable = retryable

    def frame(self) -> dict:
        return {"code": self.code, "message": str(self),
                "retryable": self.retryable}


class ServeRequest:
    """One submitted generation request and its event stream.

    The scheduler pushes ``("token", id)`` events as the request's slot
    produces them, then exactly one terminal event: ``("done", reason)``
    with reason in {"stop", "length", "cancelled"} or ``("error", msg)``.
    ``tokens()`` iterates the stream; ``cancel()`` asks the scheduler to
    retire the request at its next iteration (the consumer-side stop for
    text-level stop sequences and client disconnects)."""

    def __init__(self, rid: int, prompt: list[int], max_tokens: int,
                 sampler, stop_ids: set[int],
                 deadline: float | None = None, trace_id: int = 0,
                 tenant: str | None = None, priority: str = "normal"):
        self.id = rid
        # multi-tenant fairness tags (runtime/fleet.py): which tenant's
        # WFQ share + token budget this request rides, and its priority
        # band — inert under the plain FIFO deque, read by WFQueue
        self.tenant = tenant
        self.priority = priority
        # flight-recorder span id (runtime/trace.py): minted ONCE per
        # client request at the front door and shared by every retry
        # attempt (and, across the process boundary, by the worker's
        # events) — 0 means untraced
        self.trace_id = trace_id
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.sampler = sampler
        self.stop_ids = stop_ids
        # absolute time.perf_counter() bound: past it the request is
        # failed with a structured "deadline" frame wherever it sits
        # (queued or mid-decode) — overload degrades to fast rejections
        self.deadline = deadline
        self.events: _queue.Queue = _queue.Queue()
        self.finished = threading.Event()
        self.finish_reason: str | None = None
        self.stats = RequestStats(n_prompt=len(prompt))
        self._cancelled = False
        self._terminal_lock = threading.Lock()
        self._terminal = False

    def _claim_terminal(self) -> bool:
        """Exactly-once guard for the terminal event: concurrent failure
        paths (a dying generation's _abort_all racing the supervisor's
        failed-during-submit fallback, close() racing a wedged step) may
        BOTH try to finish a request; only the first claim delivers the
        event and counts in the stats."""
        with self._terminal_lock:
            if self._terminal:
                return False
            self._terminal = True
            return True

    def cancel(self) -> None:
        self._cancelled = True

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline

    def tokens(self, timeout: float = 600.0) -> Iterator[int]:
        """Yield generated token ids until the terminal event. `timeout`
        bounds the wait per event so a dead scheduler thread surfaces as
        an error instead of a hung consumer. Error frames raise
        ``RequestError`` with the structured payload attached."""
        while True:
            kind, val = self.events.get(timeout=timeout)
            if kind == "token":
                yield val
            elif kind == "done":
                return
            elif isinstance(val, dict):
                raise RequestError(val.get("code", "error"),
                                   val.get("message", "scheduler error"),
                                   val.get("retryable", True))
            else:  # legacy bare-string frame
                raise RequestError("error", f"scheduler error: {val}")


def chunk_ladder(chunk: int, rungs: int = 4) -> list[int]:
    """The adaptive admission policy's FIXED chunk-width menu: descending
    halvings of the configured width, at most `rungs` entries, floor 1.
    A ladder (not a continuum) keeps the prefill compile-key set bounded
    and knowable up front — ``Scheduler.warmup()`` compiles every rung,
    so an adaptive run mints ZERO post-warmup keys and ``--freeze-
    compiles`` stays green while the width moves."""
    ladder = [int(chunk)]
    while len(ladder) < rungs and ladder[-1] > 1:
        ladder.append(max(ladder[-1] // 2, 1))
    return ladder


def chained_segments(left: int, chunk: int, most: int) -> int:
    """Segments of `chunk` tokens that a slot prefilling ALONE takes in one
    chunk program, of the `left` tokens its prompt still has, when a
    program has `most` rows to give it: what is left is cut over the
    fewest programs, EVENLY in whole segments (300 tokens at 8 x 32 are
    160 + 140, not 256 + 44: the same iterations, and no chunk heavier
    than it has to be beside the rows that decode)."""
    segs = -(-left // chunk)
    programs = -(-segs // most)
    return -(-segs // programs)


def chain_map(slot: int, k: int, batch: int) -> np.ndarray:
    """The slot map of a chunk program whose rows 0..k-1 are consecutive
    segments of `slot`: the rows left over (gated by their position) name
    the OTHER slots, one each, so that the old bytes a gated row's cache
    write puts back are never those of a tile a live row writes
    (ops/pallas_kv_write.py)."""
    return np.asarray(
        [slot] * k + [i for i in range(batch) if i != slot][:batch - k],
        np.int32)


class AdmissionPolicy:
    """SLO-aware self-tuning admission: trade per-iteration chunked-
    prefill width against decode occupancy (Orca's iteration-level knob)
    using the LIVE step timeline, entirely host-side.

    A scheduler iteration with both prefill and decode rows costs one
    (B, C) chunk forward plus one (B, 1) decode forward, and every
    decoding row's inter-token gap IS that iteration's wall time — so the
    chunk width C is the admission policy's one real lever: wide chunks
    finish prompts in few iterations (good TTFT) but stretch every
    running stream's gap (bad ITL); narrow chunks the reverse. The policy
    walks a fixed width ladder (``chunk_ladder``) one rung at a time:

      * SHRINK one rung when decoding rows saw prefill interference and
        the ITL EWMA is approaching ``slo_itl_ms`` (> shrink_frac of it);
      * WIDEN one rung when decode rows are idle (a pure-prefill
        iteration stretches nobody's gap), when the ITL EWMA sits
        comfortably under the SLO (< widen_frac), or when the TTFT EWMA
        is endangering ``slo_ttft_ms`` while ITL still has headroom.

    ``cooldown`` observed steps of hysteresis separate transitions so one
    noisy step cannot thrash the width. Pure bookkeeping — no device
    dispatch, no new jitted programs (the rung widths are all warmed) —
    so dlgrind fingerprints and the compile sentinel are untouched by
    construction. Exported as the ``admission`` /stats block and the
    ``dllama_admission_*`` /metrics family."""

    def __init__(self, chunk: int, *, slo_ttft_ms: float | None = None,
                 slo_itl_ms: float | None = None, rungs: int = 4,
                 alpha: float = 0.25, shrink_frac: float = 0.85,
                 widen_frac: float = 0.5, cooldown: int = 2):
        assert slo_ttft_ms or slo_itl_ms, "an SLO-less policy has no goal"
        self.slo_ttft_ms = slo_ttft_ms
        self.slo_itl_ms = slo_itl_ms
        self.ladder = chunk_ladder(chunk, rungs)
        self._rung = 0              # index into ladder; 0 = widest
        self.alpha = float(alpha)   # EWMA weight of the newest sample
        self.shrink_frac = float(shrink_frac)
        self.widen_frac = float(widen_frac)
        self.cooldown = int(cooldown)
        self._since_change = self.cooldown  # first decision is eligible
        self.itl_ewma_ms: float | None = None
        self.ttft_ewma_ms: float | None = None
        self.shrinks = 0
        self.widens = 0
        # the "degrade — no speculation" actuator (ROADMAP item 2's
        # overload degrade, wired here where the live ITL signal is): a
        # speculative verify forward is WIDER than a plain decode step,
        # so when the ITL EWMA endangers the SLO the policy turns
        # drafting off before (independently of) shrinking the chunk
        # ladder, and re-arms it once ITL sits comfortably under the
        # target again. Same hysteresis bands as the width walk.
        self.spec_on = True
        self.spec_disables = 0
        self.spec_enables = 0

    @property
    def spec_allowed(self) -> bool:
        """Whether the scheduler may run speculative verify steps this
        iteration (runtime/draft.py per-slot drafting consults this
        before every draft dispatch)."""
        return self.spec_on

    @property
    def width(self) -> int:
        return self.ladder[self._rung]

    def _mix(self, prev: float | None, sample: float) -> float:
        return sample if prev is None else (
            self.alpha * sample + (1.0 - self.alpha) * prev)

    def observe_ttft(self, ttft_ms: float) -> None:
        self.ttft_ewma_ms = self._mix(self.ttft_ewma_ms, float(ttft_ms))

    def observe_step(self, wall_ms: float, decode_rows: int,
                     prefill_rows: int) -> None:
        """One WORKING iteration's composition + wall ms (called by
        ``_step_body`` after the forwards ran). A step with decode rows
        is their observed inter-token gap — that, not a per-request
        after-the-fact average, is the signal that can still save the
        requests currently running."""
        if decode_rows:
            self.itl_ewma_ms = self._mix(self.itl_ewma_ms, float(wall_ms))
        # speculation actuator first: it is independent of the width
        # cooldown (turning drafting off must not wait out a recent
        # chunk transition — the verify width is the bigger lever)
        if self.slo_itl_ms and self.itl_ewma_ms is not None:
            if (self.spec_on
                    and self.itl_ewma_ms > self.shrink_frac * self.slo_itl_ms):
                self.spec_on = False
                self.spec_disables += 1
            elif (not self.spec_on
                  and self.itl_ewma_ms < self.widen_frac * self.slo_itl_ms):
                self.spec_on = True
                self.spec_enables += 1
        self._since_change += 1
        if self._since_change < self.cooldown:
            return
        itl, slo_i = self.itl_ewma_ms, self.slo_itl_ms
        ttft, slo_t = self.ttft_ewma_ms, self.slo_ttft_ms
        if (slo_i and decode_rows and prefill_rows and itl is not None
                and itl > self.shrink_frac * slo_i):
            if self._rung + 1 < len(self.ladder):
                self._rung += 1
                self.shrinks += 1
                self._since_change = 0
            return
        comfortable = (slo_i is not None and itl is not None
                       and itl < self.widen_frac * slo_i)
        ttft_pressure = (slo_t is not None and ttft is not None
                         and ttft > self.shrink_frac * slo_t
                         and (slo_i is None or itl is None
                              or itl < self.shrink_frac * slo_i))
        if ((decode_rows == 0 or comfortable or ttft_pressure)
                and self._rung > 0):
            self._rung -= 1
            self.widens += 1
            self._since_change = 0

    def summary(self) -> dict:
        rnd = lambda v: None if v is None else round(v, 3)  # noqa: E731
        return {
            "slo_ttft_ms": self.slo_ttft_ms,
            "slo_itl_ms": self.slo_itl_ms,
            "chunk_width": self.width,
            "chunk_ladder": list(self.ladder),
            "itl_ewma_ms": rnd(self.itl_ewma_ms),
            "ttft_ewma_ms": rnd(self.ttft_ewma_ms),
            "shrinks": self.shrinks,
            "widens": self.widens,
            "spec_allowed": self.spec_on,
            "spec_disables": self.spec_disables,
            "spec_enables": self.spec_enables,
        }


class _Slot:
    """One row of the batched KV cache. state is derived: FREE when req is
    None, PREFILL while off < len(prompt), DECODE after. `pos` is the next
    cache write position, `last` the token to feed next step. `pins` is
    the prefix-cache path the slot was seeded from (held until the slot
    releases so eviction can't free its source blocks). With per-slot
    drafting armed (runtime/draft.py): `draft_pos` is the row's draft-KV
    frontier (positions < draft_pos of the draft cache hold the true
    stream — host bookkeeping only, reset on every lease like the main
    cache's; the next lease's prefill overwrites the predecessor's draft
    K/V before the draft can attend it) and `toks` the fed-token history
    draft catch-up chunks read from (prompt + emitted tokens)."""

    __slots__ = ("idx", "req", "pos", "off", "n_out", "last", "pins",
                 "draft_pos", "toks")

    def __init__(self, idx: int):
        self.idx = idx
        self.req: ServeRequest | None = None
        self.pos = 0
        self.off = 0
        self.n_out = 0
        self.last = 0
        self.pins: tuple = ()
        self.draft_pos = 0
        self.toks: list[int] = []


class Scheduler:
    def __init__(self, engine, *, chunk: int | None = None,
                 max_queue: int = 0, queue_timeout: float | None = None,
                 request_deadline: float | None = None,
                 prefix_cache=None, fault_key: str | None = None,
                 slo_ttft_ms: float | None = None,
                 slo_itl_ms: float | None = None,
                 draft_factory=None, draft_len: int = 0,
                 draft_vocab: int | None = None,
                 sample_vocab: int | None = None,
                 fair_queue=None):
        self.engine = engine
        # identifies THIS scheduler at the replica-level fault sites
        # (runtime/faults.py replica_raise/replica_stall): the router
        # names replica i's scheduler "r{i}" so chaos tests can kill one
        # replica deterministically while its siblings keep serving
        self.fault_key = fault_key
        self.chunk = int(chunk or min(engine.prefill_chunk, engine.seq_len))
        assert 1 <= self.chunk <= engine.seq_len, self.chunk
        # SLO-aware self-tuning admission (either SLO flag arms it): the
        # policy walks the chunk-width ladder per iteration off the live
        # step timeline; `chunk` stays the WIDEST rung (and the only
        # width when no SLO is set)
        self.admission = (AdmissionPolicy(self.chunk,
                                          slo_ttft_ms=slo_ttft_ms,
                                          slo_itl_ms=slo_itl_ms)
                          if (slo_ttft_ms or slo_itl_ms) else None)
        self.slots = [_Slot(i) for i in range(engine.batch)]
        # radix prefix cache (runtime/prefix_cache.PrefixCache) — must be
        # built over THIS engine's arena; a supervisor rebuild passes a
        # fresh one (the arena dies with the engine). None = reuse off.
        self.prefix_cache = prefix_cache
        assert prefix_cache is None or prefix_cache.engine is engine, (
            "prefix cache arena belongs to a different engine")
        # admission control: max_queue bounds the waiting line (0 = no
        # bound — the supervisor/API layer sets one); queue_timeout bounds
        # how long a request may WAIT before it must be failed rather than
        # started; request_deadline is the default per-request end-to-end
        # budget applied at submit when the caller gives none
        self.max_queue = int(max_queue)
        self.queue_timeout = queue_timeout
        self.request_deadline = request_deadline
        # per-slot REAL-draft speculation (runtime/draft.py): the factory
        # builds a DraftModel over THIS scheduler's engine (a supervisor
        # rebuild passes a fresh engine — the draft's params are views of
        # its buffers and must die with it). One batched draft KV cache
        # serves every slot; per-slot frontiers live on the slots.
        from .stats import SpecStats

        if draft_factory:
            engine.spec.refuse("speculation")
        self.draft = draft_factory(engine) if draft_factory else None
        self.draft_len = int(draft_len) if self.draft is not None else 0
        assert self.draft is None or self.draft_len >= 1, \
            "a draft without a draft length proposes nothing"
        # device-argmax vocab for greedy verify: the TOKENIZER's vocab
        # (the host Sampler truncates there — sampler.py:69). Requests
        # whose sampler vocab differs simply never speculate.
        self.draft_vocab = int(draft_vocab or engine.spec.vocab_size)
        # sharded-sampling vocab (vocab-sharded engines,
        # ops/sharded_vocab.py): the TOKENIZER vocab the warmed
        # sample-prep executable truncates at — one compile key, warmed
        # below; requests whose sampler vocab differs take the warmed
        # per-row parity fallback instead of minting keys
        self.sample_vocab = int(sample_vocab or draft_vocab
                                or engine.spec.vocab_size)
        self.draft_cache = (self.draft.new_cache()
                            if self.draft is not None else None)
        self._spec_stats = SpecStats(
            mode=(self.draft.label if self.draft is not None else "off"),
            draft_len=self.draft_len)
        # deque.append/popleft are atomic under the GIL, so submit() never
        # touches the step mutex: a submitter must not wait out an
        # in-flight forward (measured: mutex-taking submits stalled a
        # 2.8 s arrival trace to 8.5 s behind back-to-back steps — lock
        # handoff is not FIFO)
        # fair_queue (runtime/fleet.WFQueue) duck-types this exact deque
        # slice — append/popleft/len/bool — swapping FIFO admission for
        # weighted-fair when tenant budgets are armed; its own internal
        # lock is tiny and never held across a forward, preserving the
        # cheap-submit constraint above
        self._queue = (fair_queue if fair_queue is not None
                       else deque())  # dlrace: guarded-by(self._mutex)
        # fleet overload ladder actuator (runtime/fleet.ShedLadder rung
        # "no_spec"): ORs with the admission policy's own spec gate —
        # either may turn drafting off, both must agree to turn it on.
        # Bool store/read is atomic under the GIL; written by the fleet
        # controller thread, read by the stepping thread.
        self.spec_degraded = False
        self._mutex = threading.RLock()  # step()/exclusive() mutual excl.
        self._wake = threading.Event()
        self.stats = ServeStats()
        itemsize = np.dtype(engine.cache_dtype).itemsize
        self.stats.cache_bytes_per_token = (
            engine.spec.cache_values_per_token * itemsize)
        self.stats.state_bytes_per_slot = (
            engine.spec.state_bytes_per_slot(itemsize))
        if prefix_cache is not None:
            self.stats.prefix = prefix_cache.stats
        self.stats.admission = self.admission  # None when no SLO is set
        self.stats.spec = self._spec_stats  # always attached (mode "off"
        # when no draft: a tier must not lose the family to a launch flag)
        self._thread: threading.Thread | None = None
        self._stop = False
        self._closed = False  # dlrace: guarded-by(self._mutex)
        # watchdog heartbeat: perf_counter when the CURRENT step body
        # entered, None while idle/between steps. Written only by the
        # stepping thread; read lock-free by the supervisor's watchdog
        # (a float store is atomic under the GIL) — a mutex-holding
        # borrow (exclusive()) therefore never looks like a stall.
        self._step_t0: float | None = None  # dlrace: guarded-by(self._mutex)
        # the iteration's open `sched.step` span (runtime/trace.py), None
        # unless --trace is on or a device capture is running; and the
        # seconds this iteration has spent blocked in a device fetch.
        # Both belong to the stepping thread alone.
        self._span = None
        self._step_wait = 0.0
        self._rid = 0  # dlrace: guarded-by(self._rid_lock)
        self._rid_lock = threading.Lock()

    # -- submission --------------------------------------------------------

    def submit(self, prompt: list[int], max_tokens: int, sampler,
               eos_id: int | set[int] | None = None,
               deadline: float | None = None,
               trace_id: int | None = None,
               tenant: str | None = None,
               priority: str = "normal") -> ServeRequest:
        """Enqueue a request; it joins the running batch as soon as a slot
        frees. `sampler` is PER REQUEST (its RNG stream is the slot's
        sampling state — concurrent requests never share coins).
        max_tokens <= 0 prefills and emits nothing (Engine.generate's
        hard-cap contract). Raises PromptTooLong before queueing when the
        prompt cannot fit the context, QueueFull when the waiting line is
        at max_queue, SchedulerClosed after close(). `deadline` is an
        absolute perf_counter bound (default: now + request_deadline when
        configured)."""
        if self._closed:
            raise SchedulerClosed("scheduler is closed")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.engine.seq_len:
            raise PromptTooLong(
                f"prompt is {len(prompt)} tokens; context is "
                f"{self.engine.seq_len}")
        if self.max_queue and len(self._queue) >= self.max_queue:
            with self._rid_lock:
                self.stats.requests_rejected += 1
            raise QueueFull(len(self._queue), self.max_queue)
        stop_ids = ({eos_id} if isinstance(eos_id, int)
                    else set(eos_id or ()))
        now = time.perf_counter()
        if deadline is None and self.request_deadline is not None:
            deadline = now + self.request_deadline
        with self._rid_lock:
            self._rid += 1
            rid = self._rid
        if trace_id is None:
            # single-supervisor tier: the scheduler door IS the front
            # door, so it mints the span id (the router mints earlier so
            # retries share one id and passes it through here)
            trace_id = TRACER.new_id() if TRACER.enabled else 0
        req = ServeRequest(rid, prompt, max_tokens, sampler, stop_ids,
                           deadline=deadline, trace_id=trace_id,
                           tenant=tenant, priority=priority)
        req.stats.t_submit = now
        if TRACER.enabled:
            TRACER.event("enqueue", trace_id, rid=rid,
                         n_prompt=len(prompt), max_tokens=max_tokens,
                         key=self.fault_key)
        with self._rid_lock:
            self.stats.requests_submitted += 1
        self.stats.requests.append(req.stats)  # deque.append: atomic
        self._queue.append(req)
        self._wake.set()
        if self._closed:
            # close() ran between the entry check and the append: its
            # _abort_all may already have drained the queue, so this
            # request would hang its waiter forever — fail it here
            # (idempotent: if the abort DID see it, the claim loses)
            self._fail_req(req, {"code": "shutdown",
                                 "message": "scheduler shutdown",
                                 "retryable": False})
        return req

    # -- the scheduling iteration -----------------------------------------

    def step(self) -> bool:
        """One scheduling iteration: admit queued requests into free slots,
        run one chunked-prefill forward for prefilling rows, one decode
        step for decoding rows. Returns False when there was no work.
        Synchronous entry point (tests drive it directly; the
        background thread calls the same body)."""
        with self._mutex:
            return self._step_locked()

    def _step_locked(self) -> bool:
        self._step_t0 = time.perf_counter()  # watchdog heartbeat: in-step
        self._step_wait = 0.0
        try:
            return self._step_body()
        finally:
            self._step_t0 = None
            if self._span is not None:  # a step that raised mid-phase
                TRACER.end(self._span)
                self._span = None

    def idle_wait(self, timeout: float = 0.05) -> None:
        """Block until a submit wakes the loop or `timeout` passes: the
        wait of BOTH step loops (``_run`` here, the supervisor's
        ``_loop``) when an iteration found no work — the `sched.idle_wait`
        span, so an idle device reads as idle and not as host work."""
        sp = TRACER.span("sched.idle_wait") if TRACER.spans else None
        self._wake.wait(timeout=timeout)
        if sp is not None:
            TRACER.end(sp)

    def _step_body(self) -> bool:
        if not self._queue and all(s.req is None for s in self.slots):
            # idle iteration: nothing to do AND no fault site fires — an
            # armed fault must land on a WORKING step (a crash on an idle
            # loop is meaningless, and another scheduler's idle loop in
            # the same process must never consume a globally-armed fault
            # out from under the one being tested)
            return False
        # spans (runtime/trace.py): `sched.step` over the working
        # iteration, its phases as children one after another. One
        # attribute read unless --trace is on or a capture is running.
        sp = None
        if TRACER.spans:
            sp = self._span = TRACER.span("sched.step")
            TRACER.phase(sp, "sched.admit")
        # named fault sites (runtime/faults.py): no-ops unless armed; fired
        # BEFORE any device dispatch so injection never alters a jitted
        # program (the dlgrind fingerprints are injection-invariant)
        FAULTS.fire("step_raise")
        FAULTS.fire("step_stall")
        FAULTS.fire("slow_step")
        # replica-level sites: key-filtered, so an armed key=rK spec only
        # counts/fires on replica K's working steps (other schedulers —
        # including fault_key=None ones — pass through untouched)
        FAULTS.fire("replica_raise", key=self.fault_key)
        FAULTS.fire("replica_stall", key=self.fault_key)
        now = time.perf_counter()
        # reap cancellations and expired deadlines FIRST so a disconnected
        # client's request never burns another forward — in particular a
        # long prompt must not prefill its remaining chunks into a dead
        # slot — and an over-deadline request fails NOW, not after its
        # budget drains
        for s in self.slots:
            if s.req is None:
                continue
            if s.req._cancelled:
                self._finish_slot(s, "cancelled")
            elif s.req.expired(now):
                req, s.req = s.req, None
                self._release_slot_cache(s, req)
                self._expire_req(req)
        self._admit()
        pre = [s for s in self.slots
               if s.req is not None and s.off < len(s.req.prompt)]
        dec = [s for s in self.slots
               if s.req is not None and s.off >= len(s.req.prompt)]
        if not pre and not dec:
            return False
        self.stats.steps += 1
        self.stats.occupancy.append(len(pre) + len(dec))
        self.stats.queue_depth.append(len(self._queue))
        # per-iteration chunk width: the SLO-aware policy's current rung
        # (a warmed compile key — see AdmissionPolicy/chunk_ladder), or
        # the one configured width when no SLO is set
        cw = (self.admission.width if self.admission is not None
              else self.chunk) if pre else 0
        segments = self._prefill_chunk(pre, cw) if pre else 0
        # per-slot drafting (runtime/draft.py): the admission policy's
        # "degrade — no speculation" actuator gates every draft dispatch
        # — when the live ITL EWMA endangers the SLO, the scheduler
        # falls back to plain (B, 1) decode steps until it recovers
        spec_ok = (self.draft is not None
                   and not self.spec_degraded
                   and (self.admission is None
                        or self.admission.spec_allowed))
        if self.draft is not None and dec and not spec_ok:
            self._spec_stats.degraded_steps += 1
        if spec_ok:
            # one draft catch-up chunk per iteration: rows whose draft
            # frontier trails the target (fresh admissions, prefix-cache
            # seeded prompts the draft must prefill itself, k == 0
            # rounds) advance up to one chunk — d/L of a target chunk
            self._draft_catchup_chunk()
        if dec:
            # rows that finished their prompt inside _prefill_chunk above
            # wait for the NEXT iteration: every live row gets at most one
            # decode forward per iteration (bounded ITL under admission)
            if spec_ok and any(self._spec_capable(s) for s in dec):
                self._decode_spec(dec)
            else:
                self._decode(dec)
        if sp is not None:
            TRACER.end(sp)
            self._span = None
        # wall from the watchdog heartbeat t0: one clock, one read, for
        # the window counters, the step timeline and the policy alike
        wall_ms = (time.perf_counter() - self._step_t0) * 1e3
        wait_ms = self._step_wait * 1e3
        st = self.stats
        st.busy_ms += wall_ms
        st.wait_ms += wait_ms
        st.host_ms += wall_ms - wait_ms
        if TRACER.enabled:
            # step timeline: batch composition + wall ms, the raw
            # measurement behind /metrics' dllama_step_ms (ROADMAP item
            # 1's knee search), with the iteration's number, start and
            # ms per phase
            TRACER.step(decode_rows=len(dec), prefill_rows=len(pre),
                        chunk=cw, queue_depth=len(self._queue),
                        wall_ms=wall_ms, key=self.fault_key, n=st.steps,
                        ts0=self._step_t0,
                        phases=sp.phases if sp is not None else None,
                        segments=segments)
        if self.admission is not None:
            # the same wall the timeline records is the policy's signal;
            # it adapts the NEXT iteration's width (never this one's)
            self.admission.observe_step(wall_ms, len(dec), len(pre))
        return True

    def _expire_req(self, req: ServeRequest, code: str = "deadline",
                    message: str = "request deadline exceeded") -> None:
        """Fail one request with a structured expiry frame."""
        if self._fail_req(req, {"code": code, "message": message,
                                "retryable": code != "deadline"}):
            self.stats.requests_expired += 1

    def _admit(self) -> None:  # dlrace: holds(self._mutex)
        now = time.perf_counter()
        free = [s for s in self.slots if s.req is None]
        while free and self._queue:
            req = self._queue.popleft()
            if req._cancelled:
                self._finish_req(req, "cancelled")
                continue
            if req.expired(now):
                self._expire_req(req)
                continue
            if (self.queue_timeout is not None
                    and now - req.stats.t_submit > self.queue_timeout):
                # queue-time budget: a request that waited too long is
                # failed at admission instead of started late — its waiter
                # gets a fast structured rejection it can retry elsewhere
                self._expire_req(req, code="queue_timeout",
                                 message="queue-time budget exceeded")
                continue
            s = free.pop(0)
            s.req = req
            s.off = 0
            s.pos = 0
            s.n_out = 0
            s.last = 0
            s.pins = ()
            # per-slot draft state resets with the lease (finish, cancel,
            # deadline, and abort all come back through here): the new
            # request's draft prefill overwrites the predecessor's draft
            # K/V before the draft can attend it — the same invariant as
            # the main cache's slot reuse
            s.draft_pos = 0
            s.toks = list(req.prompt)
            queue_ms = (now - req.stats.t_submit) * 1e3
            self.stats.admitted += 1
            self.stats.queue_wait_ms_sum += queue_ms
            if TRACER.enabled:
                TRACER.event("admit", req.trace_id, slot=s.idx,
                             queue_ms=round(queue_ms, 3),
                             key=self.fault_key)
            # slot "reset" is host-side bookkeeping ONLY — no cache zeroing
            # or reallocation. The new request's prefill/decode overwrites
            # every position before any of its queries can attend it, so
            # the predecessor's stale K/V is unreachable by construction.
            if self.prefix_cache is not None:
                # cross-request KV reuse: seed the longest cached prefix
                # (whole blocks, capped at len - 1 so the finishing chunk
                # still samples real logits) and prefill only the suffix.
                # The matched path stays pinned until the slot releases.
                n, ids, pins = self.prefix_cache.lookup_pin(req.prompt)
                if n > 0:
                    self.prefix_cache.seed_slot(s.idx, ids)
                    s.off = n
                    s.pins = pins
                if TRACER.enabled:
                    # recorded even on a miss (hit=0): a cold prefill is
                    # timeline information too
                    TRACER.event("seed", req.trace_id, hit=n,
                                 n_prompt=len(req.prompt))
                # (tokens_prefilled is counted per dispatched chunk in
                # _prefill_chunk — counting the whole suffix here would
                # overstate the denominator for requests cancelled or
                # expired mid-prefill)

    def _row_temps(self, rows: list[_Slot], at=None) -> np.ndarray:
        """Each sampling row's temperature, a traced input of whatever
        computes the row's candidates (greedy and gated rows pass 1.0).
        `at`: the program row each slot's logits stand in (its own index
        unless a chunk's rows were chained)."""
        temps = np.ones((self.engine.batch,), np.float32)
        for s, r in zip(rows, at or [s.idx for s in rows]):
            t = getattr(s.req.sampler, "temperature", 0.0)
            if t:
                temps[r] = t
        return temps

    def _sample_operands(self, rows: list[_Slot], at=None) -> dict:
        """What a slot step program is dispatched with so that it can end
        with the sampling summary of `rows` (Engine.slot_decode_step);
        nothing for a duck-typed test engine, whose logits are fetched."""
        if not hasattr(self.engine, "sample_view"):
            return {}
        return {"temps": self._row_temps(rows, at),
                "n_vocab": self.sample_vocab}

    def _sample_view(self, logits, rows: list[_Slot], at=None):
        """Wrap one forward's on-device logits for host sampling
        (Engine.sample_view): the rows are served from the tiny
        argmax/candidate summary (the step program's own, computed at the
        dispatch with _sample_operands, or a vocab-sharded engine's
        separate prep at these rows' temperatures) instead of a
        (B, vocab) fetch; logits without one (a verify step's position
        0 on one chip, a duck-typed test engine's) get the classic
        full-logits view."""
        eng = self.engine
        sv = getattr(eng, "sample_view", None)
        t0 = self._wait_begin()
        if sv is None:
            from .sampling import FullLogitsView

            view = FullLogitsView(eng.fetch_logits(logits))
        else:
            view = sv(logits, self._row_temps(rows, at), self.sample_vocab)
        self._wait_end(t0)
        view.window = self.stats
        return view

    def _wait_begin(self) -> float:
        """Before a blocking device fetch (the logits, the candidate
        summary, a verify step's argmax): opens the `sched.wait` span.
        The calls that follow are the same with spans on and off. jax
        wraps the fetch in an annotation of its own
        (`np.asarray(jax.Array)`), nested in this span: in a capture the
        wait for the program and the copy to the host both read under
        that label."""
        if self._span is not None:
            TRACER.phase(self._span, "sched.wait")
        return time.perf_counter()

    def _wait_end(self, t0: float) -> None:
        """After it: the fetch's wall is the iteration's share of
        `wait_ms`; what follows is `sched.sample_emit` until the next
        phase opens."""
        self._step_wait += time.perf_counter() - t0
        if self._span is not None:
            TRACER.phase(self._span, "sched.sample_emit")
        self._count_experts()  # every program up to this fetch has run

    def _count_experts(self) -> None:
        """Add the expert counts of the step programs that have run since
        the last call (Engine.take_expert_counts; never blocks): after
        every logits fetch, and after a mid-prompt chunk, which fetches
        nothing."""
        take = getattr(self.engine, "take_expert_counts", None)
        for program, reads, pairs, *tiles in (take() if take is not None
                                              else ()):
            # a program whose rows fit one row tile counts no tiles: a
            # group is one tile there, the tiles are the reads
            tiles = tiles[0] if tiles else reads
            if program == "decode":
                self.stats.expert_reads_decode += reads
                self.stats.expert_pairs_decode += pairs
                self.stats.expert_tiles_decode += tiles
            else:
                self.stats.expert_reads_prefill += reads
                self.stats.expert_pairs_prefill += pairs
                self.stats.expert_tiles_prefill += tiles

    def _prefill_chunk(self, rows: list[_Slot],
                       width: int | None = None) -> int:
        """One chunk program over the slots that prefill; returns its live
        rows. Several slots: row s.idx carries slot s's next segment (the
        identity map). ONE slot, on an engine whose chunk rows follow a
        slot map (Engine.prefill_rows_per_slot), at the widest rung and on
        a chunk boundary: rows 0..k-1 carry k consecutive segments of it
        (`chained_segments`), each attending what the rows before it wrote,
        and the rows left over, gated, name the other slots, so that no
        row's cache write lands on a tile of the live slot
        (ops/pallas_kv_write.py says why that matters)."""
        eng = self.engine
        sp = self._span
        if sp is not None:
            TRACER.phase(sp, "sched.dispatch.prefill")
        b, c = eng.batch, int(width or self.chunk)
        tok = np.zeros((b, c), np.int32)
        pos = np.full((b,), eng.seq_len, np.int32)  # gated rows: writes drop
        lidx = np.zeros((b,), np.int32)
        slots = None
        live = [(s, s.idx) for s in rows]           # (slot, program row)
        if len(rows) == 1 and c == self.chunk and rows[0].off % c == 0:
            s = rows[0]
            k = chained_segments(len(s.req.prompt) - s.off, c,
                                 getattr(eng, "prefill_rows_per_slot", 1))
            if k > 1:
                live = [(s, r) for r in range(k)]
                slots = chain_map(s.idx, k, b)
        finishing = []
        first = [s.off for s in rows]
        self.stats.prefill_steps += 1
        self.stats.attn_grid_steps_prefill += eng.attn_grid_steps(c)
        self.stats.prefill_rows += len(rows)
        self.stats.prefill_segments += len(live)
        self.stats.gated_rows += b - len(live)
        for s, r in live:
            n = min(c, len(s.req.prompt) - s.off)
            tok[r, :n] = s.req.prompt[s.off:s.off + n]
            self.stats.prefill_tokens += n
            self.stats.attn_pairs_prefill += n * s.off + n * (n + 1) // 2
            self.stats.prefill_cached_tokens += s.off + n
            if self.prefix_cache is not None:
                # real (non-pad) tokens this forward actually prefills —
                # the honest denominator for prefill_saved_frac
                self.prefix_cache.stats.tokens_prefilled += n
            # tail padding (token 0) writes land beyond the prompt and are
            # overwritten by decode before any later query attends them
            pos[r] = s.off
            lidx[r] = n - 1
            s.off += n
            if s.off == len(s.req.prompt):
                finishing.append((s, r))    # its LAST row's logits are read
        if TRACER.enabled:
            for s, off in zip(rows, first):
                TRACER.event("prefill", s.req.trace_id, off=off,
                             n=s.off - off, slot=s.idx,
                             step=self.stats.steps)
        done = ([s for s, _ in finishing], [r for _, r in finishing])
        # a mid-prompt chunk samples no row: it is dispatched without
        # temperatures, and the step computes no summary
        logits = eng.slot_prefill_chunk(
            tok, pos, lidx, *(() if slots is None else (slots,)),
            **(self._sample_operands(*done) if finishing else {}))
        if not finishing:
            self._count_experts()
            return len(live)  # mid-prompt chunk: no D2H fetch at all
        view = self._sample_view(logits, *done)
        for s, r in finishing:
            s.pos = len(s.req.prompt)
            if self.prefix_cache is not None:
                # publish the prompt's blocks the moment they are all
                # written — NOT at slot finish — so concurrent requests
                # sharing the prefix hit while this one still decodes
                # (blocks are immutable once published; a re-publish of
                # already-indexed blocks walks the tree and copies
                # nothing)
                if sp is not None:
                    TRACER.phase(sp, "sched.publish")
                self.prefix_cache.publish(s.idx, s.req.prompt)
                if sp is not None:
                    TRACER.phase(sp, "sched.sample_emit")
            if s.req.max_tokens <= 0:
                # hard-cap contract, same as Engine.generate: the prefill
                # ran, nothing is emitted
                self._finish_slot(s, "length")
                continue
            self._emit(s, view.sample(s.req.sampler, r))
        return len(live)

    def _decode(self, rows: list[_Slot]) -> None:
        # cancellations were reaped at the top of the iteration; a cancel
        # landing mid-step costs at most this one extra forward
        live = rows
        eng = self.engine
        if self._span is not None:
            TRACER.phase(self._span, "sched.dispatch.decode")
        self.stats.decode_steps += 1
        self.stats.attn_grid_steps_decode += eng.attn_grid_steps(1)
        self.stats.decode_rows += len(live)
        self.stats.gated_rows += eng.batch - len(live)
        tok = np.zeros((eng.batch, 1), np.int32)
        pos = np.full((eng.batch,), eng.seq_len, np.int32)
        for s in live:
            tok[s.idx, 0] = s.last
            pos[s.idx] = s.pos
            self.stats.attn_pairs_decode += s.pos + 1
        logits = eng.slot_decode_step(tok, pos,
                                      **self._sample_operands(live))
        view = self._sample_view(logits, live)
        for s in live:
            s.pos += 1
            self._emit(s, view.sample(s.req.sampler, s.idx))

    # -- per-slot real-draft speculation (runtime/draft.py) ----------------

    def _spec_capable(self, s: _Slot) -> bool:
        """Whether slot s can ride a speculative verify THIS iteration:
        greedy request (verification is the target's argmax — sampled
        rows would need per-row rejection chains, they ride the same
        verify forward's position-0 logits instead), sampler truncated
        at the scheduler's verify vocab, draft caught up to the target
        frontier, and at least 2 tokens of budget AND context headroom
        (drafting for a single remaining token buys nothing)."""
        req = s.req
        smp = req.sampler
        return (getattr(smp, "temperature", None) == 0.0
                and getattr(smp, "vocab_size", 0) == self.draft_vocab
                and s.draft_pos >= s.pos
                and req.max_tokens - s.n_out >= 2
                and self.engine.seq_len - s.pos >= 2)

    def _draft_catchup_chunk(self) -> None:
        """One batched (B, C) draft prefill chunk covering every slot
        whose draft-KV frontier trails what the target has written (the
        fed-token history is `s.toks`, capped at the written frontier —
        the final emitted token is never fed, there or here). Fixed
        width C = the configured chunk (ONE compile key however ragged
        the gaps); chunk-tail padding writes land beyond each row's
        frontier and are overwritten before the draft attends them."""
        eng, c = self.engine, self.chunk
        rows = []
        for s in self.slots:
            if s.req is None:
                continue
            smp = s.req.sampler
            if not (getattr(smp, "temperature", None) == 0.0
                    and getattr(smp, "vocab_size", 0) == self.draft_vocab):
                # a row that can never speculate (sampled request,
                # foreign vocab) gets no draft K/V — catch-up for it
                # would be a pure extra dispatch per iteration
                continue
            avail = min(len(s.toks), max(s.off, s.pos))
            if s.draft_pos < avail:
                rows.append((s, avail))
        if not rows:
            return
        if self._span is not None:
            TRACER.phase(self._span, "sched.dispatch.decode")
        tok = np.zeros((eng.batch, c), np.int32)
        pos = np.full((eng.batch,), eng.seq_len, np.int32)
        for s, avail in rows:
            n = min(c, avail - s.draft_pos)
            tok[s.idx, :n] = s.toks[s.draft_pos:s.draft_pos + n]
            pos[s.idx] = s.draft_pos
            s.draft_pos += n
        self.draft_cache = self.draft.prefill_chunk(self.draft_cache,
                                                    tok, pos)
        self._spec_stats.draft_forwards += 1

    def _decode_spec(self, rows: list[_Slot]) -> None:
        """The speculative decode iteration: ONE draft-scan dispatch
        proposes draft_len tokens per speculating row, ONE fixed-width
        verify forward confirms each row's accepted prefix + 1 — every
        row advances 1..draft_len+1 tokens per iteration at exact greedy
        parity (emission is always the TARGET's argmax; a wrong draft
        costs only its cheap forwards). Non-speculating rows (sampled,
        vocab-mismatched, draft catching up) ride the SAME verify
        forward: their segment pads with their own token and they sample
        one token from the position-0 logits — a (B, 1+K) forward costs
        ~one weight read like (B, 1), which is the whole bet."""
        from .speculative import count_accepted

        eng, k = self.engine, self.draft_len
        if self._span is not None:
            TRACER.phase(self._span, "sched.dispatch.decode")
        self.stats.decode_steps += 1
        self.stats.attn_grid_steps_decode += eng.attn_grid_steps(1 + k)
        self.stats.decode_rows += len(rows)
        self.stats.gated_rows += eng.batch - len(rows)
        spec_rows = [s for s in rows if self._spec_capable(s)]
        dtok = np.zeros((eng.batch,), np.int32)
        dpos = np.full((eng.batch,), eng.seq_len, np.int32)  # gated rows
        for s in spec_rows:
            dtok[s.idx] = s.last
            dpos[s.idx] = s.pos
        drafts_np, self.draft_cache = self.draft.propose(
            self.draft_cache, dtok, dpos, k, n_vocab=self.draft_vocab)
        self._spec_stats.draft_forwards += 1
        tok = np.zeros((eng.batch, 1 + k), np.int32)
        pos = np.full((eng.batch,), eng.seq_len, np.int32)
        drafts: dict[int, list[int]] = {}
        for s in rows:
            tok[s.idx, :] = s.last  # pad = the row's own token (its
            # writes sit beyond the accepted prefix and are overwritten
            # before any later query attends them)
            pos[s.idx] = s.pos
            self.stats.attn_pairs_decode += s.pos + 1
        for s in spec_rows:
            # the scan always proposes k (one compile key); clamp to the
            # row's budget/headroom — surplus drafts become padding
            kk = min(k, eng.seq_len - s.pos - 1,
                     s.req.max_tokens - s.n_out - 1)
            d = [int(t) for t in drafts_np[s.idx][:kk]]
            drafts[s.idx] = d
            tok[s.idx, 1:1 + len(d)] = d
            s.draft_pos = s.pos + k  # the scan wrote pos..pos+k-1
        # the verify step returns the argmax on the host: dispatch and
        # fetch in one call, so all of it counts as the wait
        t0 = self._wait_begin()
        greedy, logits0 = eng.slot_verify_step(tok, pos, self.draft_vocab)
        self._wait_end(t0)
        self._spec_stats.verify_forwards += 1
        nonspec = [s for s in rows if s.idx not in drafts]
        # position-0 sampling rides the sharded view like any decode
        # step; built only when a non-speculating row exists (an
        # all-speculating iteration pays no extra dispatch)
        view0 = self._sample_view(logits0, nonspec) if nonspec else None
        for s in rows:
            d = drafts.get(s.idx)
            if d is None:
                s.pos += 1
                self._emit(s, view0.sample(s.req.sampler, s.idx))
                continue
            req = s.req
            m = count_accepted(d, greedy[s.idx])
            emitted = [int(g) for g in greedy[s.idx][: m + 1]]
            self._spec_stats.drafted += len(d)
            self._spec_stats.accepted += m
            self._spec_stats.emitted_spec += len(emitted)
            req.stats.spec_forwards += 1
            req.stats.spec_drafted += len(d)
            req.stats.spec_accepted += m
            pos0 = s.pos
            for t in emitted:
                s.pos += 1
                self._emit(s, t)
                if s.req is None:  # stop/budget retired the slot: the
                    break          # rest of the accepts are discarded
            if s.req is not None:
                # clamp the draft frontier to the TRUE verified stream:
                # positions past the first rejection hold rejected-token
                # K/V. The next speculative scan would overwrite them
                # contiguously before attending them — but intervening
                # PLAIN rounds (SLO degrade, budget tail) advance s.pos
                # without touching the draft cache, and a later catch-up
                # starting at an inflated draft_pos would leave the
                # stale entries below the frontier, silently decaying
                # the accept rate for the rest of the stream
                # (review-found)
                s.draft_pos = min(pos0 + k, s.pos)

    def _emit(self, s: _Slot, token: int) -> None:
        """Record one sampled token and retire the slot the moment the
        request is done — the freed slot is admissible next iteration.
        Exactly Engine.generate's continue condition, negated: a stop
        token is emitted then stops the row; budget and context-edge rows
        finish as "length". The final emitted token is never fed back
        (generate() parity — no overrun forward)."""
        req = s.req
        token = int(token)
        s.n_out += 1
        s.last = token
        s.toks.append(token)  # the draft catch-up's fed-token history
        now = time.perf_counter()
        if req.stats.t_first is None:
            req.stats.t_first = now
            if self.admission is not None:
                self.admission.observe_ttft(
                    (now - req.stats.t_submit) * 1e3)
            if TRACER.enabled:
                TRACER.event("first_token", req.trace_id,
                             ttft_ms=round((now - req.stats.t_submit)
                                           * 1e3, 3),
                             step=self.stats.steps)
        elif TRACER.enabled and s.n_out % TRACER.decode_every == 0:
            # decode progress at a bounded cadence: a per-token event
            # would let one long stream flush the whole ring
            TRACER.event("decode", req.trace_id, n_out=s.n_out,
                         step=self.stats.steps)
        req.stats.n_out = s.n_out
        self.stats.tokens_out += 1
        req.events.put(("token", token))
        if token in req.stop_ids:
            self._finish_slot(s, "stop")
        elif s.n_out >= req.max_tokens or s.pos >= self.engine.seq_len:
            self._finish_slot(s, "length")

    def _release_slot_cache(self, s: _Slot, req: ServeRequest) -> None:
        """Prefix-cache bookkeeping for a slot leaving any path: release
        the seed pins, and for a slot retiring MID-PREFILL (cancel,
        deadline) publish the prompt prefix it did write (s.off only
        advances after a chunk's forward ran, so [0, off) is always real
        data). Completed prompts published at prefill-finish already.

        Only PREFILL-written blocks are ever published — never the
        decode extension (req.prompt + fed tokens): decode-step K/V is
        not guaranteed bitwise-equal to what a cold prefill of the same
        tokens would write (different executables may reduce in a
        different order under bf16), and seeding it would silently void
        the cache-on == cache-off token-parity guarantee. Multi-turn
        reuse barely loses: turn N+1's prompt embeds turn N's reply,
        hits turn N's PROMPT blocks, re-prefills just the reply + new
        message — and its own prefill-finish publish then covers the
        full turn-N+1 prompt for turn N+2. This also bounds publish
        work to once per prompt, not per retirement."""
        if self.prefix_cache is None:
            return
        if 0 < s.off < len(req.prompt):
            self.prefix_cache.publish(s.idx, req.prompt[: s.off])
        self.prefix_cache.unpin(s.pins)
        s.pins = ()

    def _finish_slot(self, s: _Slot, reason: str) -> None:
        req, s.req = s.req, None  # slot is FREE from here on
        self._release_slot_cache(s, req)
        self._finish_req(req, reason)

    def _finish_req(self, req: ServeRequest, reason: str) -> None:
        if not req._claim_terminal():
            return
        req.finish_reason = reason
        req.stats.t_done = time.perf_counter()
        self.stats.requests_finished += 1
        if TRACER.enabled:
            if req.stats.spec_forwards:
                # the request's honest accept record, on its span — what
                # dlprof needs to attribute verify-forward cost per
                # request (one event per request, not per verify)
                TRACER.event("spec", req.trace_id,
                             forwards=req.stats.spec_forwards,
                             drafted=req.stats.spec_drafted,
                             accepted=req.stats.spec_accepted,
                             key=self.fault_key)
            TRACER.event("finish", req.trace_id, reason=reason,
                         n_out=req.stats.n_out)
        req.events.put(("done", reason))
        req.finished.set()

    def warmup(self) -> None:
        """Compile the serving executables (slot_prefill_chunk_C and
        slot_decode_step) by running each once with EVERY row gated off
        (pos == seq_len: cache writes drop out of bounds, logits unread) —
        state-neutral by the same invariant the scheduler always relies
        on. The supervisor runs this on a rebuilt engine BEFORE marking it
        ready, so first-step compile time is spent while the watchdog is
        not watching; without it a stall_timeout below the compile time
        would trip on every fresh engine's first real step (an infinite
        recovery loop on TPU, where compiles run tens of seconds)."""
        eng = self.engine
        with self._mutex:
            gate = np.full((eng.batch,), eng.seq_len, np.int32)
            # with the SLO-aware policy armed, EVERY ladder rung is a
            # planned prefill width: warm them all here so an adaptive
            # run mints zero post-warmup compile keys (the sentinel —
            # and --freeze-compiles — stay green while the width moves)
            widths = (self.admission.ladder if self.admission is not None
                      else [self.chunk])
            for w in widths:
                eng.slot_prefill_chunk(np.zeros((eng.batch, w), np.int32),
                                       gate, np.zeros((eng.batch,), np.int32))
            lg = eng.slot_decode_step(
                np.zeros((eng.batch, 1), np.int32), gate,
                **self._sample_operands([]))
            # run what sampling runs against the warmed decode step's
            # logits, fallback included (the step's own summary and the
            # whole fetch behind it; a vocab-sharded engine's sample-prep
            # + per-row fallback executables) — sampled traffic then
            # mints ZERO post-warmup keys (the prefill/verify paths share
            # the same batch-shaped keys)
            warm_sample = getattr(eng, "warm_sample_ops", None)
            if warm_sample is not None:
                warm_sample(lg, self.sample_vocab)
            if self.draft is not None:
                # the draft key set is planned and bounded: one prefill
                # width, one scan shape, one verify width — compile all
                # three here (all rows gated: state-neutral by the same
                # OOB invariant) so speculative traffic mints ZERO
                # post-warmup keys and --freeze-compiles stays green
                self.draft_cache = self.draft.prefill_chunk(
                    self.draft_cache,
                    np.zeros((eng.batch, self.chunk), np.int32), gate)
                _, self.draft_cache = self.draft.propose(
                    self.draft_cache, np.zeros((eng.batch,), np.int32),
                    gate, self.draft_len, n_vocab=self.draft_vocab)
                eng.slot_verify_step(
                    np.zeros((eng.batch, 1 + self.draft_len), np.int32),
                    gate, self.draft_vocab)
            if self.prefix_cache is not None:
                # the seed/publish executables compile here too — a
                # rebuilt engine's first prefix-cache admission must not
                # read as a stall either. Unlike the gated forwards
                # above, the seed warmup REALLY writes row 0, so the
                # prose precondition (idle scheduler) is enforced: a
                # warmup over a live slot 0 would replace its prefix K/V
                # with arena bytes and silently corrupt its output
                assert all(s.req is None for s in self.slots), (
                    "prefix-cache warmup requires an idle scheduler")
                self.prefix_cache.warmup()
            # the serving set is compiled: arm the recompile sentinel —
            # from here any NEW compile key on this engine is a
            # compile_after_warmup event (and a structured refusal under
            # --freeze-compiles; runtime/profiler.py). Engine-only:
            # duck-typed test engines without the ledger pass through.
            mark = getattr(eng, "mark_compile_warm", None)
            if mark is not None:
                mark()

    # -- background thread -------------------------------------------------

    def start(self) -> None:
        with self._mutex:
            if self._thread is not None:
                return
            self._stop = False
            self._thread = threading.Thread(
                target=self._run, name="dllama-scheduler", daemon=True)
            self._thread.start()

    def _run(self) -> None:
        while not self._stop:
            # clear-before-step ordering: a submit landing after the clear
            # is either seen by this step (queue appended before set) or
            # re-arms the event so the wait below returns immediately
            self._wake.clear()
            with self._mutex:
                try:
                    did = self._step_locked()
                except Exception as e:  # fail every request, keep serving
                    self._abort_all(f"{type(e).__name__}: {e}")
                    did = False
            if not did and not self._stop:
                self.idle_wait()

    def _fail_req(self, req: ServeRequest, frame: dict) -> bool:
        """Terminal structured-error delivery for one request
        (exactly-once: concurrent failure paths both calling this deliver
        one event and count one failure). Returns whether THIS call won
        the claim."""
        if not req._claim_terminal():
            return False
        req.finish_reason = "error"
        req.stats.t_done = time.perf_counter()
        self.stats.requests_finished += 1
        self.stats.requests_failed += 1
        if TRACER.enabled:
            TRACER.event("error", req.trace_id,
                         code=frame.get("code", "error"),
                         retryable=bool(frame.get("retryable", True)),
                         n_out=req.stats.n_out, key=self.fault_key)
        req.events.put(("error", dict(frame)))
        req.finished.set()
        return True

    def _abort_all(self, msg: str, code: str = "engine_error",
                   retryable: bool = True) -> None:
        """Fail every in-flight and queued request with one structured
        frame. Called WITHOUT the mutex from close()/the supervisor when
        the step thread may be wedged inside a forward holding it — slot
        hand-off here races only against that dead/stuck thread, whose
        scheduler generation is already discarded."""
        frame = {"code": code, "message": msg, "retryable": retryable}
        if self.prefix_cache is not None:
            # the engine generation behind the arena is being discarded
            # (crash recovery, close) — recovered engines must never
            # seed from a dead engine's blocks, so the WHOLE tree goes
            # (a mere step exception on the legacy unsupervised loop
            # also lands here: conservative cache loss, never staleness)
            self.prefix_cache.invalidate()
        for s in self.slots:
            s.pins = ()  # pinned nodes were detached by the invalidate
            if s.req is not None:
                req, s.req = s.req, None
                self._fail_req(req, frame)
        while self._queue:
            try:
                self._fail_req(self._queue.popleft(), frame)
            except IndexError:  # racing submit/abort: queue drained under us
                break

    def close(self, timeout: float = 30.0) -> None:
        """Stop the loop and FAIL whatever is still queued or in flight —
        a submitter blocked in ServeRequest.tokens() must get its terminal
        frame now, not a 600 s timeout (pre-fix, close() left queued
        requests un-failed and their waiters hanging)."""
        self._closed = True  # new submits raise SchedulerClosed
        self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        # no mutex: a cleanly-joined thread is gone; a stuck one (hung
        # forward) holds the mutex forever and the waiters still need
        # their frames
        self._abort_all("scheduler shutdown", code="shutdown",
                        retryable=False)

    @contextlib.contextmanager
    def exclusive(self):
        """Lend the batched engine to a legacy whole-batch caller: blocks
        the step loop, drives every queued/in-flight request to completion
        on the caller's thread, then yields the engine. The borrower may
        reset()/step the engine freely — all slots are free while held.
        This is how the process keeps exactly ONE live batched KV cache
        (apps/api_server routes /v1/batch/completions through here)."""
        with self._mutex:
            while self._step_locked():
                pass
            yield self.engine

    # -- cross-replica KV block transfer (runtime/kv_transfer.py) ----------
    #
    # The admit-seeded-from-transfer path needs NO new admission code: a
    # fill publishes the fetched blocks into THIS scheduler's radix tree
    # before submit, and _admit's ordinary lookup_pin then seeds them —
    # so the PR-4 invariant (seeded K/V == a cold prefill's writes, greedy
    # bit-identical) carries over unchanged: the shipped bytes ARE a
    # prefill's writes, just a sibling replica's. These helpers exist so
    # the transfer engine never reaches into the step mutex directly.

    def kv_match_len(self, tokens: list[int]) -> int:
        """Lock-free peek at this scheduler's cached prefix (0 with the
        cache off) — the importer's n_have before deciding a fetch."""
        pc = self.prefix_cache
        return pc.match_len(tokens) if pc is not None else 0

    def kv_export_pin(self, tokens: list[int]):
        """Donor: pin + describe the exportable path (under the step
        mutex). Returns (n_tokens, block_ids, pins); (0, [], ()) with
        the cache off."""
        if self.prefix_cache is None:
            return 0, [], ()
        with self._mutex:
            return self.prefix_cache.export_pin(tokens)

    def kv_export_block(self, block_id: int):
        """Donor: one pinned block's host K/V pair (under the step mutex
        — see PrefixCache.export_block_host for why)."""
        with self._mutex:
            return self.prefix_cache.export_block_host(block_id)

    def kv_unpin(self, pins) -> None:
        with self._mutex:
            if self.prefix_cache is not None:
                self.prefix_cache.unpin(pins)

    def kv_import_prefix(self, tokens: list[int], start_block: int,
                         blocks: list) -> int:
        """Importer: publish fetched blocks into this scheduler's tree
        (under the step mutex). Returns tokens imported (0 = nothing
        attachable: the next admission simply re-prefills)."""
        if self.prefix_cache is None:
            return 0
        with self._mutex:
            return self.prefix_cache.import_path(tokens, start_block,
                                                 blocks)

    # -- observability -----------------------------------------------------

    def wire_estimate(self):
        """Per-emitted-token collective bytes under the measured mean
        occupancy (runtime/netstats.estimate_serve_wire): a gated slot
        still rides through every collective, so low occupancy inflates
        the per-token wire cost proportionally."""
        from .netstats import estimate_serve_wire

        occ = (sum(self.stats.occupancy) / len(self.stats.occupancy)
               if self.stats.occupancy else self.engine.batch)
        return estimate_serve_wire(
            self.engine.spec, self.engine.mesh, batch=self.engine.batch,
            occupancy=occ, q80=self.engine.q80_collectives)
