"""Engine supervision: watchdog, crash recovery, backoff, circuit breaker.

The reference engine has NO fault tolerance — a wedged or crashed node
takes the whole cluster down (SURVEY §"no fault tolerance") — and a step
that stalls inside a device call raises nothing at all, while the
scheduler's only in-loop handling was a blanket abort that failed every
request and hoped the engine was still usable. ``EngineSupervisor`` makes
the serving hot loop survive faults instead of merely reporting them:

  * it OWNS the step loop (the scheduler's ``start()`` thread is not used
    under supervision) and catches step exceptions;
  * a WATCHDOG thread reads the scheduler's in-step heartbeat
    (``Scheduler._step_t0``) and declares a stall when one step exceeds
    ``stall_timeout`` — the silent stall, which no exception will
    ever surface (the thread is wedged inside a jax call and cannot be
    interrupted; it is abandoned, its generation discarded);
  * RECOVERY aborts in-flight/queued requests with structured error
    frames (``RequestError`` payloads), rebuilds the engine + scheduler
    through ``engine_factory`` under exponential backoff, and resumes
    admitting — a CIRCUIT BREAKER keeps the supervisor unready after
    ``breaker_threshold`` consecutive failures (``reset_breaker()`` is
    the operator's manual half-open);
  * ADMISSION CONTROL: while not ready, ``submit()`` raises
    ``EngineUnready`` with a ``retry_after`` hint; the queue bound and
    per-request deadlines live in the scheduler it supervises
    (``QueueFull`` / "deadline" frames) so overload returns fast
    structured rejections instead of unbounded latency.

Generations: every (engine, scheduler) pair is one generation. Failure
invalidates the generation FIRST (a wedged step thread that eventually
wakes finds ``gen != self._gen`` and exits without touching anything),
then aborts the old generation's requests, then rebuilds. The recovery
path reuses the same two jitted entry points as steady state
(``slot_prefill_chunk``/``slot_decode_step`` — fingerprints pinned in
analysis/baseline.json), so a rebuilt engine's first step compiles the
identical programs and dlgrind's gate covers it by construction.

Docs: docs/operations.md (tuning, drain procedure, fault injection).
"""

from __future__ import annotations

import contextlib
import threading
import time

from .scheduler import Scheduler
from .stats import WINDOW_COUNTERS, SupervisorStats
from .trace import TRACER

READY = "ready"
RECOVERING = "recovering"
BROKEN = "broken"          # circuit open: stays unready until reset
DRAINING = "draining"
CLOSED = "closed"

_COUNTER_KEYS = ("requests_submitted", "requests_finished",
                 "requests_failed", "requests_expired",
                 "requests_rejected", "tokens_out", "steps",
                 *WINDOW_COUNTERS)


class EngineUnready(RuntimeError):
    """Admission refused: the engine is recovering, broken, or draining.
    ``retry_after`` is the client hint (HTTP Retry-After at the API
    layer)."""

    def __init__(self, state: str, retry_after: float):
        super().__init__(f"engine not ready (state: {state})")
        self.state = state
        self.retry_after = retry_after


class EngineSupervisor:
    """Supervised continuous-batching front door. Duck-types the
    ``Scheduler`` surface the API server uses — ``submit``, ``engine``,
    ``stats``, ``exclusive()``, ``close()`` — plus the resilience surface:
    ``ready``/``state``, ``summary()``, ``drain()``, ``reset_breaker()``.
    """

    def __init__(self, engine_factory, *, chunk: int | None = None,
                 max_queue: int = 0, queue_timeout: float | None = None,
                 request_deadline: float | None = None,
                 stall_timeout: float = 10.0, watchdog_poll: float = 0.02,
                 backoff_base: float = 0.1, backoff_max: float = 5.0,
                 breaker_threshold: int = 3,
                 prefix_blocks: int = 0, prefix_block_len: int = 32,
                 kv_transfer: bool = False,
                 fault_key: str | None = None,
                 slo_ttft_ms: float | None = None,
                 slo_itl_ms: float | None = None,
                 draft: str | None = None, draft_len: int = 0,
                 draft_vocab: int | None = None,
                 fair_queue_factory=None):
        self._factory = engine_factory
        self._chunk = chunk
        # replica identity at the key-filtered fault sites (runtime/
        # faults.py replica_raise/replica_stall) — every generation's
        # scheduler carries it, so an armed kill follows THIS replica
        # across rebuilds
        self._fault_key = fault_key
        # prefix_blocks > 0 attaches a radix prefix cache
        # (runtime/prefix_cache.py) to every generation's scheduler. The
        # cache is minted FRESH in _make_sched: its block arena holds
        # K/V only the generation's own engine wrote, so a rebuild
        # invalidates the whole tree by construction (plus the explicit
        # Scheduler._abort_all invalidate on the dying generation).
        self._prefix_blocks = int(prefix_blocks)
        self._prefix_block_len = int(prefix_block_len)
        # cross-replica KV block transfer (runtime/kv_transfer.py): arms
        # the per-generation prefix cache's export/import warmup so
        # fills and donor serving mint ZERO post-warmup compile keys
        self._kv_transfer = bool(kv_transfer)
        # SLO targets for the adaptive admission policy — every rebuilt
        # generation's scheduler gets a FRESH policy (its EWMAs describe
        # the dead engine's steps; the new one re-learns in a few steps)
        self._slo_ttft_ms = slo_ttft_ms
        self._slo_itl_ms = slo_itl_ms
        # per-slot speculative decoding (runtime/draft.py): the spec
        # string ("self:2" / "model:PATH") is rebuilt into a DraftModel
        # PER GENERATION inside _make_sched — a self-draft's params are
        # views of the dying engine's buffers and must never outlive it
        self._draft = draft
        self._draft_len = int(draft_len)
        self._draft_vocab = draft_vocab
        # multi-tenant weighted-fair admission (runtime/fleet.py): a
        # zero-arg callable minting a fresh WFQueue per generation —
        # the TenantLedger behind it is held by the CALLER (the fleet
        # controller / API layer) so budgets survive rebuilds, the same
        # externally-held discipline as the counter carry below
        self._fair_queue_factory = fair_queue_factory
        self.max_queue = int(max_queue)
        self._queue_timeout = queue_timeout
        self._request_deadline = request_deadline
        self.stall_timeout = float(stall_timeout)
        self._poll = watchdog_poll
        self._backoff_base = backoff_base
        self._backoff_max = backoff_max
        self.breaker_threshold = int(breaker_threshold)

        self.sup_stats = SupervisorStats()
        self._state_lock = threading.RLock()
        # dead generations' ServeStats stay LIVE in _dead_stats (a
        # straggler — e.g. the failed-during-submit fallback — may still
        # increment one briefly after the swap; summing live objects
        # never loses those counts); only ancient generations past the
        # cap are compressed into the _carry snapshot, long after any
        # writer can exist
        self._dead_stats: list = []  # dlrace: guarded-by(self._state_lock)
        self._carry = {k: 0 for k in _COUNTER_KEYS}  # dlrace: guarded-by(self._state_lock)
        self._stop = False
        self._gen = 0  # dlrace: guarded-by(self._state_lock)
        self._state = READY  # dlrace: guarded-by(self._state_lock)
        self._sched = self._make_sched(engine_factory())  # dlrace: guarded-by(self._state_lock)
        # compile the serving executables BEFORE the watchdog exists: a
        # first-step compile must never read as a stall (see
        # Scheduler.warmup) and /readyz must mean "will serve promptly"
        self._sched.warmup()
        self._loop_threads: dict[int, threading.Thread] = {}
        self._rebuild_thread: threading.Thread | None = None  # dlrace: guarded-by(self._state_lock)
        self._start_loop(self._sched, self._gen)
        self._watchdog_thread = threading.Thread(
            target=self._watchdog, name="dllama-watchdog", daemon=True)
        self._watchdog_thread.start()

    # -- scheduler surface (what the API server/tests already use) --------

    @property
    def engine(self):
        return self._sched.engine

    @property
    def stats(self):
        """The CURRENT generation's ServeStats (windows/percentiles);
        cross-generation totals live in summary()."""
        return self._sched.stats

    @property
    def prefix_cache(self):
        """The CURRENT generation's radix prefix cache (None when off) —
        like `stats`, this swaps wholesale on recovery."""
        return self._sched.prefix_cache

    @property
    def state(self) -> str:
        with self._state_lock:
            return self._state

    @property
    def ready(self) -> bool:
        """Readiness = engine healthy AND queue under bound — the
        /readyz contract."""
        with self._state_lock:
            if self._state != READY:
                return False
            sched = self._sched
        return not self.max_queue or len(sched._queue) < self.max_queue

    def submit(self, prompt, max_tokens, sampler, eos_id=None,
               deadline=None, trace_id=None, tenant=None,
               priority="normal"):
        with self._state_lock:
            if self._state != READY:
                self.sup_stats.rejected_unready += 1
                raise EngineUnready(self._state, self._retry_after())
            sched = self._sched
        req = sched.submit(prompt, max_tokens, sampler, eos_id=eos_id,
                           deadline=deadline, trace_id=trace_id,
                           tenant=tenant, priority=priority)
        if sched._stop and not req.finished.is_set():
            # the generation died between the state check and the enqueue:
            # its abort may already have drained the queue, so deliver this
            # request's terminal frame ourselves rather than strand it
            sched._fail_req(req, {"code": "engine_error",
                                  "message": "engine failed during submit",
                                  "retryable": True})
        return req

    @contextlib.contextmanager
    def exclusive(self):
        """Borrow the current generation's engine (Scheduler.exclusive).
        Refused while not ready — a borrower must never receive an engine
        that is about to be discarded. A crash inside the borrow (the
        drain loop or the borrower's own engine use — everything fallible
        at the API layer is parsed BEFORE entering) is an engine failure
        like any step crash: it triggers the same recovery (abort frames,
        rebuild, backoff) and re-raises to the borrower."""
        with self._state_lock:
            if self._state != READY:
                raise EngineUnready(self._state, self._retry_after())
            sched, gen = self._sched, self._gen
        try:
            with sched.exclusive() as eng:
                yield eng
        except Exception as e:  # noqa: BLE001 — GeneratorExit (client
            # disconnect teardown) is BaseException and passes through
            self._on_failure(gen, f"{type(e).__name__}: {e} "
                                  "(exclusive borrow)", kind="crash")
            raise

    def close(self, timeout: float = 30.0) -> None:
        end = time.perf_counter() + timeout
        with self._state_lock:
            self._stop = True
            self._state = CLOSED
            self._gen += 1  # invalidate every loop thread
            sched = self._sched
            rebuild = self._rebuild_thread
        sched.close(timeout=timeout)
        if rebuild is not None and rebuild.is_alive():
            # a close that lands mid-rebuild must WAIT for the rebuild's
            # factory/warmup to notice _stop: a daemon thread still inside
            # an XLA compile when the interpreter finalizes is a segfault,
            # not a clean exit (seen as intermittent rc=-11 in a
            # subprocess after a kill-then-close chaos pass)
            rebuild.join(timeout=max(end - time.perf_counter(), 1.0))
        if self._watchdog_thread.is_alive():
            self._watchdog_thread.join(timeout=max(self._poll * 10, 1.0))

    # -- resilience surface ------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful drain: stop admitting (state DRAINING — /readyz goes
        unready, submits are refused), keep stepping until in-flight and
        queued work completes or `timeout` elapses. Returns True when the
        scheduler went idle in time; stragglers past the deadline are the
        caller's to abort (close())."""
        with self._state_lock:
            if self._state == READY:
                self._state = DRAINING
            elif self._state in (RECOVERING, BROKEN):
                return True  # nothing in flight: failures already aborted
        end = time.perf_counter() + timeout
        while time.perf_counter() < end:
            sched = self._sched
            # lock-free busy check (the step mutex is one a wedged
            # forward may hold forever)
            if not sched._queue and all(s.req is None for s in sched.slots):
                return True
            time.sleep(0.02)
        return False

    def trip_cluster(self, exc) -> None:
        """Map a :class:`parallel.multihost.ClusterPeerLost` onto the
        BROKEN path: the engine's mesh spans a process that is gone, so a
        local rebuild cannot help — every in-flight/queued request gets a
        structured ``cluster_peer_lost`` error frame immediately (instead
        of hanging to its deadline in a collective that will never
        complete) and the circuit opens without burning rebuild attempts.
        ``reset_breaker()`` remains the operator's half-open once the
        worker is back. Idempotent; callable from the link's detection
        thread while the step thread is wedged (the abort path takes no
        step mutex — Scheduler._abort_all)."""
        with self._state_lock:
            if self._state in (CLOSED, BROKEN):
                return
            self._gen += 1          # wedged/stale threads exit on wake
            old = self._sched
            old._stop = True
            self._state = BROKEN
            self.sup_stats.cluster_losses += 1
            self.sup_stats.consecutive_failures = self.breaker_threshold
        if TRACER.enabled:
            TRACER.event("cluster_lost", 0, msg=str(exc)[:200],
                         key=self._fault_key)
        # retryable=False: the SAME replica cannot serve a retry until an
        # operator (or orchestrator) restores the lost worker and resets
        # the breaker — clients should fail over, not hammer
        old._abort_all(str(exc), code="cluster_peer_lost", retryable=False)

    def reset_breaker(self) -> None:
        """Operator half-open: clear the failure streak and try one
        rebuild. No-op unless the breaker is open."""
        with self._state_lock:
            if self._state != BROKEN:
                return
            self.sup_stats.consecutive_failures = 0
            self._state = RECOVERING
            self._rebuild_thread = threading.Thread(
                target=self._rebuild, args=(time.perf_counter(),),
                daemon=True)
        if TRACER.enabled:
            TRACER.event("circuit", 0, scope="engine", state="half_open",
                         key=self._fault_key)
        self._rebuild_thread.start()

    def summary(self) -> dict:
        """ServeStats summary with cross-generation counter totals folded
        in, plus the supervisor block — the /stats payload."""
        with self._state_lock:
            sched = self._sched
            carry = dict(self._carry)
            dead = list(self._dead_stats)
            state = self._state
        out = sched.stats.summary()
        for k in _COUNTER_KEYS:
            # from the raw attributes, rounded once after the sum: a
            # total of separately rounded floats could fall when a
            # generation moves from live to dead
            v = (getattr(sched.stats, k) + carry[k]
                 + sum(getattr(d, k, 0) for d in dead))
            out[k] = round(v, 3) if isinstance(v, float) else v
        out["state"] = state
        out["resilience"] = self.sup_stats.summary()
        # device-tier blocks (runtime/profiler.py): live-bytes by
        # category for the CURRENT generation's engine + arena and the
        # process compile ledger. Cheap per scrape: the shape-derived
        # bytes are cached on the engine, the rest are a handful of
        # nbytes reads and dict copies.
        from .profiler import COMPILES, hbm_ledger

        try:
            out["hbm"] = hbm_ledger(sched.engine, sched.prefix_cache)
        except Exception:  # noqa: BLE001 — a mid-rebuild engine swap
            pass           # must never fail a stats scrape
        out["compiles"] = COMPILES.summary()
        return out

    def _retry_after(self) -> float:
        # RECOVERING: one backoff step is the honest estimate; BROKEN:
        # nothing will change until an operator intervenes — back way off
        n = max(self.sup_stats.consecutive_failures, 1)
        if self._state == BROKEN:
            return 30.0
        return min(self._backoff_base * (2 ** (n - 1)), self._backoff_max)

    # -- internals ---------------------------------------------------------

    def _make_sched(self, engine) -> Scheduler:
        pc = None
        if self._prefix_blocks > 0:
            from .prefix_cache import PrefixCache

            pc = PrefixCache(engine, num_blocks=self._prefix_blocks,
                             block_len=self._prefix_block_len,
                             transfer=self._kv_transfer)
        draft_factory = None
        if self._draft:
            from .draft import build_draft

            spec_str = self._draft
            draft_factory = lambda eng: build_draft(eng, spec_str)  # noqa: E731
        return Scheduler(engine, chunk=self._chunk,
                         max_queue=self.max_queue,
                         queue_timeout=self._queue_timeout,
                         request_deadline=self._request_deadline,
                         prefix_cache=pc, fault_key=self._fault_key,
                         slo_ttft_ms=self._slo_ttft_ms,
                         slo_itl_ms=self._slo_itl_ms,
                         draft_factory=draft_factory,
                         draft_len=self._draft_len,
                         draft_vocab=self._draft_vocab,
                         fair_queue=(self._fair_queue_factory()
                                     if self._fair_queue_factory else None))

    def _start_loop(self, sched: Scheduler, gen: int) -> None:
        for g in [g for g, t in self._loop_threads.items()
                  if not t.is_alive()]:
            del self._loop_threads[g]  # dead generations; wedged ones stay
        t = threading.Thread(target=self._loop, args=(sched, gen),
                             name=f"dllama-supervised-step-gen{gen}",
                             daemon=True)
        self._loop_threads[gen] = t
        t.start()

    def _loop(self, sched: Scheduler, gen: int) -> None:
        """Supervised step loop — Scheduler._run's body, with failures
        escalated to recovery instead of swallowed."""
        while not self._stop and gen == self._gen and not sched._stop:
            sched._wake.clear()
            try:
                with sched._mutex:
                    did = sched._step_locked()
            except Exception as e:  # noqa: BLE001 — any step failure
                self._on_failure(gen, f"{type(e).__name__}: {e}",
                                 kind="crash")
                return
            if did and self.sup_stats.consecutive_failures:
                with self._state_lock:
                    if gen == self._gen:
                        # a real step succeeded post-recovery: streak over
                        self.sup_stats.consecutive_failures = 0
            if not did and not self._stop and gen == self._gen:
                sched.idle_wait()

    def _watchdog(self) -> None:
        """Detect the stall no exception will ever report: a step body
        running longer than stall_timeout. The wedged thread cannot be
        interrupted — its generation is discarded and it exits on wake."""
        while not self._stop:
            time.sleep(self._poll)
            with self._state_lock:
                if self._state != READY:
                    continue
                sched, gen = self._sched, self._gen
            t0 = sched._step_t0
            if t0 is not None and time.perf_counter() - t0 > self.stall_timeout:
                self.sup_stats.watchdog_trips += 1
                self._on_failure(
                    gen, f"step stalled > {self.stall_timeout:.1f}s "
                         "(watchdog)", kind="stall")

    def _on_failure(self, gen: int, msg: str, kind: str) -> None:
        """Failure entry point (loop crash or watchdog stall): invalidate
        the generation, fail its requests with structured frames, then
        rebuild in the background. Idempotent per generation."""
        with self._state_lock:
            if gen != self._gen or self._state in (CLOSED,):
                return
            t_detect = time.perf_counter()
            self._gen += 1          # wedged/stale threads exit on wake
            old = self._sched
            old._stop = True
            self._state = RECOVERING
            if kind == "crash":
                self.sup_stats.crashes += 1
            self.sup_stats.consecutive_failures += 1
        if TRACER.enabled:
            TRACER.event("engine_failure", 0, failure=kind, msg=msg[:200],
                         gen=gen, key=self._fault_key)
        # abort OUTSIDE the state lock (waiter wakeups run arbitrary
        # consumer code) and WITHOUT the step mutex (a wedged step holds
        # it forever) — see Scheduler._abort_all
        old._abort_all(f"engine failure: {msg}")
        t = threading.Thread(target=self._rebuild, args=(t_detect,),
                             daemon=True)
        with self._state_lock:
            self._rebuild_thread = t
        t.start()

    def _rebuild(self, t_detect: float) -> None:
        """Backoff → factory → install → resume. Runs on its own thread
        (the failing thread is wedged or must exit; the watchdog must keep
        watching). Factory failures count toward the breaker."""
        while not self._stop:
            with self._state_lock:
                n = self.sup_stats.consecutive_failures
                if n >= self.breaker_threshold:
                    self._state = BROKEN  # circuit open: stay unready
                    if TRACER.enabled:
                        TRACER.event("circuit", 0, scope="engine",
                                     state="open", fails=n,
                                     key=self._fault_key)
                    return
            time.sleep(min(self._backoff_base * (2 ** max(n - 1, 0)),
                           self._backoff_max))
            if self._stop:
                return  # closed during backoff: skip the doomed compile
            try:
                sched = self._make_sched(self._factory())
                # compile while still unready — the watchdog only watches
                # READY generations, so rebuild compile time can never
                # trip it (a stall_timeout below compile time would
                # otherwise recovery-loop forever)
                sched.warmup()
            except Exception:  # noqa: BLE001 — a failing factory is just
                with self._state_lock:  # another consecutive failure
                    self.sup_stats.consecutive_failures += 1
                continue
            with self._state_lock:
                if self._stop or self._state == CLOSED:
                    sched.close(timeout=1.0)
                    return
                self._gen += 1
                gen = self._gen
                self._dead_stats.append(self._sched.stats)
                if len(self._dead_stats) > 32:
                    old = self._dead_stats.pop(0)  # ancient: no writers
                    for k in _COUNTER_KEYS:
                        self._carry[k] += getattr(old, k, 0)
                self._sched = sched
                self._state = READY
                self.sup_stats.recoveries += 1
                recovery_ms = (time.perf_counter() - t_detect) * 1e3
                self.sup_stats.recovery_ms.append(recovery_ms)
            if TRACER.enabled:
                TRACER.event("recovery", 0, ms=round(recovery_ms, 3),
                             gen=gen, key=self._fault_key)
            self._start_loop(sched, gen)
            return
