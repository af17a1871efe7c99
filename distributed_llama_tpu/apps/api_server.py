"""OpenAI-compatible HTTP API server.

TPU-native equivalent of the reference's dllama-api
(ref: src/apps/dllama-api/dllama-api.cpp):

  * POST /v1/chat/completions — completion + SSE streaming
    (ref: dllama-api.cpp:202-314)
  * GET /v1/models (ref: dllama-api.cpp:316-322)
  * Llama-3 header chat template (ref: dllama-api.cpp:173-181)
  * per-request temperature / seed / max_tokens / stop
    (ref: dllama-api.cpp:211-232), applied via Sampler setters
    (ref: src/tokenizer.cpp:358-364)
  * stop-sequence scan over the trailing pieces (ref: dllama-api.cpp:272-286)
  * prefix/session reuse (net-new — the reference resets the KV cache per
    request, ref: dllama-api.cpp:236-249): the longest common token prefix
    of the previous session stays cached and only the suffix re-prefills,
    which on TPU removes the dominant cost of a chat follow-up turn.
    Single-process only — multi-host clusters reset per request so a
    worker-side resync can never desync the processes' prefill shapes

Front-end: a THREADED accept loop (ThreadingHTTPServer — net-new vs the
reference's single-threaded accept, ref: dllama-api.cpp:341-352; stdlib
only, no external deps). With --serve-batch B the process runs the
continuous-batching scheduler (runtime/scheduler.py): /v1/completions and
/v1/chat/completions enqueue onto the shared slot scheduler and stream
tokens per-request as their slot produces them, so concurrent clients
share one batched decode instead of queueing whole requests. Without it,
requests serialize on the single engine behind state.engine_lock (the
reference's behavior, minus dropped connections).
"""

from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from http.server import (BaseHTTPRequestHandler, HTTPServer,
                         ThreadingHTTPServer)

import numpy as np

from ..runtime.fleet import ShedReject
from ..runtime.resilience import EngineUnready
from ..runtime.scheduler import PromptTooLong, QueueFull, RequestError

CHAT_EOS_MARKERS = ("<|eot_id|>", "<|end_of_text|>")

# SSE keepalive cadence for collected (non-streaming-engine) paths: the
# batch endpoint's greedy+lookup path buffers all rows before the first
# data event, so comment frames (": keepalive") flow while it collects —
# a long generation must not trip client/proxy idle timeouts (ADVICE r5
# low). Comments are protocol-invisible to SSE clients. Tests shrink this.
KEEPALIVE_SECS = 1.0


class BadRequest(ValueError):
    """Deterministic client-input error (malformed temperature/seed/stop/
    prompt types): must map to HTTP 400, never to a retryable 503 — a
    well-behaved client would otherwise retry the permanently-invalid
    request forever."""


def _is_loopback(addr: str) -> bool:
    """Default guard for the /admin/* operator endpoints: auth-free but
    loopback-only — an operator SSHed onto the box (or a sidecar) can
    reset breakers and roll replicas, while nothing routable from the
    service port's clients can. Covers IPv4 loopback (the whole
    127.0.0.0/8), IPv6 ::1, and the IPv6-mapped IPv4 form."""
    if addr.startswith("::ffff:"):
        addr = addr[len("::ffff:"):]
    return addr == "::1" or addr.startswith("127.")


def _admin_authorized(state: "ApiState", client_addr: str,
                      auth_header: str | None) -> bool:
    """May this caller use /admin/*? Loopback always can (the SSHed
    operator). Off-loopback needs ``--admin-token``: remote-replica
    deployments put the operator on another machine, where loopback-only
    was an outage (the breaker could not be reset over the network).
    The compare is constant-time (hmac.compare_digest) so the token
    cannot be recovered byte-at-a-time from response timing."""
    if _is_loopback(client_addr):
        return True
    if not state.admin_token or not auth_header:
        return False
    import hmac

    expected = "Bearer " + state.admin_token
    return hmac.compare_digest(auth_header.encode(), expected.encode())


def build_chat_prompt(messages: list[dict]) -> str:
    """Llama-3 header template (ref: dllama-api.cpp:173-181)."""
    out = []
    for m in messages:
        out.append(f"<|start_header_id|>{m.get('role', 'user')}<|end_header_id|>\n\n"
                   f"{m.get('content', '')}<|eot_id|>")
    out.append("<|start_header_id|>assistant<|end_header_id|>\n\n")
    return "".join(out)


class ApiState:
    def __init__(self, engine, tokenizer, sampler, model_name: str = "dllama",
                 lookup_decode: int = 0, serve_batch: int = 0,
                 serve_chunk: int = 0, queue_depth: int = 0,
                 request_deadline: float = 0.0, stall_timeout: float = 0.0,
                 prefix_cache: bool = False, prefix_blocks: int = 0,
                 prefix_block_len: int = 32, replicas: int = 1,
                 retry_budget: int = 1, route_policy: str = "cache_aware",
                 replica_procs: int = 0, replica_hosts=None,
                 worker_config: dict | None = None,
                 admin_token: str | None = None,
                 profile_dir: str | None = None,
                 slo_ttft_ms: float | None = None,
                 slo_itl_ms: float | None = None,
                 autosize: dict | None = None,
                 draft: str | None = None, draft_len: int = 0,
                 kv_transfer: bool = False, tiers=None,
                 min_replicas: int = 0, max_replicas: int = 0,
                 tenant_budgets: str | None = None,
                 multihost: bool = False):
        self.engine = engine
        # a --nnodes cluster root (serve() knows from the flag; asking
        # jax.process_count() here would initialize a backend in the
        # process tiers' front door, which must hold no device)
        self.multihost = bool(multihost)
        self.tokenizer = tokenizer
        self.sampler = sampler
        self.model_name = model_name
        # resilience config (docs/operations.md): bounded admission queue
        # (0 = 4x serve_batch), per-request end-to-end deadline seconds
        # (0 = off), watchdog stall bound seconds (0 = default 10)
        self.queue_depth = queue_depth
        self.request_deadline = request_deadline
        self.stall_timeout = stall_timeout
        # graceful drain (SIGTERM): admissions stop, /readyz goes 503,
        # in-flight work finishes up to --drain-timeout
        self.draining = False
        # token history whose K/V writes are live in the engine cache
        # (prefix/session reuse — see _completion_chunks)
        self.cached_tokens: list[int] = []
        # greedy requests draft+verify up to this many tokens per forward
        # (prompt-lookup speculation, runtime/speculative.py); 0 = off
        self.lookup_decode = lookup_decode
        # REAL-draft speculation (runtime/draft.py): the --draft spec
        # string ("self:D" / "model:PATH") and per-forward budget. On
        # the scheduler path the draft rides build_front_door into
        # every replica's scheduler; on the legacy path a DraftModel is
        # built lazily over this process's engine. spec_stats is the
        # LEGACY tier's aggregate accept record (the scheduler tiers
        # carry theirs on ServeStats.spec) — attached to /stats and
        # /metrics in every tier, launch flags notwithstanding.
        from ..runtime.stats import FrontDoorStats, SpecStats

        # window counters of the scheduler-path completion generator
        # (`frontdoor` /stats block): requests, ms to submit, ms tokenizing
        self.frontdoor = FrontDoorStats()
        self.draft = draft
        self.draft_len = int(draft_len or 0)
        self._draft_model = None
        self.spec_stats = SpecStats(
            mode=(draft or ("lookup" if lookup_decode else "off")),
            draft_len=self.draft_len or lookup_decode)
        # serve_batch > 0 runs the continuous-batching scheduler with this
        # many KV slots: /v1/completions and /v1/chat/completions enqueue
        # onto it, and POST /v1/batch/completions borrows its engine.
        # Decode is weight-read-bound, so b live slots amortize one weight
        # read per step.
        self.serve_batch = serve_batch
        self.serve_chunk = serve_chunk  # prefill chunk; 0 = engine default
        # radix prefix cache (runtime/prefix_cache.py): cross-request KV
        # reuse on the scheduler path. blocks = 0 auto-sizes the arena to
        # 2x the live cache footprint (2 * B * seq_len worth of blocks) —
        # enough to keep several distinct system prompts + recent
        # conversations resident without doubling engine memory twice
        self.prefix_cache = prefix_cache
        self.prefix_block_len = prefix_block_len
        self.prefix_blocks = prefix_blocks
        # multi-replica serving tier (runtime/router.py): replicas > 1
        # puts a cache-aware failover router in front of N supervised
        # engine replicas over SHARED weights; retry_budget bounds the
        # automatic resubmits of not-yet-streamed requests after a
        # replica failure, route_policy picks the placement rule
        self.replicas = replicas
        self.retry_budget = retry_budget
        self.route_policy = route_policy
        # KV block transfer + prefill/decode disaggregation (runtime/
        # kv_transfer.py): the enable flag and the per-replica roles
        # build_front_door stamps into handles/worker configs
        self.kv_transfer = bool(kv_transfer)
        self.tiers = tiers
        # PROCESS-isolated replica tier (runtime/replica_worker.py):
        # replica_procs spawns N supervised worker processes locally
        # (each its own interpreter — a segfault/SIGKILL/OOM costs one
        # process, not the service); replica_hosts connects to
        # pre-started workers at [(host, port), ...] instead
        self.replica_procs = replica_procs
        self.replica_hosts = replica_hosts
        self.worker_config = worker_config
        # optional bearer token for /admin/*: remote-replica operators
        # are not on loopback, so --admin-token is the non-local
        # alternative to _is_loopback (constant-time compare)
        self.admin_token = admin_token
        # serializes legacy single-engine requests under the threaded
        # accept loop (the scheduler path needs no lock — it queues)
        self.engine_lock = threading.RLock()
        self._scheduler = None
        # router mode = any multi-handle tier (thread, process, or
        # remote): gates session affinity and the per-replica /readyz
        # payload independent of WHICH tier is configured
        self.router_mode = bool(replicas > 1 or replica_procs
                                or replica_hosts)
        # multihost root: set to the ClusterPeerLost when the control
        # plane detects a dead/wedged worker — /readyz answers 503
        # cluster_lost during the brief window before the diagnostic exit
        self.cluster_lost = None
        # POST /admin/profile capture home (--profile-dir; a tempdir per
        # capture otherwise) and the cached build-identity block every
        # /healthz + /metrics answer carries
        self.profile_dir = profile_dir
        self._build_info: dict | None = None
        # SLO-aware admission (runtime/scheduler.AdmissionPolicy): either
        # target arms the adaptive chunk-width policy in every replica's
        # scheduler; the auto-sizing decision record (resolve_auto_shape)
        # rides /stats + /metrics so the chosen shape is always visible
        self.slo_ttft_ms = slo_ttft_ms
        self.slo_itl_ms = slo_itl_ms
        self.autosize = autosize
        # the fleet brain (runtime/fleet.py): --min/--max-replicas arm
        # load-adaptive autoscaling of the replica set, --tenant-budgets
        # arms weighted-fair queueing + per-tenant token buckets, and
        # either SLO target arms the overload shed ladder. The
        # controller is built WITH the front door (scheduler()) so the
        # fleet /stats + /metrics block exists in every scheduler tier.
        self.min_replicas = int(min_replicas or 0)
        self.max_replicas = int(max_replicas or 0)
        self.tenant_budgets = tenant_budgets
        self._fleet = None
        self.tenant_ledger = None

    def build_info(self) -> dict:
        """{version, jax, backend, device_kind, device_count, mesh} —
        computed once (the backend and mesh never change within a
        process), served on /healthz (`build` block) and /metrics
        (`dllama_build_info`)."""
        if self._build_info is not None:
            return self._build_info
        if self.replica_procs or self.replica_hosts:
            # process tiers: the front door holds no device and must never
            # initialize a backend (a parent that touched JAX holds the
            # chip its worker needs) — relay the block a worker reported
            # on its health PONG; until one has, say so
            import jax

            from .. import __version__

            sup = self._scheduler
            for h in (sup.replicas if sup is not None else ()):
                worker = h.health_snapshot().get("build")
                if worker:
                    self._build_info = {**worker, "mesh": "front-door"}
                    return self._build_info
            return {"version": __version__, "jax": jax.__version__,
                    "backend": "uninitialized", "device_kind": "",
                    "device_count": 0, "mesh": "front-door"}
        from ..runtime.profiler import build_info

        self._build_info = build_info(self.engine)
        return self._build_info

    def scheduler(self):
        """The serving front door, built and started on first use: an
        ``EngineSupervisor`` (replicas == 1) or a failover ``Router``
        over N supervised replicas — both constructed by
        runtime/router.build_front_door, the engine-owner logic that
        used to live here (the HTTP layer no longer builds engines). The
        handlers speak one duck-typed surface (``submit``/``engine``/
        ``exclusive``/``ready``/``summary``), so 1 and N replicas serve
        through identical code. Every replica's engine SHARES this
        engine's param device buffers — replication costs KV caches and
        prefix arenas, never weight copies. Single-process only; a tp
        mesh composes on the single-supervisor tier (the vocab-sharded
        serving path) — serve() refuses every other mesh axis, cluster,
        and tp×replicas combination at startup."""
        with self.engine_lock:  # two first requests must not double-build
            if self._scheduler is None:
                from ..runtime.fleet import (FleetConfig, FleetController,
                                             ShedLadder, TenantLedger,
                                             parse_tenant_budgets)
                from ..runtime.router import build_front_door

                if self.tenant_budgets and self.tenant_ledger is None:
                    self.tenant_ledger = TenantLedger(
                        parse_tenant_budgets(self.tenant_budgets))
                self._scheduler = build_front_door(
                    self.engine, serve_batch=self.serve_batch,
                    serve_chunk=self.serve_chunk,
                    queue_depth=self.queue_depth,
                    request_deadline=self.request_deadline,
                    stall_timeout=self.stall_timeout,
                    prefix_cache=self.prefix_cache,
                    prefix_blocks=self.prefix_blocks,
                    prefix_block_len=self.prefix_block_len,
                    replicas=self.replicas,
                    retry_budget=self.retry_budget,
                    route_policy=self.route_policy,
                    replica_procs=self.replica_procs,
                    replica_hosts=self.replica_hosts,
                    worker_config=self.worker_config,
                    slo_ttft_ms=self.slo_ttft_ms,
                    slo_itl_ms=self.slo_itl_ms,
                    draft=self.draft, draft_len=self.draft_len,
                    draft_vocab=self.tokenizer.vocab_size,
                    kv_transfer=self.kv_transfer, tiers=self.tiers,
                    tenant_ledger=self.tenant_ledger)
                # the fleet brain rides every scheduler tier: the shed
                # ladder arms off the SLO targets (no SLO = no ladder,
                # admit() passes through), autoscaling arms off the
                # --min/--max-replicas window (FleetController scales
                # only when the door exposes a spawn factory)
                boot = max(self.replicas, self.replica_procs,
                           len(self.replica_hosts or ()), 1)
                cfg = FleetConfig(
                    min_replicas=self.min_replicas or boot,
                    max_replicas=self.max_replicas or boot)
                ladder = (ShedLadder()
                          if (self.slo_ttft_ms or self.slo_itl_ms)
                          else None)
                self._fleet = FleetController(
                    self._scheduler, config=cfg, ladder=ladder,
                    ledger=self.tenant_ledger)
                self._fleet.start()
            return self._scheduler

    def fleet(self):
        """The fleet controller, built WITH the front door (None until
        the first scheduler-path request forces the build)."""
        self.scheduler()
        return self._fleet

    def batch_engine(self):
        """The batched engine — the SCHEDULER's engine (one live batched
        KV cache per process; callers stepping it directly must hold
        Scheduler.exclusive())."""
        return self.scheduler().engine

    def draft_model(self):
        """The LEGACY path's DraftModel over this process's engine,
        built once on first use (the scheduler tiers build their own
        per generation through build_front_door — never this one)."""
        if self._draft_model is None and self.draft:
            from ..runtime.draft import build_draft

            with self.engine_lock:
                if self._draft_model is None:
                    self._draft_model = build_draft(self.engine,
                                                    self.draft)
        return self._draft_model


def _raw_prompt_body(body: dict) -> bool:
    """A /v1/completions-shaped body (raw `prompt`, no chat template or
    chat EOS markers). Inferred from the body, not the route, so the
    multi-host worker replay (apps/dllama.cmd_worker re-runs the raw body
    through _completion_chunks) handles both endpoints with no protocol
    change."""
    return "messages" not in body and "prompt" in body


def _piece_scanner(tokenizer, first_prev: int, markers, stops):
    """Per-token text scan shared by the single-request streams (the
    legacy and scheduler paths): eos / chat-marker / stop-sequence
    semantics live exactly once — the batch endpoint's per-row scan_token
    mirrors the same rules with per-row state. Returns scan(tok) -> the
    decoded piece to emit, or None when the request just STOPPED (the
    token is consumed, never emitted)."""
    scan_state = {"prev": first_prev, "tail": ""}
    tail_len = max([len(m) for m in markers]
                   + [len(s) for s in stops] + [1]) + 16
    eos = tokenizer.eos_id

    def scan(tok: int) -> str | None:
        if tok == eos:
            return None
        piece = tokenizer.decode_piece(scan_state["prev"], tok).decode(
            "utf-8", errors="replace")
        scan_state["prev"] = tok
        # bounded trailing window (ref: dllama-api.cpp:272-286)
        scan_state["tail"] = (scan_state["tail"] + piece)[-tail_len:]
        if (any(m in scan_state["tail"] for m in markers)
                or (stops and any(s in scan_state["tail"] for s in stops))):
            return None
        return piece

    return scan


def _completion_chunks(state: ApiState, body: dict):
    """Generator of generated text pieces for one request (the legacy
    single-engine path: prefix reuse, lookup decode, shared sampler)."""
    engine, tokenizer, sampler = state.engine, state.tokenizer, state.sampler

    if _raw_prompt_body(body):
        prompt = body.get("prompt") or ""
        markers: tuple = ()
    else:
        prompt = build_chat_prompt(body.get("messages", []))
        markers = CHAT_EOS_MARKERS
    max_tokens = int(body.get("max_tokens", 0) or 0)
    stops = body.get("stop") or []
    if isinstance(stops, str):
        stops = [stops]

    tokens = tokenizer.encode(prompt)
    if len(tokens) >= engine.seq_len:
        raise PromptTooLong(
            f"prompt is {len(tokens)} tokens; context is {engine.seq_len}")

    # prefix/session reuse (net-new vs the reference's full per-request
    # reset, ref: dllama-api.cpp:236-249): chat turns share the system
    # prompt + history, and on TPU the re-prefill is the expensive part of
    # a turn. Keep the longest common token prefix of the previous
    # session's cache and prefill only the suffix — positions >= the kept
    # prefix hold stale K/V that this request overwrites position-by-
    # position before any of its queries can attend them (the same
    # invariant decode overruns rely on, runtime/engine.py).
    lcp = 0
    if not state.multihost:
        # multi-host clusters skip reuse: it is only collective-safe while
        # every process's cached_tokens agree, and a worker-local failure
        # resync (apps/dllama.cmd_worker) legitimately clears one side —
        # the next request must then prefill identically everywhere
        while (lcp < len(state.cached_tokens) and lcp < len(tokens) - 1
               and state.cached_tokens[lcp] == tokens[lcp]):
            lcp += 1
    if lcp > 0:
        engine.pos = lcp
    else:
        engine.reset()
    suffix = tokens[lcp:]
    state.cached_tokens = []  # repopulated on success below

    # per-request sampler params must not leak into later requests that omit
    # them — temperature AND the RNG stream position are restored in the
    # finally below (a request's "seed" must not permanently reseed the
    # shared sampler)
    saved_temp = sampler.temperature
    saved_rng_state = None
    if body.get("temperature") is not None:
        sampler.set_temp(float(body["temperature"]))
    if body.get("seed") is not None:
        saved_rng_state = sampler.rng_state
        sampler.set_seed(int(body["seed"]))

    limit = engine.seq_len - len(tokens) - 1
    n_gen = min(max_tokens, limit) if max_tokens > 0 else limit

    n_prompt = len(tokens)
    scan = _piece_scanner(tokenizer, tokens[-1], markers, stops)
    emitted = 0
    finish = "length"
    def plain_tokens():
        """Reference-parity sampled loop as a token iterator: yield, then
        step the token only if the consumer pulls again (so the last
        emitted token is never stepped — same as the host generate())."""
        logits = engine.prefill(suffix)
        for _ in range(n_gen):
            tok = sampler.sample(engine.fetch_logits(logits)[0])
            yield tok
            if engine.pos >= engine.seq_len:
                return
            logits = engine.step(np.asarray([[tok]], np.int32), engine.pos)
            history.append(tok)  # stepping tok wrote its K/V

    # requests can speculate: prompt-lookup drafts verified in one forward.
    # Greedy requests stream the EXACT greedy tokens (argmax verify); at
    # temperature > 0 the rejection-resampling mode keeps every emitted
    # token distributed exactly as a host-sampler draw, but on a DERIVED
    # numpy RNG — the token stream is not the plain path's xorshift stream
    # (acceptance consumes a data-dependent number of uniforms, so coin
    # parity is impossible by construction — runtime/speculative.py). Safe
    # on multi-host clusters: prefix reuse is off there, so every process
    # replays the identical request from token 0, mines identical drafts,
    # and (sampled mode) derives the identical seed from the replicated
    # sampler stream (Sampler.next_seed) — same verify widths, collectives
    # in lock-step (the --lookup-decode flag itself is in the cluster
    # config fingerprint)
    use_lookup = state.lookup_decode > 0
    use_draft = state.draft is not None
    history = list(tokens)  # every prompt position is written by prefill
    # history bookkeeping ownership: the lookup streams do NOT append their
    # emitted tokens (their K/V is already written by the verify forward, so
    # the consumer loop appends), while plain_tokens() appends as it steps.
    # `speculating` — not `use_lookup` — gates the consumer-side append, so a
    # request that falls through to the plain loop (e.g. a client-supplied
    # NEGATIVE temperature) keeps exactly one owner and the prefix cache
    # stays aligned with real K/V positions.
    speculating = False
    try:
        if use_draft and sampler.temperature == 0.0:
            # real-draft speculation (runtime/draft.py): bit-identical
            # greedy stream, drafts from the model's own truncated-depth
            # prefix (or a separate draft .m) — pays on arbitrary text
            speculating = True
            token_iter = engine.generate_draft_stream(
                suffix, n_gen, history=tokens,
                draft=state.draft_model(), draft_len=state.draft_len or 7,
                vocab_size=tokenizer.vocab_size)
        elif use_draft and sampler.temperature > 0.0:
            speculating = True
            token_iter = engine.generate_draft_sampled_stream(
                suffix, n_gen, history=tokens,
                draft=state.draft_model(),
                temperature=sampler.temperature, topp=sampler.topp,
                seed=sampler.next_seed(),
                draft_len=state.draft_len or 7,
                vocab_size=tokenizer.vocab_size)
        elif use_lookup and sampler.temperature == 0.0:
            speculating = True
            token_iter = engine.generate_lookup_stream(
                suffix, n_gen, history=tokens,
                draft_len=state.lookup_decode,
                vocab_size=tokenizer.vocab_size)
        elif use_lookup and sampler.temperature > 0.0:
            speculating = True
            token_iter = engine.generate_lookup_sampled_stream(
                suffix, n_gen, history=tokens,
                temperature=sampler.temperature, topp=sampler.topp,
                seed=sampler.next_seed(),
                draft_len=state.lookup_decode,
                vocab_size=tokenizer.vocab_size)
        else:
            token_iter = plain_tokens()
        for tok in token_iter:
            piece = scan(tok)
            if piece is None:  # eos / chat marker / stop sequence
                finish = "stop"
                break
            emitted += 1
            if speculating:
                history.append(tok)  # its K/V position is already written
            yield ("piece", piece)
        state.cached_tokens = history[: engine.pos]
    finally:
        sampler.set_temp(saved_temp)
        if saved_rng_state is not None:
            sampler.rng_state = saved_rng_state
        if speculating:
            # fold the request's accept record into the LEGACY tier's
            # aggregate `spec` block (the scheduler tiers count inline)
            ls = getattr(engine, "last_spec", None)
            if ls:
                ss = state.spec_stats
                ss.verify_forwards += ls["forwards"]
                ss.drafted += ls["drafted"]
                ss.accepted += ls["accepted"]
                ss.emitted_spec += ls["emitted"]
    yield ("done", {"finish_reason": finish,
                    "prompt_tokens": n_prompt,
                    "completion_tokens": emitted})


def _prefix_would_hit(door, tokens: list[int]) -> bool:
    """Would this prompt seed from a radix prefix cache anywhere in the
    tier? The ladder's prefix_only rung admits only work that reuses
    cached KV (cheap prefill). Read-only peeks (match_len /
    kv_match_len), never a pin; any failure reads as a miss — under
    overload the conservative answer is to shed."""
    try:
        handles = getattr(door, "replicas", None)
        if handles:
            return any(h.match_len(tokens) > 0 for h in handles
                       if not getattr(h, "reap", False))
        sched = getattr(door, "_sched", None)
        if sched is not None:
            return sched.kv_match_len(tokens) > 0
    except Exception:  # noqa: BLE001 — a mid-recovery replica peek
        pass           # must never turn the shed door into a 500
    return False


def _sched_completion_chunks(state: ApiState, body: dict, chat: bool = True):
    """Scheduler-path generator for one /v1/completions or
    /v1/chat/completions request: enqueue onto the shared
    continuous-batching scheduler (runtime/scheduler.py) and stream pieces
    as the request's slot produces tokens — concurrent requests decode in
    ONE batched step loop instead of serializing on the engine.

    Per-request temperature/seed become a PRIVATE Sampler (the slot's RNG
    state), so concurrent requests never contend for the shared sampler's
    coin stream; omitted seeds derive from it (Sampler.next_seed) under the
    engine lock so results stay run-to-run deterministic. No prefix reuse
    on this path: slots are leased per request (the legacy single-engine
    path keeps the feature). Text-level stops cancel the request, freeing
    its slot immediately."""
    from ..runtime.trace import TRACER
    from ..sampler import Sampler

    # `api.pre_submit`: the parsed request to submit() returned — chat
    # template, tokenizer, shed ladder, enqueue. Always counted in the
    # `frontdoor` /stats block; a span only under --trace or a capture.
    t_pre = time.perf_counter()
    sp = TRACER.span("api.pre_submit") if TRACER.spans else None
    try:
        tokenizer = state.tokenizer
        sched = state.scheduler()
        engine = sched.engine
        if chat and not _raw_prompt_body(body):
            prompt = build_chat_prompt(body.get("messages", []))
            markers: tuple = CHAT_EOS_MARKERS
        else:
            prompt = body.get("prompt") or ""
            markers = ()
        max_tokens = int(body.get("max_tokens", 0) or 0)
        stops = body.get("stop") or []
        if isinstance(stops, str):
            stops = [stops]

        tokens = tokenizer.encode(prompt)
        temp = (state.sampler.temperature if body.get("temperature") is None
                else float(body["temperature"]))
        with state.engine_lock:  # the shared stream is also the legacy path's
            seed = (int(body["seed"]) if body.get("seed") is not None
                    else state.sampler.next_seed())
        sampler = Sampler(tokenizer.vocab_size, temperature=temp,
                          topp=state.sampler.topp, seed=seed)
        limit = engine.seq_len - len(tokens) - 1
        n_gen = min(max_tokens, limit) if max_tokens > 0 else limit
        # the fleet brain's overload door (runtime/fleet.py): walk the shed
        # ladder BEFORE submit — speculation off and max_tokens clamps are
        # invisible degradation, prefix-only and shed raise ShedReject which
        # the handler maps to a structured 429 (Retry-After from the live
        # drain rate). Runs before any slot work, so a shed costs nothing.
        tenant = body.get("tenant")
        priority = str(body.get("priority") or "normal")
        fleet = state.fleet()
        if fleet is not None:
            n_gen = fleet.admit(tenant=tenant, n_prompt=len(tokens),
                                max_tokens=n_gen,
                                prefix_hit=_prefix_would_hit(sched, tokens))
        # PromptTooLong raises HERE (before any event) — the handler still
        # turns it into a clean 400 through the queued/threaded path
        kwargs = {}
        if state.router_mode:
            # multi-replica tier: the OpenAI `user` field (or an explicit
            # `session`) keys replica stickiness, so a conversation keeps
            # hitting the replica whose radix tree caches its history
            session = body.get("session") or body.get("user")
            if session is not None:
                kwargs["session"] = str(session)
        req = sched.submit(tokens, n_gen, sampler, eos_id=tokenizer.eos_id,
                           tenant=tenant, priority=priority, **kwargs)
    finally:
        # a bad temperature, a tokenizer error, a shed: the span closes
        # on every way out, or its annotation stays entered on this thread
        if sp is not None:
            TRACER.end(sp)
    state.frontdoor.note((time.perf_counter() - t_pre) * 1e3)

    scan = _piece_scanner(tokenizer, tokens[-1], markers, stops)
    emitted = 0
    finish = "length"
    err = None
    try:
        for tok in req.tokens():
            piece = scan(tok)
            if piece is None:  # eos / chat marker / stop sequence
                finish = "stop"
                break
            emitted += 1
            yield ("piece", piece)
    except RequestError as e:
        # structured failure frame (crash/stall recovery, deadline,
        # shutdown): the stream TERMINATES with finish_reason "error" and
        # the frame rides the done event — an already-streaming SSE client
        # receives an explicit error event, never a silent hang
        finish = "error"
        err = e.frame()
    finally:
        # no-op after a natural finish; on text-level stops, client
        # disconnects and generator teardown it frees the slot NOW
        req.cancel()
    done = {"finish_reason": finish,
            "prompt_tokens": len(tokens),
            "completion_tokens": emitted}
    if err is not None:
        done["error"] = err
    yield ("done", done)


def _batch_completion_chunks(state: ApiState, body: dict):
    """POST /v1/batch/completions generator: up to serve_batch prompts
    decoded in ONE batched engine (net-new vs the reference's batch=1
    server — decode is weight-read-bound, so b rows amortize one weight
    read).

    Yields ("piece", (row, piece)) events then one ("done", {...}) with
    per-row finish/usage. Per-request temperature/seed apply to the whole
    batch through the shared reference-parity sampler stream (coins drawn
    in row order — Sampler.sample_batch); rows are independent sequences.
    No prefix reuse here: the batch cache is reset per request (the
    single-request endpoint keeps that feature). The engine is BORROWED
    from the scheduler (Scheduler.exclusive drains in-flight slot work
    first) — one process, one live batched KV cache."""
    sched = state.scheduler()
    engine = sched.engine
    tokenizer, sampler = state.tokenizer, state.sampler

    # parse EVERY request field BEFORE taking the scheduler's engine: a
    # malformed value (non-numeric temperature/seed, a non-string stop or
    # prompt) must fail THIS request as a 400, never leave the exclusive
    # lock held or read as a retryable engine failure
    try:
        if "prompts" in body:
            texts = body["prompts"]
            raw = True
        else:
            texts = [build_chat_prompt(m)
                     for m in body.get("messages_list", [])]
            raw = False
        b = len(texts)
        if not (1 <= b <= state.serve_batch):
            raise PromptTooLong(
                f"batch size {b} outside 1..{state.serve_batch} "
                "(server started with --serve-batch "
                f"{state.serve_batch})")
        max_tokens = int(body.get("max_tokens", 64))
        want_stream = bool(body.get("stream", False))
        stops = body.get("stop") or []
        if isinstance(stops, str):
            stops = [stops]

        rows = [tokenizer.encode(t) for t in texts]  # add_bos default,
        limit = engine.seq_len - 1                   # like the single path
        for i, r in enumerate(rows):
            if len(r) >= limit:
                raise PromptTooLong(
                    f"prompt {i}: {len(r)} tokens >= context {limit}")
        # budget: MAX over rows of the per-row cache headroom (rows share
        # the step loop; a longer-prompt row hitting seq_len retires only
        # itself — the engine's per-row pos guard — so one long prompt
        # must not cap the shorter rows' output). max_tokens <= 0 means
        # "generate to the context limit", like the single endpoint.
        headroom = max(limit - len(r) for r in rows)
        n_gen = min(max_tokens, headroom) if max_tokens > 0 else headroom
        n_prompt_toks = sum(len(r) for r in rows)  # before padding joins

        req_temp = (float(body["temperature"])
                    if body.get("temperature") is not None else None)
        req_seed = (int(body["seed"])
                    if body.get("seed") is not None else None)
        markers = () if raw else CHAT_EOS_MARKERS
        tail_len = max([len(m) for m in markers]
                       + [len(s) for s in stops] + [1]) + 16
        prev = [r[-1] for r in rows]
    except PromptTooLong:
        raise
    except (ValueError, TypeError, KeyError, AttributeError) as e:
        raise BadRequest(f"{type(e).__name__}: {e}") from e
    tails = [""] * b
    emitted = [0] * b
    finish = ["length"] * b
    # the engine's batch is a build-time shape: pad sub-batch requests with
    # pre-retired rows (flagged before the first step, so they never sample
    # — no coins leave the shared stream — and never emit)
    n_pad = engine.batch - b
    rows = rows + [[rows[0][0]]] * n_pad
    stop_flags = np.zeros(engine.batch, bool)
    stop_flags[b:] = True

    def scan_token(i: int, tok: int) -> str | None:
        """Shared per-token body of both batch paths: eos / marker /
        stop-sequence semantics live exactly once. Returns the decoded
        piece to emit, or None when row i just STOPPED (finish[i] set;
        the caller applies its own retirement mechanics)."""
        if tok == tokenizer.eos_id:
            finish[i] = "stop"
            return None
        piece = tokenizer.decode_piece(prev[i], tok).decode(
            "utf-8", errors="replace")
        prev[i] = tok
        tails[i] = (tails[i] + piece)[-tail_len:]
        if (any(m in tails[i] for m in markers)
                or (stops and any(s in tails[i] for s in stops))):
            finish[i] = "stop"
            return None
        emitted[i] += 1
        return piece

    # borrow the scheduler's engine for the whole-batch run: exclusive()
    # drains in-flight slot requests, then blocks the step loop until the
    # block exits. A real `with` (not manual __enter__/__exit__(None,..)):
    # a crash inside the borrow must propagate THROUGH the supervised
    # context manager so EngineSupervisor recovery runs, and a generator
    # teardown mid-stream (GeneratorExit) still unwinds it and releases
    # the scheduler. Everything fallible was parsed above.
    with sched.exclusive():
        saved_temp = sampler.temperature
        saved_rng_state = None
        if req_temp is not None:
            sampler.set_temp(req_temp)
        if req_seed is not None:
            saved_rng_state = sampler.rng_state
            sampler.set_seed(req_seed)
        try:
            engine.reset()  # slots drained; the borrowed cache starts clean
            if state.lookup_decode > 0 and sampler.temperature == 0.0:
                # greedy batch requests SPECULATE
                # (Engine.generate_batch_lookup — per-row drafts, one
                # verify forward per step, exact per-row greedy parity).
                # Collected, not streamed: text-level stop
                # sequences trim each row post-hoc — a stopped row may
                # have burned some extra forwards, which multi-token
                # accepts more than repay; the batch cache resets per
                # request, so the overrun positions leak nothing.
                # For STREAMING requests the collect runs on a helper
                # thread so keepalive events flow meanwhile (first byte
                # within KEEPALIVE_SECS, not full batch completion —
                # ADVICE r5). Non-streaming requests collect inline: a
                # keepalive has no one to reach, and keeping the whole
                # collect before the first yield preserves the clean
                # 400/503 mapping at the handler's next(gen)
                if want_stream:
                    box: dict = {}

                    def _collect():
                        try:
                            box["outs"] = engine.generate_batch_lookup(
                                rows, n_gen, eos_id=tokenizer.eos_id,
                                draft_len=state.lookup_decode,
                                vocab_size=tokenizer.vocab_size,
                                stop_flags=stop_flags)
                        except BaseException as e:  # noqa: BLE001 —
                            box["err"] = e  # re-raised on the generator
                    t = threading.Thread(target=_collect, daemon=True)
                    t.start()
                    try:
                        while True:
                            t.join(timeout=KEEPALIVE_SECS)
                            if not t.is_alive():
                                break
                            yield ("keepalive", None)
                    finally:
                        # a torn-down generator (client disconnect) must
                        # NOT release the exclusive borrow while the
                        # collect thread still drives the engine — block
                        # until done
                        t.join()
                    if "err" in box:
                        # inside the exclusive borrow: engine failures
                        # walk the same supervisor recovery as the sync
                        # path did
                        raise box["err"]
                    outs = box["outs"]
                else:
                    outs = engine.generate_batch_lookup(
                        rows, n_gen, eos_id=tokenizer.eos_id,
                        draft_len=state.lookup_decode,
                        vocab_size=tokenizer.vocab_size,
                        stop_flags=stop_flags)
                for i in range(b):
                    for tok in outs[i]:
                        piece = scan_token(i, tok)
                        if piece is None:
                            break
                        yield ("piece", (i, piece))
            else:
                for step in engine.generate_batch_stream(
                        rows, n_gen, sampler, stop_flags=stop_flags):
                    for i, tok in enumerate(step):
                        if tok is None or stop_flags[i]:
                            continue
                        piece = scan_token(i, tok)
                        if piece is None:
                            stop_flags[i] = True
                            continue
                        yield ("piece", (i, piece))
        finally:
            sampler.set_temp(saved_temp)
            if saved_rng_state is not None:
                sampler.rng_state = saved_rng_state
            engine.reset()  # the batch cache holds nothing reusable
    yield ("done", {
        "finish_reasons": finish,
        "prompt_tokens": n_prompt_toks,
        "completion_tokens": sum(emitted),
    })


def load_server_session(state: ApiState, path: str) -> None:
    """Restore a previous server process's prefix cache + token history
    (Engine.load_session — refuses a mismatched model via the content
    fingerprint). A follow-up request whose prompt extends the saved
    conversation then re-prefills only its suffix, and the response is
    byte-identical to the no-restart path (net-new — the reference resets
    all state per request AND per process, ref: dllama-api.cpp:236-249)."""
    tokens = state.engine.load_session(path)
    # the cache holds K/V for exactly engine.pos positions; tokens beyond
    # that (a chat's final unstepped token) must not count as cached
    state.cached_tokens = tokens[: state.engine.pos]


def save_server_session(state: ApiState, path: str) -> bool:
    """Persist the live prefix cache + its token history
    (Engine.save_session). Called on server shutdown — the cache fetch is
    O(pos * layers * kv_dim) host bytes, too heavy per-request for big
    models but free at exit.

    A shutdown landing mid-request (client disconnect, signal) leaves
    cached_tokens empty while engine.pos is large — saving then would
    clobber a previously good file with an unusable one, so the save is
    SKIPPED (False) and any prior file stays; it is self-consistent (its
    cache bytes came from the file's own tokens) even though the live
    engine moved past it. The cache is also never saved beyond the token
    history that describes it."""
    if not state.cached_tokens:
        return False
    eng = state.engine
    eng.pos = min(eng.pos, len(state.cached_tokens))
    eng.save_session(path, tokens=state.cached_tokens)  # atomic (tmp+rename)
    return True


def _chunk_env(rid: str, created: int, model: str, index: int,
               delta: dict, finish_reason) -> dict:
    """One SSE chat.completion.chunk envelope (shared by the single- and
    batch-request streams; only the choice index differs between them)."""
    return {"id": rid, "object": "chat.completion.chunk", "created": created,
            "model": model,
            "choices": [{"index": index, "delta": delta,
                         "finish_reason": finish_reason}]}


def _completion_env(rid: str, created: int, model: str, choices: list,
                    prompt_tokens: int, completion_tokens: int) -> dict:
    """The non-streamed chat.completion envelope + usage
    (ref: types.hpp:10-91)."""
    return {"id": rid, "object": "chat.completion", "created": created,
            "model": model, "choices": choices,
            "usage": {"prompt_tokens": prompt_tokens,
                      "completion_tokens": completion_tokens,
                      "total_tokens": prompt_tokens + completion_tokens}}


def _text_chunk_env(rid: str, created: int, model: str, text: str,
                    finish_reason) -> dict:
    """One SSE text_completion chunk for the raw /v1/completions route."""
    return {"id": rid, "object": "text_completion", "created": created,
            "model": model,
            "choices": [{"index": 0, "text": text,
                         "finish_reason": finish_reason}]}


def _text_completion_env(rid: str, created: int, model: str, text: str,
                         finish_reason, prompt_tokens: int,
                         completion_tokens: int) -> dict:
    """The non-streamed text_completion envelope (/v1/completions)."""
    return {"id": rid, "object": "text_completion", "created": created,
            "model": model,
            "choices": [{"index": 0, "text": text,
                         "finish_reason": finish_reason}],
            "usage": {"prompt_tokens": prompt_tokens,
                      "completion_tokens": completion_tokens,
                      "total_tokens": prompt_tokens + completion_tokens}}


def make_handler(state: ApiState):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *fargs):  # quiet
            pass

        def _json(self, code: int, obj: dict,
                  retry_after: float | None = None) -> None:
            data = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            if retry_after is not None:
                # overload/recovery rejections tell the client WHEN to come
                # back instead of letting it hammer or queue unboundedly
                self.send_header("Retry-After",
                                 str(max(1, int(round(retry_after)))))
            self.end_headers()
            self.wfile.write(data)

        # SSE chunked streaming (ref: dllama-api.cpp:125-145,183-200)
        def _sse_start(self) -> None:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self.end_headers()

        def _sse(self, obj: dict) -> None:
            self.wfile.write(b"data: " + json.dumps(obj).encode() + b"\n\n")
            self.wfile.flush()

        def _sse_done(self) -> None:
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()

        def do_GET(self):
            if self.path == "/v1/models":
                # ref: dllama-api.cpp:316-322
                self._json(200, {"object": "list", "data": [
                    {"id": state.model_name, "object": "model",
                     "created": int(time.time()), "owned_by": "user"}]})
            elif self.path in ("/", "/health", "/healthz"):
                # liveness: the process is up and serving HTTP — true even
                # while the engine recovers (that is /readyz's business) or
                # the server drains (it reports so, but stays 200: a
                # liveness-restart would cut the drain short). The build
                # block answers in EVERY tier (never 404s off a launch
                # flag — the PR-8 rule): version skew across a replica
                # fleet is an outage class, and the probe everyone
                # already scrapes is where it must show.
                self._json(200, {"status": "draining" if state.draining
                                 else "ok",
                                 "build": state.build_info()})
            elif self.path == "/readyz":
                self._readyz()
            elif self.path == "/stats":
                # serving observability: TTFT/ITL percentiles, slot
                # occupancy, queue depth (runtime/stats.ServeStats). A
                # stats read must never be the thing that allocates the
                # batched cache — report idle until a request builds it.
                if state.serve_batch <= 0:
                    # legacy tier: the speculative accept record still
                    # answers (a tier must not lose the family to a
                    # launch flag — the scheduler tiers carry theirs on
                    # the summary)
                    payload = {"scheduler": "off",
                               "spec": state.spec_stats.summary()}
                elif state._scheduler is None:
                    payload = {"scheduler": "idle"}
                else:
                    # supervisor summary: scheduler counters (totals carried
                    # across recoveries) + the resilience block
                    payload = state._scheduler.summary()
                # multihost root: the control-plane block (heartbeat
                # counters, peer losses, phase — runtime/stats.ClusterStats)
                from ..parallel.multihost import cluster_summary
                cluster = cluster_summary()
                if cluster is not None:
                    payload["cluster"] = cluster
                    # the measured wire ledger, hoisted as its own block
                    # (dlwire): per-peer bytes/frames by MSG kind and
                    # direction, heartbeat RTT, clock offsets
                    if cluster.get("wire"):
                        payload["wire"] = cluster["wire"]
                if "kv_transfer" not in payload:
                    # legacy/idle/single-supervisor tiers: the transfer
                    # plane cannot exist here (it needs replicas), but
                    # the family must not vanish off a launch flag —
                    # the block answers enabled=False (router tiers
                    # carry the real aggregate on their summary)
                    from ..runtime.stats import KVTransferStats
                    payload["kv_transfer"] = KVTransferStats().summary()
                # the fleet brain's block (runtime/fleet.py): autoscale
                # decisions, ladder rung, per-tenant accounting — the
                # same tier-invariance rule, so an idle/legacy tier
                # answers enabled=False instead of losing the family
                from ..runtime.stats import FleetStats
                payload["fleet"] = (state._fleet.summary()
                                    if state._fleet is not None
                                    else FleetStats().summary())
                payload["frontdoor"] = state.frontdoor.summary()
                from ..runtime.profiler import PROFILER
                if PROFILER.last_counters is not None:
                    # the serving counters at the last capture's two ends,
                    # and what that capture says of itself
                    payload["capture"] = {**PROFILER.last_counters,
                                          "report": PROFILER.last_report}
                from ..runtime.trace import TRACER
                if TRACER.enabled:
                    payload["trace"] = TRACER.summary()
                if state.autosize:
                    # the startup auto-sizing decision (chosen shape +
                    # every input) — present in EVERY scheduler state,
                    # idle included: the decision was made at startup
                    payload["autosize"] = state.autosize
                self._json(200, payload)
            elif self.path == "/metrics":
                self._metrics()
            elif (self.path == "/admin/trace"
                  or self.path.startswith("/admin/trace?")):
                self._admin_trace()
            else:
                self._json(404, {"error": "not found"})

        def _metrics(self) -> None:
            """GET /metrics — Prometheus text exposition, identical names
            in every serving tier (legacy single-engine, --serve-batch
            supervisor, --replicas thread router, --replica-procs/-hosts
            process router): the renderer consumes the SAME summary dict
            /stats already serves, so a tier cannot drift its own metric
            namespace. Answers in every tier (legacy/idle emit process-
            level series only) — a scrape target must never 404 off a
            launch flag."""
            from ..parallel.multihost import cluster_summary
            from ..runtime.trace import TRACER, render_prometheus

            # mode comes from the CONFIG, not the lazily-built front
            # door: a router tier must label its series mode="router"
            # from the first scrape (a label flip after the first
            # request would split every dllama_up series in two)
            if state.serve_batch <= 0:
                payload, mode, st = None, "legacy", "off"
            else:
                mode = "router" if state.router_mode else "scheduler"
                if state._scheduler is None:
                    # a scrape must never be the thing that allocates
                    # the batched cache (same rule as /stats, /readyz)
                    payload, st = None, "idle"
                else:
                    payload, st = state._scheduler.summary(), None
            cluster = cluster_summary()
            payload = dict(payload or {})
            if cluster is not None:
                payload["cluster"] = cluster
            if state.autosize:
                # dllama_autosize_* gauges from the startup decision —
                # visible from the FIRST scrape (idle included)
                payload["autosize"] = state.autosize
            # device-tier blocks for the tiers whose summary has none:
            # the compile ledger is process-global (legacy engines mint
            # through it too — the supervisor summary carries the same
            # singleton), and on NON-router tiers the engine's HBM is
            # live memory worth scraping. Router tiers deliberately
            # carry NO top-level hbm (runtime/router.Router.summary —
            # per-replica blocks are the truth there; state.engine is
            # an idle template whose headroom would mislead the batch
            # auto-sizing).
            if "compiles" not in payload:
                from ..runtime.profiler import COMPILES

                payload["compiles"] = COMPILES.summary()
            if "spec" not in payload and not state.router_mode:
                # legacy/idle tiers: the process-level accept record
                # (router tiers carry the family per replica — the
                # aggregate summary deliberately has no top-level block)
                payload["spec"] = state.spec_stats.summary()
            if "kv_transfer" not in payload:
                # same tier-invariance rule for the transfer plane: a
                # legacy/idle scrape renders the family as enabled=False
                from ..runtime.stats import KVTransferStats
                payload["kv_transfer"] = KVTransferStats().summary()
            if "fleet" not in payload:
                # dllama_fleet_* in every tier incl. idle: enabled=False
                # zeros until the controller exists (same rule again)
                from ..runtime.stats import FleetStats
                payload["fleet"] = (state._fleet.summary()
                                    if state._fleet is not None
                                    else FleetStats().summary())
            if ("hbm" not in payload and state.engine is not None
                    and not state.router_mode):
                from ..runtime.profiler import hbm_ledger

                try:
                    payload["hbm"] = hbm_ledger(state.engine)
                except Exception:  # noqa: BLE001 — a weightless front
                    pass           # template has no ledger-able arrays
            data = render_prometheus(payload, tracer=TRACER,
                                     model=state.model_name, mode=mode,
                                     state=st,
                                     build=state.build_info()).encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _admin_trace(self) -> None:
            """GET /admin/trace[?n=200|?id=TID] — the flight-recorder
            ring as JSONL (docs/observability.md schema): first line the
            clock anchor, then one event per line, wall timestamps
            attached at export. Operator surface, so the same guard as
            the POST /admin/* verbs (loopback or --admin-token)."""
            if not _admin_authorized(state, self.client_address[0],
                                     self.headers.get("Authorization")):
                self._json(403, {"error": "admin endpoints need loopback "
                                          "or a valid --admin-token "
                                          "bearer"})
                return
            from urllib.parse import parse_qs, urlparse

            from ..runtime.trace import TRACER

            if not TRACER.enabled:
                self._json(404, {"error": "tracing off (start with "
                                          "--trace)"})
                return
            from ..runtime.trace import EVENT_KINDS

            try:
                # keep_blank_values: "kind=" must be rejected as garbage
                # below, not silently dropped into an unfiltered dump
                q = parse_qs(urlparse(self.path).query,
                             keep_blank_values=True)
                tid = int(q["id"][0]) if "id" in q else None
                n = int(q.get("n", ["200"])[0])
                if n < 0 or (tid is not None and tid < 0):
                    # a negative n would slice the WRONG end of the ring
                    # (evs[-n:] == evs[n:]) — reject, don't dump
                    raise ValueError(n)
                # kind= / since_ms= filters: validated, 400 on garbage —
                # a typo'd kind must not silently return an empty (or
                # unfiltered) dump an operator then misreads
                kind = q["kind"][0] if "kind" in q else None
                if kind is not None and kind not in EVENT_KINDS:
                    raise ValueError(kind)
                since_ms = (float(q["since_ms"][0]) if "since_ms" in q
                            else None)
                if since_ms is not None and not since_ms >= 0:
                    # `not >=` also rejects NaN, which every ts compare
                    # below would silently pass
                    raise ValueError(since_ms)
            except (ValueError, IndexError):
                self._json(400, {"error": "bad request"})
                return
            filtered = kind is not None or since_ms is not None
            # with filters on, filter over the WHOLE ring then tail n —
            # slicing first would make n pre-filter events, so a sparse
            # kind could return nothing even though matches exist
            events = TRACER.by_id(tid) if tid is not None \
                else TRACER.recent(0 if filtered else n)
            if kind is not None:
                events = [e for e in events if e.get("kind") == kind]
            if since_ms is not None:
                cut = time.perf_counter() - since_ms / 1e3
                events = [e for e in events if e.get("ts", 0.0) >= cut]
            if tid is None and filtered and n:
                events = events[-n:]
            lines = [json.dumps({"anchor_wall": TRACER.anchor_wall,
                                 "anchor_mono": TRACER.anchor_mono,
                                 "events": len(events)})]
            lines += [json.dumps({**e,
                                  "ts_wall": TRACER.to_wall(e["ts"])})
                      for e in events]
            data = ("\n".join(lines) + "\n").encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _readyz(self) -> None:
            """Readiness = engine healthy AND queue under bound (and not
            draining). 503 + Retry-After otherwise — the load balancer's
            signal to route elsewhere."""
            if state.cluster_lost is not None:
                # a cluster peer is gone: this replica cannot serve until
                # an operator restores it (the process is about to take
                # its diagnostic exit — answer honestly meanwhile)
                self._json(503, {"status": "cluster_lost",
                                 "detail": state.cluster_lost.summary()},
                           retry_after=30.0)
            elif state.draining:
                self._json(503, {"status": "draining"}, retry_after=1.0)
            elif state.serve_batch <= 0:
                # legacy single-engine server: always ready (requests
                # serialize behind engine_lock, no supervised loop)
                self._json(200, {"status": "ready", "scheduler": "off"})
            elif state._scheduler is None:
                # supervisor builds on first request; a readiness probe
                # must not be the thing that allocates the batched cache
                self._json(200, {"status": "ready", "scheduler": "idle"})
            else:
                sup = state._scheduler
                payload = {"state": sup.state}
                if state.router_mode:
                    # multi-replica tier: readiness is ANY-replica (one
                    # failure must not unready the service); the per-
                    # replica states ride along for the operator
                    # suffix the ROUTER-level conditions the supervisor
                    # state can't see — a replica can be supervisor-ready
                    # yet unrouted (drained or circuit open), and the
                    # operator needs to see WHY from the probe body
                    # a replica draining FOR REAP (fleet scale-down) is
                    # expected capacity loss, not ill health: it shows
                    # here as /reaping but never flips fleet readiness
                    # (Router.state + _routable exclude reap handles)
                    payload["replicas"] = {
                        f"r{h.id}": (h.state
                                     + ("/draining" if h.draining else "")
                                     + ("/reaping"
                                        if getattr(h, "reap", False)
                                        else "")
                                     + ("/breaker_open"
                                        if h.open_until > 0.0 else ""))
                        for h in sup.replicas}
                if sup.ready:
                    self._json(200, {"status": "ready", **payload})
                else:
                    self._json(503, {"status": "unready", **payload},
                               retry_after=sup._retry_after())

        def do_POST(self):
            if self.path.startswith("/admin/"):
                # operator surface: dispatched BEFORE the draining check —
                # an operator must be able to reset a breaker or undrain
                # a replica while the front door refuses client traffic
                self._admin_post()
                return
            if self.path not in ("/v1/chat/completions", "/v1/completions",
                                 "/v1/batch/completions"):
                self._json(404, {"error": "not found"})
                return
            if state.draining:
                # graceful drain: in-flight requests finish, NEW work is
                # refused fast so the client retries a live replica
                self._json(503, {"error": "server draining"},
                           retry_after=2.0)
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError):
                self._json(400, {"error": "bad request"})
                return
            if self.path == "/v1/batch/completions":
                self._batch_post(body)
            else:
                self._chat_post(body,
                                chat=self.path == "/v1/chat/completions")

        def _admin_post(self) -> None:
            """Operator endpoints (docs/operations.md "Multi-replica
            operations"): loopback-guarded (403 otherwise), never
            404-dependent on launch flags once --serve-batch is on.

              POST /admin/reset_breaker   {replica?: i}  — operator
                   half-open for the engine breaker (BROKEN state) and
                   the router circuit; omitting `replica` resets ALL.
                   This is the HTTP face of reset_breaker(): before it,
                   a BROKEN supervisor in api mode was an outage only a
                   Python REPL could end.
              POST /admin/drain_replica   {replica: i, timeout?: s}
              POST /admin/restart_replica {replica: i, timeout?: s}
              POST /admin/undrain_replica {replica: i}
                   — the rolling-restart recipe, one replica at a time
                   (multi-replica servers only)."""
            if not _admin_authorized(state, self.client_address[0],
                                     self.headers.get("Authorization")):
                self._json(403, {"error": "admin endpoints need loopback "
                                          "or a valid --admin-token "
                                          "bearer"})
                return
            if (self.path == "/admin/profile"
                    or self.path.startswith("/admin/profile?")):
                # on-demand capture: ALL tiers, legacy included — routed
                # before the supervised-scheduler checks below
                self._admin_profile()
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                replica = body.get("replica")
                if replica is not None:
                    replica = int(replica)
                timeout = float(body.get("timeout", 30.0))
            except (ValueError, TypeError, json.JSONDecodeError):
                self._json(400, {"error": "bad request"})
                return
            if state.serve_batch <= 0:
                self._json(404, {"error": "no supervised scheduler "
                                          "(start with --serve-batch N)"})
                return
            sup = state._scheduler
            if sup is None:
                # nothing built yet — nothing to reset or drain; answer
                # idempotently rather than building the engine stack
                # from an admin poke
                self._json(200, {"status": "idle"})
                return
            from ..runtime.router import Router
            is_router = isinstance(sup, Router)
            if replica is not None and not (
                    is_router and 0 <= replica < len(sup.replicas)):
                n = len(sup.replicas) if is_router else 1
                self._json(400, {"error": f"no replica {replica} "
                                 f"(tier has {n})"})
                return
            if self.path == "/admin/reset_breaker":
                if is_router:
                    sup.reset_breaker(replica)
                else:
                    sup.reset_breaker()
                self._json(200, {"status": "ok", "state": sup.state})
            elif self.path in ("/admin/drain_replica",
                               "/admin/restart_replica",
                               "/admin/undrain_replica"):
                if not is_router or replica is None:
                    self._json(400, {"error": "replica operations need "
                                              "--replicas N > 1 and a "
                                              "replica index"})
                    return
                if self.path == "/admin/drain_replica":
                    ok = sup.drain_replica(replica, timeout=timeout)
                    self._json(200, {"status": "drained" if ok
                                     else "drain_timeout",
                                     "replica": replica})
                elif self.path == "/admin/restart_replica":
                    sup.restart_replica(replica, timeout=timeout)
                    self._json(200, {"status": "restarted",
                                     "replica": replica})
                else:
                    sup.undrain_replica(replica)
                    self._json(200, {"status": "ok", "replica": replica})
            else:
                self._json(404, {"error": "not found"})

        def _admin_profile(self) -> None:
            """POST /admin/profile?ms=N — write one jax.profiler trace of
            the next N milliseconds (docs/observability.md "Device
            tier"). Synchronous: the 200 means the trace is on disk
            (the threaded accept loop keeps serving meanwhile). On the
            process tier the verb relays as RMSG_PROFILE into every
            replica worker — each captures into its own per-worker dir,
            concurrently, and the response lists them; otherwise the
            capture runs in THIS process (legacy, supervisor, and
            thread-router tiers all share one jax runtime). 409 when a
            capture is already running (jax.profiler is process-global).
            Admin-guarded like every /admin/* verb — a trace names every
            op and shape on the box."""
            import os
            import tempfile
            from urllib.parse import parse_qs, urlparse

            try:
                q = parse_qs(urlparse(self.path).query)
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                ms = (float(q["ms"][0]) if "ms" in q
                      else float(body.get("ms", 100.0)))
                if not 0.0 < ms <= 60_000.0:  # also rejects NaN
                    raise ValueError(ms)
            except (ValueError, TypeError, json.JSONDecodeError):
                self._json(400, {"error": "bad request: ms must be in "
                                          "(0, 60000]"})
                return
            sup = state._scheduler
            if sup is None and (state.replica_procs
                                or state.replica_hosts):
                # process tier, front door unbuilt: the device work
                # lives in workers that don't exist yet — answer idle
                # like the other admin verbs, never a 200 over a
                # parent-only (deviceless) capture
                self._json(200, {"status": "idle"})
                return
            if sup is not None and hasattr(sup, "profile"):
                workers = sup.profile(ms)  # Router: RMSG_PROFILE relay
                if workers is not None:    # None = no remote replicas
                    self._json(200, {"status": "ok", "ms": ms,
                                     "workers": workers})
                    return
            from ..runtime.profiler import PROFILER

            base = state.profile_dir or tempfile.mkdtemp(prefix="dlprof-")
            target = os.path.join(base,
                                  f"profile-{int(time.time() * 1e3):x}")
            def counters() -> dict:
                from ..runtime.stats import WINDOW_COUNTERS

                now = sup.summary() if hasattr(sup, "summary") else {}
                return {k: now[k] for k in ("steps", *WINDOW_COUNTERS)
                        if k in now}

            try:
                out = PROFILER.capture(target, ms, counters)
            except RuntimeError as e:  # a capture is already running
                self._json(409, {"error": str(e)}, retry_after=ms / 1e3)
                return
            self._json(200, {"status": "ok", **out})

        def _batch_post(self, body: dict) -> None:
            """POST /v1/batch/completions — up to serve_batch prompts in one
            batched decode. Response mirrors the chat shape with one choice
            per row (index = row); SSE chunks tag their row via `index`."""
            if state.serve_batch <= 0:
                self._json(404, {
                    "error": "batch endpoint off (start with --serve-batch N)"})
                return
            rid = f"batchcmpl-{int(time.time()*1000):x}"
            created = int(time.time())
            stream = bool(body.get("stream", False))
            gen = _batch_completion_chunks(state, body)
            try:
                first = next(gen)
            except (PromptTooLong, BadRequest) as e:
                self._json(400, {"error": str(e)})
                return
            except EngineUnready as e:
                # the exclusive borrow is refused while recovering/draining
                self._json(503, {"error": str(e), "state": e.state},
                           retry_after=e.retry_after)
                return
            except Exception as e:  # noqa: BLE001 — a crash inside the
                # borrow already triggered supervisor recovery (resilience
                # .exclusive); the client gets a retryable 503, not a
                # dropped connection
                self._json(503, {"error": f"engine failure: "
                                          f"{type(e).__name__}: {e}"},
                           retry_after=1.0)
                return

            def events():
                yield first
                yield from gen

            if stream:
                self._sse_start()
                usage = None
                try:
                    for kind, payload in events():
                        if kind == "piece":
                            i, piece = payload
                            self._sse(_chunk_env(rid, created,
                                                 state.model_name,
                                                 i, {"content": piece},
                                                 None))
                        elif kind == "keepalive":
                            # SSE comment frame: bytes on the wire while
                            # the collected lookup path runs, invisible
                            # to the client's event parser
                            self.wfile.write(b": keepalive\n\n")
                            self.wfile.flush()
                        else:
                            usage = payload
                except Exception as e:  # noqa: BLE001 — an engine crash
                    # AFTER the 200/SSE start (e.g. surfacing behind the
                    # keepalives): same mid-stream contract as the
                    # scheduler path — an explicit structured error event
                    # and a terminated stream, never a dropped connection
                    # (supervisor recovery already ran via exclusive())
                    self._sse({"error": f"engine failure: "
                                        f"{type(e).__name__}: {e}"})
                    self._sse_done()
                    return
                for i, fr in enumerate(usage["finish_reasons"]):
                    self._sse(_chunk_env(rid, created, state.model_name,
                                         i, {}, fr))
                self._sse_done()
                return

            texts: dict[int, str] = {}
            usage = None
            for kind, payload in events():
                if kind == "piece":
                    i, piece = payload
                    texts[i] = texts.get(i, "") + piece
                elif kind == "done":
                    usage = payload
            self._json(200, _completion_env(
                rid, created, state.model_name,
                [{"index": i,
                  "message": {"role": "assistant",
                              "content": texts.get(i, "")},
                  "finish_reason": fr}
                 for i, fr in enumerate(usage["finish_reasons"])],
                usage["prompt_tokens"], usage["completion_tokens"]))

        def _chat_post(self, body: dict, chat: bool = True) -> None:
            """/v1/chat/completions (chat=True) and /v1/completions. With
            the scheduler on (--serve-batch), the request enqueues onto the
            shared slot scheduler and streams as its slot produces tokens —
            concurrent clients batch-decode together. Otherwise the legacy
            single-engine path runs, serialized by state.engine_lock under
            the threaded accept loop."""
            rid = (f"{'chatcmpl' if chat else 'cmpl'}-"
                   f"{int(time.time() * 1000):x}")
            created = int(time.time())
            stream = bool(body.get("stream", False))
            # multi-tenant identity (runtime/fleet.py): the body's
            # `tenant` field wins, the X-Tenant header fills in — folded
            # into the body HERE so the multi-host replay and the
            # scheduler path read one source of truth
            if "tenant" not in body and self.headers.get("X-Tenant"):
                body["tenant"] = self.headers.get("X-Tenant")

            multihost = state.multihost
            use_sched = state.serve_batch > 0 and not multihost
            # legacy single-engine path: serialize under the engine lock,
            # CONTEXT-MANAGED — the old bare acquire()/release() pair
            # could leave the lock held forever if anything raised
            # between the acquire and the try that released it, wedging
            # every later legacy request behind a dead handler thread
            lock = (contextlib.nullcontext() if use_sched
                    else state.engine_lock)
            with lock:
                if multihost:
                    # multi-host cluster: workers replay this exact request
                    # from the raw body (apps/dllama.py cmd_worker);
                    # broadcast before any engine work so their collectives
                    # line up with ours
                    from ..parallel import multihost as mh
                    mh.send_api(json.dumps(body).encode())

                # pull the first event before committing a 200 so prompt
                # errors can still return a clean 4xx (on the scheduler
                # path PromptTooLong surfaces from submit() — through the
                # queue, before any slot work)
                gen = (_sched_completion_chunks(state, body, chat=chat)
                       if use_sched else _completion_chunks(state, body))
                try:
                    first = next(gen)
                except PromptTooLong as e:
                    self._json(400, {"error": str(e)})
                    return
                except QueueFull as e:
                    # admission control: overload is a FAST 429, not an
                    # unboundedly growing queue
                    self._json(429, {"error": str(e)},
                               retry_after=e.retry_after)
                    return
                except ShedReject as e:
                    # the fleet brain's overload ladder turned the
                    # request away at the door: a structured 429 whose
                    # Retry-After derives from the LIVE drain rate
                    self._json(429, {"error": str(e), "shed": e.reason},
                               retry_after=e.retry_after)
                    return
                except EngineUnready as e:
                    self._json(503, {"error": str(e), "state": e.state},
                               retry_after=e.retry_after)
                    return

                def events():
                    yield first
                    yield from gen

                def drain():
                    # multi-host: workers replay the FULL request; if this
                    # handler aborts mid-stream (client disconnect), finish
                    # the engine steps anyway so cross-host collectives
                    # stay aligned
                    if multihost:
                        for _ in gen:
                            pass

                if chat:
                    def piece_env(p):
                        return _chunk_env(rid, created, state.model_name, 0,
                                          {"content": p}, None)

                    def final_env(fr):
                        return _chunk_env(rid, created, state.model_name, 0,
                                          {}, fr)
                else:
                    def piece_env(p):
                        return _text_chunk_env(rid, created,
                                               state.model_name, p, None)

                    def final_env(fr):
                        return _text_chunk_env(rid, created,
                                               state.model_name, "", fr)

                if stream:
                    self._sse_start()
                    usage = None
                    try:
                        for kind, payload in events():
                            if kind == "piece":
                                self._sse(piece_env(payload))
                            else:
                                usage = payload
                    finally:
                        drain()
                    if usage.get("error"):
                        # mid-stream failure: the client gets an EXPLICIT
                        # structured error event and a terminated stream
                        # (finish_reason "error"), never a silent hang
                        self._sse({"error": usage["error"]})
                    self._sse(final_env(usage["finish_reason"]))
                    self._sse_done()
                    return

                text = ""
                usage = {"finish_reason": "length", "prompt_tokens": 0,
                         "completion_tokens": 0}
                try:
                    for kind, payload in events():
                        if kind == "piece":
                            text += payload
                        else:
                            usage = payload
                finally:
                    drain()
                if usage.get("error") and not text:
                    # failed before any output: a clean retryable status
                    # beats a 200 carrying an empty completion
                    self._json(503, {"error": usage["error"]},
                               retry_after=1.0)
                    return
                if chat:
                    self._json(200, _completion_env(
                        rid, created, state.model_name,
                        [{"index": 0,
                          "message": {"role": "assistant", "content": text},
                          "finish_reason": usage["finish_reason"]}],
                        usage["prompt_tokens"], usage["completion_tokens"]))
                else:
                    self._json(200, _text_completion_env(
                        rid, created, state.model_name, text,
                        usage["finish_reason"], usage["prompt_tokens"],
                        usage["completion_tokens"]))

    return Handler


def serve(args) -> None:
    import os
    import signal
    import threading

    from .dllama import build_engine, check_session_flags

    session = getattr(args, "session", None)
    check_session_flags(args)
    serve_batch = getattr(args, "serve_batch", 0)
    if serve_batch:
        # the scheduler's batch engine is single-process by design (a
        # cluster needs request replay for b-row steps) and composes
        # with exactly ONE mesh axis: tp — the slot programs gate rows
        # by position, which is dp/sp/pp-agnostic only on paper, and tp
        # is what vocab sharding (ops/sharded_vocab.py) serves through.
        # Loud error beats a silently ignored flag for the rest.
        if getattr(args, "nnodes", 1) > 1:
            sys.exit("error: --serve-batch does not compose with --nnodes")
        if max(getattr(args, k, 1) for k in ("dp", "sp", "ep", "pp")) > 1:
            sys.exit("error: --serve-batch needs a single-process engine "
                     "(no --dp/--sp/--ep/--pp; --tp composes)")
        if getattr(args, "tp", 1) > 1 and (
                getattr(args, "replicas", 1) > 1
                or getattr(args, "replica_procs", 0)
                or getattr(args, "replica_hosts", None)):
            # one tp mesh = one engine's devices: replicas would contend
            # for the same chips (ROADMAP item 3's remaining work is
            # exactly workers spanning their own meshes)
            sys.exit("error: --serve-batch with --tp serves the "
                     "single-supervisor tier only (no --replicas/"
                     "--replica-procs/--replica-hosts)")
        if session:
            # scheduler slots are leased per request — there is no single
            # prefix cache a --session file could describe
            sys.exit("error: --serve-batch (continuous-batching scheduler) "
                     "does not compose with --session prefix persistence")
    # SLO-aware admission + auto-sizing flags (runtime/scheduler.
    # AdmissionPolicy / runtime/profiler.resolve_auto_shape): dead-flag
    # discipline like every knob family above — an SLO nobody enforces
    # must be a parse-time error
    slo_ttft = getattr(args, "slo_ttft_ms", None)
    slo_itl = getattr(args, "slo_itl_ms", None)
    if (slo_ttft is not None or slo_itl is not None) and not serve_batch:
        sys.exit("error: --slo-ttft-ms/--slo-itl-ms require "
                 "--serve-batch N|auto (the SLO-aware admission policy "
                 "adapts the scheduler's chunked-prefill width)")
    for name, v in (("--slo-ttft-ms", slo_ttft), ("--slo-itl-ms", slo_itl)):
        if v is not None and not v > 0:
            sys.exit(f"error: {name} must be > 0 "
                     "(omit the flag to disable)")
    prefix_blocks = getattr(args, "prefix_blocks", 0)
    auto_batch = serve_batch == "auto"
    auto_blocks = prefix_blocks == "auto"
    if getattr(args, "prefix_cache", False) and not serve_batch:
        # the radix cache lives on the slot scheduler (the legacy path
        # keeps its own single-session prefix reuse) — loud error beats
        # a silently ignored flag
        sys.exit("error: --prefix-cache requires --serve-batch N "
                 "(the radix cache serves the slot scheduler; the legacy "
                 "path already reuses its single session's prefix)")
    if not getattr(args, "prefix_cache", False) and (
            auto_blocks or prefix_blocks > 0
            or getattr(args, "prefix_block_len", None) is not None):
        # same principle one flag over: sizing knobs without the cache
        # itself would be silently dead configuration (block-len uses a
        # None sentinel, so an EXPLICIT value — even the default 32 —
        # is caught, and changing the default cannot break this check)
        sys.exit("error: --prefix-blocks/--prefix-block-len have no "
                 "effect without --prefix-cache")
    replicas = getattr(args, "replicas", None)
    replicas = 1 if replicas is None else replicas
    if replicas < 1:
        # explicit `--replicas 0` must hit this, not coerce to 1
        sys.exit("error: --replicas must be >= 1")
    replica_procs = getattr(args, "replica_procs", 0) or 0
    replica_hosts_raw = getattr(args, "replica_hosts", None)
    if replica_procs < 0:
        sys.exit("error: --replica-procs must be >= 1")
    if replica_procs and replica_hosts_raw:
        sys.exit("error: --replica-procs (local spawn) and "
                 "--replica-hosts (connect to pre-started workers) are "
                 "mutually exclusive")
    process_tier = bool(replica_procs or replica_hosts_raw)
    if process_tier and replicas > 1:
        sys.exit("error: --replicas (thread tier) does not compose with "
                 "--replica-procs/--replica-hosts (process tier) — pick "
                 "one replication boundary")
    if process_tier and getattr(args, "nnodes", 1) > 1:
        sys.exit("error: --replica-procs/--replica-hosts do not compose "
                 "with --nnodes (each worker is its own single-host "
                 "engine; see ROADMAP item 2 for the composition)")
    if replica_hosts_raw and getattr(args, "draft", None):
        # same contract as the --slo-* refusal below: pre-started
        # workers own their configs — the parent cannot arm drafting in
        # them, and a silently plain-decoding fleet the operator
        # believes is speculating is the dead-flag hazard this
        # discipline exists for (review-found)
        sys.exit("error: --draft does not reach --replica-hosts workers "
                 "(their configs are their operators'): pass --draft in "
                 "each worker's own config instead")
    if replica_hosts_raw and (slo_ttft is not None or slo_itl is not None):
        # pre-started workers were launched with their OWN configs; the
        # parent cannot arm a policy in them (unlike --replica-procs,
        # whose spawned workers receive the SLOs via the shipped worker
        # config) — an SLO nobody enforces must be a parse-time error
        sys.exit("error: --slo-ttft-ms/--slo-itl-ms do not reach "
                 "--replica-hosts workers (their configs are their "
                 "operators'): set the SLOs in each worker's own config "
                 "instead")
    if (auto_batch or auto_blocks) and process_tier:
        # resolve_auto_shape needs a LOCAL engine's real array shapes;
        # the process tier's parent holds only a spec template — refuse
        # clearly at parse time instead of crashing mid-build
        sys.exit("error: --serve-batch/--prefix-blocks 'auto' need a "
                 "ledger-capable local engine; the process tier's "
                 "workers own their engines — pass explicit sizes")
    if not serve_batch and (
            replicas > 1 or process_tier
            or getattr(args, "retry_budget", None) is not None
            or getattr(args, "route_policy", None) is not None):
        # the router fronts N slot schedulers — without --serve-batch
        # these flags would be silently dead configuration (retry-budget
        # and route-policy use None sentinels so even an explicit
        # default value is caught)
        sys.exit("error: --replicas/--replica-procs/--replica-hosts/"
                 "--retry-budget/--route-policy require --serve-batch N "
                 "(the failover router fronts the continuous-batching "
                 "scheduler)")
    if replicas == 1 and not process_tier and (
            getattr(args, "retry_budget", None) is not None
            or getattr(args, "route_policy", None) is not None):
        sys.exit("error: --retry-budget/--route-policy have no effect "
                 "without --replicas N > 1 or a process tier")
    # KV block transfer + disaggregation (runtime/kv_transfer.py):
    # dead-flag discipline — a transfer plane with nothing to transfer
    # (no prefix cache) or nobody to transfer between (one replica) is
    # silently-dead configuration
    kv_transfer = bool(getattr(args, "kv_transfer", False))
    tier_raw = getattr(args, "tier", None)
    if kv_transfer and not getattr(args, "prefix_cache", False):
        sys.exit("error: --kv-transfer moves published prefix-cache "
                 "blocks and requires --prefix-cache")
    n_fleet = (int(replica_procs) if replica_procs
               else len(str(replica_hosts_raw).split(","))
               if replica_hosts_raw else int(replicas))
    if kv_transfer and n_fleet < 2:
        sys.exit("error: --kv-transfer needs >= 2 replicas "
                 "(--replicas N, --replica-procs N, or --replica-hosts "
                 "h:p,...) — one replica has no sibling to transfer "
                 "with")
    tiers = None
    if tier_raw is not None:
        if not kv_transfer:
            sys.exit("error: --tier requires --kv-transfer (a prefill-"
                     "tier replica is useless unless its blocks can "
                     "move to the decode tier)")
        if replica_hosts_raw:
            sys.exit("error: --tier does not reach --replica-hosts "
                     "workers (their configs are their operators'): "
                     "set `tier` in each worker's own config — the "
                     "router adopts it from the health PONG")
        n_rep = int(replica_procs) if replica_procs else int(replicas)
        tiers = [t.strip() for t in str(tier_raw).split(",")]
        if len(tiers) == 1:
            tiers = tiers * n_rep
        if len(tiers) != n_rep:
            sys.exit(f"error: --tier lists {len(tiers)} roles for "
                     f"{n_rep} replicas (one value, or one per replica)")
        bad = [t for t in tiers if t not in ("prefill", "decode",
                                             "mixed")]
        if bad:
            sys.exit(f"error: --tier roles must be prefill|decode|"
                     f"mixed (got {bad[0]!r})")
        if all(t == "prefill" for t in tiers):
            sys.exit("error: --tier needs at least one decode or mixed "
                     "replica (prefill-tier replicas never serve "
                     "requests)")
    # fleet brain (runtime/fleet.py): same dead-flag discipline — an
    # autoscaling window nothing can scale, or tenant budgets nothing
    # enqueues fairly, must refuse at parse time, not silently no-op
    min_reps = getattr(args, "min_replicas", 0) or 0
    max_reps = getattr(args, "max_replicas", 0) or 0
    if min_reps < 0 or max_reps < 0:
        sys.exit("error: --min-replicas/--max-replicas must be >= 1")
    if (min_reps or max_reps) and not serve_batch:
        sys.exit("error: --min-replicas/--max-replicas require "
                 "--serve-batch N (the fleet controller scales the "
                 "replica set behind the scheduler front door)")
    if min_reps and max_reps and min_reps > max_reps:
        sys.exit(f"error: --min-replicas {min_reps} exceeds "
                 f"--max-replicas {max_reps}")
    if max_reps and replica_hosts_raw:
        sys.exit("error: autoscaling does not reach --replica-hosts "
                 "workers (their lifetimes are their operators'): the "
                 "controller can only spawn/reap locally supervised "
                 "replicas (--replicas/--replica-procs)")
    if max_reps and max_reps > n_fleet and not (replicas > 1
                                                or replica_procs):
        sys.exit("error: --max-replicas needs a replica tier to grow "
                 "(--replicas N or --replica-procs N)")
    tenant_budgets_raw = getattr(args, "tenant_budgets", None)
    if tenant_budgets_raw is not None:
        if not serve_batch:
            sys.exit("error: --tenant-budgets requires --serve-batch N "
                     "(weighted-fair queueing replaces the scheduler's "
                     "FIFO admission queue)")
        if replica_hosts_raw:
            # same contract as --draft/--slo-*: pre-started workers own
            # their configs — fairness the parent cannot arm worker-side
            # would silently degrade to FIFO where the queueing happens
            sys.exit("error: --tenant-budgets does not reach "
                     "--replica-hosts workers (their configs are their "
                     "operators'): set tenant_budgets in each worker's "
                     "own config instead")
        from ..runtime.fleet import parse_tenant_budgets
        try:
            # parse NOW so a malformed spec refuses at startup, never
            # mid-traffic in a worker process
            parse_tenant_budgets(tenant_budgets_raw)
        except ValueError as e:
            sys.exit(f"error: --tenant-budgets: {e}")
    trace_on = bool(getattr(args, "trace", False))
    if not trace_on and (
            getattr(args, "trace_dir", None)
            or getattr(args, "trace_sample", None) is not None
            or getattr(args, "trace_buffer", None) is not None
            or getattr(args, "trace_decode_every", None) is not None):
        # dead-flag discipline, same as the prefix/router knobs: sizing
        # a recorder that is off is silently-dead configuration
        sys.exit("error: --trace-dir/--trace-sample/--trace-buffer/"
                 "--trace-decode-every have no effect without --trace")
    if trace_on:
        sample = getattr(args, "trace_sample", None)
        if sample is not None and not 0.0 <= sample <= 1.0:
            sys.exit("error: --trace-sample must be in [0, 1]")
        from ..runtime.trace import TRACER
        TRACER.configure(
            capacity=getattr(args, "trace_buffer", None) or 8192,
            sample=1.0 if sample is None else float(sample),
            decode_every=getattr(args, "trace_decode_every", None) or 8,
            sink_dir=getattr(args, "trace_dir", None))
    # device-tier observability (runtime/profiler.py): the recompile
    # sentinel's freeze hangs off the slot scheduler (warmup arms the
    # sentinel) — without --serve-batch it is a dead flag
    freeze_compiles = bool(getattr(args, "freeze_compiles", False))
    if freeze_compiles and not serve_batch:
        sys.exit("error: --freeze-compiles requires --serve-batch N (the "
                 "sentinel arms at scheduler warmup)")
    if freeze_compiles:
        from ..runtime.profiler import COMPILES

        COMPILES.freeze = True
    replica_hosts = None
    if replica_hosts_raw:
        replica_hosts = []
        for spec in str(replica_hosts_raw).split(","):
            host, _, port = spec.strip().rpartition(":")
            if not host or not port.isdigit():
                sys.exit(f"error: --replica-hosts entry {spec.strip()!r} "
                         "is not host:port")
            replica_hosts.append((host, int(port)))
    worker_config = None
    if replica_procs:
        if not getattr(args, "model", None):
            sys.exit("error: --replica-procs workers load their own "
                     "weights and need --model")
        # one process per chip: worker i is given chip i (runtime/
        # replica_worker.chip_assignment_env), so on a TPU host more
        # workers than chips can never all come up — refuse at start-up
        # (counted from device nodes: the front door never asks JAX). Hosts
        # without a TPU (chips == 0) and runs pinned off it are exempt.
        from ..runtime.replica_worker import local_tpu_chips
        chips = local_tpu_chips()
        on_tpu = "tpu" in (os.environ.get("JAX_PLATFORMS") or "tpu")
        most = max(int(replica_procs), max_reps)
        if chips and on_tpu and most > chips:
            sys.exit(f"error: --replica-procs/--max-replicas {most} "
                     f"exceeds the {chips} TPU chip(s) on this host (each "
                     "worker process owns exactly one chip)")
        from ..runtime.replica_worker import config_from_cli_args
        worker_config = config_from_cli_args(args, serve_batch)

    if process_tier:
        # the workers own the weights — the parent reads only the .m
        # spec header (shape validation) + tokenizer: no N+1-th weight
        # copy locally, and a pure --replica-hosts router box holds none
        from .dllama import build_front_template
        engine, tokenizer, sampler = build_front_template(args)
    else:
        engine, tokenizer, sampler = build_engine(args)
    draft_spec = getattr(args, "draft", None)
    if draft_spec:
        # depth bound needs the spec — validate at STARTUP, not on the
        # first request (runtime/draft.parse_draft_spec already vetted
        # the format at parse time)
        from ..runtime.draft import parse_draft_spec
        kind, arg = parse_draft_spec(draft_spec)
        if kind == "self" and not 1 <= int(arg) < engine.spec.n_layers:
            sys.exit(f"error: --draft self:{arg}: depth must be in "
                     f"1..{engine.spec.n_layers - 1} (the model has "
                     f"{engine.spec.n_layers} layers)")
        if kind == "model" and getattr(engine, "mesh", None) is not None:
            # DraftModel.from_file refuses meshed targets — fail at
            # STARTUP where the mesh is known, not mid-serve inside the
            # lazily-built supervisor (review-found; the legacy api
            # path is the only way to combine --draft with a mesh,
            # --serve-batch already refuses meshes)
            sys.exit("error: --draft model:PATH needs a mesh-less "
                     "engine (use --draft self:<depth>, which shares "
                     "the target's sharded buffers)")
        if worker_config is not None:
            # the verify argmax truncates at the TOKENIZER vocab; the
            # workers have no tokenizer, so the bound ships in the config
            worker_config["draft_vocab"] = tokenizer.vocab_size
    prefix_block_len = getattr(args, "prefix_block_len", None) or 32
    if getattr(args, "prefix_cache", False):
        # validate the arena config against the REAL engine context at
        # startup — the supervisor builds lazily on the first request,
        # and a bad block length must be a CLI error, not a 500 every
        # request (PrefixCache.__init__ would assert there)
        bl = prefix_block_len
        if not 1 <= bl <= engine.seq_len:
            sys.exit(f"error: --prefix-block-len {bl} outside 1.."
                     f"{engine.seq_len} (the engine context)")
        if not auto_blocks and prefix_blocks < 0:
            sys.exit("error: --prefix-blocks must be >= 0 "
                     "(0 = the 2xBxcontext default, or 'auto')")
    autosize = None
    if auto_batch or auto_blocks:
        # resolve the sentinels against the REAL engine's ledger, once,
        # before any scheduler exists: the default-heuristic knee capped
        # by measured headroom. The decision record is
        # logged here and exported on /stats + /metrics so an operator
        # can always see what was chosen and why.
        from ..runtime.profiler import resolve_auto_shape
        try:
            autosize = resolve_auto_shape(
                engine, serve_batch=serve_batch,
                prefix_blocks=prefix_blocks,
                prefix_block_len=prefix_block_len, replicas=replicas,
                slo_itl_ms=slo_itl)
        except ValueError as e:
            sys.exit(f"error: {e}")
        serve_batch = autosize["serve_batch"]
        prefix_blocks = autosize["prefix_blocks"]
        inp = autosize["inputs"]
        print(f"⚖️  auto-sized: --serve-batch {serve_batch} "
              f"({autosize['serve_batch_basis']})"
              + (f", --prefix-blocks {prefix_blocks} "
                 f"({autosize['prefix_blocks_basis']})"
                 if auto_blocks else "")
              + f" — knee={inp['knee_rows']}, "
                f"headroom_bytes={inp['headroom_bytes']}, "
                f"slots_addable={inp['slots_addable']}")
    state = ApiState(engine, tokenizer, sampler,
                     lookup_decode=getattr(args, "lookup_decode", 0),
                     serve_batch=serve_batch,
                     serve_chunk=getattr(args, "serve_chunk", 0),
                     queue_depth=getattr(args, "queue_depth", 0),
                     request_deadline=getattr(args, "request_deadline", 0.0),
                     stall_timeout=getattr(args, "stall_timeout", 0.0),
                     prefix_cache=getattr(args, "prefix_cache", False),
                     prefix_blocks=prefix_blocks,
                     prefix_block_len=prefix_block_len,
                     slo_ttft_ms=slo_ttft, slo_itl_ms=slo_itl,
                     autosize=autosize,
                     draft=draft_spec,
                     draft_len=(getattr(args, "draft_len", None) or 7
                                if draft_spec else 0),
                     replicas=replicas,
                     retry_budget=(1 if getattr(args, "retry_budget", None)
                                   is None else args.retry_budget),
                     route_policy=(getattr(args, "route_policy", None)
                                   or "cache_aware"),
                     replica_procs=replica_procs,
                     replica_hosts=replica_hosts,
                     worker_config=worker_config,
                     admin_token=getattr(args, "admin_token", None),
                     profile_dir=getattr(args, "profile_dir", None),
                     kv_transfer=kv_transfer, tiers=tiers,
                     min_replicas=getattr(args, "min_replicas", 0) or 0,
                     max_replicas=getattr(args, "max_replicas", 0) or 0,
                     tenant_budgets=getattr(args, "tenant_budgets", None),
                     multihost=getattr(args, "nnodes", 1) > 1)
    if serve_batch:
        # build + warm the front door BEFORE listening: an engine that
        # cannot be built (out of memory, a compile the chip refuses, a
        # worker that dies at boot) must end the server with a traceback
        # and a non-zero exit here — not surface as a 500 on the first
        # request of a process that looked ready. ApiState itself stays
        # lazy (library users and tests probe it unbuilt).
        state.scheduler()
    if session and os.path.exists(session):
        load_server_session(state, session)
        print(f"💾 resumed session from {session} "
              f"({engine.pos} cached positions)")
    if state.multihost:
        # multihost api root: a lost worker means every future forward
        # would hang in an orphaned collective. Map the detection onto the
        # supervisor's BROKEN path first (structured cluster_peer_lost
        # error frames to anything in flight, circuit open) — were a
        # cluster-capable scheduler ever live — flip /readyz to 503
        # cluster_lost, give handler threads a beat to flush those frames,
        # then take the standard diagnostic exit (43): an orchestrator
        # restart beats a zombie that 503s forever
        from ..parallel import multihost as mh

        def _on_peer_lost(exc):
            state.cluster_lost = exc
            sup = state._scheduler
            if sup is not None:
                sup.trip_cluster(exc)
            time.sleep(0.5)
            mh.diagnostic_exit(exc)

        mh.install_peer_lost_exit(_on_peer_lost)
        mh.set_phase("serve")
    # threaded accept loop (daemon handler threads): the scheduler path
    # serves concurrent clients from one batched decode; legacy paths
    # serialize on state.engine_lock / Scheduler.exclusive
    server = ThreadingHTTPServer((args.host, args.port),
                                 make_handler(state))
    drain_timeout = getattr(args, "drain_timeout", 30.0)

    def _begin_drain(*_):
        # graceful drain (SIGTERM — docker stop, k8s rollout, systemd):
        # stop admitting (POSTs 503, /readyz unready), stop accepting,
        # let serve_forever return; the finally below finishes in-flight
        # work up to --drain-timeout, saves the session, and exits. The
        # default SIGTERM handler would exit WITHOUT unwinding the stack
        # — no drain, no save.
        state.draining = True
        threading.Thread(target=server.shutdown, daemon=True).start()

    def _hup(*_):
        # SIGHUP = the conventional "reload" signal: run the zero-failed-
        # requests rolling restart (drain + rebuild each replica in turn)
        # in a background thread — a signal handler must return fast, and
        # the restart takes seconds per replica. Router tiers only: a
        # single supervisor has no sibling to absorb traffic, so a
        # "rolling" restart of it would just be an outage.
        if not state.router_mode:
            print("🔁 SIGHUP ignored: rolling restart needs a replica "
                  "tier (--replicas/--replica-procs)")
            return
        print("🔁 SIGHUP: rolling restart started")

        def _run():
            # scheduler() builds lazily on first use — a SIGHUP that
            # arrives before any traffic must still restart, not no-op
            state.scheduler().rolling_restart()

        threading.Thread(target=_run, name="dllama-sighup-restart",
                         daemon=True).start()

    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGTERM, _begin_drain)
        if hasattr(signal, "SIGHUP"):
            signal.signal(signal.SIGHUP, _hup)
    print(f"🔌 dllama-api listening on {args.host}:{args.port}")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        state.draining = True
        server.server_close()
        if state._fleet is not None:
            # stop the fleet brain BEFORE draining the door: a scale
            # decision landing mid-shutdown would race the close below
            state._fleet.close()
        if state._scheduler is not None:
            # finish in-flight/queued scheduler work before exiting; past
            # the deadline, close() fails stragglers with structured
            # shutdown frames (no waiter ever hangs on a dead process)
            if state._scheduler.drain(timeout=drain_timeout):
                print("🔌 drained: all in-flight requests completed")
            else:
                print(f"🔌 drain deadline ({drain_timeout:.0f}s) elapsed; "
                      "failing stragglers")
            state._scheduler.close()
        if session:
            if save_server_session(state, session):
                print(f"💾 saved session to {session} "
                      f"({engine.pos} cached positions)")
            else:
                print("💾 no completed session to save "
                      f"(leaving {session} untouched)")
