"""Device-tier observability: compile ledger, HBM ledger, profiler capture.

PR 8's flight recorder (runtime/trace.py) made the HOST side legible —
spans, /metrics, the per-iteration step timeline — but the device stayed
a black box: nothing watched for post-warmup recompiles at runtime
(dlgrind's fingerprint gate is static-only), nobody accounted HBM by
category (the number ROADMAP item 1 needs to auto-size ``--serve-batch``
and ``--prefix-blocks``), and device time was attributable only by
hand-running ``jax.profiler`` offline. This module is the device half:

  * **Compile ledger + recompile sentinel** (``COMPILES``) — every
    executable the engine mints routes through :meth:`CompileLedger.watch`
    (``Engine._mint``), which times the first call (trace + compile wall
    ms) and records (key, wall ms, count). After ``Scheduler.warmup()``
    marks an engine's serving set warm, any NEW compile key emits a
    ``compile_after_warmup`` trace event + counter — the runtime twin of
    dlgrind's static fingerprint gate — and, under ``--freeze-compiles``,
    raises a structured ``RequestError`` BEFORE the compile runs. The
    ledger exports the ``dllama_compiles_total`` / ``dllama_compile_ms``
    /metrics families and the ``compiles`` /stats block, in every tier
    (replica workers run their own ledger; its block rides their stats
    reply like every other per-replica block).
  * **HBM ledger** (:func:`hbm_ledger`) — per-category live bytes from
    the engine's KNOWN array shapes (weights / KV slot cache / prefix
    arena / logits+workspace), reconciled against
    ``device.memory_stats()`` where the backend provides it (TPU/GPU;
    CPU test runs report the exact shape-derived bytes with device
    fields null), plus the headroom estimate — ``slots_addable`` /
    ``prefix_blocks_addable`` — that item 1's auto-sizing consumes.
    Exported as ``dllama_hbm_bytes{category=}`` gauges and the ``hbm``
    /stats block.
  * **On-demand capture** (:meth:`Profiler.capture`) — the
    ``POST /admin/profile?ms=`` body: one bounded ``jax.profiler`` trace
    written to a directory, refusals instead of concurrent captures
    (``jax.profiler`` is process-global). The Python tracer is off, and
    while the capture runs the tracer's spans (runtime/trace.py
    ``SPAN_NAMES``) are written as ``TraceAnnotation``s, so the host
    plane holds what the scheduler did on the device's clock.
    ``RMSG_PROFILE`` relays the verb into replica worker processes
    (per-worker capture dirs).

Everything here is host code running strictly pre/post device dispatch —
no jitted program changes, and the dlgrind fingerprint set is invariant
by construction (the watch wrapper swaps itself out of ``Engine._steps``
after the first call, so the steady-state hot path is the raw jitted
callable again). Docs: docs/observability.md ("Device tier").
"""

from __future__ import annotations

import re
import threading
import time

from .trace import TRACER

# -- compile ledger ---------------------------------------------------------


def _key_elem(x) -> str:
    if isinstance(x, tuple):  # nested shape/stop-id tuples: 16x2x4
        return "x".join(_key_elem(e) for e in x)
    return str(x)


def compile_key_str(key) -> str:
    """Engine compile-cache key -> a bounded, label-safe string (the
    ``key=`` label of ``dllama_compiles_total``). Tuple keys join with
    ':' (nested tuples with 'x'); bare ints are forward-segment widths;
    anything outside [0-9A-Za-z_:.x-] flattens to '_' so the string is
    a clean Prometheus label value and JSONL field."""
    if isinstance(key, tuple):
        s = ":".join(_key_elem(x) for x in key)
    elif isinstance(key, int):
        s = f"seg:{key}"
    else:
        s = str(key)
    return re.sub(r"[^0-9A-Za-z_:.x-]", "_", s)[:120]


class _CompileWatch:
    """First-call timer around one freshly-jitted executable: the first
    invocation is trace + compile + dispatch (jax compiles synchronously;
    execution is async), so its wall ms IS the number an operator needs —
    how long minting this key stalled serving. After that call the watch
    swaps the raw jitted callable back into ``engine._steps[key]``, so
    the steady-state hot path pays nothing; a caller holding a stale
    reference to the watch itself pays one attribute check."""

    __slots__ = ("_fn", "_key", "_engine", "_done")

    def __init__(self, engine, key, fn):
        self._engine = engine
        self._key = key
        self._fn = fn
        self._done = False

    def __call__(self, *args):
        if self._done:
            return self._fn(*args)
        eng = self._engine
        # sentinel BEFORE the compile: a frozen serving set refuses the
        # mint outright rather than paying for it first
        COMPILES.pre_compile(eng, self._key)
        t0 = time.perf_counter()
        kernels = _kernels_in(eng, self._fn, args)
        out = self._fn(*args)
        ms = (time.perf_counter() - t0) * 1e3
        self._done = True
        COMPILES.record(eng, self._key, ms, kernels=kernels)
        steps = getattr(eng, "_steps", None)
        if steps is not None and steps.get(self._key) is self:
            steps[self._key] = self._fn  # steady state: zero wrapper cost
        return out


_KERNEL_NAME_RE = re.compile(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"')


def kernel_call_sites(lowered_text: str) -> dict:
    """{kernel name: call sites} of the Pallas TPU kernels
    (`tpu_custom_call`s — q40_matmul, q40_expert_matmul, flash_attention,
    kv_cache_write)
    in a lowered (StableHLO) module. Sites, not executions: jit emits one
    function per distinct shape and a layer loop calls it many times —
    presence is the signal."""
    out: dict = {}
    for name in _KERNEL_NAME_RE.findall(lowered_text):
        out[name] = out.get(name, 0) + 1
    return out


def _kernels_in(engine, fn, args) -> dict | None:
    """kernel_call_sites of the program lowered for these arguments: the
    record that makes a silent trip to the XLA dequant path
    (ops/matmul.local_matmul — `use_pallas` on but the operands do not
    qualify, e.g. a prefill segment of more than pallas_q40.MAX_T rows)
    visible per executable. None = not inspected: the engine runs without
    compiled kernels (use_pallas off, or interpret mode) or `fn` is not a
    jitted callable. The lowering shares jit's trace cache with the call
    that follows, so it costs one extra jaxpr -> StableHLO pass per MINT,
    never per step."""
    if not getattr(engine, "use_pallas", False) or getattr(
            engine, "pallas_interpret", False):
        return None
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    return kernel_call_sites(lower(*args).as_text())


class CompileLedger:
    """Process-wide record of every executable mint (module singleton:
    ``COMPILES``). Compiles are rare by the fixed-compilation-key
    discipline the whole engine keeps, so an always-on ledger costs
    nothing on the hot path — only the mint moment is instrumented.
    The warm flag lives on the ENGINE (``Engine._compile_warm``), not
    here: a supervisor rebuild mints a fresh engine whose own warmup
    legitimately recompiles the serving set, and a global flag would
    misread those as post-warmup compiles."""

    MAX_KEYS = 256  # label-cardinality bound on the by_key map

    def __init__(self):
        self._lock = threading.Lock()
        self.freeze = False        # --freeze-compiles
        self.total = 0
        self.total_ms = 0.0
        self.after_warmup = 0      # compiles on an already-warm engine
        self.key_overflow = 0
        self.by_key: dict[str, dict] = {}  # dlrace: guarded-by(self._lock)

    def watch(self, engine, key, fn):
        """Wrap one freshly-jitted callable (the ``Engine._mint`` hook)."""
        return _CompileWatch(engine, key, fn)

    def pre_compile(self, engine, key) -> None:
        """The recompile sentinel, fired before a compile on a WARM
        engine: trace event + counter always; a structured error under
        ``--freeze-compiles`` (the runtime twin of dlgrind's static
        fingerprint gate — the offending caller fails, the compile never
        runs, the serving executables stay exactly the warmed set)."""
        if not getattr(engine, "_compile_warm", False):
            return
        ks = compile_key_str(key)
        with self._lock:
            self.after_warmup += 1
        if TRACER.enabled:
            TRACER.event("compile_after_warmup", 0, key=ks,
                         frozen=self.freeze)
        if self.freeze:
            from .scheduler import RequestError

            raise RequestError(
                "compile_after_warmup",
                f"new compile key {ks!r} after warmup with "
                "--freeze-compiles (the serving set is frozen; see "
                "docs/operations.md 'Recompile storms')",
                retryable=False)

    def record(self, engine, key, ms: float, *,
               kernels: dict | None = None) -> None:
        ks = compile_key_str(key)
        warm = bool(getattr(engine, "_compile_warm", False))
        with self._lock:
            self.total += 1
            self.total_ms += ms
            rec = self.by_key.get(ks)
            if rec is None:
                if len(self.by_key) >= self.MAX_KEYS:
                    self.key_overflow += 1
                else:
                    rec = self.by_key[ks] = {"count": 0, "ms": 0.0}
            if rec is not None:
                rec["count"] += 1
                rec["ms"] = round(rec["ms"] + ms, 3)
                rec["last_ms"] = round(ms, 3)
                # Pallas kernels in the minted program (see
                # _kernels_in; None = not inspected)
                rec["kernels"] = kernels
        if TRACER.enabled:
            TRACER.event("compile", 0, key=ks, ms=round(ms, 3), warm=warm)

    def summary(self) -> dict:
        """The ``compiles`` /stats block (and the /metrics source)."""
        from ..utils.compile_cache import COUNTS

        with self._lock:
            return {"total": self.total,
                    "total_ms": round(self.total_ms, 3),
                    "after_warmup": self.after_warmup,
                    "frozen": self.freeze,
                    "key_overflow": self.key_overflow,
                    # persistent compilation cache traffic (zeros until
                    # utils/compile_cache.ensure_compile_cache ran)
                    "persistent_cache_hits": COUNTS["hits"],
                    "persistent_cache_misses": COUNTS["misses"],
                    "by_key": {k: dict(v) for k, v in self.by_key.items()}}

    def reset(self) -> None:
        """Test isolation; the singleton survives."""
        with self._lock:
            self.freeze = False
            self.total = 0
            self.total_ms = 0.0
            self.after_warmup = 0
            self.key_overflow = 0
            self.by_key = {}


COMPILES = CompileLedger()


# -- HBM ledger -------------------------------------------------------------


def _tree_bytes(tree) -> int:
    """PER-DEVICE live bytes of a pytree (max across devices): sharded
    leaves count only the shard a device actually holds, replicated
    leaves count fully on every device. This is the number the 2.42
    GB/chip budget talks about — global ``nbytes`` would overstate a
    tp-sharded weight tp-fold (and understate what vocab sharding
    frees). On mesh-less engines every leaf lives whole on one device
    and this equals the old global sum."""
    import jax

    per_dev: dict = {}
    plain = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            plain += int(getattr(leaf, "nbytes", 0) or 0)
            continue
        this_leaf: dict = {}
        try:
            for sh in shards:
                d = sh.device.id
                this_leaf[d] = this_leaf.get(d, 0) + int(sh.data.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated buffers:
            # fall back to the leaf's PER-DEVICE share (global nbytes /
            # shard count), discarding the partial walk — adding global
            # bytes here would inflate a per-device sum up to
            # mesh-size-fold and shrink the auto-sizers' headroom
            n = max(len(shards), 1)
            plain += int(getattr(leaf, "nbytes", 0) or 0) // n
            continue
        for d, b in this_leaf.items():
            per_dev[d] = per_dev.get(d, 0) + b
    return (max(per_dev.values()) if per_dev else 0) + plain


def device_memory_stats():
    """{bytes_in_use, bytes_limit, per_device_bytes_in_use} from the local
    devices' allocators (in_use/limit are the first device's; the list
    has every local device, in id order — a tp mesh should show ~equal
    entries, a placement bug everything on device 0), or None where the
    backend has no allocator stats (CPU test runs)."""
    import jax

    try:
        all_ms = [d.memory_stats() for d in jax.local_devices()]
    except Exception:  # noqa: BLE001 — backend-dependent surface
        return None
    ms = all_ms[0] if all_ms else None
    if not ms or "bytes_in_use" not in ms:
        return None
    return {"bytes_in_use": int(ms["bytes_in_use"]),
            "bytes_limit": int(ms.get("bytes_limit", 0)) or None,
            "per_device_bytes_in_use": [int((m or {}).get("bytes_in_use", 0))
                                        for m in all_ms]}


def hbm_ledger(engine, prefix_cache=None, *, block_len: int | None = None,
               device_stats: dict | None | bool = True) -> dict:
    """Per-category live-bytes for one engine — the ``hbm`` block of
    /stats.

    Categories, all derived from KNOWN allocated shapes (exact for
    weights / KV slots / arena — they are real array ``nbytes``;
    logits+workspace is the modeled transient: the (B, vocab) f32 logits
    fetch plus one (B, chunk, dim) activation segment):

      * ``weights_bytes``      — every LAYER/norm param leaf (quantized
        tensors count their packed bytes). NOTE: thread-tier replicas SHARE weight
        buffers, so summing this across replica blocks multi-counts one
        allocation — the per-replica truth is kv+arena, the weights are
        per-process.
      * ``vocab_bytes``        — the embedding table + logits head
        (tok_emb/wcls), split out of weights so vocab sharding's freed
        bytes are VISIBLE: replicated they cost the full table per
        device, sharded 1/S of it — and the difference lands directly
        in ``slots_addable``/``prefix_blocks_addable`` below.
      * ``kv_slot_bytes``      — the batched slot cache (all B rows).
      * ``prefix_arena_bytes`` — the radix cache's K/V block arena.
      * ``logits_workspace_bytes`` — modeled per-step transient (a
        vocab-sharded head fetches candidate summaries, so the modeled
        logits transient is vocab/S there).

    All categories are PER-DEVICE bytes (max across devices): sharded
    leaves count their shard, replicated ones their full copy — the
    chip-budget number, not the global array size.

    Reconciliation: ``device_bytes_in_use``/``device_bytes_limit`` from
    ``device.memory_stats()`` where the backend provides it (None on
    CPU), with ``unaccounted_bytes`` = in_use - accounted when both
    sides exist (XLA scratch, compiled executables, fusion temps).

    Headroom (what ROADMAP item 1's auto-sizing consumes):
    ``per_slot_bytes`` (one more batch row's K/V) and
    ``per_block_bytes`` (one more arena block) are always reported;
    ``slots_addable``/``prefix_blocks_addable`` = free HBM divided by
    those, when the backend reports a limit."""
    spec = engine.spec
    # shape-derived bytes, walked once per engine object: weights never
    # change size, and the slot cache keeps its shapes for the engine's
    # life (every step donates and replaces the arrays, never resizes
    # them) — the walk is milliseconds over a sharded tree and would
    # otherwise run, racing a donation, on every /stats and /metrics read
    cached = getattr(engine, "_hbm_shape_bytes", None)
    if cached is None:
        params = engine.params
        cached = (_tree_bytes({k: v for k, v in params.items()
                               if k not in ("tok_emb", "wcls")}),
                  _tree_bytes([params[k] for k in ("tok_emb", "wcls")
                               if k in params]),
                  _tree_bytes(engine.cache))
        try:
            engine._hbm_shape_bytes = cached
        except AttributeError:  # a read-only engine shim: skip the cache
            pass
    weights, vocab_b, kv = cached
    arena = 0
    n_blocks = 0
    bl = block_len
    if prefix_cache is not None:
        arena = (int(prefix_cache.arena_k.nbytes)
                 + int(prefix_cache.arena_v.nbytes))
        n_blocks = prefix_cache.num_blocks
        bl = prefix_cache.block_len
    import jax.numpy as jnp

    cache_itemsize = jnp.dtype(engine.cache_dtype).itemsize
    compute_itemsize = jnp.dtype(engine.compute_dtype).itemsize
    # vocab-sharded engines keep logits vocab/S per device and fetch
    # candidate summaries instead of the (B, vocab) array
    n_vshards = 1
    if getattr(engine, "shard_vocab", False):
        mesh = getattr(engine, "mesh", None)
        for a in getattr(engine, "_vocab_axes", ()) or ():
            n_vshards *= mesh.shape[a]
    logits_ws = (engine.batch * spec.vocab_size * 4 // n_vshards
                 + engine.batch * engine.prefill_chunk * spec.dim
                 * compute_itemsize)
    per_token = spec.cache_values_per_token * cache_itemsize
    per_slot = (kv // engine.batch if engine.batch else 0) or (
        engine.seq_len * per_token
        + spec.state_bytes_per_slot(cache_itemsize))
    per_block = (arena // n_blocks) if n_blocks else (
        int(bl or 32) * per_token)
    accounted = weights + vocab_b + kv + arena + logits_ws
    dev = (device_memory_stats() if device_stats is True
           else (device_stats or None))
    if dev is not None and "bytes_in_use" not in dev:
        # a caller supplying only a budget ({"bytes_limit": L}) gets the
        # MODELED in-use — the accounted bytes — so headroom questions
        # ("what does vocab sharding free?") answer on backends without
        # allocator stats (CPU) and in what-if sizing
        dev = {"bytes_in_use": accounted,
               "bytes_limit": int(dev.get("bytes_limit") or 0) or None}
    out = {
        "weights_bytes": weights,
        "vocab_bytes": vocab_b,
        "kv_slot_bytes": kv,
        "prefix_arena_bytes": arena,
        "logits_workspace_bytes": logits_ws,
        "accounted_bytes": accounted,
        "per_slot_bytes": per_slot,
        "per_block_bytes": per_block,
        "device_bytes_in_use": None,
        "device_bytes_limit": None,
        "per_device_bytes_in_use": None,
        "unaccounted_bytes": None,
        "headroom_bytes": None,
        "slots_addable": None,
        "prefix_blocks_addable": None,
    }
    if dev is not None:
        out["device_bytes_in_use"] = dev["bytes_in_use"]
        out["device_bytes_limit"] = dev["bytes_limit"]
        out["per_device_bytes_in_use"] = dev.get("per_device_bytes_in_use")
        out["unaccounted_bytes"] = max(dev["bytes_in_use"] - accounted, 0)
        if dev["bytes_limit"]:
            free = max(dev["bytes_limit"] - dev["bytes_in_use"], 0)
            out["headroom_bytes"] = free
            out["slots_addable"] = free // per_slot if per_slot else None
            out["prefix_blocks_addable"] = (free // per_block
                                            if per_block else None)
    return out


# -- auto-sizing (the measurement→decision half of ROADMAP item 1) ----------

# heuristic knee: decode is weight-read-bound, so batching keeps paying
# until KV traffic competes with the weight read — 32 rows is the
# conservative cross-model default (docs/serving.md "Auto-sizing")
DEFAULT_KNEE_ROWS = 32


def resolve_auto_shape(engine, *, serve_batch, prefix_blocks=0,
                       prefix_block_len: int = 32, replicas: int = 1,
                       default_knee: int = DEFAULT_KNEE_ROWS,
                       slo_itl_ms: float | None = None,
                       device_stats=True) -> dict:
    """Resolve the ``--serve-batch auto`` / ``--prefix-blocks auto``
    sentinels at engine-build time: the batch knee capped by HBM-ledger
    headroom (vLLM's size-from-measured-memory precedent).

      * serve_batch  — `default_knee` rows capped by the slots the
        free HBM can hold, split across `replicas` (thread replicas
        share weights but each owns a B-row cache). Where the backend
        reports no allocator stats (CPU), the knee stands alone.
        ``slo_itl_ms`` is recorded beside the decision, not applied.
      * prefix_blocks — the existing 2×B×context heuristic target,
        capped at HALF the blocks the free HBM could hold (the arena
        must not eat the headroom the slots were just granted).

    `engine` is the already-built template (any batch) — per-slot /
    per-block bytes come from its real array shapes via ``hbm_ledger``.
    Raises ValueError when the engine cannot be ledgered (a weightless
    front-door template): ``auto`` needs a local engine, and the caller
    owes the operator a clear startup error, not a crash mid-build.

    Returns the full decision record — chosen values, every input, and
    the basis ("default_heuristic" | "hbm_cap" | "context_heuristic" |
    "static") — which the API server logs at startup and exports on
    /stats and /metrics so an operator can always see WHAT was chosen
    and WHY."""
    if getattr(engine, "params", None) is None or not hasattr(engine,
                                                              "cache"):
        raise ValueError(
            "auto sizing needs a ledger-capable local engine (the "
            "process tier's workers own their engines — pass explicit "
            "sizes there)")
    ledger = hbm_ledger(engine, block_len=prefix_block_len,
                        device_stats=device_stats)
    replicas = max(int(replicas), 1)
    knee = int(default_knee)
    inputs = {
        "knee_rows": knee,
        "slo_itl_ms": slo_itl_ms,
        "replicas": replicas,
        "per_slot_bytes": ledger["per_slot_bytes"],
        "per_block_bytes": ledger["per_block_bytes"],
        "headroom_bytes": ledger["headroom_bytes"],
        "slots_addable": ledger["slots_addable"],
        "prefix_blocks_addable": ledger["prefix_blocks_addable"],
    }
    out = {"inputs": inputs}
    if serve_batch == "auto":
        cap = None
        if ledger["slots_addable"] is not None:
            cap = max(int(ledger["slots_addable"]) // replicas, 1)
        b = min(knee, cap) if cap is not None else knee
        out["serve_batch"] = max(int(b), 1)
        out["serve_batch_basis"] = ("hbm_cap"
                                    if cap is not None and cap < knee
                                    else "default_heuristic")
    else:
        out["serve_batch"] = int(serve_batch)
        out["serve_batch_basis"] = "static"
    b = out["serve_batch"]
    if prefix_blocks == "auto":
        bl = max(int(prefix_block_len), 1)
        target = max(2 * b * engine.seq_len // bl, 1)
        cap = None
        if ledger["prefix_blocks_addable"] is not None:
            cap = max(int(ledger["prefix_blocks_addable"])
                      // (2 * replicas), 1)
        out["prefix_blocks"] = min(target, cap) if cap is not None \
            else target
        out["prefix_blocks_basis"] = ("hbm_cap"
                                      if cap is not None and cap < target
                                      else "context_heuristic")
    else:
        out["prefix_blocks"] = (int(prefix_blocks)
                                if prefix_blocks else prefix_blocks)
        out["prefix_blocks_basis"] = "static"
    return out


# -- build info -------------------------------------------------------------


def mesh_label(mesh) -> str:
    if mesh is None:
        return "single"
    try:
        return "x".join(f"{k}{v}" for k, v in mesh.shape.items())
    except Exception:  # noqa: BLE001 — shim engines without a real mesh
        return "unknown"


def build_info(engine=None) -> dict:
    """The ``dllama_build_info`` label set / ``build`` healthz block:
    package version, jax version, active backend, the device as JAX
    reports it (kind + count) and the mesh shape. INITIALIZES the backend
    — only a process that owns its device may call this; the process
    tiers' front door relays a worker's block instead
    (apps/api_server.ApiState.build_info)."""
    import jax

    from .. import __version__

    devices = jax.devices()
    return {"version": __version__,
            "jax": jax.__version__,
            "backend": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "mesh": mesh_label(getattr(engine, "mesh", None))}


# -- on-demand capture -------------------------------------------------------

# the host tracer level of a capture: 1 is the lowest that records the
# program's own TraceAnnotations (runtime/trace.py SPAN_NAMES); it also
# records jax's (`np.asarray(jax.Array)`, `PjitFunction(...)`). Level 2,
# jax's default, added nothing the reduction reads (chip, PR 25).
HOST_TRACER_LEVEL = 1


class Profiler:
    """On-demand jax.profiler capture (module singleton: ``PROFILER``).

    ``jax.profiler`` is process-global, so exactly one trace may run at
    a time: a second ``capture()`` is refused, never queued. While one
    runs, ``TRACER.capturing`` is set and every span site of the
    scheduler, the step loops and the front door writes a
    ``TraceAnnotation``; otherwise those sites cost one attribute read."""

    def __init__(self):
        self.captures = 0           # /admin/profile captures completed
        # the caller's counters at the last capture's two ends, /stats
        # `capture`: what a reader needs to set the capture's device times
        # against the work of the SAME seconds (a closed loop's contexts
        # swing together, so a window's mean is not the capture's)
        self.last_counters: dict | None = None
        self._lock = threading.Lock()
        self._busy = False  # dlrace: guarded-by(self._lock)

    def capture(self, directory: str, ms: float, counters=None) -> dict:
        """Write one jax.profiler trace of the next `ms` milliseconds to
        `directory` (created). `counters`: a callable giving the serving
        counters, read as the trace starts and again as it stops (before
        the export) into `last_counters`. Synchronous — the caller's thread sleeps
        out the window (the threaded HTTP server keeps serving), so a
        200 means the trace is on disk. The Python tracer is off: with it
        on, the stop froze serving for seconds and the host plane held
        frames of every thread instead of the program's spans. Returns
        {"dir", "ms", "t_start_mono", "t_stop_mono", "stop_ms"} — the
        ``perf_counter`` instants between which the trace ran (the clock
        of the tracer's ring records) and how long the stop took; raises
        RuntimeError("capture busy") when a trace is already running."""
        import os

        import jax

        with self._lock:
            if self._busy:
                raise RuntimeError("capture busy: a profiler trace is "
                                   "already running in this process")
            self._busy = True
        try:
            os.makedirs(directory, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = HOST_TRACER_LEVEL
            jax.profiler.start_trace(directory, profiler_options=opts)
            t_start = time.perf_counter()
            at_start = counters() if counters else None
            TRACER.set_capturing(True)
            try:
                time.sleep(max(float(ms), 0.0) / 1e3)
            finally:
                TRACER.set_capturing(False)
                if counters:
                    self.last_counters = {"start": at_start,
                                          "stop": counters()}
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
            stop_ms = (time.perf_counter() - t_stop) * 1e3
            self.captures += 1
            if TRACER.enabled:
                TRACER.event("profile", 0, dir=directory, ms=float(ms),
                             t_start_mono=t_start, t_stop_mono=t_stop)
            return {"dir": directory, "ms": float(ms),
                    "t_start_mono": t_start, "t_stop_mono": t_stop,
                    "stop_ms": round(stop_ms, 3)}
        finally:
            with self._lock:
                self._busy = False

    def reset(self) -> None:
        self.captures = 0
        self.last_counters = None


PROFILER = Profiler()
