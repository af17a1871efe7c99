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

import functools
import os
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
        # the last capture's report of itself (capture_report), /stats
        # `capture.report`: {"error": ...} where none could be made
        self.last_report: dict | None = None
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
        {"dir", "ms", "t_start_mono", "t_stop_mono", "stop_ms", "report",
        "report_ms"} — the ``perf_counter`` instants between which the
        trace ran (the clock of the tracer's ring records), how long the
        stop took, and what the capture says of itself (`make_report`,
        after the export, in a child process; also `report.json` beside
        the trace, and `last_report`) with the ms that took; raises
        RuntimeError("capture busy") when a trace is already running."""
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
            report, report_ms = make_report(directory)
            self.last_report = report
            self.captures += 1
            if TRACER.enabled:
                TRACER.event("profile", 0, dir=directory, ms=float(ms),
                             t_start_mono=t_start, t_stop_mono=t_stop)
            return {"dir": directory, "ms": float(ms),
                    "t_start_mono": t_start, "t_stop_mono": t_stop,
                    "stop_ms": round(stop_ms, 3), "report": report,
                    "report_ms": round(report_ms, 3)}
        finally:
            with self._lock:
                self._busy = False

    def reset(self) -> None:
        self.captures = 0
        self.last_counters = None
        self.last_report = None


PROFILER = Profiler()


# -- capture report ----------------------------------------------------------
#
# What a capture says of itself: the one xplane walker of the program. The
# arithmetic is on plain lists (tests feed it hand-made events); only
# `walk_trace` and `hlo_op_names` touch a file. Times are seconds on the
# profiler's clock, which the device planes and the host planes share.

REPORT_LIMIT_S = 60.0    # the report's child process (make_report)
REPORT_MODULE = "distributed_llama_tpu.runtime.profiler"   # run as a script
UNSCOPED = "unscoped"    # an op whose op_name carries no DEVICE_SCOPES name
NO_SPAN = "no_span"      # idle time under none of the program's spans
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def stem(name: str) -> str:
    """`jit_slot_prefill_chunk_32(123)` -> `slot_prefill_chunk_32`;
    `%copy_bitcast_fusion.12 = ...` -> `copy_bitcast_fusion` (an op event's
    name is its whole HLO line: only the part before ` = ` counts)."""
    name = name.split("(")[0].split(" = ")[0].lstrip("%").strip()
    if name.startswith("jit_"):
        name = name[4:]
    return re.sub(r"\.\d+$", "", name) or "op"


@functools.lru_cache(maxsize=None)   # a capture has a few thousand names
def scope_path(op_name: str | None) -> str:
    """A framework op name (`jit(slot_decode_step)/ffn/act_q80/jit(
    quantize_q80_jax)/reduce_max`) cut down to the DEVICE_SCOPES names on
    it, joined by `/`; UNSCOPED where it carries none."""
    from ..models.scopes import DEVICE_SCOPES

    if not op_name:
        return UNSCOPED
    # an op the compiler merged from two carries both names, `a;b`: the first
    parts = op_name.split(";")[0].split("/")
    # XLA's inliner may write a callee's whole path behind the call's
    # (`jit(f)/moe_routed/jit(searchsorted)/jit(f)/moe_routed/...`): the
    # path starts at the LAST mention of the program
    last = len(parts) - 1 - parts[::-1].index(parts[0])
    names = [part for part in parts[last:] if part in DEVICE_SCOPES]
    return "/".join(names) or UNSCOPED


def innermost(events: list, inside: dict | None = None) -> list:
    """Who owns each instant that some event covers: (start, end, index)
    segments, the index that of the event of `events` ((start, end, ...)
    tuples) that started last among those open. Summed by index they are
    each event's SELF time, its duration less what it contains, and all
    together the union of the events: a `while` and the kernels of its
    body, an async pair around other ops, a span and its phases. `inside`
    receives {index: index of the event open when it started}."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    out, stack, at = [], [], 0.0

    def run_to(upto: float) -> None:
        nonlocal at
        while stack:
            end, i = stack[-1]
            if end <= at:
                stack.pop()
                continue
            if at >= upto:
                return
            cut = min(end, upto)
            out.append((at, cut, i))
            at = cut
        at = max(at, upto)

    for i in order:
        start, end = events[i][0], events[i][1]
        if end <= start:
            continue
        run_to(start)
        if stack and inside is not None:
            inside[i] = stack[-1][1]
        stack.append((end, i))
    run_to(float("inf"))
    return out


def _execution_of(starts: list, spans: list, t: float):
    """Index of the (start, end, ...) span of sorted `spans` that holds
    instant t (`starts`: their starts), or None."""
    import bisect

    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 and t < spans[i][1] else None


def program_scopes(modules: list, ops: list) -> dict:
    """{program: {"executions", "device_ms", "busy_ms", "scopes"}} of one
    device. modules: (start, end, name) of the `XLA Modules` line, one an
    execution; ops: (start, end, name, op_name) of the `XLA Ops` line,
    op_name the framework's (None: the plane has none for it).

    `scopes` is {scope path: {"kernel": {kernel: ms}, "xla": {stem: ms}}}:
    mean SELF milliseconds an execution (`innermost`), a Pallas call under
    its kernel's name, everything else under its HLO instruction's stem
    (`copy`, `fusion`, `reshape`, `while`, ...). An op that names no line
    and contains others (the compiler clones a loop and drops its name)
    takes the scope its contents share. `busy_ms` is their sum, the union
    of the program's ops; `device_ms` the execution's own length."""
    mods = sorted(modules)
    starts = [m[0] for m in mods]
    self_s = [0.0] * len(ops)
    parent_of: dict[int, int] = {}
    for a, b, i in innermost(ops, parent_of):
        self_s[i] += b - a
    paths = [scope_path(op[3]) for op in ops]
    contents: dict[int, list] = {}
    for child, parent in parent_of.items():
        if paths[parent] == UNSCOPED and paths[child] != UNSCOPED:
            contents.setdefault(parent, []).append(paths[child].split("/"))
    for parent, kids in contents.items():
        # element-wise on lists of names: the scopes all its contents share
        paths[parent] = "/".join(os.path.commonprefix(kids)) or UNSCOPED
    out: dict = {}
    for s, e, name in mods:
        prog = out.setdefault(stem(name), {"executions": 0, "device_ms": 0.0,
                                           "busy_ms": 0.0, "scopes": {}})
        prog["executions"] += 1
        prog["device_ms"] += (e - s) * 1e3
    for i, op in enumerate(ops):
        at = _execution_of(starts, mods, op[0])
        if at is None or self_s[i] <= 0.0:
            continue
        prog = out[stem(mods[at][2])]
        kernel = _PALLAS_TARGET in op[2]
        kind = prog["scopes"].setdefault(paths[i], {"kernel": {}, "xla": {}})[
            "kernel" if kernel else "xla"]
        key = stem(op[2])
        kind[key] = kind.get(key, 0.0) + self_s[i] * 1e3
        prog["busy_ms"] += self_s[i] * 1e3
    for prog in out.values():
        n = prog["executions"]
        prog["device_ms"] = round(prog["device_ms"] / n, 4)
        prog["busy_ms"] = round(prog["busy_ms"] / n, 4)
        for kinds in prog["scopes"].values():
            for kind in kinds.values():
                for key in kind:
                    kind[key] = round(kind[key] / n, 4)
    return out


def idle_by_span(ops: list, window: tuple, spans: list) -> dict:
    """The seconds of `window` in which no op of `ops` ran, split by
    OVERLAP over the innermost of `spans` ((start, end, name): the
    program's own, trace.SPAN_NAMES) open at that instant, NO_SPAN for the
    rest: a gap that runs through three phases gives each its seconds, and
    their parent only what lies between them."""
    owner = innermost(spans)
    totals: dict[str, float] = {}
    k, at = 0, window[0]

    def split(a: float, b: float) -> None:
        nonlocal k
        while k < len(owner) and owner[k][1] <= a:
            k += 1
        j = k
        while a < b:
            if j < len(owner) and owner[j][0] < b:
                s, e, i = owner[j]
                if s > a:
                    totals[NO_SPAN] = totals.get(NO_SPAN, 0.0) + s - a
                name = spans[i][2]
                totals[name] = (totals.get(name, 0.0)
                                + min(e, b) - max(s, a))
                a = min(e, b)
                j += 1
            else:
                totals[NO_SPAN] = totals.get(NO_SPAN, 0.0) + b - a
                a = b

    busy = sorted((max(op[0], window[0]), min(op[1], window[1]))
                  for op in ops)
    for s, e in busy:
        if e <= s:
            continue
        if s > at:
            split(at, s)
        at = max(at, e)
    if window[1] > at:
        split(at, window[1])
    return totals


def report_events(modules: list, ops: list, spans: list) -> dict:
    """The report of one device plane: `program_scopes` of its step
    programs, and `idle_by_span` of the captured window (from the first op
    or execution to the last). {} for a plane without events."""
    edges = [(x[0], x[1]) for x in ops] + [(x[0], x[1]) for x in modules]
    if not edges:
        return {}
    window = (min(s for s, _ in edges), max(e for _, e in edges))
    idle = idle_by_span(ops, window, spans)
    idle_s = sum(idle.values())
    return {"window_s": round(window[1] - window[0], 6),
            "busy_s": round(window[1] - window[0] - idle_s, 6),
            "idle_s": round(idle_s, 6),
            "idle": {k: round(v, 6) for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])},
            "programs": program_scopes(modules, ops)}


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one protobuf message in buf[i:end]: a
    varint's integer, a length-delimited field's (start, end) in buf;
    fixed-width fields are skipped."""
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield tag >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield tag >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _map_entry(buf, span: tuple) -> tuple[int, tuple]:
    key, value = 0, (0, 0)
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _packed_ints(buf, value) -> list:
    """A repeated int64 field's values: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _module_op_names(buf, span: tuple) -> dict:
    """{instruction name: framework op name} of one HloProto (.hlo_module =
    1; HloModuleProto .computations = 3; HloComputationProto .instructions
    = 2, .is_fusion_computation = 7; HloInstructionProto .name = 1, .opcode
    = 2, .metadata = 7 (OpMetadata .op_name = 2), .id = 35, .operand_ids =
    36), over the computations whose instructions run as ops (not a
    fusion's inside). An instruction that names no line of the program (the
    compiler's own: a relayout `copy`, an async pair's `copy-done`, a
    `bitcast`) takes the name of the first instruction that does among
    those that read what it makes, breadth first through its users in its
    computation; a `while`, `conditional` or `call` without a name keeps
    none (program_scopes gives it the scope its contents share)."""
    names: dict = {}
    for f, module in _fields(buf, *span):
        if f != 1:
            continue
        for g, comp in _fields(buf, *module):
            if g != 3:
                continue
            rows, fused = [], False
            for h, v in _fields(buf, *comp):
                if h == 7:
                    fused = bool(v)
                elif h == 2:
                    name = opcode = op_name = ""
                    uid, operands = 0, []
                    for k, w in _fields(buf, *v):
                        if k == 1:
                            name = bytes(buf[w[0]:w[1]]).decode()
                        elif k == 2:
                            opcode = bytes(buf[w[0]:w[1]]).decode()
                        elif k == 7:
                            for m, x in _fields(buf, *w):
                                if m == 2:
                                    op_name = bytes(buf[x[0]:x[1]]).decode(
                                        "utf-8", "replace")
                        elif k == 35:
                            uid = w
                        elif k == 36:
                            operands += _packed_ints(buf, w)
                    rows.append((uid, name, opcode, op_name, operands))
            if fused:
                continue
            own = {uid: op_name for uid, _, _, op_name, _ in rows
                   if op_name.startswith("jit(")}
            users: dict = {}
            for uid, _, _, _, operands in rows:
                for o in operands:
                    users.setdefault(o, []).append(uid)
            for uid, name, opcode, _, _ in rows:
                found = own.get(uid)
                if found is None and opcode not in ("while", "conditional",
                                                    "call"):
                    seen, queue = {uid}, [uid]
                    while queue and found is None and len(seen) < 256:
                        for u in users.get(queue.pop(0), ()):
                            if u in own:
                                found = own[u]
                                break
                            if u not in seen:
                                seen.add(u)
                                queue.append(u)
                if found is not None:
                    names[name] = found
    return names


def hlo_op_names(path: str) -> dict:
    """{program id: {HLO instruction name: framework op name}} of the step
    programs a capture ran, from the compiled modules the `.xplane.pb`
    itself carries (plane `/host:metadata`: one event metadata a program,
    its id the program's, one bytes stat: the HloProto), where
    `jax.named_scope` wrote the scopes as `op_name`; read off the wire
    format (XSpace .planes = 1; XPlane .name = 2, .event_metadata = 4;
    XEventMetadata .id = 1, .stats = 5; XStat .bytes_value = 6), since
    jax's ProfileData shows neither a plane's metadata nor this one's
    bytes. `_module_op_names` says what an op without a name of its own
    is given."""
    import mmap

    out: dict = {}
    with open(path, "rb") as f, mmap.mmap(f.fileno(), 0,
                                          access=mmap.ACCESS_READ) as buf:
        for f1, plane in _fields(buf, 0, len(buf)):
            if f1 != 1:
                continue
            entries = [v for g, v in _fields(buf, *plane) if g == 4]
            if not any(g == 2 and bytes(buf[v[0]:v[1]]) == b"/host:metadata"
                       for g, v in _fields(buf, *plane)):
                continue
            for entry in entries:
                _, meta = _map_entry(buf, entry)
                program, proto = 0, None
                for h, w in _fields(buf, *meta):
                    if h == 1:
                        program = w & (2 ** 64 - 1)
                    elif h == 5:
                        for j, x in _fields(buf, *w):
                            if j == 6:
                                proto = x
                if proto is not None:
                    out[program] = _module_op_names(buf, proto)
    return out


def make_report(directory: str, limit_s: float | None = None) -> tuple:
    """(report, ms it took: also the report's `report_ms`) of the capture
    under `directory`: `capture_report` in a CHILD process (this module as
    a script, on the CPU, at most limit_s seconds, REPORT_LIMIT_S by
    default), because a Python walk over 10^5-10^6 events here would share
    the interpreter with the scheduler's thread; also written as
    `report.json` there. A capture without a device plane
    (a CPU run) starts no child. The report of a child that fails or runs
    out of time is {"error": ...}: a capture never fails for its report."""
    import json
    import subprocess
    import sys

    t0 = time.perf_counter()
    limit_s = REPORT_LIMIT_S if limit_s is None else limit_s
    path = newest_xplane(directory)
    try:
        if path is None or not device_planes(path):
            report = {"error": "no device plane in the capture"}
        else:
            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
            done = subprocess.run(
                [sys.executable, "-m", REPORT_MODULE, directory], env=env,
                capture_output=True, text=True, timeout=limit_s)
            if done.returncode != 0:
                raise RuntimeError(f"exit code {done.returncode}: "
                                   + done.stderr.strip()[-400:])
            report = json.loads(done.stdout.strip().splitlines()[-1])
    except subprocess.TimeoutExpired:
        report = {"error": f"no report within {limit_s:g} s"}
    except Exception as e:  # noqa: BLE001 — whatever the child did
        report = {"error": f"{type(e).__name__}: {e}"[:500]}
    took_ms = (time.perf_counter() - t0) * 1e3
    report["report_ms"] = round(took_ms, 3)   # /stats and the file say it too
    try:
        with open(os.path.join(directory, "report.json"), "w") as f:
            json.dump(report, f)
    except OSError:
        pass
    return report, took_ms


def device_planes(path: str) -> list:
    """Names of the `/device:` planes of an `.xplane.pb`, off the wire
    format (the events are skipped whole)."""
    import mmap

    with open(path, "rb") as f, mmap.mmap(f.fileno(), 0,
                                          access=mmap.ACCESS_READ) as buf:
        names = [buf[v[0]:v[1]].decode("utf-8", "replace")
                 for f1, plane in _fields(buf, 0, len(buf)) if f1 == 1
                 for g, v in _fields(buf, *plane) if g == 2]
    return [n for n in names if n.startswith("/device:")]


def newest_xplane(trace_dir: str) -> str | None:
    import glob

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    return files[-1] if files else None


def walk_trace(trace_dir: str, *, op_lines: tuple = ("XLA Ops",),
               devices: int | None = None, host_names: tuple = ()) -> dict:
    """The newest `.xplane.pb` under trace_dir as plain lists: {"file",
    "devices": [{"plane", "modules": [(start, end, name)], "ops": [(start,
    end, name)]}] (the `XLA Modules` line, one event an execution, and the
    events of `op_lines`; the first `devices` device planes, None: all),
    "host": [(start, end, name)] of the host planes' events named in
    `host_names`}. {} where there is no file; no device entry for a trace
    without a device plane (a CPU run)."""
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    if path is None:
        return {}
    out = {"file": path, "devices": [], "host": []}
    wanted = set(host_names)
    names: dict = {}    # an op's name is its whole HLO line: keep one copy

    def events(line):
        return [(e.start_ns / 1e9, e.end_ns / 1e9,
                 names.setdefault(e.name, e.name)) for e in line.events]

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" not in lines or (
                    devices is not None and len(out["devices"]) >= devices):
                continue
            out["devices"].append({
                "plane": plane.name,
                "modules": events(lines["XLA Modules"]),
                "ops": [ev for n in op_lines if n in lines
                        for ev in events(lines[n])]})
        elif wanted and plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in wanted:
                        out["host"].append((e.start_ns / 1e9, e.end_ns / 1e9,
                                            e.name))
    return out


def per_execution_ms(devices: list, markers: tuple,
                     module_hint: str | None = None) -> list:
    """Summed device ms of the ops whose name holds a marker, one float an
    execution, in timeline order, of the first device of `devices`
    (walk_trace's) that ran a module whose name holds `module_hint` (None:
    any); [] where none did."""
    for dev in devices:
        spans = sorted((s, e) for s, e, name in dev["modules"]
                       if module_hint is None or module_hint in name)
        if not spans:
            continue
        starts = [s for s, _ in spans]
        out = [0.0] * len(spans)
        for s, e, name in dev["ops"]:
            if any(m in name for m in markers):
                i = _execution_of(starts, spans, s)
                if i is not None:
                    out[i] += (e - s) * 1e3
        return out
    return []


def _program_id(module: str) -> int:
    """`jit_slot_decode_step(8808336842711562480)` -> that number, as the
    plane's metadata has it under `program_id`; 0 where there is none."""
    m = re.search(r"\((\d+)\)$", module)
    return int(m.group(1)) & (2 ** 64 - 1) if m else 0


def capture_report(trace_dir: str) -> dict:
    """What a capture under trace_dir says of its FIRST device plane:
    `report_events` of its executions, its ops (each with the framework op
    name its program's compiled module holds for it, `hlo_op_names`) and
    the host's spans of
    trace.SPAN_NAMES. {"error": ...} for a trace without a file or without
    a device plane (a CPU run)."""
    from .trace import SPAN_NAMES

    trace = walk_trace(trace_dir, devices=1, host_names=SPAN_NAMES)
    if not trace.get("devices"):
        return {"error": "no device plane" if trace
                else "no .xplane.pb under the directory"}
    dev = trace["devices"][0]
    named = hlo_op_names(trace["file"])
    mods = sorted(dev["modules"])
    starts = [m[0] for m in mods]
    programs = [named.get(_program_id(m[2]), {}) for m in mods]
    ops = []
    for s, e, name in dev["ops"]:
        at = _execution_of(starts, mods, s)
        ops.append((s, e, name, None if at is None else programs[at].get(
            name.split(" = ")[0].lstrip("%"))))
    out = report_events(mods, ops, trace["host"])
    out["file"] = trace["file"]
    out["plane"] = dev["plane"]
    return out


if __name__ == "__main__":
    # the report's child (Profiler.capture): one JSON object on stdout
    import json
    import sys

    print(json.dumps(capture_report(sys.argv[1])))
