"""Level 2: trace the public jitted entry points and audit their jaxprs.

The AST lint (Level 1) sees what the source SAYS; this level sees what the
tracer actually BUILT. Each entry point from entrypoints.py is traced with
tiny abstract-friendly inputs (no XLA compile) and its jaxpr — including
every sub-jaxpr under pjit/scan/while/cond/shard_map/custom-call — is
walked for:

  DLG201  device-to-host transfer primitives (pure_callback, io_callback,
          debug_callback, debug_print, ...) — a host round-trip compiled INTO the step
          function stalls the TPU pipeline every token
  DLG202  float64 anywhere in the program. Traced under
          jax.enable_x64(True) so promotion leaks are visible: with
          the production x64=off default JAX silently truncates them to
          f32, and the first time the flag flips (a debug session, a new
          deployment) the step function doubles its HBM traffic
  DLG203  full-precision activation re-replication: an all_gather whose
          float output is at least the full activation size. The Q80 TP
          path exists precisely to move int8 blocks instead of replicating
          f32 partial sums (ref: src/tasks.cpp:124-163) — an f32/bf16
          all_gather of a whole activation inside a manual region is the
          regression this guards against. int8/uint8 gathers (the q80
          payload) and sub-activation gathers (flash stats, scales) pass
  DLG204  entry-point signature fingerprint drift vs the committed
          baseline — the jit compilation key changed (an input dtype
          widened, a scalar became weak-typed, an argument appeared):
          every distinct call now recompiles or the cache key churns
  DLG205  full-vocab logits materialization in a vocab-sharded serving
          program (entries declaring meta["vocab"]): a program output or
          an all_gather with a vocab-sized dim — the sharded sampling
          path (ops/sharded_vocab.py) exists so only candidate
          summaries ever cross to the host

Severity: DLG201/202/203/205 are errors, DLG204 a warning (legitimate
signature changes are accepted by re-running with --update-baseline).
DLG200 (error) reports an entry point the backend could not audit at all
(too few devices) — the gate must fail loudly rather than pass vacuously.
"""

from __future__ import annotations

import numpy as np

from .entrypoints import (EntryPoint, entry_points, make_jaxpr_for,
                          signature_fingerprint)
from .findings import Finding

# primitives that move data to the host (or schedule host execution)
D2H_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "device_get", "callback",
}

# collective primitives that replicate data (vs reduce it)
GATHER_PRIMITIVES = {"all_gather", "all_gather_invariant"}

FLOAT_WIDE = {np.dtype("float32"), np.dtype("float64"),
              np.dtype("bfloat16"), np.dtype("float16")}


def _iter_eqns(jaxpr):
    """Depth-first over every eqn in jaxpr and all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(param):
    # duck-typed: Jaxpr has .eqns, ClosedJaxpr wraps one as .jaxpr — no
    # isinstance against jax internals (their module moved across versions)
    vals = param if isinstance(param, (list, tuple)) else [param]
    for v in vals:
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr


def _callback_name(cb) -> str:
    """Stable name for a callback param — never its repr, which embeds a
    memory address and would make the baseline key differ every process."""
    if cb is None:
        return ""
    inner = getattr(cb, "func", cb)  # unwrap functools.partial
    return (getattr(inner, "__qualname__", "")
            or getattr(inner, "__name__", "")
            or type(cb).__name__)


def _aval_dtype(var):
    aval = getattr(var, "aval", None)
    return getattr(aval, "dtype", None)


def _aval_size(var) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:  # symbolic dim
            return 0
    return n


def audit_entry(ep: EntryPoint) -> tuple[list[Finding], str]:
    """(findings, fingerprint) for one entry point."""
    findings: list[Finding] = []
    file = f"<entry:{ep.name}>"

    closed = make_jaxpr_for(ep)

    # DLG201: host transfers compiled into the step
    for eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in D2H_PRIMITIVES:
            cb_name = _callback_name(eqn.params.get("callback"))
            findings.append(Finding(
                "DLG201", "error", file, 0,
                f"host callback `{name}`"
                + (f" ({cb_name})" if cb_name else "")
                + " compiled into the step — device-to-host round-trip "
                "every invocation"))

    # DLG203: full-precision activation re-replication
    act = max(int(ep.meta.get("activation_elems", 0)), 1)
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name not in GATHER_PRIMITIVES:
            continue
        out = eqn.outvars[0]
        dt = _aval_dtype(out)
        if dt is None or np.dtype(dt) not in FLOAT_WIDE:
            continue  # int8 q80 payload (or bool masks) — the cheap wire
        if _aval_size(out) >= act:
            axis = eqn.params.get("axis_name",
                                  eqn.params.get("axes", "?"))
            findings.append(Finding(
                "DLG203", "error", file, 0,
                f"float all_gather over {axis} re-replicates a "
                f"full activation ({_aval_size(out)} elems, dtype "
                f"{np.dtype(dt).name}) — the sharded-on-entry tensor "
                "comes back replicated; use a psum/reduce_scatter or the "
                "q80 exchange (parallel/collectives.py)"))

    # DLG205: full-vocab logits materialization on a vocab-sharded
    # serving program (entries declaring meta["vocab"]). Two shapes of
    # the leak: the program RETURNS an array with a vocab-sized dim
    # (the host fetch would gather the whole head output), or an
    # all_gather inside it re-replicates one (the sharded matmul's
    # output coming back whole). The sharded sampling path exists
    # precisely so only (B, S·k) candidate summaries cross; a
    # vocab-sized anything here is the regression this rule guards.
    vocab = int(ep.meta.get("vocab", 0))
    if vocab:
        for var in closed.jaxpr.outvars:
            aval = getattr(var, "aval", None)
            dims = tuple(getattr(aval, "shape", ()) or ())
            if any(d == vocab for d in dims if isinstance(d, int)):
                findings.append(Finding(
                    "DLG205", "error", file, 0,
                    f"program output of shape {dims} carries a full "
                    f"vocab ({vocab}) dim — the serving path must fetch "
                    "candidate summaries, never the logits "
                    "(ops/sharded_vocab.py)"))
        for eqn in _iter_eqns(closed.jaxpr):
            if eqn.primitive.name not in GATHER_PRIMITIVES:
                continue
            out = eqn.outvars[0]
            aval = getattr(out, "aval", None)
            dims = tuple(getattr(aval, "shape", ()) or ())
            if any(d == vocab for d in dims if isinstance(d, int)):
                findings.append(Finding(
                    "DLG205", "error", file, 0,
                    f"all_gather re-replicates a full-vocab array "
                    f"{dims} inside a vocab-sharded serving program"))

    # DLG202: f64 promotion, visible only under x64 tracing
    closed64 = make_jaxpr_for(ep, x64=True)
    seen_f64 = set()
    for eqn in _iter_eqns(closed64.jaxpr):
        for var in list(eqn.outvars):
            dt = _aval_dtype(var)
            if dt is not None and np.dtype(dt) == np.dtype("float64"):
                key = eqn.primitive.name
                if key not in seen_f64:
                    seen_f64.add(key)
                    findings.append(Finding(
                        "DLG202", "error", file, 0,
                        f"float64 produced by `{key}` under x64 tracing — "
                        "an unpinned literal/np-constant promotes; pin the "
                        "dtype (jnp.float32(...)) so the program is "
                        "x64-proof"))

    return findings, signature_fingerprint(ep)


def audit_all(baseline_fingerprints: dict[str, str] | None = None,
              ) -> tuple[list[Finding], dict[str, str]]:
    """Audit every entry point available on this backend. Returns findings
    (including DLG204 fingerprint drift vs the given baseline) plus the
    current fingerprint map."""
    import jax

    findings: list[Finding] = []
    fingerprints: dict[str, str] = {}
    n_dev = jax.device_count()
    entries, unavailable = entry_points()
    # an un-audited entry point is a FINDING, not a silent skip — otherwise
    # a short virtual mesh (stray XLA_FLAGS) makes the gate pass vacuously
    # on exactly the tp/ep paths DLG203 exists to watch
    for name, needs in unavailable:
        findings.append(Finding(
            "DLG200", "error", f"<entry:{name}>", 0,
            f"entry point not audited: needs {needs} devices, "
            f"backend has {n_dev} — run with XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 (the CI/test "
            "configuration)"))
    for ep in entries:
        f, fp = audit_entry(ep)
        findings.extend(f)
        fingerprints[ep.name] = fp
        if baseline_fingerprints and ep.name in baseline_fingerprints:
            want = baseline_fingerprints[ep.name]
            if fp != want:
                findings.append(Finding(
                    "DLG204", "warning", f"<entry:{ep.name}>", 0,
                    f"static-signature fingerprint drift ({want} -> {fp}) "
                    "— the jit compilation key changed (input dtype/"
                    "weak-type/arity); intended changes re-baseline with "
                    "--update-baseline"))
    if baseline_fingerprints is not None:
        # completeness both ways: a NEW entry point must be pinned
        # deliberately (not silently accepted), and a DELETED one must not
        # leave a stale fingerprint in the baseline forever. Entries the
        # mesh could not build already failed via DLG200 — not stale.
        for name in sorted(set(fingerprints) - set(baseline_fingerprints)):
            findings.append(Finding(
                "DLG204", "warning", f"<entry:{name}>", 0,
                "new entry point with no pinned signature fingerprint — "
                "accept with --update-baseline"))
        skipped = {n for n, _ in unavailable}
        for name in sorted(set(baseline_fingerprints) - set(fingerprints)
                           - skipped):
            findings.append(Finding(
                "DLG108", "warning", f"<entry:{name}>", 0,
                "stale baseline: pinned fingerprint for an entry point "
                "that no longer exists — prune with --update-baseline"))
    return findings, fingerprints
