"""Deterministic fault injection for the serving stack.

The reference engine has no fault tolerance (SURVEY §"no fault tolerance"),
and real failure shapes are not reproducible at will: a step that stalls
inside a device call raises nothing, and a mid-decode crash poisons the
donated KV cache. This registry makes every one of those
shapes a one-line, count-deterministic trigger so the whole resilience
layer (runtime/resilience.py) is testable in CI on CPU.

Named sites, fired host-side BEFORE any device dispatch (so arming a fault
never changes a jitted program — the dlgrind entry-point fingerprints are
invariant under injection):

  * ``step_raise``    — scheduler step loop, start of an iteration: raises
                        ``FaultError`` (the crash shape)
  * ``step_stall``    — same place: blocks for ``ms`` milliseconds or until
                        ``release()`` (the silent-stall shape — a watchdog must
                        detect it, nothing else will)
  * ``prefill_raise`` — Engine.slot_prefill_chunk entry: raises
                        ``FaultError`` mid-admission
  * ``slow_step``     — scheduler step loop: sleeps ``ms`` per fire (the
                        degraded-but-alive shape deadlines must catch)

Replica-level sites, fired in the scheduler step loop of schedulers that
carry a ``fault_key`` (the router's replicas — runtime/router.py names
replica i's scheduler ``r{i}``), so multi-replica chaos tests can kill or
wedge ONE replica deterministically while its siblings keep serving:

  * ``replica_raise`` — like ``step_raise``, but an armed ``key=rK`` spec
                        only counts and fires on replica K's steps (the
                        kill-one-mid-trace shape: the router must retry
                        not-yet-streamed requests on a survivor)
  * ``replica_stall`` — like ``step_stall`` with the same key filter (one
                        replica wedges; only ITS watchdog may trip)
  * ``worker_exit``   — replica-worker PROCESS token stream
                        (runtime/replica_worker.py): the worker queries
                        ``triggered()`` before each token frame and
                        ``os._exit``s hard — the in-process stand-in for
                        SIGKILL/OOM, count-deterministic and key-filtered
                        like the other replica sites (armed via
                        ``DLLAMA_FAULTS`` in the worker's environment)

Socket-layer sites, fired inside the multihost control-plane frame codec
(parallel/multihost.py) so two-process chaos tests can kill or stall either
side of the root<->worker star and assert bounded detection
(tests/test_cluster_chaos.py):

KV-transfer sites, fired in the donor's block-export loop
(runtime/kv_transfer.py) so chaos tests can kill or wedge a transfer at
an exact BLOCK_DATA frame (key-filtered like the replica sites — the
donor worker's fault_key):

  * ``kvx_stall``      — donor export loop, before a BLOCK_DATA send:
                         blocks like ``step_stall`` (wedged donor — the
                         importer's per-transfer deadline must fire and
                         degrade to a local re-prefill)
  * ``kvx_exit``       — same place, ``triggered()`` form: the donor
                         ``os._exit``s hard mid-stream (the SIGKILL/OOM
                         shape landing exactly between two block frames)

Fleet-controller sites, fired in the autoscaler's decision loop
(runtime/fleet.py) so anti-flap hysteresis and spawn backoff are
count-deterministically testable (tests/test_fleet.py):

  * ``spawn_stall``    — controller scale-up path, before the replica
                         spawn: blocks like ``step_stall`` (a slow
                         container/TPU grant — the controller must keep
                         serving and keep its ``scaling_up`` state
                         truthful while one spawn crawls; key-filtered
                         by the new replica's ``rK`` so ONE scale-up
                         stalls while siblings spawn clean)
  * ``scale_flap``     — controller tick, ``triggered()`` form: each
                         fire flips a synthetic full/empty load signal
                         (the oscillating-traffic shape — the
                         controller's EWMA + cooldown must NOT flap
                         replicas up and down; the test counts fires,
                         not wall time)

  * ``conn_refused``   — worker connect attempt: raises
                         ``ConnectionRefusedError`` (exercises the
                         cluster-formation retry/backoff path; ``times=K``
                         fails the first K attempts deterministically)
  * ``recv_stall``     — frame receive entry: blocks like ``step_stall``
                         (a wedged peer that holds its socket open but
                         stops reading — and so stops answering
                         heartbeats; only the PING/PONG timeout detects it)
  * ``frame_truncate`` — frame send: writes half the frame then closes the
                         socket (the peer sees a mid-frame EOF — the
                         torn-write shape)
  * ``peer_close``     — frame send: closes the socket without writing
                         (the abrupt-death shape at a protocol point)

Arming is test-driven (``FAULTS.arm(...)``) or env-driven for subprocess
harnesses (CI):

    DLLAMA_FAULTS="step_raise:after=40;times=1,slow_step:ms=50;times=0"

``after=N`` skips the first N invocations of the site, ``times=K`` fires on
the next K (K=0 → every invocation), ``ms=F`` sets the stall/sleep length,
``key=S`` restricts a replica-level site to the scheduler whose
``fault_key`` is S (invocations from other keys are not even counted, so
``after`` stays deterministic per replica).
Counters are per-site and monotonically increasing, so a given arm spec
fires at exactly the same invocations on every run — crashes land on the
same scheduler iteration every time.
"""

from __future__ import annotations

import dataclasses
import os
import threading

from .trace import TRACER

SITES = ("step_raise", "step_stall", "prefill_raise", "slow_step",
         "replica_raise", "replica_stall", "worker_exit",
         "conn_refused", "recv_stall", "frame_truncate", "peer_close",
         "kvx_stall", "kvx_exit", "spawn_stall", "scale_flap")


class FaultError(RuntimeError):
    """The injected failure (distinct type so tests can tell an injected
    crash from a real one)."""


@dataclasses.dataclass
class _Armed:
    site: str
    after: int = 0     # skip this many invocations of the site first
    times: int = 1     # then fire on this many (0 = every one from there on)
    ms: float = 0.0    # stall/sleep milliseconds (step_stall / slow_step)
    key: str | None = None  # replica filter: only fire() calls carrying
    # this key count or fire (None = any caller)
    hits: int = 0      # invocations seen
    fired: int = 0     # invocations that actually fired

    def should_fire(self) -> bool:
        self.hits += 1
        if self.hits <= self.after:
            return False
        if self.times and self.fired >= self.times:
            return False
        self.fired += 1
        return True


class FaultRegistry:
    """Thread-safe, count-deterministic fault trigger store. One process
    singleton (``FAULTS``); the scheduler/engine call ``fire(site)`` at the
    named sites and pay one dict lookup when nothing is armed."""

    def __init__(self):
        self._lock = threading.Lock()
        self._armed: dict[str, _Armed] = {}  # dlrace: guarded-by(self._lock)
        # a stalled site blocks on this event, so tests can release a
        # "hung" thread instead of leaking it for the stall duration
        self._release = threading.Event()

    def arm(self, site: str, *, after: int = 0, times: int = 1,
            ms: float = 0.0, key: str | None = None) -> None:
        if site not in SITES:
            raise ValueError(f"unknown fault site {site!r} (have {SITES})")
        with self._lock:
            self._release.clear()
            self._armed[site] = _Armed(site, after=after, times=times, ms=ms,
                                       key=key)

    def clear(self, site: str | None = None) -> None:
        """Disarm (one site or everything) and release any in-progress
        stall — test teardown must never leave a thread blocked."""
        with self._lock:
            if site is None:
                self._armed.clear()
            else:
                self._armed.pop(site, None)
            self._release.set()

    def release(self) -> None:
        """Unblock any thread currently inside a ``step_stall``."""
        self._release.set()

    def armed(self, site: str) -> bool:
        with self._lock:
            return site in self._armed

    def fired(self, site: str) -> int:
        with self._lock:
            a = self._armed.get(site)
            return a.fired if a else 0

    def fire(self, site: str, key: str | None = None) -> None:
        """Called at the named site. No-op unless armed; otherwise raises
        (``*_raise``), stalls (``step_stall``) or sleeps (``slow_step``)
        per the armed spec. ``key`` identifies the caller for the
        replica-level sites: an armed spec carrying a key neither fires
        NOR counts a hit for any other caller, so ``after=N`` lands on
        replica K's N+1-th step regardless of what its siblings do."""
        with self._lock:
            a = self._armed.get(site)
            if a is None or (a.key is not None and key != a.key):
                return
            if not a.should_fire():
                return
            ms = a.ms
            fired = a.fired
        if TRACER.enabled:
            # the flight recorder sees every fault that actually FIRED —
            # a chaos timeline must show the injected kill next to the
            # spans it killed (runtime/trace.py)
            TRACER.event("fault", 0, site=site, key=key, n=fired)
        if site == "conn_refused":
            # the REAL exception type the connect retry path handles — an
            # injected refusal must walk the same backoff code as a root
            # that is not up yet
            raise ConnectionRefusedError(f"injected {site} (fire #{a.fired})")
        if site.endswith("_raise"):
            raise FaultError(f"injected {site} (fire #{a.fired})")
        if site in ("step_stall", "recv_stall", "replica_stall",
                    "kvx_stall", "spawn_stall"):
            # block like the real hang: until released or ms elapses
            # (default: effectively forever — the watchdog's / the peer
            # heartbeat timeout's job)
            self._release.wait(timeout=(ms / 1e3) if ms else 3600.0)
            return
        if site == "slow_step" and ms:
            import time

            time.sleep(ms / 1e3)

    def triggered(self, site: str, key: str | None = None) -> bool:
        """Count-deterministic QUERY form of ``fire()`` for sites whose
        effect the CALLER performs rather than this registry raising or
        stalling (``frame_truncate``/``peer_close`` — the codec owns the
        socket and performs the mangle itself; ``worker_exit`` — the
        replica worker os._exits). Consumes one invocation count, with
        the same key filter as ``fire()``: an armed spec carrying a key
        neither triggers nor counts for callers with a different key."""
        with self._lock:
            a = self._armed.get(site)
            if a is None or (a.key is not None and key != a.key):
                return False
            fire = a.should_fire()
            fired = a.fired
        if fire and TRACER.enabled:
            TRACER.event("fault", 0, site=site, key=key, n=fired)
        return fire

    def load_env(self, env=None) -> None:
        """Parse ``DLLAMA_FAULTS`` (see module docstring). Malformed specs
        raise ValueError loudly — a typo'd chaos run must not silently
        measure a healthy system."""
        spec = (env if env is not None else os.environ).get(
            "DLLAMA_FAULTS", "")
        for part in filter(None, (p.strip() for p in spec.split(","))):
            site, _, opts = part.partition(":")
            kw: dict = {}
            for opt in filter(None, (o.strip() for o in opts.split(";"))):
                name, _, val = opt.partition("=")
                if name not in ("after", "times", "ms", "key"):
                    raise ValueError(
                        f"bad DLLAMA_FAULTS option {opt!r} in {part!r}")
                kw[name] = (float(val) if name == "ms"
                            else val if name == "key" else int(val))
            self.arm(site, **kw)


FAULTS = FaultRegistry()
FAULTS.load_env()
