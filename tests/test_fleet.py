"""The fleet brain (runtime/fleet.py): load-adaptive autoscaling,
SLO-aware overload shedding, and multi-tenant fairness.

Three tiers of coverage, matching the ISSUE 18 acceptance bars:

  * PURE host-side units (no engine, no sleeps): WFQueue invariants
    (strict priority bands, weighted share, the two-tenant starvation
    bound, the deque duck-type contract ``Scheduler._queue`` relies
    on), TenantLedger token-bucket refill under an injectable clock,
    budget demotion that stays work-conserving, and the ShedLadder's
    monotone rung-by-rung walk with count-based hysteresis + cooldown.
  * FleetController decision units over a FAKE door (tick-driven, zero
    wall-clock dependence): sustained pressure spawns, the scale_flap
    fault proves the anti-flap counters hold, the HBM ledger's
    ``slots_addable`` is a hard ceiling, a dead spawn folds into
    spawn_failures + backoff (never a confused respawn), ``spawn_stall``
    is key-filtered, sustained idle reaps the highest-id idle replica
    down to ``min_replicas``, and the ladder's ``no_spec`` rung lands on
    every local scheduler (and re-lands after a rebuild).
  * Engine-backed e2e (real thread/process replicas): the /readyz +
    ``Router.state`` regression — a draining-for-reap replica must NOT
    flip fleet readiness, and an in-flight scale event reports
    ``scaling_up``/``scaling_down`` — plus a real scale-up → serve →
    scale-down round trip with greedy parity against the single-engine
    oracle. The process-tier regression and the e2e round trip run in
    the CI chaos job (the main matrix deselects them, same split as
    tests/test_bench_outage.py's subprocess smokes).

Everything decision-shaped is count-deterministic: the controller's
``tick()`` is a public synchronous entry point, hysteresis is measured
in ticks, and the ledger takes an injectable clock.
"""

import os
import threading
import time
import types

import pytest

jnp = pytest.importorskip("jax.numpy")

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.faults import FAULTS
from distributed_llama_tpu.runtime.fleet import (DEFAULT_TENANT,
                                                 LADDER_RUNGS, PRIORITIES,
                                                 FleetConfig,
                                                 FleetController,
                                                 ShedLadder, ShedReject,
                                                 TenantLedger, WFQueue,
                                                 parse_tenant_budgets)
from distributed_llama_tpu.runtime.router import ReplicaHandle, Router
from distributed_llama_tpu.sampler import Sampler

SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=SEQ,
                     hidden_act=HiddenAct.SILU)
    host = random_tensors(spec, seed=3, scale=0.05)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    return spec, params


@pytest.fixture(autouse=True)
def clean_faults():
    FAULTS.clear()
    yield
    FAULTS.clear()


def _factory(tiny, batch=2):
    spec, params = tiny

    def make():
        return Engine(spec, params, batch=batch, compute_dtype=jnp.float32,
                      cache_dtype=jnp.float32)

    return make


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)


def _oracle(spec, params, prompt, max_tokens):
    eng = Engine(spec, params, batch=1, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    return eng.generate(prompt, max_tokens, _greedy(spec)).tokens


def _wait(pred, timeout=30.0, poll=0.01):
    end = time.perf_counter() + timeout
    while time.perf_counter() < end:
        if pred():
            return True
        time.sleep(poll)
    return False


class Req:
    """The slice of scheduler.ServeRequest the WFQueue tags read."""

    def __init__(self, tenant=None, priority="normal", cost=8, tag=None):
        self.tenant = tenant
        self.priority = priority
        self.prompt = list(range(max(cost - 1, 1)))
        self.max_tokens = 1
        self.tag = tag if tag is not None else tenant


# -- parse_tenant_budgets -------------------------------------------------


def test_parse_tenant_budgets_accepts_and_refuses():
    assert parse_tenant_budgets(None) == {}
    assert parse_tenant_budgets("") == {}
    out = parse_tenant_budgets("acme=3:5000, free=1:200 ,solo=2")
    assert out == {"acme": (3.0, 5000.0), "free": (1.0, 200.0),
                   "solo": (2.0, 0.0)}
    for bad in ("noequals", "a=x", "a=1:y", "a=0", "a=-1", "a=1:-5"):
        with pytest.raises(ValueError):
            parse_tenant_budgets(bad)


# -- WFQueue: the deque duck-type + fairness invariants -------------------


def test_wfq_duck_types_the_scheduler_deque_slice():
    q = WFQueue()
    assert len(q) == 0 and not q
    q.append(Req("a"))
    assert len(q) == 1 and q
    assert q.popleft().tenant == "a"
    with pytest.raises(IndexError):
        q.popleft()  # the contract Scheduler._abort_all drains on
    assert q.snapshot_depths() == {p: 0 for p in PRIORITIES}


def test_wfq_strict_priority_across_bands():
    """high drains before normal drains before low, regardless of
    arrival order or tags within a band."""
    q = WFQueue()
    for prio in ("low", "normal", "high", "low", "normal", "high"):
        q.append(Req("t", priority=prio, tag=prio))
    assert q.snapshot_depths() == {"high": 2, "normal": 2, "low": 2}
    got = [q.popleft().tag for _ in range(6)]
    assert got == ["high", "high", "normal", "normal", "low", "low"]
    # an unknown priority string lands in the normal band, not a crash
    q.append(Req("t", priority="nonsense", tag="x"))
    assert q.snapshot_depths()["normal"] == 1
    assert q.popleft().tag == "x"


def test_wfq_weighted_share_within_band():
    """Weight 4 vs weight 1, equal-cost backlogs enqueued alternating:
    the first 10 admissions split 8:2 — the SFQ finish tags realise the
    4:1 share without any scan or sort."""
    ledger = TenantLedger({"big": (4.0, 0.0), "small": (1.0, 0.0)})
    q = WFQueue(ledger)
    for _ in range(10):
        q.append(Req("big", cost=8))
        q.append(Req("small", cost=8))
    first = [q.popleft().tenant for _ in range(10)]
    assert first.count("big") == 8 and first.count("small") == 2


def test_wfq_two_tenant_starvation_bound():
    """A victim arriving BEHIND a 50-deep hog backlog is served within
    one pop: its start tag is the band virtual time, not the end of the
    hog's queue — the bound that keeps a hog's burst out of a victim's
    p99. Same priority band, so this is the WFQ's doing, not the
    priority ladder's."""
    ledger = TenantLedger({"hog": (1.0, 0.0), "victim": (4.0, 0.0)})
    q = WFQueue(ledger)
    for _ in range(50):
        q.append(Req("hog", cost=8))
    # let the hog make progress first so the band virtual time moved
    assert q.popleft().tenant == "hog"
    assert q.popleft().tenant == "hog"
    q.append(Req("victim", cost=8))
    assert q.popleft().tenant == "victim"


def test_wfq_budget_demotes_but_stays_work_conserving():
    """An over-budget tenant is served only when no in-budget tenant
    waits — and IS served then (overage rides idle capacity, it is
    never rejected by the queue)."""
    now = [100.0]
    ledger = TenantLedger({"hog": (1.0, 10.0)}, burst_secs=1.0,
                          clock=lambda: now[0])
    assert ledger.in_budget("hog")          # bucket starts full (10)
    ledger.charge("hog", 20)                # balance -10
    assert not ledger.in_budget("hog")
    q = WFQueue(ledger)
    q.append(Req("hog", cost=2))            # smallest finish tag...
    q.append(Req("payer", cost=8))
    assert q.popleft().tenant == "payer"    # ...but demoted behind budget
    assert q.popleft().tenant == "hog"      # work-conserving fallback
    # refill repays the overage: +2 s at 10 tok/s covers the -10 debt
    now[0] += 2.0
    assert ledger.in_budget("hog")


def test_tenant_ledger_refill_caps_at_burst():
    now = [0.0]
    ledger = TenantLedger({"t": (1.0, 10.0)}, burst_secs=2.0,
                          clock=lambda: now[0])
    ledger.charge("t", 15)                  # 20 - 15 = 5
    assert ledger.summary()["t"]["budget_remaining"] == 5.0
    now[0] += 1000.0                        # refill is capped, not a bank
    assert ledger.summary()["t"]["budget_remaining"] == 20.0
    s = ledger.summary()["t"]
    assert s["admitted"] == 1 and s["tokens_charged"] == 15
    # unlimited tenants report no budget and never demote
    assert ledger.summary().get("t")["weight"] == 1.0
    assert ledger.in_budget("never-seen")
    assert ledger.weight("never-seen") == 1.0


# -- ShedLadder: monotone walk, hysteresis, per-rung semantics ------------


def test_ladder_walks_one_rung_at_a_time_with_cooldown():
    lad = ShedLadder(hi=0.8, lo=0.3, up_after=2, down_after=2, cooldown=2)
    up = [lad.observe(1.0) for _ in range(10)]
    # 2 observations above hi per move, 2 ticks of dead time after each:
    # never skips a rung, tops out at shed and stays
    assert up == [0, 1, 1, 2, 2, 3, 3, 4, 4, 4]
    assert lad.escalations == 4 and lad.name == "shed"
    down = [lad.observe(0.0) for _ in range(10)]
    assert down == [4, 3, 3, 2, 2, 1, 1, 0, 0, 0]
    assert lad.recoveries == 4 and lad.name == "healthy"
    # mid-band pressure resets BOTH hysteresis counters
    lad2 = ShedLadder(hi=0.8, lo=0.3, up_after=2, down_after=2, cooldown=0)
    lad2.observe(1.0)
    lad2.observe(0.5)   # between lo and hi: the streak is broken
    lad2.observe(1.0)
    assert lad2.rung == 0


def test_ladder_rung_semantics_per_request():
    lad = ShedLadder(clamp_tokens=64)
    lad.rung = LADDER_RUNGS.index("no_spec")
    assert lad.spec_degraded
    assert lad.admit(max_tokens=500, prefix_hit=False) == (True, 500, None)
    lad.rung = LADDER_RUNGS.index("clamp")
    assert lad.admit(max_tokens=500, prefix_hit=False) == (True, 64, "clamp")
    assert lad.admit(max_tokens=0, prefix_hit=False) == (True, 64, "clamp")
    assert lad.admit(max_tokens=8, prefix_hit=False) == (True, 8, None)
    lad.rung = LADDER_RUNGS.index("prefix_only")
    allowed, _, reason = lad.admit(max_tokens=8, prefix_hit=False)
    assert (allowed, reason) == (False, "prefix_only")
    assert lad.admit(max_tokens=8, prefix_hit=True) == (True, 8, None)
    lad.rung = LADDER_RUNGS.index("shed")
    allowed, _, reason = lad.admit(max_tokens=8, prefix_hit=True)
    assert (allowed, reason) == (False, "shed")


def test_ladder_retry_after_tracks_drain_rate():
    lad = ShedLadder()
    assert lad.retry_after() == 30.0        # no drain signal: worst case
    lad.observe(0.0, queued=16, drained=6.0)
    assert lad.retry_after() == pytest.approx(16 / 3.0)
    lad.observe(0.0, queued=0, drained=100.0)
    assert lad.retry_after() == 0.5         # floor
    lad.observe(0.0, queued=10_000, drained=0.0)
    assert lad.retry_after() == 30.0        # ceiling


# -- FleetController decision units over a fake door ----------------------


class FakeSched:
    def __init__(self):
        self.spec_degraded = False


class FakeSup:
    def __init__(self):
        self.ready = True
        self._sched = FakeSched()


class FakeHandle:
    has_local_engine = True

    def __init__(self, rid, tier="mixed", load=0):
        self.id = rid
        self.tier = tier
        self.reap = False
        self.draining = False
        self.sup = FakeSup()
        self._load = load
        self.drained = False
        self.reap_at_drain = None

    def load(self):
        return self._load

    def drain(self, timeout=30.0):
        self.reap_at_drain = self.reap  # the mark must precede the drain
        self.drained = True
        return True

    def close(self, timeout=30.0):
        pass

    def note_routed(self, prompt):
        pass


class FakeDoor:
    def __init__(self, n=1, tier="mixed", batch=4):
        self.engine = types.SimpleNamespace(batch=batch)
        self.replicas = [FakeHandle(i, tier) for i in range(n)]
        self.scaling = None
        self._spawn_factory = None
        self._kv_transfer = False
        self._summary = {}
        self.reaped = []

    def summary(self):
        return dict(self._summary)

    def add_replica(self, handle):
        self.replicas.append(handle)

    def reap_replica(self, rid, timeout=30.0):
        self.reaped.append(rid)
        self.replicas = [h for h in self.replicas if h.id != rid]


def _settle(fc, timeout=30.0):
    for t in list(fc._scaling_threads):
        t.join(timeout=timeout)
        assert not t.is_alive()


def _cfg(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("up_after", 2)
    kw.setdefault("down_after", 2)
    kw.setdefault("cooldown_ticks", 0)
    kw.setdefault("ewma_alpha", 1.0)  # ewma == raw pressure: exact ticks
    return FleetConfig(**kw)


def test_controller_scales_up_on_sustained_pressure():
    door = FakeDoor(n=1)
    door.replicas[0]._load = 8          # pressure 8 / (1*4) = 2.0
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    fc = FleetController(door, config=_cfg())
    fc.tick()                           # above = 1: not yet
    assert len(door.replicas) == 1
    fc.tick()                           # above = 2 = up_after: spawn
    _settle(fc)
    assert [h.id for h in door.replicas] == [0, 1]
    assert fc.stats.scale_ups == 1
    assert fc.stats.target_replicas == 2
    assert door.scaling is None         # cleared when the spawn lands
    assert fc.summary()["actual_replicas"] == 2
    # at max_replicas the walk refuses further spawns
    door.replicas.append(FakeHandle(2))
    fc.tick()
    fc.tick()
    _settle(fc)
    assert len(door.replicas) == 3 and fc.stats.scale_ups == 1


def test_concurrent_spawns_mint_distinct_ids_and_respect_ceiling():
    """A spawn can take minutes. A second decision inside that window
    must count the in-flight spawn toward max_replicas and mint a
    DISTINCT id — never a duplicate add_replica, never an overshoot
    (the double-mint race the bench's first run exposed)."""
    door = FakeDoor(n=1)
    door.replicas[0]._load = 8
    gate = threading.Event()
    minted = []

    def slow_factory(rid, tier):
        minted.append(rid)
        gate.wait(timeout=30.0)
        return FakeHandle(rid, tier)

    door._spawn_factory = slow_factory
    fc = FleetController(door, config=_cfg(up_after=1, max_replicas=3))
    fc.tick()                           # spawn r1 (parked on the gate)
    assert _wait(lambda: minted == [1])
    fc.tick()                           # r1 still pending: mints r2
    assert _wait(lambda: minted == [1, 2])
    fc.tick()                           # 1 live + 2 pending = max: refused
    gate.set()
    _settle(fc)
    assert minted == [1, 2]
    assert sorted(h.id for h in door.replicas) == [0, 1, 2]
    assert fc.stats.scale_ups == 2 and fc.stats.spawn_failures == 0


def test_controller_scale_flap_fault_proves_antiflap():
    """scale_flap replaces the measured pressure with a 1.0/0.0 square
    wave — count-based hysteresis must ride it out with ZERO decisions
    in either direction."""
    door = FakeDoor(n=2)
    attempts = []
    door._spawn_factory = lambda rid, tier: attempts.append(rid)
    fc = FleetController(door, config=_cfg())
    FAULTS.arm("scale_flap", times=8)
    for _ in range(8):
        fc.tick()
    _settle(fc)
    assert FAULTS.fired("scale_flap") == 8
    assert attempts == [] and door.reaped == []
    assert fc.stats.scale_ups == 0 and fc.stats.scale_downs == 0
    assert fc.stats.ticks == 8


def test_controller_hbm_ceiling_blocks_spawn():
    door = FakeDoor(n=1)
    door.replicas[0]._load = 8
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    door._summary = {"replicas": [{"hbm": {"slots_addable": 0}}]}
    fc = FleetController(door, config=_cfg())
    for _ in range(4):
        fc.tick()
    _settle(fc)
    assert len(door.replicas) == 1
    assert fc.stats.scale_ups == 0
    assert fc.stats.scale_blocked_hbm >= 1
    # headroom appears (an eviction, a reap elsewhere): the next
    # sustained window spawns
    door._summary = {"replicas": [{"hbm": {"slots_addable": 4}}]}
    fc.tick()
    fc.tick()
    _settle(fc)
    assert len(door.replicas) == 2 and fc.stats.scale_ups == 1


def test_controller_spawn_failure_folds_into_backoff():
    """A spawn that dies (the SIGKILL-mid-scale-up shape) counts one
    spawn_failure and backs the walk off for spawn_backoff_ticks —
    never a half-entered handle, never a tight respawn loop."""
    door = FakeDoor(n=1)
    door.replicas[0]._load = 8
    attempts = []

    def dying_factory(rid, tier):
        attempts.append(rid)
        raise RuntimeError("injected spawn death")

    door._spawn_factory = dying_factory
    fc = FleetController(door, config=_cfg(up_after=1,
                                           spawn_backoff_ticks=3))
    fc.tick()
    _settle(fc)
    assert attempts == [1]
    assert fc.stats.spawn_failures == 1
    assert len(door.replicas) == 1 and door.scaling is None
    fc.tick()   # backoff 3 -> 2: no new attempt
    fc.tick()   # 2 -> 1: still backing off
    _settle(fc)
    assert attempts == [1]
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    fc.tick()   # 1 -> 0: backoff expired, the walk tries again
    _settle(fc)
    assert len(door.replicas) == 2 and fc.stats.scale_ups == 1


def test_spawn_stall_fault_is_key_filtered():
    """An armed spawn_stall carrying key=rK neither stalls NOR counts
    for any other replica's spawn — one scale-up can be stalled
    deterministically while siblings spawn clean."""
    door = FakeDoor(n=1)
    door.replicas[0]._load = 8
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    fc = FleetController(door, config=_cfg(up_after=1))
    FAULTS.arm("spawn_stall", key="r99", ms=60_000)  # not our replica
    fc.tick()
    _settle(fc)
    assert len(door.replicas) == 2
    assert FAULTS.fired("spawn_stall") == 0          # not even a hit
    # now stall THE replica the next scale-up mints (rid 2), briefly
    FAULTS.clear()
    FAULTS.arm("spawn_stall", key="r2", ms=100)
    door.replicas[0]._load = 12
    door.replicas[1]._load = 12
    fc.tick()
    _settle(fc)
    assert FAULTS.fired("spawn_stall") == 1
    assert len(door.replicas) == 3                   # stalled, not dead


def test_controller_scales_down_idle_and_respects_floor():
    door = FakeDoor(n=3)
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    fc = FleetController(door, config=_cfg(min_replicas=2))
    fc.tick()                           # idle = 1 (pressure 0 < 0.15)
    fc.tick()                           # idle = 2 = down_after: reap
    _settle(fc)
    assert door.reaped == [2]           # highest-id idle victim
    assert fc.stats.scale_downs == 1 and door.scaling is None
    # the reap mark preceded the drain (the /readyz satellite's ordering)
    fc.tick()
    fc.tick()
    _settle(fc)
    assert door.reaped == [2]           # min_replicas=2 is the floor
    assert len(door.replicas) == 2


def test_reap_mark_precedes_drain():
    door = FakeDoor(n=2)
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    fc = FleetController(door, config=_cfg())
    victim = door.replicas[1]
    fc.tick()
    fc.tick()
    _settle(fc)
    assert victim.drained and victim.reap_at_drain is True


def test_controller_never_reaps_last_replica():
    door = FakeDoor(n=1)
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    fc = FleetController(door, config=_cfg(min_replicas=1))
    for _ in range(6):
        fc.tick()
    _settle(fc)
    assert door.reaped == [] and len(door.replicas) == 1


def test_controller_applies_and_recovers_degrade():
    """Rung >= no_spec lands on every local scheduler, re-lands after a
    rebuild (fresh scheduler object), and recovery clears it."""
    door = FakeDoor(n=1)
    h = door.replicas[0]
    h._load = 8
    lad = ShedLadder(hi=0.8, lo=0.3, up_after=1, down_after=1, cooldown=0)
    fc = FleetController(door, ladder=lad)
    fc.tick()
    assert lad.rung == 1 and h.sup._sched.spec_degraded
    h.sup._sched = FakeSched()          # supervisor rebuild mid-degrade
    assert not h.sup._sched.spec_degraded
    fc.tick()                           # re-applied within one tick
    assert h.sup._sched.spec_degraded   # (and escalated again: rung 2)
    assert lad.rung == 2
    h._load = 0
    fc.tick()                           # rung 2 -> 1: still degraded
    fc.tick()                           # rung 1 -> 0: recovered
    assert lad.rung == 0 and not h.sup._sched.spec_degraded
    assert fc.stats.rung == 0


def test_controller_admit_accounts_clamps_and_sheds():
    lad = ShedLadder(clamp_tokens=64)
    ledger = TenantLedger({"acme": (2.0, 0.0)})
    fc = FleetController(FakeDoor(n=1), ladder=lad, ledger=ledger)
    # healthy: pass-through
    assert fc.admit(tenant="acme", n_prompt=4, max_tokens=500) == 500
    lad.rung = LADDER_RUNGS.index("clamp")
    assert fc.admit(tenant="acme", n_prompt=4, max_tokens=500) == 64
    assert fc.stats.clamped == 1
    lad.rung = LADDER_RUNGS.index("prefix_only")
    assert fc.admit(tenant="acme", n_prompt=4, max_tokens=8,
                    prefix_hit=True) == 8
    with pytest.raises(ShedReject) as e:
        fc.admit(tenant="acme", n_prompt=4, max_tokens=8, prefix_hit=False)
    assert e.value.reason == "prefix_only"
    lad.rung = LADDER_RUNGS.index("shed")
    with pytest.raises(ShedReject) as e:
        fc.admit(tenant=None, n_prompt=4, max_tokens=8)
    assert e.value.reason == "shed"
    assert 0.5 <= e.value.retry_after <= 30.0
    assert fc.stats.sheds == 2
    assert fc.stats.sheds_by_reason == {"prefix_only": 1, "shed": 1}
    tenants = fc.summary()["tenants"]
    assert tenants["acme"]["shed"] == 1
    assert tenants[DEFAULT_TENANT]["shed"] == 1
    # no ladder (no SLO flags): admit never touches the request
    fc2 = FleetController(FakeDoor(n=1))
    assert fc2.admit(tenant="x", n_prompt=1, max_tokens=10 ** 6) == 10 ** 6


def test_controller_summary_shape():
    door = FakeDoor(n=2)
    door._spawn_factory = lambda rid, tier: FakeHandle(rid, tier)
    fc = FleetController(door, config=_cfg(),
                         ladder=ShedLadder(),
                         ledger=TenantLedger({"a": (1.0, 0.0)}))
    s = fc.summary()
    assert s["actual_replicas"] == 2 and s["target_replicas"] == 2
    assert s["min_replicas"] == 1 and s["max_replicas"] == 3
    assert s["autoscaling"] is True
    assert s["ladder"]["name"] == "healthy"
    assert "a" in s["tenants"]
    # a reap-marked replica is not actual capacity
    door.replicas[1].reap = True
    assert fc.summary()["actual_replicas"] == 1


def test_prefill_and_serve_tiers_observed_independently():
    door = FakeDoor(n=2)
    door.replicas[1].tier = "prefill"
    door.replicas[0]._load = 8          # serve pressure 2.0
    door.replicas[1]._load = 0          # prefill pressure 0.0
    fc = FleetController(door)
    obs = fc.tick()["obs"]
    assert obs["serve"][0] == pytest.approx(2.0)
    assert obs["prefill"][0] == pytest.approx(0.0)
    # a reap-marked replica is excluded from the signal entirely
    door.replicas[1].reap = True
    assert "prefill" not in fc.tick()["obs"]


# -- engine-backed: /readyz + state regression (thread tier) --------------


def test_reap_mark_does_not_flip_readiness_thread_tier(tiny):
    """Satellite 2: a replica draining FOR REAP is a capacity decision —
    /readyz stays ready, Router.state stays "ready" (or reports the
    in-flight scale direction), and requests route around the victim."""
    spec, params = tiny
    router = Router(_factory(tiny), replicas=2, chunk=8,
                    stall_timeout=60.0, backoff_base=0.01)
    try:
        assert _wait(lambda: router.ready)
        assert router.state == "ready"
        router.replicas[1].reap = True
        assert router.ready                     # sibling still routable
        assert router.state == "ready"          # NOT "draining"
        router.scaling = "scaling_down"
        assert router.state == "scaling_down"   # in-flight scale event
        router.scaling = None
        # the reaped replica never takes traffic
        p = [1, 2, 3]
        got = list(router.submit(p, 3, _greedy(spec)).tokens(timeout=60.0))
        assert got == _oracle(spec, params, p, 3)
        assert router.replicas[1].load() == 0
        # every replica reap-marked: the tier is draining, and an
        # in-flight scale event still wins the report
        router.replicas[0].reap = True
        assert not router.ready
        assert router.state == "draining"
        router.scaling = "scaling_up"
        assert router.state == "scaling_up"
    finally:
        router.scaling = None
        for h in router.replicas:
            h.reap = False
        router.close()


# -- engine-backed e2e: scale-up -> serve -> scale-down (chaos job) -------


def test_fleet_scale_roundtrip_thread_tier(tiny):
    """A real scale-up (fresh supervised replica over shared weights),
    greedy parity through the grown fleet, then a scale-down that reaps
    the newest replica — readiness never flickers."""
    spec, params = tiny
    factory = _factory(tiny)
    sup_kwargs = dict(chunk=8, stall_timeout=60.0)
    router = Router(factory, replicas=2, chunk=8, stall_timeout=60.0,
                    backoff_base=0.01)
    router._spawn_factory = lambda rid, tier: ReplicaHandle(
        rid, factory, sup_kwargs, tier=tier)
    fc = FleetController(router, config=FleetConfig(
        min_replicas=2, max_replicas=3, up_pressure=-1.0,
        down_pressure=-2.0, up_after=1, down_after=1,
        cooldown_ticks=0, ewma_alpha=1.0))
    try:
        assert _wait(lambda: router.ready)
        fc.tick()                       # pressure 0 > -1: scale up
        _settle(fc, timeout=120.0)
        assert [h.id for h in router.replicas] == [0, 1, 2]
        assert fc.stats.scale_ups == 1
        assert router.ready and router.state == "ready"
        p = [2, 4, 6]
        got = list(router.submit(p, 3, _greedy(spec)).tokens(timeout=60.0))
        assert got == _oracle(spec, params, p, 3)
        before = router.summary()["requests_finished"]
        # flip the thresholds: idle now reads as scale-down pressure
        fc.config.up_pressure = 10.0
        fc.config.down_pressure = 10.0
        fc.tick()
        _settle(fc, timeout=60.0)
        assert [h.id for h in router.replicas] == [0, 1]
        assert fc.stats.scale_downs == 1
        assert router.ready and router.state == "ready"
        # counter totals survive the reap (the _reap_carry fold)
        assert router.summary()["requests_finished"] >= before
        got = list(router.submit(p, 3, _greedy(spec)).tokens(timeout=60.0))
        assert got == _oracle(spec, params, p, 3)
    finally:
        fc.close()
        router.close()


# -- process tier: reap/state regression (chaos job) ----------------------


def test_reap_mark_does_not_flip_readiness_process_tier(tmp_path):
    """The same satellite-2 regression across the REAL fault boundary:
    two spawned worker processes, one reap-marked — the tier stays
    ready and the state report never calls a controller decision a
    health problem."""
    from distributed_llama_tpu.runtime.replica_worker import WorkerProc
    from distributed_llama_tpu.runtime.router import RemoteReplicaHandle

    cfg = {"test_spec": dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                             n_kv_heads=2, vocab_size=128, seq_len=SEQ),
           "seed": 3, "scale": 0.05, "compute_dtype": "f32", "batch": 2,
           "serve": {"stall_timeout": 60.0}}
    wenv = {"JAX_PLATFORMS": "cpu"}

    def mk(i):
        proc = WorkerProc(i, dict(cfg, fault_key=f"r{i}"),
                          workdir=str(tmp_path), env=wenv)
        return RemoteReplicaHandle(i, proc=proc, poll_interval=0.1,
                                   spawn_timeout=120.0,
                                   respawn_timeout=120.0)

    handles = [None, None]

    def build(i):
        handles[i] = mk(i)

    threads = [threading.Thread(target=build, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(h is not None for h in handles), "worker spawn failed"
    router = Router(None, handle_factories=[lambda: handles[0],
                                            lambda: handles[1]])
    try:
        assert _wait(lambda: router.ready, timeout=120.0)
        router.replicas[1].reap = True
        assert router.ready and router.state == "ready"
        router.scaling = "scaling_down"
        assert router.state == "scaling_down"
        router.scaling = None
        # traffic routes around the reap-marked worker
        sam = Sampler(128, temperature=0.0, topp=0.9, seed=1)
        got = list(router.submit([1, 2, 3], 3, sam).tokens(timeout=60.0))
        assert len(got) == 3
    finally:
        router.close()
