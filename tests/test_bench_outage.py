"""bench.py failure behavior: a failure or a SIGTERM after rows were
measured must still yield the structured JSON line with those rows — and
a non-zero exit, so a cut run is never read as a complete one. Every row
names the device it ran on (ref for the bar these protect:
src/apps/dllama/dllama.cpp benchmark output always prints).

Also: the dryrun entry point is a CPU check that pins its platform itself
and refuses a process that already holds a backend."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env: dict, timeout: float = 300.0):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_MODEL": "tiny",
        "BENCH_TOKENS": "4",
        "BENCH_REPEATS": "1",
        "BENCH_VARIANTS": "0",
    })
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def test_midrun_outage_keeps_completed_rows():
    # a failure AFTER the main row was measured must still print the final
    # JSON with the measured value plus the error annotation
    r = _run_bench({"BENCH_SIMULATE_OUTAGE": "1"})
    assert r.returncode != 0, "a failed run must not exit 0"
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["value"] is not None and row["value"] > 0
    assert "simulated mid-run outage" in row["error"]
    # the completed main row was also flushed incrementally to stderr
    flushed = [json.loads(line) for line in r.stdout.splitlines()[:-1]] + [
        json.loads(line) for line in r.stderr.splitlines()
        if line.startswith("{")]
    assert any(x.get("metric") == row["metric"] and x.get("value")
               for x in flushed)


def test_healthy_run_emits_one_parseable_line():
    r = _run_bench({})
    assert r.returncode == 0, r.stderr
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    assert len(lines) == 1  # stdout carries exactly the one JSON line
    row = json.loads(lines[0])
    assert row["value"] > 0 and "error" not in row
    assert row["unit"] == "ms/token"
    # every row names its device; a CPU run carries no device metric
    assert (row["platform"], row["device_kind"]) == ("cpu", "cpu")
    assert row["device_count"] >= 1
    assert row["mfu"] is None and row["effective_hbm_gbs"] is None


def test_unknown_model_is_an_error_not_tiny():
    r = _run_bench({"BENCH_MODEL": "no-such-model"}, timeout=120.0)
    assert r.returncode != 0
    assert "unknown BENCH_MODEL" in r.stderr
    assert not r.stdout.strip()  # no row under a wrong model's name


def test_serve_row_emits_valid_json():
    """BENCH_SERVE=1 adds the continuous-batching Poisson-arrival row
    (bench._serve_row) with the serving metrics the scheduler promises —
    aggregate tok/s, the static-batch ratio, TTFT/ITL percentiles — all
    as one valid JSON variant (a tiny trace keeps this smoke-fast; the
    default bench stays serve-free)."""
    r = _run_bench({
        "BENCH_SERVE": "1",
        "BENCH_SERVE_REQUESTS": "4",
        "BENCH_SERVE_BATCH": "2",
        "BENCH_SERVE_BUDGETS": "4,8",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    serve = [v for v in row.get("variants", [])
             if "continuous" in v["metric"]]
    assert len(serve) == 1, row
    s = serve[0]
    assert s["unit"] == "tok/s" and s["value"] > 0
    assert s["static_agg_tok_per_s"] > 0 and s["vs_static_batch"] > 0
    assert s["batch"] == 2 and s["requests"] >= 2
    assert s["ttft_p50_ms"] >= 0 and s["ttft_p99_ms"] >= s["ttft_p50_ms"]
    assert 0 < s["mean_slot_occupancy"] <= 2
    # ISSUE-10 satellite: every bench row carries the hbm ledger next to
    # step_timeline — exact allocated bytes, not estimates
    hbm = s["hbm"]
    assert hbm["kv_slot_bytes"] > 0 and hbm["weights_bytes"] > 0
    assert hbm["per_slot_bytes"] * s["batch"] == hbm["kv_slot_bytes"]
    assert s["step_timeline"], s  # the curve dlprof consumes below
    json.dumps(s)  # the row round-trips as machine-readable JSON

    # ISSUE-10 acceptance: tools/dlprof.py over this REAL BENCH_SERVE=1
    # artifact reproduces the batch-composition -> ms/step curve from
    # the step_timeline block and emits a non-null knee + --serve-batch
    # recommendation
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import dlprof

    report = dlprof.analyze([], [row] + row.get("variants", []))
    sc = report["step_curve"]
    assert sc["decode_points"], sc       # the curve reproduced
    assert sc["knee"] is not None and sc["knee"]["knee_rows"] >= 1
    rec = sc["recommendation"]
    assert rec is not None and rec["serve_batch"] >= 1
    assert report["hbm"] is not None     # the ledger rode the artifact


def test_kvx_row_emits_valid_json():
    """BENCH_KVX=1 adds the cross-replica KV block transfer row
    (bench._kvx_row). The DETERMINISTIC acceptance bars are exact here:
    greedy TOKEN PARITY transfer-on vs -off AND unified vs
    disaggregated, every cold request filled (hit rate 1.0 on this
    trace, zero fallbacks), the measured BLOCK_DATA wire bytes
    RECONCILED against the frame arithmetic at drift 0.0, and zero
    post-warmup compiles with the ledger frozen through the ON serve.
    The >= 30% cold-TTFT bar is pinned on the COMMITTED BENCH_r08.json
    row, not on CI timing."""
    r = _run_bench({
        "BENCH_KVX": "1",
        "BENCH_KVX_FAMILIES": "3",
        "BENCH_KVX_SYS": "48",
        "BENCH_KVX_BLOCK": "16",
        "BENCH_KVX_TOKENS": "6",
        "BENCH_KVX_STREAMS": "2",
        "BENCH_KVX_LONG": "64",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    rows = [v for v in row.get("variants", [])
            if "kv_transfer" in v["metric"]]
    assert len(rows) == 1, row
    v = rows[0]
    assert v["token_parity"] is True, v
    assert v["token_parity_disagg"] is True, v
    assert v["fills_ok"] == 3 and v["fill_fallbacks"] == 0, v
    assert v["fill_hit_rate"] == 1.0, v
    assert v["compiles_after_warmup"] == 0, v
    rec = v["reconcile"]
    assert rec["drift"] is False and rec["drift_frac"] == 0.0, rec
    assert v["bytes_rx"] > 0 and v["tokens_filled"] > 0
    assert v["unified"]["itl_p99_ms"] is not None
    assert v["disaggregated"]["itl_p99_ms"] is not None
    json.dumps(v)  # machine-readable round trip

    # the COMMITTED row carries the acceptance bars the CI run cannot
    # time-assert: >= 30% cold-replica TTFT p50 gain with fills on,
    # reconcile within the 25% bar, zero frozen-ledger compiles
    art = os.path.join(REPO, "BENCH_r08.json")
    committed = json.load(open(art))
    cv = [x for x in committed["variants"]
          if "kv_transfer" in x["metric"]][0]
    assert cv["value"] >= 30.0, cv["value"]
    assert cv["token_parity"] is True and cv["token_parity_disagg"] \
        is True
    assert cv["reconcile"]["drift"] is False
    assert cv["compiles_after_warmup"] == 0
    assert cv["fill_hit_rate"] == 1.0


def test_vocab_row_emits_valid_json():
    """BENCH_VOCAB=1 adds the vocab-sharding A/B row (bench._vocab_row):
    sharded vs replicated embedding+head served over a tp=2 CPU mesh on
    the SAME mixed greedy/sampled trace. The DETERMINISTIC acceptance
    bars are exact: greedy TOKEN PARITY sharded vs replicated, the
    per-chip embedding shard exactly halving the `vocab` HBM category,
    and ZERO post-warmup compiles per variant with the ledger frozen
    (head ms is reported, never time-asserted in CI). The committed
    BENCH_r09.json row pins the same bars."""
    r = _run_bench({
        "BENCH_VOCAB": "1",
        "BENCH_VOCAB_REQUESTS": "6",
        "BENCH_VOCAB_TOKENS": "6",
        "BENCH_VOCAB_STEPS": "6",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    rows = [v for v in row.get("variants", [])
            if "vocab_shard" in v["metric"]]
    assert len(rows) == 1, row
    v = rows[0]
    assert "error" not in v, v
    assert v["token_parity"] is True, v
    assert v["tp"] == 2
    assert v["compiles_after_warmup_sharded"] == 0, v
    assert v["compiles_after_warmup_replicated"] == 0, v
    # the freed bytes are real: the embedding shard is exactly 1/tp
    # (wcls was row-split already — both variants carry its half)
    on, off = (v["vocab_bytes_per_chip_sharded"],
               v["vocab_bytes_per_chip_replicated"])
    assert 0 < on < off, v
    assert v["value"] > 0 and v["head_sample_ms_replicated"] > 0
    assert v["sampled_via_candidates"] > 0
    json.dumps(v)

    # committed-row bars (BENCH_r09.json): parity + zero compiles +
    # the byte split — pinned on the artifact, not CI timing
    art = os.path.join(REPO, "BENCH_r09.json")
    committed = json.load(open(art))
    cv = [x for x in committed["variants"]
          if "vocab_shard" in x["metric"]][0]
    assert cv["token_parity"] is True
    assert cv["compiles_after_warmup_sharded"] == 0
    assert (cv["vocab_bytes_per_chip_sharded"]
            < cv["vocab_bytes_per_chip_replicated"])


def test_spec_row_emits_valid_json():
    """BENCH_SPEC=1 adds the REAL-draft speculative-decoding row
    (bench._spec_row): self-draft vs prompt-lookup vs plain greedy on a
    fixed-seed eval + the per-slot Poisson serving A/B. The
    DETERMINISTIC acceptance bars are exact here — bit-identical token
    streams across all paths AND zero post-warmup compiles with the
    ledger frozen through the speculative serve; the >1.5x single-stream
    and serving-gain bars are pinned on the COMMITTED BENCH_r07.json row
    (wall-clock ratios on a loaded CI box are not a regression signal).
    The accept rate and the repetitive/non-repetitive label must be ON
    the row — the VERDICT #6 reporting debt."""
    r = _run_bench({
        "BENCH_SPEC": "1",
        "BENCH_SPEC_TOKENS": "48",
        "BENCH_SPEC_REQUESTS": "6",
        "BENCH_SPEC_BATCH": "2",
        "BENCH_SPEC_REPEATS": "1",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    sp = [v for v in row.get("variants", [])
          if "selfdraft" in v["metric"]]
    assert len(sp) == 1, row
    s = sp[0]
    assert s["unit"] == "x" and s["value"] > 0
    assert s["token_parity"] is True          # bit-identical everywhere
    assert s["compiles_after_warmup"] == 0    # the frozen serve held
    # the honest-reporting bars: measured accept rate + regime label
    assert s["eval_label"] in ("repetitive", "non_repetitive")
    assert 0.0 <= s["selfdraft"]["accept_rate"] <= 1.0
    assert s["selfdraft"]["drafted"] >= s["selfdraft"]["accepted"]
    assert s["prompt_lookup"]["tokens_per_forward"] >= 1.0
    assert s["serving_ab"]["draft_on"]["spec"]["verify_forwards"] >= 1
    json.dumps(s)  # machine-readable round trip

    # the committed row's acceptance bars: >1.5x single-stream at exact
    # parity on a NON-repetitive eval, serving A/B gain, zero compiles
    committed = json.load(open(os.path.join(REPO, "BENCH_r07.json")))
    cs = [v for v in committed["variants"] if "selfdraft" in v["metric"]][0]
    assert cs["value"] > 1.5
    assert cs["eval_label"] == "non_repetitive"
    assert cs["repeated_3gram_frac"] <= 0.2
    assert cs["token_parity"] is True
    assert cs["compiles_after_warmup"] == 0
    assert cs["serving_ab"]["agg_speedup"] > 1.0
    # the control: prompt lookup proposes ~nothing on this trace — the
    # regime the lookup rows never covered is exactly where the real
    # draft generalizes the win
    assert cs["prompt_lookup"]["tokens_per_forward"] < 1.2


def test_autotune_row_emits_valid_json():
    """BENCH_AUTOTUNE=1 adds the closed batch-knee-loop row
    (bench._autotune_row): inline calibration -> auto-sized batch ->
    SLO-aware adaptive serve, A/B'd against static settings. The
    DETERMINISTIC acceptance bars ride the assertions — greedy token
    parity across every policy and ZERO post-warmup compiles across the
    adaptive run (the freeze held) — plus artifact structure; the
    beats-all-static goodput bar is pinned on the COMMITTED
    BENCH_r06.json row (a timing race on a loaded CI box is not a
    regression signal, the committed A/B is)."""
    r = _run_bench({
        "BENCH_AUTOTUNE": "1",
        "BENCH_AUTOTUNE_REQUESTS": "8",
        "BENCH_AUTOTUNE_TOKENS": "8",
        "BENCH_AUTOTUNE_BATCHES": "2,4",
        "BENCH_AUTOTUNE_STATIC": "2:16,2:8",
        "BENCH_AUTOTUNE_REPEATS": "1",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    at = [v for v in row.get("variants", [])
          if "autotune" in v["metric"]]
    assert len(at) == 1, row
    a = at[0]
    assert a["unit"] == "tok/s" and a["value"] > 0
    assert a["token_parity"] is True          # greedy outputs identical
    assert a["compiles_after_warmup"] == 0    # the ladder was all warmed
    assert a["freeze_compiles"] is True       # ...and the freeze held
    # the loop's decision record is complete and machine-readable
    assert a["calibration"]["knee"]["knee_rows"] >= 1
    assert a["calibration"]["decode_curve"], a["calibration"]
    assert a["autosize"]["serve_batch"] == a["serve_batch_auto"] >= 1
    assert a["adaptive"]["adaptive"] is True
    assert a["adaptive"]["admission"]["chunk_ladder"][0] == 32
    assert len(a["static"]) == 2
    assert a["best_static"]["goodput_tok_s"] > 0
    assert isinstance(a["beats_all_static"], bool)
    json.dumps(a)  # the row round-trips as machine-readable JSON

    # the committed artifact's acceptance bar: the self-tuned scheduler
    # met or beat every swept static setting on goodput-at-SLO there
    committed = json.load(open(os.path.join(REPO, "BENCH_r06.json")))
    cat = [v for v in committed["variants"] if "autotune" in v["metric"]][0]
    assert cat["beats_all_static"] is True
    assert cat["token_parity"] is True
    assert cat["compiles_after_warmup"] == 0

    # dlprof consumes the committed row + the committed calibration
    # artifact end to end (the drift machinery over real data)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import dlprof

    art = dlprof.load_autotune(os.path.join(REPO, "AUTOTUNE.json"))
    report = dlprof.analyze([], [committed] + committed["variants"],
                            autotune=art)
    assert report["autotune"]["calibrated_knee_rows"] >= 1
    assert isinstance(report["autotune"]["drift"], bool)


def test_prefix_row_emits_valid_json():
    """BENCH_PREFIX=1 adds the radix prefix-cache row (bench._prefix_row):
    the shared-system-prompt Poisson trace served cache OFF vs ON. The
    acceptance bar rides the assertions: >= 50% of prefill tokens served
    from cache on this workload, and greedy outputs TOKEN-IDENTICAL to
    the cache-off run — all as one machine-readable JSON variant."""
    r = _run_bench({
        "BENCH_PREFIX": "1",
        "BENCH_PREFIX_REQUESTS": "4",
        "BENCH_PREFIX_BATCH": "2",
        "BENCH_PREFIX_SYS": "48",
        "BENCH_PREFIX_BLOCK": "16",
        "BENCH_PREFIX_TOKENS": "6",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    pfx = [v for v in row.get("variants", [])
           if "prefix_cache" in v["metric"]]
    assert len(pfx) == 1, row
    p = pfx[0]
    assert p["unit"] == "%" and p["value"] >= 50.0  # acceptance bar
    assert p["token_parity"] is True                # exact greedy parity
    assert p["requests"] == 4 and p["hit_rate"] > 0
    assert p["tokens_saved"] >= 48 * 3  # every replayed request seeded
    assert p["ttft_p50_ms_on"] >= 0 and p["ttft_p50_ms_off"] >= 0
    assert p["hbm"]["prefix_arena_bytes"] > 0  # the REAL arena's bytes
    json.dumps(p)  # the row round-trips as machine-readable JSON


def test_router_row_emits_valid_json():
    """BENCH_ROUTER=1 adds the 2-replica failover-router row
    (bench._router_row). The acceptance bars ride the assertions:
    cache-aware placement beats round-robin on prefix hit rate
    (deterministic closed-loop A/B), the open-loop chaos pass with one
    injected replica kill loses ZERO not-yet-streamed requests while
    service-level readiness never blinks, and every completed request is
    greedy token-identical across all three serves."""
    r = _run_bench({
        "BENCH_ROUTER": "1",
        "BENCH_ROUTER_PROCS": "0",   # thread row only (procs row below)
        "BENCH_ROUTER_REQUESTS": "10",
        "BENCH_ROUTER_GROUPS": "3",
        "BENCH_ROUTER_SYS": "32",
        "BENCH_ROUTER_BLOCK": "16",
        "BENCH_ROUTER_TOKENS": "6",
        "BENCH_ROUTER_KILL_AFTER": "4",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    rows = [v for v in row.get("variants", []) if "router" in v["metric"]]
    assert len(rows) == 1, row
    v = rows[0]
    assert v["unit"] == "%" and v["replicas"] == 2
    # cache-aware beats round-robin on the shared-prefix trace (the
    # ISSUE-6 acceptance bar; closed-loop => deterministic, no timing luck)
    assert v["hit_rate_gain_pct"] > 0, v
    assert v["cache_aware"]["hit_rate_pct"] > \
        v["round_robin"]["hit_rate_pct"], v
    assert v["value"] == v["cache_aware"]["hit_rate_pct"]
    # the chaos pass really killed a replica, and clients never saw an
    # unstreamed request fail — only structured mid-stream frames
    chaos = v["cache_aware_chaos"]
    assert chaos["crashes_injected"] >= 1, chaos
    assert chaos["unstreamed_failures"] == 0, chaos
    assert chaos["completed"] + chaos["midstream_failures"] == 10, chaos
    assert chaos["availability_pct"] is not None
    assert chaos["availability_pct"] >= 99.0, chaos  # readiness held
    assert v["token_parity"] is True
    assert v["hbm"]["kv_slot_bytes"] > 0  # one replica's exact shape
    json.dumps(v)  # the row round-trips as machine-readable JSON


def test_router_procs_row_emits_valid_json():
    """BENCH_ROUTER=1 also grows the PROCESS-mode row
    (bench._router_procs_row; BENCH_ROUTER_PROCS=only selects just it):
    two real replica worker OS processes behind the framed protocol, one
    delivered a genuine SIGKILL mid-Poisson-trace. The ISSUE-7 acceptance
    bars ride the assertions: ZERO unstreamed request failures (failover
    to the sibling), service availability held by the survivor, the
    supervisor classified the SIGKILL and respawned the worker to
    routable within the bound, and every completed serve of the same
    prompt is greedy token-identical — including post-respawn."""
    r = _run_bench({
        "BENCH_ROUTER": "1",
        "BENCH_ROUTER_PROCS": "only",
        "BENCH_PROCS_REQUESTS": "6",
        "BENCH_PROCS_TOKENS": "4",
        "BENCH_PROCS_KILL_AFTER": "3",
        "BENCH_PROCS_STEP_MS": "40",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    rows = [v for v in row.get("variants", [])
            if "router_procs" in v["metric"]]
    assert len(rows) == 1, row
    v = rows[0]
    assert v["unit"] == "ms" and v["mode"] == "process"
    # the kill really happened and was classified as a real SIGKILL
    assert v["exit_classes"].get("signal:SIGKILL") == 1, v
    assert v["respawns"] == 1, v
    # supervised respawn-to-routable within the configured bound
    assert v["within_bound"] is True, v
    assert v["value"] is not None and v["value"] > 0
    assert v["respawn_p50_ms"] is not None and v["respawn_p50_ms"] > 0
    # zero unstreamed failures; mid-stream casualties only, structured
    assert v["unstreamed_failures"] == 0, v
    assert v["completed"] + v["midstream_failures"] == 6 + 2, v
    # the surviving replica kept the service available throughout
    assert v["availability_pct"] is not None
    assert v["availability_pct"] >= 99.0, v
    assert v["token_parity"] is True, v
    # per-WORKER hbm ledgers merged off the stats replies (each process
    # owns its weights)
    assert any(k.startswith("r") and v["hbm"][k]["kv_slot_bytes"] > 0
               for k in v.get("hbm") or {}), v.get("hbm")
    json.dumps(v)  # the row round-trips as machine-readable JSON


def test_chaos_row_emits_valid_json():
    """BENCH_CHAOS=1 adds the fault-injection resilience row
    (bench._chaos_row): the Poisson trace replayed through the supervised
    scheduler with injected mid-trace crashes, reporting availability %,
    recovered vs failed request counts, and recovery p50 latency — all as
    one machine-readable JSON variant (matching the structured-error
    contract every other bench failure path follows)."""
    r = _run_bench({
        "BENCH_CHAOS": "1",
        "BENCH_CHAOS_REQUESTS": "4",
        "BENCH_CHAOS_BATCH": "2",
        "BENCH_CHAOS_CRASHES": "1",
        "BENCH_CLUSTER_REPEATS": "1",
        "BENCH_CLUSTER_TIMEOUT": "1.5",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    chaos = [v for v in row.get("variants", [])
             if "chaos" in v["metric"]]
    assert len(chaos) == 1, row
    c = chaos[0]
    # the cluster control-plane row rides the same BENCH_CHAOS flag:
    # two-process worker-loss detection, bounded by --worker-timeout
    cluster = [v for v in row.get("variants", [])
               if "cluster_detect" in v["metric"]]
    assert len(cluster) == 1, row
    cl = cluster[0]
    assert cl["unit"] == "ms" and cl["value"] > 0
    assert cl["within_bound"] is True, cl
    assert cl["value"] / 1e3 < cl["worker_timeout_s"], cl
    assert cl["stall_reason"] == "timeout", cl
    # dlwire (ISSUE 12): the cluster row's wire block is POPULATED — a
    # clean run's measured ledger from both ends, nonzero per-peer
    # bytes, heartbeat RTT, and the exact frame-arithmetic
    # reconciliation (drift 0.0 by construction)
    wire = cl["wire"]
    root_peer = wire["root"]["peers"]["1"]
    assert root_peer["tx"]["PING"]["bytes"] > 0, wire
    assert root_peer["rx"]["PONG"]["frames"] >= 1, wire
    assert root_peer["rtt_ms"]["n"] >= 1, wire
    assert wire["worker"]["peers"]["0"]["rx"]["RUN"]["bytes"] > 0, wire
    rec = wire["reconcile"]
    assert rec["drift_frac"] == 0.0 and rec["drift"] is False, rec
    assert rec["measured"] == rec["modeled"] > 0, rec
    # and the step_timeline is no longer empty-by-construction: the
    # control plane's "step" is one heartbeat round trip
    tl = cl["step_timeline"]
    assert tl.get("dec0_pre0_c0", {}).get("n", 0) >= 1, tl
    json.dumps(cl)  # machine-readable round-trip
    assert c["unit"] == "%" and 0.0 <= c["value"] <= 100.0
    assert c["requests"] == 4 and c["crashes_injected"] >= 1
    assert c["recoveries"] >= 1
    assert c["requests_failed_frames"] >= 1  # structured frames delivered
    # every request resolved one way or the other — nothing hung
    assert (c["ok_first_attempt"] + c["recovered_by_retry"]
            + c["unrecovered"]) == 4
    assert c["recovery_p50_ms"] is None or c["recovery_p50_ms"] >= 0
    json.dumps(c)  # the row round-trips as machine-readable JSON


def test_fleet_row_emits_valid_json():
    """BENCH_FLEET=1 adds the fleet-brain chaos row (bench._fleet_row):
    two tenants drive a process-replica tier through a 10x Poisson load
    spike with one replica SIGKILLed mid-spike, under the
    FleetController. The ISSUE-18 acceptance bars ride the assertions:
    the high-priority victim tenant's spike-phase p99 TTFT stays at SLO
    while the budgeted hog floods, the controller VISIBLY scaled the
    replica set up under the spike, zero not-yet-streamed requests were
    lost to the SIGKILL, and the respawn landed within the bound. The
    absolute-latency bars are pinned on the COMMITTED BENCH_r10.json
    row, not on CI timing."""
    r = _run_bench({
        "BENCH_FLEET": "1",
        "BENCH_FLEET_REQUESTS": "8",
        "BENCH_FLEET_VICTIM": "4",
        "BENCH_FLEET_TOKENS": "4",
        "BENCH_FLEET_STEP_MS": "30",
        "BENCH_FLEET_IAT": "0.4",
    }, timeout=560.0)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [line for line in r.stdout.strip().splitlines()
             if line.startswith("{")]
    row = json.loads(lines[-1])
    assert "error" not in row, row
    rows = [v for v in row.get("variants", []) if "fleet" in v["metric"]]
    assert len(rows) == 1, row
    v = rows[0]
    assert v["unit"] == "ms" and v["mode"] == "process"
    # the fairness bar: the victim's spike p99 TTFT held the SLO while
    # the hog flooded at 10x — WFQ + budget demotion did the isolation
    assert v["victim_within_slo"] is True, v
    assert v["value"] is not None and v["value"] > 0
    assert v["victim_base_p99_ttft_ms"] > 0, v
    # the autoscaling bar: the controller grew the set under the spike
    assert v["scale_ups"] >= 1, v
    assert v["actual_replicas_end"] >= 3, v
    # the chaos bar: SIGKILL mid-spike lost nothing unstreamed, and the
    # supervised respawn landed within the configured bound
    assert v["unstreamed_failures"] == 0, v
    assert v["within_bound"] is True, v
    assert v["completed"] >= 4, v
    # both tenants completed work — demotion, never starvation
    t = v["tenants"]
    assert t["victim"]["completed"] >= 4, t
    assert t["hog"]["completed"] >= 1, t
    json.dumps(v)  # the row round-trips as machine-readable JSON

    # the COMMITTED row carries the bars CI cannot time-assert: victim
    # p99 at SLO through the spike+kill, visible scale-up, zero
    # unstreamed losses
    art = os.path.join(REPO, "BENCH_r10.json")
    committed = json.load(open(art))
    cv = [x for x in committed["variants"] if "fleet" in x["metric"]][0]
    assert cv["victim_within_slo"] is True
    assert cv["value"] <= cv["slo_ms"]
    assert cv["scale_ups"] >= 1
    assert cv["unstreamed_failures"] == 0
    assert cv["within_bound"] is True
    assert cv["tenants"]["victim"]["completed"] > 0
    assert cv["tenants"]["hog"]["completed"] > 0


@pytest.mark.slow  # full dryrun compile in a subprocess (~100 s)
def test_dryrun_pins_cpu_before_any_jax_call():
    # dryrun_multichip must succeed with NO ambient cpu pin: it is a CPU
    # check on virtual devices, and its own config pin must land before
    # any backend initializes (it refuses a process that already has one)
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    code = ("import __graft_entry__ as g; g.dryrun_multichip(2); "
            "print('DRYRUN_OK')")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600.0, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DRYRUN_OK" in r.stdout


def test_sigterm_flushes_partial_json():
    """A driver-side `timeout` delivers SIGTERM mid-run; bench must flush
    the accumulated JSON line (partial rows kept) instead of dying
    silently — and exit non-zero: a cut run is not a complete one."""
    import signal
    import time

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_MODEL": "tiny", "BENCH_TOKENS": "200",
        "BENCH_REPEATS": "200", "BENCH_VARIANTS": "0",
    })
    p = subprocess.Popen([sys.executable, os.path.join(REPO, "bench.py")],
                         stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                         text=True, env=env, cwd=REPO)
    try:
        time.sleep(18)  # past compile, mid-measurement (typical machines)
        p.send_signal(signal.SIGTERM)
        out, _ = p.communicate(timeout=120)
    finally:
        if p.poll() is None:  # never leak a decode-looping child
            p.kill()
            p.communicate(timeout=30)
    if p.returncode == -signal.SIGTERM:
        # the signal landed during module imports, before main() could
        # install the handler — an environment too slow for this probe,
        # not a product failure
        pytest.skip("SIGTERM landed before bench.py main() started")
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    assert lines, out
    row = json.loads(lines[-1])
    # either the handler fired mid-run (error annotated, NON-ZERO exit) or
    # the run beat the signal (fast machine, exit 0) — both must yield one
    # parseable line
    terminated = "terminated" in row.get("error", "")
    assert terminated or row.get("value")
    assert (p.returncode != 0) == terminated, (p.returncode, row)
