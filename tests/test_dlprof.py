"""tools/dlprof.py — the offline capacity analyzer: knee math, span
decomposition, timeline merging (worker prefixes), the end-to-end path
over a REAL scheduler's --trace-dir sink, and the CLI smoke the CI main
matrix runs (--selftest)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import dlprof  # noqa: E402


# -- knee math --------------------------------------------------------------


def test_knee_on_a_saturating_curve():
    # linear ms growth past 4 rows: marginal throughput collapses there
    curve = [(1, 5.0), (2, 5.2), (4, 5.9), (8, 14.0), (16, 30.0)]
    k = dlprof.knee_estimate(curve)
    assert k["knee_rows"] == 4
    assert k["method"] == "marginal_throughput"
    assert len(k["curve"]) == 5


def test_knee_without_saturation_recommends_measuring_higher():
    curve = [(1, 5.0), (2, 5.1), (4, 5.3)]  # still nearly flat
    k = dlprof.knee_estimate(curve)
    assert k["knee_rows"] == 4
    assert k["method"] == "no_saturation_observed"
    assert "larger batches" in k["note"]


def test_knee_single_point_and_empty():
    assert dlprof.knee_estimate([]) is None
    k = dlprof.knee_estimate([(2, 6.0)])
    assert k["knee_rows"] == 2 and k["method"] == "single_point"


def test_recommendation_caps_at_hbm_headroom():
    k = dlprof.knee_estimate([(1, 5.0), (2, 5.2), (4, 5.9), (8, 14.0)])
    assert k["knee_rows"] == 4
    rec = dlprof.serve_batch_recommendation(
        k, {"slots_addable": 0})          # no headroom past measured max
    assert rec["serve_batch"] == 4        # knee under the cap: unchanged
    rec = dlprof.serve_batch_recommendation(k, {"slots_addable": None})
    assert rec["serve_batch"] == 4 and rec["hbm_cap_rows"] is None


# -- timeline merging -------------------------------------------------------


def test_merge_strips_worker_prefixes_and_prefers_larger_n():
    events = [{"kind": "step", "dec": 2, "pre": 0, "chunk": 0, "ms": 7.0}]
    rows = [{"step_timeline": {
        "r0_dec2_pre0_c0": {"n": 50, "p50_ms": 6.5, "p99_ms": 7.0,
                            "mean_ms": 6.6},
        "dec4_pre1_c16": {"n": 3, "p50_ms": 9.0, "p99_ms": 9.5,
                          "mean_ms": 9.1},
        "not_a_key": {"n": 1}}}]
    tl = dlprof.merge_timelines(events, rows)
    assert (2, 0, 0) in tl and (4, 1, 16) in tl
    assert tl[(2, 0, 0)]["n"] == 50      # bench summary outweighs 1 event
    assert (0, 0, 0) not in tl
    assert dlprof.decode_curve(tl) == [(2, 6.5)]  # prefill row excluded


# -- span decomposition -----------------------------------------------------


def _span(tid=7, error=False):
    t = 100.0
    evs = [{"ts_wall": t, "kind": "enqueue", "tid": tid, "n_prompt": 9},
           {"ts_wall": t + 0.004, "kind": "route", "tid": tid,
            "replica": 0},
           {"ts_wall": t + 0.005, "kind": "admit", "tid": tid,
            "queue_ms": 5.0},
           {"ts_wall": t + 0.006, "kind": "seed", "tid": tid, "hit": 4},
           {"ts_wall": t + 0.030, "kind": "first_token", "tid": tid,
            "ttft_ms": 30.0}]
    if error:
        evs.append({"ts_wall": t + 0.050, "kind": "error", "tid": tid,
                    "code": "replica_lost", "n_out": 2})
    else:
        evs.append({"ts_wall": t + 0.090, "kind": "finish", "tid": tid,
                    "reason": "length", "n_out": 7})
    return evs


def test_critical_path_decomposes_phases():
    p = dlprof.critical_path(_span())
    assert p["status"] == "length" and p["n_out"] == 7
    assert p["queue_ms"] == 5.0 and p["seed_hit"] == 4
    assert p["ttft_ms"] == 30.0
    assert abs(p["prefill_ms"] - 25.0) < 0.5    # admit -> first token
    assert abs(p["decode_ms"] - 60.0) < 0.5
    assert abs(p["total_ms"] - 90.0) < 0.5
    assert p["itl_ms"] == pytest.approx(10.0, abs=0.5)
    assert p["dominant_phase"] == "decode"


def test_critical_path_error_span_and_unterminated():
    p = dlprof.critical_path(_span(error=True))
    assert p["status"] == "error:replica_lost" and p["n_out"] == 2
    assert dlprof.critical_path(_span()[:3]) is None  # no terminal


def test_goodput_splits_on_slo():
    paths = [dlprof.critical_path(_span(tid=t)) for t in (1, 2)]
    events = _span(1) + _span(2)
    g = dlprof.goodput(paths, events, slo_ttft_ms=500.0, slo_itl_ms=100.0)
    assert g["within_slo"] == 2 and g["slo_fraction"] == 1.0
    g = dlprof.goodput(paths, events, slo_ttft_ms=10.0, slo_itl_ms=100.0)
    assert g["within_slo"] == 0  # ttft 30 ms misses a 10 ms SLO


# -- the wire report (dlwire) -----------------------------------------------


def test_wire_report_merges_ledgers_and_reconciles():
    """wire_report: bench rows' wire blocks (both the {root,worker}
    bench-row shape and a raw WireStats summary) merge into per-peer
    totals; every reconcile entry is collected with the drift flag
    re-derived at the 25% bar."""
    row = {"wire": {
        "root": {"peers": {"1": {
            "tx": {"PING": {"frames": 4, "bytes": 96},
                   "RUN": {"frames": 1, "bytes": 120}},
            "rx": {"PONG": {"frames": 4, "bytes": 128}},
            "rtt_ms": {"n": 4, "p50_ms": 1.0, "p99_ms": 2.0,
                       "mean_ms": 1.2},
            "clock_offset_ms": 0.1}}},
        "worker": {"peers": {"0": {
            "rx": {"PING": {"frames": 4, "bytes": 96}}}}},
        "reconcile": {"measured": 120.0, "modeled": 120.0,
                      "unit": "bytes", "drift_frac": 0.0}}}
    raw = {"wire": {"peers": {"2": {
        "tx": {"RUN": {"frames": 1, "bytes": 50}}}}}}
    w = dlprof.wire_report([], [row, raw])
    assert w["peers"]["root:peer1"]["tx_bytes"] == 216
    assert w["peers"]["root:peer1"]["rtt_ms"]["p99_ms"] == 2.0
    assert w["peers"]["worker:peer0"]["rx_bytes"] == 96
    assert w["peers"]["peer2"]["tx_bytes"] == 50
    assert "sync" not in w  # went with --profile-sample
    assert len(w["reconcile"]) == 1 and not w["drift"]

    # a stale artifact whose producer never flagged: the report
    # re-derives drift at its own bar (0.3 >= 0.25 -> flagged)
    stale = {"wire": {"reconcile": {"measured": 130.0, "modeled": 100.0,
                                    "drift_frac": 0.3}}}
    w2 = dlprof.wire_report([], [stale])
    assert w2["drift"] and w2["reconcile"][0]["drift"] is True

    # no wire data anywhere: the section is honestly absent
    assert dlprof.wire_report([], [{"metric": "x"}]) is None
    r = dlprof.analyze([], [{"metric": "x"}], wire=True)
    assert r["wire"] is None and "Wire" not in dlprof.render_markdown(r)


def test_wire_markdown_renders_peer_table_and_flags():
    row = {"wire": {"peers": {"1": {
        "tx": {"RUN": {"frames": 2, "bytes": 250}},
        "rtt_ms": {"n": 5, "p50_ms": 0.9, "p99_ms": 1.8, "mean_ms": 1.1},
        "clock_offset_ms": 0.07}},
        "reconcile": {"measured": 140.0, "modeled": 100.0,
                      "unit": "bytes", "drift_frac": 0.4}}}
    report = dlprof.analyze([], [row], wire=True)
    md = dlprof.render_markdown(report)
    assert "## Wire (measured cluster plane)" in md
    assert "| peer1 | 250 |" in md
    assert "0.9/1.8" in md
    assert "DRIFTED" in md


# -- the scheduler's phases (step records of runtime/trace.py) ---------------


def test_host_phases_means_self_time_and_absence():
    """host_phases: mean ms per iteration for every span name in the
    step records' `phases`, `self` = ms less the phases, the host's part
    = all but sched.wait; None when no record carries phases."""
    evs = [{"kind": "step", "tid": 0, "ms": 50.0, "n": 1,
            "phases": {"sched.admit": 1.0, "sched.dispatch.decode": 2.0,
                       "sched.wait": 42.0, "sched.sample_emit": 4.0}},
           {"kind": "step", "tid": 0, "ms": 150.0, "n": 2,
            "phases": {"sched.admit": 3.0, "sched.dispatch.prefill": 5.0,
                       "sched.dispatch.decode": 2.0, "sched.wait": 130.0,
                       "sched.sample_emit": 6.0, "sched.publish": 2.0}},
           {"kind": "step", "tid": 0, "ms": 9.0}]     # no phases: skipped
    hp = dlprof.host_phases(evs)
    assert hp["steps"] == 2 and hp["step_mean_ms"] == 100.0
    assert hp["mean_ms"]["sched.wait"] == 86.0
    assert hp["mean_ms"]["sched.sample_emit"] == 5.0
    assert hp["mean_ms"]["sched.publish"] == 1.0
    assert hp["mean_ms"]["self"] == 1.5          # (1 + 2) / 2
    assert hp["host_mean_ms"] == 14.0            # 100 - 86
    assert dlprof.host_phases([{"kind": "step", "ms": 1.0}]) is None
    md = dlprof.render_markdown(dlprof.analyze(evs))
    assert "## Scheduler iteration by phase" in md
    assert "| sched.wait | 86.0 |" in md


# -- end to end over a REAL scheduler trace ---------------------------------


def test_analyze_real_trace_dir_end_to_end(tmp_path):
    """Drive the real scheduler with a --trace-dir sink, then run the
    analyzer over the JSONL it wrote: spans decompose, the step curve
    has decode compositions, the knee is non-null."""
    jnp = pytest.importorskip("jax.numpy")
    from distributed_llama_tpu.models import (ArchType, HiddenAct,
                                              ModelSpec)
    from distributed_llama_tpu.models.params import (load_params,
                                                     random_tensors)
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.runtime.scheduler import Scheduler
    from distributed_llama_tpu.runtime.trace import TRACER
    from distributed_llama_tpu.sampler import Sampler

    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
                     seq_len=64, hidden_act=HiddenAct.SILU)
    params = load_params(spec, random_tensors(spec, seed=3, scale=0.05),
                         mode="dense", dtype=jnp.float32)
    sink = str(tmp_path / "trace")
    TRACER.reset()
    TRACER.configure(capacity=4096, sink_dir=sink, decode_every=2)
    try:
        eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                     cache_dtype=jnp.float32)
        sched = Scheduler(eng, chunk=8)
        reqs = [sched.submit([1, 9, 23, 54, 7, 11, 40, 3, 15], 6,
                             Sampler(128, 0.0, 0.9, 1))
                for _ in range(2)]
        while not all(r.finished.is_set() for r in reqs):
            sched.step()
        for r in reqs:
            assert len(list(r.tokens(timeout=10.0))) == 6
        sched.close()
    finally:
        TRACER.reset()  # closes (flushes) the sink

    events = dlprof.load_trace_dir(sink)
    assert events, "sink wrote nothing"
    report = dlprof.analyze(events)
    assert report["requests"]["requests"] == 2
    assert report["requests"]["completed"] == 2
    assert report["requests"]["ttft_ms"]["p50"] > 0
    assert report["step_curve"]["decode_points"], report["step_curve"]
    assert report["step_curve"]["knee"] is not None
    assert report["step_curve"]["knee"]["knee_rows"] >= 1
    assert report["goodput"]["completed"] == 2
    assert report["tail"] and report["tail"][0]["dominant_phase"]
    json.dumps(report)
    md = dlprof.render_markdown(report)
    assert "# dlprof report" in md and "Knee:" in md


# -- the CLI ----------------------------------------------------------------


def test_cli_selftest_subprocess():
    """The exact invocation the CI `dlprof smoke` step runs."""
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "dlprof.py"),
                        "--selftest"],
                       capture_output=True, text=True, timeout=120,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_cli_writes_report_files(tmp_path):
    trace = tmp_path / "t"
    trace.mkdir()
    with open(trace / "trace-00000001.jsonl", "w") as f:
        for e in _span() + [{"ts_wall": 101.0, "kind": "step", "tid": 0,
                             "dec": 2, "pre": 0, "chunk": 0, "ms": 6.0}]:
            f.write(json.dumps(e) + "\n")
    out = str(tmp_path / "report")
    rc = dlprof.main(["--trace-dir", str(trace), "--out", out])
    assert rc == 0
    with open(out + ".json") as f:
        rep = json.load(f)
    assert rep["requests"]["requests"] == 1
    assert rep["step_curve"]["knee"]["knee_rows"] == 2
    assert os.path.exists(out + ".md")
