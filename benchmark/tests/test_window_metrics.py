"""The per-layer metrics that read the program's window counters and spans
(PR 25): each metric file through its reader on a hand-made `ctx`, the gap
share on hand-made gaps, a server that lacks the counters, and the whole
command at tiny size with --trace 1."""

import importlib
import json
import os

import pytest

import run
from test_rehearsal import plan

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

A = {"at": 100.0, "steps": 10, "tokens_out": 50, "admitted": 2,
     "queue_wait_ms_sum": 3.0, "prefill_steps": 4, "prefill_tokens": 100,
     "decode_steps": 8, "decode_rows": 20, "busy_ms": 500.0,
     "wait_ms": 450.0, "host_ms": 50.0,
     "frontdoor": {"requests": 2, "pre_submit_ms_sum": 8.0}}
B = {"at": 110.0, "steps": 110, "tokens_out": 450, "admitted": 12,
     "queue_wait_ms_sum": 28.0, "prefill_steps": 29, "prefill_tokens": 1525,
     "decode_steps": 98, "decode_rows": 410, "busy_ms": 5200.0,
     "wait_ms": 4780.0, "host_ms": 420.0,
     "frontdoor": {"requests": 12, "pre_submit_ms_sum": 48.0}}

WANT = {"frontdoor_pre_submit_ms": 4.0,      # 40 ms over 10 requests
        "queue_wait_ms": 2.5,                # 25 ms over 10 admissions
        "prefill_tokens_per_chunk": 57.0,    # 1425 tokens over 25 chunks
        "prefill_step_share": 25.0,          # 25 of 100 iterations
        "step_busy_ms": 47.0,                # 4700 ms over 100 iterations
        "host_ms_per_step": 3.7,             # 370 ms over 100 iterations
        "decode_rows_per_step": 390 / 90}    # 390 rows over 90 decode steps


def through_reader(name: str, ctx: dict):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_metric_is_the_ratio_of_window_differences(name):
    ctx = {"stats": {"window_start": A, "window_end": B}, "trace": {}}
    assert through_reader(name, ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_metric_finds_nothing_on_a_server_without_the_counter(name):
    """The parent commit's /stats has `steps` and `tokens_out` only: the
    reader returns None (the metric is left out of the line), it does not
    raise — and neither without snapshots, nor over an empty window."""
    old = {k: v for k, v in A.items() if k in ("at", "steps", "tokens_out")}
    old_b = {k: v for k, v in B.items() if k in ("at", "steps", "tokens_out")}
    ctx = {"stats": {"window_start": old, "window_end": old_b}, "trace": {}}
    assert through_reader(name, ctx) is None
    assert through_reader(name, {"stats": {}, "trace": {}}) is None
    same = {"stats": {"window_start": B, "window_end": B}, "trace": {}}
    assert through_reader(name, same) is None


def test_only_the_missing_counter_is_forgiven():
    """A counter that is there and is no number is a fault of the program,
    not an older program: the reader raises, it does not hide the metric."""
    bad = dict(B, host_ms=None)
    ctx = {"stats": {"window_start": A, "window_end": bad}, "trace": {}}
    with pytest.raises(TypeError):
        through_reader("host_ms_per_step", ctx)


def test_gap_share_counts_span_gaps_but_not_the_idle_wait():
    gaps = [["sched.sample_emit", 0.20], ["sched.idle_wait", 0.30],
            ["sched.dispatch.decode", 0.05], ["api.pre_submit", 0.01],
            ["np.asarray", 0.10],       # jax's own, nested in sched.wait
            ["gaps_under_1ms", 0.02], ["unattributed", 0.04],
            ["PjitFunction", 0.03]]
    ctx = {"trace": {"window_s": 4.0, "busy_s": 3.25, "idle_gaps": gaps}}
    got = through_reader("device_idle_host_work", ctx)
    assert got == pytest.approx(100 * (0.20 + 0.05 + 0.01 + 0.10) / 4.0)
    # only the idle wait: the host caused none of the idle time
    ctx["trace"]["idle_gaps"] = [["sched.idle_wait", 0.5]]
    assert through_reader("device_idle_host_work", ctx) == 0.0


def test_gap_share_finds_nothing_without_spans_or_without_a_device_plane():
    parent = {"trace": {"window_s": 4.0, "busy_s": 3.6, "idle_gaps": [
        ["_time_sleep", 0.285], ["slot_publish_block", 0.054]]}}
    assert through_reader("device_idle_host_work", parent) is None
    assert through_reader("device_idle_host_work", {"trace": {}}) is None


def test_traced_rehearsal_reports_the_counter_metrics():
    out = run.run(plan("closed", trace=True))
    assert out["correct"]
    m = out["metrics"]
    assert set(WANT) <= set(m), sorted(m)
    assert {"rows_per_step", "step_ms", "frontdoor_ms"} <= set(m)
    assert "device_idle_host_work" not in m      # no device plane on a CPU
    assert m["step_busy_ms"]["value"] >= m["host_ms_per_step"]["value"] > 0
    assert m["step_busy_ms"]["value"] <= m["step_ms"]["value"]
    assert 0 < m["prefill_step_share"]["value"] <= 100
    assert 1 <= m["prefill_tokens_per_chunk"]["value"] <= 4 * 8
    assert m["frontdoor_pre_submit_ms"]["value"] > 0
    assert m["queue_wait_ms"]["value"] >= 0
    assert 1 <= m["decode_rows_per_step"]["value"] <= 4
