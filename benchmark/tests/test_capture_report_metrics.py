"""The four per-layer metrics that read the server's own report of its
capture (`/stats` `capture.report`; readers/capture_report.py): the
manifest's entries and files, and the reader on a hand-made report against
hand-worked numbers.

The four entries stand at the END of `per_layer`, where a PR that adds to
the benchmark has to put them. test_granite_hybrid.py holds the LAST five
entries to granite's and olmo's names in two tests, which a PR that appends
an entry cannot satisfy and may not edit: both run here, unedited, on the
manifest without the four, so every assertion of theirs is kept and only
"nothing comes after `decode_experts_read_per_layer`" is lost."""

import importlib.util
import json
import os

import pytest

from readers import capture_report

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
ENGINE = "engine, model step (runtime/engine.py)"
SCHEDULER = "scheduler (runtime/scheduler.py)"
NEW = {"decode_outside_kernels_ms": ("ms", "device_trace", ENGINE,
                                     "itl_p50_ms"),
       "prefill_outside_kernels_ms": ("ms", "device_trace", ENGINE,
                                      "ttft_p50_ms"),
       "step_unscoped_share": ("%", "device_trace", ENGINE, "itl_p50_ms"),
       "device_idle_host_running": ("%", "program_span", SCHEDULER,
                                    "itl_p50_ms")}

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    M = json.load(f)
# by file path, under a name of its own: tests/ has a test_granite_hybrid.py
# too, and a run of both directories may import only one under that name
_spec = importlib.util.spec_from_file_location(
    "granite_manifest_tests", os.path.join(BENCH, "tests",
                                           "test_granite_hybrid.py"))
granite = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(granite)

REPORT = {
    "report_ms": 21500.0, "window_s": 4.0, "busy_s": 3.0, "idle_s": 1.0,
    "idle": {"sched.wait": 0.5, "sched.sample_emit": 0.2,
             "sched.idle_wait": 0.15, "sched.dispatch.decode": 0.1,
             "no_span": 0.03, "sched.step": 0.02},
    "programs": {
        "slot_decode_step": {
            "executions": 100, "device_ms": 12.5, "busy_ms": 12.0,
            "scopes": {
                "ffn": {"kernel": {"q40_matmul": 5.0},
                        "xla": {"fusion": 0.5}},
                "ffn/act_q80": {"kernel": {}, "xla": {"abs_reduce_fusion":
                                                      0.25, "copy": 0.25}},
                "attn_core": {"kernel": {"flash_attention": 4.0},
                              "xla": {"copy": 1.0}},
                "unscoped": {"kernel": {}, "xla": {"copy-done": 1.0}}}},
        "slot_prefill_chunk_32": {
            "executions": 50, "device_ms": 40.0, "busy_ms": 36.0,
            "scopes": {
                "moe_routed": {"kernel": {"q40_expert_matmul": 20.0},
                               "xla": {"while": 6.0, "fusion": 4.0}},
                "unscoped": {"kernel": {}, "xla": {"slice-done": 6.0}}}},
        "sample_rows": {"executions": 9, "device_ms": 0.1, "busy_ms": 0.1,
                        "scopes": {"unscoped": {"kernel": {},
                                                "xla": {"sort": 0.1}}}}}}
CONFIG = {"executables": {"decode": "slot_decode_step",
                          "prefill": "slot_prefill_chunk_32"}}


def ctx(report):
    capture = {"start": {}, "stop": {}}
    if report is not None:
        capture["report"] = report
    return {"config": CONFIG, "stats": {"trace_end": {"capture": capture}}}


def test_the_four_entries_come_last_with_their_files():
    assert [m["name"] for m in M["per_layer"]][-4:] == list(NEW)
    for m in M["per_layer"][-4:]:
        unit, source, layer, moves = NEW[m["name"]]
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            unit, source, layer, moves)
        assert m["better"] == "lower" and "workloads" not in m  # every cell
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "capture_report"
        assert spec["args"]["what"] in ("outside_kernels", "unscoped_share",
                                        "idle_host_running")
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k]
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("held", [
    "test_the_manifest_has_seven_cells_and_the_new_entries_come_last",
    "test_olmos_entries_keep_their_places_and_their_keys"])
def test_what_came_before_the_four_is_what_granites_tests_hold(
        held, monkeypatch):
    monkeypatch.setattr(granite, "M",
                        {**M, "per_layer": M["per_layer"][:-4]})
    getattr(granite, held)()


@pytest.mark.parametrize("what,program,want", [
    # xla self ms an execution, all scopes: 0.5 + 0.5 + 1.0 + 1.0
    ("outside_kernels", "decode", 3.0),
    ("outside_kernels", "prefill", 16.0),
    # (1.0 x 100 + 6.0 x 50) of (12 x 100 + 36 x 50) ms, the two step
    # programs alone
    ("unscoped_share", None, 100.0 * 400 / 3000),
    # 0.2 + 0.1 + 0.02 of 4 s: not sched.wait, sched.idle_wait or no_span
    ("idle_host_running", None, 8.0),
])
def test_the_reader_on_a_hand_made_report(what, program, want):
    args = {"what": what, **({"program": program} if program else {})}
    got = capture_report.read(ctx(REPORT), **args)
    value = got["value"] if isinstance(got, dict) else got
    assert value == pytest.approx(want)
    if what == "outside_kernels":
        name = CONFIG["executables"][program]
        assert f"executions of {name}" in got["note"]
        assert ("moe_routed 20.000/10.000" in got["note"]) == (
            program == "prefill")
    if what == "idle_host_running":
        assert "sched.wait 0.500" in got["note"]
    if what == "unscoped_share":
        assert "made the report in 21.5 s" in got["note"]


@pytest.mark.parametrize("report", [
    None,                                    # a program older than the report
    {"error": "no device plane in the capture"},          # a CPU run
    {"programs": {}, "window_s": 0.0, "idle": {}},
])
@pytest.mark.parametrize("args", [
    {"what": "outside_kernels", "program": "decode"},
    {"what": "unscoped_share"}, {"what": "idle_host_running"}])
def test_nothing_to_read_leaves_the_metric_out(report, args):
    assert capture_report.read(ctx(report), **args) is None
    assert capture_report.read({"config": CONFIG, "stats": {}}, **args) is None


def test_a_program_the_capture_did_not_run_is_left_out():
    report = {**REPORT, "programs": {
        "slot_decode_step": REPORT["programs"]["slot_decode_step"]}}
    assert capture_report.read(ctx(report), what="outside_kernels",
                               program="prefill") is None
    assert capture_report.read(ctx(report), what="unscoped_share")[
        "value"] == pytest.approx(100.0 / 12.0)
