"""What `benchmark/tests` pins of the END of the manifest, held on the
manifest WITHOUT what PR 48 appended (an eighth cell, a sixth configuration,
`kda_decode_roofline` and `kda_prefill_roofline`), PR 49 after it (one
per-layer metric, `prefill_tiles_per_expert_read`) and PR 52 after that (a
ninth cell, a seventh configuration, `selscan_decode_roofline` and
`selscan_prefill_roofline`, held by tests/test_jamba_bench.py), PR 53 (one
per-layer metric, `sample_summary_share`, held by
tests/test_sample_summary.py) and PR 54 (one, `decode_attention_grid_steps`,
held here; PR 48's and PR 49's entries are found by NAME here, whatever
comes after them): a
`model_config` PR puts
its entries last and may edit no file under benchmark/, so
test_capture_report_metrics.py's pin of the last four `per_layer` entries
and its two runs of test_granite_hybrid.py's tests stand in
tests/test_benchmark_units.SUPERSEDED; they run here, every assertion
kept, and only "nothing comes after" is lost. What PR 48 appended is held
by tests/test_kimi_linear_bench.py."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests"), REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

# by PRs 48, 49, 52, 53 and 54
APPENDED = {"workloads": 2, "configs": 2, "per_layer": 7}

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    M = json.load(f)
BEFORE = {**M, **{k: M[k][:-n] for k, n in APPENDED.items()}}


def _at(entries, name):
    """Index of the entry of that name."""
    return [e["name"] for e in entries].index(name)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "manifest_tail_" + name, os.path.join(BENCH, "tests", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def capture():
    return _load("test_capture_report_metrics")


def test_what_pr_48_appended_is_one_cell_one_configuration_two_metrics():
    # each FIRST after what stood before it, and the two metrics together
    assert _at(M["workloads"], "kimi-linear-48b-a3b-ep4.long-doc") == len(
        BEFORE["workloads"])
    assert _at(M["configs"], "kimi-linear-48b-a3b-ep4") == len(
        BEFORE["configs"])
    at = _at(M["per_layer"], "kda_decode_roofline")
    assert at == len(BEFORE["per_layer"])
    assert [m["name"] for m in M["per_layer"][at:at + 2]] == [
        "kda_decode_roofline", "kda_prefill_roofline"]
    # no accepted entry lists the new cell (a model_config PR edits none)
    assert all("kimi-linear-48b-a3b-ep4.long-doc"
               not in (m.get("workloads") or ())
               for m in BEFORE["per_layer"] + M["end_to_end"])


def test_what_pr_49_appended_is_one_counter_metric_as_data():
    """`prefill_tiles_per_expert_read`, next after PR 48's two: the row tiles
    one read of an expert serves in a chunk program, over the reader the
    benchmark had (`stats_delta_opt`: nothing to read, and no error, from a
    program without the counter), in the five cells whose model routes."""
    import traffic

    last = M["per_layer"][_at(M["per_layer"],
                              "prefill_tiles_per_expert_read")]
    assert last is M["per_layer"][len(BEFORE["per_layer"]) + 2]
    moe = [w["name"] for w in M["workloads"] if w["config"].split("-")[0] in (
        "mixtral", "sarvam", "granite", "kimi")]
    assert last == {
        "name": "prefill_tiles_per_expert_read", "unit": "tiles",
        "better": "lower", "source": "program_counter",
        "layer": "kernels (ops/pallas_q40.py)", "moves": "ttft_p50_ms",
        "workloads": moe}
    assert len(moe) == 5
    spec = traffic.load_json("layer_metrics", last["name"] + ".json")
    assert spec["reader"] == "stats_delta_opt" and spec["args"] == {
        "num": "expert_tiles_prefill", "den": "expert_reads_prefill"}
    assert {k: spec[k] for k in last if k != "workloads"} == {
        k: v for k, v in last.items() if k != "workloads"}
    from readers import stats_delta_opt

    older = {"stats": {"window_start": {"expert_reads_prefill": 1},
                       "window_end": {"expert_reads_prefill": 9}}}
    assert stats_delta_opt.read(older, **spec["args"]) is None


def test_what_pr_54_appended_is_one_counter_metric_as_data():
    """`decode_attention_grid_steps`, the manifest's last entry: the grid
    steps `flash_attention` takes a decode program, over the reader the
    benchmark had (`stats_delta_opt`: nothing to read, and no error, from a
    program without the counter), in the seven cells whose programs hold
    the kernel (the two latent-cache cells run `mla_attention`)."""
    import traffic

    from distributed_llama_tpu.runtime.stats import WINDOW_COUNTERS

    last = M["per_layer"][-1]
    holds = [w["name"] for w in M["workloads"] if w["config"].split("-")[0]
             not in ("sarvam", "kimi")]
    assert last == {
        "name": "decode_attention_grid_steps", "unit": "steps",
        "better": "lower", "source": "program_counter",
        "layer": "kernels (ops/pallas_attention.py)", "moves": "itl_p50_ms",
        "workloads": holds}
    assert len(holds) == 7
    spec = traffic.load_json("layer_metrics", last["name"] + ".json")
    assert spec["reader"] == "stats_delta_opt" and spec["args"] == {
        "num": "attn_grid_steps_decode", "den": "decode_steps"}
    assert set(spec["args"].values()) <= set(WINDOW_COUNTERS)
    assert {k: spec[k] for k in last if k != "workloads"} == {
        k: v for k, v in last.items() if k != "workloads"}
    from readers import stats_delta_opt

    older = {"stats": {"window_start": {"decode_steps": 1},
                       "window_end": {"decode_steps": 9}}}
    assert stats_delta_opt.read(older, **spec["args"]) is None
    newer = {"stats": {
        "window_start": {"decode_steps": 1, "attn_grid_steps_decode": 2048},
        "window_end": {"decode_steps": 9, "attn_grid_steps_decode": 18432}}}
    assert stats_delta_opt.read(newer, **spec["args"]) == 2048


def test_the_four_entries_came_last_with_their_files(capture, monkeypatch):
    """test_capture_report_metrics.py's own test, unedited, on the manifest
    as it stood before PR 48."""
    monkeypatch.setattr(capture, "M", BEFORE)
    capture.test_the_four_entries_come_last_with_their_files()
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("held", [
    "test_the_manifest_has_seven_cells_and_the_new_entries_come_last",
    "test_olmos_entries_keep_their_places_and_their_keys"])
def test_what_came_before_the_four_is_what_granites_tests_hold(
        capture, held, monkeypatch):
    """Its run of granite's two tests, unedited, likewise."""
    monkeypatch.setattr(capture.granite, "M",
                        {**BEFORE, "per_layer": BEFORE["per_layer"][:-4]})
    getattr(capture.granite, held)()
