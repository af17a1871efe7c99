"""The benchmark's own unit tests (benchmark/tests/), collected by tier-1:
the harness decides every PR, and `pytest tests/` does not look there.

Each pure module is loaded by file path and its tests re-exported as
``<module>__<test>``, parametrisation intact; nothing under benchmark/ is
edited, and a test added to one of these modules is collected as it is.
Left out are the five tests that boot a server child (test_rehearsal.py's
four and the traced rehearsal of test_window_metrics.py): the child
inherits tests/conftest.py's eight host devices where the cell is for one,
and five server boots do not belong in a six-worker run; they run under
`pytest benchmark/tests`, and tests/test_sarvam_mla_bench.py runs the whole
command at tiny size here."""

import importlib.util
import os
import sys

BTESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")
# by plain name: test_window_metrics imports test_rehearsal's plan,
# test_workmodel imports shape_tiny
sys.path.insert(0, BTESTS)

PURE = ("test_traffic", "test_workmodel", "test_window_metrics",
        "test_tracereduce", "test_manifest", "test_metrics",
        "test_reference", "test_olmo_hybrid", "test_granite_hybrid",
        "test_capture_report_metrics")
BOOTS_A_SERVER = {"test_traced_rehearsal_reports_the_counter_metrics"}
# pins the manifest at SIX cells with olmo's configuration last: a PR that
# adds a cell cannot satisfy it and a model_config PR may edit no file under
# benchmark/ (PR 39). Only that count is lost: test_granite_hybrid.py carries
# every other assertion of it (the cells' order with the seventh, olmo's
# published numbers, the delta_rule_* entries, the equal cell files)
# PR 41 appends four `per_layer` entries, which a PR that adds to the
# benchmark may put nowhere else; test_granite_hybrid.py's two tests of the
# LAST five entries run unedited on the manifest without the four, from
# test_capture_report_metrics.py, so only "nothing comes after" is lost
# PR 48 appends an eighth cell, a sixth configuration and two `per_layer`
# entries (`kda_*_roofline`): test_capture_report_metrics.py's pin of the
# LAST four entries and its two runs of granite's tests on "the manifest
# without the four" cannot hold any more. tests/test_manifest_tail.py runs
# the same three, every assertion kept, on the manifest without what PR 48
# appended; only "nothing comes after the four" is lost (PERF.md section 7
# names the edit benchmark/tests needs from a later `benchmark` PR)
SUPERSEDED = {"test_the_manifest_has_six_cells_and_the_new_entries_come_last",
              "test_the_manifest_has_seven_cells_and_the_new_entries_come_last",
              "test_olmos_entries_keep_their_places_and_their_keys",
              "test_the_four_entries_come_last_with_their_files",
              "test_what_came_before_the_four_is_what_granites_tests_hold"}


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests." + name, os.path.join(BTESTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


_load("conftest")  # puts benchmark/ and the repository on sys.path
for _module in PURE:
    for _name, _test in vars(_load(_module)).items():
        if (_name.startswith("test_") and callable(_test)
                and _name not in BOOTS_A_SERVER | SUPERSEDED):
            globals()[f"{_module}__{_name[5:]}"] = _test
