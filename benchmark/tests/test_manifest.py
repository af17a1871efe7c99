"""BENCHMARK.json against the benchmark's contract and against the files the
harness finds by name."""

import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    M = json.load(f)


def one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_and_units():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"] and 1 <= M["run_seconds"] <= 51
    assert len(M["command"]) <= 32 and all(one_line(w) for w in M["command"])
    names = []
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
        names.append(c["name"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and one_line(m["layer"])
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    for group in (M["configs"], M["workloads"],
                  M["end_to_end"] + M["per_layer"]):
        ns = [x["name"] for x in group]
        assert len(ns) == len(set(ns))
    assert len(json.dumps(M)) < 64 * 1024


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in M["configs"]}
    used = set()
    pairs = set()
    for w in M["workloads"]:
        c = configs[w["config"]]
        used.add(c["name"])
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["chips"] == w["chips"]
        assert os.path.exists(os.path.join(BENCH, cfg["reference"]))
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "cells",
                                           w["name"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert used == set(configs)              # every configuration has a cell
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(len(M["workloads"]) // 4, 1)


def cells_of(metric):
    return set(metric.get("workloads") or [w["name"] for w in M["workloads"]])


def test_per_layer_metrics_have_readers_and_move_what_their_cells_report():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    layers = set()
    for m in M["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert cells_of(m) <= cells_of(e2e[m["moves"]])
        layers.add(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)
    for w in M["workloads"]:                 # what every cell must report
        mine = [m for m in M["end_to_end"] if w["name"] in cells_of(m)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(w["name"] in cells_of(m) for m in M["per_layer"])
