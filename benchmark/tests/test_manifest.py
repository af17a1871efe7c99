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


# What `reduced` may never name: a width. A hidden, intermediate, latent,
# state or projection size, a key that ends in `_dim` or `_rank`, a head
# size, an expansion factor, the experts per token. Depth, the experts or
# heads HELD here, the vocabulary's slice and the context are the chip's
# share of a deployment and may be listed.
WIDTH = re.compile(
    r"(_dim|_rank)$|hidden_size|intermediate_size|latent|state_size|d_state"
    r"|proj|head_size|head_dim|expand|expansion|experts_per_tok|^top_k$"
    r"|^d_model$|^d_ff$|^sliding_window$")


def check_config(entry, cfg):
    """A `configs` entry of the manifest and the file it names: what the
    contract asks of both, whatever the architecture."""
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert one_line(entry["source"]) and one_line(entry["why"])
    assert entry["file"].startswith("benchmark/")
    assert len(entry["reduced"]) <= 16
    assert all(NAME.match(k) for k in entry["reduced"])
    assert not [k for k in entry["reduced"] if WIDTH.search(k)]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for k in entry["reduced"]:               # each cut is there, with its why
        assert k in cfg and k in cfg["reduced_why"], k
    assert cfg["assumed"] and cfg["deployment"]
    assert cfg["chips"] in (1, 4)
    for k in ("reference", "shape"):         # found by name, inside benchmark/
        if k in cfg:
            assert re.match(r"^[A-Za-z0-9_.\-/]+\.py$", cfg[k]), cfg[k]
            assert ".." not in cfg[k] and not cfg[k].startswith("/")
    if "check" in cfg:
        lengths = {k: v for k, v in cfg["check"].items()
                   if k in ("prompt_tokens", "decode_steps")}
        assert set(cfg["check"]) - set(lengths) <= {"judge", "worst_tolerance"}
        assert all(isinstance(v, int) and v > 0 for v in lengths.values())
        if "max_position_embeddings" in cfg:
            assert sum(lengths.values()) <= cfg["max_position_embeddings"]
        if "judge" in cfg["check"]:   # the median row, and a limit for the worst
            assert cfg["check"]["judge"] == "median"
            assert cfg["check"]["worst_tolerance"] > cfg["logit_tolerance"] > 0


def test_keys_names_and_units():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"] and 1 <= M["run_seconds"] <= 51
    assert len(M["command"]) <= 32 and all(one_line(w) for w in M["command"])
    names = []
    for c in M["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            check_config(c, json.load(f))
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
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["chips"] == w["chips"]
        assert os.path.exists(os.path.join(BENCH, cfg["reference"]))
        assert "shape" not in cfg or os.path.exists(
            os.path.join(BENCH, cfg["shape"]))
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


def test_reduced_may_name_a_cut_of_scale_and_never_a_width():
    for k in ("num_hidden_layers", "max_position_embeddings", "vocab_size",
              "num_experts", "num_local_experts", "n_routed_experts",
              "layer_types", "num_attention_heads"):
        assert not WIDTH.search(k), k
    for k in ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "prefix_dense_intermediate_size", "head_dim", "v_head_dim",
              "qk_rope_head_dim", "kv_lora_rank", "q_lora_rank",
              "num_experts_per_tok", "ssm_state_size", "expand",
              "mamba_d_state", "sliding_window", "linear_key_head_dim"):
        assert WIDTH.search(k), k


def test_a_new_architectures_file_and_entry_pass_with_no_harness_file_touched():
    """command-a-plus-05-2026 cut to one chip (ISSUE 28): published keys
    the default shape does not know (`head_dim`, `sliding_window`,
    `layer_types`, `num_shared_experts`, experts held against experts routed
    over) in a file that names its own `shape`, `reference` and `check`. The
    file is a fixture: it is in no cell, and its shape module and reference
    are a `model_config` PR's to write."""
    fx = os.path.join(BENCH, "tests", "fixtures", "command-a-plus-05-2026-16l")
    with open(fx + ".entry.json") as f:
        entry = json.load(f)
    with open(os.path.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    check_config(entry, cfg)
    assert entry["name"] not in {c["name"] for c in M["configs"]}
    assert "vocab_size" in entry["reduced"]          # a sliced vocabulary
    assert cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]
    assert cfg["num_experts"] < cfg["published_num_experts"] == 128
    assert cfg["num_experts_per_tok"] == 8 and cfg["num_shared_experts"] == 4
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]                            # whole periods
    assert cfg["check"]["prompt_tokens"] > cfg["sliding_window"]
