"""The whole benchmark command at tiny size on the CPU for a JAMBA
configuration, through the new shape, reference and the cell's own traffic
file: the check child drives the served step programs of a file with
selective-scan and one-KV-head attention layers against reference/jamba.py
(chunks with a padded tail, then decode from the carried state and the
rows), the server runs the slot cache at MORE slots than 8 with a state a
slot and NO prefix arena, and /stats carries both gauges; the shape's
arithmetic at the published sizes is the issue's; and what this PR appended
to the manifest is there, last in its lists."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
import traffic  # noqa: E402
import workmodel  # noqa: E402

CELL = "jamba2-3b.long-doc-16"
TINY = {
    "name": "tiny-jamba-test", "arch": "JAMBA", "model_type": "jamba",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "attn_layer_period": 4, "attn_layer_offset": 2,
    "expert_layer_period": 2, "expert_layer_offset": 1, "num_experts": 1,
    "num_experts_per_tok": 1, "num_attention_heads": 4,
    "num_key_value_heads": 1, "vocab_size": 288, "hidden_act": "silu",
    "rms_norm_eps": 1e-6, "mamba_expand": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_dt_rank": 160, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "sliding_window": None,
    "tie_word_embeddings": True, "max_position_embeddings": 160,
    "shape": "shapes/jamba.py", "reference": "reference/jamba.py",
    "weights_seed": 29, "chips": 1,
    "server": {"serve_batch": 10, "serve_chunk": 16, "max_seq_len": 160,
               "prefix_blocks": 0, "prefix_block_len": 8},
    "server_flags": ["--serve-batch", "10", "--serve-chunk", "16",
                     "--max-seq-len", "160"],
    "executables": {"decode": "slot_decode_step",
                    "prefill": "slot_prefill_chunk_16"},
    "compile_keys": ["slot_decode", "slot_prefill:16"],
    "kernels": ["q40_matmul", "flash_attention", "kv_cache_write"],
    # float32 engine (engine_flags below): only summation order differs
    "logit_tolerance": 0.001,
    # 2 chunks of 16 and a tail of 13, then 3 decode steps; judged as the
    # real configuration is
    "check": {"prompt_tokens": 45, "decode_steps": 3, "judge": "median",
              "worst_tolerance": 0.002}}


@pytest.fixture(scope="module", autouse=True)
def _free_compiled_programs():
    """As tests/test_kimi_linear_bench.py's: compiled programs outlive
    their tests here (tests/conftest.py), so the module gives its own
    back."""
    yield
    import jax

    jax.clear_caches()


def test_the_whole_command_at_tiny_size_on_cpu(monkeypatch, tmp_path):
    # a cache directory of its own (tests/test_olmo_hybrid_bench.py says why)
    monkeypatch.setattr(run, "CACHE", str(tmp_path / "cache"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = [dict(m, workloads=None) for m in manifest["per_layer"]]
    # the cell's own traffic file, cut to what three seconds hold
    mix = dict(traffic.load_json("traffic", "long-doc-16.json"), clients=4,
               pool=12,
               prompt_tokens={"dist": "uniform", "min": 20, "max": 70},
               output_tokens={"dist": "uniform", "min": 2, "max": 5})
    assert mix["loop"] == "closed" and mix["temperature"] == 0.8
    plan = run.Plan(
        workload={"name": "tiny-jamba-test.closed", "chips": 1},
        config=dict(TINY), mix=mix,
        cell=traffic.load_json("cells", CELL + ".json") | {
            "ramp_s": 1.5, "trace_after_s": 0.5, "trace_ms": 500,
            "drain_s": 60},
        end_to_end=manifest["end_to_end"], per_layer=per_layer,
        seed=3000000019, seconds=3.0, trace=True,
        engine_flags=["--compute-dtype", "f32", "--cache-dtype", "f32",
                      "--buffer-float-type", "f32"],
        chip_env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},  # one device
        want_platform="cpu")
    seen = {}
    layer_metrics = run.layer_metrics

    def spy(plan, ctx):
        seen.update(ctx)
        return layer_metrics(plan, ctx)

    monkeypatch.setattr(run, "layer_metrics", spy)
    out = run.run(plan)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["logits_median_rel_l2"]["value"] <= 0.001
    assert out["compared"]["logits_worst_rel_l2"]["limit"] == 0.002
    ends = seen["stats"]["trace_end"]["capture"]
    assert {"prefill_rows", "prefill_tokens", "prefill_steps", "decode_rows",
            "decode_steps", "attn_pairs_decode"} <= set(ends["stop"])
    end = seen["stats"]["window_end"]
    assert end["prefill_rows"] >= end["prefill_steps"] > 0
    # BOTH gauges: 2 attention layers x ONE KV head x 16 x (K, V), 6 scan
    # layers x (16 x 128 state + a 3 x 128 tail), float32
    assert end["cache_bytes_per_token"] == 2 * 2 * 16 * 4
    assert end["state_bytes_per_slot"] == 6 * (16 * 128 * 4 + 3 * 128 * 4)
    assert "prefix_cache" not in end
    assert end["attn_pairs_decode"] > 0 and end["attn_pairs_prefill"] > 0
    assert "expert_reads_prefill" not in end or not end["expert_reads_prefill"]
    # no device plane on a CPU: the trace readers leave their metrics out
    assert "selscan_decode_roofline" not in out["metrics"]
    assert "selscan_prefill_roofline" not in out["metrics"]
    assert "prefill_tokens_per_chunk" in out["metrics"]


@pytest.fixture(scope="module")
def real():
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def test_the_configuration_holds_every_number_of_the_catalogs_row(real):
    """Every key of the catalog row's `config` under the same name with the
    same value but the ONE in `reduced`: the positions a slot may reach, cut
    to what the cell's traffic reaches (review of PR 52: a reserved pool the
    traffic never fills is no deployment's memory)."""
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("no catalog on this machine")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    assert real["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if real.get(k) != v} == {
        "max_position_embeddings"} == set(real["reduced"]) == set(
        real["reduced_why"])
    assert row["config"]["max_position_embeddings"] == 262144
    assert real["max_position_embeddings"] == 8192
    assert real["server"]["max_seq_len"] == 8192
    longest = traffic.load_json("traffic", "long-doc-16.json")
    assert (longest["prompt_tokens"]["max"] + longest["output_tokens"]["max"]
            < 8192)
    assert real["server"]["serve_batch"] == real["server"]["serve_chunk"] == 16
    assert real["server_flags"] == ["--serve-batch", "16", "--serve-chunk",
                                    "16", "--max-seq-len", "8192"]
    assert real["kernels"] == ["q40_matmul", "flash_attention",
                               "kv_cache_write"]
    assert set(real["weights_recipe"]) <= {"zero_mean", "scales", "gains",
                                           "embedding_std", "zero_rows"}
    assert {"mamba", "layers_block_type", "attention", "tie_word_embeddings",
            "weights"} <= set(real["assumed"])


def test_spec_sizing_and_work_at_the_published_sizes(real):
    """The shape's spec is the program's (28 layers, two whole periods of
    14 with layers 7 and 21 attending), its sizing is the issue's
    arithmetic, and the two kinds of work are hand-worked numbers."""
    shape = workmodel.for_config(real)
    spec = shape.spec(real)
    spec.validate()
    kinds = [int(k) for k in spec.layer_kinds]
    assert [l for l, k in enumerate(kinds) if k == 0] == [7, 21]
    assert set(kinds) == {0, 3} and spec.rope_theta == 0
    assert (spec.n_state_layers, spec.n_cache_layers) == (26, 2)
    assert (spec.n_heads, spec.n_kv_heads, spec.head_size) == (20, 1, 128)
    assert spec.state_bytes_per_slot(2) == 9_318_400
    assert spec.cache_values_per_token * 2 == 1_024
    assert (spec.ssm_inner, spec.ssm_d_state, spec.ssm_dt_rank) == (
        5120, 16, 160)
    assert spec.ssm_selective and spec.ssm_conv_dim == 5120
    assert not spec.is_moe and spec.vocab_size == 65536
    size = shape.sizing(real)
    assert size["cache_per_token"] == 1_024
    assert size["state_per_slot"] == 9_318_400
    assert size["slots"] == 16 * (8192 * 1_024 + 9_318_400)
    assert size["arena"] == 0
    assert 2.0e9 < size["weights"] < 2.15e9           # the issue's 2.05-2.11
    s = shape.shapes(real)
    assert s["mamba_mixer"] == 3 * 5120 * 2560 == 39_321_600
    assert s["thin"] == 192 * 5120 + 5120 * 160 == 1_802_240
    assert s["attention_mixer"] == 2 * 2560 * 2560 + 2 * 128 * 2560
    assert s["mlp"] == 3 * 8192 * 2560 == 62_914_560
    # matmul_work: the Q40 kernels' matmuls ALONE (x_proj and dt_proj are
    # dense leaves and left out, so neither matmul share can pass 100 %)
    q40 = 26 * 39_321_600 + 2 * 13_762_560 + 28 * 62_914_560
    m = shape.matmul_work(real, 256, 16)
    assert m["bytes"] == (q40 + 65536 * 2560) * 18 / 32
    assert m["flops"] == 2 * 256 * q40 + 2 * 16 * 65536 * 2560
    # state_work: 7 x 5120 x 16 FLOPs a token a layer; a live row's
    # 327,680 B state read and written once a program a layer
    w = shape.state_work(real, "decode", rows=16, tokens=16)
    assert w["flops"] == 16 * 26 * 7 * 5120 * 16
    assert w["bytes"] == 26 * (16 * 2 * 327_680
                               + 4 * 16 * (3 * 5120 + 32))
    w = shape.state_work(real, "prefill", rows=1, tokens=16)
    assert w["bytes"] == 26 * (2 * 327_680 + 4 * 16 * (3 * 5120 + 32))
    assert not hasattr(shape, "moe")


def test_the_manifest_ends_with_this_cell_and_its_two_metrics(real):
    """What this PR appended: one configuration with only the positions a
    slot may reach reduced, one
    one-chip cell under a traffic file of its own and two per-layer metrics
    over the reader the benchmark has, each LAST in its list; the cell's
    file is olmo's, the traffic is `long-doc`'s lengths at 16 callers."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert len(m["workloads"]) == 9 and len(m["configs"]) == 7
    assert m["configs"][-1] == {
        "name": "jamba2-3b", "source": real["source"],
        "file": "benchmark/configs/jamba2-3b.json",
        "reduced": ["max_position_embeddings"],
        "why": m["configs"][-1]["why"]}
    assert m["workloads"][-1] == {
        "name": CELL, "config": "jamba2-3b", "traffic": "long-doc-16",
        "chips": 1, "why": m["workloads"][-1]["why"]}
    assert all(len(x["why"]) <= 200 for x in (m["configs"][-1],
                                              m["workloads"][-1]))
    # (what PRs 53 and 54 appended after them, a counter metric each, is
    # tests/test_sample_summary.py's and tests/test_manifest_tail.py's to
    # hold)
    assert [x["name"] for x in m["per_layer"][-2:]] == [
        "sample_summary_share", "decode_attention_grid_steps"]
    per_layer = m["per_layer"][:-2]
    assert [x["name"] for x in per_layer[-2:]] == [
        "selscan_decode_roofline", "selscan_prefill_roofline"]
    for x, moves, kernel in zip(
            per_layer[-2:], ("itl_p50_ms", "ttft_p50_ms"),
            ("selective_scan_decode", "selective_scan_chunk")):
        assert x["workloads"] == [CELL]
        assert x["moves"] == moves and x["source"] == "device_trace"
        assert x["layer"] == "kernels (ops/pallas_selective_scan.py)"
        spec = traffic.load_json("layer_metrics", x["name"] + ".json")
        assert spec["reader"] == "trace_state_roofline"
        assert spec["args"]["kernels"] == [kernel]
        assert {k: spec[k] for k in x if k != "workloads"} == {
            k: v for k, v in x.items() if k != "workloads"}
    # no accepted entry lists the new cell (a model_config PR edits none)
    assert all(CELL not in (x.get("workloads") or ())
               for x in per_layer[:-2] + m["end_to_end"])
    assert traffic.load_json("cells", CELL + ".json") == traffic.load_json(
        "cells", "olmo-hybrid-7b.long-doc.json")
    mix = traffic.load_json("traffic", "long-doc-16.json")
    base = traffic.load_json("traffic", "long-doc.json")
    mix.pop("what"), base.pop("what")
    assert mix == {**base, "clients": 16, "pool": 96}
    assert mix == {"loop": "closed", "clients": 16, "pool": 96,
                   "prompt_tokens": {"dist": "uniform", "min": 2048,
                                     "max": 6144},
                   "output_tokens": {"dist": "uniform", "min": 32,
                                     "max": 128}, "temperature": 0.8}


def test_the_plan_of_the_new_cell_loads_from_its_files():
    """run.load_plan finds the configuration, the traffic, the cell and the
    metrics of the new cell by name, as the driver's command will."""
    plan = run.load_plan(CELL, seed=2520000011, seconds=51.0, trace=True)
    assert plan.config["arch"] == "JAMBA" and plan.mix["clients"] == 16
    names = {m["name"] for m in plan.per_layer}
    assert {"selscan_decode_roofline", "selscan_prefill_roofline",
            "decode_matmul_roofline", "prefill_step_mfu",
            "step_unscoped_share"} <= names
    assert "kda_decode_roofline" not in names
    assert plan.check_lengths == (plan.config["check"]["prompt_tokens"],
                                  plan.config["check"]["decode_steps"])
