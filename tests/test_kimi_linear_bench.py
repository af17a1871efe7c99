"""The whole benchmark command at tiny size on the CPU for a KIMI_LINEAR
configuration, through the new shape, reference and the cell's own traffic
file: the check child drives the served step programs of a file with KDA
and latent layers and a held share of experts against
reference/kimi_linear.py (chunks with a padded tail, then decode from the
carried state and the latent rows), the server runs the slot cache with a
state a slot, a latent leaf and NO prefix arena, and /stats carries both
gauges and the expert counters; and the shape's arithmetic at the published
sizes is the issue's."""

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

TINY = {
    "name": "tiny-kimi-test", "arch": "KIMI_LINEAR", "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 7, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 288, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "first_k_dense_replace": 1, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "q_lora_rank": None, "mla_use_nope": True,
    "linear_attn_config": {"full_attn_layers": [4, 7],
                           "kda_layers": [1, 2, 3, 5, 6], "head_dim": 32,
                           "num_heads": 2, "short_conv_kernel_size": 4},
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "num_experts": 4, "published_num_experts": 8,
    "expert_offset": 0, "num_experts_per_token": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "rope_theta": 10000,
    "max_position_embeddings": 128, "shape": "shapes/kimi_linear.py",
    "reference": "reference/kimi_linear.py", "weights_seed": 23,
    "chips": 1,
    "server": {"serve_batch": 4, "serve_chunk": 8, "max_seq_len": 128,
               "prefix_blocks": 0, "prefix_block_len": 8},
    "server_flags": ["--serve-batch", "4", "--serve-chunk", "8",
                     "--max-seq-len", "128"],
    "executables": {"decode": "slot_decode_step",
                    "prefill": "slot_prefill_chunk_8"},
    "compile_keys": ["slot_decode", "slot_prefill:8"],
    "kernels": ["q40_matmul", "q40_expert_matmul", "mla_attention",
                "kv_cache_write"],
    # float32 engine (engine_flags below): only summation order differs
    "logit_tolerance": 0.001,
    # 5 chunks + 5 of 8; judged as the real configuration is: the median
    # row against logit_tolerance, the worst against worst_tolerance
    "check": {"prompt_tokens": 45, "decode_steps": 3, "judge": "median",
              "worst_tolerance": 0.002}}


@pytest.fixture(scope="module", autouse=True)
def _free_compiled_programs():
    """tests/conftest.py turns the cyclic collector off for the whole run,
    so an engine's compiled programs outlive its test. This module mints
    some dozens of them a worker; three whole runs with them left alive
    each lost a worker to a segmentation fault inside XLA's CPU compiler
    or its cache read, late in the run and in a test of another file each
    time (the parent's tree lost none). Dropping jit's caches when the
    module is done gives the executables back."""
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
    mix = dict(traffic.load_json("traffic", "long-doc.json"), clients=3,
               pool=12,
               prompt_tokens={"dist": "uniform", "min": 20, "max": 70},
               output_tokens={"dist": "uniform", "min": 2, "max": 5})
    assert mix["loop"] == "closed" and mix["temperature"] == 0.8
    plan = run.Plan(
        workload={"name": "tiny-kimi-test.closed", "chips": 1},
        config=dict(TINY), mix=mix,
        cell={"ramp_s": 1.5, "trace_after_s": 0.5, "trace_ms": 500,
              "drain_s": 60, "schedule_seed": 3},
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
            "decode_steps", "attn_pairs_decode",
            "expert_pairs_prefill"} <= set(ends["stop"])
    end = seen["stats"]["window_end"]
    assert end["prefill_rows"] >= end["prefill_steps"] > 0
    # BOTH gauges non-zero, and the cache latent: 2 latent layers x 40
    # values, 5 KDA layers x (2 x 32 x 32 state + a 3 x 192 tail), float32
    assert end["cache_bytes_per_token"] == 2 * 40 * 4
    assert end["state_bytes_per_slot"] == 5 * (2 * 32 * 32 * 4 + 3 * 192 * 4)
    assert "prefix_cache" not in end
    assert end["attn_pairs_decode"] > 0 and end["attn_pairs_prefill"] > 0
    # no device plane on a CPU: the trace readers leave their metrics out
    assert "kda_decode_roofline" not in out["metrics"]
    assert "prefill_tokens_per_chunk" in out["metrics"]
    # a count, not a time: row tiles a read of an expert serves in a chunk;
    # the CPU's server runs no kernel, so no grouped call lays out a tile
    assert out["metrics"]["prefill_tiles_per_expert_read"]["value"] == 0
    assert end["expert_reads_prefill"] > end["expert_tiles_prefill"] == 0


@pytest.fixture(scope="module")
def real():
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b-ep4.json")) as f:
        return json.load(f)


def test_the_configuration_holds_every_number_of_the_catalogs_row(real):
    """Every key of the catalog row's `config` under the same name with the
    same value, but the three in `reduced`; `model_max_length` stays as
    published and `max_position_embeddings` is the key the harness reads."""
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("no catalog on this machine")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert real["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if real.get(k) != v}
    assert differ == {"num_experts", "vocab_size"}
    assert set(real["reduced"]) == differ | {"max_position_embeddings"}
    assert real["model_max_length"] == 1048576
    assert (real["published_num_experts"], real["num_experts"]) == (256, 64)
    assert real["vocab_size"] * 4 == row["config"]["vocab_size"]


def test_spec_sizing_and_work_at_the_published_sizes(real):
    """The shape's spec is the program's (27 layers, six periods of K K K M
    and the tail K K M), its sizing is the issue's arithmetic, and the
    three kinds of work are hand-worked numbers."""
    shape = workmodel.for_config(real)
    spec = shape.spec(real)
    spec.validate()
    kinds = [int(k) for k in spec.layer_kinds]
    assert kinds == ([2, 2, 2, 1] * 6 + [2, 2, 1]) and spec.rope_theta == 0
    assert (spec.n_state_layers, spec.n_cache_layers) == (20, 7)
    assert spec.state_bytes_per_slot(2) == 43_417_600
    assert spec.cache_values_per_token * 2 == 8_064
    assert (spec.router_width, spec.n_experts, spec.n_active_experts) == (
        256, 64, 8)
    assert spec.lin_decay_dim == 128 and spec.lin_beta_scale == 1
    size = shape.sizing(real)
    assert size["cache_per_token"] == 8_064
    assert size["state_per_slot"] == 43_417_600
    assert size["slots"] == 8 * (8192 * 8_064 + 43_417_600)
    assert size["arena"] == 0
    assert 7.5e9 < size["weights"] < 7.8e9            # the issue's 7.64 GB
    s = shape.shapes(real)
    assert s["expert"] == 3 * 2304 * 1024 == 7_077_888
    assert s["kda_mixer"] == 4 * 4096 * 2304
    assert s["latent_mixer"] == (32 * 192 + 576 + 4096) * 2304
    # state_work: 7 x 128 x 128 FLOPs a token a head a layer; a live row's
    # 2,097,152 B state read and written once a program a layer
    w = shape.state_work(real, "decode", rows=8, tokens=8)
    assert w["flops"] == 8 * 20 * 32 * 7 * 128 * 128
    assert w["bytes"] == 20 * 4 * (8 * 2 * 32 * 128 * 128
                                   + 8 * 32 * (5 * 128 + 1))
    w = shape.state_work(real, "prefill", rows=1, tokens=32)
    assert w["bytes"] == 20 * (2 * 2_097_152 + 4 * 32 * 32 * 641)
    # attention_work: the 7 latent layers, 576-wide bf16 rows
    a = shape.attention_work(real, "decode", pairs=1000, cached_tokens=0)
    assert a["flops"] == 1000 * 7 * 2 * 32 * (576 + 512)
    assert a["bytes"] == 1000 * 7 * 1152
    a = shape.attention_work(real, "prefill", pairs=5000, cached_tokens=300)
    assert a["bytes"] == 300 * 7 * 1152
    # matmul_work: the readers' experts= / pairs=; nothing held is a floor
    m0 = shape.matmul_work(real, 8, 8, experts=0, pairs=0)
    m1 = shape.matmul_work(real, 8, 8, experts=3, pairs=5)
    assert m1["bytes"] - m0["bytes"] == 26 * 3 * 7_077_888 * 18 / 32
    assert m1["flops"] - m0["flops"] == 2 * 26 * 5 * 7_077_888
    assert shape.moe(real)["layers"] == 26
    assert shape.moe(real)["floor"](100) == {"experts": 0.0, "pairs": 0.0}


def test_the_manifest_ends_with_this_cell_and_its_two_metrics(real):
    """What `benchmark/tests`' pins of "the last entries" would say of this
    PR's: one configuration, one one-chip cell under long-doc and two
    per-layer metrics over the reader the benchmark has, each LAST in its
    list (PR 49's counter metric has come after them since); the cell's
    file is olmo's."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    # found by name: PR 52 appended a ninth cell, a seventh configuration
    # and two metrics after these (tests/test_jamba_bench.py holds them)
    assert len(m["workloads"]) >= 8 and len(m["configs"]) >= 6
    assert m["configs"][5]["name"] == "kimi-linear-48b-a3b-ep4"
    assert m["configs"][5]["reduced"] == real["reduced"] == [
        "num_experts", "vocab_size", "max_position_embeddings"]
    assert m["workloads"][7] == {
        "name": "kimi-linear-48b-a3b-ep4.long-doc",
        "config": "kimi-linear-48b-a3b-ep4", "traffic": "long-doc",
        "chips": 1, "why": m["workloads"][7]["why"]}
    at = [x["name"] for x in m["per_layer"]].index("kda_decode_roofline")
    kda = m["per_layer"][at:at + 2]
    assert [x["name"] for x in kda] == [
        "kda_decode_roofline", "kda_prefill_roofline"]
    for x, moves in zip(kda, ("itl_p50_ms", "ttft_p50_ms")):
        assert x["workloads"] == ["kimi-linear-48b-a3b-ep4.long-doc"]
        assert x["moves"] == moves and x["source"] == "device_trace"
        spec = traffic.load_json("layer_metrics", x["name"] + ".json")
        assert spec["reader"] == "trace_state_roofline"
    assert traffic.load_json(
        "cells", "kimi-linear-48b-a3b-ep4.long-doc.json") == (
        traffic.load_json("cells", "olmo-hybrid-7b.long-doc.json"))
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
