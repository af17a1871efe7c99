"""The whole benchmark command at tiny size on the CPU for a GRANITE_HYBRID
configuration, through the new shape, reference and traffic file: the check
child drives the served step programs of a hybrid file with a held share of
experts against reference/granitemoehybrid.py (chunks with a padded tail,
then decode from the carried state), the server runs the slot cache with a
state a slot and NO prefix arena, and /stats carries the gauges and the
expert counters of all six layers."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
import traffic  # noqa: E402

TINY = {
    "name": "tiny-granite-test", "arch": "GRANITE_HYBRID", "hidden_size": 64,
    "intermediate_size": 32, "shared_intermediate_size": 64,
    "num_hidden_layers": 6, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 288, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "layer_types": (["mamba"] * 2 + ["attention"]) * 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "position_embedding_type": "nope",
    "num_local_experts": 4, "published_num_local_experts": 8,
    "expert_offset": 0, "num_experts_per_tok": 4,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0625, "logits_scaling": 16,
    "max_position_embeddings": 128, "shape": "shapes/granitemoehybrid.py",
    "reference": "reference/granitemoehybrid.py", "weights_seed": 19,
    "chips": 1,
    "server": {"serve_batch": 4, "serve_chunk": 8, "max_seq_len": 128,
               "prefix_blocks": 0, "prefix_block_len": 8},
    "server_flags": ["--serve-batch", "4", "--serve-chunk", "8",
                     "--max-seq-len", "128"],
    "executables": {"decode": "slot_decode_step",
                    "prefill": "slot_prefill_chunk_8"},
    "compile_keys": ["slot_decode", "slot_prefill:8"],
    "kernels": ["q40_matmul", "q40_expert_matmul", "flash_attention",
                "kv_cache_write"],
    # float32 engine (engine_flags below): only summation order differs
    "logit_tolerance": 0.001,
    # 5 chunks + 5 of 8; judged as the real configuration is: the median
    # row against logit_tolerance, the worst against worst_tolerance
    "check": {"prompt_tokens": 45, "decode_steps": 3, "judge": "median",
              "worst_tolerance": 0.002}}


def test_the_whole_command_at_tiny_size_on_cpu(monkeypatch, tmp_path):
    # a cache directory of its own (tests/test_olmo_hybrid_bench.py says why)
    monkeypatch.setattr(run, "CACHE", str(tmp_path / "cache"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = [dict(m, workloads=None) for m in manifest["per_layer"]]
    # the cell's own traffic file, cut to what three seconds hold
    mix = dict(traffic.load_json("traffic", "decode-batch.json"), clients=3,
               pool=12,
               prompt_tokens={"dist": "uniform", "min": 20, "max": 70},
               output_tokens={"dist": "uniform", "min": 2, "max": 5})
    assert mix["loop"] == "closed" and mix["temperature"] == 0.8
    plan = run.Plan(
        workload={"name": "tiny-granite-test.closed", "chips": 1},
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
            "decode_steps"} <= set(ends["stop"])
    end = seen["stats"]["window_end"]
    assert end["prefill_rows"] >= end["prefill_steps"] > 0
    assert end["state_bytes_per_slot"] == 4 * (8 * 16 * 32 * 4 + 3 * 192 * 4)
    assert end["cache_bytes_per_token"] == 2 * 2 * 32 * 4
    assert "prefix_cache" not in end
    # no device plane on a CPU: the trace readers leave their metrics out;
    # the XLA path counts no experts, so the counter metric reads 0 experts
    assert "ssd_decode_roofline" not in out["metrics"]
    assert "prefill_tokens_per_chunk" in out["metrics"]
    assert "decode_experts_read_per_layer" in out["metrics"]
