"""The whole benchmark command at tiny size on the CPU for an OLMO_HYBRID
configuration: the check child drives the served step programs of a hybrid
file against reference/olmo_hybrid.py (chunks with a padded tail, then
decode from the carried state), the server runs the slot cache with a state
a slot and NO prefix arena, and /stats carries the new counter and gauge."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

TINY = {
    "name": "tiny-hybrid-test", "arch": "OLMO_HYBRID", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 288,
    "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 32, "linear_value_head_dim": 64,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "max_position_embeddings": 128, "shape": "shapes/olmo_hybrid.py",
    "reference": "reference/olmo_hybrid.py", "weights_seed": 17, "chips": 1,
    "server": {"serve_batch": 4, "serve_chunk": 8, "max_seq_len": 128,
               "prefix_blocks": 0, "prefix_block_len": 8},
    "server_flags": ["--serve-batch", "4", "--serve-chunk", "8",
                     "--max-seq-len", "128"],
    "executables": {"decode": "slot_decode_step",
                    "prefill": "slot_prefill_chunk_8"},
    "compile_keys": ["slot_decode", "slot_prefill:8"],
    "kernels": ["q40_matmul", "flash_attention", "kv_cache_write"],
    # float32 engine (engine_flags below): only summation order differs
    "logit_tolerance": 0.001,
    "check": {"prompt_tokens": 45, "decode_steps": 3}}   # 5 chunks + 5 of 8


def test_the_whole_command_at_tiny_size_on_cpu(monkeypatch, tmp_path):
    # a cache directory of its own: the harness empties `<cache>/run` at
    # the start of every run, and tests/test_sarvam_mla_bench.py runs the
    # command too, on another worker of the same suite
    monkeypatch.setattr(run, "CACHE", str(tmp_path / "cache"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = [dict(m, workloads=None) for m in manifest["per_layer"]]
    plan = run.Plan(
        workload={"name": "tiny-hybrid-test.closed", "chips": 1},
        config=dict(TINY),
        mix={"loop": "closed", "clients": 3, "pool": 12, "temperature": 0.8,
             "prompt_tokens": {"dist": "uniform", "min": 20, "max": 70},
             "output_tokens": {"dist": "uniform", "min": 2, "max": 5}},
        cell={"ramp_s": 1.5, "trace_after_s": 0.5, "trace_ms": 500,
              "drain_s": 60, "schedule_seed": 3},
        end_to_end=manifest["end_to_end"], per_layer=per_layer,
        seed=3000000019, seconds=3.0, trace=True,
        engine_flags=["--compute-dtype", "f32", "--cache-dtype", "f32",
                      "--buffer-float-type", "f32"],
        chip_env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},  # one device,
        # not the suite's eight virtual ones
        want_platform="cpu")
    seen = {}
    layer_metrics = run.layer_metrics

    def spy(plan, ctx):
        seen.update(ctx)
        return layer_metrics(plan, ctx)

    monkeypatch.setattr(run, "layer_metrics", spy)
    out = run.run(plan)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["logits_worst_rel_l2"]["value"] <= 0.001
    # what the state reader differences reached it: the server's own
    # counters at the capture's two ends, the new one among them
    ends = seen["stats"]["trace_end"]["capture"]
    assert {"prefill_rows", "prefill_tokens", "prefill_steps", "decode_rows",
            "decode_steps"} <= set(ends["stop"])
    end = seen["stats"]["window_end"]
    assert end["prefill_rows"] >= end["prefill_steps"] > 0
    assert end["state_bytes_per_slot"] == 6 * (2 * 32 * 64 * 4 + 3 * 256 * 4)
    assert end["cache_bytes_per_token"] == 2 * 2 * 64 * 4
    assert "prefix_cache" not in end
    # no device plane on a CPU: the trace readers leave their metrics out
    assert "delta_rule_decode_roofline" not in out["metrics"]
    assert "prefill_tokens_per_chunk" in out["metrics"]
