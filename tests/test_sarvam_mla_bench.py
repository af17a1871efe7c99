"""The benchmark's files for `sarvam_mla` configurations, on the CPU: the
shape's counts against hand-worked numbers, the attention reader on
hand-made executions and on a program without the counters, the published
configuration against the program's spec, and the whole command at tiny
size (the served step programs against the reference through the check
child, the new counters through /stats)."""

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
import workmodel  # noqa: E402
from readers import trace_attention_roofline  # noqa: E402

with open(os.path.join(BENCH, "configs", "sarvam-105b-ep8.json")) as f:
    CFG = json.load(f)

TINY = {
    "name": "tiny-mla-test", "arch": "SARVAM_MLA", "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 4,
    "published_num_experts": 8, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "vocab_size": 288, "hidden_act": "silu",
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "type": "deepseek_yarn"},
    "max_position_embeddings": 128, "shape": "shapes/sarvam_mla.py",
    "reference": "reference/sarvam_mla.py", "weights_seed": 13, "chips": 1,
    "server": {"serve_batch": 4, "serve_chunk": 8, "max_seq_len": 128,
               "prefix_blocks": 16, "prefix_block_len": 8},
    "server_flags": ["--serve-batch", "4", "--serve-chunk", "8",
                     "--max-seq-len", "128", "--prefix-cache",
                     "--prefix-blocks", "16", "--prefix-block-len", "8"],
    "executables": {"decode": "slot_decode_step",
                    "prefill": "slot_prefill_chunk_8"},
    "compile_keys": ["slot_decode", "slot_prefill:8"],
    "kernels": ["q40_matmul", "mla_attention", "kv_cache_write"],
    # float32 engine (engine_flags below): only summation order differs
    "logit_tolerance": 0.001,
    "check": {"prompt_tokens": 44, "decode_steps": 3}}


def test_published_configuration_maps_onto_the_programs_spec():
    spec = workmodel.for_config(CFG).spec(CFG)
    spec.validate()
    assert (spec.n_layers, spec.n_heads, spec.head_size) == (32, 64, 192)
    assert (spec.cache_head_size, spec.cache_v_head_size) == (576, 0)
    assert spec.cache_values_per_token * 2 == 36864
    assert (spec.n_experts, spec.router_width, spec.n_active_experts) == (
        16, 128, 8)
    assert spec.is_dense_layer(0) and not spec.is_dense_layer(1)
    assert spec.attn_softmax_scale == pytest.approx(192 ** -0.5 * 1.36889 ** 2,
                                                    rel=1e-5)
    assert CFG["reduced"] == ["num_experts", "vocab_size",
                              "max_position_embeddings"]
    assert CFG["kernels"] == ["q40_matmul", "mla_attention", "kv_cache_write"]


def test_work_and_sizing_against_hand_worked_numbers():
    shape = workmodel.for_config(CFG)
    att = 64 * 192 * 4096 + 576 * 4096 + 4096 * 64 * 128     # 86.24 M
    expert = 3 * 2048 * 4096
    one = shape.matmul_work(CFG, 1.0, 1.0)
    # one token: the shared expert and ONE routed expert's worth (8 x 16/128)
    per_token = 32 * att + 3 * 16384 * 4096 + 31 * expert * 2
    assert one["flops"] == pytest.approx(2 * per_token + 2 * 32768 * 4096)
    touched = 16 * (1 - (1 - 8 / 128) ** 1)
    assert one["bytes"] == pytest.approx(
        (32 * att + 3 * 16384 * 4096 + 31 * expert * (1 + touched)
         + 32768 * 4096) * 18 / 32)
    # 256 tokens touch every held expert: all the chip's Q40 weights read
    full = shape.matmul_work(CFG, 256.0, 1.0)["bytes"]
    every = (32 * att + 3 * 16384 * 4096 + 31 * expert * 17
             + 32768 * 4096) * 18 / 32
    assert 0.999 * every < full <= every
    dec = shape.attention_work(CFG, "decode", 8 * 4000, 0)
    assert dec == {"flops": 8 * 4000 * 32 * 139264.0,
                   "bytes": 8 * 4000 * 32 * 1152.0}
    pre = shape.attention_work(CFG, "prefill", 100000.0, 12000.0)
    assert pre == {"flops": 100000 * 32 * 139264.0,
                   "bytes": 12000 * 32 * 1152.0}
    size = shape.sizing(CFG)
    assert size["cache_per_token"] == 36864
    assert size["slots"] == 8 * 8192 * 36864
    assert size["arena"] == 1024 * 32 * 36864
    assert 10.0e9 < size["weights"] < 10.4e9      # 9.63 GB + 0.54 GB bf16


def _ctx(config, stats_end, kernel_s=0.001):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return {"config": config, "peaks": peaks,
            "trace": {"executions": [
                {"module": "slot_decode_step",
                 "kernel_s": {"mla_attention": kernel_s}}] * 3},
            # the server's own record of the capture's two ends
            "stats": {"trace_end": {"capture": {
                "start": {k: 0 for k in stats_end}, "stop": stats_end}}}}


def test_attention_reader_on_hand_made_executions():
    # 10 decode steps of 8 rows at 4000 cached positions: 32,000 pairs a
    # step; bytes 32000 x 32 x 1152 = 1.18 GB -> 1.44 ms; FLOPs 0.72 ms
    ctx = _ctx(CFG, {"attn_pairs_decode": 320000, "decode_steps": 10},
               kernel_s=0.002880)
    got = trace_attention_roofline.read(ctx, "decode", ["mla_attention"])
    least = 32000 * 32 * 1152 / 819e9
    assert got["value"] == pytest.approx(100 * least / 0.002880)
    assert "memory-bound" in got["note"] and "32000 pairs" in got["note"]
    # a program without the counters (the parent), a shape without
    # attention_work, a trace without the kernel: nothing to read
    assert trace_attention_roofline.read(
        _ctx(CFG, {"decode_steps": 10}), "decode", ["mla_attention"]) is None
    with open(os.path.join(BENCH, "configs", "mistral-7b.json")) as f:
        mistral = json.load(f)
    assert trace_attention_roofline.read(
        _ctx(mistral, {"attn_pairs_decode": 320000, "decode_steps": 10}),
        "decode", ["mla_attention"]) is None
    assert trace_attention_roofline.read(ctx, "decode", ["other"]) is None
    assert trace_attention_roofline.read(ctx, "prefill",
                                         ["mla_attention"]) is None


def test_a_capture_records_the_counters_at_its_two_ends(tmp_path):
    """What the attention reader differences: the server's counters as the
    trace starts and as it stops, before the export (/stats `capture`)."""
    from distributed_llama_tpu.runtime.profiler import PROFILER

    ticks = iter(({"decode_steps": 3, "attn_pairs_decode": 100},
                  {"decode_steps": 5, "attn_pairs_decode": 900}))
    try:
        out = PROFILER.capture(str(tmp_path), 5, lambda: next(ticks))
        assert out["ms"] == 5.0
        assert PROFILER.last_counters == {
            "start": {"decode_steps": 3, "attn_pairs_decode": 100},
            "stop": {"decode_steps": 5, "attn_pairs_decode": 900}}
    finally:
        PROFILER.reset()
    assert PROFILER.last_counters is None


def test_the_whole_command_at_tiny_size_on_cpu(monkeypatch):
    """Closed loop, traced: the check child drives the served step programs
    of a SARVAM_MLA file against reference/sarvam_mla.py, the server runs
    the latent slot cache and arena, and the counter metric of the new
    cell reads the new counters."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = [dict(m, workloads=None) for m in manifest["per_layer"]]
    plan = run.Plan(
        workload={"name": "tiny-mla-test.closed", "chips": 1},
        config=dict(TINY),
        mix={"loop": "closed", "clients": 3, "pool": 12, "temperature": 0.8,
             "prompt_tokens": {"dist": "uniform", "min": 20, "max": 70},
             "output_tokens": {"dist": "uniform", "min": 2, "max": 5}},
        cell={"ramp_s": 1.5, "trace_after_s": 0.5, "trace_ms": 500,
              "drain_s": 60, "schedule_seed": 3},
        end_to_end=manifest["end_to_end"], per_layer=per_layer,
        seed=3000000019, seconds=3.0, trace=True,
        # float32 throughout: in bf16 a near-tie in the tiny router (top 4
        # of 8, half of them held) sends a token to another expert than the
        # reference's and moves its logits by a fifth
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
    # what the attention reader differences reached it: the server's own
    # counters at the capture's two ends
    ends = seen["stats"]["trace_end"]["capture"]
    assert ends["stop"]["prefill_steps"] >= ends["start"]["prefill_steps"]
    assert {"attn_pairs_decode", "attn_pairs_prefill",
            "prefill_cached_tokens", "decode_steps"} <= set(ends["stop"])
    assert out["compared"]["logits_worst_rel_l2"]["value"] <= 0.001
    assert out["metrics"]["decode_context_per_row"]["value"] > 20
    # no device plane on a CPU: the trace readers leave their metrics out
    assert "mla_decode_attention_roofline" not in out["metrics"]
