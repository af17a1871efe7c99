"""The whole command at a tiny size on the CPU, through a test-only plan
(the command itself has no option that lets it pass without a chip), and the
command as the driver calls it, which must fail here."""

import json
import os
import subprocess
import sys

import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {
    "name": "tiny-test", "arch": "LLAMA", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 288,
    "hidden_act": "silu", "rope_theta": 10000.0,
    "max_position_embeddings": 128, "reference": "reference/llama.py",
    "weights_seed": 7, "chips": 1,
    "server": {"serve_batch": 4, "serve_chunk": 8, "max_seq_len": 128,
               "prefix_blocks": 16, "prefix_block_len": 8},
    "server_flags": ["--serve-batch", "4", "--serve-chunk", "8",
                     "--max-seq-len", "128", "--prefix-cache",
                     "--prefix-blocks", "16", "--prefix-block-len", "8"],
    "executables": {"decode": "slot_decode_step",
                    "prefill": "slot_prefill_chunk_8"},
    "compile_keys": ["slot_decode", "slot_prefill:8"],
    "kernels": ["q40_matmul", "flash_attention"], "logit_tolerance": 0.06}
MIXES = {
    "open": {"loop": "open", "arrivals": "exponential", "temperature": 0.8,
             "prompt_tokens": {"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 60},
             "output_tokens": {"dist": "uniform", "min": 3, "max": 8}},
    "closed": {"loop": "closed", "clients": 3, "pool": 12, "temperature": 0.8,
               "prompt_tokens": {"dist": "uniform", "min": 20, "max": 70},
               "output_tokens": {"dist": "uniform", "min": 2, "max": 5}}}


def plan(loop: str, trace: bool) -> run.Plan:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return run.Plan(
        workload={"name": "tiny-test." + loop, "chips": 1},
        config=dict(TINY), mix=MIXES[loop],
        cell={"rate_rps": 4.0, "ramp_s": 1.5, "trace_after_s": 0.5,
              "trace_ms": 500, "drain_s": 60, "schedule_seed": 3},
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"],
        seed=3000000019, seconds=3.0, trace=trace,
        chip_env={"JAX_PLATFORMS": "cpu"}, want_platform="cpu",
        check_prompt_tokens=20, check_decode_steps=3)


def test_open_loop_end_to_end_on_cpu():
    out = run.run(plan("open", trace=False))
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == 12          # rate x seconds, every seed
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p50_ms", "itl_p99_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"   # named, never a device metric


def test_closed_loop_traced_on_cpu_leaves_out_device_metrics():
    out = run.run(plan("closed", trace=True))
    assert out["correct"] and out["attempted"] > 0
    # host-side readers report; a CPU trace has no device plane, so the
    # trace readers find nothing and their metrics are left out
    assert {"rows_per_step", "step_ms", "frontdoor_ms"} <= set(out["metrics"])
    assert "device_idle" not in out["metrics"]
    assert "decode_matmul_roofline" not in out["metrics"]
    assert "breakdown" not in out


def test_the_command_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")   # the command overrides it
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "mistral-7b.chat-steady", "--seed", "1",
         "--seconds", "2", "--trace", "0"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    last = p.stdout.strip().splitlines()[-1]
    assert not last.startswith("{"), last
