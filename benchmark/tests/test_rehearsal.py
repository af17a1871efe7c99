"""The whole command at a tiny size on the CPU, through a test-only plan
(the command itself has no option that lets it pass without a chip), once
more for a configuration that brings its own shape and check lengths, and
the command as the driver calls it, which must fail here."""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

import children
import run
from readers import trace_roofline
from reference.blocks import ModelFile

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
    "kernels": ["q40_matmul", "flash_attention"], "logit_tolerance": 0.06,
    "check": {"prompt_tokens": 20, "decode_steps": 3}}
# the same model by a shape module of its own: no head counts for the
# default mapping to read, and a check of other lengths
SHAPED = {k: v for k, v in TINY.items()
          if k not in ("num_attention_heads", "num_key_value_heads")}
SHAPED.update({"name": "tiny-shape-test", "weights_seed": 11, "head_dim": 16,
               "num_key_value_groups": 2, "shape": "tests/shape_tiny.py",
               "check": {"prompt_tokens": 40, "decode_steps": 2}})
MIXES = {
    "open": {"loop": "open", "arrivals": "exponential", "temperature": 0.8,
             "prompt_tokens": {"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 60},
             "output_tokens": {"dist": "uniform", "min": 3, "max": 8}},
    "closed": {"loop": "closed", "clients": 3, "pool": 12, "temperature": 0.8,
               "prompt_tokens": {"dist": "uniform", "min": 20, "max": 70},
               "output_tokens": {"dist": "uniform", "min": 2, "max": 5}}}


def plan(loop: str, trace: bool, config: dict = TINY) -> run.Plan:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return run.Plan(
        workload={"name": f"{config['name']}.{loop}", "chips": 1},
        config=dict(config), mix=MIXES[loop],
        cell={"rate_rps": 4.0, "ramp_s": 1.5, "trace_after_s": 0.5,
              "trace_ms": 500, "drain_s": 60, "schedule_seed": 3},
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"],
        seed=3000000019, seconds=3.0, trace=trace,
        chip_env={"JAX_PLATFORMS": "cpu"}, want_platform="cpu")


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


def test_a_configuration_brings_its_own_shape_and_check_lengths():
    """No file of the harness knows `head_dim`, `num_key_value_groups` or
    the constants of tests/shape_tiny.py: the model file, the verdict's
    positions and the roofline's count can only come from the
    configuration's own `shape` and `check`."""
    d = os.path.join(run.CACHE, "tiny-shape-test-11")
    shutil.rmtree(d, ignore_errors=True)      # written and checked here
    with pytest.raises(KeyError):             # the default mapping cannot
        children.spec_of({k: v for k, v in SHAPED.items() if k != "shape"})
    out = run.run(plan("open", trace=False, config=SHAPED))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 12
    assert list(out)[-1] == "compared"
    assert out["compared"]["logits_worst_rel_l2"]["value"] <= 0.06

    h = ModelFile(os.path.join(d, "model.m")).h
    assert (h["dim"], h["n_heads"], h["n_kv_heads"]) == (64, 4, 2)
    (vpath,) = glob.glob(os.path.join(d, "verdict-*.json"))
    with open(vpath) as f:
        rows = json.load(f)["rows"]
    assert [(r["position"], r["step"]) for r in rows] == [
        (39, "prefill"), (40, "decode"), (41, "decode")]

    # hand-made executions: 2 ms of kernel time in each decode program;
    # shape_tiny's 819e6 bytes are 1 ms at the table's 819 GB/s
    ctx = {"config": SHAPED, "peaks": {"bf16_flops_per_s": 197e12,
                                       "hbm_bytes_per_s": 819e9},
           "trace": {"executions": [
               {"module": "slot_decode_step",
                "kernel_s": {"q40_matmul": 0.0015, "other": 0.0005}}] * 3},
           "stats": {"trace_end": {"capture": {
               "start": {"decode_steps": 0, "decode_rows": 0},
               "stop": {"decode_steps": 10, "decode_rows": 30}}}}}
    got = trace_roofline.read(ctx, "decode", ["q40_matmul", "other"])
    assert got["value"] == pytest.approx(50.0)
    assert "3.00 real tokens" in got["note"] and "memory-bound" in got["note"]


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
