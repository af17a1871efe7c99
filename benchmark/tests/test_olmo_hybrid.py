"""The benchmark's files for `olmo_hybrid` configurations, on the CPU: the
reference against a per-token numpy oracle written here, the shape's counts
against hand-worked numbers, the state reader on hand-made executions and on
a program without the counters, the published configuration against the
program's spec, and the manifest with its six cells."""

import json
import os

import numpy as np
import pytest

import workmodel
from readers import trace_state_roofline
from reference import olmo_hybrid
from server import BenchFailure

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    M = json.load(f)


def _oracle(tensors, spec, tokens):
    """One token at a time in float64 numpy, explicit loops over heads: the
    published equations as ISSUE 34 writes them."""
    w = {k: t.to_f32().astype(np.float64) for k, t in tensors.items()}
    eps = spec.rms_eps
    h, dk, dv = spec.lin_heads, spec.lin_k_head_dim, spec.lin_v_head_dim
    taps, hs = spec.lin_conv_width, spec.dim // spec.n_heads

    def rms(x, g):
        return g * x / np.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def silu(x):
        return x / (1.0 + np.exp(-x))

    state = {l: np.zeros((h, dv, dk)) for l in range(spec.n_layers)}
    tail = {l: np.zeros((taps - 1, spec.lin_conv_dim))
            for l in range(spec.n_layers)}
    rows = {l: ([], []) for l in range(spec.n_layers)}
    out = []
    for tok in tokens:
        x = w["tok_emb"][tok]
        for l, kind in enumerate(spec.layer_kinds):
            p = f"layers.{l}."
            if int(kind) == 2:
                new = np.concatenate([w[p + n] @ x for n in ("wq", "wk", "wv")])
                window = np.vstack([tail[l], new])
                tail[l] = window[1:]
                y = silu((w[p + "conv_w"] * window).sum(0))
                a = np.exp(-np.exp(w[p + "a_log"]) * np.log1p(np.exp(
                    w[p + "wa"] @ x + w[p + "dt_bias"])))
                beta = spec.lin_beta_scale / (1.0 + np.exp(-(w[p + "wb"] @ x)))
                z = (w[p + "wg"] @ x).reshape(h, dv)
                o = np.zeros((h, dv))
                for i in range(h):
                    q = y[i * dk:(i + 1) * dk]
                    k = y[h * dk + i * dk:h * dk + (i + 1) * dk]
                    v = y[2 * h * dk + i * dv:2 * h * dk + (i + 1) * dv]
                    q = q / np.sqrt((q * q).sum() + 1e-6) * dk ** -0.5
                    k = k / np.sqrt((k * k).sum() + 1e-6)
                    s = a[i] * state[l][i]
                    s = s + beta[i] * np.outer(v - s @ k, k)
                    state[l][i] = s
                    o[i] = s @ q
                mix = w[p + "wo"] @ (rms(o, w[p + "rms_o"]) * silu(z)).ravel()
            else:
                q = rms(w[p + "wq"] @ x, w[p + "rms_q"]).reshape(-1, hs)
                k = rms(w[p + "wk"] @ x, w[p + "rms_k"]).reshape(-1, hs)
                rows[l][0].append(k)
                rows[l][1].append((w[p + "wv"] @ x).reshape(-1, hs))
                ks, vs = np.stack(rows[l][0]), np.stack(rows[l][1])
                sc = np.einsum("hd,shd->hs", q, ks) * hs ** -0.5
                sc = np.exp(sc - sc.max(-1, keepdims=True))
                att = np.einsum("hs,shd->hd", sc / sc.sum(-1, keepdims=True),
                                vs)
                mix = w[p + "wo"] @ att.ravel()
            x = x + rms(mix, w[p + "rms_att"])
            ffn = w[p + "w2"] @ (silu(w[p + "w1"] @ x) * (w[p + "w3"] @ x))
            x = x + rms(ffn, w[p + "rms_ffn"])
        out.append(w["wcls"] @ rms(x, w["rms_final"]))
    return np.stack(out)


def test_reference_equals_the_oracle(tmp_path):
    from distributed_llama_tpu.io.model_file import read_model
    from distributed_llama_tpu.testing import (tiny_hybrid_spec,
                                               write_synthetic_model)

    path = str(tmp_path / "m.m")
    write_synthetic_model(path, tiny_hybrid_spec(), 5)
    spec, tensors = read_model(path)
    toks = np.random.default_rng(1).integers(3, spec.vocab_size, 14)
    toks = toks.astype(np.int32)
    want = _oracle(tensors, spec, toks)
    got = olmo_hybrid.forward(path, toks)
    assert got.shape == want.shape
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5


def test_published_configuration_maps_onto_the_programs_spec():
    spec = workmodel.for_config(CFG).spec(CFG)
    spec.validate()
    assert (spec.n_layers, spec.dim, spec.n_heads, spec.n_kv_heads,
            spec.head_size) == (32, 3840, 30, 30, 128)
    assert spec.vocab_size == 100352 and spec.seq_len == 8192
    assert [int(k) for k in spec.layer_kinds] == [2, 2, 2, 0] * 8
    assert (spec.lin_heads, spec.lin_k_head_dim, spec.lin_v_head_dim,
            spec.lin_conv_width, spec.lin_beta_scale) == (30, 96, 192, 4, 2)
    assert spec.rope_theta == 0.0 and spec.rms_eps == 1e-6
    assert spec.cache_values_per_token * 2 == 122_880
    assert spec.state_bytes_per_slot(2) == 54_743_040
    assert CFG["reduced"] == ["max_position_embeddings"]
    assert CFG["check"] == {"prompt_tokens": 2100, "decode_steps": 4}
    assert "--prefix-cache" not in CFG["server_flags"]
    assert {"linear_attention", "block", "rope_parameters",
            "weights"} <= set(CFG["assumed"])


def test_work_and_sizing_against_hand_worked_numbers():
    shape = workmodel.for_config(CFG)
    linear = 11_059_200 * 2 + 22_118_400 * 3            # 88,473,600 in Q40
    full, mlp = 58_982_400, 126_812_160
    q40 = 24 * (linear + mlp) + 8 * (full + mlp)
    assert linear + 230_400 == 88_704_000               # + W_a, W_b: dense
    assert 24 * (88_704_000 + mlp) + 8 * (full + mlp) == 6_658_744_320
    head = 100352 * 3840
    one = shape.matmul_work(CFG, 1.0, 1.0)
    assert one == {"flops": 2.0 * q40 + 2.0 * head,
                   "bytes": (q40 + head) * 18 / 32}
    chunk = shape.matmul_work(CFG, 170.0, 1.0)
    assert chunk["flops"] == 2.0 * 170 * q40 + 2.0 * head
    assert chunk["bytes"] == one["bytes"]                # dense: read once
    # decode, 3 live rows: 24 layers x 30 heads x (3 states read and written
    # + 3 tokens' q, k, v, o) in float32; 6 x 96 x 192 FLOPs a token a head
    dec = shape.state_work(CFG, "decode", 3.0, 3.0)
    assert dec == {"flops": 3 * 24 * 30 * 6.0 * 96 * 192,
                   "bytes": 24 * 30 * 4 * (3 * 2.0 * 96 * 192
                                           + 3 * 2.0 * (96 + 192))}
    assert 24 * 30 * 4 * 2 * 96 * 192 == 2 * 24 * 2_211_840
    pre = shape.state_work(CFG, "prefill", 5.0, 150.0)
    assert pre["flops"] == 150 * 24 * 30 * 6.0 * 96 * 192
    assert pre["bytes"] == 24 * 30 * 4 * (5 * 2.0 * 96 * 192
                                          + 150 * 2.0 * 288)
    size = shape.sizing(CFG)
    assert size["cache_per_token"] == 122_880
    assert size["state_per_slot"] == 54_743_040
    assert size["slots"] == 8 * (8192 * 122_880 + 54_743_040)
    assert size["arena"] == 0
    assert 4.70e9 < size["weights"] < 4.76e9             # 3.75 + 0.22 + 0.77


def _ctx(config, stats_end, module, kernel, kernel_s=0.001):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return {"config": config, "peaks": peaks,
            "trace": {"executions": [
                {"module": module, "kernel_s": {kernel: kernel_s}}] * 3},
            "stats": {"trace_end": {"capture": {
                "start": {k: 0 for k in stats_end}, "stop": stats_end}}}}


def test_state_reader_on_hand_made_executions():
    # 10 decode steps of 3 live rows: 3 x 24 x 2 x 2,211,840 B = 318.5 MB
    # of state + q, k, v, o -> 0.389 ms at 819 GB/s; FLOPs 1.2 us
    ctx = _ctx(CFG, {"decode_rows": 30, "decode_steps": 10},
               "slot_decode_step", "delta_rule_decode", kernel_s=0.001)
    got = trace_state_roofline.read(ctx, "decode", ["delta_rule_decode"])
    least = 24 * 30 * 4 * (3 * 2 * 96 * 192 + 3 * 2 * 288) / 819e9
    assert got["value"] == pytest.approx(100 * least / 0.001)
    assert 38 < got["value"] < 40 and "memory-bound" in got["note"]
    pre = _ctx(CFG, {"prefill_rows": 50, "prefill_tokens": 1500,
                     "prefill_steps": 10}, "slot_prefill_chunk_32",
               "delta_rule_chunk", kernel_s=0.004)
    got = trace_state_roofline.read(pre, "prefill", ["delta_rule_chunk"])
    least = 24 * 30 * 4 * (5 * 2 * 96 * 192 + 150 * 2 * 288) / 819e9
    assert got["value"] == pytest.approx(100 * least / 0.004)
    # a program without the counter (the parent has no prefill_rows), a
    # shape without state_work, a trace without the kernel: nothing to read
    assert trace_state_roofline.read(
        _ctx(CFG, {"prefill_tokens": 1500, "prefill_steps": 10},
             "slot_prefill_chunk_32", "delta_rule_chunk"),
        "prefill", ["delta_rule_chunk"]) is None
    with open(os.path.join(BENCH, "configs", "mistral-7b.json")) as f:
        mistral = json.load(f)
    assert trace_state_roofline.read(
        _ctx(mistral, {"decode_rows": 30, "decode_steps": 10},
             "slot_decode_step", "delta_rule_decode"),
        "decode", ["delta_rule_decode"]) is None
    assert trace_state_roofline.read(ctx, "prefill",
                                     ["delta_rule_decode"]) is None


def test_state_reader_fails_a_chip_run_that_fell_back_to_the_twin():
    """The step program ran in the capture and none of its executions holds
    the kernel: on a chip that is the XLA twin being served, which
    `kernels` cannot catch for a kernel one program only holds; without
    peaks (the CPU rehearsal, where the twin is the path) nothing to read."""
    ctx = _ctx(CFG, {"decode_rows": 30, "decode_steps": 10},
               "slot_decode_step", "fusion.12")
    with pytest.raises(BenchFailure, match="fell back to the XLA twin"):
        trace_state_roofline.read(ctx, "decode", ["delta_rule_decode"])
    assert trace_state_roofline.read(dict(ctx, peaks=None), "decode",
                                     ["delta_rule_decode"]) is None


def test_the_manifest_has_six_cells_and_the_new_entries_come_last():
    cells = [w["name"] for w in M["workloads"]]
    assert cells == ["mistral-7b.chat-steady", "mixtral-8x7b-12l.chat-steady",
                     "mistral-7b.doc-batch", "sarvam-105b-ep8.long-doc",
                     "olmo-hybrid-7b.long-doc", "mixtral-8x7b-12l.doc-batch"]
    assert [c["name"] for c in M["configs"]][-1] == "olmo-hybrid-7b"
    assert all(w["chips"] == 1 for w in M["workloads"])
    new = [m for m in M["per_layer"] if m["name"].startswith("delta_rule_")]
    assert [m["name"] for m in M["per_layer"]][-2:] == [m["name"] for m in new]
    for m, moves in zip(new, ("itl_p50_ms", "ttft_p50_ms")):
        assert m["workloads"] == ["olmo-hybrid-7b.long-doc"]
        assert m["moves"] == moves and m["source"] == "device_trace"
        assert m["layer"] == "kernels (ops/pallas_delta_rule.py)"
    for name, like in (("olmo-hybrid-7b.long-doc", "sarvam-105b-ep8.long-doc"),
                       ("mixtral-8x7b-12l.doc-batch", "mistral-7b.doc-batch")):
        with open(os.path.join(BENCH, "cells", name + ".json")) as f, \
                open(os.path.join(BENCH, "cells", like + ".json")) as g:
            assert json.load(f) == json.load(g)
    # the catalog's every number, under its key; only the context is cut
    published = {"vocab_size": 100352, "hidden_size": 3840,
                 "intermediate_size": 11008, "num_hidden_layers": 32,
                 "num_attention_heads": 30, "num_key_value_heads": 30,
                 "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
                 "linear_num_value_heads": 30, "linear_key_head_dim": 96,
                 "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4}
    assert {k: CFG[k] for k in published} == published
    assert CFG["max_position_embeddings"] == 8192
    assert CFG["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 8
    assert CFG["rope_parameters"] == {"rope_theta": None}
