"""The benchmark's files for `granitemoehybrid` configurations, on the CPU:
the reference against a per-token numpy oracle written here, the shape's
counts against hand-worked numbers, the state reader through this shape, the
published configuration against the program's spec and against the catalog's
numbers, and the manifest with its seven cells: the successor of
test_olmo_hybrid.py's six-cell test, which a PR that adds a cell cannot
satisfy and may not edit. Of that test only "exactly six cells, olmo's
configuration last" is lost; every other assertion of it is carried here
(the accepted cells' order, olmo's published numbers, the `delta_rule_*`
entries, the cell files that equal one another)."""

import json
import os

import numpy as np
import pytest

import workmodel
from readers import trace_state_roofline
from reference import granitemoehybrid

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = "granite-4.0-h-small-ep2"
CELL = NAME + ".decode-batch"
with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
    CFG = json.load(f)
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    M = json.load(f)


def _oracle(tensors, spec, tokens):
    """One token at a time in float64 numpy, explicit loops over heads and
    experts: the published equations as ISSUE 39 writes them."""
    w = {k: t.to_f32().astype(np.float64) for k, t in tensors.items()}
    eps, m_r = spec.rms_eps, spec.residual_scale
    h, p, n = spec.ssm_heads, spec.ssm_head_dim, spec.ssm_d_state
    inner, taps = spec.ssm_inner, spec.ssm_conv_width
    hs, group = spec.dim // spec.n_heads, spec.n_heads // spec.n_kv_heads

    def rms(x, g):
        return g * x / np.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def mlp(x, gate, up, down):
        return down @ (silu(gate @ x) * (up @ x))

    state = {l: np.zeros((h, p, n)) for l in range(spec.n_layers)}
    tail = {l: np.zeros((taps - 1, spec.ssm_conv_dim))
            for l in range(spec.n_layers)}
    rows = {l: ([], []) for l in range(spec.n_layers)}
    out = []
    for tok in tokens:
        x = spec.embedding_scale * w["tok_emb"][tok]
        for l, kind in enumerate(spec.layer_kinds):
            pre = f"layers.{l}."
            u = rms(x, w[pre + "rms_att"])
            if int(kind) == 3:
                new = np.concatenate([w[pre + "wx"] @ u, w[pre + "wbc"] @ u])
                window = np.vstack([tail[l], new])
                tail[l] = window[1:]
                y = silu((w[pre + "conv_w"] * window).sum(0)
                         + w[pre + "conv_b"])
                xs, b, c = y[:inner], y[inner:inner + n], y[inner + n:]
                dt = np.log1p(np.exp(w[pre + "wdt"] @ u + w[pre + "dt_bias"]))
                a = -np.exp(w[pre + "a_log"])
                o = np.zeros((h, p))
                for i in range(h):
                    xi = xs[i * p:(i + 1) * p]
                    state[l][i] = (np.exp(dt[i] * a[i]) * state[l][i]
                                   + dt[i] * np.outer(xi, b))
                    o[i] = state[l][i] @ c + w[pre + "ssm_d"][i] * xi
                gated = o.ravel() * silu(w[pre + "wz"] @ u)
                mix = w[pre + "wo"] @ rms(gated, w[pre + "rms_o"])
            else:
                q = (w[pre + "wq"] @ u).reshape(-1, hs)
                rows[l][0].append((w[pre + "wk"] @ u).reshape(-1, hs))
                rows[l][1].append((w[pre + "wv"] @ u).reshape(-1, hs))
                ks = np.repeat(np.stack(rows[l][0]), group, axis=1)
                vs = np.repeat(np.stack(rows[l][1]), group, axis=1)
                sc = np.einsum("hd,shd->hs", q, ks) * spec.attn_scale
                sc = np.exp(sc - sc.max(-1, keepdims=True))
                att = np.einsum("hs,shd->hd", sc / sc.sum(-1, keepdims=True),
                                vs)
                mix = w[pre + "wo"] @ att.ravel()
            x = x + m_r * mix
            u = rms(x, w[pre + "rms_ffn"])
            logits = w[pre + "moe_router"] @ u
            top = np.argsort(-logits)[:spec.n_active_experts]
            gates = np.exp(logits[top] - logits[top].max())
            gates /= gates.sum()
            ffn = mlp(u, w[pre + "sh_w1"], w[pre + "sh_w3"], w[pre + "sh_w2"])
            for e, g in zip(top, gates):
                i = e - spec.expert_offset
                if 0 <= i < spec.n_experts:          # held here
                    pe = pre + f"experts.{i}."
                    ffn = ffn + g * mlp(u, w[pe + "gate"], w[pe + "up"],
                                        w[pe + "down"])
            x = x + m_r * ffn
        out.append(spec.logit_scale * (w["wcls"] @ rms(x, w["rms_final"])))
    return np.stack(out)


@pytest.mark.parametrize("offset", [0, 4])
def test_reference_equals_the_oracle(tmp_path, offset):
    from distributed_llama_tpu.io.model_file import read_model
    from distributed_llama_tpu.testing import (tiny_granite_spec,
                                               write_synthetic_model)

    path = str(tmp_path / "m.m")
    write_synthetic_model(path, tiny_granite_spec(expert_offset=offset), 5)
    spec, tensors = read_model(path)
    toks = np.random.default_rng(1).integers(3, spec.vocab_size, 14)
    toks = toks.astype(np.int32)
    want = _oracle(tensors, spec, toks)
    routing: list = []
    got = granitemoehybrid.forward(path, toks, routing=routing)
    assert got.shape == want.shape
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5
    assert len(routing) == spec.n_layers
    assert routing[0]["top_i"].shape == (14, 4)
    assert (routing[0]["margin"] >= 0).all()


def test_published_configuration_maps_onto_the_programs_spec():
    spec = workmodel.for_config(CFG).spec(CFG)
    spec.validate()
    assert spec.arch.name == "GRANITE_HYBRID"
    assert (spec.n_layers, spec.dim, spec.n_heads, spec.n_kv_heads,
            spec.head_size, spec.hidden_dim) == (40, 4096, 32, 8, 128, 768)
    assert spec.vocab_size == 50176 and spec.seq_len == 8192
    assert [int(k) for k in spec.layer_kinds] == ([3] * 5 + [0] + [3] * 4) * 4
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_d_state,
            spec.ssm_groups, spec.ssm_conv_width, spec.ssm_conv_bias) == (
        128, 64, 128, 1, 4, 1)
    assert (spec.n_experts, spec.router_width, spec.n_active_experts,
            spec.expert_offset, spec.n_shared_experts) == (36, 72, 10, 0, 2)
    assert (spec.embedding_scale, spec.residual_scale, spec.attn_scale,
            spec.logit_scale) == (12.0, 0.22, 0.0078125, 0.0625)
    assert spec.rope_theta == 0.0 and spec.rms_eps == 1e-5
    assert spec.cache_values_per_token * 2 == 16_384
    assert spec.state_bytes_per_slot(2) == 152_819_712
    assert CFG["check"] == {"prompt_tokens": 1000, "decode_steps": 768,
                            "judge": "median", "worst_tolerance": 0.2}
    assert "--prefix-cache" not in CFG["server_flags"]
    assert CFG["kernels"] == ["q40_matmul", "q40_expert_matmul",
                              "flash_attention", "kv_cache_write"]
    assert {"mamba", "in_proj_layout", "position_embedding_type",
            "multipliers", "router", "weights"} <= set(CFG["assumed"])


def test_the_file_holds_the_catalogs_every_number_under_its_key():
    published = {
        "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "shared_intermediate_size": 1536}
    assert {k: CFG[k] for k in published} == published
    assert CFG["layer_types"] == (["mamba"] * 5 + ["attention"]
                                  + ["mamba"] * 4) * 4
    assert CFG["reduced"] == ["num_local_experts", "vocab_size",
                              "max_position_embeddings"]
    assert (CFG["num_local_experts"], CFG["published_num_local_experts"],
            CFG["vocab_size"], CFG["max_position_embeddings"]) == (
        36, 72, 50176, 8192)
    assert CFG["mamba_conv_bias"] is True and CFG["mamba_proj_bias"] is False
    assert CFG["tie_word_embeddings"] is True and CFG["rope_scaling"] is None
    assert CFG["position_embedding_type"] == "nope"
    assert CFG["source"].endswith("granite-4.0-h-small/blob/main/config.json")


def test_work_and_sizing_against_hand_worked_numbers():
    shape = workmodel.for_config(CFG)
    mamba = 3 * 8192 * 4096                     # gate, x, out_proj: Q40
    assert mamba + 384 * 4096 == 102_236_160    # + B | C | dt rows: the
    attn, shared, expert = 41_943_040, 18_874_368, 9_437_184   # issue's 102.2 M
    dense = 36 * mamba + 4 * attn + 40 * shared
    head = 50176 * 4096
    # a decode step of 8 rows that chose 25.1 held experts, 40 pairs a layer
    got = shape.matmul_work(CFG, 8.0, 8.0, experts=25.1, pairs=40.0)
    assert got["flops"] == pytest.approx(
        2.0 * (8 * dense + 40 * expert * 40.0) + 2.0 * 8 * head)
    assert got["bytes"] == pytest.approx(
        (dense + 40 * expert * 25.1 + head) * 18 / 32)
    assert 5.2e9 < 40 * expert * 25.1 * 18 / 32 < 5.4e9       # the 5.3 GB
    assert 2.5e9 < dense * 18 / 32 < 2.6e9
    # left out, the expectation under even routing: 36 x (1 - (62/72)^8)
    even = shape.matmul_work(CFG, 8.0, 8.0)
    want = 36 * (1 - (62 / 72) ** 8)
    assert 25.0 < want < 25.2
    assert even["bytes"] == pytest.approx(
        (dense + 40 * expert * want + head) * 18 / 32)
    moe = shape.moe(CFG)
    assert moe["layers"] == 40                  # every layer has experts
    assert moe["floor"](8.0) == {"experts": 0.0, "pairs": 0.0}  # a share
    # decode, 8 live rows: 36 layers x (8 states of 4,194,304 B read and
    # written + 8 tokens' x, y, dt, B, C); 6 x 128 x 64 x 128 FLOPs a token
    dec = shape.state_work(CFG, "decode", 8.0, 8.0)
    assert dec == {"flops": 8 * 36 * 128 * 6.0 * 64 * 128,
                   "bytes": 36 * 4 * (8 * 2.0 * 128 * 64 * 128
                                      + 8 * (2.0 * 8192 + 128 + 256))}
    assert 128 * 64 * 128 * 4 == 4_194_304
    assert 2.4e9 < dec["bytes"] < 2.45e9
    pre = shape.state_work(CFG, "prefill", 1.0, 32.0)
    assert pre["flops"] == 32 * 36 * 128 * 6.0 * 64 * 128
    assert pre["bytes"] == 36 * 4 * (2.0 * 128 * 64 * 128
                                     + 32 * (2.0 * 8192 + 384))
    size = shape.sizing(CFG)
    assert size["cache_per_token"] == 16_384
    assert size["state_per_slot"] == 152_819_712
    assert size["slots"] == 8 * (8192 * 16_384 + 152_819_712)
    assert size["arena"] == 0
    assert 10.7e9 < size["weights"] < 10.9e9    # the issue's 10.77 GB
    assert 12.9e9 < size["weights"] + size["slots"] < 13.2e9


def test_state_reader_reads_the_cell_through_this_shape():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ends = {"decode_rows": 80, "decode_steps": 10}
    ctx = {"config": CFG, "peaks": peaks,
           "trace": {"executions": [
               {"module": "slot_decode_step",
                "kernel_s": {"ssd_decode": 0.006}}] * 3},
           "stats": {"trace_end": {"capture": {
               "start": {k: 0 for k in ends}, "stop": ends}}}}
    got = trace_state_roofline.read(ctx, "decode", ["ssd_decode"])
    least = 36 * 4 * (8 * 2 * 128 * 64 * 128 + 8 * (2 * 8192 + 384)) / 819e9
    assert got["value"] == pytest.approx(100 * least / 0.006)
    assert 48 < got["value"] < 50 and "memory-bound" in got["note"]
    # the other program's kernel is not in this one: nothing to read
    assert trace_state_roofline.read(ctx, "prefill", ["ssd_chunk"]) is None


def test_the_manifest_has_seven_cells_and_the_new_entries_come_last():
    cells = [w["name"] for w in M["workloads"]]
    assert cells == ["mistral-7b.chat-steady", "mixtral-8x7b-12l.chat-steady",
                     "mistral-7b.doc-batch", "sarvam-105b-ep8.long-doc",
                     "olmo-hybrid-7b.long-doc", "mixtral-8x7b-12l.doc-batch",
                     CELL]
    assert [c["name"] for c in M["configs"]][-2:] == ["olmo-hybrid-7b", NAME]
    assert all(w["chips"] == 1 for w in M["workloads"])
    assert M["workloads"][-1]["traffic"] == "decode-batch"
    names = [m["name"] for m in M["per_layer"]]
    assert names[-5:] == ["delta_rule_decode_roofline",
                          "delta_rule_prefill_roofline",
                          "ssd_decode_roofline", "ssd_prefill_roofline",
                          "decode_experts_read_per_layer"]
    for m, moves, layer in zip(
            M["per_layer"][-3:], ("itl_p50_ms", "ttft_p50_ms", "itl_p50_ms"),
            ("kernels (ops/pallas_ssd.py)", "kernels (ops/pallas_ssd.py)",
             "engine, model step (runtime/engine.py)")):
        assert m["workloads"] == [CELL]
        assert m["moves"] == moves and m["layer"] == layer
    # no accepted entry lists the new cell (a model_config PR edits none)
    assert all(CELL not in (m.get("workloads") or ())
               for m in M["per_layer"][:-3])
    with open(os.path.join(BENCH, "cells", CELL + ".json")) as f, \
            open(os.path.join(BENCH, "cells",
                              "olmo-hybrid-7b.long-doc.json")) as g:
        assert json.load(f) == json.load(g)
    with open(os.path.join(BENCH, "traffic", "decode-batch.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["pool"], mix["temperature"]) == (
        "closed", CFG["server"]["serve_batch"], 64, 0.8)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256, "max": 768}


def test_olmos_entries_keep_their_places_and_their_keys():
    """Carried from test_olmo_hybrid.py's six-cell test: what it held of the
    `delta_rule_*` entries, which stand where PR 34 put them."""
    new = [m for m in M["per_layer"] if m["name"].startswith("delta_rule_")]
    assert [m["name"] for m in M["per_layer"]][-5:-3] == [
        m["name"] for m in new]
    assert len(new) == 2
    for m, moves in zip(new, ("itl_p50_ms", "ttft_p50_ms")):
        assert m["workloads"] == ["olmo-hybrid-7b.long-doc"]
        assert m["moves"] == moves and m["source"] == "device_trace"
        assert m["layer"] == "kernels (ops/pallas_delta_rule.py)"
    for m in M["per_layer"][-3:-1]:
        assert m["source"] == "device_trace"
    assert M["per_layer"][-1]["source"] == "program_counter"


@pytest.mark.parametrize("name,like", [
    ("olmo-hybrid-7b.long-doc", "sarvam-105b-ep8.long-doc"),
    ("mixtral-8x7b-12l.doc-batch", "mistral-7b.doc-batch"),
    (CELL, "olmo-hybrid-7b.long-doc")])
def test_a_cell_file_equals_the_one_it_was_taken_from(name, like):
    """Carried from the six-cell test (its first two pairs)."""
    with open(os.path.join(BENCH, "cells", name + ".json")) as f, \
            open(os.path.join(BENCH, "cells", like + ".json")) as g:
        assert json.load(f) == json.load(g)


def test_olmo_hybrid_7b_keeps_the_catalogs_every_number():
    """Carried from the six-cell test: an accepted configuration's widths
    cannot drift unseen. The catalog's every number, under its key; only
    the context is cut."""
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        olmo = json.load(f)
    published = {"vocab_size": 100352, "hidden_size": 3840,
                 "intermediate_size": 11008, "num_hidden_layers": 32,
                 "num_attention_heads": 30, "num_key_value_heads": 30,
                 "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
                 "linear_num_value_heads": 30, "linear_key_head_dim": 96,
                 "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4}
    assert {k: olmo[k] for k in published} == published
    assert olmo["max_position_embeddings"] == 8192
    assert olmo["layer_types"] == (["linear_attention"] * 3
                                   + ["full_attention"]) * 8
    assert olmo["rope_parameters"] == {"rope_theta": None}
