"""Operations and bytes for both configurations against numbers worked out
by hand from the published sizes, and the peaks table."""

import json
import os

import numpy as np
import pytest

import workmodel as w

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# Mistral-7B: a layer holds wq, wo 4096 x 4096, wk, wv 1024 x 4096 and three
# 14336 x 4096 matrices; the head is 32000 x 4096
ATT = 2 * 4096 * 4096 + 2 * 1024 * 4096          # 41,943,040
FFN = 3 * 14336 * 4096                            # 176,160,768
HEAD = 32000 * 4096                               # 131,072,000


def test_mistral_7b_decode_and_prefill_work():
    c = config("mistral-7b")
    assert ATT == 41_943_040 and FFN == 176_160_768
    values = 32 * (ATT + FFN) + HEAD
    assert values == 7_110_393_856                # "7.11 G Q40 values"
    dec = w.matmul_work(c, 8, logit_rows=8)
    assert dec["bytes"] == values * 18 / 32 == 3_999_596_544
    assert dec["flops"] == 2 * 8 * 32 * (ATT + FFN) + 2 * 8 * HEAD
    pre = w.matmul_work(c, 256)
    assert pre["bytes"] == dec["bytes"]           # dense: read once, always
    assert pre["flops"] == 2 * 256 * 32 * (ATT + FFN) + 2 * HEAD
    peaks = w.load_peaks("TPU v5 lite")
    t, bound = w.roofline_seconds(dec, peaks)
    assert bound == "memory" and t == pytest.approx(3_999_596_544 / 819e9)
    t, bound = w.roofline_seconds(pre, peaks)
    assert bound == "compute" and t == pytest.approx(pre["flops"] / 197e12)


def test_mixtral_12_layers_routes_top_2_of_8():
    c = config("mixtral-8x7b-12l")
    router = 8 * 4096
    one = w.matmul_work(c, 1)                     # one token: two experts
    assert one["bytes"] == (12 * (ATT + 2 * FFN + router) + HEAD) * 18 / 32
    assert one["flops"] == 2 * 12 * (ATT + 2 * FFN + router) + 2 * HEAD
    s = w.shapes(c)
    assert w.experts_touched(s, 1) == pytest.approx(2.0)
    assert w.experts_touched(s, 8) == pytest.approx(8 * (1 - 0.75 ** 8))
    full = w.matmul_work(c, 256)                  # 256 tokens: all eight
    assert full["bytes"] == pytest.approx(
        (12 * (ATT + 8 * FFN + router) + HEAD) * 18 / 32, rel=1e-9)
    # ... but each token still needs only its two experts' FLOPs
    assert full["flops"] == 2 * 256 * 12 * (ATT + 2 * FFN + router) + 2 * HEAD


def test_sizing_matches_the_issue_and_fits_the_chip():
    m = w.sizing(config("mistral-7b"))
    assert m["weights"] == 3_999_596_544 + 32000 * 4096 * 2     # 4.26 GB
    assert m["cache_per_token"] == 128 * 1024                   # 128 KiB
    assert m["slots"] == m["arena"] == 4 * 2**30                # 4.0 GiB each
    x = w.sizing(config("mixtral-8x7b-12l"))
    assert x["cache_per_token"] == 48 * 1024
    assert 10.0e9 < x["weights"] < 10.2e9
    for s in (m, x):
        total = s["weights"] + s["slots"] + s["arena"]
        assert 0.25 * 16.9e9 < total < 15.75 * 2**30


def test_an_unknown_device_is_an_error():
    assert w.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        w.load_peaks("cpu")


# -- the loader: a configuration's own shape, or this default ---------------

# (tokens, logit_rows) -> (flops, bytes) as the harness of PR 27 (the parent
# of the loader) reckoned them; rows of 1.0 are the prefill reader's call,
# rows equal to tokens the decode reader's
PINNED = {
    "mistral-7b": {
        (1, 1.0): (14220787712.0, 3999596544.0),
        (1.8, 1.0): (25387702681.600002, 3999596544.0),
        (1.8, 1.8): (25597417881.600002, 3999596544.0),
        (4.4, 1.0): (61680176332.8, 3999596544.0),
        (4.4, 4.4): (62571465932.8, 3999596544.0),
        (169.5, 1.0): (2366252253184.0, 3999596544.0),
        (169.5, 169.5): (2410423517184.0, 3999596544.0),
        (256, 1.0): (3573674934272.0, 3999596544.0),
        (256, 256): (3640521654272.0, 3999596544.0)},
    "mixtral-8x7b-12l": {
        (1, 1.0): (9725280256.0, 2735235072.0),
        (1.8, 1.0): (17295789260.800003, 4201962984.311789),
        (1.8, 1.8): (17505504460.800003, 4201962984.311789),
        (4.4, 1.0): (41899943526.4, 7187043677.405032),
        (4.4, 4.4): (42791233126.4, 7187043677.405032),
        (169.5, 1.0): (1604263739392.0, 9869746176.0),
        (169.5, 169.5): (1648435003392.0, 9869746176.0),
        (256, 1.0): (2422825025536.0, 9869746176.0),
        (256, 256): (2489671745536.0, 9869746176.0)}}
PINNED_SIZING = {
    "mistral-7b": {"weights": 4261740544, "cache_per_token": 131072,
                   "slots": 4294967296, "arena": 4294967296},
    "mixtral-8x7b-12l": {"weights": 10131890176, "cache_per_token": 49152,
                         "slots": 1610612736, "arena": 1610612736}}


@pytest.mark.parametrize("name,tokens,rows", [
    (n, t, r) for n, table in PINNED.items() for t, r in table])
def test_work_through_the_loader_is_the_parents_bit_for_bit(name, tokens, rows):
    c = config(name)
    shape = w.for_config(c)
    assert shape is w                        # neither file names a shape
    got = shape.matmul_work(c, tokens, logit_rows=rows)
    assert (got["flops"], got["bytes"]) == PINNED[name][(tokens, rows)]


@pytest.mark.parametrize("name", sorted(PINNED_SIZING))
def test_sizing_through_the_loader_is_the_parents(name):
    c = config(name)
    assert w.for_config(c).sizing(c) == PINNED_SIZING[name]


def parents_spec(name):
    """The ModelSpec the parent's `spec_of` gave, written out."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType

    common = dict(dim=4096, hidden_dim=14336, n_heads=32, n_kv_heads=8,
                  vocab_size=32000, seq_len=4096, hidden_act=HiddenAct.SILU,
                  rope_theta=1000000.0, weights_float_type=FloatType.Q40,
                  version=0)
    return {"mistral-7b": ModelSpec(arch=ArchType.LLAMA, n_layers=32,
                                    n_experts=0, n_active_experts=0, **common),
            "mixtral-8x7b-12l": ModelSpec(arch=ArchType.MIXTRAL, n_layers=12,
                                          n_experts=8, n_active_experts=2,
                                          **common)}[name]


@pytest.mark.parametrize("name", ["mistral-7b", "mixtral-8x7b-12l"])
def test_spec_of_is_the_parents_field_by_field(name):
    import dataclasses

    import children

    got, want = children.spec_of(config(name)), parents_spec(name)
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
        assert type(getattr(got, f.name)) is type(getattr(want, f.name))


TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=288,
            max_position_embeddings=128)


@pytest.mark.parametrize("name", ["mistral-7b", "mixtral-8x7b-12l"])
def test_the_synthetic_file_is_the_same_bytes_at_a_tiny_size(tmp_path, name):
    """The configuration's own keys cut to a size a test can write: the
    file `children.synth` writes through `spec_of` and the benchmark's own
    draw (weights.py) is, for a configuration that names no recipe, the
    file the program writes from the parent's spec cut the same way."""
    import dataclasses

    import children
    from distributed_llama_tpu.testing import write_synthetic_model

    c = dict(config(name), **TINY)
    c.pop("weights_recipe", None)
    a, b = str(tmp_path / "a.m"), str(tmp_path / "b.m")
    children.synth({"config": c, "model": a, "tokenizer": str(tmp_path / "t")})
    write_synthetic_model(b, dataclasses.replace(
        parents_spec(name), dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, vocab_size=288, seq_len=128), c["weights_seed"])
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()


@pytest.mark.parametrize("arch", ["LLAMA", "MIXTRAL", "SARVAM_MLA",
                                  "OLMO_HYBRID"])
def test_the_benchmarks_draw_is_the_programs_for_every_architecture(
        tmp_path, arch):
    """weights.py is a copy: without a recipe every architecture gets the
    bytes the program's write_synthetic_model gives it, so the three
    configurations that name none keep their files and hashes."""
    import weights
    from distributed_llama_tpu import testing
    from distributed_llama_tpu.models.spec import ArchType

    spec = {"LLAMA": testing.tiny_spec,
            "MIXTRAL": lambda: testing.tiny_spec(
                arch=ArchType.MIXTRAL, n_experts=4, n_active_experts=2),
            "SARVAM_MLA": testing.tiny_mla_spec,
            "OLMO_HYBRID": testing.tiny_hybrid_spec}[arch]()
    a, b = str(tmp_path / "a.m"), str(tmp_path / "b.m")
    assert weights.write_model(a, spec, 7) == \
        testing.write_synthetic_model(b, spec, 7)
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()


def test_mixtral_names_the_zero_mean_recipe_and_no_nibble_is_zero(tmp_path):
    """`mixtral-8x7b-12l` is drawn zero-mean (nibbles 1..15 in every Q40
    block), the other default-shape configuration keeps its bytes, and a
    recipe nobody wrote is an error."""
    import children
    import weights
    from reference.blocks import ModelFile

    assert config("mixtral-8x7b-12l")["weights_recipe"]["zero_mean"] is True
    assert "weights_recipe" not in config("mistral-7b")
    c = dict(config("mixtral-8x7b-12l"), **TINY)
    path = str(tmp_path / "m.m")
    children.synth({"config": c, "model": path,
                    "tokenizer": str(tmp_path / "t")})
    mf = ModelFile(path)
    off, shape, _ = mf.offsets["layers.1.experts.3.up"]
    n = shape[0] * shape[1] // 32
    with open(path, "rb") as f:
        f.seek(off)
        raw = np.frombuffer(f.read(n * 18), np.uint8).reshape(n, 18)[:, 2:]
    nibbles = np.concatenate([raw & 15, raw >> 4])
    assert nibbles.min() == 1 and nibbles.max() == 15
    assert abs(nibbles.astype(float).mean() - 8.0) < 0.2
    for bad in ("zero_mean", {"zero-mean": True}):
        with pytest.raises(KeyError):
            weights.write_model(path, children.spec_of(c), 1, bad)


def test_a_named_shape_is_loaded_from_its_file_and_held_to_its_three_functions(
        tmp_path):
    import shape_tiny

    c = {"shape": "tests/shape_tiny.py", "hidden_size": 64, "head_dim": 16,
         "num_hidden_layers": 2,
         "server": {"serve_batch": 4, "max_seq_len": 128, "prefix_blocks": 16,
                    "prefix_block_len": 8}}
    shape = w.for_config(c)
    assert shape is not w and shape is w.for_config(dict(c))   # loaded once
    assert shape.matmul_work(c, 3.0, logit_rows=3.0) == {
        "flops": 3 * shape_tiny.FLOPS_PER_TOKEN
        + 3 * shape_tiny.FLOPS_PER_LOGIT_ROW, "bytes": shape_tiny.BYTES}
    assert shape.sizing(c)["cache_per_token"] == 2 * 2 * 16 * 2
    for bad in ("../bench.py", "/etc/hostname.py", "tests/fixtures",
                "metrics.py"):                # outside, no .py, no functions
        with pytest.raises(KeyError):
            w.for_config({"shape": bad})
    with pytest.raises(OSError):
        w.for_config({"shape": "shapes/no-such-architecture.py"})


# -- the matmul roofline reader: the capture's own tokens and experts --------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ROUTER = 8 * 4096


def mixtral_bytes(experts):
    return (12 * (ATT + experts * FFN + ROUTER) + HEAD) * 18 / 32


def capture_ctx(cfg, counters, kernel_s, program="decode"):
    """A traced capture: one execution for every entry of `kernel_s` (its
    q40_expert_matmul + q40_matmul seconds), and the program's counters at
    the capture's stop (zeros at its start)."""
    return {"config": cfg, "peaks": PEAKS,
            "trace": {"executions": [
                {"module": cfg["executables"][program],
                 "kernel_s": {"q40_expert_matmul": 0.8 * s,
                              "q40_matmul": 0.2 * s}} for s in kernel_s]},
            "stats": {"trace_end": {"capture": {
                "start": {k: 0 for k in counters}, "stop": counters}}}}


KERNELS = ["q40_matmul", "q40_expert_matmul"]


@pytest.mark.parametrize("experts,share", [(2.0, 67.3), (4.28, 133.9)])
def test_the_reader_replays_pr_35s_decode_line(experts, share):
    """`mixtral-8x7b-12l.doc-batch`, change side of PR 35 (ledger): 2.66 rows
    a decode step, 4.97 ms of Q40 kernels an execution. Charged the 4.28
    experts a layer that even routing would touch it read 133.9 %; charged
    the two that uniform-byte weights send every token to, 67.3 %."""
    from readers import trace_roofline

    steps = 100
    ctx = capture_ctx(config("mixtral-8x7b-12l"), {
        "decode_steps": steps, "decode_rows": 266,
        "expert_reads_decode": round(experts * 12 * steps),
        "expert_pairs_decode": 2 * 266 * 12}, [4.97e-3] * 7)
    got = trace_roofline.read(ctx, "decode", KERNELS)
    assert got["value"] == pytest.approx(share, abs=1.0)
    assert got["value"] == pytest.approx(
        100 * mixtral_bytes(experts) / 819e9 / 4.97e-3, rel=1e-3)
    assert "counted by the program" in got["note"]
    assert "2.66 real tokens" in got["note"] and "memory-bound" in got["note"]


def test_sum_over_sum_reads_a_skewed_capture_as_one_of_equal_executions():
    """Three decode steps of one row (two experts a layer) and one of three
    rows (five), each at 74 % of its own least time: the median execution
    holds 1 row, the mean 1.5. Mean work over mean time reads 74 %, as a
    capture of four equal executions of the mean does; the median of shares
    against the window's mean work read 1.5 rows' bytes over 1 row's time."""
    from readers import trace_roofline

    cfg = config("mixtral-8x7b-12l")
    rows, experts = [1, 1, 1, 3], [2, 2, 2, 5]
    times = [mixtral_bytes(e) / 819e9 / 0.74 for e in experts]
    counters = {"decode_steps": 4, "decode_rows": sum(rows),
                "expert_reads_decode": sum(experts) * 12,
                "expert_pairs_decode": 2 * sum(rows) * 12}
    skewed = trace_roofline.read(capture_ctx(cfg, counters, times),
                                 "decode", KERNELS)
    equal = trace_roofline.read(
        capture_ctx(cfg, counters, [sum(times) / 4] * 4), "decode", KERNELS)
    assert skewed["value"] == pytest.approx(74.0, rel=1e-6)
    assert equal["value"] == pytest.approx(skewed["value"], rel=1e-9)
    assert "1.50 real tokens" in skewed["note"]
    assert "2.75 experts" in skewed["note"]


def test_a_program_that_counts_no_experts_is_charged_the_floor_of_any_routing():
    """No expert counters in the capture (today's program): no expectation
    stands in. Mixtral is charged the two experts a layer that one token
    already touches, whatever the rows; a held share nothing of its routed
    experts. The floor never reads above what a count would."""
    from readers import trace_roofline

    cfg = config("mixtral-8x7b-12l")
    base = {"decode_steps": 10, "decode_rows": 27}
    floor = trace_roofline.read(capture_ctx(cfg, base, [16.8e-3] * 3),
                                "decode", KERNELS)
    assert floor["value"] == pytest.approx(
        100 * mixtral_bytes(2) / 819e9 / 16.8e-3)
    assert "floor of any routing" in floor["note"]
    counted = trace_roofline.read(capture_ctx(cfg, dict(
        base, expert_reads_decode=43 * 12, expert_pairs_decode=54 * 12),
        [16.8e-3] * 3), "decode", KERNELS)
    assert counted["value"] == pytest.approx(
        100 * mixtral_bytes(4.3) / 819e9 / 16.8e-3)
    assert floor["value"] < counted["value"]
    # a prefill chunk: FLOPs of exactly top_k pairs a token, bytes of two
    pre = trace_roofline.read(capture_ctx(
        cfg, {"prefill_steps": 5, "prefill_tokens": 5 * 256},
        [0.05] * 2, "prefill"), "prefill", KERNELS)
    flops = 2 * 256 * 12 * (ATT + 2 * FFN + ROUTER) + 2 * HEAD
    assert "compute-bound" in pre["note"]
    assert pre["value"] == pytest.approx(100 * flops / 197e12 / 0.05)

    sarvam = config("sarvam-105b-ep8")
    shape = w.for_config(sarvam)
    got = trace_roofline.read(capture_ctx(sarvam, base, [20e-3] * 3),
                              "decode", KERNELS)
    none_held = shape.matmul_work(sarvam, 2.7, logit_rows=2.7,
                                  experts=0.0, pairs=0.0)
    assert got["value"] == pytest.approx(
        100 * none_held["bytes"] / 819e9 / 20e-3)
    got = trace_roofline.read(capture_ctx(sarvam, dict(
        base, expert_reads_decode=31 * 10 * 2, expert_pairs_decode=31 * 10 * 3),
        [20e-3] * 3), "decode", KERNELS)
    two = shape.matmul_work(sarvam, 2.7, logit_rows=2.7, experts=2.0,
                            pairs=3.0)
    assert shape.moe(sarvam)["layers"] == 31
    assert got["value"] == pytest.approx(100 * two["bytes"] / 819e9 / 20e-3)
    assert two["bytes"] - none_held["bytes"] == pytest.approx(
        31 * 2 * 3 * sarvam["moe_intermediate_size"] * sarvam["hidden_size"]
        * 18 / 32)


@pytest.mark.parametrize("name", ["mixtral-8x7b-12l", "sarvam-105b-ep8",
                                  "mistral-7b", "olmo-hybrid-7b"])
def test_no_reader_reaches_the_even_routing_expectation(name, monkeypatch):
    """With or without the program's expert counters, the reader never
    calls `experts_touched` nor lets a shape fall back to its expectation:
    `matmul_work` gets `experts=` and `pairs=` for every configuration with
    experts, and no reader's source names the expectation."""
    from readers import trace_roofline

    cfg = config(name)
    shape = w.for_config(cfg)
    monkeypatch.setattr(w, "experts_touched", lambda *a: 1 / 0)
    seen = []
    work = shape.matmul_work

    def spy(c, tokens, logit_rows=1.0, **kw):
        seen.append(kw)
        return work(c, tokens, logit_rows=logit_rows, **kw)

    monkeypatch.setattr(shape, "matmul_work", spy)
    for extra in ({}, {"expert_reads_decode": 400, "expert_pairs_decode": 500}):
        got = trace_roofline.read(capture_ctx(cfg, dict(
            {"decode_steps": 10, "decode_rows": 27}, **extra), [0.02] * 3),
            "decode", KERNELS)
        assert 0 < got["value"] < 100
    has_experts = getattr(shape, "moe", lambda c: None)(cfg) is not None
    assert has_experts == (name in ("mixtral-8x7b-12l", "sarvam-105b-ep8"))
    for kw in seen:
        assert (set(kw) == {"experts", "pairs"}) if has_experts else not kw
        assert all(v is not None for v in kw.values())
    readers = os.path.join(BENCH, "readers")
    for f in os.listdir(readers):
        if f.endswith(".py"):
            with open(os.path.join(readers, f)) as fh:
                code = fh.read().split('"""', 2)[-1]     # past the docstring
            assert "experts_touched" not in code and "p_held" not in code


@pytest.mark.parametrize("case", ["no capture", "no counters", "no steps",
                                  "no real token", "no kernel time",
                                  "another program"])
def test_the_reader_finds_nothing_to_read(case):
    from readers import trace_roofline

    cfg = config("mistral-7b")
    counters = {"decode_steps": 10, "decode_rows": 27}
    ctx = capture_ctx(cfg, counters, [4.9e-3] * 3)
    assert trace_roofline.read(ctx, "decode", KERNELS)["value"] == \
        pytest.approx(100 * 3_999_596_544 / 819e9 / 4.9e-3)
    if case == "no capture":
        ctx["stats"] = {"window_start": {"steps": 0, "tokens_out": 0},
                        "window_end": {"steps": 10, "tokens_out": 27}}
    elif case == "no counters":
        ctx = capture_ctx(cfg, {"decode_steps": 10}, [4.9e-3] * 3)
    elif case == "no steps":
        ctx = capture_ctx(cfg, {"decode_steps": 0, "decode_rows": 0},
                          [4.9e-3] * 3)
    elif case == "no real token":
        ctx = capture_ctx(cfg, {"decode_steps": 10, "decode_rows": 0},
                          [4.9e-3] * 3)
    elif case == "no kernel time":
        ctx = capture_ctx(cfg, counters, [0.0] * 3)
    else:
        assert trace_roofline.read(ctx, "prefill", KERNELS) is None
        return
    assert trace_roofline.read(ctx, "decode", KERNELS) is None


@pytest.mark.parametrize("program,tokens,dur_s", [("decode", 2.66, 15.5e-3),
                                                  ("prefill", 172.0, 75e-3)])
def test_the_step_mfu_is_the_matmul_flops_over_the_whole_programs_time(
        program, tokens, dur_s):
    """Beside each matmul roofline the whole step program's share of the
    bf16 peak: the same capture's mean work, over the program's own device
    time, kernels or not. Mixtral's top-2 pairs are arithmetic, so the
    floor's FLOPs are the count's; no execution of the program: None."""
    from readers import trace_step_mfu

    cfg = config("mixtral-8x7b-12l")
    counters = ({"decode_steps": 100, "decode_rows": 266}
                if program == "decode" else
                {"prefill_steps": 10, "prefill_tokens": 1720})
    ctx = capture_ctx(cfg, counters, [1e-3] * 4, program)
    for x in ctx["trace"]["executions"]:
        x["dur_s"] = dur_s
    flops = (2 * tokens * 12 * (ATT + 2 * FFN + ROUTER)
             + 2 * (tokens if program == "decode" else 1.0) * HEAD)
    got = trace_step_mfu.read(ctx, program)
    assert got["value"] == pytest.approx(100 * flops / 197e12 / dur_s)
    assert 0 < got["value"] < 100 and "4 executions" in got["note"]
    other = "prefill" if program == "decode" else "decode"
    assert trace_step_mfu.read(ctx, other) is None


def test_a_recipes_zero_rows_mute_the_end_of_sequence_logit(tmp_path):
    """`mixtral-8x7b-12l` mutes the head's row of token 2, the tokenizer's
    end-of-sequence (children.synth): the reference's logit there is exactly
    0 at every position, where the other logits are spread, so sampling ends
    no request early and every seed sends the same work."""
    import children
    from reference import mixtral

    c = dict(config("mixtral-8x7b-12l"), **TINY)
    assert c["weights_recipe"]["zero_rows"] == {"wcls": [2]}
    path = str(tmp_path / "m.m")
    children.synth({"config": c, "model": path,
                    "tokenizer": str(tmp_path / "t")})
    logits = mixtral.forward(path, np.arange(3, 19, dtype=np.int32))
    assert (logits[:, 2] == 0).all()
    assert (np.abs(logits[:, 3:]).max(-1) > 0.05).all()
    assert (logits[:, 1] != 0).all()
