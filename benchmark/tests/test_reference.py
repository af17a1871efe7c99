"""The two plain references against the repo's own numpy oracle
(tests/reference_oracle.py) at tiny size, and the block decode against the
definition."""

import numpy as np
import pytest

from reference import blocks, llama, mixtral


@pytest.mark.parametrize("arch,kw", [("LLAMA", {}),
                                     ("MIXTRAL", dict(n_experts=4,
                                                      n_active_experts=2))])
def test_reference_equals_the_oracle(tmp_path, arch, kw):
    import reference_oracle
    from distributed_llama_tpu.io.model_file import read_model
    from distributed_llama_tpu.models.spec import ArchType
    from distributed_llama_tpu.testing import (tiny_spec,
                                               write_synthetic_model)

    spec = tiny_spec(arch=ArchType[arch], rope_theta=1e6, **kw)
    path = str(tmp_path / "m.m")
    write_synthetic_model(path, spec, 5)
    spec2, tensors = read_model(path)
    oracle = reference_oracle.Oracle(
        spec2, {k: t.to_f32() for k, t in tensors.items()})
    toks = np.random.default_rng(1).integers(3, spec.vocab_size, 12)
    toks = toks.astype(np.int32)
    want = np.stack([oracle.step(int(t), i) for i, t in enumerate(toks)])
    got = (llama if arch == "LLAMA" else mixtral).forward(path, toks)
    assert got.shape == want.shape
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5


def test_q40_block_decode_is_the_definition():
    scale = np.float16(0.5)
    nibbles = np.arange(16, dtype=np.uint8) | (
        (15 - np.arange(16, dtype=np.uint8)) << 4)
    raw = np.concatenate([np.array([scale]).view(np.uint8), nibbles])
    got = np.asarray(blocks.decode_q40(raw, (32,)))
    low = (np.arange(16) - 8) * 0.5               # values 0..15
    high = ((15 - np.arange(16)) - 8) * 0.5       # values 16..31
    assert got.tolist() == np.concatenate([low, high]).tolist()


def test_an_unlisted_header_key_is_kept_and_a_subclass_can_name_it(tmp_path):
    """A file of a new architecture carries header keys this table does
    not list: the reader keeps them under their numbers, finds every tensor
    where it was, and a reference's own subclass names them."""
    import struct

    from distributed_llama_tpu.testing import tiny_spec, write_synthetic_model

    plain, extra = str(tmp_path / "m.m"), str(tmp_path / "x.m")
    write_synthetic_model(plain, tiny_spec(), 5)
    with open(plain, "rb") as f:
        magic, size = struct.unpack("<ii", f.read(8))
        header, tensors = f.read(size - 8), f.read()
    with open(extra, "wb") as f:
        f.write(struct.pack("<ii", magic, size + 8) + header
                + struct.pack("<ii", 99, 7) + tensors)

    mf = blocks.ModelFile(extra)
    assert mf.h[99] == 7 and mf.h["dim"] == blocks.ModelFile(plain).h["dim"]
    toks = np.arange(3, 11, dtype=np.int32)
    assert (llama.forward(extra, toks) == llama.forward(plain, toks)).all()

    class Wider(blocks.ModelFile):
        KEYS = {**blocks.ModelFile.KEYS, 99: "head_dim"}

        def _plan(self):
            yield from list(super()._plan())[:1]   # its own tensor order

    wider = Wider(extra)
    assert wider.h["head_dim"] == 7 and 99 not in wider.h
    assert list(wider.offsets) == ["tok_emb"]


def test_the_zero_mean_draw_routes_to_every_expert_and_uniform_bytes_do_not(
        tmp_path):
    """A 1024-wide MIXTRAL of 8 experts top-2 over one prompt of 64 tokens,
    counted from the reference's own `top_i`: uniform nibble bytes give
    every matrix a rank-one mean, the stream collapses and from the third
    layer on the tokens share five experts (four at 2048 wide, one fixed
    pair a sign group at 4096: PERF.md section 6, PR 37); nibbles 1..15
    touch all eight in every layer, each within reach of an even share."""
    import weights
    from distributed_llama_tpu.models.spec import ArchType
    from distributed_llama_tpu.testing import tiny_spec

    spec = tiny_spec(arch=ArchType.MIXTRAL, n_experts=8, n_active_experts=2,
                     rope_theta=1e6, dim=1024, hidden_dim=2048, n_layers=4,
                     n_heads=8, n_kv_heads=8, seq_len=128)
    toks = np.random.default_rng(1).integers(3, spec.vocab_size, 64)
    path = str(tmp_path / "m.m")
    touched = {}
    for recipe in (None, "zero_mean"):
        weights.write_model(path, spec, 20240925,
                            recipe and {"zero_mean": True})
        routing = []
        logits = mixtral.forward(path, toks.astype(np.int32), routing=routing)
        assert len(routing) == 4 and routing[0]["top_i"].shape == (64, 2)
        assert all((r["margin"] >= 0).all() for r in routing)
        assert (logits == mixtral.forward(path, toks.astype(np.int32))).all()
        touched[recipe] = [len(np.unique(r["top_i"])) for r in routing]
        counts = np.bincount(routing[-1]["top_i"].ravel(), minlength=8)
        assert counts.sum() == 128
        if recipe:
            assert counts.min() >= 4 and counts.max() <= 32
    assert touched["zero_mean"] == [8, 8, 8, 8]
    assert touched[None][2:] == [5, 5]


def test_the_controls_of_a_zero_mean_mixtral_fail_the_check_at_tiny_size(
        tmp_path):
    """controls.py at a size a test can hold, through children.check as the
    harness calls it (the served step programs, here in float32 on the CPU):
    the path as served agrees with the reference, an fp8 cache and a router
    that takes every token's experts one place down do not. The readings
    `logit_tolerance` stands between are the chip's, at the cell's size
    (PERF.md section 6, PR 37)."""
    import json
    import os

    import children
    import controls

    with open(os.path.join(controls.HERE, "configs",
                           "mixtral-8x7b-12l.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=256, intermediate_size=512, num_hidden_layers=3,
               num_attention_heads=8, num_key_value_heads=4, vocab_size=512,
               max_position_embeddings=256, weights_seed=11)
    cfg["server"] = dict(cfg["server"], serve_batch=4, max_seq_len=256,
                         prefix_blocks=8)
    # a sixteenth of the width: scales four times as large keep every
    # projection's gain, and with it what an fp8 cache does to the scores
    recipe = cfg["weights_recipe"]
    cfg["weights_recipe"] = dict(recipe,
                                 scales=[4 * s for s in recipe["scales"]])
    # float32 serves this size to 1e-5 of the reference: the limits stand a
    # hundred times over that floor here, as the chip's stand over its own
    cfg["logit_tolerance"] = 1e-3
    cfg["check"] = dict(cfg["check"], worst_tolerance=1e-2)
    model, tok = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    children.synth({"config": cfg, "model": model, "tokenizer": tok})
    out, routing = controls.readings(
        cfg, model, tok, seeds=2, control_seeds=1,
        engine_flags=["--compute-dtype", "f32", "--cache-dtype", "f32",
                      "--buffer-float-type", "f32"])
    assert set(out) == {"served", "cache_fp8", "router_next_best"}
    served = [v["worst_rel_l2"] for v in out["served"].values()]
    assert len(served) == 2 and max(served) < 1e-4
    assert all(v["ok"] for v in out["served"].values())
    for name in ("cache_fp8", "router_next_best"):
        (v,) = out[name].values()
        assert not v["ok"] and v["median_rel_l2"] > cfg["logit_tolerance"]
    assert sorted(routing) == [12, 13]
    assert np.stack([r["top_i"] for r in routing[12]]).shape == (3, 104, 2)
