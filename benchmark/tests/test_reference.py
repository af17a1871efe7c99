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
