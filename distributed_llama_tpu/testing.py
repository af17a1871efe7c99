"""Tiny fixture model/tokenizer writers shared by tests and examples.

One place for the end-to-end fixture the suite uses everywhere: a small
random-weight Llama spec written to a real `.m` file plus a llama2.c-style
byte-fallback tokenizer `.t` (vocab 288 = 3 specials + 256 byte tokens +
fillers; byte b maps to token b+3), so CLI/API/cluster paths exercise the
same file formats the reference consumes. `write_synthetic_model` streams
a random-but-valid `.m` of ANY size (chip_smoke.py's 7B file,
tools/rehearse_70b.py's 70B-width one) without holding it in memory.
"""

from __future__ import annotations

import numpy as np

from .io import (TokenizerData, model_tensor_plan, write_model,
                 write_tokenizer_file)
from .models import ArchType, HiddenAct, ModelSpec
from .quants import FloatType


def tiny_spec(weights_float_type: FloatType = FloatType.Q40,
              **overrides) -> ModelSpec:
    base = dict(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, vocab_size=288, seq_len=160, hidden_act=HiddenAct.SILU,
        weights_float_type=weights_float_type)
    base.update(overrides)
    return ModelSpec(**base)


def free_port() -> int:
    """An OS-assigned free TCP port (shared by the cluster tests, the
    chaos harness spawners, and bench's cluster row — one home for the
    bind-port-0 idiom)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def byte_fallback_vocab(vocab_size: int) -> list[bytes]:
    vocab = [b"<unk>", b"<s>", b"</s>"]
    vocab += [f"<0x{b:02X}>".encode() for b in range(256)]
    vocab += [f"<fill{i}>".encode() for i in range(len(vocab), vocab_size)]
    return vocab


def write_fixture(dirpath, seed: int = 77, rng=None,
                  spec: ModelSpec | None = None,
                  **spec_overrides) -> tuple[str, str]:
    """Write model.m + tok.t under dirpath; returns their paths.

    Weights are `rng.standard_normal * 0.05` from `rng` (or a fresh
    default_rng(seed)) in plan order — tests that pin golden outputs must
    keep their seed/spec stable.
    """
    if spec is None:
        spec = tiny_spec(**spec_overrides)
    if rng is None:
        rng = np.random.default_rng(seed)
    tensors = {name: rng.standard_normal(shape).astype(np.float32) * 0.05
               for name, shape, _ in model_tensor_plan(spec)}
    mpath = f"{dirpath}/model.m"
    write_model(mpath, spec, tensors)
    tpath = f"{dirpath}/tok.t"
    write_tokenizer_file(tpath, TokenizerData(
        vocab=byte_fallback_vocab(spec.vocab_size),
        scores=[0.0] * spec.vocab_size, bos_id=1, eos_id=2))
    return mpath, tpath


def write_synthetic_model(path: str, spec: ModelSpec, seed: int) -> int:
    """Stream random-but-valid tensors to `path` in exact plan order, one
    tensor resident at a time: Q40 blocks get f16 scales in [0.005, 0.02]
    + uniform nibble bytes; f32 tensors small gaussians (norm weights near
    1). Returns the file size in bytes."""
    import os

    from .io.model_file import write_header
    from .quants.types import BLOCK_SIZE, Q40_BLOCK_BYTES

    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        write_header(f, spec)
        for name, shape, ftype in model_tensor_plan(spec):
            n = int(np.prod(shape))
            if ftype == FloatType.F32:
                x = rng.standard_normal(n, dtype=np.float32) * 0.02
                if "rms" in name:
                    x += 1.0
                f.write(x.tobytes())
            elif ftype == FloatType.Q40:
                nb = n // BLOCK_SIZE
                raw = np.empty((nb, Q40_BLOCK_BYTES), np.uint8)
                scales = rng.uniform(0.005, 0.02, nb).astype(np.float16)
                raw[:, :2] = scales.reshape(nb, 1).view(np.uint8)
                raw[:, 2:] = rng.integers(
                    0, 256, (nb, Q40_BLOCK_BYTES - 2), dtype=np.uint8)
                f.write(raw.tobytes())
            else:
                raise AssertionError(ftype)
    return os.path.getsize(path)
