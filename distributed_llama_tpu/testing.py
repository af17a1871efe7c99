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
from .models import ArchType, HiddenAct, LayerKind, ModelSpec
from .quants import FloatType


def tiny_spec(weights_float_type: FloatType = FloatType.Q40,
              **overrides) -> ModelSpec:
    base = dict(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, vocab_size=288, seq_len=160, hidden_act=HiddenAct.SILU,
        weights_float_type=weights_float_type)
    base.update(overrides)
    return ModelSpec(**base)


def tiny_mla_spec(weights_float_type: FloatType = FloatType.Q40,
                  **overrides) -> ModelSpec:
    """SARVAM_MLA at a size a CPU holds: 4 heads over a 32-wide latent, one
    leading dense layer, then layers of 4 held experts (of 8 routed over,
    top 4) and a shared expert; yarn past an original context of 32."""
    base = dict(
        arch=ArchType.SARVAM_MLA, dim=64, hidden_dim=32, n_layers=3,
        n_heads=4, n_kv_heads=1, vocab_size=288, seq_len=160,
        hidden_act=HiddenAct.SILU, n_experts=4, n_active_experts=4,
        weights_float_type=weights_float_type,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_dense_layers=1, dense_hidden_dim=128,
        n_shared_experts=1, n_routed_experts=8, expert_offset=0,
        routed_scaling=2.5, rms_eps=1e-6, rope_factor=40.0, rope_orig_len=32,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0)
    base.update(overrides)
    return ModelSpec(**base)


def tiny_hybrid_spec(weights_float_type: FloatType = FloatType.Q40,
                     **overrides) -> ModelSpec:
    """OLMO_HYBRID at a size a CPU holds: two periods of (DELTA x 3,
    ATTENTION), 2 delta heads of 32 / 64 over a 4-tap convolution, 4
    attention heads without rotation, beta in (0, 2)."""
    period = (LayerKind.DELTA,) * 3 + (LayerKind.ATTENTION,)
    base = dict(
        arch=ArchType.OLMO_HYBRID, dim=64, hidden_dim=128, n_layers=8,
        n_heads=4, n_kv_heads=4, vocab_size=288, seq_len=160,
        hidden_act=HiddenAct.SILU, rope_theta=0.0,
        weights_float_type=weights_float_type, rms_eps=1e-6,
        mixers=tuple(int(k) for k in period * 2), lin_heads=2,
        lin_k_head_dim=32, lin_v_head_dim=64, lin_conv_width=4,
        lin_beta_scale=2)
    base.update(overrides)
    return ModelSpec(**base)


def tiny_granite_spec(weights_float_type: FloatType = FloatType.Q40,
                      **overrides) -> ModelSpec:
    """GRANITE_HYBRID at a size a CPU holds: two periods of (SSM x 2,
    ATTENTION), 8 state-space heads of 16 over a state of 32 and a 4-tap
    convolution with a bias, 4 query / 2 KV heads without rotation, in
    every layer 4 held experts (of 8 routed over, top 4) and a shared
    expert of twice an expert's width, the four multipliers away from 1."""
    period = (LayerKind.SSM,) * 2 + (LayerKind.ATTENTION,)
    base = dict(
        arch=ArchType.GRANITE_HYBRID, dim=64, hidden_dim=32, n_layers=6,
        n_heads=4, n_kv_heads=2, vocab_size=288, seq_len=160,
        hidden_act=HiddenAct.SILU, rope_theta=0.0, n_experts=4,
        n_active_experts=4, weights_float_type=weights_float_type,
        n_shared_experts=2, n_routed_experts=8, expert_offset=0,
        rms_eps=1e-5, mixers=tuple(int(k) for k in period * 2),
        ssm_heads=8, ssm_head_dim=16, ssm_d_state=32, ssm_groups=1,
        ssm_conv_width=4, ssm_conv_bias=1, embedding_scale=12.0,
        residual_scale=0.22, attn_scale=0.0625, logit_scale=0.0625)
    base.update(overrides)
    return ModelSpec(**base)


def tiny_kimi_spec(weights_float_type: FloatType = FloatType.Q40,
                   **overrides) -> ModelSpec:
    """KIMI_LINEAR at a size a CPU holds: a period of (DELTA x 3, LATENT)
    and the published tail (DELTA x 2, LATENT), 2 KDA heads of 32 / 32 with
    a decay a key channel over a 4-tap convolution, 4 latent-attention
    heads over a 32-wide latent WITHOUT rotation, a leading dense layer,
    then in every layer 4 held experts (of 8 routed over, top 4) and a
    shared expert."""
    kinds = ((LayerKind.DELTA,) * 3 + (LayerKind.LATENT,)
             + (LayerKind.DELTA,) * 2 + (LayerKind.LATENT,))
    base = dict(
        arch=ArchType.KIMI_LINEAR, dim=64, hidden_dim=32, n_layers=7,
        n_heads=4, n_kv_heads=1, vocab_size=288, seq_len=160,
        hidden_act=HiddenAct.SILU, rope_theta=0.0, n_experts=4,
        n_active_experts=4, weights_float_type=weights_float_type,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_dense_layers=1, dense_hidden_dim=128,
        n_shared_experts=1, n_routed_experts=8, expert_offset=0,
        routed_scaling=2.446, rms_eps=1e-5,
        mixers=tuple(int(k) for k in kinds), lin_heads=2,
        lin_k_head_dim=32, lin_v_head_dim=32, lin_conv_width=4,
        lin_beta_scale=1, lin_decay_dim=32)
    base.update(overrides)
    return ModelSpec(**base)


def tiny_jamba_spec(weights_float_type: FloatType = FloatType.Q40,
                    **overrides) -> ModelSpec:
    """JAMBA at a size a CPU holds: two periods of (SSM x 2, ATTENTION, SSM)
    whose SSM layers are selective scans (Mamba-1) over 128 channels, every
    channel a head of its own, with the state, the step's rank and the
    convolution at their PUBLISHED sizes (16, 160, 4 taps with a bias); 4
    query heads on ONE KV head without rotation; a dense SwiGLU in every
    layer."""
    period = (LayerKind.SSM,) * 2 + (LayerKind.ATTENTION, LayerKind.SSM)
    base = dict(
        arch=ArchType.JAMBA, dim=64, hidden_dim=128, n_layers=8, n_heads=4,
        n_kv_heads=1, vocab_size=288, seq_len=160, hidden_act=HiddenAct.SILU,
        rope_theta=0.0, weights_float_type=weights_float_type, rms_eps=1e-6,
        mixers=tuple(int(k) for k in period * 2), ssm_heads=128,
        ssm_head_dim=1, ssm_d_state=16, ssm_groups=1, ssm_conv_width=4,
        ssm_conv_bias=1, ssm_dt_rank=160)
    base.update(overrides)
    return ModelSpec(**base)


def free_port() -> int:
    """An OS-assigned free TCP port (shared by the cluster tests and the
    chaos harness spawners — one home for the bind-port-0 idiom)."""
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


# OLMO_HYBRID's synthetic draw, chosen so that a check on the logits can
# see precision (PERF.md section 6, PR 34 after review, has every draw read
# on the chip). Its norms sit on each sublayer's OUTPUT: with unit gains on
# a 0.02 embedding every sublayer REPLACES a sixty-fourth of the stream and
# the served path's own bf16 / Q80 rounding reads 0.13 at the logits,
# hiding whatever a lower precision adds. A unit embedding and output
# gains of a tenth (each sublayer moves the stream by about a tenth of its
# norm, as a trained block does) put that floor at 0.03-0.05. q / k gains
# of 4.5 in the full-attention layers give scores a std of ~20 (4.5^2:
# peaked heads, as trained ones are): at a std of 1 over 2100 positions
# softmax is a near-uniform average that forgives its terms, and fp8 rows
# read as bf16 ones. The decay and step rows keep a and b at a std of ~0.4
# on that stream.
HYBRID_EMBEDDING_STD = 1.0
HYBRID_NORM_GAINS = {"rms_att": 0.1, "rms_ffn": 0.1,
                     "rms_q": 4.5, "rms_k": 4.5}
HYBRID_DECAY_ROWS_SCALE = 0.1


def write_synthetic_model(path: str, spec: ModelSpec, seed: int) -> int:
    """Stream random-but-valid tensors to `path` in exact plan order, one
    tensor resident at a time: Q40 blocks get f16 scales in [0.005, 0.02]
    + uniform nibble bytes; f32 tensors small gaussians (norm weights near
    1). Returns the file size in bytes."""
    import os

    from .io.model_file import write_header
    from .quants.types import BLOCK_SIZE, Q40_BLOCK_BYTES

    rng = np.random.default_rng(seed)
    hybrid = spec.arch == ArchType.OLMO_HYBRID
    granite = spec.arch == ArchType.GRANITE_HYBRID
    zero_mean = spec.is_mla or hybrid or granite
    with open(path, "wb") as f:
        write_header(f, spec)
        for name, shape, ftype in model_tensor_plan(spec):
            n = int(np.prod(shape))
            if ftype == FloatType.F32:
                x = rng.standard_normal(n, dtype=np.float32) * 0.02
                if hybrid and name == "tok_emb":
                    x *= HYBRID_EMBEDDING_STD / 0.02
                if "rms" in name:
                    x += 1.0
                    if hybrid:
                        x *= HYBRID_NORM_GAINS.get(name.split(".")[-1], 1.0)
                elif name.endswith("a_log"):
                    # the published initialisation of the layer: A uniform
                    # in (0, 16), so that with dt below heads forget over a
                    # few tokens or over thousands
                    x = np.log(rng.uniform(1e-3, 16.0, n)).astype(np.float32)
                elif name.endswith("dt_bias"):
                    # dt log-uniform in [0.001, 0.1], stored through the
                    # inverse of softplus
                    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), n))
                    x = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
                elif name.endswith("conv_w"):
                    # a depthwise convolution's default: uniform within
                    # 1 / sqrt(taps)
                    x = rng.uniform(-0.5, 0.5, n).astype(np.float32)
                elif granite and name.endswith("ssm_d"):
                    # the skip term's weight, initialised to ones as
                    # published (conv_b keeps the small gaussian). JAMBA's
                    # keeps the gaussian too: its file is the benchmark's
                    # draw byte for byte (benchmark/weights.py has no rule
                    # for ssm_d), and its tests draw D themselves
                    x += 1.0
                elif name.endswith("moe_bias"):
                    # a router with preferences (std 0.5 beside scores in
                    # (0, 1)): with a bias of 0.02 every token's eighth
                    # and ninth expert are a near-tie, rounding sends
                    # thousands of tokens to other experts than the
                    # reference's, and the logits agree to 0.3 whatever
                    # the precision (PERF.md section 6, PR 30)
                    x *= 25.0
                f.write(x.tobytes())
            elif ftype == FloatType.Q40:
                nb = n // BLOCK_SIZE
                raw = np.empty((nb, Q40_BLOCK_BYTES), np.uint8)
                # SARVAM_MLA: scales at which attention scores have a
                # std of ~3 over 4096 columns, where a check on the logits
                # tells an fp8 cache from a bf16 one (below)
                scales = rng.uniform(
                    *((0.0035, 0.008) if spec.is_mla else (0.005, 0.02)),
                    nb)
                if ((hybrid and name.endswith((".wa", ".wb")))
                        or (granite and name.endswith(".wdt"))):
                    # decay and beta rows (the SSM layer's dt rows): small,
                    # so that the drawn a_log and dt_bias set a head's
                    # memory and beta stays inside (0, 2) (at the other
                    # matrices' scale both saturate on every token)
                    scales *= HYBRID_DECAY_ROWS_SCALE
                scales = scales.astype(np.float16)
                raw[:, :2] = scales.reshape(nb, 1).view(np.uint8)
                if zero_mean:
                    # nibbles 1..15, so that a weight (nibble - 8) x scale
                    # has mean ZERO. Uniform bytes (below) give every
                    # matrix a mean of -0.5 x scale, a rank-one part that
                    # outweighs the random part at these widths: the
                    # residual stream collapses onto +-ones, the logits
                    # hardly depend on the prompt, and a check on them is
                    # blind (an fp8 cache and a missing router bias both
                    # passed; PERF.md section 6, PR 30, has the readings
                    # of every scale range and bias tried). The older
                    # architectures keep their bytes: their files, hashes
                    # and verdicts are recorded.
                    lo, hi = (rng.integers(1, 16, (nb, Q40_BLOCK_BYTES - 2),
                                           dtype=np.uint8) for _ in "lh")
                    raw[:, 2:] = lo | (hi << 4)
                    f.write(raw.tobytes())
                    continue
                raw[:, 2:] = rng.integers(
                    0, 256, (nb, Q40_BLOCK_BYTES - 2), dtype=np.uint8)
                f.write(raw.tobytes())
            else:
                raise AssertionError(ftype)
    return os.path.getsize(path)
