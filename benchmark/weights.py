"""The benchmark's own draw of a configuration's weights: one `.m` file from
`weights_seed`, streamed in the file format's tensor order, one tensor
resident at a time.

Copied from the program's `testing.write_synthetic_model` (PR 37) so that
the draw is part of the yardstick and no later PR can move it: the same
generator, the same order of draws from it, so a configuration that names
no recipe gets the file, byte for byte, that the program's function wrote
for it (tests/test_reference.py holds the two together). What differs is
WHO decides the recipe: there the architecture alone, here also the
configuration's `"weights_recipe"`, an object of up to five keys:

  (absent)      the architecture's draw: zero-mean nibbles for SARVAM_MLA
                and OLMO_HYBRID, uniform bytes for LLAMA and MIXTRAL
  "zero_mean"   true: nibbles 1..15, so that a weight (nibble - 8) x scale
                has mean zero
  "scales"      [lo, hi]: the range the blocks' f16 scales are drawn from,
                in place of the architecture's
  "gains"       {suffix: factor}: the scales of every tensor whose name
                ends so are multiplied (a router that prefers: "moe_router";
                sublayers that each move a tenth of the stream: "wo", "down")
  "embedding_std"  the embedding's, in place of 0.02: what the sublayers'
                outputs are small or large beside
  "zero_rows"   {suffix: [row, ...]}: those rows of such a tensor get scale
                zero ("wcls": [2], the head's row of the tokenizer's
                end-of-sequence token: a logit of 0 beside logits of std ~4
                is never sampled, so no seed ends a request early and
                every seed sends the same work)

Uniform bytes give every matrix a mean of -0.5 x scale, a rank-one part
that outweighs the random part at published widths: the residual stream
collapses onto +-ones, every token gets the same router logits and every
token picks the same experts (at 4096 wide: one fixed pair a sign group,
PERF.md section 6, PR 37). A dense step's time does not depend on its
values, so `mistral-7b` keeps its bytes; a MoE step's does as soon as the
program reads only the experts its tokens chose, so `mixtral-8x7b-12l`
names "zero_mean".

Zero-mean nibbles alone are not enough at 4096 wide: beside a 0.02
embedding the stream is nothing but its sublayers' outputs, attention
scores have a std of ~12 at the architecture's scales and every router
near-tie swaps half a sublayer, so bf16 rounding grows to a relative error
of 1 within twelve layers and the check says nothing (PERF.md section 6,
PR 37, has the readings). Hence the other three keys: a unit embedding
beside which `wo` and `down` are turned down until a sublayer moves about a
tenth of the stream, scales at which attention scores have a std of ~2.5,
and a router whose logits are four times as far apart, so that the second
expert's weight is small where the second and third are close.

From the program this takes the file format alone (`model_tensor_plan`,
`write_header`): the layout of the system's input, not arithmetic.
"""

from __future__ import annotations

import os

import numpy as np

RECIPE_KEYS = {"zero_mean", "scales", "gains", "embedding_std", "zero_rows"}

# OLMO_HYBRID's draw (PERF.md section 6, PR 34): a unit embedding, output
# gains of a tenth, q / k gains of 4.5, small decay and beta rows
HYBRID_EMBEDDING_STD = 1.0
HYBRID_NORM_GAINS = {"rms_att": 0.1, "rms_ffn": 0.1,
                     "rms_q": 4.5, "rms_k": 4.5}
HYBRID_DECAY_ROWS_SCALE = 0.1


def write_model(path: str, spec, seed: int, recipe: dict | None = None) -> int:
    """Write the file; returns its size in bytes. Q40 blocks get f16 scales
    in [0.005, 0.02] (SARVAM_MLA: [0.0035, 0.008], where attention scores
    have a std of ~3; a recipe's `scales` and `gains` otherwise) and nibbles
    by the recipe; f32 tensors small gaussians (norm weights near 1)."""
    from distributed_llama_tpu.io.model_file import (model_tensor_plan,
                                                     write_header)
    from distributed_llama_tpu.models.spec import ArchType
    from distributed_llama_tpu.quants.types import (BLOCK_SIZE,
                                                    Q40_BLOCK_BYTES, FloatType)

    recipe = recipe or {}
    if not isinstance(recipe, dict) or set(recipe) - RECIPE_KEYS:
        raise KeyError(f"weights_recipe {recipe!r}: an object with keys "
                       f"among {sorted(RECIPE_KEYS)}")
    rng = np.random.default_rng(seed)
    hybrid = spec.arch == ArchType.OLMO_HYBRID
    zero_mean = spec.is_mla or hybrid or bool(recipe.get("zero_mean"))
    scale_range = tuple(recipe.get("scales") or (
        (0.0035, 0.008) if spec.is_mla else (0.005, 0.02)))
    with open(path, "wb") as f:
        write_header(f, spec)
        for name, shape, ftype in model_tensor_plan(spec):
            n = int(np.prod(shape))
            if ftype == FloatType.F32:
                x = rng.standard_normal(n, dtype=np.float32) * 0.02
                if hybrid and name == "tok_emb":
                    x *= HYBRID_EMBEDDING_STD / 0.02
                elif name == "tok_emb":
                    x *= recipe.get("embedding_std", 0.02) / 0.02
                if "rms" in name:
                    x += 1.0
                    if hybrid:
                        x *= HYBRID_NORM_GAINS.get(name.split(".")[-1], 1.0)
                elif name.endswith("a_log"):
                    # the published initialisation: A uniform in (0, 16)
                    x = np.log(rng.uniform(1e-3, 16.0, n)).astype(np.float32)
                elif name.endswith("dt_bias"):
                    # dt log-uniform in [0.001, 0.1], through the inverse
                    # of softplus
                    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), n))
                    x = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
                elif name.endswith("conv_w"):
                    x = rng.uniform(-0.5, 0.5, n).astype(np.float32)
                elif name.endswith("moe_bias"):
                    # a router with preferences: std 0.5 beside scores in
                    # (0, 1) (PERF.md section 6, PR 30)
                    x *= 25.0
                f.write(x.tobytes())
                continue
            assert ftype == FloatType.Q40, ftype
            nb = n // BLOCK_SIZE
            raw = np.empty((nb, Q40_BLOCK_BYTES), np.uint8)
            scales = rng.uniform(*scale_range, nb)
            if hybrid and name.endswith((".wa", ".wb")):
                scales *= HYBRID_DECAY_ROWS_SCALE
            for suffix, gain in recipe.get("gains", {}).items():
                if name.endswith(suffix):
                    scales *= gain
            for suffix, rows in recipe.get("zero_rows", {}).items():
                if name.endswith(suffix):
                    scales.reshape(shape[0], -1)[rows] = 0.0
            raw[:, :2] = scales.astype(np.float16).reshape(nb, 1).view(np.uint8)
            if zero_mean:
                lo, hi = (rng.integers(1, 16, (nb, Q40_BLOCK_BYTES - 2),
                                       dtype=np.uint8) for _ in "lh")
                raw[:, 2:] = lo | (hi << 4)
            else:
                raw[:, 2:] = rng.integers(
                    0, 256, (nb, Q40_BLOCK_BYTES - 2), dtype=np.uint8)
            f.write(raw.tobytes())
    return os.path.getsize(path)
