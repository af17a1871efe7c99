"""Rotary position embeddings, both reference styles.

* `rope_llama` — interleaved adjacent-pair rotation over the flat q/k vector
  with frequency exponent (i % head_size)/head_size (ref:
  src/transformer.cpp:98-135 LlamaRopeSlice). Used by LLAMA-arch models;
  the HF converter permutes q/k weights into this layout
  (ref: converter/convert-hf.py:12-15).

* `rope_falcon` — half-rotation within each head: element j pairs with
  j + head_size/2 (ref: src/transformer.cpp:137-159 FalconRopeSlice).
  Used by GROK1/MIXTRAL-arch models.

Angles are computed on the fly (a table is a trace-time constant under jit;
XLA hoists it), in f32. Functions take x shaped (..., n_heads, head_size)
and positions shaped (...-batch,) broadcastable.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..models.spec import ArchType


def _angles(pos: jnp.ndarray, head_size: int, theta: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin of pos * theta^(-2j/head_size) for j in [0, head_size/2).

    pos: (...,) -> returns (..., head_size/2) each.
    """
    j = jnp.arange(head_size // 2, dtype=jnp.float32)
    freq = 1.0 / jnp.power(jnp.float32(theta), 2.0 * j / head_size)
    val = pos.astype(jnp.float32)[..., None] * freq
    return jnp.cos(val), jnp.sin(val)


def rope_llama(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Interleaved rotation: pairs (2j, 2j+1) within each head.

    x: (..., H, hs); pos broadcastable to x.shape[:-2].
    """
    *lead, h, hs = x.shape
    fcr, fci = _angles(pos, hs, theta)  # (..., hs/2)
    fcr = fcr[..., None, :]
    fci = fci[..., None, :]
    xf = x.astype(jnp.float32).reshape(*lead, h, hs // 2, 2)
    x0 = xf[..., 0]
    x1 = xf[..., 1]
    r0 = x0 * fcr - x1 * fci
    r1 = x0 * fci + x1 * fcr
    return jnp.stack([r0, r1], axis=-1).reshape(*lead, h, hs).astype(x.dtype)


def rope_falcon(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Half-rotation: element j pairs with j + hs/2 within each head."""
    *lead, h, hs = x.shape
    fcr, fci = _angles(pos, hs, theta)
    fcr = fcr[..., None, :]
    fci = fci[..., None, :]
    xf = x.astype(jnp.float32)
    x0 = xf[..., : hs // 2]
    x1 = xf[..., hs // 2:]
    r0 = x0 * fcr - x1 * fci
    r1 = x0 * fci + x1 * fcr
    return jnp.concatenate([r0, r1], axis=-1).astype(x.dtype)


def yarn_mscale(factor: float, m: float) -> float:
    """DeepSeek yarn's attention factor for a context scaled by `factor`."""
    import math

    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_inv_freq(head_size: int, theta: float, factor: float, orig_len: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """DeepSeek yarn's per-pair frequencies: the plain ones where a pair
    turns more than beta_fast times over the original context, the plain
    ones divided by `factor` where it turns fewer than beta_slow times, a
    linear blend between; all plain at factor 1."""
    import math

    j = jnp.arange(head_size // 2, dtype=jnp.float32)
    plain = 1.0 / jnp.power(jnp.float32(theta), 2.0 * j / head_size)
    if factor <= 1.0:
        return plain

    def turn_dim(turns: float) -> float:
        return (head_size * math.log(orig_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turn_dim(beta_fast)), 0)
    high = min(math.ceil(turn_dim(beta_slow)), head_size - 1)
    ramp = jnp.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_yarn(x: jnp.ndarray, pos: jnp.ndarray, spec) -> jnp.ndarray:
    """Rope on a (..., H, d_r) slice of a head, DeepSeek-V2's way: pairs
    (2j, 2j+1) turn together and come out de-interleaved (all first
    elements, then all second). cos/sin carry yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim); spec.rope_factor == 1 is plain
    rope in the same pairing."""
    *lead, h, hs = x.shape
    inv = yarn_inv_freq(hs, spec.rope_theta, spec.rope_factor,
                        spec.rope_orig_len, spec.rope_beta_fast,
                        spec.rope_beta_slow)
    amp = (yarn_mscale(spec.rope_factor, spec.rope_mscale)
           / yarn_mscale(spec.rope_factor, spec.rope_mscale_all_dim))
    val = pos.astype(jnp.float32)[..., None, None] * inv
    c, s = jnp.cos(val) * amp, jnp.sin(val) * amp
    xf = x.astype(jnp.float32).reshape(*lead, h, hs // 2, 2)
    x0, x1 = xf[..., 0], xf[..., 1]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c],
                           axis=-1).astype(x.dtype)


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float, arch: ArchType) -> jnp.ndarray:
    """Arch dispatch (ref: src/transformer.cpp:391-395)."""
    if arch == ArchType.LLAMA:
        return rope_llama(x, pos, theta)
    return rope_falcon(x, pos, theta)
