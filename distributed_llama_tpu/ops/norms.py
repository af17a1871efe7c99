"""RMS normalization.

Same math as the reference (ref: src/funcs.cpp:94-145): inv = 1/sqrt(mean(x^2)
+ 1e-5), o = w * (inv * x). The 1e-5 epsilon is added AFTER the mean, matching
the reference exactly. Computed in f32 regardless of the activation dtype —
the reference keeps the residual stream f32 too.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-5


def rms_inv(x: jnp.ndarray, eps: float = RMS_EPS) -> jnp.ndarray:
    """1/rms over the last axis, keepdims. (ref: src/funcs.cpp:94-123)"""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return lax.rsqrt(ms + eps)


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray,
            eps: float = RMS_EPS) -> jnp.ndarray:
    """o = weight * (x / rms(x)) in f32, cast back to x.dtype; `eps` is the
    model's own where its header states one (ModelSpec.norm_eps).

    (ref: src/funcs.cpp:125-145)
    """
    xf = x.astype(jnp.float32)
    out = weight.astype(jnp.float32) * (rms_inv(xf, eps) * xf)
    return out.astype(x.dtype)
