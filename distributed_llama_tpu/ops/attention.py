"""Attention over a pre-filled KV cache.

TPU-native replacement for the reference's serial per-head loop
(ref: src/llama2-tasks.cpp:54-94): one masked `dot_general` pair that XLA
tiles onto the MXU, with GQA handled by reshaping query heads into
(kv_head, group) blocks instead of the reference's `h / kvMul` indexing.

Numerics match the reference: scores = q·k / sqrt(head_size), softmax with
max-subtraction over positions t <= pos, f32 accumulation.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def is_narrow_cache(dtype) -> bool:
    """True for sub-bf16 KV-cache dtypes (the fp8 option). The contract:
    writes saturate then narrow (models/transformer._to_cache_dtype), reads
    upcast k/v at the dot operand so q and the softmax state never drop
    below the compute dtype (here and in ops/pallas_attention.py)."""
    return jnp.dtype(dtype).itemsize < 2


def decode_attention(
    q: jnp.ndarray,        # (B, T, H, hs) — rotated queries
    k_cache: jnp.ndarray,  # (B, KVH, S, hs) — cache already updated at query positions
    v_cache: jnp.ndarray,  # (B, KVH, S, hs)
    q_pos: jnp.ndarray,    # (B, T) absolute position of each query token
    scale: float | None = None,
) -> jnp.ndarray:
    """Causal attention of T query tokens against the full cache.

    Works for decode (T=1) and chunked prefill (T>1). Returns (B, T, H, hs)
    (the last dim is v_cache's where that differs from the key's: latent
    attention hands in its one leaf as keys and its leading columns as
    values, with its own `scale`).
    """
    b, t, h, hs = q.shape
    kvh = k_cache.shape[1]
    s = k_cache.shape[2]
    group = h // kvh  # ref kvMul: src/llama2-tasks.cpp:60

    # keep k/v in their cache dtype: upcasting the whole cache to f32 would
    # materialize 2x f32 copies in HBM (measured 6.7 -> 1.6 ms/token for
    # 32 layers @ seq 2048 on v5e after this change); the MXU accumulates
    # bf16 contractions in f32 natively via preferred_element_type. The
    # cache is head-major (see models/transformer.KVCache) so each head's
    # (S, hs) panel reads sequentially. Sub-bf16 caches (the fp8 option —
    # half the cache bytes) upcast at the dot operand, where XLA fuses the
    # convert into the read; q/probs never narrow below the compute dtype.
    if is_narrow_cache(k_cache.dtype):
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
    qg = q.reshape(b, t, kvh, group, hs)

    # scores: (B, T, KVH, G, S)
    scores = jnp.einsum("btkgh,bksh->btkgs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(jnp.float32(hs))
    else:
        scores = scores * jnp.float32(scale)
    # causal mask: cache position s visible iff s <= q_pos
    mask = jnp.arange(s)[None, None, :] <= q_pos[..., None]  # (B, T, S)
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("btkgs,bksh->btkgh", probs.astype(k_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, v_cache.shape[-1]).astype(q.dtype)
