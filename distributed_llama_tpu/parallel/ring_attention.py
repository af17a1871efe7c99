"""Ring attention: sequence/context-parallel causal attention over the sp axis.

Net-new relative to the reference, which has no sequence parallelism at all
(SURVEY.md §2.5: seqLen capped by one node's KV memory, serial O(pos) loop —
ref: src/llama2-tasks.cpp:54-94). Here the sequence is sharded over the mesh's
`sp` axis: each device holds one contiguous Q/K/V chunk, K/V blocks rotate
around the ring via `ppermute` (ICI neighbor exchange), and each device
accumulates its chunk's attention with numerically stable online-softmax
merging — the blockwise/flash decomposition, so no device ever materializes
the full (T, T) score matrix or the full K/V.

Wall-clock per layer: sp steps of (local block attention + neighbor ppermute),
with the K/V transfer overlapping compute when XLA schedules it; KV memory per
device is seq_len/sp — the sequence-length scaling axis the reference lacked.

Layout convention matches ops/attention.py: q/k/v are (B, T, H, hs) with GQA
via n_kv_heads <= n_heads; causal masking uses absolute positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import SP_AXIS


def _block_attn(q, k, v, q_pos, k_pos, scale):
    """One (Tq x Tk) causal block: returns (acc, m, l) flash-style stats.

    q: (B, Tq, H, hs); k/v: (B, Tk, KVH, hs); positions absolute.
    acc: (B, Tq, H, hs) unnormalized sum of softmax-weighted V;
    m: (B, Tq, H) running max; l: (B, Tq, H) running normalizer.
    """
    b, tq, h, hs = q.shape
    kvh = k.shape[2]
    group = h // kvh

    qf = q.astype(jnp.float32).reshape(b, tq, kvh, group, hs)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    scores = jnp.einsum("bqkgd,bskd->bqkgs", qf, kf) * scale  # s = Tk
    mask = q_pos[:, :, None] >= k_pos[:, None, :]             # (B, Tq, Tk)
    scores = jnp.where(mask[:, :, None, None, :], scores, -jnp.inf)

    m = jnp.max(scores, axis=-1)                              # (B, Tq, KVH, G)
    # fully masked rows (no visible keys in this block) contribute nothing
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(mask[:, :, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # (B, Tq, KVH, G)
    acc = jnp.einsum("bqkgs,bskd->bqkgd", p, vf)              # (B, Tq, KVH, G, hs)

    m = jnp.where(jnp.isfinite(m), m, -jnp.inf)
    return (acc.reshape(b, tq, h, hs), m.reshape(b, tq, h), l.reshape(b, tq, h))


def _merge(acc1, m1, l1, acc2, m2, l2):
    """Merge two flash-stat triples (online softmax combination)."""
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    a1 = jnp.where(jnp.isfinite(m1), jnp.exp(m1 - m_safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m_safe), 0.0)
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return acc, m, l


def ring_attention_local(q, k, v, chunk_pos0, axis_name: str = SP_AXIS):
    """Per-shard body: causal attention of the local Q chunk against the full
    (ring-distributed) K/V. Call under shard_map with q/k/v sharded on the
    sequence axis over `axis_name`.

    q, k, v: (B, T_local, H|KVH, hs) — this device's chunk.
    chunk_pos0: scalar int32 — absolute position of this chunk's first token
      (normally sp_index * T_local; passed in so prefill offsets compose).
    Returns (B, T_local, H, hs) attention output for the local chunk.
    """
    n = lax.axis_size(axis_name)  # static at trace time
    idx = lax.axis_index(axis_name)
    b, t, h, hs = q.shape
    scale = 1.0 / (hs ** 0.5)

    q_pos = chunk_pos0 + jnp.arange(t, dtype=jnp.int32)[None, :]
    q_pos = jnp.broadcast_to(q_pos, (b, t))

    acc = jnp.zeros((b, t, h, hs), jnp.float32)
    m = jnp.full((b, t, h), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, t, h), jnp.float32)
    # k/v rotate in their input dtype (bf16 halves ppermute bytes); _block_attn
    # casts to f32 per block
    k_cur, v_cur = k, v
    perm = [(i, (i + 1) % n) for i in range(n)]

    # k/v blocks travel the ring: at step s this device holds the chunk that
    # started on device (idx - s) mod n, whose first absolute position is
    # derived from its origin index. Unrolled (n is the static sp size) so the
    # final rotate can be skipped and XLA can overlap transfer with compute.
    for s in range(n):
        src = (idx - s) % n
        k_pos0 = (chunk_pos0 - idx * t) + src * t  # origin chunk's first pos
        k_pos = k_pos0 + jnp.arange(t, dtype=jnp.int32)[None, :]
        k_pos = jnp.broadcast_to(k_pos, (b, t))

        if s + 1 < n:  # start the next rotation before consuming this block
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)

        acc2, m2, l2 = _block_attn(q, k_cur, v_cur, q_pos, k_pos, scale)
        acc, m, l = _merge(acc, m, l, acc2, m2, l2)
        if s + 1 < n:
            k_cur, v_cur = k_nxt, v_nxt

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def sp_cache_attention(q, k_cache, v_cache, q_pos, mesh, axis_name: str = SP_AXIS):
    """Decode/continuation attention over an sp-sharded KV cache.

    The counterpart of ring_attention for steps AFTER the sequence-parallel
    prefill: the cache's sequence dim is sharded over sp (cache_pspec(sp=True))
    while the new queries are replicated over sp, so each device computes
    flash stats (acc, m, l) of the full query block against its local cache
    chunk and the stats merge exactly with a pmax/psum online-softmax
    combination — no device ever materializes the full-sequence cache.

    q: (B, T, H, hs); k_cache/v_cache: (B, KVH, S, hs) with S sharded over sp;
    q_pos: (B, T) absolute positions (cache slots > q_pos are masked, so
    not-yet-written positions never contribute). Returns (B, T, H, hs).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .mesh import DP_AXIS, TP_AXIS

    n = mesh.shape[axis_name]
    assert k_cache.shape[2] % n == 0, (k_cache.shape, n)
    tp = TP_AXIS if TP_AXIS in mesh.axis_names else None

    q_spec = P(DP_AXIS, None, tp, None)
    cache_spec = P(DP_AXIS, tp, axis_name, None)
    pos_spec = P(DP_AXIS, None)

    def body(q_l, k_l, v_l, qp_l):
        return sp_cache_attention_local(q_l, k_l, v_l, qp_l,
                                        axis_name=axis_name)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(q_spec, cache_spec, cache_spec, pos_spec),
                   out_specs=q_spec, check_vma=False)
    return fn(q, k_cache, v_cache, q_pos)


def sp_cache_attention_local(q_l, k_l, v_l, qp_l, axis_name: str = SP_AXIS):
    """The per-shard body of sp_cache_attention (local shapes: the cache's
    sequence dim is this device's S/sp chunk, queries replicated): local
    flash stats + the exact pmax/psum online-softmax merge. Called from
    sp_cache_attention's shard_map AND directly inside the fully-manual pp
    region (parallel/pp.py — shard_map cannot nest, so sp under pp runs
    manually exactly like tp and ep do)."""
    s_local = k_l.shape[2]
    hs = q_l.shape[-1]
    scale = 1.0 / (hs ** 0.5)
    idx = lax.axis_index(axis_name)
    bl = q_l.shape[0]
    k_pos = idx * s_local + jnp.arange(s_local, dtype=jnp.int32)[None, :]
    k_pos = jnp.broadcast_to(k_pos, (bl, s_local))
    kt = k_l.transpose(0, 2, 1, 3)  # (B, S_l, KVH, hs) — _block_attn layout
    vt = v_l.transpose(0, 2, 1, 3)
    acc, m, l = _block_attn(q_l, kt, vt, qp_l, k_pos, scale)
    # exact online-softmax merge across the sp chunks
    m_max = lax.pmax(m, axis_name)
    m_safe = jnp.where(jnp.isfinite(m_max), m_max, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    num = lax.psum(acc * alpha[..., None], axis_name)
    den = lax.psum(l * alpha, axis_name)
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q_l.dtype)


def ring_attention(q, k, v, mesh, pos0: int = 0, axis_name: str = SP_AXIS):
    """Sequence-parallel causal attention over a mesh's sp axis.

    q, k, v: (B, T, H|KVH, hs) global arrays; T must divide by mesh sp size.
    Returns (B, T, H, hs). Entry point for tests and the sp-prefill path;
    sharding: sequence axis over sp, everything else replicated.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from .mesh import TP_AXIS

    n = mesh.shape[axis_name]
    t = q.shape[1]
    assert t % n == 0, (t, n)
    t_local = t // n

    # heads stay tp-sharded through the ring (wq/wk/wv are row-split on tp —
    # parallel/sharding.py), so attention keeps its tensor parallelism; the
    # GQA group math is unaffected because h and kvh shard identically
    tp = TP_AXIS if TP_AXIS in mesh.axis_names else None
    spec = P(None, axis_name, tp, None)

    def body(q_l, k_l, v_l):
        idx = lax.axis_index(axis_name)
        chunk_pos0 = pos0 + idx * t_local
        return ring_attention_local(q_l, k_l, v_l, chunk_pos0, axis_name)

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
