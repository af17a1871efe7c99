"""Kimi Delta Attention's rule: the gated delta rule of
ops/pallas_delta_rule.py with a decay that is a VECTOR over a head's key
channels, as a Pallas TPU kernel pair (`kda_chunk`, `kda_decode`) with an
XLA twin behind one function. Per head, the state S kept (d_k, d_v) float32,
rows the key channels:

    S'  = Diag(a_t) S_{t-1},            a_t = exp(g_t) in (0, 1)^d_k
    S_t = S' + k_t u_t^T,               u_t = b_t (v_t - S'^T k_t)
    o_t = S_t^T q_t

What the vector changes in the chunked form. With G the running sum of g
inside the chunk (C x d_k), the corrected values still solve

    (I + A) U = b * (V - (K * exp(G)) S_0),
    A[i, j]   = b_i P_kk[i, j]  for j < i, else 0,
    P_xk[i, j] = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])        (j <= i)

but the pair decay no longer factors out of k_i . k_j: P is not (K K^T) *
decay, and the factored form (K * exp(G)) (K * exp(-G))^T forms exp(-G),
which overflows float32 inside a 32-token chunk as soon as one channel
forgets by more than e^-2.8 a token. Here NO exponential of a positive
number is ever formed: column j of P_kk and of P_qk is reckoned against
token j itself, exp(G_i - G_j) for the rows i >= j (masked to -inf BEFORE
the exponential for i < j), times k_j, summed over the channels: C passes
over a (C, d_k) tile on the VPU, exact for any decay however strong. Every
other decay in the chunk is against its start or its end, so at most 1:

    O   = (Q * exp(G)) S_0 + tril(P_qk) U,
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U.

(I + A)^-1 is pallas_delta_rule's product of five factors; `fresh`,
`n_valid` and the gated rows are its three rules, through its own
pallas_call (`_call`). A decay constant over the channels gives the scalar
rule's numbers (tests/test_kda.py holds the two together). Decode is the
recurrence itself on the VPU. Everything is float32, the matmuls at full
precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .pallas_delta_rule import (MAX_CHUNK, _call, _dot, _hand_back, _solve,
                                _xla_dot, delta_rule_supported)

HEAD_BLOCK = 4          # heads a grid step (a state block of 4 x 128 x 128)


def _column(row):
    """(..., 1, n) -> (..., n, 1), exactly: a masked identity and a lane
    reduction (Mosaic has no cheap transpose of a single row)."""
    n = row.shape[-1]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)


def _chunk(q, k, kend_t, v, gs, bc, s0, dot):
    """One chunk of one head (or a batch of them, as pallas_delta_rule's
    `_chunk`). q, k (C, d_k), v (C, d_v); gs (C, d_k) the running log-decay;
    kend_t (d_k, C) = (k * exp(G_C - G))^T, formed by the caller; bc (C, 1)
    the step; s0 (d_k, d_v). Returns (o (C, d_v), the new state)."""
    c, dk = q.shape[-2], q.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    p_kk = p_qk = jnp.zeros(q.shape[:-1] + (c,), jnp.float32)
    for t in range(c):   # column t of both pair matrices, against token t
        e = jnp.exp(jnp.where(row >= t, gs - gs[..., t:t + 1, :],
                              -jnp.inf)) * k[..., t:t + 1, :]
        p_kk = jnp.where(j == t, jnp.sum(k * e, -1, keepdims=True), p_kk)
        p_qk = jnp.where(j == t, jnp.sum(q * e, -1, keepdims=True), p_qk)
    eg = jnp.exp(gs)
    a = jnp.where(i > j, bc * p_kk, 0.0)
    u = _solve(a, bc * (v - dot(k * eg, s0)), c, dot)
    o = dot(q * eg, s0) + dot(p_qk, u)       # rows above the diagonal: 0
    s1 = _column(jnp.exp(gs[..., c - 1:, :])) * s0 + dot(kend_t, u)
    return o, s1


def _chunk_kernel(nv_ref, fresh_ref, row_ref, blk_ref, q_ref, k_ref, kt_ref,
                  v_ref, gs_ref, bc_ref, s_ref, o_ref, so_ref, *, heads):
    """A chunk of C tokens, `heads` heads of one row a grid step."""
    b = pl.program_id(0)

    @pl.when(nv_ref[b] > 0)
    def _():
        fresh = fresh_ref[b] > 0
        for h in range(heads):
            s0 = s_ref[0, h]
            o, s1 = _chunk(q_ref[0, h], k_ref[0, h], kt_ref[0, h],
                           v_ref[0, h], gs_ref[0, h], bc_ref[0, h],
                           jnp.where(fresh, jnp.zeros_like(s0), s0), _dot)
            o_ref[0, h] = o
            so_ref[0, h] = s1

    _hand_back(nv_ref, row_ref, s_ref, so_ref)


def _step_kernel(nv_ref, fresh_ref, row_ref, blk_ref, q_ref, k_ref, v_ref,
                 a_ref, b_ref, s_ref, o_ref, so_ref, *, heads):
    """One token: the decay a state ROW, then the rank-one update, on the
    VPU. q, k and the decay arrive as rows of lanes and are needed as
    columns of sublanes (_column)."""
    b = pl.program_id(0)

    @pl.when(nv_ref[b] > 0)
    def _():
        fresh = fresh_ref[b] > 0
        for h in range(heads):
            s0 = s_ref[0, h]
            kc, qc = _column(k_ref[0, h]), _column(q_ref[0, h])
            sk = (jnp.where(fresh, jnp.zeros_like(s0), s0)
                  * _column(a_ref[0, h]))
            u = b_ref[0, h] * (v_ref[0, h]
                               - jnp.sum(sk * kc, axis=0, keepdims=True))
            s1 = sk + kc * u
            o_ref[0, h] = jnp.sum(s1 * qc, axis=0, keepdims=True)
            so_ref[0, h] = s1

    _hand_back(nv_ref, row_ref, s_ref, so_ref)


def _end_decayed_t(k, gs):
    """(k * exp(G_C - G))^T: (..., d_k, C), every exponent <= 0."""
    return (k * jnp.exp(gs[..., -1:, :] - gs)).swapaxes(-1, -2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk(q, k, v, g, beta, state, n_valid, fresh,
              interpret: bool = False):
    """Head-major operands: q, k and the log decay g (B, H, C, d_k), v (B,
    H, C, d_v), beta (B, H, C), the state (B, H, d_k, d_v). Returns (o (B,
    H, C, d_v), the state aliased onto its input)."""
    gs = jnp.cumsum(g, axis=-2)
    return _call(_chunk_kernel, "kda_chunk",
                 (q, k, _end_decayed_t(k, gs), v, gs, beta[..., None]),
                 state, v.shape[2:], n_valid, fresh, interpret, HEAD_BLOCK)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode(q, k, v, g, beta, state, n_valid, fresh,
               interpret: bool = False):
    """The same operands at C = 1; the step rides in as a row of d_v
    lanes, the decay as its row of d_k."""
    dv = v.shape[-1]
    return _call(_step_kernel, "kda_decode",
                 (q, k, v, jnp.exp(g),
                  jnp.broadcast_to(beta[..., None], beta.shape + (dv,))),
                 state, v.shape[2:], n_valid, fresh, interpret, HEAD_BLOCK)


def kda_rule(q, k, v, g, beta, state, n_valid, fresh, *,
             use_pallas: bool = False, interpret: bool = False):
    """Advance `state` (B, H, d_k, d_v) float32 by T tokens a row:
    pallas_delta_rule.delta_rule's contract with g (B, T, H, d_k), the log
    decay a key channel. q, k (B, T, H, d_k), k of unit length, q already
    scaled; v (B, T, H, d_v); beta (B, T, H); n_valid (B,) int32, fresh
    (B,) bool. Returns (o (B, T, H, d_v) float32, new state); rows of `o`
    past n_valid are not meaningful, a gated row's are zeros."""
    t = q.shape[1]
    f32 = jnp.float32
    valid = (jnp.arange(t, dtype=jnp.int32)[None, :]
             < n_valid[:, None])[..., None]                   # (B, T, 1)
    g = jnp.where(valid[..., None], g.astype(f32), 0.0)
    beta = jnp.where(valid, beta.astype(f32), 0.0)
    # head-major: a head's chunk is a tile of its own
    qh, kh, vh, gh = (x.astype(f32).transpose(0, 2, 1, 3)
                      for x in (q, k, v, g))
    bh = beta.transpose(0, 2, 1)
    fresh = fresh & (n_valid > 0)
    live = (n_valid > 0)[:, None, None, None]
    if use_pallas and delta_rule_supported(t):
        kernel = kda_decode if t == 1 else kda_chunk
        o, state = kernel(qh, kh, vh, gh, bh, state,
                          n_valid.astype(jnp.int32), fresh,
                          interpret=interpret)
    else:
        o, state = _kda_xla(qh, kh, vh, gh, bh, state, fresh)
    return jnp.where(live, o, 0.0).transpose(0, 2, 1, 3), state


def _kda_xla(q, k, v, g, beta, state, fresh):
    """The XLA twin: the same chunk algebra over (B, H) at once, chunks of
    MAX_CHUNK tokens one after another (any T)."""
    t = q.shape[2]
    s = jnp.where(fresh[:, None, None, None], 0.0, state)
    outs = []
    for lo in range(0, t, MAX_CHUNK):
        sl = slice(lo, min(lo + MAX_CHUNK, t))
        gs = jnp.cumsum(g[:, :, sl], axis=-2)
        o, s = _chunk(q[:, :, sl], k[:, :, sl],
                      _end_decayed_t(k[:, :, sl], gs), v[:, :, sl], gs,
                      beta[:, :, sl, None], s, _xla_dot)
        outs.append(o)
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, 2)), s
