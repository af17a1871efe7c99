"""The gated delta rule: a slot's recurrent state, advanced by a chunk of
tokens (prefill) or by one (decode), as a Pallas TPU kernel with an XLA twin
behind the same function.

Per head, with the state S^T kept as (d_k, d_v) float32:

    S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T,    o_t = S_t q_t

a_t = exp(g_t) in (0, 1) the decay, b_t in (0, 2) the step. A token-by-token
loop is C dependent slivers of work; the CHUNKED form does a chunk of C <= 32
tokens in a handful of matmuls. With G the running sum of g inside the chunk
and u_t = b_t (v_t - a_t S_{t-1} k_t) the corrected values,

    (I + A) U = b * (V - exp(G) * (K S_0)),
    A[i, j] = b_i exp(G_i - G_j) (k_i . k_j)  for j < i, else 0.

A is strictly lower triangular, so A^C = 0 and (I + A)^-1 = (I - A)(I + A^2)
(I + A^4)(I + A^8)(I + A^16) exactly: MXU work, no loop over tokens. Then

    O = exp(G) * (Q S_0) + tril(Q K^T * exp(G_i - G_j)) U,
    S_C = exp(G_C) S_0 + (K * exp(G_C - G))^T U.

Three rules a cache of rows never needed, because later writes overwrite
rows and nothing overwrites a state:

  * `fresh[b]` (the row's segment starts at position 0): S_0 is zeros,
    whatever the slot's last request left;
  * `n_valid[b] == 0` (a gated row): the state is untouched — the kernel
    reads `n_valid` by scalar prefetch and sends such a row's every block
    to one its neighbour already holds, so it costs no state traffic;
  * tokens t >= n_valid[b] (the pad of a tail chunk) get g = 0 and b = 0:
    they neither decay nor write. Their outputs are never read.

Decode is the recurrence itself, one rank-one update a head, on the VPU
(`delta_rule_decode`; the chunk algebra at C = 1 gives the same numbers, and
is what the XLA twin runs). Everything is float32, the chunk's matmuls at
full precision: the state carries every earlier token, and a rounding made
now stays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_CHUNK = 32          # tokens a chunk: A^32 = 0 takes five factors
HEAD_BLOCK = 6          # heads a grid step (a state block of 6 x 96 x 192)
_HI = lax.Precision.HIGHEST


def delta_rule_supported(t: int) -> bool:
    """Kernel precondition: one token, or one chunk of whole sublane tiles
    of tokens."""
    return t == 1 or (t <= MAX_CHUNK and t % 8 == 0)


def _solve(a, rhs, c, dot):
    """(I + A)^-1 rhs for strictly lower-triangular A of size c: the factors
    (I + A^16) ... (I + A^2)(I - A) commute, so they apply in any order."""
    powers = []          # A^2, A^4, ... below A^c
    for _ in range(max((c - 1).bit_length() - 1, 0)):
        last = powers[-1] if powers else a
        powers.append(dot(last, last))
    x = rhs
    for ap in reversed(powers):
        x = x + dot(ap, x)
    return x - dot(a, x)


def _chunk(q, k, kt, v, gc, gr, bc, s0, dot):
    """One chunk of one head (or a batch of them: `dot` contracts the last
    axis of its left operand with the second-to-last of its right). q, k
    (C, d_k), kt (d_k, C), v (C, d_v), gc / bc (C, 1) and gr (1, C): the
    running log-decay as a column and as a row, the step as a column;
    s0 (d_k, d_v). Returns (o (C, d_v), the new state)."""
    c = q.shape[-2]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # exp(G_i - G_j) for j <= i, 0 above the diagonal (masked BEFORE the
    # exponential: G_i - G_j > 0 there)
    decay = jnp.exp(jnp.where(i >= j, gc - gr, -jnp.inf))
    a = jnp.where(i > j, bc * dot(k, kt) * decay, 0.0)
    u = _solve(a, bc * (v - jnp.exp(gc) * dot(k, s0)), c, dot)
    o = jnp.exp(gc) * dot(q, s0) + dot(dot(q, kt) * decay, u)
    g_end = gc[..., c - 1:, :]                                  # (1, 1)
    # (1, 1) -> a row of lanes BEFORE the exponential: Mosaic does not
    # broadcast over sublanes and lanes in one step, and two broadcasts
    # in a row are folded into one
    keep = jnp.exp(jnp.broadcast_to(g_end, g_end.shape[:-1] + s0.shape[-1:]))
    s1 = keep * s0 + dot(kt * jnp.exp(g_end - gr), u)
    return o, s1


def _xla_dot(x, y):
    return jnp.matmul(x, y, precision=_HI)


def _dot(x, y):
    return jnp.dot(x, y, preferred_element_type=jnp.float32, precision=_HI)


def _chunk_kernel(nv_ref, fresh_ref, row_ref, blk_ref, q_ref, k_ref, kt_ref,
                  v_ref, gc_ref, gr_ref, bc_ref, s_ref, o_ref, so_ref, *,
                  heads):
    """A chunk of C tokens, `heads` heads of one row a grid step: _chunk on
    the MXU."""
    b = pl.program_id(0)

    @pl.when(nv_ref[b] > 0)
    def _():
        fresh = fresh_ref[b] > 0
        for h in range(heads):
            s0 = s_ref[0, h]
            o, s1 = _chunk(q_ref[0, h], k_ref[0, h], kt_ref[0, h],
                           v_ref[0, h], gc_ref[0, h], gr_ref[0, h],
                           bc_ref[0, h],
                           jnp.where(fresh, jnp.zeros_like(s0), s0), _dot)
            o_ref[0, h] = o
            so_ref[0, h] = s1

    _hand_back(nv_ref, row_ref, s_ref, so_ref)


def _step_kernel(nv_ref, fresh_ref, row_ref, blk_ref, q_ref, k_ref, v_ref,
                 a_ref, b_ref, s_ref, o_ref, so_ref, *, heads):
    """One token: the rank-one update of a head, on the VPU (five passes
    over the (d_k, d_v) state; an MXU pass would be all latency). q and k
    arrive as rows of lanes and are needed as columns of sublanes: a
    masked identity and a lane reduction turn them, exactly."""
    b = pl.program_id(0)

    @pl.when(nv_ref[b] > 0)
    def _():
        fresh = fresh_ref[b] > 0
        dk = s_ref.shape[2]
        eye = (lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
               == lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

        def column(row):                       # (1, d_k) -> (d_k, 1)
            return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

        for h in range(heads):
            s0 = s_ref[0, h]
            kc, qc = column(k_ref[0, h]), column(q_ref[0, h])
            sk = jnp.where(fresh, jnp.zeros_like(s0), s0) * a_ref[0, h]
            u = b_ref[0, h] * (v_ref[0, h]
                               - jnp.sum(sk * kc, axis=0, keepdims=True))
            s1 = sk + kc * u
            o_ref[0, h] = jnp.sum(s1 * qc, axis=0, keepdims=True)
            so_ref[0, h] = s1

    _hand_back(nv_ref, row_ref, s_ref, so_ref)


def _hand_back(nv_ref, row_ref, s_ref, so_ref):
    """No live row in the whole call (a warm-up): every step visits block
    (0, 0), and its first visit hands the state back as it came."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when((nv_ref[b] == 0) & (row_ref[b] == b) & (j == 0))
    def _():
        so_ref[...] = s_ref[...]


def _gated_blocks(live, n_j: int):
    """Where a gated row's grid steps look, (row, head block): the LAST
    block of the nearest live row before it, else the FIRST block of the
    first live row, else (row 0, block 0). Its steps then name the block
    the neighbouring step holds: nothing is copied in, nothing written
    back, and the skipped body leaves that block's output as it stands."""
    n = live.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    prev = lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live).astype(jnp.int32)    # 0 where none is live
    has_prev = prev >= 0
    row = jnp.where(has_prev, prev, first)
    blk = jnp.where(has_prev, n_j - 1, 0).astype(jnp.int32)
    return jnp.where(live, idx, row), blk


def _call(body, name, operands, state, out_tail, n_valid, fresh, interpret,
          head_block: int = HEAD_BLOCK):
    """The pallas_call the kernels share (ops/pallas_kda.py's too): grid
    (rows, head blocks), every operand (B, H, . , .) in blocks of
    `head_block` heads, the state last and aliased onto the second output;
    a gated row's blocks as _gated_blocks says."""
    b, h = state.shape[:2]
    hb = head_block if h % head_block == 0 else 1
    n_j = h // hb
    row, blk = _gated_blocks(n_valid > 0, n_j)

    def at(i, j, nv, fr, rw, bk):
        on = nv[i] > 0
        return (jnp.where(on, i, rw[i]), jnp.where(on, j, bk[i]), 0, 0)

    def spec(x):
        return pl.BlockSpec((1, hb) + tuple(x.shape[2:]), at)

    operands = (*operands, state)
    out = jax.ShapeDtypeStruct((b, h) + out_tail, jnp.float32)
    return pl.pallas_call(
        functools.partial(body, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, n_j),
            in_specs=[spec(x) for x in operands],
            out_specs=[spec(out), spec(state)],
        ),
        out_shape=[out, jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the four scalars come first; the state is written where it stands
        input_output_aliases={3 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(n_valid, fresh.astype(jnp.int32), row, blk, *operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_chunk(q, k, v, g, beta, state, n_valid, fresh,
                     interpret: bool = False):
    """Head-major operands: q, k (B, H, C, d_k), v (B, H, C, d_v), g and
    beta (B, H, C), the state (B, H, d_k, d_v). Returns (o (B, H, C, d_v),
    the state aliased onto its input)."""
    gsum = jnp.cumsum(g, axis=-1)
    return _call(_chunk_kernel, "delta_rule_chunk",
                 (q, k, k.swapaxes(-1, -2), v, gsum[..., None],
                  gsum[..., None, :], beta[..., None]),
                 state, v.shape[2:], n_valid, fresh, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_decode(q, k, v, g, beta, state, n_valid, fresh,
                      interpret: bool = False):
    """The same operands at C = 1. The decay and the step ride in as rows
    of d_v lanes, so that the body multiplies them in without a broadcast
    over both axes."""
    dv = v.shape[-1]
    row = lambda x: jnp.broadcast_to(x[..., None], x.shape + (dv,))  # noqa: E731
    return _call(_step_kernel, "delta_rule_decode",
                 (q, k, v, row(jnp.exp(g)), row(beta)),
                 state, v.shape[2:], n_valid, fresh, interpret)


def delta_rule(q, k, v, g, beta, state, n_valid, fresh, *,
               use_pallas: bool = False, interpret: bool = False):
    """Advance `state` (B, H, d_k, d_v) float32 by T tokens a row.

    q, k (B, T, H, d_k) — k of unit length, q already scaled; v (B, T, H,
    d_v); g (log decay) and beta (B, T, H); n_valid (B,) int32: the tokens
    of row b that count (0: a gated row); fresh (B,) bool: row b starts
    from zeros. Returns (o (B, T, H, d_v) float32, new state). Rows of `o`
    past n_valid are not meaningful; a gated row's are zeros."""
    b, t, h, _ = q.shape
    f32 = jnp.float32
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None]
    g = jnp.where(valid[..., None], g.astype(f32), 0.0)
    beta = jnp.where(valid[..., None], beta.astype(f32), 0.0)
    # head-major: a head's chunk is a tile of its own
    qh, kh, vh = (x.astype(f32).transpose(0, 2, 1, 3) for x in (q, k, v))
    gh, bh = g.transpose(0, 2, 1), beta.transpose(0, 2, 1)
    fresh = fresh & (n_valid > 0)
    live = (n_valid > 0)[:, None, None, None]
    if use_pallas and delta_rule_supported(t):
        kernel = delta_rule_decode if t == 1 else delta_rule_chunk
        o, state = kernel(qh, kh, vh, gh, bh, state,
                          n_valid.astype(jnp.int32), fresh,
                          interpret=interpret)
    else:
        o, state = _delta_rule_xla(qh, kh, vh, gh, bh, state, fresh)
    return jnp.where(live, o, 0.0).transpose(0, 2, 1, 3), state


def _delta_rule_xla(q, k, v, g, beta, state, fresh):
    """The XLA twin: the same chunk algebra over (B, H) at once, chunks of
    MAX_CHUNK tokens one after another (any T; the pad of the last chunk
    neither decays nor writes, like a tail chunk's)."""
    t = q.shape[2]
    s = jnp.where(fresh[:, None, None, None], 0.0, state)
    outs = []
    for lo in range(0, t, MAX_CHUNK):
        sl = slice(lo, min(lo + MAX_CHUNK, t))
        gsum = jnp.cumsum(g[..., sl], axis=-1)
        o, s = _chunk(q[:, :, sl], k[:, :, sl],
                      k[:, :, sl].swapaxes(-1, -2), v[:, :, sl],
                      gsum[..., None], gsum[..., None, :],
                      beta[..., sl, None], s, _xla_dot)
        outs.append(o)
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, 2)), s
