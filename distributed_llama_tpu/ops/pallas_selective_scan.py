"""The selective scan (Mamba-1) recurrence: a slot's state, advanced by a
chunk of tokens (prefill) or by one (decode), as two Pallas TPU kernels with
one XLA twin behind the same function.

Per channel d of the D = d_inner channels, with the state h kept as (N, D)
float32 (N the state size, the CHANNELS in the lanes; B_t and C_t in R^N
are shared by every channel):

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] B_t[n] x_t[d]
    y_t[d]    = sum_n C_t[n] h_t[n, d]

A < 0 a number a (state index, channel) PAIR, dt_t > 0 the step a channel.
That is what parts it from ops/pallas_ssd.py: there the decay is one scalar
a head, so a chunk factors into `C B^T` under a decay mask and runs on the
MXU; here exp(dt_t[d] A[n, d]) differs over n AND d, no such product
exists, and a chunk IS the recurrence, one token after another on the VPU
and the EUP (N x D exponentials a token). What a chunk still buys is the
state's traffic: it is read and written once a program, not once a token.

Layout: a grid step holds one row's tokens and CHANNEL_BLOCK channels; the
state block (N, CHANNEL_BLOCK) has N in the sublanes (two tiles at N = 16)
and the channels in the lanes, so the decay, the write and the state are
whole tiles, B_t and C_t arrive as COLUMNS ((N, T), a lane slice of one
broadcast over the lanes), dt_t and x_t as rows, and y_t is a reduction
over the sublanes. The body walks a block in sub-blocks of LANES lanes,
each kept in registers across its tokens.

The three rules of a recurrent state (ops/pallas_delta_rule.py states them
and the gated-row block trick; the same SegmentRows contract):

  * `fresh[b]`: h_0 is zeros, whatever the slot's last request left;
  * `n_valid[b] == 0` (a gated row): the state is untouched, to the bit,
    and costs no state traffic;
  * tokens t >= n_valid[b] (the pad of a tail chunk) get dt = 0: they
    neither decay nor write. Their outputs are never read.

With a slot map (`slots`, the chunk program's) the chunk kernel chains as
ssd_chunk does, through the same pallas_call (ops/pallas_ssd._call): grid
channel blocks outermost and rows innermost, a slot's consecutive rows
name ONE state block, which stays in VMEM between their steps; a row that
continues the row before it reads h_0 from the output block. Everything is
float32: the state carries every earlier token, and a rounding made now
stays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .pallas_delta_rule import _hand_back
from .pallas_ssd import MAX_CHUNK, SHARED, _call, _xla_mapped, last_rows

# set on the v5e at d_inner 5120, N 16, 16 rows (PERF.md section 6, PR 52:
# ms for a program's 26 layers, chunk | decode at 16 live rows): blocks of
# 1280 channels 1.97 | 1.02, 2560 1.65 | 0.80, 5120 1.51 | 0.58-0.60;
# sub-blocks of 256 lanes 1.60, 512 1.51, 1280 1.56 in the widest block
CHANNEL_BLOCK = 5120    # channels a grid step (a 320 KiB state block at N 16)
LANES = 512             # channels the body keeps in registers at a time


def selective_scan_supported(t: int, inner: int, d_state: int) -> bool:
    """Kernel precondition: one token, or one chunk of whole sublane tiles
    of tokens; whole tiles of channels and of the state."""
    return ((t == 1 or (t <= MAX_CHUNK and t % 8 == 0))
            and inner % 128 == 0 and d_state % 8 == 0)


def _block(d: int, want: int) -> int:
    """The widest multiple of 128 lanes that divides d, `want` at most."""
    return max(w for w in range(128, min(d, want) + 1, 128) if d % w == 0)


def _advance(x_ref, dt_ref, b_ref, c_ref, a_ref, s0, o_ref, so_ref):
    """The recurrence over the T tokens of one row's block: x_ref, dt_ref
    (1, T, DB), b_ref, c_ref (1, N, T), a_ref (1, N, DB), s0 (N, DB)."""
    t, db = x_ref.shape[1], x_ref.shape[2]
    lanes = _block(db, LANES)
    for lo in range(0, db, lanes):
        at = slice(lo, lo + lanes)
        a, h = a_ref[0, :, at], s0[:, at]
        for i in range(t):
            dt = dt_ref[0, i:i + 1, at]                        # (1, L)
            h = (jnp.exp(dt * a) * h
                 + (dt * x_ref[0, i:i + 1, at]) * b_ref[0, :, i:i + 1])
            o_ref[0, i:i + 1, at] = jnp.sum(h * c_ref[0, :, i:i + 1],
                                            axis=0, keepdims=True)
        so_ref[0, :, at] = h


def _chunk_kernel(nv_ref, fresh_ref, chain_ref, row_ref, slot_ref, x_ref,
                  dt_ref, b_ref, c_ref, a_ref, s_ref, o_ref, so_ref):
    """A chunk of T tokens, one channel block of one row a grid step; the
    grid is (channel blocks, rows). A chained row's h_0 is what the step
    before it left in the output block, which both steps name."""
    r = pl.program_id(1)

    @pl.when(nv_ref[r] > 0)
    def _():
        s0 = jnp.where(chain_ref[r] > 0, so_ref[0], s_ref[0])
        s0 = jnp.where(fresh_ref[r] > 0, jnp.zeros_like(s0), s0)
        _advance(x_ref, dt_ref, b_ref, c_ref, a_ref, s0, o_ref, so_ref)

    # no live row in the whole call (a warm-up): every row looks at row 0's
    # blocks, and row 0 hands each channel block back as it came
    @pl.when((nv_ref[r] == 0) & (row_ref[r] == r))
    def _():
        so_ref[...] = s_ref[...]


def _step_kernel(nv_ref, fresh_ref, row_ref, blk_ref, x_ref, dt_ref, b_ref,
                 c_ref, a_ref, s_ref, o_ref, so_ref):
    """One token: the same body at T = 1; the grid is (rows, channel
    blocks), row r is slot r."""
    r = pl.program_id(0)

    @pl.when(nv_ref[r] > 0)
    def _():
        s0 = s_ref[0]
        s0 = jnp.where(fresh_ref[r] > 0, jnp.zeros_like(s0), s0)
        _advance(x_ref, dt_ref, b_ref, c_ref, a_ref, s0, o_ref, so_ref)

    _hand_back(nv_ref, row_ref, s_ref, so_ref)


def _scan_call(body, name, x, dt, a, bm, cm, state, n_valid, fresh,
               interpret, chain):
    b, t, d = x.shape
    n = state.shape[1]
    db = _block(d, CHANNEL_BLOCK)
    cols = lambda v: v.transpose(0, 2, 1)             # noqa: E731  (B, N, T)
    return _call(
        body, name, (x, dt, cols(bm), cols(cm), a[None]),
        [((1, t, db), 2), ((1, t, db), 2), ((1, n, t), None),
         ((1, n, t), None), ((1, n, db), 2, SHARED)],
        state, (1, n, db), (b, t, d), ((1, t, db), 2), d // db, n_valid,
        fresh, interpret, chain=chain, state_dim=2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_chunk(x, dt, a, bm, cm, state, n_valid, fresh, slots=None,
                         chained=None, interpret: bool = False):
    """x, dt (B, T, D), dt 0 past n_valid; a (N, D); bm, cm (B, T, N); the
    state (slots, N, D); slots (B,) int32, the slot whose state row r
    advances (None: row r's), and chained (B,) bool as
    pallas_ssd.chained_rows gives it. Returns (y (B, T, D), the state
    aliased onto its input)."""
    if slots is None:
        slots = jnp.arange(x.shape[0], dtype=jnp.int32)
        chained = jnp.zeros((x.shape[0],), bool)
    return _scan_call(_chunk_kernel, "selective_scan_chunk", x, dt, a, bm,
                      cm, state, n_valid, fresh, interpret,
                      (chained, slots.astype(jnp.int32)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_decode(x, dt, a, bm, cm, state, n_valid, fresh,
                          interpret: bool = False):
    """The same operands at T = 1, row r slot r."""
    return _scan_call(_step_kernel, "selective_scan_decode", x, dt, a, bm,
                      cm, state, n_valid, fresh, interpret, None)


def selective_scan(x, dt, a, bm, cm, state, n_valid, fresh, slots=None,
                   chained=None, *, use_pallas: bool = False,
                   interpret: bool = False):
    """Advance `state` (B, N, D) float32 by T tokens a row.

    x (B, T, D), the convolved input; dt (B, T, D), the step after its
    softplus; a (N, D) < 0; bm, cm (B, T, N); n_valid (B,) int32: the
    tokens of row b that count (0: a gated row); fresh (B,) bool: row b
    starts from zeros; slots (B,) int32 with chained =
    pallas_ssd.chained_rows(slots, n_valid): row b advances
    state[slots[b]], from the row before's end where it continues it (the
    caller owes what ops/pallas_ssd.py's docstring says); None: row b is
    slot b. Returns (y (B, T, D) float32, WITHOUT the skip term D x, new
    state). Rows of `y` past n_valid are not meaningful; a gated row's are
    zeros."""
    t, d = x.shape[1:]
    f32 = jnp.float32
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    x, a, bm, cm = (v.astype(f32) for v in (x, a, bm, cm))
    fresh = fresh & (n_valid > 0)
    kernels = use_pallas and selective_scan_supported(t, d, state.shape[1])
    tight = (x, dt, a, bm, cm, state, n_valid.astype(jnp.int32), fresh)
    if kernels and t > 1:
        y, state = selective_scan_chunk(*tight, slots, chained,
                                        interpret=interpret)
    elif kernels and slots is None:
        y, state = selective_scan_decode(*tight, interpret=interpret)
    elif slots is None:
        y, state = _scan_xla(x, dt, a, bm, cm, state, fresh)
    else:   # the decode kernel takes no map: a one-token CHUNK under one
        y, state = _xla_mapped(
            lambda x, dt, *rest: _scan_xla(x, dt, a, *rest), (x, dt, bm, cm),
            state, fresh, slots, chained, last_rows(chained, n_valid))
    return jnp.where((n_valid > 0)[:, None, None], y, 0.0), state


def _scan_xla(x, dt, a, bm, cm, state, fresh):
    """The XLA twin: the recurrence over (B, N, D) at once, token after
    token, any T. A token with dt = 0 neither decays nor writes, so a row
    that is not live keeps its state to the bit."""
    def step(h, xs):
        xt, dtt, bt, ct = xs                      # (B, D) x 2, (B, N) x 2
        h = (jnp.exp(dtt[:, None, :] * a) * h
             + (dtt * xt)[:, None, :] * bt[:, :, None])
        return h, jnp.sum(h * ct[:, :, None], axis=1)

    s, y = lax.scan(step, jnp.where(fresh[:, None, None], 0.0, state),
                    tuple(v.swapaxes(0, 1) for v in (x, dt, bm, cm)))
    return y.swapaxes(0, 1), s
