"""The state-space duality (Mamba-2) recurrence: a slot's state, advanced by
a chunk of tokens (prefill) or by one (decode), as two Pallas TPU kernels
with one XLA twin behind the same function.

Per head, with the state S kept as (P, N) float32 (P the head's width, N
the state size; B_t and C_t in R^N are SHARED by the heads of a group):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,        y_t = S_t C_t

A < 0 a scalar a head, dt_t > 0 the step. With g_t = dt_t A and G the
running sum of g inside a chunk of C <= 32 tokens,

    Y   = exp(G) * (Cm S_0^T) + ((Cm Bm^T) * L) (dt * X),
          L[i, j] = exp(G_i - G_j) for j <= i, else 0,
    S_C = exp(G_C) S_0 + (X * dt * exp(G_C - G))^T Bm.

`Cm Bm^T` is one (C, C) product for ALL heads of a group; only the decay
mask L is a head's own. The chunk kernel never loops over heads: a grid
step holds HEAD_BLOCK heads side by side in the lanes, as x arrives ((C,
HB x P): no transpose outside), the heads' masks side by side in one (C,
HB x C) operand against a block-diagonal (HB x C, HB x P) copy of dt * X,
and what is a head's own scalar (dt, exp(G), the end decay) is spread over
its P lanes or rows by a 0/1 matrix on the MXU, which at full precision
copies exactly.

The three rules of a recurrent state (ops/pallas_delta_rule.py states them
and the gated-row block trick; the same SegmentRows contract):

  * `fresh[b]`: S_0 is zeros, whatever the slot's last request left;
  * `n_valid[b] == 0` (a gated row): the state is untouched, to the bit,
    and costs no state traffic;
  * tokens t >= n_valid[b] (the pad of a tail chunk) get dt = 0: they
    neither decay nor write. Their outputs are never read.

With a slot map (`slots`, the chunk program's: models/transformer.forward)
row r advances the state of slot slots[r], and a row that CONTINUES the row
before it (`chained_rows`: the same slot, both live) starts from that row's
final state inside the call: ssd_chunk walks its grid head blocks outermost
and rows innermost, so a slot's consecutive rows name ONE state block, which
stays in VMEM between their steps (the accumulator pattern: a chained row
reads S_0 from the output block), is fetched once and written back once a
call however many rows advance it. The caller owes: a slot's live rows are
consecutive, every one but the last a whole chunk.

Decode is the recurrence itself, a rank-one update a head on the VPU
(`ssd_decode`), as `delta_rule_decode` is and for the same reason. Everything
is float32, the matmuls at full precision: the state carries every earlier
token, and a rounding made now stays. `mamba_chunk_size` of a published
configuration is a parameter of ITS scan's blocking, not of the function
computed: any chunking gives the recurrence's result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_delta_rule import _gated_blocks, _hand_back

MAX_CHUNK = 32          # tokens a chunk program takes
HEAD_BLOCK = 8          # heads a grid step of ssd_chunk (8 x 64 lanes)
DECODE_HEAD_BLOCK = 32  # heads a grid step of ssd_decode (a 1 MiB block)
_HI = lax.Precision.HIGHEST


def ssd_supported(t: int, heads: int, head_dim: int, d_state: int,
                  groups: int) -> bool:
    """Kernel precondition: one token, or one chunk of whole sublane tiles
    of tokens; one group (B and C shared by every head); whole tiles."""
    return ((t == 1 or (t <= MAX_CHUNK and t % 8 == 0)) and groups == 1
            and head_dim % 8 == 0 and d_state % 8 == 0)


def _head_block(h: int, want: int) -> int:
    return want if h % want == 0 else h


def _dot(x, y, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(x, y, dims, precision=_HI,
                           preferred_element_type=jnp.float32)


def _spread(rows: int, cols: int, by_col: int):
    """(rows, cols) 0/1: column j belongs to row j // by_col."""
    r = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return ((c >= r * by_col) & (c < (r + 1) * by_col)).astype(jnp.float32)


def _chunk_kernel(nv_ref, fresh_ref, chain_ref, row_ref, slot_ref, x_ref,
                  b_ref, c_ref, dg_ref, grow_ref, end_ref, s_ref, o_ref,
                  so_ref, *, heads, p):
    """A chunk of C tokens, `heads` heads of one row a grid step; the grid
    is (head blocks, rows). A chained row's S_0 is what the step before it
    left in the output block, which both steps name."""
    r = pl.program_id(1)

    @pl.when(nv_ref[r] > 0)
    def _():
        c = x_ref.shape[1]
        hb, hp, hc = heads, heads * p, heads * c
        x, bm, cm = x_ref[0], b_ref[0], c_ref[0]       # (C, HP), (C, N) x 2
        s0 = jnp.where(chain_ref[r] > 0, so_ref[0], s_ref[0])     # (HP, N)
        s0 = jnp.where(fresh_ref[r] > 0, jnp.zeros_like(s0), s0)
        dt, g = dg_ref[0, 0, :c], dg_ref[0, 0, c:]     # (C, HB) each
        g_end = g[c - 1:]                              # (1, HB)
        # a head's scalars over its P lanes: [dt ; exp(G) ; dt exp(G_C - G)]
        lanes = _dot(jnp.concatenate(
            [dt, jnp.exp(g), dt * jnp.exp(g_end - g)], axis=0),
            _spread(hb, hp, p))                        # (3C, HP)
        xdt = x * lanes[:c]
        # every head's decay mask side by side: column h C + j of row i
        # holds exp(G_h,i - G_h,j) for j <= i
        i = lax.broadcasted_iota(jnp.int32, (c, hc), 0)
        col = lax.broadcasted_iota(jnp.int32, (c, hc), 1)
        j = col - (col // c) * c
        gi = _dot(g, _spread(hb, hc, c))               # (C, HC)
        decay = jnp.exp(jnp.where(i >= j, gi - grow_ref[0, 0], -jnp.inf))
        # Cm Bm^T once for all heads, repeated a head: (C, C) -> (C, HC)
        cb = _dot(_dot(cm, bm, (((1,), (1,)), ((), ()))),
                  (i == j).astype(jnp.float32))
        # dt X block-diagonal: head h's rows h C .. h C + C hold its lanes
        rr = lax.broadcasted_iota(jnp.int32, (hc, hp), 0)
        cc = lax.broadcasted_iota(jnp.int32, (hc, hp), 1)
        xbd = jnp.where(rr // c == cc // p,
                        jnp.concatenate([xdt] * hb, axis=0), 0.0)
        o_ref[0] = (lanes[c:2 * c] * _dot(cm, s0, (((1,), (1,)), ((), ())))
                    + _dot(cb * decay, xbd))
        # exp(G_C) of a head down its P rows, then the chunk's writes
        keep = _dot(_spread(hb, hp, p), end_ref[0], (((0,), (0,)), ((), ())))
        so_ref[0] = keep * s0 + _dot(x * lanes[2 * c:], bm,
                                     (((0,), (0,)), ((), ())))

    # no live row in the whole call (a warm-up): every row looks at row 0's
    # blocks, and row 0 hands each head block back as it came
    @pl.when((nv_ref[r] == 0) & (row_ref[r] == r))
    def _():
        so_ref[...] = s_ref[...]


def _step_kernel(nv_ref, fresh_ref, row_ref, blk_ref, xdt_ref, b_ref, c_ref,
                 dec_ref, s_ref, o_ref, so_ref, *, heads):
    """One token: the rank-one update of a head, on the VPU. dt x arrives
    as a row of lanes and is needed as a column of sublanes, and y comes out
    as a column and is stored as a row: a masked identity and a reduction
    turn them, exactly."""
    r = pl.program_id(0)

    @pl.when(nv_ref[r] > 0)
    def _():
        fresh = fresh_ref[r] > 0
        p = s_ref.shape[2]
        eye = (lax.broadcasted_iota(jnp.int32, (p, p), 0)
               == lax.broadcasted_iota(jnp.int32, (p, p), 1))
        brow, crow = b_ref[0], c_ref[0]                # (1, N)
        for h in range(heads):
            s0 = s_ref[0, h]
            s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
            xcol = jnp.sum(jnp.where(eye, xdt_ref[0, h:h + 1, :], 0.0),
                           axis=1, keepdims=True)      # (P, 1)
            s1 = s0 * dec_ref[0, h:h + 1, :] + xcol * brow
            ycol = jnp.sum(s1 * crow, axis=1, keepdims=True)
            o_ref[0, h:h + 1, :] = jnp.sum(jnp.where(eye, ycol, 0.0),
                                           axis=0, keepdims=True)
            so_ref[0, h] = s1

    _hand_back(nv_ref, row_ref, s_ref, so_ref)


def chained_rows(slots, n_valid):
    """(B,) bool: row r CONTINUES row r - 1, the same slot and both live,
    so its state and its convolution's tail start where that row's end."""
    live = n_valid > 0
    return jnp.concatenate([jnp.zeros((1,), bool), (slots[1:] == slots[:-1])
                            & live[1:] & live[:-1]])


def last_rows(chained, n_valid):
    """(B,) bool: the live rows no row continues, a slot's LAST: what they
    leave is their slot's new state and tail."""
    return (n_valid > 0) & ~jnp.concatenate([chained[1:],
                                             jnp.zeros((1,), bool)])


# what a block's first dimension counts (`_call`): a program row's operand,
# the state of the row's slot, or an operand every row shares (block 0)
ROW, SLOT, SHARED = "row", "slot", "shared"


def _call(body, name, operands, per_row, state, state_block, out_shape,
          out_block, n_j, n_valid, fresh, interpret, chain=None,
          state_dim: int = 1):
    """The pallas_call both kernels share (ops/pallas_selective_scan.py's
    too): each operand's block with the dimension its head block counts
    along (None: one block a row) and, for an operand every row shares, a
    third entry SHARED (block 0 along its first dimension); the state last, its blocks counted along `state_dim`, and aliased onto the
    second output.

    chain None (ssd_decode): grid (rows, head blocks), row r is slot r, a
    gated row's blocks as _gated_blocks says.

    chain (chained, slots) (ssd_chunk): grid (head blocks, rows), so that
    the steps of a slot's consecutive rows follow each other and name one
    state block, (slots[r], j). _gated_blocks' rule in this order and in
    slot indices: a gated row's steps name, at the SAME head block, the
    blocks of the nearest live row before it, else of the first live row
    (else row 0's), and the state block of THAT row's slot: the block the
    neighbouring step holds, so nothing is copied in or written back."""
    row, blk = _gated_blocks(n_valid > 0, n_j)
    if chain is None:
        scalars = (n_valid, fresh.astype(jnp.int32), row, blk)
        grid = (state.shape[0], n_j)
    else:
        chained, slots = chain
        scalars = (n_valid, fresh.astype(jnp.int32),
                   chained.astype(jnp.int32), row, slots[row])
        grid = (n_j, n_valid.shape[0])

    def spec(block, head_dim, first=ROW):
        def at(i, j, nv, fr, rw, bk):
            on = nv[i] > 0
            idx = [0] * len(block)
            if first != SHARED:     # row r is slot r: the state's too
                idx[0] = jnp.where(on, i, rw[i])
            if head_dim is not None:
                idx[head_dim] = jnp.where(on, j, bk[i])
            return tuple(idx)

        def at_slot(j, i, nv, fr, ch, rw, sl):
            idx = [0] * len(block)
            if first != SHARED:
                idx[0] = (sl if first == SLOT else rw)[i]
            if head_dim is not None:
                idx[head_dim] = j
            return tuple(idx)
        return pl.BlockSpec(block, at if chain is None else at_slot)

    operands = (*operands, state)
    in_specs = [spec(*e) for e in per_row] + [spec(state_block, state_dim,
                                                  SLOT)]
    out = jax.ShapeDtypeStruct(out_shape, jnp.float32)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=[spec(*out_block), spec(state_block, state_dim, SLOT)],
        ),
        out_shape=[out, jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the scalars come first; the state is written where it stands
        input_output_aliases={len(scalars) + len(operands) - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*scalars, *operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x, dt, g, bm, cm, state, n_valid, fresh, slots=None,
              chained=None, interpret: bool = False):
    """x (B, C, H, P), dt and g = dt A (B, C, H), both 0 past n_valid; bm,
    cm (B, C, N); the state (slots, H, P, N); slots (B,) int32, the slot
    whose state row r advances (None: row r's), and chained (B,) bool as
    chained_rows gives it. Returns (y (B, C, H, P), the state aliased onto
    its input)."""
    b, c, h, p = x.shape
    n = state.shape[-1]
    if slots is None:
        slots = jnp.arange(b, dtype=jnp.int32)
        chained = jnp.zeros((b,), bool)
    hb = _head_block(h, HEAD_BLOCK)
    n_j = h // hb
    gsum = jnp.cumsum(g, axis=1)
    by_block = lambda a: a.reshape(b, c, n_j, hb).transpose(0, 2, 1, 3)  # noqa: E731
    dg = jnp.concatenate([by_block(dt), by_block(gsum)], axis=2)
    grow = gsum.transpose(0, 2, 1).reshape(b, n_j, 1, hb * c)
    end = jnp.broadcast_to(jnp.exp(gsum[:, -1])[..., None], (b, h, n))
    y, s = _call(
        functools.partial(_chunk_kernel, heads=hb, p=p), "ssd_chunk",
        (x.reshape(b, c, h * p), bm, cm, dg, grow, end),
        [((1, c, hb * p), 2), ((1, c, n), None), ((1, c, n), None),
         ((1, 1, 2 * c, hb), 1), ((1, 1, 1, hb * c), 1), ((1, hb, n), 1)],
        state.reshape(-1, h * p, n), (1, hb * p, n), (b, c, h * p),
        ((1, c, hb * p), 2), n_j, n_valid, fresh, interpret,
        chain=(chained, slots.astype(jnp.int32)))
    return y.reshape(b, c, h, p), s.reshape(state.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode(x, dt, g, bm, cm, state, n_valid, fresh,
               interpret: bool = False):
    """The same operands at C = 1. The decay rides in as rows of N lanes,
    so that the body multiplies it in without a broadcast over both axes."""
    b, _, h, p = x.shape
    n = state.shape[-1]
    hb = _head_block(h, DECODE_HEAD_BLOCK)
    dec = jnp.broadcast_to(jnp.exp(g[:, 0])[..., None], (b, h, n))
    y, s = _call(
        functools.partial(_step_kernel, heads=hb), "ssd_decode",
        (x[:, 0] * dt[:, 0, :, None], bm, cm, dec),
        [((1, hb, p), 1), ((1, 1, n), None), ((1, 1, n), None),
         ((1, hb, n), 1)],
        state, (1, hb, p, n), (b, h, p), ((1, hb, p), 1), h // hb,
        n_valid, fresh, interpret)
    return y[:, None], s


def ssd_scan(x, dt, a, bm, cm, state, n_valid, fresh, slots=None,
             chained=None, *, use_pallas: bool = False,
             interpret: bool = False):
    """Advance `state` (B, H, P, N) float32 by T tokens a row.

    x (B, T, H, P); dt (B, T, H), the step after its softplus; a (H,) < 0;
    bm, cm (B, T, G, N), a group's B and C; n_valid (B,) int32: the tokens
    of row b that count (0: a gated row); fresh (B,) bool: row b starts
    from zeros; slots (B,) int32 with chained = chained_rows(slots,
    n_valid): row b advances state[slots[b]], from the row before's end
    where it continues it (the module docstring says what the caller
    owes); None: row b is slot b. Returns (y (B, T, H, P) float32, WITHOUT
    the skip term D x, new state). Rows of `y` past n_valid are not
    meaningful; a gated row's are zeros."""
    b, t, h, p = x.shape
    groups, n = bm.shape[2:]
    f32 = jnp.float32
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    g = dt * a.astype(f32)
    x, bm, cm = (v.astype(f32) for v in (x, bm, cm))
    fresh = fresh & (n_valid > 0)
    live = (n_valid > 0)[:, None, None, None]
    kernels = use_pallas and ssd_supported(t, h, p, n, groups)
    tight = (x, dt, g, bm[:, :, 0], cm[:, :, 0], state,
             n_valid.astype(jnp.int32), fresh)
    if kernels and t > 1:
        y, state = ssd_chunk(*tight, slots, chained, interpret=interpret)
    elif kernels and slots is None:
        y, state = ssd_decode(*tight, interpret=interpret)
    elif slots is None:
        y, state = _ssd_xla(x, dt, g, bm, cm, state, fresh)
    else:   # ssd_decode takes no map: a one-token CHUNK under one, too
        y, state = _xla_mapped(_ssd_xla, (x, dt, g, bm, cm), state, fresh,
                               slots, chained, last_rows(chained, n_valid))
    return jnp.where(live, y, 0.0), state


def _xla_mapped(twin, ops, state, fresh, slots, chained, last):
    """An XLA twin under a slot map (ops/pallas_selective_scan.py's too):
    the rows one after another, each `twin(*row's ops, its state, fresh)`
    on its own, from its slot's state (gathered) or, where it continues the
    row before, from that row's end; a slot's last live row writes its
    state back."""
    def row(prev, xs):
        *ops, s0, fr, ch = xs
        y, s1 = twin(*(v[None] for v in ops), jnp.where(ch, prev, s0)[None],
                     fr[None])
        return s1[0], (y[0], s1[0])

    _, (y, ends) = lax.scan(row, jnp.zeros_like(state[0]),
                            (*ops, state[slots], fresh, chained))
    return y, state.at[jnp.where(last, slots, state.shape[0])].set(
        ends, mode="drop")


def _ssd_xla(x, dt, g, bm, cm, state, fresh):
    """The XLA twin: the same chunk algebra over (B, H) at once, chunks of
    MAX_CHUNK tokens one after another (a token with dt = 0 neither decays
    nor writes). A row that is not live has dt = 0 everywhere and keeps its
    state to the bit. It is also what runs where the kernels' contract ends:
    any T (Engine.prefill hands the CLI's `inference` and `generate`
    segments of up to 256 tokens) and any number of groups (a header key;
    the one configuration served so far has one)."""
    t, h = x.shape[1], x.shape[2]
    rep = h // bm.shape[2]
    bh, ch = (jnp.repeat(v, rep, axis=2) for v in (bm, cm))   # (B, T, H, N)
    s = jnp.where(fresh[:, None, None, None], 0.0, state)
    ein = functools.partial(jnp.einsum, precision=_HI)
    outs = []
    for lo in range(0, t, MAX_CHUNK):
        sl = slice(lo, min(lo + MAX_CHUNK, t))
        c = sl.stop - sl.start
        gs = jnp.cumsum(g[:, sl], axis=1)                      # (B, C, H)
        i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
        j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
        decay = jnp.exp(jnp.where(
            (i >= j)[None, :, :, None],
            gs[:, :, None, :] - gs[:, None, :, :], -jnp.inf))  # (B, i, j, H)
        m = ein("bihn,bjhn->bijh", ch[:, sl], bh[:, sl]) * decay
        xdt = x[:, sl] * dt[:, sl, :, None]
        y = (ein("bijh,bjhp->bihp", m, xdt)
             + jnp.exp(gs)[..., None] * ein("bihn,bhpn->bihp", ch[:, sl], s))
        w = dt[:, sl] * jnp.exp(gs[:, -1:] - gs)
        s = (jnp.exp(gs[:, -1])[..., None, None] * s
             + ein("bjhp,bjhn->bhpn", x[:, sl] * w[..., None], bh[:, sl]))
        outs.append(y)
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, 1)), s
