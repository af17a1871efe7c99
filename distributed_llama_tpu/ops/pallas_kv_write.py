"""Pallas TPU kernel: write a step's new K/V rows into the slot cache IN PLACE.

The functional form of the reference's in-place cache write
(ref: src/llama2-tasks.cpp:38-44) for the batched, per-row-position step
programs. The XLA form — a drop-mode scatter, `cache.at[b, :, idx].set(...)`
— is correct but XLA's layout assignment re-lays the operand of ANY
XLA-level update of a few rows (scatter and `dynamic_update_slice` alike):
every layer's whole K and V cache went from layout {3,2,1,0} to {3,1,2,0}
and back, four cache-sized copies a layer and 62 % of a Mistral-7B decode
step on v5e (PERF.md section 6, PR 27). A `pallas_call` pins its operands
to the row-major layout `flash_attention` already reads, and
`input_output_aliases` makes the donated cache the output buffer, so the
step programs keep no cache-shaped copy at all.

Mosaic moves HBM in whole sublane tiles — `row_tile` rows of the sequence
axis (8 for f32, 16 for bf16, 32 for fp8; a one-row DMA compiles only for
f32), so the kernel is a read-modify-write of the aligned tiles a row's
window `pos[b] .. pos[b]+T-1` touches: grid `(B, tiles a T-row window can
touch)`, cache block `(1, KVH, R, hs)` in and out through ONE index map,
body `out = where(position is in the window, new row, old row)`. A chunk's
new rows arrive already placed at their offset inside those tiles
(`_window_tokens`, a gather over the step's few rows in XLA; decode's
single row needs none), token-major as the projection left them — a head
is a lane slice — so the body is a select and is bit-exact for every
value: a one-hot matmul would place rows as cheaply but turns one `inf`
into NaN in its neighbours (0 * inf).

In and out are the same buffer, and Pallas prefetches the next input block
before the last output block is written back. That is safe because every
visit writes its block's complete final content: distinct (b, tile) pairs
are disjoint, and the visits the clamp at the context's last tile
duplicates select from the same window tile, so they write equal bytes.

With a slot map (`slots`, the prefill chunk program of an engine whose
scheduler chains rows: runtime/scheduler.py) row b writes the cache rows
of slot `slots[b]`, and several rows may name ONE slot at consecutive
windows. The argument above must then hold for (slot, tile) pairs, and the
map-less index map breaks it: a window that starts on a tile boundary
touches one tile fewer than `_n_tiles` visits, the spare visit selects
nothing and re-writes the OLD bytes of the tile after the window, which is
the first tile of the next chained row. So with a map a row's visits stop
at its own last tile (`_tile`, `own=True`: the spare visits duplicate that
tile and write equal bytes), and the caller keeps three promises that
`visited_blocks` lets a host-side test check without running a kernel
(interpret mode walks the grid in order and can never show the race):
chained rows start on multiples of a width that is whole tiles, so their
windows share no tile; live rows of different slots are different slots;
and a gated row (pos == S: it re-writes the old bytes of its slot's last
tile) names a slot no live row names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_tile(dtype) -> int:
    """Rows of the sequence axis in one packed sublane tile of `dtype`."""
    return 32 // jnp.dtype(dtype).itemsize


def kv_write_supported(seq_len: int, dtype) -> bool:
    """Kernel precondition: whole row tiles cover the context (a ragged
    last tile would need a masked DMA). Callers fall back to the scatter."""
    return seq_len % row_tile(dtype) == 0


def _n_tiles(t: int, r: int) -> int:
    """Most R-row tiles a window of T rows can touch at any start."""
    return (t + r - 2) // r + 1


def _window_tokens(off, rows: int, t: int):
    """(B, rows, 1) token index per window row: row b's token i sits at
    window row off[b] + i; the other rows name some token and are never
    selected."""
    src = jnp.arange(rows, dtype=jnp.int32)[None, :] - off[:, None]
    return jnp.clip(src, 0, t - 1)[:, :, None]


def _tile(p, j, *, r: int, t: int, last: int, own: bool):
    """The cache tile visit j of a row whose window starts at p reads and
    writes: tile p // r + j, held inside the context. own (a slot map is
    passed): held inside the row's OWN window too, so that no visit lands
    on the tile after it, which a chained row of the same slot may own.
    The one place the kernel, its index maps and `visited_blocks` take the
    tile from."""
    return _window_tile(p, j, r=r, t=t, last=last, own=own)[1]


def _window_tile(p, j, *, r: int, t: int, last: int, own: bool):
    """(the window's first tile, `_tile`): the window's index map wants
    both, and the map-less program's text is the operations written here,
    in this order."""
    first = p // r
    tile = first + j
    if own:
        tile = jnp.minimum(tile, (p + t - 1) // r)
    return first, jnp.clip(tile, 0, last)


def visited_blocks(pos, slots, *, t: int, seq_len: int, dtype,
                   own: bool = True):
    """(B, visits, 2) int32: the (slot, tile) cache block each grid step of
    a slot-mapped `kv_cache_write` reads and writes back, from the index
    map itself (`_tile`); no kernel runs. Two rows that visit one block
    write it twice, from copies that may both be in flight. own=False: the
    tiles of the map-less index map, which chained rows could not use."""
    r = row_tile(dtype)
    pos = jnp.asarray(pos, jnp.int32)[:, None]
    tiles = _tile(pos, jnp.arange(_n_tiles(t, r), dtype=jnp.int32)[None, :],
                  r=r, t=t, last=seq_len // r - 1, own=own)
    return jnp.stack(
        [jnp.broadcast_to(jnp.asarray(slots, jnp.int32)[:, None],
                          tiles.shape), tiles], axis=-1)


def _kernel(pos_ref, kc_ref, vc_ref, kw_ref, vw_ref, ko_ref, vo_ref,
            *, r, last, t, own=False):
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    tile = _tile(pos, j, r=r, t=t, last=last, own=own)
    kvh, _, hs = kc_ref.shape[1:]
    row = tile * r + jax.lax.broadcasted_iota(jnp.int32, (r, hs), 0)
    hit = (row >= pos) & (row < pos + t)
    # the window's rows are token-major, (rows, KVH*hs): a head is a lane
    # slice of them. Decode's one row broadcasts over the tile.
    for c_ref, w_ref, o_ref in ((kc_ref, kw_ref, ko_ref),
                                (vc_ref, vw_ref, vo_ref)):
        for h in range(kvh):
            o_ref[0, h] = jnp.where(hit, w_ref[0, :, h * hs:(h + 1) * hs],
                                    c_ref[0, h])


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_cache_write(
    k_cache: jnp.ndarray,  # (B, KVH, S, hs)
    v_cache: jnp.ndarray,  # (B, KVH, S, hs)
    k_new: jnp.ndarray,    # (B, T, KVH, hs), already in the cache dtype
    v_new: jnp.ndarray,    # (B, T, KVH, hs)
    pos: jnp.ndarray,      # (B,) first position row b writes; >= 0
    slots: jnp.ndarray | None = None,  # (B,) the cache slot row b writes
    interpret: bool = False,
):
    """Row b's T new rows land at positions pos[b] .. pos[b]+T-1 of its
    cache rows; positions >= S are dropped (a gated row passes pos[b] == S)
    — what `.at[b, :, pos[b] + arange(T)].set(..., mode="drop")` does.
    slots: row b writes the cache rows of slot slots[b] instead of its own
    (the module docstring says what the caller owes); None, the identity,
    is the program it always was. Returns the two caches, aliased onto the
    inputs."""
    b, kvh, s, hs = k_cache.shape
    t = k_new.shape[1]
    r = row_tile(k_cache.dtype)
    assert kv_write_supported(s, k_cache.dtype), (s, k_cache.dtype)
    assert k_new.dtype == k_cache.dtype and v_new.dtype == v_cache.dtype
    n = _n_tiles(t, r)
    last = s // r - 1
    pos = pos.astype(jnp.int32)
    k_new = k_new.reshape(b, t, kvh * hs)
    v_new = v_new.reshape(b, t, kvh * hs)

    own = slots is not None
    tiles = dict(r=r, t=t, last=last, own=own)

    # index maps take the prefetched scalars last: (pos,), or (pos, slots)
    def cache_index(i, j, p, *sl):
        return (sl[0][i] if own else i, 0, _tile(p[i], j, **tiles), 0)

    if t == 1:
        # decode: the row itself is the window, whichever tile it lands in
        window_block = pl.BlockSpec((1, 1, kvh * hs),
                                    lambda i, j, *_: (i, 0, 0))
    else:
        src = _window_tokens(pos % r, n * r, t)
        k_new = jnp.take_along_axis(k_new, src, axis=1)
        v_new = jnp.take_along_axis(v_new, src, axis=1)

        def window_index(i, j, p, *_):
            # the window tile that lands on the (clamped) cache tile; where
            # the clamp leaves nothing to write, any tile does
            first, tile = _window_tile(p[i], j, **tiles)
            return (i, jnp.clip(tile - first, 0, n - 1), 0)

        window_block = pl.BlockSpec((1, r, kvh * hs), window_index)
    cache_block = pl.BlockSpec((1, kvh, r, hs), cache_index)
    shape = jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype)
    kernel = functools.partial(_kernel, r=r, last=last, t=t)
    scalars = (pos,)
    if own:
        kernel = functools.partial(_mapped_kernel, r=r, last=last, t=t)
        scalars = (pos, slots.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, n),
            in_specs=[cache_block, cache_block, window_block, window_block],
            out_specs=[cache_block, cache_block],
        ),
        out_shape=[shape, shape],
        # the scalars come first; the caches are written where they stand
        input_output_aliases={len(scalars): 0, len(scalars) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kv_cache_write",
    )(*scalars, k_cache, v_cache, k_new, v_new)


def _mapped_kernel(pos_ref, slots_ref, *refs, **kw):
    """`_kernel` under a slot map: the map moves blocks (the index maps
    read it), the body only stops a row's visits at its own last tile."""
    del slots_ref
    _kernel(pos_ref, *refs, own=True, **kw)


# -- a cache leaf whose SEQUENCE is the minor dimension ----------------------
#
# The latent cache's rows are 576 wide (SARVAM_MLA: 512 + 64), four and a
# half lane tiles, and the TPU's own layout for a (B, 1, S, 576) array puts
# S, not 576, in the lanes (no padding that way; row-major would pad every
# row to 640). A pallas_call on the row-major view would make XLA re-lay
# the whole leaf before and after it, the very copies this file removed.
# So the step programs hand the kernels the leaf as XLA holds it, viewed
# (B, KVH, W, S) — the transpose is a bitcast — and a token is a COLUMN:
# the same read-modify-write, over 128-lane tiles of the sequence.

LANES = 128


def kv_write_seq_minor_supported(seq_len: int) -> bool:
    return seq_len % LANES == 0


def _seq_minor_kernel(pos_ref, c_ref, w_ref, o_ref, *, last, t):
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    tile = jnp.clip(pos // LANES + j, 0, last)
    lane = tile * LANES + jax.lax.broadcasted_iota(
        jnp.int32, c_ref.shape[1:], 1)
    hit = (lane >= pos) & (lane < pos + t)
    # decode's one column broadcasts over the tile's lanes
    o_ref[0] = jnp.where(hit, w_ref[0], c_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_cache_write_seq_minor(
    cache_t: jnp.ndarray,  # (B, KVH, W, S): a leaf, sequence minor
    new: jnp.ndarray,      # (B, T, KVH, W), already in the cache dtype
    pos: jnp.ndarray,      # (B,) first position row b writes; >= 0
    interpret: bool = False,
):
    """kv_cache_write for ONE leaf held sequence-minor: row b's T new rows
    become columns pos[b] .. pos[b]+T-1; positions >= S are dropped.
    Returns the leaf, aliased onto the input."""
    b, kvh, w, s = cache_t.shape
    t = new.shape[1]
    assert kv_write_seq_minor_supported(s), s
    assert new.dtype == cache_t.dtype, (new.dtype, cache_t.dtype)
    n = _n_tiles(t, LANES)
    last = s // LANES - 1
    pos = pos.astype(jnp.int32)
    new = new.reshape(b, t, kvh * w).transpose(0, 2, 1)      # (B, KW, T)

    def cache_index(i, j, p):
        return (i, 0, jnp.clip(p[i] // LANES + j, 0, last))

    if t == 1:
        window_block = pl.BlockSpec((1, kvh * w, 1), lambda i, j, p: (i, 0, 0))
    else:
        src = _window_tokens(pos % LANES, n * LANES, t)      # (B, lanes, 1)
        new = jnp.take_along_axis(new, src.transpose(0, 2, 1), axis=2)

        def window_index(i, j, p):
            first = p[i] // LANES
            return (i, 0, jnp.clip(jnp.clip(first + j, 0, last) - first,
                                   0, n - 1))

        window_block = pl.BlockSpec((1, kvh * w, LANES), window_index)
    cache_block = pl.BlockSpec((1, kvh * w, LANES), cache_index)
    flat = cache_t.reshape(b, kvh * w, s)
    out = pl.pallas_call(
        functools.partial(_seq_minor_kernel, last=last, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n),
            in_specs=[cache_block, window_block],
            out_specs=cache_block,
        ),
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kv_cache_write",
    )(pos, flat, new)
    return out.reshape(cache_t.shape)
