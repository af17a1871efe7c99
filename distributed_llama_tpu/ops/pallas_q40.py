"""Pallas TPU kernel: fused Q40-dequant matmul.

TPU-native replacement for the reference's hot Q40xQ80 NEON/AVX2 kernel
(ref: src/funcs.cpp:286-385). The reference streams 4.5-bit weights through
SIMD integer dot products; here the same HBM-traffic win comes from reading
the packed nibbles (0.5625 B/weight + 1/16 scale byte) and dequantizing in
VMEM right before the MXU contraction — the dense weight matrix never
touches HBM.

Decode at batch=1 makes this op VPU-bound on the unpack arithmetic (the
packed read itself is far under the HBM roofline), so the kernel minimizes
per-byte VPU work with an algebraic restructure. With the reference decoder
value = (nibble - 8) * scale (ref: src/quants.cpp:166-179):

    y = x_lo·(lo-8)s + x_hi·(hi-8)s
      = x_lo·(lo s) + x_hi·(hi s) - 8 Σ_b s[d,b]·xsum[b]

so the per-element subtractions vanish: the hot loop touches each packed
byte with only widen, and, shift, two converts, and two scale-muls. The
correction term is a tiny (t, nb)x(td, nb) dot of per-block activation sums
against the scales already resident in VMEM. (A further restructure that
feeds the raw byte pk = lo + 16*hi to the MXU saves the `and` but amplifies
f32 rounding ~36x through cancellation — rejected. bf16 VPU arithmetic
measures *slower* than f32 — the VPU is f32-native.)

Decode roofline (measured v5e): the ~7 VPU ops per packed byte above cap
the kernel at ~475 GB/s of packed-byte throughput (v5e VPU ~3.8 Tops/s),
and whole-model decode measures 409-472 GB/s effective — the kernel runs
at its VPU design ceiling, not the 819 GB/s HBM ceiling. For PREFILL
chunks (t=256, bf16 MXU feeds) the kernel also wins decisively: 7B
2048-token prefill measures 6317 tok/s fused vs 2299 tok/s on the XLA
dequant-einsum path (2.7x) — the round-3 kernel measured 5771 tok/s with
the nibble unpack (VPU) fully serialized against the MXU contraction;
sub-tiling the td=256 tile (see _n_sub) overlaps the two for +9.5%
whole-model (+41% on the w1/w3 matmul alone). Cutting ops/byte further
means int8 MXU dots — measured and REJECTED: an int4-unpack -> int8
dot_general variant runs 4x slower at t=1 (82 vs 331 GB/s packed; PERF.md
section 6, "Kernel experiments not taken") because Mosaic has no efficient
int8 gemv path; Q80 weights would unpack cheaper (~2.5 ops/byte) but carry
1.9x the bytes, a net loss. 7B Q40 decode lands at ~9.5 ms/token accordingly.

Layout: QuantizedTensor packed is nibble-position-major, stored flattened
(d, m) uint8 with lane order m = j*nb + b (see quants/jax_codec.py) — the
kernel consumes the HBM buffer in place, no reshape/re-tile.
Consequences inside the kernel:
  * the per-block scale expansion s16[d, m] = s[d, m % nb] lays a row's nb
    scales 16 times side by side (an element-wise repeat of the block-major
    order would need a shape cast Mosaic cannot lower): `pltpu.repeat(s,
    16)`, whole-vreg copies where nb is whole lane tiles (a 4096-wide
    contraction: 128 blocks) and a few aligned pieces a lane tile at 32 and
    64; under 32 blocks, where every lane tile is five or more shifted
    pieces, a product with a 0/1 matrix on the MXU, to the same bits
    (_spread_scales, _spreads_on_mxu);
  * no weight shuffle is needed; instead the small activation is pre-split
    outside the kernel into matching lo/hi orders:
      x_lo[t, j*nb + b] = x[t, b*32 + j]       (low-nibble elements)
      x_hi[t, j*nb + b] = x[t, b*32 + 16 + j]  (high-nibble elements)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..quants.jax_codec import QuantizedTensor

LANES = 128
# output-dim tile candidates, largest first (larger tiles amortize grid
# overhead; measured td=1024 ~7% faster than td=256 on v5e)
TILE_D_CANDIDATES = (1024, 512, 256, LANES)
# above this token count the op is FLOPs-amortized and the XLA dequant path
# is used instead; also bounds the kernel's (t, m) VMEM blocks (ADVICE r1)
MAX_T = 256


def _f16_bits_to_f32(u: jnp.ndarray) -> jnp.ndarray:
    """Decode f16 bit patterns (int32-widened uint16) to f32 exactly with
    integer ops + bitcast — Mosaic has no f16 arithmetic, and keeping the
    scales 2 bytes wide in HBM saves ~10% of the kernel's traffic (measured
    1.19x; PERF.md section 6, that table). Handles normals and subnormals; inf/nan
    cannot occur in Q40 scales."""
    sign = (u & 0x8000) << 16
    e = (u >> 10) & 0x1F
    m = u & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        sign | ((e + 112) << 23) | (m << 13), jnp.float32)
    sub = jnp.where(sign != 0, -1.0, 1.0) * (
        m.astype(jnp.float32) * (2.0 ** -24))
    return jnp.where(e == 0, sub, normal)


def _spreads_on_mxu(nb: int) -> bool:
    """Which spread of a row's block scales over the tile's lanes
    (_spread_scales) a contraction of nb blocks takes: the MXU's where more
    than four of the 16 copies share a lane tile, `pltpu.repeat`'s
    otherwise. Read on the v5e at 4096 rows, both kernels, 8 rows in f32
    and 64 / 256 in bf16 (`tools/microbench.py q40_shapes`; PERF.md
    section 6, PR 40): at 12, 16, 24 and 30 blocks the MXU spread wins every
    reading (1.2-2.8 x: repeat takes 17 us a 4096-row tile at 24 blocks
    where the MXU takes 6.6); at 32 and 64, where repeat is four and two
    aligned pieces a lane tile, it ties or loses (+6 to -28 % over two
    calls); at 48 it wins 17-30 % at 8 rows and ties or loses under the
    bf16 feed (+4 to -11 %)."""
    return nb < LANES // 4


def _spread_matrix(nb: int, bf16_parts: int):
    """The 0/1 matrix R[k, m] = (k % nb == m % nb) that lays a row's nb
    block scales, cut into `bf16_parts` bf16 terms side by side along k,
    over lcm(nb, LANES) lanes (all 16 nb where that does not divide them):
    the lane pattern m % nb repeats from there on. None where the shape
    keeps pltpu.repeat. A constant operand of the call, fetched once (made
    in the kernel from iotas and their remainders at every grid step it
    read 0-13 % slower)."""
    if not _spreads_on_mxu(nb):
        return None
    width = math.lcm(nb, LANES)
    if (16 * nb) % width:
        width = 16 * nb
    k, m = np.arange(bf16_parts * nb), np.arange(width)
    return jnp.asarray(k[:, None] % nb == m % nb, jnp.bfloat16)


def _spread_scales(s, spread):
    """s16[d, j*nb + b] = s[d, b]: a row's nb block scales laid 16 times
    side by side, the packed tile's lane order.

    Where nb is whole lane tiles that is `pltpu.repeat`, whole-vreg copies
    (spread None). Where many copies share a lane tile (_spreads_on_mxu)
    repeat assembles every lane tile of every row from shifted pieces of
    an nb-lane strip and the shifts bound the kernel, so the MXU, idle at
    few rows, places them: s is cut into bf16 terms that sum to it exactly (an
    f16 scale's 11 significant bits are two terms of 8, a hand-built f32
    scale's 24 three), laid side by side along the contraction, and
    multiplied by _spread_matrix. Every output element is a sum of products
    by 1.0 and 0.0 accumulated in f32: repeat's value (a scale of -0.0
    comes out as +0.0, which no sum with a term that is not zero shows)."""
    nb = s.shape[1]
    if spread is None:
        return pltpu.repeat(s, 16, axis=1)
    # each term but the last is the 8 leading bits of what is left, cut by
    # a mask: a float32 -> bf16 -> float32 round trip is one a compiler may
    # remove; what is left for the last has 8 bits or fewer
    parts, rest = [], s
    for _ in range(spread.shape[0] // nb - 1):
        head = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rest, jnp.int32) & -0x10000,
            jnp.float32)
        parts.append(head.astype(jnp.bfloat16))
        rest = rest - head
    parts.append(rest.astype(jnp.bfloat16))
    one = jnp.dot(jnp.concatenate(parts, axis=1), spread,
                  preferred_element_type=jnp.float32)
    copies = 16 * nb // spread.shape[1]
    return pltpu.repeat(one, copies, axis=1) if copies > 1 else one


def _dequant(pk_u8, s_raw, spread, *, scales_u16, mxu_bf16):
    """Dequantise a (TD, M) packed tile: (wl, wh, s), the low- and
    high-nibble halves times their block scales (bf16 under mxu_bf16) and
    the decoded (TD, NB) scales, what _contract multiplies the pre-split
    activations by.

    (A round-5 re-try of the pk-substitution — fold lo = pk - 16*hi into
    the contraction to drop the `& 0xF` — was REJECTED twice over: timing
    FLAT at 1.000x (the and-op co-issues off the critical path) and 6.4%
    relative error (DEFAULT-precision dots pass f32 operands through the
    MXU as bf16; pk's 8 value bits fill the mantissa and the 16x
    cancellation amplifies the truncation). Full record: PERF.md section
    6, "Kernel experiments not taken".)"""
    pk = pk_u8.astype(jnp.int32)                         # (TD, M=16*nb)
    lo = (pk & 0xF).astype(jnp.float32)
    hi = (pk >> 4).astype(jnp.float32)
    if scales_u16:
        s = _f16_bits_to_f32(s_raw.astype(jnp.int32))    # (TD, NB)
    else:
        s = s_raw                                        # f32 (hand-built)
    s16 = _spread_scales(s, spread)                      # (TD, NB) -> (TD, M)
    wl, wh = lo * s16, hi * s16
    if mxu_bf16:
        # multi-token (prefill) chunks are MXU-bound: f32 feeds cap the MXU
        # at 1/4 of its bf16 rate (v5e 49 vs 197 TFLOP/s), so cast the
        # dequantized tiles down. 4-bit weight levels and bf16 engine
        # activations fit bf16 exactly; only requested when the caller's
        # out_dtype is bf16 (decode t=1 stays f32/VPU-bound)
        wl, wh = wl.astype(jnp.bfloat16), wh.astype(jnp.bfloat16)
    return wl, wh, s


def _contract(x_lo, x_hi, xsum, wl, wh, s, *, out_dtype):
    """The pre-split activations against a dequantised tile (_dequant).
    Activations must already be in the contraction dtype (bf16 when the
    tile is)."""
    # DEFAULT precision: single-pass MXU feed (HIGHEST = multi-pass f32
    # decomposition, measured ~5x slower for the whole kernel); operands are
    # engine-bf16 activations and 4-bit weights, so nothing real is lost
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT,
    )
    acc = dot(x_lo, wl)                                  # (T, TD)
    acc += dot(x_hi, wh)
    acc += dot(xsum, s) * jnp.float32(-8.0)              # fold every (nib-8) offset
    return acc.astype(out_dtype)


def _n_sub(td: int, m: int, mxu_bf16: bool) -> int:
    """Sub-tile count for the unpack/MXU interleave (prefill mode only).

    Splitting the (td, m) packed tile into n_sub row sub-tiles and issuing
    each sub-tile's dot right after its unpack lets the MXU chew on sub-tile
    i while the VPU unpacks i+1. Measured on v5e at t=256
    (PERF.md section 6, "Kernel experiments not taken"; the w2-shape probe):
      * w1/w3 shape (d=11008, m=2048, td=256): n_sub=8 wins 1.41x
        (n_sub=2: 1.37x, n_sub=4: 1.38x)
      * w2 shape (d=4096, m=5504, td=256): n_sub=2 wins 2.26x
        (36.6 vs 82.9 ms/call); n_sub=4 measured SLOWER than whole-tile
        and n_sub=8 OOMs scoped VMEM (16.77M > 16M limit)
      * attention-projection shape (d=4096, m=2048, td=1024): every
        sub-tile variant flat or worse (0.89-0.98x) — whole-tile stays
    so: sub-tile only the td=256 tile, 8-way when the packed tile is at
    most 512 KB (m <= 2048, the measured-safe regime), else 2-way. Decode
    (t=1) is VPU-bound with nothing to overlap, so f32 mode stays
    whole-tile. 32-row sub-tiles satisfy the uint8 sublane tile."""
    if not (mxu_bf16 and td == 256):
        return 1
    return 8 if td * m <= (1 << 19) else 2


def _subtiled_write(x_lo, x_hi, xsum, load_packed, load_scales, rest,
                    *, out_dtype, scales_u16, mxu_bf16, held=None):
    """Dequantise and contract per 1/n_sub row slice of the packed tile,
    writing each output column slice as soon as its dot is issued.
    load_packed / load_scales map a row slice -> loaded sub-block (ref
    slicing stays at the call site because the expert kernel's refs carry a
    leading dim); `rest` is the call's last refs: the spread matrix where
    the shape takes one (_spread_matrix), and the output.

    `held`, the stationary expert call's (first, followed, wl, wh, s):
    scratch that keeps the tile DEQUANTISED from one grid step to the
    next. The FIRST row tile of an expert's run does what every tile does
    without it, and leaves what it unpacked in the scratch if another tile
    of its expert FOLLOWS; every other tile contracts against what it finds
    there: the same sub-slices, the same dots, the same bits."""
    *spread, out_ref = rest
    spread = spread[0][:] if spread else None
    td = out_ref.shape[-1]
    n_sub = _n_sub(td, x_lo.shape[-1], mxu_bf16)
    if mxu_bf16:
        x_lo, x_hi = x_lo.astype(jnp.bfloat16), x_hi.astype(jnp.bfloat16)
    h = td // n_sub
    slices = [slice(i * h, (i + 1) * h) for i in range(n_sub)]
    first, followed, *kept = held or (None, None)

    def unpack_and_contract():
        made = []
        for sl in slices:
            w = _dequant(load_packed(sl), load_scales(sl), spread,
                         scales_u16=scales_u16, mxu_bf16=mxu_bf16)
            out_ref[:, sl] = _contract(x_lo, x_hi, xsum, *w,
                                       out_dtype=out_dtype)
            made.append((sl, w))
        return made

    if held is None:
        unpack_and_contract()
        return

    # three bodies, one a grid step: a first tile that no tile of its
    # expert follows runs the very text every tile runs without the
    # scratch (a group of one tile pays nothing for the order); read on the
    # chip, a store under a condition of its own inside ONE first-tile body
    # cost a tile of three 0.6 us more than this (PERF.md section 6, PR 49)
    @pl.when(first & jnp.logical_not(followed))
    def _():
        unpack_and_contract()

    @pl.when(first & followed)
    def _():
        for sl, w in unpack_and_contract():
            for ref, part in zip(kept, w):
                ref[sl, :] = part

    @pl.when(jnp.logical_not(first))
    def _():
        for sl in slices:
            out_ref[:, sl] = _contract(
                x_lo, x_hi, xsum, *(ref[sl, :] for ref in kept),
                out_dtype=out_dtype)


def _kernel(x_lo_ref, x_hi_ref, xsum_ref, packed_ref, scales_ref, *rest,
            nb, out_dtype, scales_u16, mxu_bf16):
    _subtiled_write(
        x_lo_ref[:], x_hi_ref[:], xsum_ref[:],
        lambda sl: packed_ref[sl, :], lambda sl: scales_ref[sl, :], rest,
        out_dtype=out_dtype, scales_u16=scales_u16, mxu_bf16=mxu_bf16)


def _gather_rows(src_ref, first_row, panels, gathered):
    """Row r of each `gathered` scratch <- row src[first_row + r] of its
    whole-array panel: a tile's activations of the gathered expert call
    (_q40_call), one dynamic sublane load and one store a lane tile of a
    row, the values `x[src]` would have laid out."""
    for r in range(gathered[0].shape[0]):
        row = pl.ds(src_ref[first_row + r], 1)
        for panel, out in zip(panels, gathered):
            out[r:r + 1, :] = panel[row, :]


def _expert_kernel(tiles_ref, used_ref, *rest, nb, out_dtype, scales_u16,
                   mxu_bf16, stationary, gathers):
    # the row tile of this grid step (_q40_call's two orders); its expert
    # is consumed by the index maps
    j = pl.program_id(1 if stationary else 0)
    if gathers:
        src_ref, *rest = rest
        # read here: the interpreter has no program_id under a pl.when
        first_block = pl.program_id(1) == 0
    *x_refs, packed_ref, scales_ref = rest[:5]
    rest = rest[5:]
    held = None
    if stationary:
        # the scratch comes last. Row tiles run innermost and a group's
        # tiles are consecutive: a tile whose expert is the one of the tile
        # before it finds that expert's block i dequantised there
        *rest, wl_ref, wh_ref, s_ref = rest
        e_j = tiles_ref[j]
        first = (j == 0) | (e_j != tiles_ref[jnp.maximum(j - 1, 0)])
        after = jnp.minimum(j + 1, pl.num_programs(1) - 1)
        followed = (j + 1 < used_ref[0]) & (e_j == tiles_ref[after])
        held = (first, followed, wl_ref, wh_ref, s_ref)
    if gathers:
        *rest, g_lo, g_hi, g_sum = rest

    # a row tile past the used ones holds no live pair: its index maps name
    # the blocks already resident (_q40_call), and its body is skipped
    @pl.when(j < used_ref[0])
    def _():
        feed = x_refs
        if gathers:
            # the panels hold the TOKEN rows, whole; the tile's rows are
            # gathered from them when the grid reaches the tile: once a
            # tile where row tiles run outermost (its weight blocks stream
            # past what block 0 gathered), once a step where they run
            # innermost (one tile after the other against one block)
            feed = (g_lo, g_hi, g_sum)
            gather = functools.partial(
                _gather_rows, src_ref, j * g_lo.shape[0], x_refs, feed)
            if stationary:
                gather()
            else:
                pl.when(first_block)(gather)

        _subtiled_write(
            *(ref[:] for ref in feed),
            lambda sl: packed_ref[0, sl, :], lambda sl: scales_ref[0, sl, :],
            rest, held=held,
            out_dtype=out_dtype, scales_u16=scales_u16, mxu_bf16=mxu_bf16)


# f32 unpack intermediates are the dominant VMEM consumers (~4 bytes per
# packed byte each): a (td, m) packed tile past this many bytes overflows
# the ~16 MB scoped-VMEM budget
_TILE_BYTES_MAX = 2_300_000
# The compiler's default scoped-VMEM limit, which _TILE_BYTES_MAX budgets
# the TILES against. The whole-array activation panels (x_lo, x_hi, xsum)
# are extra: XLA usually hands them over already in VMEM and they cost the
# kernel nothing, but where its memory-space assignment decides otherwise
# they land in the kernel's own scoped allocation — at 256 rows 4.2 MB
# (m = 2048) to 14.7 MB (m = 7168), which took a Mixtral prefill program
# to 19.2 MB and failed its compile once the step programs' schedule
# changed (PERF.md section 6, PR 27). Both entry points (_q40_call)
# therefore ask for the default plus their panels.
_SCOPED_VMEM_DEFAULT = 16 * 2**20


def _tile_d(d: int, m: int) -> int:
    """Output-dim tile: Mosaic wants the last block dim to be a multiple of
    128 lanes OR the whole array dim. Preference order, every choice within
    the scoped-VMEM budget (_TILE_BYTES_MAX packed bytes per tile):

      1. the largest candidate that divides d (an exact grid);
      2. d whole (grid of 1) when the entire (d, m) weight fits the budget
         — small or narrow weights no candidate divides;
      3. a candidate over a `cdiv` grid whose last block is ragged: the
         largest one wasting at most d/16 padded rows, else the one that
         pads least. tp row shards land here (Llama-2-7B w1/w3 at tp=4 ->
         2752 rows, a 32000-vocab head -> 8000, Llama-3's -> 32064). The
         ragged block is safe because output row i depends on weight row i
         alone: the out-of-bounds rows Pallas pads in feed only output
         columns that are dropped on writeback.

    A shape that fits none of these raises — it must never fall through to
    a whole-weight block the chip's compiler refuses."""
    # Scales whose block count a row (m / 16) is not whole lane tiles cost
    # more VMEM: pltpu.repeat lays its 16 copies side by side in the lanes,
    # and copies that do not start on a lane tile are shifted through
    # temporaries. Compiled for a described v5e at 8 rows, a (1024, 1920)
    # tile (120 blocks) needs 17.8 MiB of scoped VMEM where (1024, 2048)
    # needs 13.0, and (512, 1920) 8.4 where (512, 2048) needs 5.4; a
    # (256, 7168) tile (448 blocks, 3.5 lane tiles) needs 11.2. The charge
    # below, one f32 copy of the tile's scales (4 B for each 16 packed
    # bytes, a quarter), is a BUDGET that sends the one overflowing tile a
    # size down and moves no other (tests/test_pallas_q40.py pins every
    # configuration's tiles); it is not a model of those temporaries. A
    # shape that spreads its scales on the MXU (under 32 blocks) has no
    # such temporaries and is charged all the same: charged, its widest tile
    # is 1024 x 620 bytes, under a third of the budget, and moves none
    cost = m + m // 4 if (m // 16) % LANES else m
    fits = [t for t in TILE_D_CANDIDATES if t * cost <= _TILE_BYTES_MAX]
    for t in fits:
        if d % t == 0:
            return t
    if d * m <= _TILE_BYTES_MAX:
        return d
    if not fits:
        raise ValueError(
            f"q40 kernel: no output tile of a ({d}, {m}) packed weight fits "
            f"the scoped-VMEM budget ({LANES} x {m} > {_TILE_BYTES_MAX})")
    padded = {t: -(-d // t) * t - d for t in fits}
    for t in fits:
        if padded[t] * 16 <= d:
            return t
    return min(fits, key=lambda t: (padded[t], -t))


def supports_pallas(w: QuantizedTensor, t: int = 1) -> bool:
    """Kernel preconditions: 2D weight (d, m) — callers slice leading
    (layer/expert) dims first — and a token count small enough that decode/
    short-prefill VMEM blocks fit (longer segments are FLOPs-amortized and
    take the XLA dequant path)."""
    return w.packed.ndim == 2 and t <= MAX_T


def _split_activation(x: jnp.ndarray, nb: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(T, n) -> lo/hi halves in kernel lane order m = j*nb + b."""
    t = x.shape[0]
    x4 = x.reshape(t, nb, 2, 16)                         # [t, b, half, j]
    x_lo = x4[:, :, 0, :].transpose(0, 2, 1).reshape(t, nb * 16)
    x_hi = x4[:, :, 1, :].transpose(0, 2, 1).reshape(t, nb * 16)
    return x_lo, x_hi


# Rows of the row tile whose grouped expert call runs stationary
# (_unpacks_once): the sublane tile, the smallest tile expert_row_tile gives
_STATIONARY_ROW_TILE = 8


def _unpacks_once(tm: int, token_rows: int) -> bool:
    """Whether a grouped expert call runs STATIONARY (_q40_call): weight
    blocks outermost, so that the consecutive row tiles of one expert are
    contracted against ONE dequantised copy of its block, where row tiles
    outermost unpack the expert once a tile. Decided from the call's shapes
    alone: a group can span tiles at all (the program's token rows exceed
    the tile's `tm`: never in a decode step, whose row tile holds every row
    and whose rows bring distinct experts a token), and the tile is the
    sublane tile's 8 rows.

    What the order gains or costs goes with the row tiles an expert's group
    fills, which is the TRAFFIC's and no shape's (`tools/microbench.py
    q40_orders`, PERF.md section 6, PR 49 after review: us a used tile on
    the v5e under the bf16 feed of a 256-row chunk, row tiles outermost ->
    stationary, at 1 / 2 / 3 / 5 tiles an expert | at the ~1.4 an EVEN
    router's Poisson groups fill): kimi-linear's gate (8-row tiles, one
    1024 x 1152 block, 72 scale blocks a row) 5.2 -> 5.1 / 5.4 -> 3.6 /
    5.4 -> 3.0 / 5.5 -> 2.5 | 5.4 -> 4.3; its down projection (nine 256-row
    blocks, a grid step each) 4.6 -> 5.1 / 4.4 -> 4.9 / 4.3 -> 4.1 / 4.4 ->
    3.6 | 4.3 -> 4.5; sarvam's gate and down (16-row tiles, two and four
    1024-row blocks) 7.0 -> 7.7 / 7.7 -> 7.6 / 7.6 -> 7.1 / 7.9 -> 6.8 |
    7.8 -> 7.8 and 9.9 -> 10.4 / 9.0 -> 9.8 / 9.1 -> 8.7 / 9.2 -> 7.6 | 9.3
    -> 9.4; Mixtral's gate (64-row tiles) 58 -> 69 / 58 -> 63 / 57 -> 57 /
    57 -> 50 | 57 -> 62; granite's down 6.8 -> 8.0 / 7.3 -> 5.7 / 7.1 -> 5.0
    / 7.0 -> 4.4 | 7.1 -> 7.3 (its 64-row tile holds 1.8 even shares: one
    tile an expert). A following tile saves the fetch and the unpack and
    still pays the MXU's pass over the block, so it saves most where the
    unpack bounds a tile (kimi's 72 blocks a row, which `pltpu.repeat` lays
    out in shifted pieces) and little where that pass does (sarvam's whole
    lane tiles); a first tile pays up to 12-18 % for the order at one or two
    tiles an expert.

    So the rule is as narrow as what a cell has shown END TO END: the 8-row
    tile is kimi-linear's chunk (a router four times as wide as the held
    share, 8 rows an expert when even), whose cell's file routes a chunk to
    8 of 64 held experts, 5.2 tiles an expert: `itl_p50_ms` -8 %,
    `ttft_p50_ms` -11 %. THAT GAIN IS THE CELL'S SKEW: at an even router
    the three projections together read 15.1 -> 13.1 us a tile (-13 % of
    the kernel, a few per cent of a chunk). A wider tile keeps row tiles
    outermost and the kernel text it had: sarvam's 16-row tiles ran
    stationary in this PR's first hand-in and moved nothing end to end at
    its cell's 3.1 tiles an expert (the kernel -8 %, 0.9 ms of a 69 ms
    chunk); Mixtral's and granite's chunks hold 1.0-1.2. A cell that shows
    a gain there is what widens this."""
    return token_rows > tm == _STATIONARY_ROW_TILE


def _q40_call(x, w: QuantizedTensor, e, out_dtype, interpret, used=None,
              token_rows=None, src=None):
    """The pallas_call both entry points share: `w` is one (d, m) packed
    weight (e None, the `_kernel` body) or an (E, d, m) stack read through
    the scalar-prefetch operands of the blocks' index maps
    (`_expert_kernel`): `e` names the expert of each ROW TILE of x (one
    tile of all rows where it is a scalar), `used` how many leading tiles
    hold a live row. Everything else — the activation split, the output
    tile, the blocks' memory space, the scoped-VMEM request — is one
    decision for both.

    `src` (an expert call's alone) makes the call GATHER: x holds the
    step's token rows and row r of the call is x[src[r]]. The split below
    runs over the token rows, once; the panels stay whole in VMEM under a
    constant index map, src rides in with the prefetched scalars, and a
    used tile copies its rows out of the panels into scratch
    (_expert_kernel): the values, and so the bits, of x[src] laid out
    beforehand (rounded to x's dtype, as an array in HBM is)."""
    d, m = w.packed.shape[-2:]
    nb = m // 16
    n = nb * 32

    lead = x.shape[:-1]
    t = 1
    for s in lead:
        t *= s
    xf = x.reshape(t, n).astype(jnp.float32)
    if src is not None and x.dtype != jnp.float32:
        # x[src] was an array of x's dtype in HBM. Without it the TPU
        # compiler fuses x's producer (the Q80 round trip's bf16 product)
        # into the cast above and keeps the product's float32 bits
        # (xla_allow_excess_precision): read on the chip, granite's check
        # moved from 0.062543589 to 0.061926940 (PERF.md section 6, PR 50).
        # The panels hold the values the gathered rows held
        kind = jnp.finfo(x.dtype)
        xf = jax.lax.reduce_precision(xf, kind.nexp, kind.nmant)
    x_lo, x_hi = _split_activation(xf, nb)
    xsum = (x_lo + x_hi).reshape(t, 16, nb).sum(axis=1)  # (t, nb) per-block sums

    td = _tile_d(d, m)
    scales_u16 = w.scales.dtype == jnp.uint16
    scales = w.scales if scales_u16 else w.scales.astype(jnp.float32)
    # multi-token chunks with a bf16 consumer take the bf16 MXU feed (see
    # _dequant); single-token decode and f32 consumers keep exact f32.
    # The PROGRAM's token rows decide, not the pair rows a grouped call
    # lays them out in (token_rows)
    mxu_bf16 = (jnp.dtype(out_dtype) == jnp.bfloat16
                and (t if token_rows is None else token_rows) >= 16)

    # index maps take the grid index and, in the expert call, the prefetched
    # scalars' refs; the packed weight is already stored flattened (d, m) —
    # consumed in place, and so is the stack: block (e, i, 0) of it
    block = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    n_i = pl.cdiv(d, td)
    scratch = []
    out_rows = t if src is None else src.shape[0]
    if e is None:
        kernel, name, prefetched = _kernel, "q40_matmul", ()
        tm, grid = t, (n_i,)
        w_block, w_at = (td,), lambda i: (i, 0)
        x_at, out_at = (lambda i, *_: (0, 0)), (lambda i, *_: (0, i))
    else:
        name = "q40_expert_matmul"
        tiles = jnp.atleast_1d(e).astype(jnp.int32)
        n_tiles = tiles.shape[0]
        prefetched = (tiles, jnp.atleast_1d(
            n_tiles if used is None else used).astype(jnp.int32))
        if src is not None:
            prefetched += (src.astype(jnp.int32),)
        tm = out_rows // n_tiles
        stationary = _unpacks_once(tm, t if token_rows is None else token_rows)
        kernel = functools.partial(_expert_kernel, stationary=stationary,
                                   gathers=src is not None)

        # Row tiles outermost: a tile's activations are fetched once and
        # its expert's weight blocks stream past them. A tile past the used
        # ones names the LAST block of the last used tile, which is what
        # the step before it left resident, so it moves nothing (the rule
        # ops/pallas_attention._last_attended gives a gated row).
        # STATIONARY (_unpacks_once), weight blocks outermost: steps (i, j)
        # and (i, j + 1) of one expert name the same block (e_j, i), which
        # is fetched once and kept dequantised in scratch (_expert_kernel),
        # and a tile's activations are fetched once a block; a tile past the
        # used ones names block i of the last used tile
        def at(*step):
            step, (tiles_ref, used_ref) = step[:2], step[2:4]
            j, i = reversed(step) if stationary else step
            live = used_ref[0] > 0 if stationary else j < used_ref[0]
            j = jnp.maximum(jnp.minimum(j, used_ref[0] - 1), 0)
            return j, jnp.where(live, i, n_i - 1), tiles_ref[j]

        def w_at(*step):
            j, i, e_j = at(*step)
            return e_j, i, 0

        def x_at(*step):
            return (at(*step)[0] if src is None else 0), 0

        def out_at(*step):
            return at(*step)[:2]

        grid = (n_tiles, n_i)
        if src is not None:
            scratch = [pltpu.VMEM((tm, m), jnp.float32),
                       pltpu.VMEM((tm, m), jnp.float32),
                       pltpu.VMEM((tm, nb), jnp.float32)]
        if stationary:
            grid = grid[::-1]
            wide = jnp.bfloat16 if mxu_bf16 else jnp.float32
            scratch += [pltpu.VMEM((td, m), wide), pltpu.VMEM((td, m), wide),
                        pltpu.VMEM((td, nb), jnp.float32)]
        w_block = (1, td)
    spread = _spread_matrix(nb, 2 if scales_u16 else 3)
    consts = () if spread is None else (spread,)
    # rows of an activation block: the call's row tile, or every token row
    # where the tile's rows are gathered in the kernel
    xm = tm if src is None else t
    specs = dict(
        grid=grid,
        in_specs=[
            block((xm, m), x_at),
            block((xm, m), x_at),
            block((xm, nb), x_at),
            block((*w_block, m), w_at),
            block((*w_block, nb), w_at),
            *(block(c.shape, lambda *_: (0, 0)) for c in consts),
        ],
        out_specs=block((tm, td), out_at),
    )
    if scratch:
        specs["scratch_shapes"] = scratch
    if prefetched:
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched), **specs))

    # the activation panels: whole and fetched once in the dense call, one
    # row tile (every token row where the call gathers) double-buffered in
    # the expert call; and the scratch: a gathered tile, the stationary
    # call's dequantised block (a scale row fills a lane tile)
    panels = 4 * xm * (2 * m + nb) * (1 if e is None else 2)
    held = sum(math.prod((*b.shape[:-1], -(-b.shape[-1] // LANES) * LANES))
               * b.dtype.itemsize for b in scratch)
    out = pl.pallas_call(
        functools.partial(kernel, nb=nb, out_dtype=out_dtype,
                          scales_u16=scales_u16, mxu_bf16=mxu_bf16),
        **specs,
        out_shape=jax.ShapeDtypeStruct((out_rows, d), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * out_rows * d * n,
            bytes_accessed=(d * m + d * nb * 2 + 2 * t * m * 4
                            + out_rows * d * 4),
            transcendentals=0,
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SCOPED_VMEM_DEFAULT + panels + held),
        interpret=interpret,
        name=name,
    )(*prefetched, x_lo, x_hi, xsum, w.packed, scales, *consts)

    return out if src is not None else out.reshape(*lead, d)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def q40_matmul(
    x: jnp.ndarray,
    w: QuantizedTensor,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """y[..., d] = sum_n x[..., n] * W[d, n] with W in packed Q40 form.

    Matches matmul()'s convention (ref: src/funcs.cpp:413-454); x may have any
    leading dims. Weight stays packed through HBM; dequant happens per-tile in
    VMEM fused into the MXU contraction.
    """
    return _q40_call(x, w, None, out_dtype, interpret)


# Most rows a row tile of a grouped expert call holds (expert_row_tile).
# Measured on the chip (PERF.md section 6, PR 38): a tile is one pass over
# its expert's weights and costs the same from 8 to 64 rows (the unpack
# bounds it: 57 us at Mixtral's 14336 x 4096, 51 us at 8 rows), half as
# much again at 128 and three times at 256, where the MXU does
EXPERT_ROW_TILE = 64


def expert_row_tile(group_rows: float) -> int:
    """Rows of one row tile of a grouped expert call, from the rows an
    expert's group holds when the program's tokens are all real and route
    evenly (token rows x top-k / the router's width): the power of two at
    or over it, so that a group is mostly ONE pass over its expert's
    weights, within a sublane tile's 8 rows and EXPERT_ROW_TILE. 8 in the
    served decode steps, 64 in Mixtral's 256-row chunk (top-2 of 8), 16 in
    sarvam's (top-8 of 128): what is not kernel costs by the rows of tile
    padding, an expert's ragged tile among them."""
    tile = 8
    while tile < min(group_rows, EXPERT_ROW_TILE):
        tile *= 2
    return tile


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "token_rows"))
def q40_expert_matmul(
    x: jnp.ndarray,
    w: QuantizedTensor,    # stacked (E, d, m) packed / (E, d, nb) scales
    e: jnp.ndarray,        # i32 expert of each row tile of x (or of all x)
    used: jnp.ndarray | None = None,  # i32 leading row tiles that are live
    out_dtype=jnp.float32,
    interpret: bool = False,
    token_rows: int | None = None,
    src: jnp.ndarray | None = None,   # i32 row of x each row is gathered from
) -> jnp.ndarray:
    """y[r, d] = sum_n x[r, n] * W[e[r // tile], d, n]: every routed expert
    matmul of models/transformer._moe_ffn on a plain single-shard stack,
    ONE call a projection (the reference computes just the active experts
    too, ref: src/grok1-tasks.cpp:128-143).

    x holds the step's live (token, expert) pairs sorted by expert, each
    expert's group starting on a row tile of x.shape[0] / len(e) rows
    (models/transformer._grouped_experts lays them out; expert_row_tile
    sizes the tile). Tile j is
    multiplied by expert e[j] alone; the first `used` tiles hold the live
    pairs and the rest are skipped — no block of theirs is fetched, no body
    runs — so an expert no live token chose is never read and a row is
    computed for its own experts only. Rows of a used tile past its group's
    end are computed and mean nothing; a skipped tile's rows are never
    written. A scalar `e` is one tile of all rows: x @ W[e]^T.
    `token_rows`, the PROGRAM's token rows, decides the operand feed
    (_q40_call) whatever the pair rows number.

    With `src` (len(e) x tile,) the call lays the rows out ITSELF: x is
    the step's token rows (rows, n), row r of the layout above is x[src[r]]
    and y has src's rows. What XLA does to the activations before the
    kernel (the float32 split into the packed lane order, the block sums)
    then runs over the token rows once, not over a pair buffer whose size
    is the program's and not the traffic's, and a tile past `used` gathers
    nothing. The gate and up projections, whose input is a token's own
    row, come so; the down projection's input is born in the layout.

    The tiles' experts ride in as scalar-prefetch operands and the block
    index maps offset straight into the (E, d, m) HBM stack, so the kernel
    reads the expert's packed bytes IN PLACE. The alternative —
    lax.dynamic_index_in_dim then q40_matmul — materializes a full HBM copy
    of the expert's weight before the kernel can read it: a Pallas call
    takes whole buffers, so the slice is read and written once more than
    the matmul reads it (36 % of mixtral-8x7b-12l's device time, PERF.md
    section 6, PR 31).
    """
    return _q40_call(x, w, e, out_dtype, interpret, used, token_rows, src)
