"""Pallas TPU kernel: flash attention over the KV cache (decode AND chunked
prefill).

TPU-native replacement for the reference's serial per-head attention loop
(ref: src/llama2-tasks.cpp:54-94). XLA's fused decode attention kept
assigning the KV cache a head-minor layout (32 kv heads in the 128-lane
dim -> 4x lane waste, ~75 GB/s effective on v5e); and for prefill chunks the
dense path materializes the full (B, T, KVH, G, S) score tensor in HBM
(ops/attention.py:56-63 — 67 MB per layer at T=256/S=2048). This kernel
fixes both by construction: each grid step streams the (SB, hs)
key/value panels of a TILE of KV heads — hs=128 exactly fills the lanes —
against those heads' (T*G, hs) query panels, and keeps the running softmax
state in VMEM scratch, so scores never touch HBM.

Shapes: q (B, T, H, hs) with H = KVH * G (GQA group, ref kvMul:
src/llama2-tasks.cpp:60), laid out here as (B, KVH, T*G, hs) row panels;
k/v cache (B, KVH, S, hs), read as it stands. Grid is
(B, KVH/kh, S/SB) with the sequence dimension innermost: blocks
(1, kh, SB, hs) of K and V and (1, kh, T*G, hs) of q and out, scratch
acc (kh, T*G, hs), m and l (kh, T*G, 1) carry the online-softmax state
across S blocks of the same heads (flash decomposition), reset at block 0
and finalized at the last block. Every KV head of a row shares the row's
position, so the clamp, the skip and the gate below decide once a tile
what they decided once a head; a head's arithmetic is what it was, one dot
batched over the tile's heads. `head_tile` cuts kh from the call's shapes:
the largest divisor of KVH whose step fits FLASH_VMEM_BYTES (8 of 8 at
7B-class GQA shapes, decode and 32-token chunk; all 30 of an MHA model's
30 in bf16; 1 for one KV head, which is the grid of a head a step;
a few where T*G is 1,024 and the score tile sets it). `flash_grid` is the
grid, for the call and for whoever counts its steps
(/stats attn_grid_steps_*).

Causality: query row r (= token t*G + g) attends to cache positions
s <= pos0[b] + r//G — the cache is already updated at the chunk's
positions; positions beyond the last query — including cache slots not yet
written — are masked with -inf before the softmax.

HBM scaling with context: pos rides in as a scalar-prefetch operand and the
K/V index maps CLAMP the sequence-block index at the block containing the
chunk's LAST query position — Mosaic skips the DMA when consecutive grid
steps map to the same block, so the kernel reads ~pos bytes of cache, not
the full preallocated seq_len (at 7B/seq 2048 that dead read was
~1 GB/token early in a session); the repeated block's scores are fully
masked, and a pl.when skips its compute.

A gated row (pos >= S: how the scheduler parks a slot that takes no part
in a call; its cache writes drop and nothing reads its output) costs ONE
block: `_last_attended` sends it to block 0 in the index map and the
pl.when, unmasked, so its softmax state stays finite. The plain clamp
would put pos == S in the LAST block, and every empty slot would stream
the whole cache in every layer. What such a row still costs is its grid
steps, B x KVH/kh x S/SB a call whatever the positions: ~0.18 us each past
a row's last block (DMA elided, compute skipped), which at a head a step
(16,384 a decode step of a 32-layer model at 8 slots of 4,096) was most of
a short-context decode step's attention and at a tile of 8 heads is 2,048;
and block 0's K and V of every head, read and attended for nobody (left:
skipping it too needs `_done` to write zeros for such a row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEF_BLOCK_S = 512
NEG_INF = -1e30
F8_DTYPE = jnp.float8_e4m3fn


def _f8_bits_to(u8, out_dtype):
    """e4m3fn bits (uint8) -> out_dtype, vectorized f32-bit reassembly.

    Mosaic's own fp8 `astype` on v5e (no native fp8) lowers to a slow
    conversion that cost +0.74 ms/layer/token at 8k fill — the whole fp8
    KV-cache regression recorded before PR 1 (tools/exp_f8_flash.py:
    astype 4.447 vs 3.686 ms/call for this decode, bit-exact). 16-bit vector shifts are
    also unsupported, so the reassembly stays in 32-bit lanes: a normal
    number's f32 bits are sign<<31 | (exp+120)<<23 | mant<<20; subnormals
    (mag < 8) take an int->float ladder (value = mant * 2^-9, exact in
    3 mantissa bits). Writes saturate (models/transformer._to_cache_dtype)
    and seeding boundaries sanitize (saturate_f8_nan_codes below), so
    NaN/inf bit patterns never occur in the cache."""
    i = u8.astype(jnp.int32)
    sign = (i & 0x80) << 24
    mag = i & 0x7F
    normal = (mag << 20) + (120 << 23)
    sub = mag.astype(jnp.float32) * jnp.float32(2.0 ** -9)
    bits = jnp.where(mag < 8, jax.lax.bitcast_convert_type(sub, jnp.int32),
                     normal) | sign
    f = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return f if out_dtype == jnp.float32 else f.astype(out_dtype)


def saturate_f8_nan_codes(x):
    """Map e4m3fn NaN bit patterns (magnitude 0x7F) to the saturated max
    (+-448) so they can never reach ``_f8_bits_to``, which decodes the
    0x7F magnitude as a finite 480.0 (ADVICE r5).

    The kernel's correctness rests on the invariant that every cache
    producer saturates (models/transformer._to_cache_dtype) — true for
    all in-engine writes, but NOT enforceable for bytes that arrive from
    OUTSIDE a forward: a checkpoint-restored session file
    (Engine.load_session) or a prefix-cache arena seed
    (Engine.slot_seed_prefix) could carry 0x7F from a buggy or foreign
    producer, and one such byte at position p poisons every later
    attention read past p. This is the guard every cache-SEEDING
    boundary applies (Engine._seed_guard); non-f8 inputs pass through
    untouched. Saturating (rather than asserting) keeps the seeding
    paths jittable — a device-side assert would be a host callback in
    the serving hot path."""
    if x.dtype != F8_DTYPE:
        return x
    bits = jax.lax.bitcast_convert_type(x, jnp.uint8)
    mag = bits & jnp.uint8(0x7F)
    fixed = jnp.where(mag == jnp.uint8(0x7F),
                      (bits & jnp.uint8(0x80)) | jnp.uint8(0x7E), bits)
    return jax.lax.bitcast_convert_type(fixed.astype(jnp.uint8), F8_DTYPE)


# cap on T*G query rows per head panel: bounds the (rows, SB) f32 score tile
# in VMEM (1024x512x4 = 2 MB; acc another 512 KB). Prefill chunks above it
# fall back to the dense path — the engine's default chunk (256) stays under
# for G <= 4
MAX_Q_ROWS = 1024
# what the blocks, score tile and scratch of ONE grid step may hold
# (_tile_bytes): head_tile fits the most KV heads a step under it. Over the
# compiler's default scoped limit (16 MiB), so the call states its own,
# with room above the budget for what _tile_bytes does not count (of v5e's
# 128 MiB; mla_attention's is the same)
FLASH_VMEM_BYTES = 24 * 2**20
_FLASH_VMEM_LIMIT = 48 * 2**20


def _last_attended(pos, last_tok, s):
    """Last cache position a panel of query rows attends: `pos` is the
    slot's first query position, `last_tok` the panel's last token within
    the chunk, `s` the cache's length. The K/V index map clamps at its
    block and the kernel skips the blocks past it. A gated slot
    (pos >= S: its writes were dropped and its output is never read)
    attends block 0 alone, unmasked, so that it costs one block and its
    rows stay finite (skipping block 0 too would leave 0 / 0)."""
    last = pos + last_tok
    return jnp.where(pos >= s, 0, last)


def _kernel(pos_ref, q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref,
            *, sb, n_sb, t, g, scale, out_dtype):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = pos_ref[pl.program_id(0)]  # first query row's absolute position

    # blocks entirely past the last query position are fully masked: their
    # K/V DMA was clamped away (see index maps) and their compute is skipped
    @pl.when(j * sb <= _last_attended(pos, t - 1, sb * n_sb))
    def _accumulate():
        q = q_ref[0]                               # (kh, T*G, hs)
        k = k_ref[0]                               # (kh, SB, hs)
        v = v_ref[0]
        if k.dtype == F8_DTYPE:
            # e4m3 cache: HBM/VMEM/DMA stay narrow; reinterpret the block's
            # bits in-register (free) and do the exact upcast as cheap
            # 32-bit-lane VPU work before the dot (Mosaic's fp8 astype was
            # a 2.3x f8 stall; an XLA-side whole-cache bitcast
            # materialized a copy per step and cost another ~50%)
            k = _f8_bits_to(jax.lax.bitcast_convert_type(k, jnp.uint8),
                            q.dtype)
            v = _f8_bits_to(jax.lax.bitcast_convert_type(v, jnp.uint8),
                            q.dtype)
        elif k.dtype != q.dtype:
            # other sub-bf16 cache dtypes: generic per-block upcast
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)

        # one dot batched over the tile's heads: a head's arithmetic is
        # what its own 2-D dot gave (bit for bit on the chip), and Mosaic
        # runs it faster than a loop over the heads, unrolled or not
        # (tools/microbench.py flash_grid; PERF.md section 6, PR 54)
        dot = functools.partial(
            jax.lax.dot_general,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        scores = dot(q, k, dimension_numbers=(
            ((2,), (2,)), ((0,), (0,)))) * scale   # (kh, T*G, SB)

        # causal: row r is query token r//G at absolute position pos + r//G
        row_pos = pos + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1) // g
        s_pos = j * sb + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        scores = jnp.where(s_pos <= row_pos, scores, NEG_INF)

        m_prev = m_ref[:]                          # (kh, T*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)    # masked cols underflow to 0
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = dot(p.astype(v.dtype), v, dimension_numbers=(
            ((2,), (1,)), ((0,), (0,))))           # (kh, T*G, hs)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(j == n_sb - 1)
    def _done():
        out_ref[0] = (acc_ref[:] / l_ref[:]).astype(out_dtype)


def _block_s(s: int) -> int:
    """SB=512 measured best across fills on v5e (a larger SB trades fewer
    grid steps for a bigger clamp over-read at low fill; A/B at seq 8192
    showed no net win)."""
    for sb in (DEF_BLOCK_S, 256, 128):
        if s % sb == 0:
            return sb
    return s


def flash_supported(t: int, h: int, kvh: int) -> bool:
    """Kernel precondition: the (T*G, SB) score tile must fit the VMEM
    budget. T == 1 (decode) always qualifies."""
    return t * (h // kvh) <= MAX_Q_ROWS


def _tile_bytes(kh: int, rows: int, sb: int, hs: int, cache_bytes: int,
                q_bytes: int) -> int:
    """VMEM a grid step of `kh` heads holds: K and V blocks and the q and
    out panels, each double-buffered; the float32 score tile and its p;
    acc, m and l (a (rows, 1) float32 leaf pads to whole (8, 128) tiles);
    and, where the cache is narrower than q, K's and V's blocks on their
    way up through 32-bit lanes (_f8_bits_to: three planes in flight)."""
    pad8 = -(-rows // 8) * 8
    blocks = 2 * 2 * kh * (sb * hs * cache_bytes + pad8 * hs * q_bytes)
    scores = 2 * kh * pad8 * sb * 4
    scratch = kh * pad8 * (hs + 2 * 128) * 4
    upcast = 2 * kh * sb * hs * 3 * 4 if cache_bytes < q_bytes else 0
    return blocks + scores + scratch + upcast


def head_tile(kvh: int, rows: int, sb: int, hs: int, cache_bytes: int,
              q_bytes: int) -> int:
    """KV heads one grid step holds: the largest divisor of `kvh` whose
    blocks, score tile and scratch (_tile_bytes) fit FLASH_VMEM_BYTES, for
    `rows` = T*G query rows a head, sequence blocks of `sb` and a cache of
    `cache_bytes` an element. 1 where nothing larger fits (or kvh is 1):
    the grid and the blocks of a head a step."""
    for kh in range(kvh, 1, -1):
        if kvh % kh == 0 and _tile_bytes(
                kh, rows, sb, hs, cache_bytes, q_bytes) <= FLASH_VMEM_BYTES:
            return kh
    return 1


def flash_grid(b: int, t: int, h: int, kvh: int, s: int, hs: int,
               cache_dtype, q_dtype=jnp.bfloat16) -> tuple[int, int, int]:
    """The grid of one flash_attention call on q (b, t, h, hs) of
    `q_dtype` and caches (b, kvh, s, hs) of `cache_dtype`: (rows, head
    tiles, sequence blocks). The call runs it and the scheduler counts it
    (/stats attn_grid_steps_*, Engine.attn_grid_steps)."""
    from .attention import is_narrow_cache

    sb = _block_s(s)
    cache_dtype = jnp.dtype(cache_dtype)
    if not is_narrow_cache(cache_dtype):
        q_dtype = cache_dtype  # flash_attention lifts q to a wider cache
    kh = head_tile(kvh, t * (h // kvh), sb, hs, cache_dtype.itemsize,
                   jnp.dtype(q_dtype).itemsize)
    return b, kvh // kh, s // sb


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def flash_attention(
    q: jnp.ndarray,        # (B, T, H, hs) — rotated queries
    k_cache: jnp.ndarray,  # (B, KVH, S, hs)
    v_cache: jnp.ndarray,  # (B, KVH, S, hs)
    q_pos: jnp.ndarray,    # (B, T) absolute position of each query token
    interpret: bool = False,
    scale: float | None = None,
    slots: jnp.ndarray | None = None,  # (B,) the cache slot row b attends
) -> jnp.ndarray:
    """Causal attention of T query tokens against the cache; returns
    (B, T, H, hs). Matches ops/attention.decode_attention semantics,
    `scale` too (None: head_size ** -0.5) —
    q_pos rows must be contiguous (pos0[b] + arange(T), which is how every
    engine path builds them — models/transformer.forward).
    slots: row b attends the cache rows of slot slots[b] (several rows may
    be consecutive segments of one slot: each attends what the rows before
    it wrote in the same program, and the mask hides what the rows after
    it did); the K/V index map reads the map, no cache row is gathered or
    copied. None, the identity, is the program it always was."""
    b, t, h, hs = q.shape
    kvh, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    assert flash_supported(t, h, kvh), (t, g)

    # kernel dots need matching operand dtypes (lax.dot_general does not
    # promote); compute dtype and cache dtype may differ. Wider caches
    # (f32) lift q; narrower caches (fp8) are lifted per-block in-kernel —
    # q and the softmax state never drop below the compute dtype
    from .attention import is_narrow_cache

    grid = flash_grid(b, t, h, kvh, s, hs, k_cache.dtype, q.dtype)
    if not is_narrow_cache(k_cache.dtype):
        q = q.astype(k_cache.dtype)
    kh, n_sb = kvh // grid[1], grid[2]
    sb = s // n_sb
    # (B, T, KVH, G, hs) -> (B, KVH, T*G, hs) row panels, one per kv head
    qh = (q.reshape(b, t, kvh, g, hs).transpose(0, 2, 1, 3, 4)
          .reshape(b, kvh, t * g, hs))
    pos = q_pos[:, 0].astype(jnp.int32)

    # index maps take the prefetched scalars last: (pos,), or (pos, slots)
    def kv_index(i, ht, j, pos_ref, *sl):
        # clamp at the block containing the chunk's last query position:
        # steps past it re-map to the same block, so Mosaic elides their HBM
        # copy (the dead-read fix)
        last = _last_attended(pos_ref[i], t - 1, s)
        return (sl[0][i] if sl else i, ht, jnp.minimum(j, last // sb), 0)

    kernel, scalars = _kernel, (pos,)
    if slots is not None:
        kernel, scalars = _mapped_kernel, (pos, slots.astype(jnp.int32))
    panel = pl.BlockSpec((1, kh, t * g, hs),
                         lambda i, ht, j, *_: (i, ht, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            kernel, sb=sb, n_sb=n_sb, t=t, g=g,
            scale=scale or 1.0 / (hs ** 0.5), out_dtype=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=[
                panel,
                pl.BlockSpec((1, kh, sb, hs), kv_index),
                pl.BlockSpec((1, kh, sb, hs), kv_index),
            ],
            out_specs=panel,
            scratch_shapes=[
                pltpu.VMEM((kh, t * g, hs), jnp.float32),
                pltpu.VMEM((kh, t * g, 1), jnp.float32),
                pltpu.VMEM((kh, t * g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, t * g, hs), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_LIMIT),
        interpret=interpret,
        name="flash_attention",
    )(*scalars, qh, k_cache, v_cache)

    return (out.reshape(b, kvh, t, g, hs).transpose(0, 2, 1, 3, 4)
            .reshape(b, t, h, hs))


def _mapped_kernel(pos_ref, slots_ref, *refs, **kw):
    """`_kernel` under a slot map: only the K/V index map reads it."""
    del slots_ref
    _kernel(pos_ref, *refs, **kw)


# -- latent (absorbed) attention over a one-leaf cache ------------------------

# query rows (token x head) one grid step holds. A 32-token chunk of 64
# heads is 2048 rows: flash_attention's one panel per head would not fit
# VMEM (and MAX_Q_ROWS sends such a chunk to the dense path), so the rows
# are tiled and every tile streams the cache once.
MLA_ROW_TILE = 512
_MLA_VMEM_BYTES = 48 * 2**20


def mla_row_tile(rows: int) -> int:
    if rows <= MLA_ROW_TILE:
        return rows
    for tr in (MLA_ROW_TILE, 256, 128):
        if rows % tr == 0:
            return tr
    return 0


def mla_supported(t: int, h: int) -> bool:
    """Kernel precondition: the T x H query rows fall into whole tiles."""
    return mla_row_tile(t * h) > 0


def _mla_last(pos, i, *, tr, t, h, s):
    """Last cache position row tile i of a slot attends: the tile's last
    row belongs to token ((i + 1) * tr - 1) // h of the chunk."""
    return _last_attended(
        pos, jnp.minimum(((i + 1) * tr - 1) // h, t - 1), s)


def _mla_kernel(pos_ref, q_ref, c_ref, out_ref, acc_ref, m_ref, l_ref,
                *, sb, n_sb, tr, t, h, vw, scale, out_dtype):
    """One (row tile, cache block) step of absorbed latent attention: a
    cache row [c~ ; k_r] is the key of every head, and its first `vw`
    columns are the value too. Same online softmax as _kernel."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = pos_ref[pl.program_id(0)]
    last = _mla_last(pos, i, tr=tr, t=t, h=h, s=sb * n_sb)

    @pl.when(j * sb <= last)
    def _accumulate():
        q = q_ref[0]                               # (TR, W)
        c = c_ref[0]                               # (W, SB): a token a column
        if c.dtype == F8_DTYPE:
            c = _f8_bits_to(jax.lax.bitcast_convert_type(c, jnp.uint8),
                            q.dtype)
        elif c.dtype != q.dtype:
            c = c.astype(q.dtype)
        dot = functools.partial(
            jax.lax.dot_general,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        nn = (((1,), (0,)), ((), ()))
        # the latent part (vw wide, whole lane tiles of q) and the rope
        # key's narrow tail contract apart: each is a shape the MXU takes
        scores = (dot(q[:, :vw], c[:vw], dimension_numbers=nn)
                  + dot(q[:, vw:], c[vw:], dimension_numbers=nn)) * scale

        row_pos = pos + (i * tr + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0)) // h
        s_pos = j * sb + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(s_pos <= row_pos, scores, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = dot(p.astype(c.dtype), c[:vw],
                 dimension_numbers=(((1,), (1,)), ((), ())))
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(j == n_sb - 1)
    def _done():
        out_ref[0] = (acc_ref[:] / l_ref[:]).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("v_width", "scale", "interpret"))
def mla_attention(
    q: jnp.ndarray,        # (B, T, H, W): absorbed queries [W_uk^T q_n ; q_r]
    cache_t: jnp.ndarray,  # (B, 1, W, S): [normed latent ; rotated rope key]
    #                        a COLUMN a token (the leaf as the TPU holds it,
    #                        sequence minor: ops/pallas_kv_write.py)
    q_pos: jnp.ndarray,    # (B, T) absolute positions, contiguous per row
    *,
    v_width: int,          # the latent's width: the value is its first rows
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal attention of T x H query rows over ONE latent cache leaf
    (handed in sequence-minor, as kv_cache_write_seq_minor leaves it),
    already written at the chunk's positions; returns the attended latents
    (B, T, H, v_width), which W_uv unfolds outside. The cache is read up
    to the last query position of each row tile (the clamp of
    flash_attention) once per tile."""
    b, t, h, w = q.shape
    s = cache_t.shape[3]
    assert cache_t.shape[1:3] == (1, w), cache_t.shape
    rows = t * h
    tr = mla_row_tile(rows)
    assert tr > 0, (t, h)
    sb = _block_s(s)
    n_sb = s // sb

    from .attention import is_narrow_cache

    if not is_narrow_cache(cache_t.dtype):
        q = q.astype(cache_t.dtype)
    qh = q.reshape(b, rows, w)
    ch = cache_t.reshape(b, w, s)
    pos = q_pos[:, 0].astype(jnp.int32)

    def c_index(bi, i, j, pos_ref):
        last = _mla_last(pos_ref[bi], i, tr=tr, t=t, h=h, s=s)
        return (bi, 0, jnp.minimum(j, last // sb))

    out = pl.pallas_call(
        functools.partial(
            _mla_kernel, sb=sb, n_sb=n_sb, tr=tr, t=t, h=h, vw=v_width,
            scale=scale, out_dtype=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, rows // tr, n_sb),
            in_specs=[
                pl.BlockSpec((1, tr, w), lambda bi, i, j, p: (bi, i, 0)),
                pl.BlockSpec((1, w, sb), c_index),
            ],
            out_specs=pl.BlockSpec((1, tr, v_width),
                                   lambda bi, i, j, p: (bi, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((tr, v_width), jnp.float32),
                pltpu.VMEM((tr, 1), jnp.float32),
                pltpu.VMEM((tr, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_MLA_VMEM_BYTES),
        interpret=interpret,
        name="mla_attention",
    )(pos, qh, ch)
    return out.reshape(b, t, h, v_width)


def flash_decode_attention(
    q: jnp.ndarray,        # (B, T=1, H, hs)
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    q_pos: jnp.ndarray,    # (B, 1)
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-position decode attention — the T=1 case of flash_attention
    (kept as a named entry point: decode is the latency-critical path)."""
    return flash_attention(q, k_cache, v_cache, q_pos, interpret=interpret)
