"""Flash attention kernel (decode + chunked prefill) vs the XLA
decode_attention oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.ops.attention import decode_attention
from distributed_llama_tpu.ops.pallas_attention import (
    F8_DTYPE, FLASH_VMEM_BYTES, _block_s, _last_attended, _mla_last,
    _tile_bytes, flash_attention, flash_decode_attention, flash_grid,
    flash_supported, head_tile)


@pytest.mark.parametrize("b,h,kvh,s,pos", [
    (1, 8, 8, 256, 255),    # full cache, MHA
    (1, 8, 2, 256, 255),    # GQA group 4
    (1, 8, 8, 256, 0),      # only position 0 visible
    (2, 8, 4, 512, 100),    # batch, partial cache, multiple s-blocks
    (1, 4, 4, 384, 300),    # s = 384 -> 128-wide blocks
    (2, 32, 1, 512, 300),   # one KV head: a tile of one, the grid of a head
    (2, 30, 30, 1024, 700),  # G = 1, 30 heads in float32: several tiles
    (2, 120, 30, 1024, 700),  # ... and G = 4
])
def test_flash_decode_matches_oracle(b, h, kvh, s, pos):
    hs = 128
    rng = np.random.default_rng(pos + s + h)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hs)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.float32)
    q_pos = jnp.full((b, 1), pos, jnp.int32)

    want = decode_attention(q, k, v, q_pos)
    got = flash_decode_attention(q, k, v, q_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("b,h,kvh,s,t,pos0", [
    (1, 8, 8, 256, 16, 0),     # prefill chunk from 0, MHA
    (1, 8, 2, 256, 16, 100),   # GQA group 4, mid-session chunk
    (2, 8, 4, 512, 32, 37),    # batch, multiple s-blocks
    (1, 4, 4, 384, 8, 300),    # 128-wide blocks, chunk near the cache edge
    (2, 4, 1, 512, 32, 300),   # one KV head: a tile of one
    (1, 30, 30, 1024, 32, 600),  # G = 1, 30 heads in float32: several tiles
    (1, 120, 30, 1024, 32, 600),  # ... and G = 4
])
def test_flash_prefill_matches_oracle(b, h, kvh, s, t, pos0):
    """T>1 chunks: per-row causal limits must match the dense masked path.
    The cache is pre-filled at the chunk's positions (the engine writes K/V
    before attending — models/transformer._attention_block)."""
    hs = 128
    rng = np.random.default_rng(pos0 + s + h + t)
    q = jnp.asarray(rng.standard_normal((b, t, h, hs)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.float32)
    q_pos = pos0 + jnp.arange(t, dtype=jnp.int32)[None, :]
    q_pos = jnp.broadcast_to(q_pos, (b, t))

    want = decode_attention(q, k, v, q_pos)
    got = flash_attention(q, k, v, q_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


def test_flash_prefill_per_row_pos0():
    """Batched generation decodes with per-row positions; the kernel reads
    each panel's own pos_ref[b]."""
    b, t, h, kvh, s, hs = 3, 1, 4, 4, 256, 128
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((b, t, h, hs)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.float32)
    q_pos = jnp.asarray([[3], [100], [255]], jnp.int32)

    want = decode_attention(q, k, v, q_pos)
    got = flash_attention(q, k, v, q_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


def test_flash_supported_bounds():
    assert flash_supported(1, 32, 8)        # decode always
    assert flash_supported(256, 32, 32)     # 7B chunk: 256 rows
    assert flash_supported(256, 32, 8)      # 8B chunk: 1024 rows
    assert not flash_supported(512, 32, 8)  # 2048 rows > VMEM budget


def test_flash_decode_bf16():
    b, h, kvh, s, hs = 1, 8, 8, 256, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hs)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), jnp.bfloat16)
    q_pos = jnp.full((b, 1), s - 1, jnp.int32)

    want = decode_attention(q, k, v, q_pos)
    got = flash_decode_attention(q, k, v, q_pos, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=5e-2, rtol=5e-2)


GATED_LAYOUTS = {"first": (True, True, False, False),
                 "last": (False, False, True, True),
                 "interleaved": (True, False, True, False)}


# (KV heads, GQA group) a grid step's tile is cut from: one head (a tile of
# one: the grid of a head a step), two and eight (one tile), thirty (bf16:
# tiles of 15 at G = 1, t = 1 and of 30 in f8; of 6 and 10 at 32 tokens)
HEADS = [(kvh, g) for kvh in (1, 2, 8, 30) for g in (1, 4)]
_ids = lambda kvh_g: "kvh%d-g%d" % kvh_g  # noqa: E731


@pytest.mark.parametrize("kvh_g,layout", [
    *(((2, 4), layout) for layout in GATED_LAYOUTS),
    *((kvh_g, "interleaved") for kvh_g in HEADS if kvh_g != (2, 4))],
    ids=lambda v: v if isinstance(v, str) else _ids(v))
@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, F8_DTYPE],
                         ids=["bf16", "f8"])
@pytest.mark.parametrize("t", [1, 32])
def test_flash_gated_rows_cost_block_zero_and_leave_live_rows(
        t, cache_dtype, kvh_g, layout):
    """The scheduler parks a slot that takes no part in a call at
    pos == S. Such a row attends block 0 alone, so it stays finite
    whatever the rest of its cache holds; a live row's panel is its own,
    so it equals the oracle and, bit for bit, the same call with the
    gated slots given a live position. Every head of a tile, whatever the
    tile: the poison below lies in every head."""
    kvh, g = kvh_g
    b, h, s, hs = 4, kvh * g, 1536, 128  # three 512-blocks
    gated = np.asarray(GATED_LAYOUTS[layout])
    live = ~gated
    rng = np.random.default_rng(t + len(layout))
    q = jnp.asarray(rng.standard_normal((b, t, h, hs)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), cache_dtype)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), cache_dtype)
    # live rows: one inside block 0, one whose chunk ends in the last block
    pos0 = np.where(gated, s, 0)
    pos0[live] = [100, s - t]
    q_pos = jnp.asarray(pos0[:, None] + np.arange(t)[None, :], jnp.int32)

    got = np.asarray(flash_attention(q, k, v, q_pos, interpret=True),
                     np.float32)
    # the oracle in float32: XLA's CPU backend has no batched bf16 dot
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    want = np.asarray(decode_attention(f32(q), f32(k), f32(v), q_pos))
    np.testing.assert_allclose(got[live], want[live], atol=5e-2, rtol=5e-2)
    assert np.isfinite(got).all()

    all_live = np.where(gated, 700, pos0)
    q_live = jnp.asarray(all_live[:, None] + np.arange(t)[None, :], jnp.int32)
    twin = np.asarray(flash_attention(q, k, v, q_live, interpret=True),
                      np.float32)
    np.testing.assert_array_equal(got[live], twin[live])

    # every block past block 0 of the gated slots poisoned: untouched
    poison = jnp.asarray(gated)[:, None, None, None] & (
        jnp.arange(s) >= 512)[None, None, :, None]
    nan = jnp.asarray(jnp.nan, cache_dtype)
    kp, vp = jnp.where(poison, nan, k), jnp.where(poison, nan, v)
    assert np.isnan(np.asarray(kp, np.float32)).any()
    again = np.asarray(flash_attention(q, kp, vp, q_pos, interpret=True),
                       np.float32)
    np.testing.assert_array_equal(again, got)


@pytest.mark.parametrize("t", [1, 32])
def test_last_attended_gates_at_seq_len_and_clamps_below_it(t):
    """The one statement of the gate both kernels' index maps and
    pl.whens share: pos >= S -> position 0, so block 0 for every grid
    step j; pos < S -> the panel's last query position, as before."""
    s, sb = 4096, 512
    j = np.arange(s // sb)
    for pos in (s, s + 5):
        last = int(_last_attended(jnp.int32(pos), t - 1, s))
        assert last == 0
        assert (np.minimum(j, last // sb) == 0).all()
        assert (j * sb <= last).tolist() == [True] + [False] * (len(j) - 1)
    for pos in (0, 511, 512, 3000, s - t, s - 1):  # the last two: last block
        last = int(_last_attended(jnp.int32(pos), t - 1, s))
        assert last == pos + t - 1
        np.testing.assert_array_equal(np.minimum(j, last // sb),
                                      np.minimum(j, (pos + t - 1) // sb))

    # mla_attention's row tiles: tile i of TR rows ends at token
    # ((i + 1) * TR - 1) // H of the chunk, capped at the chunk's last
    h, tr = 64, min(512, t * 64)
    for i in range(t * h // tr):
        tok = min(((i + 1) * tr - 1) // h, t - 1)
        kw = dict(tr=tr, t=t, h=h, s=s)
        assert int(_mla_last(jnp.int32(s), i, **kw)) == 0
        assert int(_mla_last(jnp.int32(3000), i, **kw)) == 3000 + tok


# -- the slot map: program row b attends cache slot slots[b] ------------------

def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("kvh_g,cache_dtype", [
    *(((2, 4), dt) for dt in (jnp.float32, jnp.bfloat16, F8_DTYPE)),
    *((kvh_g, dt) for kvh_g in HEADS if kvh_g != (2, 4)
      for dt in (jnp.bfloat16, F8_DTYPE))],
    ids=lambda v: _ids(v) if isinstance(v, tuple) else jnp.dtype(v).name)
@pytest.mark.parametrize("t", [1, 32])
def test_a_slot_mapped_call_equals_the_call_on_the_gathered_slots(
        t, kvh_g, cache_dtype):
    """The K/V index map reads the map: bit for bit the map-less call on
    caches gathered by it, gated rows (block 0 of the slot they name)
    included, and the XLA twin's values; a tile's heads all follow the
    row's slot."""
    kvh, g = kvh_g
    b, h, s, hs = 4, kvh * g, 1024, 128
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.standard_normal((b, t, h, hs)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), cache_dtype)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), cache_dtype)
    slots = jnp.asarray([2, 0, 3, 1], jnp.int32)
    pos0 = jnp.asarray([5, s, 700, s - t], jnp.int32)
    q_pos = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    got = flash_attention(q, k, v, q_pos, interpret=True, slots=slots)
    want = flash_attention(q, k[slots], v[slots], q_pos, interpret=True)
    assert np.array_equal(_bits(got), _bits(want))
    live = np.asarray(pos0) < s
    # the oracle in float32: XLA's CPU backend has no batched bf16 dot
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    oracle = decode_attention(f32(q), f32(k)[slots], f32(v)[slots], q_pos)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(oracle, np.float32)[live],
        atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("kvh_g,cache_dtype,start,live", [
    *(((2, 4), dt, start, live)
      for dt in (jnp.float32, jnp.bfloat16, F8_DTYPE)
      for start, live in ((0, 8), (480, 5), (1024 - 256, 8))),
    # a tile of one head, one of eight and several of thirty's, across a
    # block's edge
    *((kvh_g, dt, 480, 3) for kvh_g in ((1, 4), (8, 4), (30, 1))
      for dt in (jnp.bfloat16, F8_DTYPE))],
    ids=lambda v: (_ids(v) if isinstance(v, tuple) else
                   str(v) if isinstance(v, int) else jnp.dtype(v).name))
def test_rows_chained_on_one_slot_equal_as_many_calls_in_a_row(
        kvh_g, cache_dtype, start, live):
    """Eight rows as consecutive 32-token segments of ONE slot, whose cache
    already holds all of them (the write precedes the attention inside a
    layer), attend what each would attend in a call of its own, in which
    the later segments are not written yet: the mask hides them, so the
    outputs are equal bit for bit."""
    kvh, g = kvh_g
    b, t, h, s, hs, slot = 8, 32, kvh * g, 1024, 128, 5
    rng = np.random.default_rng(start + live)
    q = jnp.asarray(rng.standard_normal((b, t, h, hs)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), cache_dtype)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, hs)), cache_dtype)
    pos0 = np.full((b,), s, np.int32)
    pos0[:live] = start + t * np.arange(live)
    slots = np.asarray([slot] * live
                       + [i for i in range(b) if i != slot][:b - live])
    q_pos = jnp.asarray(pos0)[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    got = flash_attention(q, k, v, q_pos, interpret=True,
                          slots=jnp.asarray(slots, jnp.int32))
    for r in range(live):
        # the call of its own: row `slot` is the slot, as the scheduler
        # packs a single segment; what lies past the segment is stale
        one = np.full((b,), s, np.int32)
        one[slot] = pos0[r]
        stale = pos0[r] + t
        k1 = k.at[slot, :, stale:].set(jnp.asarray(7.0, cache_dtype))
        v1 = v.at[slot, :, stale:].set(jnp.asarray(-3.0, cache_dtype))
        alone = flash_attention(
            jnp.zeros_like(q).at[slot].set(q[r]), k1, v1,
            jnp.asarray(one)[:, None] + jnp.arange(t, dtype=jnp.int32)[None],
            interpret=True)
        assert np.array_equal(_bits(got[r]), _bits(alone[slot])), r


# -- the tile of KV heads a grid step holds ------------------------------------

BF16, HS = jnp.bfloat16, 128


@pytest.mark.parametrize("kvh,g,t,s,cache_dtype,want", [
    (8, 4, 1, 4096, BF16, 8),       # Mistral's, Mixtral's, granite's decode
    (8, 4, 32, 4096, BF16, 8),      # ... and their 32-token chunk
    (8, 4, 1, 4096, F8_DTYPE, 8),   # the f8 cache's decode
    (30, 1, 1, 8192, BF16, ">1"),   # olmo-hybrid's 30 heads, G = 1
    (30, 1, 32, 8192, BF16, ">1"),
    (30, 1, 1, 8192, F8_DTYPE, ">1"),
    (1, 32, 1, 8192, BF16, 1),      # jamba's one KV head: today's grid
    (1, 32, 16, 8192, BF16, 1),
    (2, 4, 1, 4096, BF16, 2),       # a tp = 4 shard of Mistral's heads
    (8, 4, 256, 4096, BF16, "<8"),  # T*G = 1,024: the score tile sets it
    (32, 1, 256, 1024, BF16, "<32"),
    (8, 4, 256, 4096, jnp.float32, "<8"),
])
def test_head_tile_divides_the_heads_and_fits_the_budget(
        kvh, g, t, s, cache_dtype, want):
    """The one function that chooses the tile: a divisor of KVH, the
    largest whose step fits the stated VMEM budget (the next divisor up
    does not), 1 where none above 1 does; flash_grid is that tile's grid."""
    sb, rows = _block_s(s), t * g
    cb = jnp.dtype(cache_dtype).itemsize
    qb = max(cb, 2)
    kh = head_tile(kvh, rows, sb, HS, cb, qb)
    assert kvh % kh == 0
    fits = lambda n: _tile_bytes(n, rows, sb, HS, cb, qb) <= FLASH_VMEM_BYTES  # noqa: E731
    assert fits(kh) or kh == 1
    assert not any(fits(n) for n in range(kh + 1, kvh + 1) if kvh % n == 0)
    if isinstance(want, int):
        assert kh == want
    else:
        assert {">1": kh > 1, "<8": kh < 8, "<32": kh < 32}[want]
    assert flash_grid(3, t, kvh * g, kvh, s, HS, cache_dtype, BF16) == (
        3, kvh // kh, s // sb)


def test_the_call_runs_the_grid_flash_grid_gives():
    """The count cannot drift from the kernel: the pallas_call in the
    traced program has the grid flash_grid computes from the same shapes
    (a float32 cache lifts q, and the tile is cut for that)."""
    import jax

    for (b, t, h, kvh, s), dt in [((4, 1, 8, 2, 1536), BF16),
                                  ((2, 32, 30, 30, 1024), jnp.float32),
                                  ((2, 1, 30, 30, 1024), F8_DTYPE),
                                  ((3, 16, 32, 1, 1024), BF16)]:
        q = jax.ShapeDtypeStruct((b, t, h, HS), BF16)
        kv = jax.ShapeDtypeStruct((b, kvh, s, HS), dt)
        pos = jax.ShapeDtypeStruct((b, t), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda *a: flash_attention(*a, interpret=True))(q, kv, kv, pos)
        grids = [e.params["grid_mapping"].grid
                 for e in jax.tree.leaves(
                     [jaxpr.jaxpr.eqns] + [
                         j.jaxpr.eqns for eq in jaxpr.jaxpr.eqns
                         for j in eq.params.values()
                         if hasattr(j, "jaxpr")])
                 if e.primitive.name == "pallas_call"]
        assert grids == [flash_grid(b, t, h, kvh, s, HS, dt, BF16)], (
            grids, b, t, h, kvh, s, dt)
