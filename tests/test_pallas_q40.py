"""Fused Q40 Pallas kernel vs the XLA dequant oracle (interpret mode on the
CPU mesh; the compiled path runs on real TPU via bench/engine opt-in).

The kernel is the TPU-native analogue of the reference's Q40xQ80 SIMD matmul
(ref: src/funcs.cpp:286-385); correctness target is the dequantize-then-dot
semantics of the reference decoder (ref: src/quants.cpp:166-179).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.ops.pallas_q40 import q40_matmul, supports_pallas, _tile_d
from distributed_llama_tpu.quants.jax_codec import QuantizedTensor, dequantize_q40_jax
from distributed_llama_tpu.quants.numpy_codec import quantize_q40


def _qt(rng, d, n, scale=0.1):
    w = rng.standard_normal((d, n), dtype=np.float32) * scale
    scales, packed = quantize_q40(w)
    return QuantizedTensor.from_numpy(scales, packed)


@pytest.mark.parametrize("d,n,t", [
    (256, 1024, 1),    # gemv, aligned
    (256, 1024, 4),    # small batch
    (704, 128 * 32, 2),  # d not 128-aligned -> whole-d tile
    (128, 704, 1),     # n/32 not lane-aligned -> full-m block padding
])
def test_kernel_matches_dequant_oracle(rng, d, n, t):
    qt = _qt(rng, d, n)
    x = jnp.asarray(rng.standard_normal((t, n), dtype=np.float32))
    ref = jnp.einsum("tn,dn->td", x, dequantize_q40_jax(qt, dtype=jnp.float32))
    got = q40_matmul(x, qt, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=1e-4)


def test_leading_dims_flattened(rng):
    qt = _qt(rng, 128, 256)
    x = jnp.asarray(rng.standard_normal((2, 3, 256), dtype=np.float32))
    got = q40_matmul(x, qt, interpret=True)
    assert got.shape == (2, 3, 128)
    ref = jnp.einsum("btn,dn->btd", x, dequantize_q40_jax(qt, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=1e-4)


def _stack(rng, n_e, d, n):
    qts = [_qt(rng, d, n) for _ in range(n_e)]
    return qts, QuantizedTensor(jnp.stack([q.packed for q in qts]),
                                jnp.stack([q.scales for q in qts]))


@pytest.mark.parametrize("e", [0, 2, 7])
def test_expert_kernel_matches_sliced_oracle(rng, e):
    """The expert-indexed kernel (traced index into the (E, d, m) stack) must
    match slicing the expert out first then running the plain kernel path."""
    from distributed_llama_tpu.ops.pallas_q40 import q40_expert_matmul

    n_e, d, n = 8, 256, 1024
    qts, stack = _stack(rng, n_e, d, n)
    x = jnp.asarray(rng.standard_normal((1, n), dtype=np.float32))
    ref = jnp.einsum("tn,dn->td", x,
                     dequantize_q40_jax(qts[e], dtype=jnp.float32))
    got = q40_expert_matmul(x, stack, jnp.int32(e), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("e", [0, 3, 7], ids=["first", "middle", "last"])
@pytest.mark.parametrize("t", [8, 256])
def test_expert_kernel_equals_plain_kernel_at_served_rows(rng, t, e):
    """At the served step programs' 8 and 256 (= MAX_T) rows the expert
    kernel on the stack is BIT-equal to `q40_matmul` on the sliced expert,
    with `e` the Python integer `_moe_ffn`'s all-experts loop passes: one
    body (`_subtiled_write`), one call (`_q40_call`)."""
    from distributed_llama_tpu.ops.pallas_q40 import q40_expert_matmul

    qts, stack = _stack(rng, 8, 256, 512)
    x = jnp.asarray(rng.standard_normal((t, 512), dtype=np.float32),
                    jnp.bfloat16)
    got = q40_expert_matmul(x, stack, e, out_dtype=jnp.bfloat16,
                            interpret=True)
    ref = q40_matmul(x, qts[e], out_dtype=jnp.bfloat16, interpret=True)
    assert got.dtype == ref.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32))


def _jit_calls(fn) -> dict:
    """How often fn's trace calls each jitted kernel entry point (call
    sites: a loop's body counts once)."""
    import jax

    calls = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.params.get("name", "")
            if name.startswith("q40_"):
                calls[name] = calls.get(name, 0) + 1
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)().jaxpr)
    return calls


def _moe_case(rng, kind, unchosen=None):
    """(spec, layer weights) of one MoE block at tiny widths: "mixtral"
    (softmax router, top-2 of 8), "grok1" (the same router under GELU) or
    "held" (a SARVAM_MLA-shaped held share with a shared expert: a
    sigmoid-bias router over 128, top-8, experts 40..55 held and favoured
    by the bias, so that a few rows choose some). Router logits are of
    order one, as a trained router's: a token's weights are spread over its
    experts. `unchosen`: a held expert whose router row is turned so that
    no token chooses it."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)

    d, h = 256, 512
    common = dict(dim=d, hidden_dim=h, n_layers=1, n_heads=4, n_kv_heads=4,
                  vocab_size=64, seq_len=64)
    if kind == "held":
        spec = ModelSpec(arch=ArchType.SARVAM_MLA, n_experts=16,
                         n_active_experts=8, n_routed_experts=128,
                         expert_offset=40, routed_scaling=2.5,
                         n_shared_experts=1, kv_lora_rank=32,
                         qk_nope_head_dim=16, qk_rope_head_dim=8,
                         v_head_dim=16, hidden_act=HiddenAct.SILU, **common)
    elif kind == "grok1":
        spec = ModelSpec(arch=ArchType.GROK1, n_experts=8,
                         n_active_experts=2, hidden_act=HiddenAct.GELU,
                         **common)
    else:
        spec = ModelSpec(arch=ArchType.MIXTRAL, n_experts=8,
                         n_active_experts=2, hidden_act=HiddenAct.SILU,
                         **common)
    e = spec.n_experts
    router = rng.standard_normal((spec.router_width, d),
                                 dtype=np.float32) / np.sqrt(d)
    lw = {"moe_up": _stack(rng, e, h, d)[1],
          "moe_gate": _stack(rng, e, h, d)[1],
          "moe_down": _stack(rng, e, d, h)[1]}
    if spec.router_width != e:
        bias = 0.5 * rng.standard_normal(spec.router_width, dtype=np.float32)
        bias[spec.expert_offset:spec.expert_offset + e] += 0.5
        if unchosen is not None:
            bias[spec.expert_offset + unchosen] = -1e4
        lw.update(moe_bias=jnp.asarray(bias),
                  sh_w1=_qt(rng, h, d), sh_w2=_qt(rng, d, h),
                  sh_w3=_qt(rng, h, d))
    elif unchosen is not None:
        router[unchosen] = 0.0  # the caller's feature 0 is a positive constant
        router[unchosen, 0] = -1e3
    lw["moe_router"] = jnp.asarray(router)
    return spec, lw


# rows of a served 8-slot program: whole, gated (n_valid 0), a right-padded
# tail, one token
_N_VALID = {1: (1, 0, 1, 0, 0, 1, 1, 0), 32: (32, 0, 27, 32, 0, 0, 1, 32)}
_CFG = dict(activation_q80=True, compute_dtype=jnp.bfloat16, use_pallas=True,
            tp_mesh=None, tp_reduce="exact", pallas_interpret=True)


def _all_experts_loop(monkeypatch):
    """Send `_moe_ffn` down the path of stacks the kernel cannot take."""
    import sys

    monkeypatch.setattr(
        sys.modules["distributed_llama_tpu.models.transformer"],
        "reads_experts_in_place", lambda *a, **k: False)


@pytest.mark.parametrize("q80", [True, False], ids=["q80", "plain"])
@pytest.mark.parametrize("t", [1, 32], ids=["decode8x1", "chunk8x32"])
@pytest.mark.parametrize("kind", ["mixtral", "grok1", "held"],
                         ids=["top2of8", "grok1", "held16top8shared"])
def test_grouped_experts_bit_equal_to_all_experts_loop(rng, monkeypatch,
                                                       kind, t, q80):
    """`_moe_ffn` at the served shapes (8 rows, and 8 x 32 = 256 kernel
    rows), with gated rows, a right-padded tail chunk and an expert nobody
    chose: ONE `q40_expert_matmul` a projection over the live (token,
    expert) pairs, and every LIVE token's output BIT-equal to the
    all-experts loop over slices + `q40_matmul`, with the Q80 activation
    round trip on (as served) and off. Both run operation by operation
    (`jax.disable_jit`: the grouped path's waves are a loop, and a compiled
    loop body keeps float32 between bfloat16 operations where its fusions
    happen to end), so what the two SAY is compared. A dead token's routed
    output is zero (what is left of it is the shared expert's)."""
    import jax

    from distributed_llama_tpu.models.transformer import (_moe_ffn,
                                                          _pair_layout)

    spec, lw = _moe_case(rng, kind, unchosen=3)
    xb = jnp.asarray(rng.standard_normal((8, t, spec.dim), dtype=np.float32),
                     jnp.bfloat16).at[..., 0].set(8.0)
    cfg = dict(_CFG, activation_q80=q80)
    n_valid = jnp.asarray(_N_VALID[t], jnp.int32)
    real = np.arange(t)[None, :] < np.asarray(n_valid)[:, None]

    def run(nv, lw=lw):  # a new function each time: traces are cached
        def ffn():
            counts = []
            return _moe_ffn(xb, lw, spec, cfg, nv, counts), counts[0]
        return ffn

    shared = {"q40_matmul": 3} if kind == "held" else {}
    assert _jit_calls(run(n_valid)) == {"q40_expert_matmul": 3, **shared}
    with jax.disable_jit():
        got, (reads, pairs, *tiles) = run(n_valid)()
    got = np.asarray(got, np.float32)
    assert 0 < reads <= spec.n_experts - 1          # expert 3: never read
    # a program whose rows fit one row tile (the decode step) counts no
    # tiles; in a chunk a read is at least one row tile, and a tile holds
    # a pair
    tile = _pair_layout(spec, 8 * t)[0]
    assert len(tiles) == (8 * t > tile)
    assert all(reads <= n <= min(pairs, reads + pairs // tile)
               for n in tiles)
    if kind == "held":
        assert reads <= pairs < real.sum() * spec.n_active_experts
    else:
        assert pairs == real.sum() * spec.n_active_experts

    _all_experts_loop(monkeypatch)
    assert _jit_calls(run(None)) == {
        "q40_matmul": 3 * spec.n_experts + shared.get("q40_matmul", 0)}
    with jax.disable_jit():
        loop = np.asarray(run(None)()[0], np.float32)
    assert np.abs(loop[real]).max() > 0
    np.testing.assert_array_equal(got[real], loop[real])
    if kind == "held":
        routed = {k: v for k, v in lw.items() if not k.startswith("sh_")}
        monkeypatch.undo()
        got = np.asarray(jax.jit(run(n_valid, routed))()[0], np.float32)
    assert not got[~real].any()


@pytest.mark.parametrize("tile", [8, 64])
@pytest.mark.parametrize("case", ["ragged", "one_expert", "nothing_live"])
def test_pair_tiles_name_no_expert_without_a_live_pair(rng, case, tile):
    """`_pair_tiles` (the layout `_grouped_experts` hands the kernel): every
    live pair has a row of its own in a tile of ITS expert, groups keep
    token order, the used tiles come first in ascending expert order and
    each holds a live pair, and no dead pair lands anywhere."""
    from distributed_llama_tpu.models.transformer import _pair_tiles

    rows, k, n_e = 256, 2, 8
    held = np.stack([rng.permutation(12)[:k] - 2 for _ in range(rows)])
    live = (held >= 0) & (held < n_e) & (rng.random((rows, 1)) < 0.6)
    if case == "one_expert":
        held[:, 0], held[:, 1] = 5, 11
        live = (held == 5) & np.ones((rows, 1), bool)
    if case == "nothing_live":
        live[:] = False
    member = (held[..., None] == np.arange(n_e)) & live[..., None]
    sizes = member.reshape(-1, n_e).sum(0).astype(np.int32)
    n_tiles = min(rows * k // tile + n_e, n_e * -(-rows // tile))
    dest, src, tile_expert, used = (np.asarray(a) for a in _pair_tiles(
        jnp.asarray(held.reshape(8, 32, k)),
        jnp.asarray(live.reshape(8, 32, k)),
        jnp.asarray(member.reshape(-1, n_e)), jnp.asarray(sizes),
        tile, n_tiles))
    dest = dest.reshape(rows, k)
    assert used == sum(-(-int(n) // tile) for n in sizes) <= n_tiles
    assert (dest[~live] == n_tiles * tile).all()
    assert len(set(dest[live])) == live.sum()      # a row of its own
    assert (dest[live] < used * tile).all()
    np.testing.assert_array_equal(tile_expert[dest[live] // tile],
                                  held[live])
    np.testing.assert_array_equal(src[dest[live]],
                                  np.nonzero(live)[0])
    # used tiles: ascending experts, each with a live pair; no others named
    assert (np.diff(tile_expert[:used]) >= 0).all()
    assert set(tile_expert[:used]) == set(np.nonzero(sizes)[0])
    assert set(dest[live] // tile) == set(range(used))
    for e in np.nonzero(sizes)[0]:                 # token order in a group
        assert (np.diff(dest[live & (held == e)]) == 1).all()


@pytest.mark.parametrize("kind,rows,want", [
    ("mixtral", 8, (8, 8, 8)), ("mixtral", 256, (64, 16, 16)),
    ("grok1", 8, (8, 8, 8)), ("grok1", 256, (64, 16, 16)),
    ("held", 8, (8, 16, 16)), ("held", 256, (16, 160, 32)),
], ids=lambda v: str(v) if not isinstance(v, tuple) else "")
def test_pair_layout_follows_the_programs_rows(rng, kind, rows, want):
    """(row tile, tiles that hold any routing, tiles a wave) of the served
    step programs: the tile is the sublane tile's 8 rows in a decode step
    and the even-routing group's power of two in a chunk (256 x 2 / 8 = 64;
    256 x 8 / 128 = 16); where every expert is held one wave holds any
    routing, where 16 of 128 are a wave holds twice the even share."""
    from distributed_llama_tpu.models.transformer import _pair_layout

    spec, _ = _moe_case(rng, kind)
    tile, n_tiles, wave = _pair_layout(spec, rows)
    assert (tile, n_tiles, wave) == want
    k, n_e = spec.n_active_experts, spec.n_experts
    # any routing fits: every pair a row, and a ragged tile an expert
    assert n_tiles * tile >= min(rows * k + n_e * (tile - 1),
                                 n_e * -(-rows // tile) * tile)
    assert n_tiles % wave == 0


def test_skipped_tiles_are_neither_read_nor_written(rng):
    """The kernel: a used tile equals a call of its own on its expert (bit
    for bit) and the dequantized oracle (to rounding), under the operand
    feed the PROGRAM's token rows decide; tiles past `used`, whatever
    expert they name, are never written (the interpreter marks memory
    nobody wrote with NaN)."""
    from distributed_llama_tpu.ops.pallas_q40 import q40_expert_matmul

    qts, stack = _stack(rng, 4, 256, 512)
    x = jnp.asarray(rng.standard_normal((6 * 8, 512), dtype=np.float32),
                    jnp.bfloat16)
    tiles = jnp.asarray([0, 2, 2, 3, 3, 3], jnp.int32)

    def call(x, e, used, rows):
        return np.asarray(q40_expert_matmul(
            x, stack, e, used, out_dtype=jnp.bfloat16, interpret=True,
            token_rows=rows), np.float32)

    feeds = {rows: call(x, tiles, jnp.int32(3), rows) for rows in (8, 256)}
    assert not np.array_equal(feeds[8][:24], feeds[256][:24])
    for rows, got in feeds.items():
        for j, e in enumerate((0, 2, 2)):
            tile = slice(8 * j, 8 * j + 8)
            np.testing.assert_array_equal(got[tile],
                                          call(x[tile], e, None, rows))
            ref = jnp.einsum("tn,dn->td", x[tile].astype(jnp.float32),
                             dequantize_q40_jax(qts[e], dtype=jnp.float32))
            np.testing.assert_allclose(got[tile], np.asarray(ref),
                                       atol=0.3, rtol=2e-2)
        assert np.isnan(got[24:]).all()
    assert np.isnan(call(x, tiles, jnp.int32(0), 8)).all()


def test_wrapped_stack_still_takes_the_slice(rng):
    """A `TpColWeight` stack (the tp placements') is not the in-place
    kernel's: `fused_expert_matmul` declines it, and `_expert_matmul`
    slices the expert out (`_take_expert`) for the wrapper's own matmul."""
    from distributed_llama_tpu.models.transformer import _expert_matmul
    from distributed_llama_tpu.ops.matmul import fused_expert_matmul
    from distributed_llama_tpu.parallel.tp_q80 import TpColWeight

    n_e, d, n = 4, 128, 256
    qts, stack = _stack(rng, n_e, d, n)
    wrapped = TpColWeight(stack)  # shard-local, as inside a manual region
    x = jnp.asarray(rng.standard_normal((8, 1, n), dtype=np.float32))
    cfg = dict(activation_q80=False, compute_dtype=jnp.float32,
               use_pallas=True, tp_mesh=None, tp_reduce="exact",
               pallas_interpret=True, manual_tp=1)
    assert fused_expert_matmul(x, wrapped, 2, **cfg) is None
    assert _jit_calls(lambda: _expert_matmul(x, wrapped, 2, cfg)) == {
        "q40_matmul": 1}
    got = _expert_matmul(x, wrapped, 2, cfg)
    ref = jnp.einsum("btn,dn->btd", x,
                     dequantize_q40_jax(qts[2], dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=1e-4)


def test_fused_expert_matmul_dispatch(rng):
    """ops/matmul.fused_expert_matmul: eligible only for single-shard Q40
    stacks under use_pallas; returns the same result as gather-then-matmul."""
    from distributed_llama_tpu.ops.matmul import fused_expert_matmul

    n_e, d, n = 4, 128, 256
    qts, stack = _stack(rng, n_e, d, n)
    x = jnp.asarray(rng.standard_normal((1, 1, n), dtype=np.float32))
    got = fused_expert_matmul(x, stack, jnp.int32(3),
                              compute_dtype=jnp.float32, use_pallas=True,
                              pallas_interpret=True)
    assert got is not None and got.shape == (1, 1, d)
    ref = jnp.einsum("btn,dn->btd", x,
                     dequantize_q40_jax(qts[3], dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=1e-4)
    # ineligible: pallas off, mesh path, dense leaf, 2D (un-stacked) weight
    assert fused_expert_matmul(x, stack, 0, compute_dtype=jnp.float32) is None
    assert fused_expert_matmul(x, stack, 0, compute_dtype=jnp.float32,
                               use_pallas=True, tp_mesh=object()) is None
    assert fused_expert_matmul(x, jnp.zeros((4, d, n)), 0,
                               compute_dtype=jnp.float32,
                               use_pallas=True) is None
    assert fused_expert_matmul(x, qts[0], 0, compute_dtype=jnp.float32,
                               use_pallas=True) is None


def test_supports_and_tiles():
    assert _tile_d(4096, 2048) == 1024
    assert _tile_d(4096, 5504) == 256     # w2: bigger m, smaller tile
    assert _tile_d(11008, 2048) == 256    # 11008 has no 512/1024 divisor
    assert _tile_d(704, 2048) == 704      # whole-dim fallback
    assert _tile_d(32000, 2048) == 256
    rng = np.random.default_rng(0)
    qt = _qt(rng, 128, 256)
    assert supports_pallas(qt)
    stacked = QuantizedTensor(qt.packed[None], qt.scales[None])  # (L, d, 16, nb)
    assert not supports_pallas(stacked)  # leading dims must be sliced first


@pytest.mark.parametrize("d,m,want", [
    (2752, 2048, 256),    # Llama-2-7B w1/w3 row shard at tp=4
    (8000, 2048, 1024),   # 32000-vocab head row shard at tp=4
    (32064, 2048, 1024),  # Llama-3 128256-vocab head row shard at tp=4
    (2752, 5504, 256),    # wide contraction: only the small tiles fit
])
def test_tile_d_is_bounded_for_ragged_row_counts(d, m, want):
    """Row counts no candidate divides (tp row shards) must get a bounded
    lane-multiple tile over a cdiv grid — never the whole local weight as
    one block, which the chip's compiler refuses (scoped VMEM)."""
    from distributed_llama_tpu.ops.pallas_q40 import (LANES,
                                                      _TILE_BYTES_MAX)

    td = _tile_d(d, m)
    assert td == want
    assert td % LANES == 0 and td * m <= _TILE_BYTES_MAX and d % td != 0


# (rows d, contraction n) -> tile, for every Q40 matmul of a configuration:
# whole on one chip, its rows split four ways and its contraction split four
# ways (tp = 4). The values are the tiles of the tree BEFORE _tile_d charged
# scale blocks that are not whole lane tiles (PR 34): that rule is a budget
# found by one compile, not a model of the kernel's VMEM, so every tile it
# must not move is pinned here. It moved one, olmo-hybrid-7b's head.
TILES = {
    "llama2-7b": {
        (4096, 4096): 1024, (1024, 4096): 1024, (4096, 1024): 1024,
        (12288, 4096): 1024, (3072, 4096): 1024, (12288, 1024): 1024,
        (11008, 4096): 256, (2752, 4096): 256, (11008, 1024): 256,
        (22016, 4096): 512, (5504, 4096): 128, (22016, 1024): 512,
        (4096, 11008): 256, (1024, 11008): 256, (4096, 2752): 1024,
        (32000, 4096): 256, (8000, 4096): 1024, (32000, 1024): 256},
    "mistral-7b, mixtral-8x7b": {
        (256, 4096): 256, (1024, 1024): 1024, (6144, 4096): 1024,
        (1536, 4096): 512, (6144, 1024): 1024, (14336, 4096): 1024,
        (3584, 4096): 512, (14336, 1024): 1024, (28672, 4096): 1024,
        (7168, 4096): 1024, (28672, 1024): 1024, (4096, 14336): 256,
        (1024, 14336): 256, (4096, 3584): 1024, (8, 4096): 8,
        (2, 4096): 2, (8, 1024): 8},
    "grok1": {
        (6144, 6144): 512, (1536, 6144): 512, (6144, 1536): 1024,
        (1024, 6144): 512, (256, 6144): 256, (1024, 1536): 1024,
        (8192, 6144): 512, (2048, 6144): 512, (8192, 1536): 1024,
        (32768, 6144): 512, (32768, 1536): 1024, (65536, 6144): 512,
        (16384, 6144): 512, (65536, 1536): 1024, (6144, 32768): 128,
        (1536, 32768): 128, (6144, 8192): 512, (131072, 6144): 512,
        (131072, 1536): 1024, (8, 6144): 8},
    "llama3-8b head": {
        (128256, 4096): 256, (32064, 4096): 1024, (128256, 1024): 256},
    "sarvam-105b-ep8": {
        (12288, 4096): 1024, (3072, 4096): 1024, (12288, 1024): 1024,
        (576, 4096): 576, (144, 4096): 144, (576, 1024): 576,
        (4096, 8192): 512, (1024, 8192): 512, (4096, 2048): 1024,
        (2048, 4096): 1024, (512, 4096): 512, (2048, 1024): 1024,
        (1024, 2048): 1024, (4096, 512): 1024, (16384, 4096): 1024,
        (16384, 1024): 1024, (32768, 4096): 1024, (8192, 4096): 1024,
        (32768, 1024): 1024, (4096, 16384): 256, (1024, 16384): 256},
    # 3840-wide contractions have 120 scale blocks a row and 11008-wide
    # ones 344: not whole lane tiles. The head's (1024, 1920) tile needed
    # 17.8 MiB of scoped VMEM at 8 rows; at 512 rows it fits
    "olmo-hybrid-7b": {
        (2880, 3840): 128, (720, 3840): 720, (2880, 960): 2880,
        (5760, 3840): 128, (1440, 3840): 512, (5760, 960): 128,
        (11520, 3840): 256, (11520, 960): 256, (3840, 5760): 256,
        (960, 5760): 512, (3840, 1440): 256, (3840, 3840): 256,
        (960, 3840): 960, (3840, 960): 256, (11008, 3840): 256,
        (2752, 3840): 256, (11008, 960): 256, (22016, 3840): 512,
        (5504, 3840): 128, (22016, 960): 512, (3840, 11008): 256,
        (960, 11008): 256, (3840, 2752): 256, (100352, 3840): 512,
        (25088, 3840): 512, (100352, 960): 1024},
    # expert gate / up and down (768-wide experts), the shared expert's
    # (1536), the state-space in projection (16768 rows, of which the
    # kernel's gate | x leaf 16384) and out projection, wqkv and wo, the
    # 50176-row head
    "granite-4.0-h-small-ep2": {
        (768, 4096): 256, (192, 4096): 192, (768, 1024): 256,
        (4096, 768): 1024, (1024, 768): 1024, (4096, 192): 1024,
        (1536, 4096): 512, (384, 4096): 128, (1536, 1024): 512,
        (4096, 1536): 1024, (1024, 1536): 1024, (4096, 384): 1024,
        (16768, 4096): 128, (4192, 4096): 256, (16768, 1024): 128,
        (16384, 4096): 1024, (4096, 4096): 1024, (16384, 1024): 1024,
        (4096, 8192): 512, (1024, 8192): 512, (4096, 2048): 1024,
        (6144, 4096): 1024, (6144, 1024): 1024, (1024, 4096): 1024,
        (4096, 1024): 1024, (50176, 4096): 1024, (12544, 4096): 256,
        (50176, 1024): 1024},
    # expert gate / up and down (1024-wide experts over a 2304-wide
    # stream: 72 scale blocks a row, not whole lane tiles), the dense first
    # layer's FFN (9216), the 40960-row head; the experts' tp = 4 shards
    "kimi-linear-48b-a3b-ep4": {
        (1024, 2304): 1024, (256, 2304): 256, (1024, 576): 1024,
        (2304, 1024): 256, (576, 1024): 576, (2304, 256): 256,
        (9216, 2304): 1024, (2304, 9216): 256, (40960, 2304): 1024},
}

# The pinned shapes whose block scales are spread over the lanes on the MXU
# (`_spreads_on_mxu`: more than four copies of a row's blocks share a lane
# tile, a contraction under 1024); every other shape keeps `pltpu.repeat`.
# The choice is made by shape when a program is traced, in every call of
# that shape or in none: this table is its record of engagement. One chip's
# programs hold such a shape in ONE configuration, granite's expert down
# projection (4096, 768); the rest are contraction shards of tp = 4.
MXU_SPREAD = {
    "llama2-7b": set(),
    "mistral-7b, mixtral-8x7b": set(),
    "grok1": set(),
    "llama3-8b head": set(),
    "sarvam-105b-ep8": {(4096, 512)},
    "olmo-hybrid-7b": {
        (2880, 960), (3840, 960), (5760, 960), (11008, 960), (11520, 960),
        (22016, 960), (100352, 960)},
    "granite-4.0-h-small-ep2": {
        (4096, 768), (1024, 768), (4096, 192), (4096, 384)},
    "kimi-linear-48b-a3b-ep4": {(1024, 576), (2304, 256)},
}


@pytest.mark.parametrize("config", sorted(TILES))
def test_tile_d_of_every_configurations_shapes_is_pinned(config):
    got = {(d, n): _tile_d(d, n // 2) for d, n in TILES[config]}
    assert got == TILES[config]


@pytest.mark.parametrize("config", sorted(TILES))
def test_which_pinned_shapes_spread_their_scales_on_the_mxu(config):
    from distributed_llama_tpu.ops.pallas_q40 import _spreads_on_mxu

    got = {(d, n) for d, n in TILES[config] if _spreads_on_mxu(n // 32)}
    assert got == MXU_SPREAD[config]


# Which order the grouped expert call of each (configuration, step program,
# projection) takes (`_unpacks_once`, decided from the call's shapes when the
# program is traced): True where weight blocks run outermost and an expert's
# consecutive row tiles share ONE dequantised block. Only the chunk program
# whose row tile is the sublane tile's 8 rows takes it, kimi-linear's, for
# all three projections; every decode step, and sarvam's (16-row tiles),
# Mixtral's and granite's chunks (64-row tiles), keep row tiles outermost
# and the parent's kernel text (PERF.md section 6, PR 49).
# Values: (row tile, tiles a wave, stationary).
ORDERS = {
    ("MIXTRAL_8X7B", 8): (8, 8, False),
    ("MIXTRAL_8X7B", 256): (64, 16, False),
    ("SARVAM_105B_EP8", 8): (8, 16, False),
    ("SARVAM_105B_EP8", 256): (16, 32, False),
    ("GRANITE_4_H_SMALL_EP2", 8): (8, 36, False),
    ("GRANITE_4_H_SMALL_EP2", 256): (64, 56, False),
    ("KIMI_LINEAR_48B_EP4", 8): (8, 64, False),
    ("KIMI_LINEAR_48B_EP4", 256): (8, 128, True),
}


@pytest.mark.parametrize("config,rows", sorted(ORDERS))
def test_which_grouped_calls_unpack_an_expert_once(config, rows):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import rehearse_chip_compile as r

    from distributed_llama_tpu.models.transformer import _pair_layout
    from distributed_llama_tpu.ops.pallas_q40 import _unpacks_once

    spec = getattr(r, config)
    tile, _, wave = _pair_layout(spec, rows)

    assert (tile, wave, _unpacks_once(tile, rows)) == ORDERS[config, rows]
    # a scalar expert (one tile of all rows) never does
    assert not _unpacks_once(rows, rows)


def _runs(*groups):
    """Tile experts of consecutive runs: (expert, tiles) pairs."""
    return [e for e, n in groups for _ in range(n)]


# (weights (E, d, n), rows a tile, the tiles' experts, used, out dtype):
# routings that differ in what the stationary order sees. The shapes are
# kimi-linear's expert projections (gate: one 1024-row block, 72 scale
# blocks a row; down: nine 256-row blocks, sub-tiled 8-way under the bf16
# feed) with four experts held, and a small one for the rest
_KIMI_GATE, _KIMI_DOWN, _SMALL = (4, 1024, 2304), (4, 2304, 1024), (4, 256, 512)
STATIONARY_CASES = {
    "one_tile_an_expert": (_SMALL, 8, [0, 1, 2, 3], 4, "bf16"),
    "five_tiles_kimi_gate_bf16": (
        _KIMI_GATE, 8, _runs((0, 5), (2, 5), (3, 2)), 12, "bf16"),
    "five_tiles_kimi_gate_f32": (
        _KIMI_GATE, 8, _runs((1, 5), (3, 1)), 6, "f32"),
    "five_tiles_kimi_down_bf16": (
        _KIMI_DOWN, 8, _runs((0, 5), (1, 1), (3, 5)), 11, "bf16"),
    "five_tiles_kimi_down_f32": (
        _KIMI_DOWN, 8, _runs((2, 5), (3, 1)), 6, "f32"),
    "every_pair_in_one_expert": (_SMALL, 8, [2] * 12, 12, "bf16"),
    "nothing_used": (_SMALL, 8, [0, 0, 1, 3], 0, "bf16"),
    # the used tiles end inside an expert's run (its last tile ragged in
    # the layout: rows computed that mean nothing), dead tiles behind them
    "used_short_of_the_tiles": (
        _SMALL, 8, _runs((0, 3), (1, 2), (3, 3)), 6, "bf16"),
    # an expert's run cut by the end of a wave: _grouped_experts runs the
    # call again over the next tiles, whose first finds nothing kept
    "two_waves": (_SMALL, 8, _runs((0, 3), (1, 4), (3, 5)), 11, "bf16"),
}


@pytest.mark.parametrize("case", sorted(STATIONARY_CASES))
def test_stationary_order_is_bit_equal_to_rows_outermost(rng, monkeypatch,
                                                         case):
    """The grouped call with weight blocks outermost and an expert's block
    kept dequantised from one row tile to the next (`_unpacks_once`)
    against the same call with row tiles outermost, forced, and against a
    call of its own on each tile's expert (`q40_matmul` on the expert's
    slice where the feed is the same, float32; the scalar-expert call,
    which shares its body, under the bf16 feed an 8-row call of its own
    would not take): every used row to the last bit; the rows past `used`
    never written (the interpreter marks memory nobody wrote with NaN)."""
    from distributed_llama_tpu.ops import pallas_q40 as q

    (n_e, d, n), tm, tiles, used, feed = STATIONARY_CASES[case]
    out_dtype = jnp.bfloat16 if feed == "bf16" else jnp.float32
    w = QuantizedTensor(
        jnp.asarray(rng.integers(0, 256, (n_e, d, n // 2), dtype=np.uint8)),
        jnp.asarray(_wide_scales(rng, "u16", False, n_e, d, n // 32)))
    x = jnp.asarray(rng.standard_normal((len(tiles) * tm, n),
                                        dtype=np.float32), jnp.bfloat16)
    waves = 2 if case == "two_waves" else 1
    wave = len(tiles) // waves
    assert q._unpacks_once(tm, 256)

    def call(stationary):
        monkeypatch.setattr(q, "_unpacks_once", lambda *a: stationary)
        q.q40_expert_matmul.clear_cache()
        out = [q.q40_expert_matmul(
            x[k * wave * tm:(k + 1) * wave * tm], w,
            jnp.asarray(tiles[k * wave:(k + 1) * wave], jnp.int32),
            jnp.int32(min(max(used - k * wave, 0), wave)),
            out_dtype=out_dtype, interpret=True, token_rows=256)
            for k in range(waves)]
        q.q40_expert_matmul.clear_cache()
        return np.concatenate([np.asarray(o, np.float32) for o in out])

    got, want = call(True), call(False)
    live = used * tm
    assert np.isnan(got[live:]).all() and np.isnan(want[live:]).all()
    assert np.isfinite(want[:live]).all()
    np.testing.assert_array_equal(got[:live], want[:live])
    for j in sorted({0, used - 1} & set(range(used))):
        rows = slice(j * tm, (j + 1) * tm)
        e = tiles[j]
        if feed == "f32":
            own = q40_matmul(x[rows], QuantizedTensor(
                w.packed[e], w.scales[e]), out_dtype=out_dtype,
                interpret=True)
        else:
            own = q.q40_expert_matmul(x[rows], w, e, out_dtype=out_dtype,
                                      interpret=True, token_rows=256)
        np.testing.assert_array_equal(got[rows],
                                      np.asarray(own, np.float32))


# (E, d, n) at tiny widths: three 128-row weight blocks, so that with row
# tiles outermost a tile's gathered rows serve the blocks after the first
_GATHER_W = (4, 384, 64)
# experts of the five tiles: two runs of two tiles (the stationary order
# keeps a run's block dequantised) and one of one
_GATHER_TILES = [0, 0, 2, 3, 3]
GATHER_FEEDS = {"f32": (12, jnp.float32), "bf16": (256, jnp.bfloat16)}


@pytest.fixture(scope="module")
def gathered_calls():
    """(x, src, pre_laid, gathered) by (row tile, order, feed): the token
    rows, the row index, and the grouped call traced ONCE in the forced
    order with its rows laid out by XLA (`x[src]` handed to the call the
    parent had) and gathered by the kernel (`src=`), both taking `used`."""
    from distributed_llama_tpu.ops import pallas_q40 as q

    made = {}

    def calls(tm, stationary, feed):
        key = tm, stationary, feed
        if key in made:
            return made[key]
        rng = np.random.default_rng([tm, stationary, feed == "bf16"])
        rows, dtype = GATHER_FEEDS[feed]
        n_e, d, n = _GATHER_W
        w = QuantizedTensor(
            jnp.asarray(rng.integers(0, 256, (n_e, d, n // 2),
                                     dtype=np.uint8)),
            jnp.asarray(_wide_scales(rng, "u16", False, n_e, d, n // 32)))
        x = jnp.asarray(rng.standard_normal((rows, n), dtype=np.float32),
                        dtype)
        src = rng.integers(0, rows, len(_GATHER_TILES) * tm)
        # a ragged last tile as _pair_tiles lays one out (token 0 where no
        # pair lands), and one token's row in two tiles
        src[-tm // 2:] = 0
        src[1] = src[2 * tm + 3] = 5
        src = jnp.asarray(src, jnp.int32)
        e = jnp.asarray(_GATHER_TILES, jnp.int32)

        def call(gathers, used):
            return q.q40_expert_matmul(
                x if gathers else x[src], w, e, used, out_dtype=dtype,
                interpret=True, token_rows=rows,
                src=src if gathers else None)

        fns = [jax.jit(lambda used, g=g: call(g, used)) for g in (0, 1)]
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(q, "_unpacks_once", lambda *a: stationary)
            q.q40_expert_matmul.clear_cache()  # traced by shape alone
            for fn in fns:
                fn(jnp.int32(0))
            q.q40_expert_matmul.clear_cache()
        made[key] = (x, src, *fns)
        return made[key]

    yield calls
    jax.clear_caches()


@pytest.mark.parametrize("used", [0, 1, 3, 5],
                         ids=["none", "one", "some", "all"])
@pytest.mark.parametrize("feed", sorted(GATHER_FEEDS))
@pytest.mark.parametrize("stationary", [False, True],
                         ids=["rows_outermost", "stationary"])
@pytest.mark.parametrize("tm", [8, 16, 64])
def test_gathered_call_is_bit_equal_to_rows_laid_out_beforehand(
        gathered_calls, tm, stationary, feed, used):
    """`q40_expert_matmul(x, ..., src=src)`, which copies a used tile's
    rows out of the token rows' panels inside the kernel, against the call
    the parent had on `x[src]` laid out by XLA: every row of the used tiles
    to the last bit, in both orders of the grid (three weight blocks: with
    row tiles outermost what block 0 gathered serves the other two), under
    the float32 feed (12 token rows) and the bf16 one (256), with a ragged
    last tile, a token whose row two tiles hold and runs of two tiles an
    expert; rows past `used` are never written (the interpreter marks
    memory nobody wrote with NaN), and with none used nothing is."""
    x, src, pre_laid, gathered = gathered_calls(tm, stationary, feed)
    want = np.asarray(pre_laid(jnp.int32(used)), np.float32)
    got = np.asarray(gathered(jnp.int32(used)), np.float32)
    assert got.shape == want.shape == (len(_GATHER_TILES) * tm,
                                       _GATHER_W[1])
    live = used * tm
    assert np.isnan(got[live:]).all() and np.isnan(want[live:]).all()
    assert np.isfinite(want[:live]).all()
    np.testing.assert_array_equal(got[:live], want[:live])
    if used:
        assert np.abs(want[:live]).max() > 0.5


@pytest.mark.parametrize("dtype,bits", [(jnp.bfloat16, (8, 7)),
                                        (jnp.float32, None)],
                         ids=["bf16", "f32"])
def test_gathered_panels_hold_the_rows_own_precision(rng, dtype, bits):
    """`x[src]` was an array of x's dtype in HBM; the gathered call's
    float32 panels must hold those values. On the chip the compiler fuses
    x's producer (the Q80 round trip's bf16 product) into the cast to
    float32 and keeps the product's float32 bits unless the call rounds
    them itself (granite's check read 0.061926940 for the parent's
    0.062543589, PERF.md section 6, PR 50): the traced call rounds bf16
    rows with `reduce_precision`, which no compiler removes, adds nothing
    for float32 rows, and nothing to a call that is handed its rows."""
    from distributed_llama_tpu.ops.pallas_q40 import q40_expert_matmul

    _, w = _stack(rng, 4, 128, 64)
    x = jnp.zeros((8, 64), dtype)
    e, src = jnp.zeros((2,), jnp.int32), jnp.zeros((16,), jnp.int32)

    def rounds(x, src):
        jaxpr = jax.make_jaxpr(lambda x, src: q40_expert_matmul(
            x, w, e, None, out_dtype=dtype, interpret=True, token_rows=8,
            src=src))(x, src)
        return [(q.params["exponent_bits"], q.params["mantissa_bits"])
                for q in jaxpr.eqns[0].params["jaxpr"].eqns
                if q.primitive.name == "reduce_precision"]

    assert rounds(x, src) == ([bits] if bits else [])
    assert rounds(jnp.zeros((16, 64), dtype), None) == []


def _wide_scales(rng, scales, one_exponent, *shape):
    """Scales over everything the spread has to place exactly: as f16 bits
    every value but inf / nan (negatives, subnormals, the largest normal,
    both zeros), as hand-built f32 all 24 bits of a significand. With
    `one_exponent` every scale is +-(1 + m / 1024) / 32 in either form: all
    11 bits of an f16 significand and nothing a sum could round."""
    bits = rng.integers(0, 1 << 16, shape, dtype=np.uint16)
    if one_exponent:
        bits = bits & 0x83FF | 0x2800
    else:
        bits = np.where(bits & 0x7C00 == 0x7C00, bits & ~np.uint16(0x4000),
                        bits)
        planted = np.asarray([0x0001, 0x8001, 0x03FF, 0x83FF, 0x0400,
                              0x7BFF, 0xFBFF, 0x0000, 0x8000, 0x3C00],
                             np.uint16)
        bits[..., :planted.size // 2, :2] = planted.reshape(-1, 2)
    if scales == "u16":
        return bits
    f32 = bits.view(np.float16).astype(np.float32)
    if not one_exponent:
        f32[..., 8:, :] *= rng.standard_normal(f32[..., 8:, :].shape,
                                               dtype=np.float32)
    return f32


@pytest.mark.parametrize("scales", ["u16", "f32"])
@pytest.mark.parametrize("t", [1, 8, 64])
@pytest.mark.parametrize("kernel", ["q40_matmul", "q40_expert_matmul"])
@pytest.mark.parametrize("nb", [16, 24, 32, 48, 64])
def test_mxu_spread_is_bit_equal_to_repeat(rng, monkeypatch, nb, kernel, t,
                                           scales):
    """The spread of a narrow row's block scales on the MXU against the
    same call with `pltpu.repeat` forced: the same output to the last bit,
    in both kernels, under the f32 feed (1 and 8 rows) and the bf16 feed
    (64 rows of a 256-row program, the 256-row tile sub-tiled 8-way), for
    f16-bit scales (two bf16 terms) and hand-built f32 ones (three).

    At ONE row the CPU compiles the kernel's dots into loops fused with
    whatever made their operands, and sums in another order by how the
    scales were spread (equal spread, other rounding): there the scales
    share one exponent and the activation is a few +-1, so that every sum
    is exact in any order, and a scale placed wrongly in any of its 11 bits
    still shows. The whole range of exponents is held at 8 and 64 rows."""
    from distributed_llama_tpu.ops import pallas_q40 as q

    d, n = 256, nb * 32
    assert _tile_d(d, n // 2) == 256
    # the spread is exact at every nb up to half a lane tile, the widest the
    # issue measured; the predicate takes those where it is also faster
    assert q._spreads_on_mxu(16) and q._spreads_on_mxu(24)
    monkeypatch.setattr(q, "_spreads_on_mxu", lambda nb_: True)
    w = QuantizedTensor(
        jnp.asarray(rng.integers(0, 256, (2, d, n // 2), dtype=np.uint8)),
        jnp.asarray(_wide_scales(rng, scales, t == 1, 2, d, nb)))
    bf16_feed = t >= 16
    assert q._n_sub(256, n // 2, bf16_feed) == (8 if bf16_feed else 1)
    out_dtype = jnp.bfloat16 if bf16_feed else jnp.float32
    x = rng.standard_normal((t, n), dtype=np.float32)
    if t == 1:
        x = np.sign(x) * (rng.random((t, n)) < 1 / 16)
    x = jnp.asarray(x, jnp.bfloat16)

    def call():
        fn = getattr(q, kernel)
        fn.clear_cache()
        if kernel == "q40_matmul":
            y = fn(x, w[1], out_dtype=out_dtype, interpret=True)
        else:
            y = fn(x, w, jnp.int32(1), out_dtype=out_dtype, interpret=True,
                   token_rows=256 if bf16_feed else t)
        fn.clear_cache()
        return np.asarray(y, np.float32)

    got = call()
    monkeypatch.setattr(q, "_spreads_on_mxu", lambda nb_: False)
    want = call()
    assert np.isfinite(want).all() and np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want)
    ref = jnp.einsum("tn,dn->td", x.astype(jnp.float32),
                     dequantize_q40_jax(w[1], dtype=jnp.float32))
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2 ** -6,
                               atol=2 ** -6 * np.abs(ref).max())


def test_tile_d_refuses_a_weight_no_tile_fits():
    with pytest.raises(ValueError, match="scoped-VMEM"):
        _tile_d(4097, 20000)  # 128 x 20000 packed bytes > the budget


@pytest.mark.parametrize("t,out_dtype", [(1, jnp.float32),
                                         (32, jnp.bfloat16)])
def test_ragged_last_block_matches_dequant_oracle(rng, monkeypatch, t,
                                                  out_dtype):
    """A ragged cdiv grid (d = 2*256 + 240) in decode (f32) and sub-tiled
    prefill (bf16) modes vs the XLA dequant path: the padded rows of the
    last block must not leak into any kept output column."""
    import distributed_llama_tpu.ops.pallas_q40 as q

    d, n = 752, 512
    # shrink the budget so the tiny test weight takes the ragged branch
    # (its 16 scale blocks a row are not whole lane tiles: a tile counts a
    # quarter larger, _tile_d)
    monkeypatch.setattr(q, "_TILE_BYTES_MAX", 320 * (n // 2))
    assert _tile_d(d, n // 2) == 256 and d % 256
    qt = _qt(rng, d, n)
    x = jnp.asarray(rng.standard_normal((t, n), dtype=np.float32))
    ref = jnp.einsum("tn,dn->td", x,
                     dequantize_q40_jax(qt, dtype=jnp.float32))
    q40_matmul.clear_cache()
    got = np.asarray(q40_matmul(x, qt, out_dtype=out_dtype, interpret=True),
                     dtype=np.float32)
    q40_matmul.clear_cache()
    assert got.shape == (t, d) and np.isfinite(got).all()
    if out_dtype == jnp.float32:
        np.testing.assert_allclose(got, np.asarray(ref), atol=2e-4,
                                   rtol=1e-4)
    else:
        np.testing.assert_allclose(got, np.asarray(ref), rtol=2 ** -6,
                                   atol=2 ** -6 * np.abs(ref).max())


@pytest.mark.parametrize("d", [256, 1024])
def test_subtiled_bf16_prefill_matches_whole_tile(rng, d, monkeypatch):
    """The mxu_bf16 unpack/MXU interleave (t>=16, bf16 out, td=256 sub-tiled
    8-way) must be a pure regrouping of output writes: each output element
    still sees one full-N contraction, so forcing n_sub=1 on the same kernel
    must reproduce the sub-tiled output to within 1 bf16 ulp (XLA's dot
    blocks its f32 accumulation differently per output shape, so bitwise
    equality is not guaranteed — but the math is the same contraction).
    (A bf16-dequant einsum oracle is deliberately not the reference here:
    the kernel's -8-offset fold amplifies bf16 rounding vs naively-rounded
    (nib-8)*s weights — see the module docstring.)"""
    from distributed_llama_tpu.ops import pallas_q40 as q

    n, t = 1024, 32
    qt = _qt(rng, d, n)
    td = _tile_d(d, qt.packed.shape[1])
    assert q._n_sub(td, qt.packed.shape[1], True) == (8 if td == 256 else 1)
    x = jnp.asarray(rng.standard_normal((t, n), dtype=np.float32))
    got = q40_matmul(x, qt, out_dtype=jnp.bfloat16, interpret=True)
    assert got.dtype == jnp.bfloat16

    monkeypatch.setattr(q, "_n_sub", lambda td_, m_, mxu: 1)
    q40_matmul.clear_cache()
    whole = q40_matmul(x, qt, out_dtype=jnp.bfloat16, interpret=True)
    q40_matmul.clear_cache()  # drop the patched-trace cache entry
    g, w = np.asarray(got, dtype=np.float32), np.asarray(whole, dtype=np.float32)
    np.testing.assert_allclose(g, w, rtol=2 ** -7, atol=2 ** -7 * np.abs(w).max())

    # loose sanity vs the exact f32 oracle (bf16 feeds: ~1% relative)
    ref = jnp.einsum("tn,dn->td", x, dequantize_q40_jax(qt, dtype=jnp.float32))
    scale = float(np.abs(np.asarray(ref)).max())
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(ref),
        atol=0.03 * scale, rtol=0.03)


def test_subtiled_expert_kernel_matches_whole_tile(rng, monkeypatch):
    """The expert kernel's leading-dim ref slicing (packed_ref[0, sl, :])
    must survive sub-tiling: a t>=16 bf16 expert matmul at a td=256 tile
    runs n_sub=8, and forcing n_sub=1 must agree to 1 bf16 ulp (MoE
    prefill's hot path — decode t=1 never sub-tiles)."""
    from distributed_llama_tpu.ops import pallas_q40 as q
    from distributed_llama_tpu.ops.pallas_q40 import q40_expert_matmul

    n_e, d, n, t, e = 4, 256, 1024, 32, 2
    qts, stack = _stack(rng, n_e, d, n)
    assert q._n_sub(_tile_d(d, stack.packed.shape[2]),
                    stack.packed.shape[2], True) == 8
    x = jnp.asarray(rng.standard_normal((t, n), dtype=np.float32))
    got = q40_expert_matmul(x, stack, jnp.int32(e),
                            out_dtype=jnp.bfloat16, interpret=True)
    assert got.dtype == jnp.bfloat16

    monkeypatch.setattr(q, "_n_sub", lambda td_, m_, mxu: 1)
    q40_expert_matmul.clear_cache()
    whole = q40_expert_matmul(x, stack, jnp.int32(e),
                              out_dtype=jnp.bfloat16, interpret=True)
    q40_expert_matmul.clear_cache()
    g = np.asarray(got, dtype=np.float32)
    w = np.asarray(whole, dtype=np.float32)
    np.testing.assert_allclose(g, w, rtol=2 ** -7, atol=2 ** -7 * np.abs(w).max())

    # and the sub-tiled output still tracks the selected expert's oracle
    ref = np.asarray(jnp.einsum("tn,dn->td", x,
                                dequantize_q40_jax(qts[e], dtype=jnp.float32)))
    scale = float(np.abs(ref).max())
    np.testing.assert_allclose(g, ref, atol=0.03 * scale, rtol=0.03)
