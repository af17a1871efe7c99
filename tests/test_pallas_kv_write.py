"""In-place K/V cache write kernel (ops/pallas_kv_write.py), interpret mode.

The contract: `kv_cache_write` leaves the SAME BITS in the caches as the
drop-mode scatter it replaces in the per-row-position step programs
(models/transformer._scatter_cache_write) — rows at 0, unaligned, on a
tile edge, gated off (pos == S), at S-1 and straddling the context edge;
every row tile (f32 8, bf16 16, fp8 32); decode (T=1), a verify window
(5), a prefill chunk (32) and the widest chunk (256). That the chip's
compiler takes it is tests/test_chip_compile.py's part.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models import transformer
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.ops import pallas_kv_write
from distributed_llama_tpu.ops.pallas_kv_write import (kv_cache_write,
                                                       kv_write_supported,
                                                       row_tile)
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.scheduler import Scheduler
from distributed_llama_tpu.sampler import Sampler

S, KVH, HS = 512, 2, 128
F8 = jnp.float8_e4m3fn
KERNEL = {"use_pallas": True, "pallas_interpret": True}


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _same_bits(got, want):
    return all(np.array_equal(_bits(g), _bits(w)) for g, w in zip(got, want))


def _inputs(dtype, t, b, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    cache, new = (b, KVH, S, HS), (b, t, KVH, HS)
    return tuple(jax.random.normal(k, shape).astype(dtype)
                 for k, shape in zip(ks, (cache, cache, new, new)))


def _rows(dtype, t):
    """Start positions: 0, unaligned, on a tile edge, gated (S), the last
    position, and a window that straddles the context edge."""
    r = row_tile(dtype)
    return jnp.array([0, 7, 3 * r, S, S - 1, S - t // 2 - 1], jnp.int32)


def _scatter(kc, vc, k, v, pos, gate=None):
    idx = pos[:, None] + jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
    return transformer._scatter_cache_write(kc, vc, k, v, idx, gate)


@pytest.mark.parametrize("t", [1, 5, 32, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, F8],
                         ids=["f32", "bf16", "fp8"])
def test_kernel_is_bit_equal_to_drop_mode_scatter(dtype, t):
    pos = _rows(dtype, t)
    kc, vc, k, v = _inputs(dtype, t, pos.shape[0], seed=t)
    want = _scatter(kc, vc, k, v, pos)
    got = kv_cache_write(kc, vc, k, v, pos, interpret=True)
    assert _same_bits(got, want)
    # and something was written: row 0's window differs from the old cache
    assert not np.array_equal(_bits(got[0][0, :, :t]), _bits(kc[0, :, :t]))


@pytest.mark.parametrize("t", [1, 32])
@pytest.mark.parametrize("gate", [None, True, False],
                         ids=["ungated", "gate_on", "gate_off"])
def test_write_gate_keeps_its_meaning_through_the_kernel(gate, t):
    """`_scatter_cache_write` with the forward's cfg takes the kernel; a
    gate that is off pushes every row to S and leaves the caches as they
    were, exactly as the scatter's OOB drop does."""
    dtype = jnp.bfloat16
    pos = _rows(dtype, t)
    kc, vc, k, v = _inputs(dtype, t, pos.shape[0], seed=7)
    g = None if gate is None else jnp.asarray(gate)
    want = _scatter(kc, vc, k, v, pos, g)
    idx = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    got = jax.jit(lambda *a: transformer._scatter_cache_write(
        *a, kernel_cfg=KERNEL))(kc, vc, k, v, idx, g)
    assert _same_bits(got, want)
    if gate is False:
        assert _same_bits(got, (kc, vc))


@pytest.mark.parametrize("t", [5, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_an_inf_in_one_new_row_leaves_its_neighbours_untouched(dtype, t):
    """Rows are placed by selects: a one-hot matmul would turn the inf into
    NaN in every row it is multiplied by zero into."""
    pos = _rows(dtype, t)
    kc, vc, k, v = _inputs(dtype, t, pos.shape[0], seed=11)
    k = k.at[:, t // 2, 0, 3].set(jnp.inf)
    v = v.at[:, t // 2, 1, 5].set(-jnp.inf)
    want = _scatter(kc, vc, k, v, pos)
    got = kv_cache_write(kc, vc, k, v, pos, interpret=True)
    assert _same_bits(got, want)
    for c in got:
        c = np.asarray(c.astype(jnp.float32))
        assert not np.isnan(c).any()
        assert np.isinf(c).sum() == 4   # rows 0, 7, 3R and S-1-t//2 land it


def test_contexts_of_ragged_row_tiles_keep_the_scatter():
    assert kv_write_supported(4096, jnp.bfloat16)
    assert not kv_write_supported(4096 + 8, jnp.bfloat16)
    assert kv_write_supported(4096 + 8, jnp.float32)
    # the fallback is the scatter itself, not an error
    kc, vc, k, v = (x[:, :, :40] if x.shape[2] == S else x
                    for x in _inputs(jnp.bfloat16, 5, 2))
    pos = jnp.array([3, 38], jnp.int32)
    idx = pos[:, None] + jnp.arange(5, dtype=jnp.int32)[None, :]
    got = transformer._scatter_cache_write(kc, vc, k, v, idx, None,
                                           kernel_cfg=KERNEL)
    assert _same_bits(got, _scatter(kc, vc, k, v, pos))


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_shards_write_their_own_kv_heads(tp):
    """shard_map next to tp_flash_attention: batch on dp, kv heads on tp."""
    from jax.sharding import NamedSharding

    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.sharding import cache_pspec
    from distributed_llama_tpu.parallel.tp_q80 import tp_kv_cache_write

    mesh = make_mesh(tp=tp, devices=jax.devices()[:tp])
    dtype, t, kvh = jnp.bfloat16, 5, 4
    pos = _rows(dtype, t)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    b = pos.shape[0]
    kc, vc = (jax.random.normal(kk, (b, kvh, S, 16)).astype(dtype)
              for kk in ks[:2])
    k, v = (jax.random.normal(kk, (b, t, kvh, 16)).astype(dtype)
            for kk in ks[2:])
    want = _scatter(kc, vc, k, v, pos)
    sh = NamedSharding(mesh, cache_pspec())
    got = jax.jit(lambda *a: tp_kv_cache_write(*a, mesh, interpret=True))(
        jax.device_put(kc, sh), jax.device_put(vc, sh), k, v, pos)
    assert _same_bits(got, want)
    assert got[0].sharding.is_equivalent_to(sh, 4)


def _serve(spec, params, prompts):
    eng = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32, use_pallas=True,
                 pallas_interpret=True)
    sched = Scheduler(eng, chunk=8)
    reqs = [sched.submit(p, n, Sampler(spec.vocab_size, temperature=0.0,
                                       topp=0.9, seed=1))
            for p, n in prompts]
    for _ in range(500):
        if all(r.finished.is_set() for r in reqs):
            break
        sched.step()
    return ([list(r.tokens(timeout=5.0)) for r in reqs],
            [np.asarray(x) for x in eng.cache.k + eng.cache.v])


def test_scheduler_streams_equal_with_kernel_and_with_scatter(monkeypatch):
    """Three mixed-length requests through `slot_prefill_chunk` (padded
    tail chunks, a queued third request) and `slot_decode_step` on two
    slots: the greedy streams AND the caches' final bytes are the same with
    the kernel writing as with the scatter (kernels on in both)."""
    spec = ModelSpec(arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=64,
                     hidden_act=HiddenAct.SILU)
    params = load_params(spec, random_tensors(spec, seed=3, scale=0.05),
                         mode="q40", dtype=jnp.float32)
    prompts = [([1, 9, 23, 54, 7, 88, 101, 5, 61, 17, 3], 10),
               ([2, 40, 77, 12, 9], 4),
               ([5, 66, 31, 90, 14, 8, 55, 4, 4, 19, 21, 33, 2, 6, 70, 11,
                 12, 13], 6)]
    calls = []
    real = pallas_kv_write.kv_cache_write
    monkeypatch.setattr(pallas_kv_write, "kv_cache_write",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    got, got_cache = _serve(spec, params, prompts)
    assert calls, "the step programs did not take the kernel"

    n_kernel = len(calls)
    monkeypatch.setattr(pallas_kv_write, "kv_write_supported",
                        lambda s, dtype: False)
    want, want_cache = _serve(spec, params, prompts)
    assert len(calls) == n_kernel, "the scatter run traced the kernel"
    assert got == want and all(len(t) for t in got)
    assert all(np.array_equal(_bits(g), _bits(w))
               for g, w in zip(got_cache, want_cache))


# -- the slot map: program row b writes cache slot slots[b] -------------------

def _scatter_mapped(kc, vc, k, v, pos, slots):
    idx = pos[:, None] + jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
    return transformer._scatter_cache_write(kc, vc, k, v, idx, None,
                                            slots=slots)


@pytest.mark.parametrize("t", [1, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, F8],
                         ids=["f32", "bf16", "fp8"])
def test_a_slot_mapped_write_equals_the_write_on_the_gathered_slots(dtype, t):
    """Under a permutation the kernel leaves what the map-less call leaves
    in the caches gathered by the map, and what the scatter, which takes
    the same map, leaves: bit for bit."""
    pos = _rows(dtype, t)
    slots = jnp.array([4, 0, 5, 1, 3, 2], jnp.int32)
    kc, vc, k, v = _inputs(dtype, t, pos.shape[0], seed=t + 3)
    got = kv_cache_write(kc, vc, k, v, pos, slots=slots, interpret=True)
    on_gathered = kv_cache_write(kc[slots], vc[slots], k, v, pos,
                                 interpret=True)
    assert _same_bits([c[slots] for c in got], on_gathered)
    assert _same_bits(got, _scatter_mapped(kc, vc, k, v, pos, slots))


@pytest.mark.parametrize("start,live", [(0, 8), (64, 5), (S - 256, 8),
                                        (S - 96, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, F8],
                         ids=["f32", "bf16", "fp8"])
def test_rows_chained_on_one_slot_equal_as_many_calls_in_a_row(dtype, start,
                                                               live):
    """Eight rows (or fewer, the rest gated and naming the other slots) as
    consecutive 32-token segments of ONE slot leave in its cache what as
    many map-less calls, one segment a call, leave; every other slot keeps
    its bits, so gated rows wrote nothing."""
    from distributed_llama_tpu.runtime.scheduler import chain_map

    b, t, slot = 8, 32, 3
    kc, vc, k, v = _inputs(dtype, t, b, seed=start + live)
    pos = np.full((b,), S, np.int32)
    pos[:live] = start + t * np.arange(live)
    slots = chain_map(slot, live, b)
    got = kv_cache_write(kc, vc, k, v, jnp.asarray(pos),
                         slots=jnp.asarray(slots), interpret=True)
    want = (kc, vc)
    for r in range(live):
        one = np.full((b,), S, np.int32)
        one[slot] = pos[r]
        seg = [jnp.zeros_like(x).at[slot].set(x[r]) for x in (k, v)]
        want = kv_cache_write(*want, *seg, jnp.asarray(one), interpret=True)
    assert _same_bits(got, want)
    others = np.arange(b) != slot
    assert _same_bits([c[others] for c in got], [kc[others], vc[others]])
    lo, hi = start, start + live * t
    assert not np.array_equal(_bits(got[0][slot, :, lo:hi]),
                              _bits(kc[slot, :, lo:hi]))
    assert _same_bits([c[slot, :, :lo] for c in got],
                      [kc[slot, :, :lo], vc[slot, :, :lo]])
    assert _same_bits([c[slot, :, hi:] for c in got],
                      [kc[slot, :, hi:], vc[slot, :, hi:]])


def _collisions(pos, slots, dtype, t=32, own=True):
    """(row, row, (slot, tile)) for every cache block two DISTINCT rows of
    a slot-mapped kv_cache_write visit."""
    blocks = np.asarray(pallas_kv_write.visited_blocks(
        pos, slots, t=t, seq_len=S, dtype=dtype, own=own))
    seen, found = {}, []
    for row, visits in enumerate(blocks):
        for blk in {tuple(int(x) for x in vis) for vis in visits}:
            if blk in seen:
                found.append((seen[blk], row, blk))
            seen[blk] = row
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, F8],
                         ids=["f32", "bf16", "fp8"])
def test_blocks_of_distinct_rows_are_disjoint_for_every_map_the_packer_makes(
        dtype):
    """The race no interpret-mode run can show (the grid runs in order
    there): in and out are one buffer and the next block is fetched before
    the last is written back, so two rows must never visit one (slot,
    tile) block. Over the maps `Scheduler._prefill_chunk` can make (the
    identity with any rows live at any position; k chained rows of any
    slot from any multiple of the chunk, the rest gated on the other
    slots) no two rows do. On the host: the index map's own function."""
    from distributed_llama_tpu.runtime.scheduler import chain_map

    b, t = 8, 32
    starts = [0, t, 7 * t, S // 2, S - 8 * t, S - 3 * t, S - t]
    for slot in (0, 3, 7):
        for k in range(2, b + 1):
            for start in starts:
                if start + (k - 1) * t >= S:
                    continue
                pos = np.full((b,), S, np.int32)
                pos[:k] = start + t * np.arange(k)
                assert not _collisions(pos, chain_map(slot, k, b), dtype), (
                    slot, k, start)
    # the identity map: rows live at aligned and unaligned positions, on
    # the context's last tile and straddling its end, or gated
    rng = np.random.default_rng(0)
    edge = [0, 1, 7, 16, 31, 33, S - 40, S - t, S - t + 1, S - 1, S]
    for _ in range(64):
        pos = rng.choice(edge + list(rng.integers(0, S, 4)), b)
        assert not _collisions(pos, np.arange(b), dtype), pos
    # and what the packer must never make is caught:
    r = row_tile(dtype)
    chained = np.full((b,), S, np.int32)
    chained[:2] = [S - 2 * t, S - t]
    # a gated row naming the live slot lands on the context's last tile
    assert _collisions(chained, np.asarray([3, 3, 3, 0, 1, 2, 4, 5]), dtype)
    # a chained start off the chunk shares a tile with its successor
    off = np.full((b,), S, np.int32)
    off[:2] = [r // 2, r // 2 + t]
    assert _collisions(off, chain_map(3, 2, b), dtype)
    # the map-less index map: an aligned row's spare visit is the next
    # row's first tile (why a mapped call stops at the row's own last tile)
    aligned = np.full((b,), S, np.int32)
    aligned[:2] = [0, t]
    assert _collisions(aligned, chain_map(3, 2, b), dtype, own=False)
    assert not _collisions(aligned, chain_map(3, 2, b), dtype)
