"""Component microbenchmarks: achievable GEMV bandwidth, attention cost,
cache-update cost — isolates where decode time goes.

Each jit call carries a constant dispatch cost, so each
benchmark runs its body R times inside one jit (outer lax.scan with a
feedback dependency) at two values of R; the slope (t2-t1)/(R2-R1) is the
true per-iteration time, free of the constant.

`q40_shapes` is the table that sets `ops/pallas_q40._spreads_on_mxu`: both
Q40 kernels at the narrow contractions the configurations hold, each with
the block scales spread by `pltpu.repeat` and on the MXU, on one chip in
one process (PERF.md section 6, PR 40).

`q40_orders` (run by `q40_shapes` too) is the table that sets
`ops/pallas_q40._unpacks_once`: the grouped expert call at the expert shapes
of the configurations, row tiles outermost against weight blocks outermost
with the block kept dequantised, at 1, 2, 3 and 5 row tiles an expert and
at the tiles an even router gives (PERF.md section 6, PR 49).

`q40_gather` (run by `q40_shapes` too) reads what the grouped call's own
row gather costs a used tile and what it takes out of XLA: the gate
projection of the five MoE cells' two step programs, token rows gathered in
the kernel by `src` against `x[src]` laid out by XLA and handed over
(PERF.md section 6, PR 50).

`flash_grid` reads one call of `flash_attention` at Mistral's and
olmo-hybrid's decode and 32-token-chunk shapes, with the rows a cell's step
holds (a few live rows at their positions, the rest gated), another tree's
kernel file (a head a grid step, the parent of PR 54) against this tree's
(a tile of heads a step), bit for bit (PERF.md section 6, PR 54).

`sample_prep` reads what the sampling summary costs at the end of a step
program (`ops/sharded_vocab.sample_summary`: masked argmax, float32 softmax
at a temperature a row, the two sorts of `top_candidates`, one packed leaf)
and what the host
then fetches, at the rows and vocabularies of the configurations;
`sample_view` what a decode step's HOST costs with the summary serving every
row, with none proven (the whole-fetch fallback) and without it (PERF.md
section 6, PR 53).

Usage: python tools/microbench.py [all|gemv|gemv_q40|gemv_pallas|attn|cache|q40_shapes|q40_orders|q40_gather|flash_grid [<another tree's ops/pallas_attention.py>]|sample_prep|sample_view]
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_llama_tpu.quants.jax_codec import QuantizedTensor, dequantize_q40_jax
from distributed_llama_tpu.ops.attention import decode_attention

L, D, H = 32, 4096, 11008
SEQ, KVH, HS = 2048, 32, 128
R1, R2 = 4, 32  # wide spread: run-to-run jitter swamps small slopes


def slope_time(make_run, *args, reps=(R1, R2), tries=3):
    """make_run(reps) -> jitted fn; returns per-rep seconds via slope."""
    times = {}
    for r in reps:
        fn = make_run(r)
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0])  # warm/compile
        best = 1e9
        for _ in range(tries):
            t0 = time.perf_counter()
            out = fn(*args)
            np.asarray(jax.tree.leaves(out)[0])
            best = min(best, time.perf_counter() - t0)
        times[r] = best
    return (times[reps[1]] - times[reps[0]]) / (reps[1] - reps[0])


def _outer(body_scan, reps):
    """Repeat body_scan(x, w) -> x' reps times with feedback."""
    def run(w, x):
        def rep(x, _):
            return body_scan(x, w), None
        x, _ = jax.lax.scan(rep, x, None, length=reps)
        return x
    return jax.jit(run)


def bench_gemv_dense():
    w = jnp.zeros((L, H, D), jnp.bfloat16)
    x = jnp.ones((1, D), jnp.bfloat16)

    def body(x, w):
        def layer(x, wl):
            y = jnp.einsum("bn,dn->bd", x, wl, preferred_element_type=jnp.bfloat16)
            return x + y[:, :D] * jnp.bfloat16(1e-6), None
        x, _ = jax.lax.scan(layer, x, w)
        return x

    dt = slope_time(lambda r: _outer(body, r), w, x)
    gb = L * H * D * 2 / 1e9
    print(f"gemv dense bf16: {dt*1e3:.3f} ms/pass for {gb:.2f} GB -> {gb/dt:.0f} GB/s")


def _q40(shape_d, shape_n, layers=L, seed=0):
    rng = np.random.default_rng(seed)
    nb = shape_n // 32
    packed = rng.integers(0, 256, (layers, shape_d, 16 * nb), dtype=np.uint8)
    scales = (rng.random((layers, shape_d, nb), dtype=np.float32) * 0.004)
    return QuantizedTensor(jnp.asarray(packed), jnp.asarray(scales))


def bench_gemv_q40():
    w = _q40(H, D)
    x = jnp.ones((1, D), jnp.bfloat16)

    def body(x, w):
        def layer(x, wl):
            wd = dequantize_q40_jax(wl, jnp.bfloat16)
            y = jnp.einsum("bn,dn->bd", x, wd, preferred_element_type=jnp.bfloat16)
            return x + y[:, :D] * jnp.bfloat16(1e-6), None
        x, _ = jax.lax.scan(layer, x, w)
        return x

    dt = slope_time(lambda r: _outer(body, r), w, x)
    gb = (w.packed.size + w.scales.size * 2) / 1e9
    print(f"gemv q40 xla: {dt*1e3:.3f} ms/pass for {gb:.2f} GB packed -> {gb/dt:.0f} GB/s")


def bench_gemv_pallas():
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul

    w = _q40(H, D)
    x = jnp.ones((1, D), jnp.bfloat16)

    def body(x, w):
        def layer(x, wl):
            y = q40_matmul(x, wl, out_dtype=jnp.bfloat16)
            return x + y[:, :D] * jnp.bfloat16(1e-6), None
        x, _ = jax.lax.scan(layer, x, w)
        return x

    dt = slope_time(lambda r: _outer(body, r), w, x)
    gb = (w.packed.size + w.scales.size * 2) / 1e9
    print(f"gemv q40 pallas: {dt*1e3:.3f} ms/pass for {gb:.2f} GB packed -> {gb/dt:.0f} GB/s")


def bench_attn():
    # head-major cache layout (B, KVH, S, hs) — models/transformer.KVCache
    k = jnp.zeros((L, 1, KVH, SEQ, HS), jnp.bfloat16)
    v = jnp.zeros((L, 1, KVH, SEQ, HS), jnp.bfloat16)
    q0 = jnp.ones((1, 1, KVH, HS), jnp.bfloat16)
    pos = jnp.full((1, 1), SEQ - 1, jnp.int32)

    def body(q, kv):
        def layer(q, kvl):
            kl, vl = kvl
            att = decode_attention(q, kl, vl, pos)
            return q + att * jnp.bfloat16(1e-6), None
        q, _ = jax.lax.scan(layer, q, kv)
        return q

    dt = slope_time(lambda r: _outer(body, r), (k, v), q0)
    gb = (k.size + v.size) * 2 / 1e9
    print(f"attention (seq={SEQ}): {dt*1e3:.3f} ms/pass for {gb:.2f} GB cache -> {gb/dt:.0f} GB/s")


def bench_cache():
    k = jnp.zeros((L, 1, KVH, SEQ, HS), jnp.bfloat16)
    new0 = jnp.ones((1, 1, KVH, HS), jnp.bfloat16)

    def body(new, k):
        def layer(new, kl):
            kl = jax.lax.dynamic_update_slice(
                kl, new.transpose(0, 2, 1, 3), (0, 0, SEQ - 1, 0))
            return new + kl[:, :, -1] * jnp.bfloat16(1e-6), kl
        new, k2 = jax.lax.scan(layer, new, k)
        return new

    dt = slope_time(lambda r: _outer(body, r), k, new0)
    gb = k.size * 2 / 1e9
    print(f"cache update scan: {dt*1e3:.3f} ms/pass ({gb:.2f} GB buffer)")


# scale blocks a row of the contractions the configurations and their tp
# shards hold at or under half a lane tile (512, 768, 1024, 1536, 2048 wide:
# granite's expert down 768 and shared down 1536, sarvam's expert and shared
# down 2048), and the 4096-wide control, which takes pltpu.repeat either way
Q40_NB = (16, 24, 32, 48, 64, 128)
Q40_ROWS = 4096          # output rows of every shape in the table
Q40_EXPERTS = 36         # granite-4.0-h-small-ep2's held experts a layer
# (rows a tile, tiles, tiles used, the program's token rows): the grouped
# call of a full decode step (f32 feed) and of a 256-row chunk (bf16 feed)
Q40_FEEDS = {"8 rows f32": (8, 36, 24, 8), "64 rows bf16": (64, 56, 28, 256)}
HBM_GBS = 819.0          # TPU v5e


def _q40_wide_scales(rng, *shape):
    """Packed bytes and f16 scale BITS over the whole f16 range but inf/nan
    (negatives, subnormals, the largest normal): what the two spreads must
    agree on to the last bit."""
    nb = shape[-1] // 32
    packed = rng.integers(0, 256, (*shape[:-1], 16 * nb), dtype=np.uint8)
    bits = rng.integers(0, 1 << 16, (*shape[:-1], nb), dtype=np.uint16)
    bits = np.where(bits & 0x7C00 == 0x7C00, bits & ~np.uint16(0x4000), bits)
    return QuantizedTensor(jnp.asarray(packed), jnp.asarray(bits))


def bench_q40_shapes():
    """us a used row tile of `q40_expert_matmul` and us a call of
    `q40_matmul`, scales spread by repeat | on the MXU, by the method of PR
    39's diagnosis: calls chained in one program (slope_time), the same
    program with NO tile used subtracted (its grid steps and the
    activation's split are not the kernel's read), the used tiles' bytes
    over what is left."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    own = pq._spreads_on_mxu
    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16

    def row(kernel, nb, feed, weight_bytes, measure):
        """One line of the table: measure() -> (us, output) under each
        spread, forced on every nb up to half a lane tile; the kernels'
        traces are cached by shape, so they are dropped at each switch."""
        us, out = {}, {}
        for mxu in (False, True):
            pq._spreads_on_mxu = lambda nb_: mxu and nb_ <= pq.LANES // 2
            pq.q40_matmul.clear_cache()
            pq.q40_expert_matmul.clear_cache()
            us[mxu], out[mxu] = measure()
        gbs = {k: weight_bytes / v / 1e3 for k, v in us.items()}
        print(f"{kernel} | {nb} | {nb * 32} | {feed} | {us[False]:.2f} | "
              f"{us[True]:.2f} "
              f"| {gbs[False]:.0f} ({100 * gbs[False] / HBM_GBS:.0f} %) "
              f"| {gbs[True]:.0f} ({100 * gbs[True] / HBM_GBS:.0f} %) | "
              f"{np.array_equal(out[False], out[True])}", flush=True)

    print(f"{jax.devices()[0].device_kind}; predicate takes nb in "
          f"{[nb for nb in Q40_NB if own(nb)]}")
    print("kernel | nb | contraction | feed | repeat us | mxu us | "
          "repeat GB/s (% of 819) | mxu GB/s (%) | bit-equal")
    try:
        for nb in Q40_NB:
            n = nb * 32
            w = _q40_wide_scales(rng, Q40_EXPERTS, Q40_ROWS, n)
            weight_bytes = Q40_ROWS * (16 * nb + 2 * nb)   # of one expert
            for feed, (tile, n_tiles, used, rows) in Q40_FEEDS.items():
                x = jnp.asarray(rng.standard_normal((n_tiles * tile, n)),
                                bf16)
                e = jnp.asarray(np.sort(rng.choice(
                    Q40_EXPERTS, n_tiles, replace=n_tiles > Q40_EXPERTS)),
                    jnp.int32)

                def call(x, w, u):
                    return pq.q40_expert_matmul(x, w, e, u, out_dtype=bf16,
                                                token_rows=rows)

                def body(x, wu):
                    return x + call(x, *wu)[:, :x.shape[1]] * bf16(1e-6)

                def measure():
                    t = [slope_time(lambda r: _outer(body, r),
                                    (w, jnp.int32(u)), x) for u in (used, 0)]
                    y = call(x, w, jnp.int32(used))[:used * tile]
                    return ((t[0] - t[1]) / used * 1e6,
                            np.asarray(y, np.float32))

                row("q40_expert_matmul", nb,
                    f"{feed}, {used} of {n_tiles} tiles used", weight_bytes,
                    measure)
            for t_rows in (8, 256):
                x = jnp.asarray(rng.standard_normal((t_rows, n)), bf16)

                def body(x, w):
                    y = pq.q40_matmul(x, w, out_dtype=bf16)
                    return x + y[:, :x.shape[1]] * bf16(1e-6)

                def measure():   # 6-70 us a call: many repetitions
                    us = slope_time(lambda r: _outer(body, r), w[0], x,
                                    reps=(16, 256)) * 1e6
                    y = pq.q40_matmul(x, w[0], out_dtype=bf16)
                    return us, np.asarray(y, np.float32)

                row("q40_matmul", nb, f"{t_rows} rows, a call", weight_bytes,
                    measure)
    finally:
        pq._spreads_on_mxu = own
        pq.q40_matmul.clear_cache()
        pq.q40_expert_matmul.clear_cache()


# (E, d, n) of an expert projection, rows a tile, tiles a wave, distinct
# experts used, rows an expert's group holds under an EVEN router (the
# chunk's 256 rows x top-k / the router's width): the grouped call of a
# 256-row chunk (bf16 feed) as `_pair_layout` lays it out. Which order
# each takes is `_unpacks_once`'s to say (the table's `own order`)
Q40_ORDER_SHAPES = {
    "kimi gate": ((64, 1024, 2304), 8, 128, 8, 8),
    "kimi down": ((64, 2304, 1024), 8, 128, 8, 8),
    "sarvam gate": ((16, 2048, 4096), 16, 32, 4, 16),
    "sarvam down": ((16, 4096, 2048), 16, 32, 4, 16),
    "granite gate": ((36, 768, 4096), 64, 56, 8, 36),
    "granite down": ((36, 4096, 768), 64, 56, 8, 36),
    "mixtral gate": ((8, 14336, 4096), 64, 16, 4, 64),
}
# row tiles an expert's group holds; "even": EVERY expert used, its group
# a Poisson draw round the even router's share (at a share of one tile:
# 59 % of the groups one tile, 40 % two), which is what a deployment's
# trained router is nearest to
Q40_ORDER_TILES = (1, 2, 3, 5, "even")


def bench_q40_orders():
    """us a call of `q40_expert_matmul` with row tiles outermost | with
    weight blocks outermost and the block kept dequantised (stationary), at
    1, 2, 3 and 5 row tiles an expert and at the tiles an even router
    gives: the table `ops/pallas_q40._unpacks_once` was set from (PERF.md
    section 6, PR 49). Same method as bench_q40_shapes: calls chained in one program, the same program with
    NO tile used subtracted; the tiles' experts and the used count are
    arguments, so one executable serves a shape's every row."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    own = pq._unpacks_once
    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16
    print(f"{jax.devices()[0].device_kind}")
    print("shape | E, d, n | tile rows | refetch / expert bytes | own order | "
          "tiles an expert | used tiles | rows-outer us | stationary us | "
          "us a used tile | bit-equal")
    try:
        for name, ((n_e, d, n), tile, n_tiles, groups, even) in (
                Q40_ORDER_SHAPES.items()):
            w = _q40_wide_scales(rng, n_e, d, n)
            x = jnp.asarray(rng.standard_normal((n_tiles * tile, n)), bf16)
            m = n // 2
            td = pq._tile_d(d, m)
            # what the stationary order re-fetches, a tile's activations
            # once a weight block, over the expert's own packed bytes
            ratio = (-(-d // td) * tile * (2 * m + m // 16) * 4
                     / (d * (m + 2 * (m // 16))))

            def call(x, w, e, u):
                return pq.q40_expert_matmul(x, w, e, u, out_dtype=bf16,
                                            token_rows=256)

            def body(x, weu):
                y = call(x, *weu)[:1, :1]
                return x + jnp.where(jnp.isfinite(y), y, 0) * bf16(1e-9)

            for per in Q40_ORDER_TILES:
                if per == "even":
                    of = np.repeat(np.arange(n_e),
                                   -(-rng.poisson(even, n_e) // tile))
                    used = min(len(of), n_tiles)
                    per = f"even ({used / len(np.unique(of[:used])):.2f})"
                else:
                    used = min(groups * per, n_tiles)
                    of = np.repeat(np.sort(rng.choice(
                        n_e, -(-used // per), replace=False)), per)
                e = np.full(n_tiles, n_e - 1, np.int32)
                e[:used] = of[:used]
                e = jnp.asarray(e)
                us, out = {}, {}
                for stationary in (False, True):
                    pq._unpacks_once = lambda *a, s=stationary: s
                    pq.q40_expert_matmul.clear_cache()
                    # 20-900 us a call: many repetitions, the best of many
                    t = [slope_time(lambda r: _outer(body, r),
                                    (w, e, jnp.int32(u)), x,
                                    reps=(16, 128), tries=7)
                         for u in (used, 0)]
                    us[stationary] = (t[0] - t[1]) * 1e6
                    out[stationary] = np.asarray(
                        call(x, w, e, jnp.int32(used))[:used * tile],
                        np.float32)
                print(f"{name} | {n_e}, {d}, {n} | {tile} | {ratio:.2f} | "
                      f"{'stationary' if own(tile, 256) else 'rows-outer'}"
                      f" | {per} | {used} | {us[False]:.1f} | {us[True]:.1f} | "
                      f"{us[False] / used:.2f} -> {us[True] / used:.2f} | "
                      f"{np.array_equal(out[False], out[True])}", flush=True)
    finally:
        pq._unpacks_once = own
        pq.q40_expert_matmul.clear_cache()


# (E, d, n) of a gate projection, the program's token rows, rows a tile,
# tiles a wave (`_pair_layout`), tiles used (a full decode batch whose rows
# choose mostly distinct experts; a chunk under an even router): the
# grouped gate call of the five MoE cells' decode step (float32 feed) and
# 256-row chunk (bf16 feed)
Q40_GATHER_SHAPES = {
    "kimi decode": ((64, 1024, 2304), 8, 8, 64, 14),
    "kimi chunk": ((64, 1024, 2304), 256, 8, 128, 96),
    "granite decode": ((36, 768, 4096), 8, 8, 36, 24),
    "granite chunk": ((36, 768, 4096), 256, 64, 56, 38),
    "sarvam decode": ((16, 2048, 4096), 8, 8, 16, 7),
    "sarvam chunk": ((16, 2048, 4096), 256, 16, 32, 24),
    "mixtral decode": ((8, 14336, 4096), 8, 8, 8, 7),
    "mixtral chunk": ((8, 14336, 4096), 256, 64, 16, 11),
}


def bench_q40_gather():
    """us a used tile of the grouped gate call with its rows laid out by
    XLA beforehand (`x[src]`, then the float32 split over the BUFFER's rows)
    | gathered in the kernel from the token rows' panels, and what is left
    of a call with NO tile used (XLA's part and the skipped grid steps) in
    both: the second pair's difference is the XLA gather and split the
    in-kernel gather replaces, the first pair's what it costs (PERF.md
    section 6, PR 50). Same method as bench_q40_shapes: calls chained in
    one program, tiles' experts, used count and row index as arguments."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16
    print(f"{jax.devices()[0].device_kind}")
    print("shape | E, d, n | token rows | tile rows | tiles | used | "
          "pre-laid us a used tile | gathered us a used tile | "
          "pre-laid us a call, none used | gathered us a call, none used | "
          "bit-equal")
    for name, ((n_e, d, n), rows, tile, n_tiles, used) in (
            Q40_GATHER_SHAPES.items()):
        w = _q40_wide_scales(rng, n_e, d, n)
        x = jnp.asarray(rng.standard_normal((rows, n)), bf16)
        src = jnp.asarray(rng.integers(0, rows, n_tiles * tile), jnp.int32)
        e = np.full(n_tiles, n_e - 1, np.int32)
        e[:used] = np.sort(rng.choice(n_e, used, replace=used > n_e))
        e = jnp.asarray(e)

        def call(gathers, x, w, e, u, src):
            return pq.q40_expert_matmul(
                x if gathers else x[src], w, e, u, out_dtype=bf16,
                token_rows=rows, src=src if gathers else None)

        us, idle, out = {}, {}, {}
        for gathers in (False, True):
            def body(x, weus):
                y = call(gathers, x, *weus)[:1, :1]
                return x + jnp.where(jnp.isfinite(y), y, 0) * bf16(1e-9)

            t = [slope_time(lambda r: _outer(body, r),
                            (w, e, jnp.int32(u), src), x,
                            reps=(16, 128), tries=7) for u in (used, 0)]
            us[gathers] = (t[0] - t[1]) / used * 1e6
            idle[gathers] = t[1] * 1e6
            out[gathers] = np.asarray(call(
                gathers, x, w, e, jnp.int32(used), src)[:used * tile],
                np.float32)
        print(f"{name} | {n_e}, {d}, {n} | {rows} | {tile} | {n_tiles} | "
              f"{used} | {us[False]:.2f} | {us[True]:.2f} | "
              f"{idle[False]:.1f} | {idle[True]:.1f} | "
              f"{np.array_equal(out[False], out[True])}", flush=True)


# name: (b, t, h, kvh, s, live rows' first positions a reading, chained).
# A decode reading of Mistral's chat cell is the mean of one and two live
# rows (`decode_rows_per_step` 1.48); a chained chunk puts its rows on one
# slot as consecutive segments (the slot map), the others each on its own
FLASH_GRID_SHAPES = {
    "mistral-7b decode": (8, 1, 32, 8, 4096, ((400,), (400, 400)), True),
    "mistral-7b chunk32": (8, 32, 32, 8, 4096,
                           (tuple(1024 + 32 * r for r in range(8)),), True),
    "olmo-hybrid-7b decode": (8, 1, 30, 30, 8192, ((4200,) * 3,), False),
    "olmo-hybrid-7b chunk32": (8, 32, 30, 30, 8192, ((2100,) * 3,), False),
}


def _kernel_file(path):
    """`flash_attention` of another tree's ops/pallas_attention.py, loaded
    beside this tree's (its relative imports resolve here)."""
    import importlib.util

    name = "distributed_llama_tpu.ops._other_pallas_attention"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod.flash_attention


def bench_flash_grid():
    """us a call of `flash_attention` alone at the served shapes with the
    rows a cell's step holds (live rows at their positions, the rest gated
    at pos == S), the kernel of another tree's file (argv[2], the parent's
    `ops/pallas_attention.py`: a head a grid step) against this tree's (a
    tile of heads a step), with this tree's grid and whether the outputs
    are equal bit for bit (else: whose differ from the first's; PERF.md
    section 6, PR 54). Calls chained in one program, as bench_q40_gather's."""
    from distributed_llama_tpu.ops import pallas_attention as pa

    candidates = {"this tree": pa.flash_attention}
    if len(sys.argv) > 2:
        candidates = {"other tree": _kernel_file(sys.argv[2]), **candidates}
    rng = np.random.default_rng(0)
    bf16, hs = jnp.bfloat16, 128
    print(f"{jax.devices()[0].device_kind}")
    print("shape | b, t, h, kvh, s | live rows | this tree's grid | "
          + " | ".join(f"{c} us a call" for c in candidates)
          + " | bit-equal")
    for name, (b, t, h, kvh, s, lives, chained) in FLASH_GRID_SHAPES.items():
        q = jnp.asarray(rng.standard_normal((b, t, h, hs)), bf16)
        k, v = (jnp.asarray(rng.standard_normal((b, kvh, s, hs)), bf16)
                for _ in range(2))
        us, out = {c: [] for c in candidates}, {}
        for live in lives:
            pos0 = np.full((b,), s, np.int32)
            pos0[:len(live)] = live
            slots = np.arange(b, dtype=np.int32)
            if chained and t > 1:
                slots[:len(live)] = 0
                slots[len(live):] = np.arange(1, b - len(live) + 1)
            q_pos = jnp.asarray(pos0[:, None] + np.arange(t)[None, :],
                                jnp.int32)
            sl = jnp.asarray(slots) if chained else None
            for c, fn in candidates.items():
                def body(q, kvps, fn=fn):
                    y = fn(q, *kvps[:3], slots=kvps[3])
                    return q + jnp.where(jnp.isfinite(y), y, 0) * bf16(1e-9)

                us[c].append(slope_time(
                    lambda r: _outer(body, r), (k, v, q_pos, sl), q,
                    reps=(16, 128), tries=7) * 1e6)
                out[c] = np.asarray(fn(q, k, v, q_pos, slots=sl), np.float32)
        first = next(iter(out.values()))
        print(f"{name} | {b}, {t}, {h}, {kvh}, {s} | "
              f"{' or '.join(str(len(x)) for x in lives)} at {lives[0][0]} | "
              f"{pa.flash_grid(b, t, h, kvh, s, hs, bf16)} | "
              + " | ".join(f"{np.mean(us[c]):.1f}" for c in candidates)
              + " | " + (", ".join(c for c, o in out.items()
                                   if not np.array_equal(first, o))
                         or "True"), flush=True)


def bench_sample_prep():
    """us a call of the summary as the step programs run it, at k
    candidates a row; of it the softmax and argmax alone (k = 1); and the
    host's fetch of the packed leaf against the fetch of the logits it
    replaces. Calls chained in one program through the temperatures, so
    that no call folds into the one before."""
    from distributed_llama_tpu.ops.sharded_vocab import sample_summary

    rng = np.random.default_rng(0)
    print(f"{jax.devices()[0].device_kind}")
    print("rows | vocab | "
          + " | ".join(f"summary k={k} us" for k in SAMPLE_PREP_KS)
          + " | fetch summary k=512 us | fetch logits us")
    for rows, vocab in SAMPLE_PREP_SHAPES:
        lg = jnp.asarray(rng.standard_normal((rows, vocab)) * 3.0,
                         jnp.float32)
        temps = jnp.full((rows,), 0.8, jnp.float32)
        us = []
        for k in SAMPLE_PREP_KS:
            def body(t, lg):
                out = sample_summary(lg, t, jnp.int32(vocab - 7), k)
                return t + (out[:, 1] & 1).astype(jnp.float32) * 1e-7

            us.append(slope_time(lambda r: _outer(body, r), lg, temps,
                                 reps=(8, 64), tries=5) * 1e6)
        prep = jax.jit(lambda lg, t: sample_summary(
            lg, t, jnp.int32(vocab - 7), 512))
        bump = jax.jit(lambda lg: lg + 1.0)
        fetch = []
        for make in (lambda: prep(lg, temps), lambda: bump(lg)):
            best = 1e9
            for _ in range(7):
                a = make()
                a.block_until_ready()
                t0 = time.perf_counter()
                np.asarray(a)
                best = min(best, time.perf_counter() - t0)
            fetch.append(best * 1e6)
        print(f"{rows} | {vocab} | "
              + " | ".join(f"{u:.1f}" for u in us)
              + f" | {fetch[0]:.1f} | {fetch[1]:.1f}", flush=True)


def bench_sample_view():
    """ms of HOST a decode step of a tiny engine (its device step is a few
    tenths of a ms: what is read is the dispatch, the fetches and the
    sampling) and the D2H fetches a step, 8 rows sampled at 0.8 / 0.9,
    three ways: the step's own summary on a head that proves every row
    (logits std 4), the same on a head that proves none (std 1.5: every
    row reads the ONE whole fetch behind the summary's small one), and
    that flat head dispatched without temperatures (no summary, the full
    view: what every step paid before PR 53). The last two differ by what
    the summary costs a head it cannot serve (PERF.md section 6, PR 53)."""
    from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
    from distributed_llama_tpu.models.params import load_params, random_tensors
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.sampler import Sampler

    rows, steps = 8, 300
    print(f"{jax.devices()[0].device_kind}")
    print("vocab | head | view | host ms a step (median) | fetches a step "
          "| rows from the summary")
    for vocab in (50176, 100352):
        spec = ModelSpec(arch=ArchType.LLAMA, dim=256, hidden_dim=512,
                         n_layers=2, n_heads=4, n_kv_heads=2,
                         vocab_size=vocab, seq_len=512,
                         hidden_act=HiddenAct.SILU)
        params = load_params(spec, random_tensors(spec, seed=5, scale=0.05),
                             mode="dense", dtype=jnp.float32)
        eng = Engine(spec, dict(params), batch=rows,
                     compute_dtype=jnp.float32, cache_dtype=jnp.float32)
        tok = np.arange(rows, dtype=np.int32)[:, None] + 1
        lg = eng.fetch_logits(eng.slot_decode_step(
            tok, np.zeros((rows,), np.int32)))
        wcls = np.asarray(params["wcls"]) / lg.std()
        temps = np.full((rows,), 0.8, np.float32)
        fetches = [0]
        real = eng.fetch_logits
        eng.fetch_logits = lambda a: fetches.__setitem__(
            0, fetches[0] + 1) or real(a)
        for head, std, summary in (("peaked", 4.0, True),
                                   ("flat", 1.5, True),
                                   ("flat", 1.5, False)):
            eng.params = dict(eng.params, wcls=jnp.asarray(wcls * std))
            smp = [Sampler(vocab, 0.8, 0.9, seed=r) for r in range(rows)]
            took = []
            fetches[0] = 0
            served = eng.vocab_sample_stats["sharded"]
            for i in range(steps):
                pos = np.full((rows,), i, np.int32)
                t0 = time.perf_counter()
                lg = eng.slot_decode_step(
                    tok, pos, **({"temps": temps} if summary else {}))
                view = eng.sample_view(lg, temps, vocab)
                for r in range(rows):
                    tok[r, 0] = view.sample(smp[r], r)
                took.append(time.perf_counter() - t0)
            served = eng.vocab_sample_stats["sharded"] - served
            print(f"{vocab} | {head} | "
                  f"{'summary' if summary else 'full'} | "
                  f"{np.median(took[20:]) * 1e3:.3f} | "
                  f"{fetches[0] / steps + summary:.2f} | "
                  f"{served} of {steps * rows}", flush=True)


SAMPLE_PREP_KS = (1, 256, 512)
SAMPLE_PREP_SHAPES = [(rows, vocab) for rows in (8, 16)
                      for vocab in (32000, 50176, 65536, 100352)]

ALL = {
    "gemv": bench_gemv_dense,
    "gemv_q40": bench_gemv_q40,
    "gemv_pallas": bench_gemv_pallas,
    "attn": bench_attn,
    "cache": bench_cache,
    "q40_shapes": bench_q40_shapes,
    "q40_orders": bench_q40_orders,
    "q40_gather": bench_q40_gather,
    "flash_grid": bench_flash_grid,
    "sample_prep": bench_sample_prep,
    "sample_view": bench_sample_view,
}

if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    for name, fn in ALL.items():
        if which in ("all", name) or (
                which == "q40_shapes" and name in ("q40_orders",
                                                   "q40_gather")):
            fn()
