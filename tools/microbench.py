"""Component microbenchmarks: achievable GEMV bandwidth, attention cost,
cache-update cost — isolates where decode time goes.

Each jit call carries a constant dispatch cost, so each
benchmark runs its body R times inside one jit (outer lax.scan with a
feedback dependency) at two values of R; the slope (t2-t1)/(R2-R1) is the
true per-iteration time, free of the constant.

Usage: python tools/microbench.py [all|gemv|gemv_q40|gemv_pallas|attn|cache]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")

from distributed_llama_tpu.quants.jax_codec import QuantizedTensor, dequantize_q40_jax
from distributed_llama_tpu.ops.attention import decode_attention

L, D, H = 32, 4096, 11008
SEQ, KVH, HS = 2048, 32, 128
R1, R2 = 4, 32  # wide spread: run-to-run jitter swamps small slopes


def slope_time(make_run, *args):
    """make_run(reps) -> jitted fn; returns per-rep seconds via slope."""
    times = {}
    for reps in (R1, R2):
        fn = make_run(reps)
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0])  # warm/compile
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            out = fn(*args)
            np.asarray(jax.tree.leaves(out)[0])
            best = min(best, time.perf_counter() - t0)
        times[reps] = best
    return (times[R2] - times[R1]) / (R2 - R1)


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


ALL = {
    "gemv": bench_gemv_dense,
    "gemv_q40": bench_gemv_q40,
    "gemv_pallas": bench_gemv_pallas,
    "attn": bench_attn,
    "cache": bench_cache,
}

if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    for name, fn in ALL.items():
        if which in ("all", name):
            fn()
