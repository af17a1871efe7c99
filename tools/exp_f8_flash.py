"""Experiment: make the fp8 KV cache PAY in the flash kernel (VERDICT r4 #3).

A pre-PR-1 record showed the f8 cache as a 2.3x decode REGRESSION (42.1 vs
18.4 ms/token at 8k fill) even though the flash kernel upcasts per block
in-kernel — Mosaic's e4m3->bf16 `astype` on v5e (no native fp8) lowers to
slow element conversion. Candidates measured here, interleaved best-of-N:

  a) bf16 cache — the baseline the f8 row must approach
  b) f8 cache, in-kernel astype (the shipped path)
  c) f8 cache read as uint8 bits, manual bf16 reassembly in integer lanes
     (sign<<8 | (mag<<4)+0x3C00, subnormal lane fixed via an f32 ladder)
  d) like (c) but subnormals flushed to zero (requires the WRITE side to
     flush |v| < 2^-6 — one extra where per cache write)

Result (v5e, 2026-07-31, B=1 KVH=32 S=8192 hs=128, fill 7680, t=1,
best of 6 interleaved, dispatch-amortized x32):
  bf16 3.715   astype-f8 4.447   bits-f8 3.686   bitsflush-f8 3.673 ms/call
  -> the manual bit reassembly is BIT-EXACT with astype and recovers the
  bf16 rate; astype costs +0.73 ms/call here, which matched the
  end-to-end regression per layer ((42.1-18.4)/32 = 0.74 ms). Flush-vs-
  exact-subnormal is noise — keep exact subnormals (no write-side
  contract change). A second end-to-end stall remained after promoting
  the in-kernel decode: an XLA-side whole-cache bitcast materialized a
  copy per step (f8 ratio 1.52x); moving the u8 reinterpret INSIDE the
  kernel (per block, in-register) fixed it. Final whole-model A/B at 7680
  fill: bf16 18.80 vs f8 18.88 ms/token — ratio 1.004, the r4 2.3x f8
  regression is gone (42.1 -> 18.9). Promoted into
  ops/pallas_attention.py (_f8_bits_to).
"""

import sys
import time

sys.path.insert(0, ".")

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _f8_bits_to_bf16(u8, flush_sub: bool):
    """e4m3fn bits (uint8) -> bf16 via f32-bit reassembly in 32-bit lanes
    (Mosaic v5e has no 16-bit vector shifts): normal numbers become
    sign<<31 | (exp+120)<<23 | mant<<20 bitcast to f32; subnormals take an
    int->float ladder (mag * 2^-9, exact in 3 mantissa bits); the final
    f32 -> bf16 convert is native."""
    i = u8.astype(jnp.int32)
    sign = (i & 0x80) << 24
    mag = i & 0x7F
    normal = (mag << 20) + (120 << 23)
    if flush_sub:
        bits = jnp.where(mag < 8, 0, normal) | sign
    else:
        sub = mag.astype(jnp.float32) * jnp.float32(2.0 ** -9)
        sub_bits = jax.lax.bitcast_convert_type(sub, jnp.int32)
        bits = jnp.where(mag < 8, sub_bits, normal) | sign
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(
        jnp.bfloat16)


def _kernel(pos_ref, q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref,
            *, sb, n_sb, kvh, scale, mode):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    b = pl.program_id(0) // kvh
    pos = pos_ref[b]

    @pl.when(j * sb <= pos)
    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        if mode == "astype":
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        elif mode in ("bits", "bitsflush"):
            k = _f8_bits_to_bf16(k, mode == "bitsflush")
            v = _f8_bits_to_bf16(v, mode == "bitsflush")
        dot = functools.partial(jax.lax.dot_general,
                                preferred_element_type=jnp.float32)
        scores = dot(q, k, dimension_numbers=(((1,), (1,)), ((), ()))) * scale
        s_pos = j * sb + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(s_pos <= pos, scores, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = dot(p.astype(v.dtype), v,
                 dimension_numbers=(((1,), (0,)), ((), ())))
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(j == n_sb - 1)
    def _done():
        out_ref[0] = (acc_ref[:] / l_ref[:]).astype(jnp.bfloat16)


def build(mode, b, kvh, s, hs, sb=512):
    n_sb = s // sb

    @jax.jit
    def run(pos, q, k, v):
        return pl.pallas_call(
            functools.partial(_kernel, sb=sb, n_sb=n_sb, kvh=kvh,
                              scale=1.0 / (hs ** 0.5), mode=mode),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b * kvh, n_sb),
                in_specs=[
                    pl.BlockSpec((1, 1, hs), lambda i, j, p: (i, 0, 0)),
                    pl.BlockSpec((1, sb, hs),
                                 lambda i, j, p: (i, jnp.minimum(
                                     j, p[i // kvh] // sb), 0)),
                    pl.BlockSpec((1, sb, hs),
                                 lambda i, j, p: (i, jnp.minimum(
                                     j, p[i // kvh] // sb), 0)),
                ],
                out_specs=pl.BlockSpec((1, 1, hs), lambda i, j, p: (i, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((1, hs), jnp.float32),
                    pltpu.VMEM((1, 1), jnp.float32),
                    pltpu.VMEM((1, 1), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b * kvh, 1, hs), jnp.bfloat16),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
        )(pos, q, k, v)

    return run


def main():
    b, kvh, s, hs = 1, 32, 8192, 128
    fill = 7680
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b * kvh, 1, hs)), jnp.bfloat16)
    k_b = jnp.asarray(rng.standard_normal((b * kvh, s, hs)), jnp.bfloat16)
    v_b = jnp.asarray(rng.standard_normal((b * kvh, s, hs)), jnp.bfloat16)
    k_8 = k_b.astype(jnp.float8_e4m3fn)
    v_8 = v_b.astype(jnp.float8_e4m3fn)
    k_u = jax.lax.bitcast_convert_type(k_8, jnp.uint8)
    v_u = jax.lax.bitcast_convert_type(v_8, jnp.uint8)
    pos = jnp.asarray([fill], jnp.int32)

    variants = {
        "bf16": (build("plain", b, kvh, s, hs), (pos, q, k_b, v_b)),
        "astype-f8": (build("astype", b, kvh, s, hs), (pos, q, k_8, v_8)),
        "bits-f8": (build("bits", b, kvh, s, hs), (pos, q, k_u, v_u)),
        "bitsflush-f8": (build("bitsflush", b, kvh, s, hs), (pos, q, k_u, v_u)),
    }

    # numeric parity first: bits must equal astype exactly (same stored
    # values, exact upcast)
    outs = {n: np.asarray(fn(*a), np.float32) for n, (fn, a) in variants.items()}
    np.testing.assert_array_equal(outs["bits-f8"], outs["astype-f8"])
    print("bits == astype exact: ok")

    iters = 32
    best = {n: None for n in variants}
    for r in range(6):
        for n, (fn, a) in variants.items():
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*a)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters * 1e3
            best[n] = dt if best[n] is None else min(best[n], dt)
    for n, v in best.items():
        print(f"{n:14s} {v:.3f} ms/call")


if __name__ == "__main__":
    main()
