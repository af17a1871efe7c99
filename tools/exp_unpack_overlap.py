"""Experiment: sub-tile unpack/MXU interleave for PREFILL chunks.

Hypothesis: whole-model prefill sits at ~40% MFU because the fused Q40
kernel's nibble unpack (VPU) and its MXU contraction serialize within each
grid step (ops/pallas_q40.py docstring). Splitting the output tile into
n_sub sub-tiles and issuing each sub-tile's dot right after its unpack
could let the MXU queue chew on sub-tile i while the VPU unpacks i+1 —
IF Mosaic's scheduler lets the data-independent VPU work run ahead of an
issued matmul.

STATUS: MEASURED (round 4, v5e). FFN shape D=11008 (td=256):
    current          36.10 ms/call   1.00x
    td=128 n_sub=2   27.48           1.31x
    td=128 n_sub=4   37.95           0.95x
    td=256 n_sub=2   26.36           1.37x
    td=256 n_sub=4   26.14           1.38x
    td=256 n_sub=8   25.60           1.41x   <- WINNER, threaded through
Attention-projection shape EXP_D=4096 (td=1024): every sub-tile variant
flat or worse (0.89-0.98x), so _n_sub in ops/pallas_q40.py sub-tiles ONLY
the td=256 tile. (ms/call includes the amortized dispatch cost of the machine it was
taken on; the kernel-only delta is larger than 1.41x.) Run with:

    cd /root/repo && python tools/exp_unpack_overlap.py          # D=11008
    EXP_D=4096 python tools/exp_unpack_overlap.py                # td=1024
(do NOT override PYTHONPATH — the TPU plugin registers through it)
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, ".")

from distributed_llama_tpu.ops import pallas_q40 as q  # noqa: E402
from distributed_llama_tpu.quants.jax_codec import QuantizedTensor  # noqa: E402

# EXP_D=4096 covers the attention-projection shape whose _tile_d pick is
# td=1024 (the FFN shape D=11008 can only tile at 128/256); EXP_D=4096 +
# EXP_N=11008 covers the w2 shape (m=5504, the n_sub=2 VMEM-bound regime)
D = int(os.environ.get("EXP_D", "11008"))
N = int(os.environ.get("EXP_N", "4096"))
T = 256
NB = N // 32
M = 16 * NB


def matmul_sub(x, w, n_sub, td):
    """Like q40_matmul's bf16-MXU mode, but unpack+dot per sub-tile."""
    from jax.experimental.pallas import tpu as pltpu

    def kern(x_lo_ref, x_hi_ref, xsum_ref, packed_ref, scales_ref, out_ref):
        dot = functools.partial(
            jax.lax.dot_general,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        x_lo = x_lo_ref[:].astype(jnp.bfloat16)
        x_hi = x_hi_ref[:].astype(jnp.bfloat16)
        xs = xsum_ref[:]
        h = td // n_sub
        for i in range(n_sub):
            pk = packed_ref[i * h:(i + 1) * h, :].astype(jnp.int32)
            lo = (pk & 0xF).astype(jnp.float32)
            hi = (pk >> 4).astype(jnp.float32)
            s = q._f16_bits_to_f32(
                scales_ref[i * h:(i + 1) * h, :].astype(jnp.int32))
            s16 = pltpu.repeat(s, 16, axis=1)
            wl = (lo * s16).astype(jnp.bfloat16)
            wh = (hi * s16).astype(jnp.bfloat16)
            acc = dot(x_lo, wl)
            acc += dot(x_hi, wh)
            acc += dot(xs, s) * -8.0
            out_ref[:, i * h:(i + 1) * h] = acc.astype(jnp.bfloat16)

    t = x.shape[0]
    x_lo, x_hi = q._split_activation(x.astype(jnp.float32), NB)
    xsum = (x_lo + x_hi).reshape(t, 16, NB).sum(axis=1)
    return pl.pallas_call(
        kern, grid=(D // td,),
        in_specs=[
            pl.BlockSpec((t, M), lambda i: (0, 0)),
            pl.BlockSpec((t, M), lambda i: (0, 0)),
            pl.BlockSpec((t, NB), lambda i: (0, 0)),
            pl.BlockSpec((td, M), lambda i: (i, 0)),
            pl.BlockSpec((td, NB), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, td), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((t, D), jnp.bfloat16),
        cost_estimate=pl.CostEstimate(flops=2 * t * D * N,
                                      bytes_accessed=D * M,
                                      transcendentals=0),
    )(x_lo, x_hi, xsum, w.packed, w.scales)


def main():
    rng = np.random.default_rng(0)
    packed = jnp.asarray(rng.integers(0, 256, (D, M), dtype=np.uint8))
    scales = jnp.asarray((rng.random((D, NB), dtype=np.float32) * 0.004
                          ).astype(np.float16).view(np.uint16))
    w = QuantizedTensor(packed, scales)
    x = jnp.asarray(rng.standard_normal((T, N), dtype=np.float32))

    def chain(fn):
        @jax.jit
        def run(x):
            y = x
            for _ in range(8):
                o = fn(y)
                y = (o[:, :N] * 1e-3).astype(jnp.float32)
            return y
        return run

    fl = 2 * T * D * N

    def whole_tile(v):
        # the engine kernel sub-tiles since round 4 — pin the baseline to
        # n_sub=1 so this experiment keeps measuring landed-vs-whole-tile
        orig = q._n_sub
        q._n_sub = lambda td, m, mxu: 1
        try:
            q.q40_matmul.clear_cache()
            return q.q40_matmul(v, w, out_dtype=jnp.bfloat16)
        finally:
            q._n_sub = orig

    def landed(v):
        # clear q40_matmul's inner jit cache at trace time so this variant
        # cannot reuse the whole-tile trace cached by the baseline above
        q.q40_matmul.clear_cache()
        return q.q40_matmul(v, w, out_dtype=jnp.bfloat16)

    variants = [("whole-tile", whole_tile), ("landed", landed)]
    # tile sizes must divide D = 11008 = 2^8 * 43 exactly — a flooring
    # grid would silently skip rows and bias the comparison (td=512 would
    # cover only 97.7% of the output) — and both the tile and its
    # sub-slices must stay 32-row aligned (the uint8 sublane tile)
    if D == 11008:
        combos = ((128, 2), (128, 4), (256, 2), (256, 4), (256, 8))
    elif N > 4096:  # w2 shape: m > 4096 bytes/row — n_sub=8 OOMs scoped VMEM
        combos = ((256, 2), (256, 4))
    else:  # D=4096: the engine's _tile_d picks 1024 here
        combos = ((256, 8), (512, 8), (1024, 2), (1024, 4), (1024, 8))
    # ... and the OUTPUT block's last dim (td) must itself be 128-aligned:
    # D = 11008 = 2^8 * 43, so the only legal tile sizes are 128 and 256
    # (td=2752 = 64*43 fails Mosaic's last-dim-divisible-by-128 check)
    assert all(D % td == 0 and td % 128 == 0 and (td // ns) % 32 == 0
               for td, ns in combos), combos
    variants += [(f"td={td} n_sub={ns}",
                  lambda v, td=td, ns=ns: matmul_sub(v, w, ns, td))
                 for td, ns in combos]
    # variants are only comparable INTERLEAVED in one process, best-of-N
    # each (the repo's A/B measurement discipline)
    runs = [(name, chain(fn)) for name, fn in variants]
    best: dict = {}
    for name, run in runs:
        np.asarray(run(x))  # compile
    for _ in range(4):
        for name, run in runs:
            t0 = time.perf_counter()
            jax.block_until_ready(run(x))
            dt = (time.perf_counter() - t0) / 8
            best[name] = min(best.get(name, dt), dt)
    base = best["whole-tile"]
    for name, _ in runs:
        dt = best[name]
        rel = base / dt
        print(f"{name}: {dt*1e3:.3f} ms/call, {fl/dt/1e12:.1f} TFLOP/s, "
              f"{rel:.2f}x vs whole-tile")
    winner = min(best, key=best.get)
    if winner == "landed" or best["landed"] <= best[winner] * 1.02:
        print("DECISION: the landed _n_sub policy is (still) within 2% of "
              "the best variant — keep it")
    elif winner == "whole-tile":
        print("DECISION: whole-tile now beats the landed sub-tiling — "
              "re-measure and revisit _n_sub in ops/pallas_q40.py")
    else:
        print(f"DECISION: {winner} beats the landed policy by "
              f"{best['landed'] / best[winner]:.2f}x — update _n_sub in "
              "ops/pallas_q40.py to match")


if __name__ == "__main__":
    main()
