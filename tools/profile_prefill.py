"""Prefill time breakdown on the real chip: where does a 256-token chunk go?

The fused Q40 matmul kernel now overlaps unpack with the MXU (+9.5%
whole-model prefill, ops/pallas_q40._n_sub); this tool measures what is
left — per-layer component times for a 7B prefill chunk so the next lever
is picked by data, not guess:

  * q40 matmuls per layer: qkv+o (d=4096 shapes, td=1024 whole-tile) and
    w1/w3 (td=256, n_sub=8) + w2 (td=256, n_sub=2)
  * flash chunked-prefill attention at a representative fill
  * everything else (norms, rope, residuals, embed/logits amortized) =
    whole-step time minus the above

Discipline: chain 8 calls per jit to amortize the per-dispatch cost;
interleave variants best-of-N in one process; end every timed region
with block_until_ready.

Usage: python tools/profile_prefill.py   (no PYTHONPATH override!)

MEASURED (round 4, before PR 1, on another machine — whole model 5926 tok/s;
not measured on today's code):
    dispatch floor   2.42 ms/run-slot (n=64 chains, ~155 ms/run)
    ffn w1+w3+w2     0.856 ms/layer  -> 27.4 ms/chunk = 63% of the chunk
    qkvo + attn      below the jitter floor individually (<~0.5 ms/layer)
    unaccounted      15.7 ms/chunk (36%) — embed/logits tail, norms/rope,
                     plus the qkvo/attn signal lost under jitter
FFN at 63% of chunk = ~81 TFLOP/s = 41% MFU on the sub-tiled kernel: the
quantized FFN matmul is still the prefill ceiling; attention and the
projections are not the next lever at 2k context.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")

import bench
from distributed_llama_tpu.ops import pallas_q40 as q40
from distributed_llama_tpu.ops.pallas_attention import flash_attention
from distributed_llama_tpu.runtime.engine import Engine

T = 256          # the engine's prefill chunk
FILL = 1024      # representative mid-prompt cache fill


def chain(fn, x0, n=64):
    @jax.jit
    def run(x):
        y = x
        for _ in range(n):
            y = fn(y)
        return y
    np.asarray(run(x0))  # compile
    return run, x0, n


def timed(run, x0, n, reps=4):
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x0))
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e3  # ms per call


def main() -> None:
    spec = bench.LLAMA2_7B
    params = bench.synth_q40_params(spec)
    layer0 = params["layers"][0]
    wq, wk, wv, wo = (layer0[k] for k in ("wq", "wk", "wv", "wo"))
    w1, w2, w3 = (layer0[k] for k in ("w1", "w2", "w3"))

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (T, spec.dim), dtype=np.float32)).astype(jnp.bfloat16)

    jobs = {}
    # identity-ish chain measures the per-run dispatch/transfer floor —
    # subtracted from every component row (ms-scale per-layer times sit
    # under it)
    jobs["dispatch floor"] = chain(lambda v: v * 1.0000001, x)
    # attention projections: all four are (4096, 4096) for 7B MHA -> td=1024
    jobs["qkvo (4x d4096 td1024)"] = chain(
        lambda v: sum(q40.q40_matmul(v, w, out_dtype=jnp.bfloat16)
                      for w in (wq, wk, wv, wo)), x)
    jobs["ffn (w1+w3+w2 td256)"] = chain(
        lambda v: q40.q40_matmul(
            q40.q40_matmul(v, w1, out_dtype=jnp.bfloat16)
            * q40.q40_matmul(v, w3, out_dtype=jnp.bfloat16),
            w2, out_dtype=jnp.bfloat16), x)

    hs = spec.dim // spec.n_heads
    qh = jnp.asarray(np.random.default_rng(1).standard_normal(
        (1, T, spec.n_heads, hs), dtype=np.float32)).astype(jnp.bfloat16)
    kc = jnp.zeros((1, spec.n_kv_heads, spec.seq_len, hs), jnp.bfloat16)
    pos = (FILL + jnp.arange(T, dtype=jnp.int32))[None, :]  # (B=1, T)

    def attn(v):
        o = flash_attention(v, kc, kc, pos)
        return (v + o.reshape(v.shape) * 1e-3).astype(jnp.bfloat16)

    jobs[f"flash attn (T={T}, fill={FILL})"] = chain(attn, qh)

    # whole-model single chunk via the engine for the total
    engine = Engine(spec, params, compute_dtype=jnp.bfloat16,
                    cache_dtype=jnp.bfloat16, max_seq_len=spec.seq_len)
    engine.reset()
    tokens = list(np.ones(2048, np.int32))
    for rep in range(3):
        engine.reset()
        t0 = time.perf_counter()
        logits = engine.prefill(tokens)
        np.asarray(logits)
        dt = time.perf_counter() - t0
        if rep == 0:
            continue
        total = min(dt if rep == 1 else total, dt)
    per_chunk_ms = total / (2048 / T) * 1e3

    results = {}
    for _ in range(4):
        for name, (run, x0, n) in jobs.items():
            ms = timed(run, x0, n, reps=1)
            results[name] = min(results.get(name, 1e9), ms)

    print(f"whole-model: {total * 1e3:8.1f} ms / 2048 tok "
          f"({2048 / total:6.0f} tok/s) -> {per_chunk_ms:6.2f} ms/chunk")
    floor = results.pop("dispatch floor")
    print(f"dispatch floor: {floor:.3f} ms/call-slot")
    acc = 0.0
    for name, ms in results.items():
        ms = max(ms - floor, 0.0)
        per_layer = ms
        per_chunk = per_layer * spec.n_layers
        acc += per_chunk
        print(f"{name:32s}: {per_layer:7.3f} ms/layer -> "
              f"{per_chunk:7.1f} ms/chunk-all-layers "
              f"({per_chunk / per_chunk_ms * 100:5.1f}% of chunk)")
    print(f"{'unaccounted (norms/rope/embed/…)':32s}: "
          f"{per_chunk_ms - acc:7.1f} ms/chunk "
          f"({(per_chunk_ms - acc) / per_chunk_ms * 100:5.1f}%)")


if __name__ == "__main__":
    main()
