"""Prefill chunk-size ladder: fused-kernel 256-chunks vs larger XLA-dequant
segments on the real chip.

The engine prefers prefill_chunk=256 (the Pallas MAX_T); segments above that
take the XLA dequant path, which re-materializes bf16 weights per matmul but
amortizes over more tokens. This measures tokens/sec for a 2048-token prompt
at several chunk sizes to find the crossover (if any).

Usage: python tools/exp_prefill_chunk.py [7b|tiny]

Measured (v5e, 7B Q40, 2048-token prompt): 256-token fused chunks win by
>2x — 128: 2196 tok/s, 256: 5771, 512: 2600, 1024: 1762, 2048: 2461 —
the XLA dequant path never catches up even with the whole prompt in one
segment, and 256 is also the kernel's VMEM ceiling for its (t, m) f32
activation blocks. The engine default stands confirmed.

Re-measured (round 4) after the unpack/MXU sub-tile interleave landed in
the kernel (ops/pallas_q40._n_sub): 128: 4650 tok/s, 256: 6317, 512: 3337,
1024: 4056, 2048: 4461 — chunk 256 still the winner, now +9.5% whole-model
over the round-3 kernel (6317 vs 5771).
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")

import bench
from distributed_llama_tpu.runtime.engine import Engine

PROMPT_LEN = 2048


def run(model: str) -> None:
    spec = bench.LLAMA2_7B if model == "7b" else bench.TINY
    params = bench.synth_q40_params(spec)
    tokens = np.ones((1, PROMPT_LEN), np.int32)

    for chunk in (128, 256, 512, 1024, 2048):
        engine = Engine(spec, params, compute_dtype=jnp.bfloat16,
                        cache_dtype=jnp.bfloat16, max_seq_len=PROMPT_LEN,
                        prefill_chunk=chunk)
        best = 1e9
        for rep in range(3):
            engine.reset()
            t0 = time.perf_counter()
            logits = engine.prefill(list(tokens[0]))
            jax.block_until_ready(logits)
            dt = time.perf_counter() - t0
            if rep:  # rep 0 compiles
                best = min(best, dt)
        print(f"chunk={chunk:5d}: {PROMPT_LEN / best:8.1f} tok/s "
              f"({best * 1e3:7.1f} ms)", flush=True)


if __name__ == "__main__":
    run(sys.argv[1] if len(sys.argv) > 1 else "7b")
