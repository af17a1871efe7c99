"""70B dress rehearsal without 16 chips (VERDICT r4 #4).

The reference actually ran Llama-2-70B across socket clusters
(ref: README.md:78,90; src/transformer.cpp:607-683 streams each worker its
shard). The repo's 70B claim was a README projection; this tool turns it
into artifacts:

1. writes a REAL 70B-width `.m` (dim 8192, hidden 28672, 64 heads, 8 kv
   heads, vocab 32000 — Llama-2-70B's exact widths), layer-truncated to
   N_LAYERS=4 for disk (~3.1 GB; full depth is the same bytes x 20),
   with valid random Q40 blocks streamed straight to disk;
2. stream-loads it at tp=16 AND tp=8 x pp=2 on a 16-virtual-device CPU
   mesh (load_params_streamed: per-device placement, kv-head replication
   at tp=16 > 8 kv heads, bounded host memory — the peak is asserted
   far below the file size);
3. AOT-lowers the decode step per mesh, counts the collective ops in the
   optimized HLO, executes real greedy steps, and cross-checks the two
   meshes emit IDENTICAL tokens (same file, same math, different
   partitioning);
4. records per-device parameter bytes and extrapolates to full 80-layer
   depth against the README's 2.42 GB/chip budget.

Writes tools/artifacts/MULTICHIP_70B.json. Each mesh config runs in a
subprocess (the virtual device count can only be set once per process).

Usage: python tools/rehearse_70b.py [--keep-file]
"""

import json
import os
import re
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N_LAYERS = 4
FULL_LAYERS = 80
MODEL_PATH = "/tmp/llama70b_width_4l.m"
OUT_PATH = os.path.join(os.path.dirname(__file__), "artifacts",
                        "MULTICHIP_70B.json")


def spec70():
    from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
    from distributed_llama_tpu.quants.types import FloatType

    return ModelSpec(arch=ArchType.LLAMA, dim=8192, hidden_dim=28672,
                     n_layers=N_LAYERS, n_heads=64, n_kv_heads=8,
                     vocab_size=32000, seq_len=2048,
                     hidden_act=HiddenAct.SILU, rope_theta=10000.0,
                     weights_float_type=FloatType.Q40)


def write_file(path: str) -> int:
    """Stream a random-but-valid 70B-width `.m` (the writer chip_smoke.py
    shares: distributed_llama_tpu/testing.write_synthetic_model). Returns
    total bytes."""
    from distributed_llama_tpu.testing import write_synthetic_model

    t0 = time.time()
    size = write_synthetic_model(path, spec70(), seed=70)
    print(f"wrote {path}: {size / 1e9:.2f} GB in {time.time() - t0:.0f}s")
    return size


def run_config(cfg: str) -> None:
    """Subprocess body: load + lower + step + account for one mesh."""
    # 16 virtual CPU devices, chosen before any backend exists
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 16)
    assert jax.device_count() == 16, jax.devices()
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_llama_tpu.models.loader import load_params_streamed
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.runtime import Engine
    from distributed_llama_tpu.sampler import Sampler

    axes = dict(kv.split("=") for kv in cfg.split(","))
    mesh = make_mesh(**{k: int(v) for k, v in axes.items()})
    spec = spec70()

    t0 = time.time()
    params, stats = load_params_streamed(
        spec, MODEL_PATH, mesh, mode="q40", dtype=jnp.bfloat16)
    load_s = time.time() - t0
    total = os.path.getsize(MODEL_PATH)
    # the streamed-load contract: host residency is bounded by the largest
    # single tensor/fusion group (here tok_emb f32, 1.05 GB), never the
    # file — at the full 80-layer depth (~48 GB) the same bound holds
    biggest = spec.vocab_size * spec.dim * 4 + (1 << 20)
    assert stats.peak_host_bytes <= biggest * 2, (
        stats.peak_host_bytes, biggest)

    # per-device parameter bytes (packed Q40 + scales + dense leaves),
    # split into layer weights (scale with depth) and the rest (tok_emb is
    # REPLICATED per device — the honest full-depth number must carry it)
    def per_device(tree) -> int:
        acc: dict[int, int] = {}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                acc[sh.device.id] = (acc.get(sh.device.id, 0)
                                     + sh.data.size * sh.data.dtype.itemsize)
        return max(acc.values())

    dev_layer_bytes = per_device(params["layers"])
    # vocab sharding (ops/sharded_vocab.py, ISSUE-15): tok_emb/wcls are
    # row-split at LOAD over the mesh's vocab axes — the 533 MB/chip
    # replicated table (VERDICT weak #3) becomes vocab/S per chip. The
    # split is reported separately so the artifact shows the freed bytes.
    dev_vocab_bytes = per_device(
        {k: v for k, v in params.items() if k in ("tok_emb", "wcls")})
    dev_other_bytes = per_device(
        {k: v for k, v in params.items()
         if k not in ("layers", "tok_emb", "wcls")})
    dev_bytes = dev_layer_bytes + dev_other_bytes + dev_vocab_bytes

    eng = Engine(spec, params, mesh, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32, max_seq_len=256)

    # AOT-lower the decode step, count collectives in the optimized HLO,
    # then EXECUTE through the same compiled object (the 70B-width CPU
    # compile is minutes; one compile serves both purposes)
    eng.reset()
    step_fn = eng._compiled_step(1)  # key 1 = the 1-token decode step
    # the compile ledger (runtime/profiler.py) wraps fresh mints in a
    # first-call watch with no .lower — AOT-lower the raw jitted callable
    step_fn = getattr(step_fn, "_fn", step_fn)
    print(f"[{cfg}] loaded in {load_s:.0f}s; lowering decode...",
          flush=True)
    t0 = time.time()
    tok = np.zeros((1, 1), np.int32)
    compiled = step_fn.lower(eng.params, jnp.asarray(tok), jnp.int32(3),
                             eng.cache).compile()
    hlo = compiled.as_text()
    compile_s = time.time() - t0
    colls = {}
    for kind in ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                 "collective-permute"):
        colls[kind] = len(re.findall(rf"= \S+ {kind}(?:-start)?\(", hlo))

    # real greedy steps off the compiled executable — the two configs must
    # agree token-for-token (same file, different partitioning)
    print(f"[{cfg}] compiled in {compile_s:.0f}s; stepping...", flush=True)
    t0 = time.time()
    logits = eng.prefill([1, 2, 3])
    toks = [int(np.argmax(eng.fetch_logits(logits)[0]))]
    for _ in range(3):
        logits, new_cache = compiled(
            eng.params, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.int32(eng.pos), eng.cache)
        eng.cache = new_cache
        eng.pos += 1
        toks.append(int(np.argmax(eng.fetch_logits(logits)[0])))
    step_s = time.time() - t0

    # full-depth extrapolation: layer bytes scale 80/4; the vocab shards
    # and norms stay as-is (tok_emb used to be replicated at 524 MB/chip
    # — now vocab/S, included honestly either way)
    dev_full = (dev_other_bytes + dev_vocab_bytes
                + dev_layer_bytes * (FULL_LAYERS // N_LAYERS))

    out = {
        "config": cfg,
        "mesh_devices": int(mesh.size),
        "decode_compile_seconds": round(compile_s, 1),
        "file_gb": round(total / 1e9, 3),
        "load_seconds": round(load_s, 1),
        "peak_host_mb_during_load": round(stats.peak_host_bytes / 1e6, 1),
        "per_device_param_mb": round(dev_bytes / 1e6, 1),
        "per_device_layer_mb": round(dev_layer_bytes / 1e6, 1),
        "per_device_vocab_mb": round(dev_vocab_bytes / 1e6, 1),
        "per_device_replicated_mb": round(dev_other_bytes / 1e6, 1),
        "shard_vocab": bool(eng.shard_vocab),
        "vocab_axes": list(getattr(eng, "_vocab_axes", ()) or ()),
        "per_device_param_gb_extrapolated_80_layers":
            round(dev_full / 1e9, 3),
        "readme_budget_gb_per_chip": 2.42,
        "budget_met_80_layers": bool(dev_full <= 2.42e9),
        "collectives_decode_step": colls,
        "greedy_tokens": toks,
        "four_token_wall_seconds": round(step_s, 1),
    }
    print("RESULT " + json.dumps(out))
    with open(f"/tmp/r70b_{cfg.replace(',', '_').replace('=', '')}.json",
              "w") as f:
        json.dump(out, f)


def main():
    if "--config" in sys.argv:
        run_config(sys.argv[sys.argv.index("--config") + 1])
        return

    if not os.path.exists(MODEL_PATH):
        write_file(MODEL_PATH)
    results = []
    for cfg in ("tp=16", "tp=8,pp=2"):
        part = f"/tmp/r70b_{cfg.replace(',', '_').replace('=', '')}.json"
        if os.path.exists(part):  # a prior (interrupted) run finished this
            with open(part) as f:
                results.append(json.load(f))
            print(f"--- {cfg}: reusing {part}")
            continue
        print(f"--- {cfg}")
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)  # run_config pins cpu in-process
        # a preset device-count flag (an 8-device test env) would beat
        # run_config's 16-device bootstrap — strip it
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(flags)
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--config", cfg],
            text=True, env=env, timeout=3600,
            cwd=os.path.join(os.path.dirname(__file__), ".."))
        if r.returncode != 0:
            sys.exit(f"config {cfg} failed rc={r.returncode}")
        with open(part) as f:
            results.append(json.load(f))

    # cross-mesh parity: same file, same math, different partitioning
    assert results[0]["greedy_tokens"] == results[1]["greedy_tokens"], results
    artifact = {
        "model_widths": "llama2-70b (dim 8192, hidden 28672, 64h/8kv)",
        "n_layers_on_disk": N_LAYERS,
        "full_depth": FULL_LAYERS,
        "cross_mesh_greedy_match": True,
        "configs": results,
    }
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {OUT_PATH}")
    if "--keep-file" not in sys.argv:
        os.remove(MODEL_PATH)


if __name__ == "__main__":
    main()
