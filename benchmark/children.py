"""Entry of the benchmark's helper children:
`python benchmark/children.py <name> <json payload>`, last stdout line one
JSON object. Each child is a fresh process, because whoever imports JAX on a
machine with a chip holds that chip until it exits.

  synth   numpy only (JAX_PLATFORMS=cpu): the configuration's `.m`/`.t`
          files, by the benchmark's own draw (weights.py) and the
          configuration's `weights_recipe`
  probe   holds the chip: which devices JAX sees
  check   holds the chip: the served step programs against the plain
          float32 reference
  reduce  JAX_PLATFORMS=cpu: profiler trace -> busy/idle, kernel time per
          execution, top operations, idle gaps
"""

from __future__ import annotations

import json
import os
import sys
import time


def spec_of(config: dict):
    """The program's ModelSpec of a configuration file, by its shape."""
    import workmodel

    return workmodel.for_config(config).spec(config)


def synth(p: dict) -> dict:
    from distributed_llama_tpu.io.tokenizer_file import (
        TokenizerData, write_tokenizer_file)
    from distributed_llama_tpu.testing import byte_fallback_vocab

    import weights

    spec = spec_of(p["config"])
    t0 = time.time()
    size = weights.write_model(p["model"] + ".part", spec,
                               p["config"]["weights_seed"],
                               p["config"].get("weights_recipe"))
    write_tokenizer_file(p["tokenizer"], TokenizerData(
        vocab=byte_fallback_vocab(spec.vocab_size),
        scores=[0.0] * spec.vocab_size, bos_id=1, eos_id=2))
    os.replace(p["model"] + ".part", p["model"])
    return {"bytes": size, "seconds": round(time.time() - t0, 1)}


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check(p: dict) -> dict:
    """One seeded prompt through the engine's slot_prefill_chunk in chunks
    and then slot_decode_step — the executables the server runs, at the
    server's batch, chunk and context, built by the CLI's own build_engine —
    against the reference's full forward over the same tokens."""
    import importlib

    import jax
    import numpy as np

    from distributed_llama_tpu.apps.dllama import (build_argparser,
                                                   build_engine)
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.utils.compile_cache import \
        ensure_compile_cache

    ensure_compile_cache()
    cfg, srv = p["config"], p["config"]["server"]
    args = build_argparser().parse_args(
        ["inference", "--model", p["model"], "--tokenizer", p["tokenizer"],
         "--max-seq-len", str(srv["max_seq_len"]), "--seed", "0",
         "--temperature", "0"] + p.get("engine_flags", []))
    built, _tok, _ = build_engine(args)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    # the batched engine exactly as runtime/router.build_front_door makes it
    eng = Engine(built.spec, built.params, built.mesh,
                 batch=srv["serve_batch"], max_seq_len=built.seq_len,
                 compute_dtype=built.compute_dtype,
                 cache_dtype=built.cache_dtype, use_pallas=built.use_pallas,
                 pallas_interpret=built.pallas_interpret,
                 activation_q80=built.activation_q80,
                 q80_collectives=built.q80_collectives,
                 shard_vocab=built.shard_vocab,
                 prefill_chunk=built.prefill_chunk)
    b, c, seq = srv["serve_batch"], srv["serve_chunk"], built.seq_len
    rng = np.random.default_rng(p["seed"])
    n_prompt, n_decode = p["prompt_tokens"], p["decode_steps"]
    tokens = rng.integers(3, built.spec.vocab_size,
                          n_prompt + n_decode).astype(np.int32)
    row = p.get("row", b // 2)   # a slot in the middle; the others stay gated

    got = {}
    for off in range(0, n_prompt, c):
        n = min(c, n_prompt - off)
        tok = np.zeros((b, c), np.int32)
        pos = np.full((b,), seq, np.int32)
        lidx = np.zeros((b,), np.int32)
        tok[row, :n] = tokens[off:off + n]
        pos[row], lidx[row] = off, n - 1
        logits = eng.slot_prefill_chunk(tok, pos, lidx)
    got[n_prompt - 1] = np.asarray(eng.fetch_logits(logits), np.float32)[row]
    for i in range(n_decode):
        tok = np.zeros((b, 1), np.int32)
        pos = np.full((b,), seq, np.int32)
        tok[row, 0], pos[row] = tokens[n_prompt + i], n_prompt + i
        logits = eng.slot_decode_step(tok, pos)
        got[n_prompt + i] = np.asarray(eng.fetch_logits(logits),
                                       np.float32)[row]

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    ref_mod = importlib.import_module(
        cfg["reference"][:-3].replace("/", "."))
    t0 = time.time()
    want = ref_mod.forward(p["model"], tokens)
    rows = []
    for at, lg in sorted(got.items()):
        rows.append({"position": at,
                     "step": "prefill" if at == n_prompt - 1 else "decode",
                     "finite": bool(np.isfinite(lg).all()),
                     "rel_l2": rel_l2(lg, want[at]),
                     "argmax_agree": bool(lg.argmax() == want[at].argmax())})
    # what is held to a limit: the worst row against `logit_tolerance`, or,
    # for a configuration whose `check` says `"judge": "median"`, the median
    # row against it and the worst against `check.worst_tolerance` (a router
    # near-tie can send ONE row to another expert than the reference's;
    # precision and a wrong step move every row)
    values = {"worst": max(r["rel_l2"] for r in rows),
              "median": float(np.median([r["rel_l2"] for r in rows]))}
    chk = cfg.get("check", {})
    limits = {"worst": cfg["logit_tolerance"]}
    if chk.get("judge") == "median":
        limits = {"median": cfg["logit_tolerance"],
                  "worst": chk["worst_tolerance"]}
    return {"rows": rows, "worst_rel_l2": values["worst"],
            "median_rel_l2": values["median"], "limits": limits,
            "tolerance": cfg["logit_tolerance"],
            "ok": bool(all(r["finite"] for r in rows)
                       and all(values[k] <= v for k, v in limits.items())),
            "reference_seconds": round(time.time() - t0, 1),
            "device": device}


def probe(p: dict) -> dict:
    """Is there a chip at all? (asked before gigabytes are written)"""
    import jax

    dev = jax.devices()
    return {"device": {"platform": dev[0].platform,
                       "kind": dev[0].device_kind, "count": len(dev)}}


def reduce_trace(p: dict) -> dict:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import tracereduce

    return tracereduce.reduce_dir(p["dir"], p["kernels"])


if __name__ == "__main__":
    name, payload = sys.argv[1], json.loads(sys.argv[2])
    out = {"synth": synth, "check": check, "reduce": reduce_trace,
           "probe": probe}[name](payload)
    print(json.dumps(out), flush=True)
