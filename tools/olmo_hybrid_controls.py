"""The controls of `olmo-hybrid-7b`'s logits check, each through the
harness's own comparison (`benchmark/children.check`: its tokens, its drive
of the served step programs, its relative L2 against the reference, the
configuration's `logit_tolerance`): the path as served, then the same path
with ONE thing wrong. A control that reads `ok: true` is a fault the check
cannot see.

    <chip tool> --chips 1 -- python tools/olmo_hybrid_controls.py \
        [--model M --tokenizer T] [--only served rows_fp8 ...] [--out FILE]

Without --model the file is written first (the synth child's function, 49 s
at real size). The weights are loaded and the reference computed once: the
file and the check's tokens are the same for every control. Holds the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

BUILT_KEYS = ("spec", "params", "mesh", "seq_len", "compute_dtype",
              "cache_dtype", "use_pallas", "pallas_interpret",
              "activation_q80", "q80_collectives", "shard_vocab",
              "prefill_chunk")


@contextlib.contextmanager
def swapped(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def controls():
    """name -> (engine flags, spec change, context manager factory)."""
    import jax

    import distributed_llama_tpu.models.transformer as tr
    import distributed_llama_tpu.ops.pallas_delta_rule as dr

    rule, rows = dr.delta_rule, tr._segment_rows

    def rule_bf16(*a, **k):
        # the state kept in bf16: rounded after every program. A bf16 round
        # trip by astype is REMOVED by the TPU compiler
        # (xla_allow_excess_precision); reduce_precision stays
        o, s = rule(*a, **k)
        return o, jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7)

    def rows_zeroed(spec, cache, pos0, b, t, li, lfa):
        # every chunk program starts its rows from zeros
        r = rows(spec, cache, pos0, b, t, li, lfa)
        return tr.SegmentRows(r.n_valid,
                              (r.n_valid > 0) if t > 1 else r.fresh)

    def rows_pad(spec, cache, pos0, b, t, li, lfa):
        # the pad tokens of the tail chunk advance the state
        return rows(spec, cache, pos0, b, t, None, lfa)

    none = contextlib.nullcontext
    return {
        "served": ([], {}, none),
        "rows_fp8": (["--cache-dtype", "f8"], {}, none),
        "state_bf16": ([], {}, lambda: swapped(dr, "delta_rule", rule_bf16)),
        "state_zeroed_between_chunks":
            ([], {}, lambda: swapped(tr, "_segment_rows", rows_zeroed)),
        "pad_tokens_advance":
            ([], {}, lambda: swapped(tr, "_segment_rows", rows_pad)),
        "beta_without_2": ([], {"lin_beta_scale": 1}, none),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        BENCH, "configs", "olmo-hybrid-7b.json"))
    ap.add_argument("--model")
    ap.add_argument("--tokenizer")
    ap.add_argument("--only", nargs="*")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    cfg.setdefault("name", os.path.basename(args.config)[:-5])

    import children
    if not args.model:
        d = os.path.join(BENCH, ".cache",
                         f"{cfg['name']}-{cfg['weights_seed']}")
        os.makedirs(d, exist_ok=True)
        args.model, args.tokenizer = d + "/model.m", d + "/tok.t"
        if not os.path.exists(args.model):
            print(children.synth({"config": cfg, "model": args.model,
                                  "tokenizer": args.tokenizer}), flush=True)

    import importlib

    import jax.numpy as jnp

    import distributed_llama_tpu.apps.dllama as cli
    ref = importlib.import_module(cfg["reference"][:-3].replace("/", "."))
    forward, build, memo = ref.forward, cli.build_engine, {}

    def forward_once(path, tokens):
        key = (path, tokens.tobytes())
        if key not in memo:
            memo[key] = forward(path, tokens)
        return memo[key]

    def build_once(a):
        if "built" not in memo:
            memo["built"] = build(a)
        eng, tok, sampler = memo["built"]
        over = memo["spec_change"]
        view = types.SimpleNamespace(**{k: getattr(eng, k)
                                        for k in BUILT_KEYS})
        view.cache_dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32,
                            "f8": jnp.float8_e4m3fn}[a.cache_dtype]
        view.spec = dataclasses.replace(eng.spec, **over)
        return view, tok, sampler

    ref.forward, cli.build_engine = forward_once, build_once
    check = cfg.get("check", {})
    payload = {"config": cfg, "model": args.model,
               "tokenizer": args.tokenizer, "seed": cfg["weights_seed"] + 1,
               "prompt_tokens": check.get("prompt_tokens", 100),
               "decode_steps": check.get("decode_steps", 4)}
    out = {}
    for name, (flags, spec_change, patch) in controls().items():
        if args.only and name not in args.only:
            continue
        memo["spec_change"] = spec_change
        with patch():
            v = children.check(dict(payload, engine_flags=flags))
        out[name] = {"ok": v["ok"], "worst_rel_l2": v["worst_rel_l2"],
                     "tolerance": v["tolerance"],
                     "rows": {r["position"]: r["rel_l2"] for r in v["rows"]}}
        print(name, json.dumps(out[name]), flush=True)
        gc.collect()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({name: [v["ok"], round(v["worst_rel_l2"], 5)]
                      for name, v in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
