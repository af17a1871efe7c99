"""The controls of `olmo-hybrid-7b`'s logits check, each through the
harness's own comparison (`benchmark/children.check`: its tokens, its drive
of the served step programs, its relative L2 against the reference, the
configuration's `logit_tolerance`): the path as served, then the same path
with ONE thing wrong. A control that reads `ok: true` is a fault the check
cannot see.

    <chip tool> --chips 1 -- python tools/olmo_hybrid_controls.py \
        [--model M --tokenizer T] [--only served rows_fp8 ...] \
        [--once NAME ...] [--seed-offsets 1 2 3] [--out FILE]

Without --model the file is written first (the synth child's function, 49 s
at real size). The weights are loaded once and the reference computed once a
token seed (`weights_seed` + an offset; 1 is the run's own check): the file
is the same for every control. Holds the chip. `run` is shared with
tools/granite_hybrid_controls.py, which brings its own table.
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


def controls(cfg: dict) -> dict:
    """name -> (engine flags, spec change, params change or None, context
    manager factory)."""
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

    none = contextlib.nullcontext
    return {
        "served": ([], {}, None, none),
        "rows_fp8": (["--cache-dtype", "f8"], {}, None, none),
        "state_bf16": ([], {}, None,
                       lambda: swapped(dr, "delta_rule", rule_bf16)),
        "state_zeroed_between_chunks":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_zeroed(rows))),
        "pad_tokens_advance":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_pad(rows))),
        "beta_without_2": ([], {"lin_beta_scale": 1}, None, none),
    }


def rows_zeroed(rows):
    """_segment_rows whose every chunk program starts its rows from zeros."""
    def zeroed(spec, cache, pos0, b, t, *rest):
        r = rows(spec, cache, pos0, b, t, *rest)
        return r._replace(fresh=(r.n_valid > 0) if t > 1 else r.fresh)
    return zeroed


def rows_pad(rows):
    """_segment_rows whose tail chunk's pad tokens advance the state."""
    def pad(spec, cache, pos0, b, t, li, *rest):
        return rows(spec, cache, pos0, b, t, None, *rest)
    return pad


def config_and_files(config: str, model=None, tokenizer=None, cache=None):
    """(the configuration of the file `config`, its model file, its
    tokenizer): the files given, else the benchmark's own under `cache`
    (benchmark/.cache), written with the benchmark's draw where missing."""
    with open(config) as f:
        cfg = json.load(f)
    cfg.setdefault("name", os.path.basename(config)[:-5])
    if not model:
        import children

        d = os.path.join(cache or os.path.join(BENCH, ".cache"),
                         f"{cfg['name']}-{cfg['weights_seed']}")
        os.makedirs(d, exist_ok=True)
        model, tokenizer = d + "/model.m", d + "/tok.t"
        if not os.path.exists(model):
            print(children.synth({"config": cfg, "model": model,
                                  "tokenizer": tokenizer}), flush=True)
    return cfg, model, tokenizer


def run(doc: str, config: str, controls, argv=None) -> int:
    """Both tools' command: `controls(cfg)` is a tool's own table."""
    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(BENCH, "configs",
                                                     config + ".json"))
    ap.add_argument("--model")
    ap.add_argument("--tokenizer")
    ap.add_argument("--only", nargs="*")
    ap.add_argument("--seed-offsets", type=int, nargs="+", default=[1],
                    help="token seeds, as offsets from weights_seed (the "
                         "run's own check uses 1)")
    ap.add_argument("--once", nargs="*", default=[],
                    help="controls read on the first token seed only (the "
                         "mechanisms: a wrong step reads far over any limit)")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cfg, args.model, args.tokenizer = config_and_files(
        args.config, args.model, args.tokenizer)

    import importlib

    import jax
    import jax.numpy as jnp

    import children
    import distributed_llama_tpu.apps.dllama as cli
    ref = importlib.import_module(cfg["reference"][:-3].replace("/", "."))
    forward, build, memo = ref.forward, cli.build_engine, {}
    top_k = jax.lax.top_k

    def forward_once(path, tokens):
        key = (path, tokens.tobytes())
        if key not in memo:
            with swapped(jax.lax, "top_k", top_k):   # never a control's
                memo[key] = forward(path, tokens)
        return memo[key]

    def build_once(a):
        if "built" not in memo:
            memo["built"] = build(a)
        eng, tok, sampler = memo["built"]
        spec_change, param_change = memo["change"]
        view = types.SimpleNamespace(**{k: getattr(eng, k)
                                        for k in BUILT_KEYS})
        view.cache_dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32,
                            "f8": jnp.float8_e4m3fn}[a.cache_dtype]
        view.spec = dataclasses.replace(eng.spec, **spec_change)
        if param_change:
            view.params = param_change(eng.params)
        return view, tok, sampler

    ref.forward, cli.build_engine = forward_once, build_once
    check = cfg.get("check", {})
    out = {}
    for offset in args.seed_offsets:
        payload = {"config": cfg, "model": args.model,
                   "tokenizer": args.tokenizer,
                   "seed": cfg["weights_seed"] + offset,
                   "prompt_tokens": check.get("prompt_tokens", 100),
                   "decode_steps": check.get("decode_steps", 4)}
        for name, (flags, spec_change, param_change,
                   patch) in controls(cfg).items():
            if args.only and name not in args.only + args.once:
                continue
            if name in args.once and offset != args.seed_offsets[0]:
                continue
            memo["change"] = (spec_change, param_change)
            with patch():
                v = children.check(dict(payload, engine_flags=flags))
            key = name if offset == 1 else f"{name}+{offset}"
            out[key] = {"ok": v["ok"], "worst_rel_l2": v["worst_rel_l2"],
                        "median_rel_l2": v["median_rel_l2"],
                        "limits": v["limits"],
                        "rows": {r["position"]: r["rel_l2"]
                                 for r in v["rows"]}}
            print(key, json.dumps({k: out[key][k] for k in out[key]
                                   if k != "rows"}), flush=True)
            gc.collect()
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(out, f, indent=1)
    print(json.dumps({name: [v["ok"], round(v["worst_rel_l2"], 5),
                             round(v["median_rel_l2"], 5)]
                      for name, v in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(run(__doc__, "olmo-hybrid-7b", controls))
