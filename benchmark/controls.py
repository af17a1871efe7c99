"""The readings a configuration's `logit_tolerance` is set from, each through
the harness's own comparison (`children.check`: its drive of the served step
programs, its relative L2 against the plain reference): the path as served
over `--seeds` token seeds (the floor), then over `--control-seeds` of them
the same path with ONE thing wrong (the controls, which have to read above
the tolerance). For a configuration with experts it also saves what the
reference's router decided for every token (`routing=` of its `forward`),
from which PERF.md's routing numbers are counted.

    <chip tool> --chips 1 -- python benchmark/controls.py \
        --config mixtral-8x7b-12l [--seeds 12] [--control-seeds 3] \
        [--only served cache_fp8 ...] [--out chiprun_out/controls]

`--recipe '<json>' --tag <name>` reads another draw of the same configuration
(its file in a cache directory of its own) and `--routing-only` the
reference's routing alone: how a draw is chosen, before it is the
configuration's.

The file is written first when the checkout has none (children.synth). The
weights are loaded once and the reference computed once a token seed. Holds
the chip. Not part of a run of the benchmark: re-read when the served step
programs' numerics or the draw (weights.py) change.

Controls:
  cache_fp8         the program's own lower precision: `--cache-dtype f8`
  router_next_best  every token's experts taken one place down the router's
                    order (2nd and 3rd for top-2): `lax.top_k` over a last
                    axis as wide as the router answers k+1 and drops the best
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (REPO, HERE):
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


def controls(router_width: int) -> dict:
    """name -> (engine flags, context manager factory)."""
    from jax import lax

    top_k = lax.top_k

    def next_best(x, k):
        if x.shape[-1] != router_width:
            return top_k(x, k)
        v, i = top_k(x, k + 1)
        return v[..., 1:], i[..., 1:]

    out = {"served": ([], contextlib.nullcontext),
           "cache_fp8": (["--cache-dtype", "f8"], contextlib.nullcontext)}
    if router_width:
        out["router_next_best"] = (
            [], lambda: swapped(lax, "top_k", next_best))
    return out


def readings(cfg: dict, model: str, tok: str, seeds: int, control_seeds: int,
             only=None, engine_flags=(), save=None) -> tuple[dict, dict]:
    """name -> token seed -> the check's reading, and token seed -> what the
    reference's router decided (empty without experts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import children
    import distributed_llama_tpu.apps.dllama as cli
    ref = importlib.import_module(cfg["reference"][:-3].replace("/", "."))
    forward, build, memo, routing = ref.forward, cli.build_engine, {}, {}
    router_width = cfg.get("num_local_experts", 0)
    top_k = jax.lax.top_k

    def forward_once(path, tokens):
        key = tokens.tobytes()
        if key not in memo:
            with swapped(jax.lax, "top_k", top_k):   # never a control's
                if router_width:     # such a reference takes `routing=`
                    routing[memo["seed"]] = []
                    memo[key] = forward(path, tokens,
                                        routing=routing[memo["seed"]])
                else:
                    memo[key] = forward(path, tokens)
        return memo[key]

    def build_once(a):
        if "built" not in memo:
            memo["built"] = build(a)
        eng, tk, sampler = memo["built"]
        view = types.SimpleNamespace(**{k: getattr(eng, k)
                                        for k in BUILT_KEYS})
        view.cache_dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32,
                            "f8": jnp.float8_e4m3fn}[a.cache_dtype]
        return view, tk, sampler

    chk = cfg.get("check", {})
    out: dict = {}
    with swapped(ref, "forward", forward_once), \
            swapped(cli, "build_engine", build_once):
        for name, (flags, patch) in controls(router_width).items():
            if only and name not in only:
                continue
            out[name] = {}
            for s in range(seeds if name == "served" else control_seeds):
                seed = memo["seed"] = cfg["weights_seed"] + 1 + s
                with patch():
                    v = children.check({
                        "config": cfg, "model": model, "tokenizer": tok,
                        "seed": seed,
                        "prompt_tokens": chk.get("prompt_tokens", 100),
                        "decode_steps": chk.get("decode_steps", 4),
                        "engine_flags": list(engine_flags) + flags})
                rows = {r["position"]: round(r["rel_l2"], 5)
                        for r in v["rows"]}
                margins = {}
                if seed in routing:  # the least margin a compared row met
                    m = np.stack([x["margin"] for x in routing[seed]])
                    margins = {p: round(float(m[:, p].min()), 4)
                               for p in rows}
                out[name][seed] = {
                    "worst_rel_l2": v["worst_rel_l2"],
                    "median_rel_l2": v["median_rel_l2"], "ok": v["ok"],
                    "rows": rows, "least_margin": margins,
                    "argmax_agree": [r["argmax_agree"] for r in v["rows"]],
                    "reference_seconds": v["reference_seconds"]}
                print(name, seed, json.dumps(out[name][seed]), flush=True)
                gc.collect()
                if save:
                    with open(save, "w") as f:
                        json.dump(out, f, indent=1)
    return out, routing


def reference_routing(cfg: dict, model: str, seeds: int) -> dict:
    """token seed -> the reference's routing over the check's tokens (drawn
    as children.check draws them), without an engine."""
    import numpy as np

    ref = importlib.import_module(cfg["reference"][:-3].replace("/", "."))
    chk = cfg.get("check", {})
    n = chk.get("prompt_tokens", 100) + chk.get("decode_steps", 4)
    routing = {}
    for s in range(seeds):
        seed = cfg["weights_seed"] + 1 + s
        tokens = np.random.default_rng(seed).integers(
            3, cfg["vocab_size"], n).astype(np.int32)
        routing[seed] = []
        ref.forward(model, tokens, routing=routing[seed])
        print("routing", seed, [len(np.unique(x["top_i"]))
                                for x in routing[seed]], flush=True)
    return routing


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a name under benchmark/configs/, or a .json file")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--only", nargs="*")
    ap.add_argument("--engine-flags", default="",
                    help="flags every engine gets, before a control's own "
                         "(a CPU rehearsal: '--compute-dtype f32 ...')")
    ap.add_argument("--routing-only", action="store_true",
                    help="no engine: the reference alone over --seeds token "
                         "seeds, for what its router decided")
    ap.add_argument("--recipe", help="draw the file under another "
                    "weights_recipe (JSON; 'null': the architecture's), in "
                    "the cache directory of --tag")
    ap.add_argument("--tag", default="other", help="names --recipe's files")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "controls"))
    args = ap.parse_args(argv)
    path = args.config if args.config.endswith(".json") else os.path.join(
        HERE, "configs", args.config + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["name"] = os.path.basename(path)[:-5]
    if args.recipe:
        cfg["name"] += "-" + args.tag
        cfg["weights_recipe"] = json.loads(args.recipe)

    import numpy as np

    import children
    d = os.path.join(HERE, ".cache", f"{cfg['name']}-{cfg['weights_seed']}")
    os.makedirs(d, exist_ok=True)
    model, tok = d + "/model.m", d + "/tok.t"
    if not os.path.exists(model):
        print(children.synth({"config": cfg, "model": model,
                              "tokenizer": tok}), flush=True)
    os.makedirs(args.out, exist_ok=True)
    if args.routing_only:
        out, routing = {}, reference_routing(cfg, model, args.seeds)
    else:
        out, routing = readings(
            cfg, model, tok, args.seeds, args.control_seeds, args.only,
            args.engine_flags.split(),
            save=os.path.join(args.out, cfg["name"] + ".json"))
    if routing:
        seeds = sorted(routing)
        np.savez_compressed(
            os.path.join(args.out, cfg["name"] + ".routing.npz"),
            seeds=np.array(seeds),
            top_i=np.stack([np.stack([x["top_i"] for x in routing[s]])
                            for s in seeds]),         # (seed, layer, T, k)
            margin=np.stack([np.stack([x["margin"] for x in routing[s]])
                             for s in seeds]))
    print(json.dumps({
        name: {f"{k}_{stat}": f([x[f"{stat}_rel_l2"] for x in v.values()])
               for stat in ("worst", "median")
               for k, f in (("least", min), ("most", max))}
        for name, v in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
