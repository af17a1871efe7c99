"""The controls of `granite-4.0-h-small-ep2`'s logits check, each through the
harness's own comparison (`benchmark/children.check`: its tokens, its drive
of the served step programs, its relative L2 against the reference, the
configuration's limits): the path as served, then the same path with ONE
thing wrong. A control that reads `ok: true` is a fault the check cannot
see. The command is tools/olmo_hybrid_controls.py's `run`; this file is the
table: the state's mixer, `dt_bias`, and the router (benchmark/controls.py's
`router_next_best` reads the router's width from `num_local_experts`, which
here counts the HELD experts: this tool asks the shape's spec).

    <chip tool> --chips 1 -- python tools/granite_hybrid_controls.py \
        [--model M --tokenizer T] [--only served rows_fp8 ...] \
        [--seed-offsets 1 2 3] [--out FILE]
    <chip tool> --chips 1 -- python tools/granite_hybrid_controls.py \
        --chained [--lengths 256 250 640] [--out FILE]

`--chained` is no control of the check but the proof the check cannot give
(it drives one segment a program): a prompt prefilled up to eight segments
a program leaves the bits of the same prompt prefilled one segment a
program (`chained_against_one_a_program`).
"""

from __future__ import annotations

import contextlib
import sys

from olmo_hybrid_controls import (BENCH, BUILT_KEYS, config_and_files,
                                  rows_pad, rows_zeroed, run, swapped)


def drop_dt_bias(params: dict) -> dict:
    """The params with every SSM layer's dt_bias at zero."""
    import jax.numpy as jnp

    layers = [dict(lw, dt_bias=jnp.zeros_like(lw["dt_bias"]))
              if "dt_bias" in lw else lw for lw in params["layers"]]
    return dict(params, layers=layers)


def controls(width: int) -> dict:
    """name -> (engine flags, spec change, params change or None, context
    manager factory), for a router of `width` outputs."""
    import jax
    from jax import lax

    import distributed_llama_tpu.models.transformer as tr
    import distributed_llama_tpu.ops.pallas_ssd as ssd

    scan, rows, top_k = ssd.ssd_scan, tr._segment_rows, lax.top_k

    def scan_bf16(*a, **k):
        # the state kept in bf16: rounded after every program. A bf16 round
        # trip by astype is REMOVED by the TPU compiler
        # (xla_allow_excess_precision); reduce_precision stays
        y, s = scan(*a, **k)
        return y, jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7)

    def next_best(x, k):
        # every token's experts one place down the router's order
        if x.shape[-1] != width:
            return top_k(x, k)
        v, i = top_k(x, k + 1)
        return v[..., 1:], i[..., 1:]

    none = contextlib.nullcontext
    return {
        "served": ([], {}, None, none),
        "rows_fp8": (["--cache-dtype", "f8"], {}, None, none),
        "state_bf16": ([], {}, None,
                       lambda: swapped(ssd, "ssd_scan", scan_bf16)),
        "state_zeroed_between_chunks":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_zeroed(rows))),
        "pad_tokens_advance":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_pad(rows))),
        "dt_bias_dropped": ([], {}, drop_dt_bias, none),
        "router_next_best": ([], {}, None,
                             lambda: swapped(lax, "top_k", next_best)),
    }


def for_config(cfg: dict) -> dict:
    import workmodel

    return controls(workmodel.for_config(cfg).spec(cfg).router_width)


def chained_against_one_a_program(argv) -> int:
    """The served engine (the benchmark's file, full depth, real widths,
    B=8, the CLI's own build_engine) prefills a seeded prompt on one slot
    twice from the SAME start, every leaf of the cache filled with noise:
    as the scheduler packs a slot that prefills alone (`chained_segments`,
    `chain_map`: 256 tokens are one 8-row program, 640 are 7 + 7 + 6), and
    one segment a program. Compared BIT FOR BIT: the logits at the last
    prompt token and after one decode step, and EVERY leaf of the cache
    whole: the slot's states, tails and rows, and the seven other slots',
    which must also be the noise they were. The last stdout line is the
    verdict; exit code 1 where any bit differs."""
    import argparse
    import json
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.apps.dllama import (build_argparser,
                                                   build_engine)
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.runtime.scheduler import (chain_map,
                                                         chained_segments)
    from distributed_llama_tpu.utils.compile_cache import \
        ensure_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--chained", action="store_true")
    ap.add_argument("--config", default=os.path.join(
        BENCH, "configs", "granite-4.0-h-small-ep2.json"))
    ap.add_argument("--cache", default=os.path.join(BENCH, ".cache"),
                    help="where the model file is, or is written")
    ap.add_argument("--lengths", type=int, nargs="+",
                    default=[256, 250, 640])
    ap.add_argument("--slot", type=int, default=5)
    ap.add_argument("--seed", type=int, default=46)
    ap.add_argument("--out")
    ap.add_argument("--engine-flags", nargs=argparse.REMAINDER, default=[])
    args = ap.parse_args(argv)
    cfg, model, tokenizer = config_and_files(args.config, cache=args.cache)
    ensure_compile_cache()
    srv = cfg["server"]
    built, _, _ = build_engine(build_argparser().parse_args(
        ["inference", "--model", model, "--tokenizer", tokenizer,
         "--max-seq-len", str(srv["max_seq_len"]), "--seed", "0",
         "--temperature", "0"] + args.engine_flags))
    kw = {k: getattr(built, k) for k in BUILT_KEYS}
    eng = Engine(kw.pop("spec"), kw.pop("params"), kw.pop("mesh"),
                 batch=srv["serve_batch"], max_seq_len=kw.pop("seq_len"),
                 **kw)
    b, c, seq = eng.batch, srv["serve_chunk"], eng.seq_len
    assert eng.prefill_rows_per_slot == b, eng.prefill_rows_per_slot

    def noise(i, like):
        return jax.jit(lambda k: jax.random.normal(
            k, like.shape, jnp.float32).astype(like.dtype))(
                jax.random.key(args.seed * 1000 + i))

    def start():
        leaves, tree = jax.tree_util.tree_flatten(eng.cache)
        like = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves]
        for x in leaves:        # room first: the cache is 2.3 GB of 15.75
            x.delete()
        eng.cache = tree.unflatten([noise(i, x) for i, x in enumerate(like)])

    def prefill(tokens, most):
        off = 0
        while off < len(tokens):
            k = chained_segments(len(tokens) - off, c, most)
            tok = np.zeros((b, c), np.int32)
            pos = np.full((b,), seq, np.int32)
            lidx = np.zeros((b,), np.int32)
            for r in range(k):
                n = min(c, len(tokens) - off)
                tok[r, :n], pos[r], lidx[r] = tokens[off:off + n], off, n - 1
                off += n
            logits = eng.slot_prefill_chunk(tok, pos, lidx,
                                            chain_map(args.slot, k, b))
        last = np.asarray(eng.fetch_logits(logits))[k - 1]
        tok = np.zeros((b, 1), np.int32)
        pos = np.full((b,), seq, np.int32)
        tok[args.slot, 0], pos[args.slot] = 7, len(tokens)
        step = np.asarray(eng.fetch_logits(eng.slot_decode_step(tok, pos)))
        return {"logits": last, "decode_logits": step[args.slot],
                **{f"{name}[{i}]": np.asarray(x)
                   for name, leaf in zip(eng.cache._fields, eng.cache)
                   for i, x in enumerate(leaf)}}

    def bits(x):
        return x.view(f"u{x.dtype.itemsize}")

    out, same = {}, True
    others = np.asarray([i for i in range(b) if i != args.slot])
    for n in args.lengths:
        tokens = np.random.default_rng(args.seed + n).integers(
            3, eng.spec.vocab_size, n).astype(np.int32)
        start()
        before = {f"{name}[{i}]": np.asarray(x[others])
                  for name, leaf in zip(eng.cache._fields, eng.cache)
                  for i, x in enumerate(leaf)}
        chained = prefill(tokens, b)
        start()
        single = prefill(tokens, 1)
        differ = [k for k in chained
                  if not np.array_equal(bits(chained[k]), bits(single[k]))]
        # decode wrote slot `--slot` only: the others are the noise still
        moved = [k for k in before
                 if not np.array_equal(bits(chained[k][others]),
                                       bits(before[k]))]
        programs = -(-(-(-n // c)) // b)
        out[str(n)] = {"programs": [programs, -(-n // c)],
                       "leaves_compared": len(chained) - 2,
                       "differ": differ, "other_slots_moved": moved,
                       "finite": bool(np.isfinite(chained["logits"]).all()),
                       "state_norm": float(np.linalg.norm(
                           chained["s[0]"][args.slot]))}
        same &= not differ and not moved and out[str(n)]["finite"]
        print(n, json.dumps(out[str(n)]), flush=True)
    verdict = {"bit_equal": bool(same), "device": jax.devices()[0].device_kind,
               "lengths": out}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=1)
    print(json.dumps(verdict), flush=True)
    return 0 if same else 1


if __name__ == "__main__":
    if "--chained" in sys.argv:
        sys.exit(chained_against_one_a_program(sys.argv[1:]))
    sys.exit(run(__doc__, "granite-4.0-h-small-ep2", for_config))
