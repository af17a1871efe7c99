"""The controls of `kimi-linear-48b-a3b-ep4`'s logits check, each through
the harness's own comparison (`benchmark/children.check`: its tokens, its
drive of the served step programs, its relative L2 against the reference,
the configuration's limits): the path as served, then the same path with ONE
thing wrong. A control that reads `ok: true` is a fault the check cannot
see. The command is tools/olmo_hybrid_controls.py's `run`; this file is the
table: the KDA state's precision, the latent rows' precision, the router
(benchmark/controls.py's `router_next_best` reads the router's width from
`num_local_experts`, a key this configuration does not have: this tool asks
the shape's spec), the held experts' own arithmetic (each answers with its
neighbour's down projection), and the mechanisms a state needs.

    <chip tool> --chips 1 -- python tools/kimi_linear_controls.py \
        [--model M --tokenizer T] [--only served rows_fp8 ...] \
        [--once state_zeroed_between_chunks ...] [--seed-offsets 1 2 3] \
        [--config FILE] [--out FILE]
"""

from __future__ import annotations

import contextlib
import sys

from olmo_hybrid_controls import rows_pad, rows_zeroed, run, swapped


def experts_rolled(params: dict) -> dict:
    """The params with every held expert's down projection its neighbour's:
    the router chooses as published and every routed expert answers wrong."""
    import jax
    import jax.numpy as jnp

    def rolled(w):
        return jax.tree.map(lambda a: jnp.roll(a, 1, axis=0), w)

    layers = [dict(lw, moe_down=rolled(lw["moe_down"]))
              if "moe_down" in lw else lw for lw in params["layers"]]
    return dict(params, layers=layers)


def controls(width: int) -> dict:
    """name -> (engine flags, spec change, params change or None, context
    manager factory), for a router of `width` outputs."""
    import jax
    from jax import lax

    import distributed_llama_tpu.models.transformer as tr
    import distributed_llama_tpu.ops.pallas_kda as kda

    rule, rows, top_k = kda.kda_rule, tr._segment_rows, lax.top_k

    def rule_bf16(*a, **k):
        # the state kept in bf16: rounded after every program. A bf16 round
        # trip by astype is REMOVED by the TPU compiler
        # (xla_allow_excess_precision); reduce_precision stays
        o, s = rule(*a, **k)
        return o, jax.lax.reduce_precision(s, exponent_bits=8,
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
                       lambda: swapped(kda, "kda_rule", rule_bf16)),
        "router_next_best": ([], {}, None,
                             lambda: swapped(lax, "top_k", next_best)),
        "experts_rolled": ([], {}, experts_rolled, none),
        "state_zeroed_between_chunks":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_zeroed(rows))),
        "pad_tokens_advance":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_pad(rows))),
    }


def for_config(cfg: dict) -> dict:
    import workmodel

    return controls(workmodel.for_config(cfg).spec(cfg).router_width)


if __name__ == "__main__":
    sys.exit(run(__doc__, "kimi-linear-48b-a3b-ep4", for_config))
