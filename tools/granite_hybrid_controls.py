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
"""

from __future__ import annotations

import contextlib
import sys

from olmo_hybrid_controls import rows_pad, rows_zeroed, run, swapped


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


if __name__ == "__main__":
    sys.exit(run(__doc__, "granite-4.0-h-small-ep2", for_config))
