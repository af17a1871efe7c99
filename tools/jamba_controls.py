"""The controls of `jamba2-3b`'s logits check, each through the harness's
own comparison (`benchmark/children.check`: its tokens, its drive of the
served step programs, its relative L2 against the reference, the
configuration's limits): the path as served, then the same path with ONE
thing wrong. A control that reads `ok: true` is a fault the check cannot
see. The command is tools/olmo_hybrid_controls.py's `run`; this file is the
table: the scan state's precision, the two attention layers' rows'
precision, the three inner norms (on dt, B and C) left out, the step's bias
dropped, and the mechanisms a state needs.

    <chip tool> --chips 1 -- python tools/jamba_controls.py \
        [--model M --tokenizer T] [--only served rows_fp8 ...] \
        [--once state_zeroed_between_chunks ...] [--seed-offsets 1 2 3] \
        [--config FILE] [--out FILE]
"""

from __future__ import annotations

import contextlib
import sys

from granite_hybrid_controls import drop_dt_bias
from olmo_hybrid_controls import rows_pad, rows_zeroed, run, swapped


def controls(cfg: dict) -> dict:
    """name -> (engine flags, spec change, params change or None, context
    manager factory)."""
    import jax

    import distributed_llama_tpu.models.transformer as tr
    import distributed_llama_tpu.ops.pallas_selective_scan as ss

    scan, rows = ss.selective_scan, tr._segment_rows

    def scan_bf16(*a, **k):
        # the state kept in bf16: rounded after every program. A bf16 round
        # trip by astype is REMOVED by the TPU compiler
        # (xla_allow_excess_precision); reduce_precision stays
        y, s = scan(*a, **k)
        return y, jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7)

    def no_inner_norms(rbc, lw, spec):
        # [r ; B ; C] as x_proj gives them: no norm, no weights
        n, r = spec.ssm_d_state, spec.ssm_dt_rank
        return rbc[..., :r], rbc[..., r:r + n], rbc[..., r + n:]

    none = contextlib.nullcontext
    return {
        "served": ([], {}, None, none),
        "rows_fp8": (["--cache-dtype", "f8"], {}, None, none),
        "state_bf16": ([], {}, None,
                       lambda: swapped(ss, "selective_scan", scan_bf16)),
        "inner_norms_dropped":
            ([], {}, None,
             lambda: swapped(tr, "_inner_norms", no_inner_norms)),
        "dt_bias_dropped": ([], {}, drop_dt_bias, none),
        "state_zeroed_between_chunks":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_zeroed(rows))),
        "pad_tokens_advance":
            ([], {}, None,
             lambda: swapped(tr, "_segment_rows", rows_pad(rows))),
    }


if __name__ == "__main__":
    sys.exit(run(__doc__, "jamba2-3b", controls))
