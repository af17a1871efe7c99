"""Roofline share of the Q40 matmul kernels inside one step program: the
least time the chip could take for the matmuls ONE execution needs
(workmodel.matmul_work over the real tokens of that execution, against the
peaks of this device kind) over the summed device time of the named kernels
in that execution; the median over the traced executions.

Real tokens per execution are not in the trace, and the capture itself
stalls the server while it is written, so they are taken from the whole
measured window. Decode: output tokens per scheduler iteration (/stats
`tokens_out` over `steps`). Prefill: the prompt tokens the client had
answered over the iterations that ran a prefill program — `steps` times the
traced ratio of prefill to decode executions. Both are capped at the rows the
program holds. For a dense model under ~70 tokens the bound is the weight
read and does not depend on the estimate.
"""

from metrics import percentile
from workmodel import matmul_work, roofline_seconds


def read(ctx: dict, program: str, kernels: list):
    t, cfg = ctx["trace"], ctx["config"]
    module = cfg["executables"][program]
    execs = [x for x in (t or {}).get("executions", ())
             if x["module"] == module
             and sum(x["kernel_s"].get(k, 0.0) for k in kernels) > 0]
    if not execs:
        return None
    srv = cfg["server"]
    a, b = ctx["stats"].get("window_start"), ctx["stats"].get("window_end")
    if not a or not b or b["steps"] <= a["steps"]:
        return None
    steps = b["steps"] - a["steps"]
    if program == "decode":
        tokens = (b["tokens_out"] - a["tokens_out"]) / steps
        cap = srv["serve_batch"]
    else:
        n_dec = sum(x["module"] == cfg["executables"]["decode"]
                    for x in t["executions"])
        share = len(execs) / max(n_dec, len(execs))
        tokens = ctx["client"]["prompt_tokens"] / (steps * share)
        cap = srv["serve_batch"] * srv["serve_chunk"]
    tokens = min(max(tokens, 1.0), cap)
    work = matmul_work(cfg, tokens,
                       logit_rows=tokens if program == "decode" else 1.0)
    least, bound = roofline_seconds(work, ctx["peaks"])
    shares = [100.0 * least / sum(x["kernel_s"].get(k, 0.0) for k in kernels)
              for x in execs]
    return {"value": percentile(shares, 50),
            "note": f"{len(execs)} executions of {module}, {tokens:.1f} real "
                    f"tokens each, {bound}-bound, least {least * 1e3:.3f} ms"}
