"""Roofline share of a recurrent-state kernel inside one step program: the
least time the chip could take for the state update ONE execution needs
(`state_work` of the configuration's shape, over the rows that really take
part and their real tokens, against the peaks of this device kind) over the
named kernels' summed device time in that execution; the median over the
traced executions.

Live rows and tokens per execution are not in the trace: they are the
program's own counters (/stats `decode_rows` over `decode_steps`;
`prefill_rows` and `prefill_tokens` over `prefill_steps`), differenced
between the two ends of the CAPTURE, which the server records itself
(/stats `capture.start` / `capture.stop`), as `trace_attention_roofline.py`
does for its pairs and for the same reason. Nothing to read (None, the
metric is left out of the line): a program without those counters (the
parent has no `prefill_rows`), a shape without `state_work`, or no traced
execution of the step program.

On a chip (the run has peaks) traced executions of the step program of
which NONE holds a named kernel FAIL the run: `delta_rule` falls to its XLA
twin without a word where the kernel does not support the shape or Pallas is
off, the configuration's `kernels` cannot list a kernel that only one of the
two programs holds (`run.kernels_listed` asks every executable for every
name), and a served fall-back would otherwise read `correct` with this
metric merely missing.
"""

from metrics import percentile
from server import check
from workmodel import for_config, roofline_seconds

COUNTERS = {"decode": ("decode_rows", "decode_rows", "decode_steps"),
            "prefill": ("prefill_rows", "prefill_tokens", "prefill_steps")}


def read(ctx: dict, program: str, kernels: list):
    t, cfg = ctx["trace"], ctx["config"]
    work_of = getattr(for_config(cfg), "state_work", None)
    module = cfg["executables"][program]
    ran = [x for x in (t or {}).get("executions", ())
           if x["module"] == module]
    execs = [x for x in ran
             if sum(x["kernel_s"].get(k, 0.0) for k in kernels) > 0]
    check(work_of is None or ctx["peaks"] is None or execs or not ran,
          f"none of {len(ran)} traced executions of {module} holds any of "
          f"{kernels}: the served program fell back to the XLA twin")
    ends = (ctx["stats"].get("trace_end") or {}).get("capture") or {}
    a, b = ends.get("start"), ends.get("stop")
    rows_key, tokens_key, steps_key = COUNTERS[program]
    if (work_of is None or not execs or not a or not b
            or any(k not in a or k not in b
                   for k in (rows_key, tokens_key, steps_key))):
        return None
    steps = b[steps_key] - a[steps_key]
    if steps <= 0:
        return None
    rows = (b[rows_key] - a[rows_key]) / steps
    tokens = (b[tokens_key] - a[tokens_key]) / steps
    if rows <= 0:
        return None
    least, bound = roofline_seconds(work_of(cfg, program, rows, tokens),
                                    ctx["peaks"])
    shares = [100.0 * least / sum(x["kernel_s"].get(k, 0.0) for k in kernels)
              for x in execs]
    return {"value": percentile(shares, 50),
            "note": f"{len(execs)} executions of {module}, {rows:.2f} live "
                    f"rows and {tokens:.1f} tokens each, {bound}-bound, "
                    f"least {least * 1e3:.3f} ms"}
