"""Roofline share of the Q40 matmul kernels inside one step program: the
least time the chip could take for the matmuls the capture's MEAN execution
needs (`matmul_work` of the configuration's shape, workmodel.for_config,
over that execution's real tokens and the experts they were routed to,
against the peaks of this device kind) over the MEAN summed device time of
the named kernels in the traced executions of that program: sum over sum.

Everything an execution's work depends on is the program's own count,
differenced between the two ends of the CAPTURE, which the server records
itself (/stats `capture.start` / `capture.stop`), as
trace_attention_roofline.py reads its pairs:

  tokens    decode `decode_rows` / `decode_steps`; prefill `prefill_tokens`
            / `prefill_steps`: real rows and real prompt tokens, no gated
            row and no pad
  experts   with experts only: distinct held experts a MoE layer that some
            real token chose, `expert_reads_<program>`, and (real token,
            chosen held expert) pairs, `expert_pairs_<program>`, both over
            steps x the shape's MoE layers

Until PR 37 tokens came from the whole WINDOW (output tokens a scheduler
iteration; for prefill an estimate from the client's prompt tokens), experts
from an expectation under even routing, and the share was the MEDIAN of
per-execution shares. Each was wrong once a kernel's time follows its
tokens: uniform-byte weights sent every token to one pair of experts and
the expectation charged 4.28 where at most 3.0 could have been read
(133.9 %; ledger, PR 35); and the median execution's time was read against
the mean execution's work (an open loop whose median decode step holds one
row and whose mean holds 1.5 read 1.5 rows' bytes over one row's time).
Bytes are linear in the experts read, so for a memory-bound step sum over
sum is exact whatever the spread of rows over executions.

A configuration with experts whose program does not report the two expert
counters is charged the FLOOR of its shape's `moe`: what every routing of
that many tokens touches (top_k experts a layer where all are held,
nothing of a held share). No formula stands in for an observation: the
floor can only understate the share, and the note says which it was.

Nothing to read (None, the metric is left out of the line): no traced
execution that holds the kernels, a program without the capture's
counters, or a capture in which the program ran no real token.
"""

from workmodel import for_config, roofline_seconds

COUNTERS = {"decode": ("decode_rows", "decode_steps"),
            "prefill": ("prefill_tokens", "prefill_steps")}


def capture_work(ctx: dict, program: str):
    """(work, said) of the capture's mean execution of `program`: the
    `matmul_work` of its real tokens and routed experts, and the words that
    say so in a note. None: the capture's ends or counters are missing, or
    the program ran no real token between them."""
    cfg = ctx["config"]
    shape = for_config(cfg)
    ends = (ctx["stats"].get("trace_end") or {}).get("capture") or {}
    a, b = ends.get("start"), ends.get("stop")
    tokens_key, steps_key = COUNTERS[program]
    if (not a or not b
            or any(k not in a or k not in b for k in COUNTERS[program])):
        return None
    steps = b[steps_key] - a[steps_key]
    if steps <= 0 or b[tokens_key] <= a[tokens_key]:
        return None
    tokens = (b[tokens_key] - a[tokens_key]) / steps
    routed, said = {}, ""
    moe = getattr(shape, "moe", lambda c: None)(cfg)
    if moe:
        keys = {"experts": f"expert_reads_{program}",
                "pairs": f"expert_pairs_{program}"}
        if all(k in a and k in b for k in keys.values()):
            routed = {name: (b[k] - a[k]) / (steps * moe["layers"])
                      for name, k in keys.items()}
            how = "counted by the program"
        else:
            routed = moe["floor"](tokens)
            how = "the floor of any routing: the program counts none"
        said = (f", {routed['experts']:.2f} experts and {routed['pairs']:.1f}"
                f" pairs a layer ({how})")
    work = shape.matmul_work(
        cfg, tokens, logit_rows=tokens if program == "decode" else 1.0,
        **routed)
    return work, f"{tokens:.2f} real tokens each{said}"


def read(ctx: dict, program: str, kernels: list):
    module = ctx["config"]["executables"][program]
    times = [sum(x["kernel_s"].get(k, 0.0) for k in kernels)
             for x in (ctx["trace"] or {}).get("executions", ())
             if x["module"] == module]
    times = [s for s in times if s > 0]
    got = capture_work(ctx, program) if times else None
    if got is None:
        return None
    work, said = got
    least, bound = roofline_seconds(work, ctx["peaks"])
    mean = sum(times) / len(times)
    return {"value": 100.0 * least / mean,
            "note": f"{len(times)} executions of {module}, {said}, "
                    f"{bound}-bound, least {least * 1e3:.3f} ms over a mean "
                    f"{mean * 1e3:.3f} ms"}
