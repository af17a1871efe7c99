"""Roofline share of an attention kernel inside one step program: the least
time the chip could take for the attention ONE execution needs
(`attention_work` of the configuration's shape, over the (query token,
cached position) pairs and the cache rows its real rows read, against the
peaks of this device kind) over the named kernel's summed device time in
that execution; the median over the traced executions.

Pairs and cache rows per execution are not in the trace: they are the
program's own counters, counted on the host where the scheduler builds the
positions (/stats `attn_pairs_decode` over `decode_steps`;
`attn_pairs_prefill` and `prefill_cached_tokens` over `prefill_steps`),
differenced between the two ends of the CAPTURE, which the server records
itself (/stats `capture.start` / `capture.stop`): a closed loop's clients
move together, so the contexts of the captured seconds are not the
window's mean (the window's counters against a capture's times read 53 %
in one run and 108 % in the next). Nothing to read (None, the metric is
left out of the line): a program without those counters, a shape without
`attention_work`, or no traced execution that holds the kernel.
"""

from metrics import percentile
from workmodel import for_config, roofline_seconds

COUNTERS = {"decode": ("attn_pairs_decode", None, "decode_steps"),
            "prefill": ("attn_pairs_prefill", "prefill_cached_tokens",
                        "prefill_steps")}


def read(ctx: dict, program: str, kernels: list):
    t, cfg = ctx["trace"], ctx["config"]
    work_of = getattr(for_config(cfg), "attention_work", None)
    module = cfg["executables"][program]
    execs = [x for x in (t or {}).get("executions", ())
             if x["module"] == module
             and sum(x["kernel_s"].get(k, 0.0) for k in kernels) > 0]
    ends = (ctx["stats"].get("trace_end") or {}).get("capture") or {}
    a, b = ends.get("start"), ends.get("stop")
    pairs_key, cached_key, steps_key = COUNTERS[program]
    if (work_of is None or not execs or not a or not b
            or any(k not in a or k not in b
                   for k in (pairs_key, steps_key))):
        return None
    steps = b[steps_key] - a[steps_key]
    if steps <= 0:
        return None
    pairs = (b[pairs_key] - a[pairs_key]) / steps
    cached = ((b[cached_key] - a[cached_key]) / steps if cached_key else 0.0)
    if pairs <= 0:
        return None
    least, bound = roofline_seconds(work_of(cfg, program, pairs, cached),
                                    ctx["peaks"])
    shares = [100.0 * least / sum(x["kernel_s"].get(k, 0.0) for k in kernels)
              for x in execs]
    return {"value": percentile(shares, 50),
            "note": f"{len(execs)} executions of {module}, {pairs:.0f} "
                    f"pairs and {cached:.0f} cache rows each, {bound}-bound, "
                    f"least {least * 1e3:.3f} ms"}
